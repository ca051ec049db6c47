"""The generator: seeded sequences, fixed work per seed, raw percentiles."""
import collections
import math

import numpy as np
import pytest

import data
import run
import traffic
from stats import percentile
from test_rehearsal import tiny

SEED = 2**31 + 12345
LUBM_TINY = {"universities": 2}  # the tests of LUBM's own generator


@pytest.fixture(scope="module")
def datasets():
    """One graph per configuration, at the configuration's own ``tiny``."""
    bm = run.benchmark()
    out = {}
    for w in bm["workloads"]:
        if w["config"] not in out:
            out[w["config"]] = data.generate(tiny(run.cell(bm, w["name"])[1]),
                                             SEED)
    return out


@pytest.fixture(scope="module")
def mixes():
    bm = run.benchmark()
    return {w["traffic"]: run.cell(bm, w["name"])[2] for w in bm["workloads"]}


@pytest.mark.parametrize("workload", [w["name"] for w in run.benchmark()["workloads"]])
def test_same_seed_same_sequence_other_seed_other(workload, datasets):
    wl, _, mix = run.cell(run.benchmark(), workload)
    ds = datasets[wl["config"]]
    a = traffic.requests(mix, ds, SEED, 200)
    assert a == traffic.requests(mix, ds, SEED, 200)
    b = traffic.requests(mix, ds, SEED + 1, 200)
    assert [r.text for r in a] != [r.text for r in b]


def test_every_seed_gets_the_same_work_in_another_order(datasets, mixes):
    ds = datasets["lubm"]
    mix = mixes["broad_open"]
    a = traffic.requests(mix, ds, SEED, 300)
    b = traffic.requests(mix, ds, SEED + 7, 300)
    assert (collections.Counter(r.template for r in a)
            == collections.Counter(r.template for r in b))
    gaps = [np.diff([0.0] + [r.due_s for r in x]) for x in (a, b)]
    assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]))
    # the seed draws the order of templates and of gaps
    assert [r.template for r in a] != [r.template for r in b]
    assert list(gaps[0]) != pytest.approx(list(gaps[1]))
    assert a[-1].due_s == pytest.approx(300 / mix["rate_qps"], rel=0.02)


def test_window_counts_come_from_the_arrival_process(mixes):
    assert traffic.count(mixes["broad_open"], 10) == round(
        10 * mixes["broad_open"]["rate_qps"])
    assert traffic.count(mixes["point_closed"], 10) == mixes["point_closed"]["sequence"]


def test_graph_does_not_depend_on_the_run_seed():
    _, cfg, _ = run.cell(run.benchmark(), "lubm.broad_open")
    a = data.generate({**cfg, **LUBM_TINY}, 1)
    b = data.generate({**cfg, **LUBM_TINY}, 2**40 + 3)
    assert np.array_equal(a.triples, b.triples)
    assert len(np.unique(a.triples, axis=0)) == len(a.triples)
    assert a.triples.max() < a.n_nodes == len(set(a.node_names))


def test_graph_follows_the_uba_profile():
    _, cfg, _ = run.cell(run.benchmark(), "lubm.broad_open")
    ds = data.generate({**cfg, **LUBM_TINY}, 0)
    t, lab = ds.triples, {n: i for i, n in enumerate(ds.label_names)}
    assert len(lab) == 18
    pr = cfg["profile"]
    depts = t[(t[:, 1] == lab["subOrganizationOf"])
              & np.isin(t[:, 2], ds.kinds["university"])]
    per_univ = np.bincount(depts[:, 2] - ds.kinds["university"][0])
    assert per_univ.min() >= pr["departments"][0]
    assert per_univ.max() <= pr["departments"][1]
    heads = t[t[:, 1] == lab["headOf"]]
    assert len(heads) == len(ds.kinds["department"])
    # every graduate student has an advisor, about one undergraduate in 5
    adv = set(t[t[:, 1] == lab["advisor"], 0].tolist())
    assert set(ds.kinds["graduate"].tolist()) <= adv
    ug = np.isin(ds.kinds["undergraduate"], list(adv)).mean()
    assert 0.15 < ug < 0.25
    per_dept_ug = np.bincount(t[(t[:, 1] == lab["memberOf"])
                                & np.isin(t[:, 0], ds.kinds["undergraduate"]), 2])
    fac = np.bincount(t[np.isin(t[:, 1], [lab["worksFor"], lab["headOf"]]), 2])
    ratio = per_dept_ug[ds.kinds["department"]] / fac[ds.kinds["department"]]
    assert ratio.min() >= 8 and ratio.max() <= 14


def test_percentiles_come_from_raw_samples():
    v = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert percentile(v, 50) == 3.0
    assert percentile(v, 95) == 5.0  # nearest rank: a measured value
    assert percentile(v + [math.inf], 95) == math.inf  # failures rank last


def test_latency_runs_from_the_due_time():
    req = traffic.Request(0.0, "{ ?x p ?y }", "t", "t0")
    rec = run.Rec(req, due=10.0, done=10.25, outcome="ok")
    assert rec.latency_ms == pytest.approx(250.0)
    rec.outcome = "deadline"
    assert rec.latency_ms == math.inf


def test_the_per_layer_median_is_the_end_to_end_median():
    """``serve.latency_p50_ms`` reads the cell's requests as
    ``latency_p50_ms`` does, where a cell holds the median per layer."""
    req = traffic.Request(0.0, "{ ?x p ?y }", "t", "t0")
    recs = [run.Rec(req, due=10.0, done=10.0 + s, outcome="ok")
            for s in (0.3, 0.1, 0.5, 0.2)]
    rec = run.RunRecord(recs, 51.0, 10.0, {}, [], None, {})
    read = run.reader("serve.latency_p50_ms")
    assert read(rec) == pytest.approx(200.0)
    assert read(rec) == run.end_to_end(rec, 1.0)["latency_p50_ms"]
    for r in recs[:3]:
        r.outcome = "deadline"  # failures rank last
    assert read(rec) == run.end_to_end(rec, 1.0)["latency_p50_ms"] == 1e9
    assert read(run.RunRecord([], 51.0, 10.0, {}, [], None, {})) is None
