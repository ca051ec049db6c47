"""A configuration, a generator, a mix, an arrival process or a metric file
dropped into its directory is found by its name, with no edit to any file
that is there."""
import asyncio
import json
import shutil

import numpy as np
import pytest

import data
import run
import traffic

GENERATOR = '''
import numpy as np
from data import Dataset


def generate(cfg, seed):
    n = cfg["nodes"]
    s = np.arange(n, dtype=np.int32)
    t = np.stack([s, np.zeros(n, np.int32), (s + 1) % n], 1)
    return Dataset(t, [f"v{i}" for i in range(n)], ["next"],
                   {"vertex": np.arange(n)})
'''

ARRIVALS = '''
import numpy as np


def count(mix, seconds):
    return round(mix["rate_qps"] * seconds)


def due_times(mix, m, gen):
    # bursts of ``burst`` requests at once, at the mix's mean rate
    b = mix["burst"]
    return (np.arange(m) // b) * b / mix["rate_qps"]


async def drive(send, reqs, t_open, t_close, mix, server):
    for r in reqs:
        send(r, t_open + r.due_s)
'''


@pytest.fixture
def tmp_bench(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "generators", "arrivals"):
        (bench / sub).mkdir(parents=True)
    monkeypatch.setattr(data, "BENCH", bench)
    data.generator.cache_clear()
    traffic.arrivals.cache_clear()
    yield tmp_path
    data.generator.cache_clear()
    traffic.arrivals.cache_clear()


def test_new_pieces_are_found_by_name(tmp_bench):
    bench = tmp_bench / "bench"
    bm = run.benchmark()
    wl, cfg, mix = run.cell(bm, bm["workloads"][0]["name"])
    (bench / "configs" / "ring.json").write_text(json.dumps(
        {**cfg, "generator": "ring", "nodes": 12}))
    (bench / "generators" / "ring.py").write_text(GENERATOR)
    (bench / "arrivals" / "burst.py").write_text(ARRIVALS)
    (bench / "traffic" / "hops.json").write_text(json.dumps({
        "arrivals": "burst", "rate_qps": 4.0, "burst": 3, "template_zipf": 0.99,
        "templates": [{"name": "hop", "text": "{ $v next ?w }",
                       "slots": {"v": {"kind": "vertex", "zipf": 0.99}}}]}))
    (bench / "metrics" / "new.metric_ms.py").write_text(
        "def read(run):\n    return 42.0\n")
    bm2 = {
        **bm,
        "configs": bm["configs"] + [{**bm["configs"][0], "name": "ring",
                                     "file": "bench/configs/ring.json"}],
        "workloads": bm["workloads"] + [{**wl, "name": "ring.hops",
                                         "config": "ring", "traffic": "hops"}],
        "per_layer": bm["per_layer"] + [{**bm["per_layer"][0],
                                         "name": "new.metric_ms",
                                         "workloads": ["ring.hops"]}],
    }
    wl2, cfg2, mix2 = run.cell(bm2, "ring.hops", root=tmp_bench)
    ds = data.generate(cfg2, 5)
    assert ds.n_nodes == 12 and ds.label_names == ["next"]
    assert traffic.count(mix2, 3.0) == 12
    reqs = traffic.requests(mix2, ds, 5, 12)
    assert [r.due_s for r in reqs] == pytest.approx(
        [0.0] * 3 + [0.75] * 3 + [1.5] * 3 + [2.25] * 3)
    sent = []
    asyncio.run(traffic.arrivals("burst").drive(
        lambda r, due: sent.append(due), reqs, 100.0, 103.0, mix2, {}))
    assert sent == pytest.approx([100.0 + r.due_s for r in reqs])
    names = [m["name"] for m in run.cell_metrics(bm2, wl2, "per_layer")]
    assert "new.metric_ms" in names
    assert "new.metric_ms" not in [
        m["name"] for m in run.cell_metrics(bm2, wl, "per_layer")]
    assert run.reader("new.metric_ms", root=tmp_bench)(None) == 42.0


def test_a_missing_piece_is_an_error(tmp_bench):
    with pytest.raises(KeyError):
        data.generator("nothing_here")
    with pytest.raises(KeyError):
        traffic.arrivals("nothing_here")


def test_every_named_piece_exists():
    bm = run.benchmark()
    for w in bm["workloads"]:
        _, cfg, mix = run.cell(bm, w["name"])
        assert callable(data.generator(cfg["generator"]))
        # the CPU test size: keys that replace the configuration's own
        assert cfg["tiny"] and set(cfg["tiny"]) <= set(cfg), w["config"]
        assert callable(traffic.arrivals(mix["arrivals"]).drive)
    for m in bm["per_layer"]:
        assert callable(run.reader(m["name"]))
    shutil.os.stat(run.BENCH / "peaks.json")
    assert np.isfinite(run.peaks("TPU v5 lite")["hbm_bytes_per_s"])


def test_every_cell_reports_what_its_layer_metrics_move():
    """A cell reports ``setup_s``, another end-to-end metric and a per-layer
    metric, and the end-to-end metric that each of its per-layer metrics
    moves."""
    bm = run.benchmark()
    names = {m["name"] for m in bm["end_to_end"]}
    for wl in bm["workloads"]:
        e2e = {m["name"] for m in run.cell_metrics(bm, wl, "end_to_end")}
        layer = run.cell_metrics(bm, wl, "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2, wl["name"]
        assert layer, wl["name"]
        for m in layer:
            assert m["moves"] in names, m["name"]
            assert m["moves"] in e2e, (wl["name"], m["name"], m["moves"])
