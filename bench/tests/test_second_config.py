"""A configuration the benchmark does not ship, added by new files alone.

In a copy of ``bench/`` and ``BENCHMARK.json`` this adds, as new files and
new entries only: a small shop generator and its configuration with its own
``tiny``; a mix of an OPTIONAL with a slot inside it, a nested OPTIONAL and
a top-level UNION; a cell; and a per-layer metric that reads the program's
spans.  The copy's own harness and tests then run that cell as they run
every other: correct untraced and traced, the new metric read, the control
not correct.  No file that was there names the new cell.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run

CELL = "shop.browse"
METRIC = "engine.solves_per_batch"

GENERATOR = '''
"""Products with a type, a producer, offers with a vendor, and optional
data: features on some, reviews on some, ratings on half of those."""
import numpy as np

from data import Dataset

LABELS = ("type", "producer", "feature", "offerOf", "vendor", "reviewFor",
          "rating")


def generate(cfg, seed):
    gen = np.random.default_rng(cfg["data_seed"])  # the run seed draws traffic
    n = cfg["products"]
    names, kinds, rows = [], {}, []

    def add(kind, k):
        kinds[kind] = np.arange(len(names), len(names) + k)
        names.extend(f"{kind}{i}" for i in range(k))
        return kinds[kind]

    def edges(label, s, o):
        rows.append(np.stack([s, np.full(len(s), LABELS.index(label)), o], 1))

    prod, typ, feat = add("product", n), add("type", 4), add("feature", 6)
    maker, vendor = add("producer", 4), add("vendor", 3)
    offer, review, score = add("offer", 2 * n), add("review", 2 * n), add("score", 5)
    two = np.repeat(prod, 2)
    edges("type", prod, typ[gen.integers(4, size=n)])
    edges("producer", prod, maker[gen.integers(4, size=n)])
    p, f = np.nonzero(gen.random((n, 6)) < 0.3)
    edges("feature", prod[p], feat[f])
    edges("offerOf", offer, two)
    edges("vendor", offer, vendor[gen.integers(3, size=2 * n)])
    kept = gen.random(2 * n) < 0.6
    edges("reviewFor", review[kept], two[kept])
    rated = kept & (gen.random(2 * n) < 0.5)
    edges("rating", review[rated], score[gen.integers(5, size=rated.sum())])
    return Dataset(np.concatenate(rows).astype(np.int32), names, list(LABELS),
                   kinds)
'''

MIX = {
    "arrivals": "poisson", "rate_qps": 4.0, "template_zipf": 0.5, "tenants": 2,
    "templates": [
        {"name": "offers_opt_feature",
         "text": "{ ?p type $t . ?o offerOf ?p . ?o vendor ?v } "
                 "OPTIONAL { ?p feature $f }",
         "slots": {"t": {"kind": "type", "zipf": 0.99},
                   "f": {"kind": "feature", "zipf": 0.99}}},
        {"name": "reviews_nested",
         "text": "{ ?p type $t . ?p producer ?m } "
                 "OPTIONAL { { ?r reviewFor ?p } OPTIONAL { ?r rating ?s } }",
         "slots": {"t": {"kind": "type", "zipf": 0.99}}},
        {"name": "feature_or_producer",
         "text": "{ ?p feature $f . ?p type ?t } "
                 "UNION { ?p producer $m . ?o offerOf ?p }",
         "slots": {"f": {"kind": "feature", "zipf": 0.99},
                   "m": {"kind": "producer", "zipf": 0.99}}},
    ],
}

READER = '''
"""``engine.solve`` spans per ``engine.batch`` span: a UNION solves its
parts one by one."""


def read(run):
    if run.spans is None or not run.spans.named("engine.batch"):
        return None
    return len(run.spans.named("engine.solve")) / len(run.spans.named("engine.batch"))
'''

RUNNER = '''
import json
import sys

sys.path[:0] = ["bench/tests", "bench"]
import run
import test_rehearsal

run.enable_compile_cache = lambda: "off"
out = {}
for name, kw in (("untraced", {}), ("traced", {"trace": True}),
                 ("control", {"control": True})):
    res = test_rehearsal.tiny_run(sys.argv[1], **kw)
    out[name] = {k: res[k] for k in ("correct", "failed", "metrics", "checks")}
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A checkout's benchmark files, with the shop cell added as new files
    and new ``BENCHMARK.json`` entries."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "bench"
    shutil.copytree(run.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = run.benchmark()
    _, lubm, _ = run.cell(bm, bm["workloads"][0]["name"])
    (bench / "generators" / "shop.py").write_text(GENERATOR)
    (bench / "configs" / "shop.json").write_text(json.dumps({
        "name": "shop", "generator": "shop", "products": 20000, "data_seed": 0,
        "tiny": {"products": 60}, "engine": "auto", "server": lubm["server"],
        "guarantee": "read-only: exact dual-simulation survivors"}))
    (bench / "traffic" / "browse.json").write_text(json.dumps(MIX))
    (bench / "metrics" / f"{METRIC}.py").write_text(READER)
    bm["configs"].append({"name": "shop", "source": "a test configuration",
                          "file": "bench/configs/shop.json", "reduced": [],
                          "why": "OPTIONAL and UNION over optional data"})
    bm["workloads"].append({"name": CELL, "config": "shop", "traffic": "browse",
                            "chips": 1, "why": "every operator of the fragment"})
    bm["per_layer"].append({"name": METRIC, "unit": "solves", "better": "lower",
                            "source": "program_span", "layer": "engine host path",
                            "moves": "goodput_qps", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm, indent=2))
    return root


def _python(root, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(run.ROOT / "src")}
    return subprocess.run([sys.executable, *args], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)


def test_the_new_cell_is_correct_and_its_control_is_not(copy):
    p = _python(copy, "-c", RUNNER, CELL)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    plain, traced, control = out["untraced"], out["traced"], out["control"]
    assert plain["correct"] and plain["failed"] == 0, plain["checks"]
    assert set(plain["metrics"]) == {m["name"] for m in run.benchmark()["end_to_end"]
                                     if "workloads" not in m}
    assert traced["correct"], traced["checks"]
    assert traced["metrics"][METRIC]["value"] > 1.0  # the UNION's two parts
    assert traced["metrics"]["plan.host_ms_per_batch"]["value"] > 0
    assert not control["correct"]
    assert control["checks"]["wrong_answers"]["value"] > 0


def test_the_existing_tests_take_the_new_configuration(copy):
    p = _python(copy, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
                "bench/tests/test_traffic.py", "bench/tests/test_registry.py")
    assert p.returncode == 0, p.stdout[-4000:]
    assert f"test_same_seed_same_sequence_other_seed_other[{CELL}]" in p.stdout
