"""Every cell end to end on the CPU at a tiny size, through the functions a
chip run uses: graph, server, warm-up, window, trace, metrics, check."""
import pytest

import run

SEED = 2**31 + 99
TINY = {"universities": 4}


def tiny_run(workload, trace=False, rate=10.0, **kw):
    wl, cfg, mix = run.cell(run.benchmark(), workload)
    mix_kw = {"rate_qps": rate} if "rate_qps" in mix else None
    return run.run(workload, SEED, 2.0, trace, require_chip=False,
                   config_override=TINY,
                   mix_override=mix_kw, **kw)


def cells():
    return [w["name"] for w in run.benchmark()["workloads"]]


@pytest.fixture(autouse=True)
def _cache(monkeypatch):
    # no persistent compile cache on the CPU: nothing written to the checkout
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")


@pytest.mark.parametrize("workload", cells())
def test_cell_end_to_end(workload):
    res = tiny_run(workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    bm = run.benchmark()
    wl = next(w for w in bm["workloads"] if w["name"] == workload)
    want = {m["name"] for m in run.cell_metrics(bm, wl, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", cells())
def test_cell_traced(workload):
    res = tiny_run(workload, trace=True)
    assert res["correct"], res["checks"]
    assert res["device"]["window_s"] > 0
    # host-only readers read something; device readers need a device plane
    for name in ("serve.requests_per_batch", "engine.prune_ms_per_batch",
                 "fixpoint.sweeps_per_solve"):
        assert res["metrics"][name]["value"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
