"""Every cell end to end on the CPU at a tiny size, through the functions a
chip run uses: graph, server, warm-up, window, trace, metrics, check.

Each configuration states its own CPU test size, the top-level ``tiny``
object of its file: the keys that replace the configuration's own.  Only
the tests read it; a chip run serves the configuration as its file states.
"""
import json

import pytest

import data
import run

SEED = 2**31 + 99
# read from the program's spans on any backend; the device readers need a
# device plane and may be absent on the CPU
HOST_READERS = ("serve.requests_per_batch", "engine.prune_ms_per_batch",
                "fixpoint.sweeps_per_solve", "plan.host_ms_per_batch",
                "plan.transfer_mb_per_batch")


def tiny(cfg: dict) -> dict:
    """``cfg`` at its own CPU test size."""
    return {**cfg, **cfg["tiny"]}


def tiny_run(workload, trace=False, rate=10.0, **kw):
    wl, cfg, mix = run.cell(run.benchmark(), workload)
    mix_kw = {"rate_qps": rate} if "rate_qps" in mix else None
    return run.run(workload, SEED, 2.0, trace, require_chip=False,
                   config_override=cfg["tiny"],
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
    for name in HOST_READERS:
        assert res["metrics"][name]["value"] > 0, name
    bm = run.benchmark()
    wl = next(w for w in bm["workloads"] if w["name"] == workload)
    for m in run.cell_metrics(bm, wl, "per_layer"):
        if m["source"] == "host_clock":  # the client's own clock
            assert res["metrics"][m["name"]]["value"] > 0, m["name"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


class _Generated(Exception):
    """Raised in place of generating a graph, with the configuration."""


@pytest.mark.parametrize("workload", cells())
def test_a_chip_run_serves_the_configuration_as_its_file_states(
        workload, monkeypatch):
    def caught(cfg, seed):
        raise _Generated(cfg)

    import jax

    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[0])
    monkeypatch.setattr(data, "generate", caught)
    with pytest.raises(_Generated) as got:
        run.main(["--workload", workload, "--seed", "5", "--seconds", "1"])
    bm = run.benchmark()
    wl = next(w for w in bm["workloads"] if w["name"] == workload)
    entry = next(c for c in bm["configs"] if c["name"] == wl["config"])
    assert got.value.args[0] == json.loads((run.ROOT / entry["file"]).read_text())
