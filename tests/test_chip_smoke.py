"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The script itself refuses to run without a TPU; its phase functions do
not check the device, so this test drives each of them through the same
control flow — ingest, ``AsyncServer`` waves, warm resumes after a delete
and a re-insert, the forced dense engines, the partitioned mesh — with
every answer held to ``dualsim.solve_worklist`` inside the phase.
"""
import jax
import numpy as np

import chip_smoke

UNIVERSITIES = 20


def test_edge_phase_matches_worklist_and_resumes_warm():
    rep = chip_smoke.edge_phase(UNIVERSITIES)
    assert rep["plan"].engine in chip_smoke.EDGE_TIER
    assert rep["auto"] == rep["plan"].engine
    assert rep["priced_by"] == "hand-tuned cost model"
    agg = rep["agg"]
    assert agg["warm_resume_solves"] >= 1 and agg["plans_resumed"] >= 1
    assert len(rep["rows"]) == chip_smoke.N_REQUESTS
    assert rep["n_nodes"] == 3300


def test_dense_phase_forces_both_packed_engines():
    rep = chip_smoke.dense_phase(UNIVERSITIES)
    for engine in ("packed_fused", "packed"):
        assert rep[engine]["plan"].engine == engine
        assert rep[engine]["agg"]["engine_counts"] == {
            engine: rep[engine]["agg"]["microbatches"]
        }
    for a, b in zip(rep["packed_fused"]["rows"], rep["packed"]["rows"]):
        assert np.array_equal(a, b)


def test_partitioned_phase_on_every_local_device():
    rep = chip_smoke.partitioned_phase(UNIVERSITIES, len(jax.devices()))
    assert rep["meshed"]["plan"].engine == "partitioned"


def test_a_wrong_answer_fails_the_wave():
    """``check_wave`` fails a request whose survivors differ."""
    import pytest

    graph, _ = chip_smoke.lubm_graph(2)
    query = chip_smoke.make_requests(2)[0]

    class Fake:
        ok, outcome, detail = True, "ok", ""

        class result:
            snapshot = graph
            survivor_mask = np.ones(graph.n_edges, bool)

    with pytest.raises(chip_smoke.SmokeFailure, match="kept"):
        chip_smoke.check_wave([Fake()], [query], chip_smoke.Reference(), "t")
