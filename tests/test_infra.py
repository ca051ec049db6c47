"""Checkpointing, fault tolerance, optimizer, compression, data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.data import pipeline
from repro.distributed import fault
from repro.optimizer import adamw, compress


# --------------------------------------------------------------------- #
# checkpoint
# --------------------------------------------------------------------- #
def _tree(rng):
    return {
        "a": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
        "nested": {"b": jnp.arange(7), "c": [jnp.ones(2), jnp.zeros(3)]},
        "step": jnp.int32(17),
    }


def test_save_restore_roundtrip(tmp_path, rng):
    t = _tree(rng)
    ckpt.save(str(tmp_path), 5, t)
    like = jax.tree.map(lambda x: jnp.zeros_like(x), t)
    restored, step = ckpt.restore(str(tmp_path), like)
    assert step == 5
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncommitted_checkpoint_ignored(tmp_path, rng):
    t = _tree(rng)
    ckpt.save(str(tmp_path), 1, t)
    # fake a half-written step (no COMMIT)
    os.makedirs(tmp_path / "step_00000002")
    assert ckpt.latest_steps(str(tmp_path)) == [1]


def test_corruption_detected(tmp_path, rng):
    t = _tree(rng)
    ckpt.save(str(tmp_path), 3, t)
    target = next((tmp_path / "step_00000003").glob("a.npy"))
    data = target.read_bytes()
    target.write_bytes(data[:-1] + bytes([data[-1] ^ 1]))
    with pytest.raises(IOError):
        ckpt.restore(str(tmp_path), t)


def test_background_save_and_gc(tmp_path, rng):
    t = _tree(rng)
    threads = [ckpt.save(str(tmp_path), s, t, background=True, keep=2) for s in range(4)]
    for th in threads:
        th.join()
    assert ckpt.latest_steps(str(tmp_path)) == [2, 3]


def test_elastic_restore_resharding(tmp_path, rng):
    """Restore onto explicit (trivial) shardings — the elastic path."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    t = _tree(rng)
    ckpt.save(str(tmp_path), 9, t)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    restored, _ = ckpt.restore(str(tmp_path), t, shardings=sh)
    np.testing.assert_array_equal(
        np.asarray(restored["a"]), np.asarray(t["a"])
    )


# --------------------------------------------------------------------- #
# fault tolerance
# --------------------------------------------------------------------- #
def test_straggler_monitor_flags_slow_host():
    mon = fault.StragglerMonitor(window=4, threshold=2.0)
    t = 0.0
    for step in range(6):
        for host, lat in [("h0", 1.0), ("h1", 1.0), ("slow", 5.0)]:
            mon.report(fault.Heartbeat(host, step, t + step * lat))
    assert mon.stragglers() == ["slow"]
    assert mon.dead(now=1e9, timeout=10) == ["h0", "h1", "slow"]


def test_restart_policy_retries_then_succeeds():
    calls = []

    def body(i):
        calls.append(i)
        if i < 2:
            raise RuntimeError("node lost")

    pol = fault.RestartPolicy(max_restarts=5, backoff_s=0)
    restarts = pol.run(body, sleep=lambda s: None)
    assert restarts == 2 and calls == [0, 1, 2]


def test_restart_policy_budget_exhausted():
    pol = fault.RestartPolicy(max_restarts=1, backoff_s=0)
    with pytest.raises(RuntimeError):
        pol.run(lambda i: (_ for _ in ()).throw(RuntimeError("x")),
                sleep=lambda s: None)


# --------------------------------------------------------------------- #
# optimizer + compression
# --------------------------------------------------------------------- #
def test_adamw_minimizes_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                            weight_decay=0.0, clip_norm=100.0)
    params = {"x": jnp.asarray([3.0, -2.0])}
    state = adamw.init(params)
    for _ in range(60):
        grads = {"x": 2 * params["x"]}
        params, state, _ = adamw.update(cfg, grads, state, params)
    assert float(jnp.abs(params["x"]).max()) < 0.3


def test_grad_clip_metric():
    cfg = adamw.AdamWConfig(clip_norm=1e-3)
    params = {"x": jnp.ones(4)}
    state = adamw.init(params)
    _, _, m = adamw.update(cfg, {"x": jnp.full(4, 100.0)}, state, params)
    assert float(m["grad_norm"]) > 1.0  # reported pre-clip


def test_topk_error_feedback_conserves_signal():
    grads = {"g": jnp.asarray(np.random.default_rng(0).normal(size=256).astype(np.float32))}
    err = compress.init_error(grads)
    kept, new_err = compress.topk_sparsify(grads, err, fraction=0.1)
    # kept + residual == grad + old error
    np.testing.assert_allclose(
        np.asarray(kept["g"] + new_err["g"]), np.asarray(grads["g"]), rtol=1e-6
    )
    nz = int((np.asarray(kept["g"]) != 0).sum())
    assert 0 < nz <= 26 + 5  # ~top 10% (ties tolerated)


def test_int8_quant_roundtrip_bounded():
    g = {"g": jnp.linspace(-4, 4, 101)}
    q, s = compress.quantize_int8(g)
    back = compress.dequantize_int8(q, s)
    assert float(jnp.abs(back["g"] - g["g"]).max()) <= float(s["g"]) * 0.51


# --------------------------------------------------------------------- #
# data pipeline determinism
# --------------------------------------------------------------------- #
def test_pipeline_deterministic_replay():
    corpus = pipeline.synthetic_corpus(vocab=50, n_tokens=5000, seed=1)
    mk = lambda start: pipeline.token_batches(
        corpus, batch=8, seq=16, seed=7,
        shard=pipeline.ShardSpec(0, 2), start_step=start,
    )
    it = mk(0)
    b0, b1, b2 = next(it), next(it), next(it)
    # replay from step 2 reproduces batch 2 exactly
    it2 = mk(2)
    b2r = next(it2)
    np.testing.assert_array_equal(b2["tokens"], b2r["tokens"])


def test_pipeline_host_shards_disjoint():
    corpus = pipeline.synthetic_corpus(vocab=50, n_tokens=50_000, seed=1)
    g0 = next(pipeline.token_batches(
        corpus, batch=8, seq=16, seed=3, shard=pipeline.ShardSpec(0, 2)))
    g1 = next(pipeline.token_batches(
        corpus, batch=8, seq=16, seed=3, shard=pipeline.ShardSpec(1, 2)))
    assert g0["tokens"].shape == (4, 16)
    assert not np.array_equal(g0["tokens"], g1["tokens"])


# --------------------------------------------------------------------- #
# streaming RDF ingest (ISSUE 8)
# --------------------------------------------------------------------- #
def test_rdf_load_stream_equals_load(tmp_path):
    """Chunked streaming ingest produces the identical dictionary-encoded
    graph as the tuple-list path, across chunk boundaries."""
    from repro.data import rdf, synth

    path = str(tmp_path / "lubm.nt")
    n = rdf.dump_stream(synth.lubm_stream(n_universities=2, seed=5), path)
    assert n > 0
    g_list = rdf.load(path)
    for chunk in (1, 7, 1 << 20):  # smaller, misaligned, larger than file
        g_stream = rdf.load_stream(path, chunk_triples=chunk)
        assert g_stream.n_nodes == g_list.n_nodes
        assert g_stream.n_labels == g_list.n_labels
        assert g_stream.node_names == g_list.node_names
        assert g_stream.label_names == g_list.label_names
        np.testing.assert_array_equal(g_stream.triples, g_list.triples)


def test_lubm_stream_matches_lubm_shape():
    """The streaming generator keeps LUBM's label mix and scaling law
    (~same node/edge count per university as lubm_like)."""
    from repro.core.graph import Graph
    from repro.data import synth

    g = Graph.from_triples(synth.lubm_stream(n_universities=3, seed=0))
    ref = synth.lubm_like(n_universities=3, seed=0)
    assert set(g.label_names) == set(ref.label_names)
    assert abs(g.n_nodes - ref.n_nodes) / ref.n_nodes < 0.05
    assert abs(g.n_edges - ref.n_edges) / ref.n_edges < 0.05


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_placement(env_dir, monkeypatch):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says (JAX reads the variable; nothing is set in code), and otherwise
    to the fixed ``<checkout>/.jax_cache``; importing sets nothing."""
    from repro.launch import compile_cache

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == os.path.join(checkout, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
