"""Berkeley-ERT-style machine probe → ``MachineSpec`` (DESIGN.md 13.1).

Replaces the seed's dead roofline-table assembly (which read a results
directory no launcher produces anymore) with the measurement layer the
calibrated cost model runs on.  Four sweeps, each a
micro-kernel the engines actually execute, best-of-N timed like ERT:

* **stream** — sustained memory bandwidth: a jitted ``uint32`` XOR stream
  over working sets from cache-resident to HBM/DRAM-resident (the
  segmented-OR sweep is this workload: packed chi planes + edge id
  streams).  Peak across sizes is the spec's ``stream_bytes_per_s``.
* **bitop** — ``bitmm_apply`` word throughput at increasing ``n`` (the
  arithmetic intensity grows with ``n``: ``V*n*n/32`` word-ops over
  ``n*n/8`` resident bytes), under BOTH lowerings the plans ship — the
  kernel path (interpret mode on CPU, compiled Pallas elsewhere) and the
  word-wise XLA path.  The smallest size gives the per-call overheads
  (``kernel_launch_s`` / ``dispatch_s``), the largest the sustained
  words/s, launch-corrected.
* **dense** — boolean matmul via the f32 MXU/BLAS path, exactly the dense
  engine's product, giving ``dense_elems_per_s``.
* **collective** — on a >= 2-device mesh only: a pmap'd ``psum`` over a
  replicated plane, giving ``collective_bytes_per_s`` (per-byte collective
  cost for the comm terms); ``None`` on one device.

Plus a **trace** probe: wall time to ``jit``-lower-and-compile a
representative packed ``while_loop`` fixpoint — the resume-vs-cold model's
``trace_cost``.

The result persists as a versioned JSON under ``results/machine/`` keyed by
:func:`repro.engine.machine.machine_fingerprint`, where
:func:`repro.engine.machine.default_spec` (and so the engine/serving cost
paths) and ``tools/perfgate`` find it.  ``--fast`` runs the reduced CI
sweep (fewer sizes/repeats — noisier, still valid calibration).
"""
from __future__ import annotations

import argparse
import datetime
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitops
from repro.kernels.bitmm import ops as bitmm_ops
from repro.launch.compile_cache import enable_compile_cache


def _best_s(fn, repeats: int) -> float:
    """Best-of-N wall seconds of ``fn`` (first call compiles, untimed)."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def stream_probe(fast: bool = False, repeats: int | None = None) -> dict:
    """Peak sustained streaming bandwidth over ``uint32`` word traffic.

    Read + write one word per element (8 bytes moved per word): the traffic
    shape of the packed-chi planes and edge-id streams the segor sweep
    moves.  Sweeps working sets past typical LLC sizes so the peak is a
    memory number, not a cache number.
    """
    sizes = [1 << 20, 1 << 22] if fast else [1 << 20, 1 << 22, 1 << 24]
    repeats = repeats or (3 if fast else 5)
    rng = np.random.default_rng(0)
    rows = []
    for words in sizes:
        x = jnp.asarray(
            rng.integers(0, 2**32, words, dtype=np.uint64).astype(np.uint32)
        )
        f = jax.jit(lambda x: x ^ np.uint32(0x9E3779B9))
        t = _best_s(lambda: f(x), repeats)
        rows.append(dict(words=words, seconds=t, bytes_per_s=8.0 * words / t))
    return dict(rows=rows, bytes_per_s=max(r["bytes_per_s"] for r in rows))


def bitop_probe(
    backend: str, fast: bool = False, repeats: int | None = None
) -> dict:
    """``bitmm_apply`` word throughput + per-call overheads, both lowerings.

    Work per call is ``V * n * n/32`` word-ops (every output word ORs over
    all ``n`` adjacency rows).  ``shipping`` is the lowering plans actually
    run on this backend (interpret-mode kernel on CPU, compiled Pallas
    kernel elsewhere); ``xla`` is the word-wise pure-jnp lowering.  The
    cheapest call bounds the per-call overhead; the best words/seconds
    across sizes is the sustained rate — max/min extraction instead of a
    launch subtraction, which is fragile when small-size timings are
    non-monotonic (observed with the interpret emulator).
    """
    repeats = repeats or (3 if fast else 5)
    v = 8
    rng = np.random.default_rng(1)
    # interpret mode emulates the grid step by step: it needs modest shapes
    # to finish in CI time, and its measured throughput IS the shipping
    # cost the calibrated model should charge packed plans on CPU
    ship_ns = [64, 256, 1024] if backend == "cpu" else [64, 1024, 4096]
    xla_ns = [64, 1024, 2048] if fast else [64, 1024, 4096]
    if fast and backend == "cpu":
        ship_ns = [64, 512]

    def measure(ns, run):
        rows = []
        for n in ns:
            nw = bitops.packed_width(n)
            a = jnp.asarray(bitops.pack_np(rng.random((n, n)) < 0.01))
            chi = jnp.asarray(bitops.pack_np(rng.random((v, n)) < 0.5))
            flags = jnp.asarray(rng.integers(0, 2, (v, v)).astype(np.uint32))
            t = _best_s(lambda: run(chi, a, flags), repeats)
            rows.append(dict(n=n, seconds=t, words=v * n * nw))
        overhead = min(r["seconds"] for r in rows)
        words_per_s = max(r["words"] / r["seconds"] for r in rows)
        return dict(rows=rows, overhead_s=overhead, words_per_s=words_per_s)

    ship = measure(
        ship_ns,
        lambda c, a, f: bitmm_ops.bitmm_apply(
            c, a, f, interpret=(backend == "cpu")
        ),
    )
    xla = measure(
        xla_ns, lambda c, a, f: bitmm_ops.bitmm_apply(c, a, f, use_ref=True)
    )
    return dict(shipping=ship, xla=xla)


def dense_probe(fast: bool = False, repeats: int | None = None) -> dict:
    """Boolean-matmul element throughput via the dense engine's f32 path."""
    repeats = repeats or (3 if fast else 5)
    v = 16
    ns = [1024, 2048] if fast else [1024, 2048, 4096]
    rng = np.random.default_rng(2)
    rows = []
    for n in ns:
        x = jnp.asarray(rng.random((v, n)) < 0.5)
        af = jnp.asarray((rng.random((n, n)) < 0.01).astype(np.float32))
        f = jax.jit(lambda x, a: (x.astype(jnp.float32) @ a) > 0)
        t = _best_s(lambda: f(x, af), repeats)
        rows.append(dict(n=n, seconds=t, elems_per_s=v * n * n / t))
    return dict(rows=rows, elems_per_s=max(r["elems_per_s"] for r in rows))


def collective_probe(
    backend: str, fast: bool = False, repeats: int | None = None
) -> dict | None:
    """Per-byte collective cost over the visible mesh; ``None`` below 2 devices.

    An all-reduce ``psum`` of a float32 plane: the measured bytes/s is the
    *payload* rate (one plane's bytes over the call's wall time) — an
    envelope for the comm terms, not a bisection-bandwidth claim.
    """
    devices = jax.devices(backend)
    d = len(devices)
    if d < 2:
        return None
    repeats = repeats or (3 if fast else 5)
    words = 1 << 16 if fast else 1 << 18
    x = jnp.ones((d, words), jnp.float32)
    f = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")
    t = _best_s(lambda: f(x), repeats)
    payload = words * 4.0
    return dict(n_devices=d, words=words, seconds=t, bytes_per_s=payload / t)


def trace_probe(n: int = 2048, v: int = 8, sweeps: int = 8) -> float:
    """Seconds to jit-trace + lower + compile a packed while_loop fixpoint.

    The shape of every plan's solver: packed chi state, a
    changed-word-driven ``while_loop``, one fused operator application per
    step.  A lower bound on a real plan's cold trace (which adds SOI build
    and operand upload), measured rather than folklore.
    """
    nw = bitops.packed_width(n)
    rng = np.random.default_rng(3)
    a = jnp.asarray(bitops.pack_np(rng.random((n, n)) < 0.01))
    flags = jnp.asarray(rng.integers(0, 2, (v, v)).astype(np.uint32))

    def fixpoint(chi):
        def cond(state):
            _, it, changed = state
            return jnp.logical_and(it < sweeps, changed != 0)

        def body(state):
            chi, it, _ = state
            chi2, changed = bitmm_ops.bitmm_apply(chi, a, flags, use_ref=True)
            return chi2, it + 1, changed

        out, _, _ = jax.lax.while_loop(
            cond, body, (chi, jnp.int32(0), jnp.uint32(1))
        )
        return out

    shape = jax.ShapeDtypeStruct((v, nw), jnp.uint32)
    t0 = time.perf_counter()
    jax.jit(fixpoint).lower(shape).compile()
    return time.perf_counter() - t0


def probe(fast: bool = False, backend: str | None = None):
    """Run every sweep; returns ``(MachineSpec, per-sweep detail dict)``."""
    from repro.engine import machine

    backend = backend or jax.default_backend()
    devices = jax.devices(backend)
    stream = stream_probe(fast)
    bitop = bitop_probe(backend, fast)
    dense = dense_probe(fast)
    coll = collective_probe(backend, fast)
    trace_s = trace_probe(1024 if fast else 2048)
    cpu = backend == "cpu"
    ship_wps = bitop["shipping"]["words_per_s"]
    xla_wps = bitop["xla"]["words_per_s"]
    spec = machine.MachineSpec(
        backend=backend,
        device_kind=devices[0].device_kind if devices else "unknown",
        fingerprint=machine.machine_fingerprint(backend),
        n_devices=len(devices),
        stream_bytes_per_s=stream["bytes_per_s"],
        dense_elems_per_s=dense["elems_per_s"],
        packed_words_per_s=ship_wps,
        packed_words_per_s_xla=xla_wps,
        # the fused engine ships the words lowering on CPU, the kernel
        # elsewhere — same measurement base as the packed engine's; the
        # fusion advantage shows up in the launch/overhead terms
        fused_words_per_s=xla_wps if cpu else ship_wps,
        kernel_launch_s=bitop["shipping"]["overhead_s"],
        dispatch_s=bitop["xla"]["overhead_s"],
        trace_s=trace_s,
        collective_bytes_per_s=coll["bytes_per_s"] if coll else None,
        probed_at=datetime.datetime.now(datetime.timezone.utc).isoformat(),
        fast=fast,
    )
    detail = dict(stream=stream, bitop=bitop, dense=dense, collective=coll)
    return spec, detail


def main(argv: list[str] | None = None) -> int:
    """CLI: run the probe, print the spec, persist under ``results/machine/``."""
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true",
                    help="reduced CI sweep (fewer sizes/repeats)")
    ap.add_argument("--no-save", action="store_true",
                    help="print only; do not persist the spec")
    ap.add_argument("--json", action="store_true",
                    help="dump the spec as JSON to stdout")
    ap.add_argument("--backend", default=None,
                    help="jax backend to probe (default: process default)")
    args = ap.parse_args(argv)
    spec, _ = probe(fast=args.fast, backend=args.backend)
    if args.json:
        print(json.dumps(spec.to_json(), indent=1, sort_keys=True))
    else:
        for k, v in sorted(spec.to_json().items()):
            print(f"machine/{k},{v}")
    if not args.no_save:
        from repro.engine import machine

        path = machine.save_spec(spec)
        print(f"# saved {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
