"""Open-loop saturation benchmark of the async serving loop (ISSUE 6).

``benchmarks/engine_bench.py`` reports *closed-loop* req/s: the driver
submits a batch, waits for it, submits the next — offered load can never
exceed service rate, so the number measures the engine at its best, not
the server under pressure.  This benchmark is the honest complement: a
Poisson arrival process offers load the server did not agree to, swept
from below to far above capacity, and reports what a capacity claim
actually needs — goodput, p50/p99 latency of *completed* requests, and the
shed rate (explicit ``overloaded`` / ``deadline`` / ``cost`` outcomes from
:class:`repro.serve.AsyncServer`; an overloaded open-loop server that
*doesn't* shed shows unbounded queue growth instead, which is the failure
mode admission control exists to prevent).

    PYTHONPATH=src python benchmarks/serve_bench.py
    PYTHONPATH=src python benchmarks/serve_bench.py --smoke   # CI gate

Output: one ``offered,goodput,p50_ms,p99_ms,shed_rate`` CSV row per load
point, ``results/bench/serve.json``, and an appended record in the
top-level ``BENCH_serve.json`` trajectory (append-style like
``BENCH_engine.json``: committed history, not a per-run snapshot).

``--smoke`` asserts the ISSUE 6 acceptance criteria on a fixed-seed sweep:
>= 3 offered-load points; every submitted future resolved with an explicit
outcome; the overload point sheds; and the tail of what *was* served stays
bounded — every completed request's queue wait is below the deadline
(dispatch sheds expired requests instead of executing them), so p99
latency is bounded by ``deadline + slowest service`` no matter how hard
the arrival process overshoots.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import time

import numpy as np

from repro.data import synth
from repro.db import GraphDB
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import OUTCOMES, AsyncServer

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")
BENCH_TOP = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve.json")

QUERY = "{{ ?d subOrganizationOf {uni} . ?s memberOf ?d }}"


def _requests(db: GraphDB, n: int, seed: int) -> list[str]:
    unis = [x for x in db.graph.node_names if x.startswith("Univ")]
    rng = np.random.default_rng(seed)
    return [QUERY.format(uni=unis[rng.integers(len(unis))]) for _ in range(n)]


async def _warmup(server: AsyncServer, db: GraphDB, seed: int) -> float:
    """Build every (bucket, replica) plan the sweep will hit; returns the
    burst capacity (closed-loop req/s through the server) used to place
    the offered-load points relative to what the machine can actually do.
    """
    unis = [x for x in db.graph.node_names if x.startswith("Univ")]
    distinct = [QUERY.format(uni=u) for u in unis]
    # the microbatcher dedups by constants, so a dispatched batch holds at
    # most len(distinct) unique instances; warm every bucket size a sweep
    # batch can chunk into, on every replica — a cold jit trace landing
    # mid-sweep would otherwise stall the queue and poison the low-load
    # point's tail
    buckets = server.router.replicas[0].engine.buckets
    sizes = sorted(
        {b for b in buckets if b <= min(server.max_batch, len(distinct))}
        | {1}
    )
    for size in sizes:
        # enough rounds that least-in-flight routing lands every replica
        for _ in range(2 * len(server.router) + 1):
            await asyncio.gather(*[
                server.submit(q, deadline_ms=60_000)
                for q in distinct[:size]
            ])
    reqs = _requests(db, server.max_batch, seed)
    t0 = time.monotonic()
    burst = [server.submit(q, deadline_ms=60_000) for q in reqs * 4]
    results = await asyncio.gather(*burst)
    dt = time.monotonic() - t0
    assert all(r.ok for r in results), "warmup burst must not shed"
    return len(burst) / dt


async def _run_point(
    server: AsyncServer,
    db: GraphDB,
    *,
    rate: float,
    n: int,
    seed: int,
    deadline_ms: float,
) -> dict:
    """Offer ``n`` requests at Poisson rate ``rate``; measure the outcome.

    Arrival times are pre-drawn and absolute: when the event loop falls
    behind the schedule (overload is the whole point), late arrivals fire
    back-to-back instead of silently stretching the offered rate.
    """
    rng = np.random.default_rng(seed)
    reqs = _requests(db, n, seed + 1)
    gaps = rng.exponential(1.0 / rate, size=n)
    t_start = time.monotonic()
    arrivals = t_start + np.cumsum(gaps)
    futs = []
    for q, t_due in zip(reqs, arrivals):
        delay = t_due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        futs.append(server.submit(
            q, tenant=f"t{len(futs) % 2}", deadline_ms=deadline_ms
        ))
    results = await asyncio.gather(*futs)
    wall = time.monotonic() - t_start

    assert len(results) == n, "every submitted request must resolve"
    outcomes = {o: 0 for o in OUTCOMES}
    for r in results:
        outcomes[r.outcome] += 1
    done = sorted(r.total_ms for r in results if r.ok)
    queue_waits = [r.queue_ms for r in results if r.ok]
    service = [r.service_ms for r in results if r.ok]
    shed = n - outcomes["ok"] - outcomes["error"]

    def pct(xs, q):
        return float(xs[min(int(q * len(xs)), len(xs) - 1)]) if xs else 0.0

    return {
        "offered_req_s": rate,
        "n": n,
        "duration_s": wall,
        "completed": outcomes["ok"],
        "goodput_req_s": outcomes["ok"] / wall,
        "outcomes": outcomes,
        "shed_rate": shed / n,
        "p50_ms": pct(done, 0.50),
        "p99_ms": pct(done, 0.99),
        "queue_p99_ms": pct(sorted(queue_waits), 0.99),
        "queue_max_ms": max(queue_waits, default=0.0),
        "service_max_ms": max(service, default=0.0),
    }


async def _sweep(args) -> tuple:
    db = GraphDB(synth.lubm_like(n_universities=args.universities, seed=0))
    print(f"# database: {db.n_triples} triples / {db.n_nodes} nodes, "
          f"{args.replicas} replicas")
    async with AsyncServer(
        db,
        replicas=args.replicas,
        max_queue=args.max_queue,
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        default_deadline_ms=args.deadline_ms,
    ) as server:
        capacity = await _warmup(server, db, seed=args.seed)
        print(f"# warm burst capacity ~{capacity:.0f} req/s "
              f"(closed-loop, the ceiling the sweep is placed against)")
        points = []
        for mult in args.multipliers:
            point = await _run_point(
                server, db,
                rate=mult * capacity,
                n=args.n_per_point,
                seed=args.seed + int(mult * 1000),
                deadline_ms=args.deadline_ms,
            )
            point["load_multiplier"] = mult
            points.append(point)
            print(
                f"serve/open_loop_x{mult:g},{point['p99_ms']*1e3:.0f},"
                f"offered={point['offered_req_s']:.0f},"
                f"goodput={point['goodput_req_s']:.0f},"
                f"p50_ms={point['p50_ms']:.2f},p99_ms={point['p99_ms']:.2f},"
                f"shed_rate={point['shed_rate']:.2f}"
            )
        snap = server.metrics.snapshot()
    return points, capacity, snap, db


def _append_trajectory(entry: dict) -> None:
    """Append one record to the committed ``BENCH_serve.json`` history.

    Shares ``tools.perfgate.history`` with ``engine_bench`` so the write is
    atomic and append-only, and stamps the machine fingerprint so the perf
    gate keeps per-machine series separate.
    """
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from repro.engine.machine import machine_fingerprint
    from tools.perfgate.history import append_record

    entry.setdefault("machine", machine_fingerprint())
    append_record(BENCH_TOP, entry)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--universities", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--n-per-point", type=int, default=400)
    ap.add_argument("--multipliers", type=float, nargs="+",
                    default=[0.5, 1.0, 1.5, 4.0],
                    help="offered load as multiples of measured capacity")
    ap.add_argument("--deadline-ms", type=float, default=500.0)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: small fixed-seed sweep + acceptance "
                         "asserts (explicit sheds under overload, bounded "
                         "p99 of completed requests, zero unresolved)")
    args = ap.parse_args()
    if args.smoke:
        args.universities = min(args.universities, 2)
        args.n_per_point = min(args.n_per_point, 120)
        if len(args.multipliers) < 3:
            raise SystemExit("--smoke needs >= 3 offered-load points")

    points, capacity, snap, db = asyncio.run(_sweep(args))

    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "serve.json"), "w") as f:
        json.dump({"capacity_burst_req_s": capacity, "points": points,
                   "metrics": dataclass_dict(snap)}, f, indent=1, default=str)

    _append_trajectory({
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "smoke": bool(args.smoke),
        "replicas": args.replicas,
        "n_triples": db.n_triples,
        "deadline_ms": args.deadline_ms,
        "max_queue": args.max_queue,
        "capacity_burst_req_s": capacity,
        "points": [
            {k: p[k] for k in (
                "load_multiplier", "offered_req_s", "goodput_req_s",
                "p50_ms", "p99_ms", "shed_rate", "outcomes",
            )}
            for p in points
        ],
    })

    low, high = points[0], points[-1]
    bound_ms = args.deadline_ms * 1.25 + high["service_max_ms"]
    print(f"# sweep: {len(points)} load points, shed rate "
          f"{low['shed_rate']:.2f} -> {high['shed_rate']:.2f}, "
          f"overload p99 {high['p99_ms']:.1f} ms "
          f"(bound {bound_ms:.1f} ms = 1.25x deadline + slowest batch)")

    if args.smoke:
        # acceptance (ISSUE 6): explicit sheds under overload, and the tail
        # of admitted-and-served requests bounded by the deadline contract
        assert len(points) >= 3, "saturation sweep needs >= 3 points"
        assert high["shed_rate"] > 0.0, \
            "overload point must shed with explicit outcomes"
        assert low["shed_rate"] <= 0.5, \
            f"below-capacity point shed {low['shed_rate']:.0%}"
        for p in points:
            assert p["queue_max_ms"] <= args.deadline_ms * 1.25, (
                f"completed request waited {p['queue_max_ms']:.1f} ms "
                f"past the {args.deadline_ms} ms deadline"
            )
            assert p["p99_ms"] <= bound_ms, \
                f"p99 {p['p99_ms']:.1f} ms exceeds the {bound_ms:.1f} ms bound"
        # after stop() drains, every submitted request (warmup included)
        # must be accounted for by exactly one explicit outcome
        assert snap.submitted == snap.resolved, \
            "drained server left futures unaccounted"
        print("# smoke acceptance: sheds explicit, p99 bounded, "
              "zero unresolved futures")


def dataclass_dict(snap) -> dict:
    """MetricsSnapshot -> plain json-able dict."""
    import dataclasses

    return dataclasses.asdict(snap)


if __name__ == "__main__":
    main()
