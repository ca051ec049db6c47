"""Chaos soak of the serving failure plane (ISSUE 10).

``serve_bench.py`` measures the front end under load it did not agree to;
this benchmark measures it under load *and* failures it did not agree to.
One open-loop run is measured twice at the same offered rate (0.25x the
measured burst capacity — sized so the *surviving* fleet under phase-B
faults still has ~1.5x headroom; see the comment at the rate choice):
phase A fault-free, phase B with a seeded
:class:`repro.faults.FaultPlan` armed —

* one of the three replicas **crashes** mid-run (its 5th armed batch) and
  stays down until the router quarantines and rebuilds it;
* ~1% of requests are **poisoned** (they fail deterministically on every
  replica — retrying them would be wasted work);
* one replica becomes a 10x **straggler** (every batch stretched).

The headline metric is **goodput retained**: phase-B goodput over phase-A
goodput.  The soak also checks the failure plane's bookkeeping: every
submitted future resolves exactly once, the crashed replica is quarantined
and rebuilt, and the rebuilt engine's results are bit-identical to the
source database's.

    PYTHONPATH=src python benchmarks/chaos_bench.py
    PYTHONPATH=src python benchmarks/chaos_bench.py --smoke   # CI gate

Output: ``results/bench/chaos.json`` and an appended machine-stamped
record in the committed ``BENCH_chaos.json`` trajectory, gated by
``python -m tools.perfgate`` (goodput retained, rebuild, bit-identity).

``--smoke`` asserts the ISSUE 10 acceptance criteria: goodput under chaos
>= 70% of fault-free goodput, zero unresolved futures, the killed replica
quarantined and rebuilt with post-rebuild results bit-identical, and the
p99 of completed requests within the deadline.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import time

import numpy as np

from repro.data import synth
from repro.db import GraphDB
from repro.faults import FaultPlan, InjectedPoison
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import OUTCOMES, AsyncServer

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")
BENCH_TOP = os.path.join(os.path.dirname(__file__), "..", "BENCH_chaos.json")

QUERY = "{{ ?d subOrganizationOf {uni} . ?s memberOf ?d }}"

CRASH_REPLICA = "r1"
SLOW_REPLICA = "r2"
POISON_MARKER = "PoisonedConstant"


def _requests(db: GraphDB, n: int, seed: int, poison_every: int) -> list[str]:
    """``n`` request texts; every ``poison_every``-th carries the marker."""
    unis = [x for x in db.graph.node_names if x.startswith("Univ")]
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        if poison_every and i % poison_every == poison_every // 2:
            out.append(QUERY.format(uni=f"{POISON_MARKER}{i}"))
        else:
            out.append(QUERY.format(uni=unis[rng.integers(len(unis))]))
    return out


async def _warmup(server: AsyncServer, db: GraphDB, seed: int) -> float:
    """Warm every (bucket, replica) plan; return burst capacity (req/s)."""
    unis = [x for x in db.graph.node_names if x.startswith("Univ")]
    distinct = [QUERY.format(uni=u) for u in unis]
    buckets = server.router.replicas[0].engine.buckets
    sizes = sorted(
        {b for b in buckets if b <= min(server.max_batch, len(distinct))}
        | {1}
    )
    for size in sizes:
        for _ in range(2 * len(server.router) + 1):
            await asyncio.gather(*[
                server.submit(q, deadline_ms=60_000)
                for q in distinct[:size]
            ])
    reqs = _requests(db, server.max_batch, seed, poison_every=0)
    t0 = time.monotonic()
    burst = [server.submit(q, deadline_ms=60_000) for q in reqs * 4]
    results = await asyncio.gather(*burst)
    dt = time.monotonic() - t0
    assert all(r.ok for r in results), "warmup burst must not shed"
    return len(burst) / dt


async def _offer(
    server: AsyncServer,
    texts: list[str],
    *,
    rate: float,
    seed: int,
    deadline_ms: float,
) -> dict:
    """Offer ``texts`` at Poisson rate ``rate``; return phase measurements.

    Arrival times are pre-drawn and absolute (late arrivals fire
    back-to-back), same discipline as ``serve_bench``.
    """
    n = len(texts)
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, size=n)
    t_start = time.monotonic()
    arrivals = t_start + np.cumsum(gaps)
    futs = []
    for q, t_due in zip(texts, arrivals):
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
    poison_errors = sum(
        1 for r in results
        if r.outcome == "error" and isinstance(r.error, InjectedPoison)
    )
    done = sorted(r.total_ms for r in results if r.ok)

    def pct(xs, q):
        return float(xs[min(int(q * len(xs)), len(xs) - 1)]) if xs else 0.0

    return {
        "offered_req_s": rate,
        "n": n,
        "duration_s": wall,
        "completed": outcomes["ok"],
        "goodput_req_s": outcomes["ok"] / wall,
        "ok_rate": outcomes["ok"] / n,
        "outcomes": outcomes,
        "poison_errors": poison_errors,
        "p50_ms": pct(done, 0.50),
        "p99_ms": pct(done, 0.99),
    }


def _bit_identical(server: AsyncServer, db: GraphDB, texts: list[str]) -> bool:
    """Rebuilt-replica results vs the source engine, raw mask equality."""
    rep = next(
        r for r in server.router.replicas if r.name == CRASH_REPLICA
    )
    for text in texts:
        prepared = db._engine.prepare(db._coerce(text))
        with rep.lock:
            theirs = rep.engine.execute_prepared([prepared])[0]
        ours = db._engine.execute_prepared([prepared])[0]
        if not np.array_equal(theirs.survivors, ours.survivors):
            return False
    return True


async def _soak(args) -> dict:
    db = GraphDB(synth.lubm_like(n_universities=args.universities, seed=0))
    print(f"# database: {db.n_triples} triples / {db.n_nodes} nodes, "
          f"{args.replicas} replicas")
    plan = (
        FaultPlan(args.seed)
        .crash_replica(CRASH_REPLICA, at_batch=args.crash_at_batch)
        .slow_replica(SLOW_REPLICA, factor=args.slow_factor, extra_s=0.02)
        .poison_matching(POISON_MARKER)
    )
    async with AsyncServer(
        db,
        replicas=args.replicas,
        max_queue=args.max_queue,
        max_batch=args.max_batch,
        max_delay_ms=args.max_delay_ms,
        default_deadline_ms=args.deadline_ms,
        fault_plan=plan,
        max_retries=2,
        hedge=True,
    ) as server:
        capacity = await _warmup(server, db, seed=args.seed)
        # pin the failure-plane budgets only after warmup: a cold compile
        # legitimately exceeds any budget sized for warm service
        server.watchdog_budget = args.deadline_ms / 2e3
        server.hedge_delay = 0.150
        # Offered rate is sized against the *surviving* fleet, not the
        # healthy one: with 1 of 3 replicas crash-looping while armed and
        # another slowed 10x, surviving capacity is ~(1 + 1/slow_factor)/3
        # ~ 0.37x — offering 0.5x would make >= 70% retention unreachable
        # even with perfect routing.  0.25x leaves ~1.5x headroom, so the
        # retention gate measures routing quality (does the remnant's
        # capacity get wasted on the straggler/crasher?), not arithmetic.
        rate = 0.25 * capacity
        # a soak has a *duration*, not a request count: goodput is
        # completed/wall, and on a phase shorter than a few hundred ms the
        # wall is dominated by the tail of the last handful of requests
        # (one 200 ms retry would halve the "goodput" of a 100 ms phase).
        # Floor the phase length so the ratio measures steady-state
        # throughput under faults, not last-request latency.
        n_phase = max(args.n_per_phase, int(rate * args.min_phase_s))
        print(f"# warm burst capacity ~{capacity:.0f} req/s; "
              f"soaking both phases at {rate:.0f} req/s (0.25x), "
              f"{n_phase} requests/phase (>= {args.min_phase_s:.1f}s)")

        # phase A: fault-free baseline at the common offered rate
        clean = _requests(db, n_phase, args.seed + 1, poison_every=0)
        base = await _offer(
            server, clean, rate=rate, seed=args.seed + 2,
            deadline_ms=args.deadline_ms,
        )
        print(f"chaos/baseline,goodput={base['goodput_req_s']:.0f},"
              f"p50_ms={base['p50_ms']:.2f},p99_ms={base['p99_ms']:.2f},"
              f"ok_rate={base['ok_rate']:.3f}")

        # phase B: same rate, plan armed — crash + straggler + poison
        dirty = _requests(
            db, n_phase, args.seed + 3,
            poison_every=args.poison_every,
        )
        plan.arm()
        chaos = await _offer(
            server, dirty, rate=rate, seed=args.seed + 4,
            deadline_ms=args.deadline_ms,
        )
        plan.disarm()
        rebuilt = server.router.wait_rebuilt(timeout=15.0)
        snap = server.metrics.snapshot()
        events = server.router.events()
        health = {h["name"]: h for h in server.router.health()}

        crash = plan.crash_fired(CRASH_REPLICA)
        quarantined_t = next(
            (e["t"] for e in events
             if e["replica"] == CRASH_REPLICA and e["event"] == "quarantined"),
            None,
        )
        time_to_quarantine_s = (
            quarantined_t - crash["t"]
            if crash is not None and quarantined_t is not None else None
        )
        # bit-identity probe AFTER the soak: the rebuilt engine must agree
        # with the source engine on fresh fault-free requests
        probes = _requests(db, 4, args.seed + 5, poison_every=0)
        identical = rebuilt and _bit_identical(server, db, probes)

        print(f"chaos/faulted,goodput={chaos['goodput_req_s']:.0f},"
              f"p50_ms={chaos['p50_ms']:.2f},p99_ms={chaos['p99_ms']:.2f},"
              f"ok_rate={chaos['ok_rate']:.3f},"
              f"retries={snap.retries},hedges={snap.hedges},"
              f"timeouts={snap.timeouts},overruns={snap.watchdog_overruns}")
        retained = (
            chaos["goodput_req_s"] / base["goodput_req_s"]
            if base["goodput_req_s"] > 0 else 0.0
        )
        print(f"chaos/verdict,goodput_retained={retained:.3f},"
              f"rebuilt={int(rebuilt)},bit_identical={int(identical)},"
              f"time_to_quarantine_s="
              f"{-1.0 if time_to_quarantine_s is None else time_to_quarantine_s:.3f}")

    return {
        "capacity_burst_req_s": capacity,
        "offered_req_s": rate,
        "baseline": base,
        "chaos": chaos,
        "goodput_retained": retained,
        "goodput_chaos_req_s": chaos["goodput_req_s"],
        "p99_chaos_ms": chaos["p99_ms"],
        "ok_rate_chaos": chaos["ok_rate"],
        "rebuilt": float(rebuilt),
        "bit_identical": float(identical),
        "time_to_quarantine_s": time_to_quarantine_s,
        "injections": plan.counts(),
        "health": {name: h["state"] for name, h in health.items()},
        "counters": {
            "retries": snap.retries,
            "hedges": snap.hedges,
            "timeouts": snap.timeouts,
            "watchdog_overruns": snap.watchdog_overruns,
        },
        "resolved_identity": snap.submitted == snap.resolved,
        "metrics": dataclasses.asdict(snap),
        "n_triples": db.n_triples,
    }


def _append_trajectory(entry: dict) -> None:
    """Append one machine-stamped record to ``BENCH_chaos.json``."""
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
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--n-per-phase", type=int, default=400,
                    help="minimum requests per phase (raised to cover "
                         "--min-phase-s at the offered rate)")
    ap.add_argument("--min-phase-s", type=float, default=4.0,
                    help="minimum phase duration in seconds")
    ap.add_argument("--deadline-ms", type=float, default=2000.0)
    ap.add_argument("--max-queue", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--crash-at-batch", type=int, default=5)
    ap.add_argument("--slow-factor", type=float, default=10.0)
    ap.add_argument("--poison-every", type=int, default=100,
                    help="poison every N-th phase-B request (~1%%)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: small fixed-seed soak + the ISSUE 10 "
                         "acceptance asserts")
    args = ap.parse_args()
    if args.smoke:
        args.universities = min(args.universities, 2)
        args.n_per_phase = min(args.n_per_phase, 150)
        args.min_phase_s = min(args.min_phase_s, 2.0)

    out = asyncio.run(_soak(args))

    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "chaos.json"), "w") as f:
        json.dump(out, f, indent=1, default=str)

    _append_trajectory({
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "smoke": bool(args.smoke),
        "replicas": args.replicas,
        "n_triples": out["n_triples"],
        "deadline_ms": args.deadline_ms,
        "capacity_burst_req_s": out["capacity_burst_req_s"],
        "goodput_retained": out["goodput_retained"],
        "goodput_chaos_req_s": out["goodput_chaos_req_s"],
        "ok_rate_chaos": out["ok_rate_chaos"],
        "p99_chaos_ms": out["p99_chaos_ms"],
        "rebuilt": out["rebuilt"],
        "bit_identical": out["bit_identical"],
        "time_to_quarantine_s": out["time_to_quarantine_s"],
        "counters": out["counters"],
        "injections": out["injections"],
    })

    if args.smoke:
        # acceptance (ISSUE 10): the chaos phase keeps >= 70% of fault-free
        # goodput, nothing leaks, the crashed replica comes back bit-exact,
        # and the served tail stays inside the deadline
        assert out["resolved_identity"], \
            "drained server left futures unaccounted"
        assert out["goodput_retained"] >= 0.70, (
            f"chaos goodput retained {out['goodput_retained']:.2f} < 0.70 "
            "of the fault-free baseline"
        )
        assert out["rebuilt"] == 1.0, \
            f"crashed replica not rebuilt (health={out['health']})"
        assert out["bit_identical"] == 1.0, \
            "rebuilt replica disagrees with the source engine"
        assert out["injections"].get("crash", 0) >= 1, \
            "the crash injection never fired"
        assert out["time_to_quarantine_s"] is not None, \
            "crashed replica was never quarantined"
        assert out["p99_chaos_ms"] <= args.deadline_ms, (
            f"chaos p99 of completed requests {out['p99_chaos_ms']:.1f} ms "
            f"exceeds the {args.deadline_ms:.0f} ms deadline"
        )
        print("# smoke acceptance: goodput retained, replica rebuilt "
              "bit-identical, zero unresolved futures, p99 in deadline")


if __name__ == "__main__":
    main()
