#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve the mix at a list of fixed rates.

    python3 bench/knee.py --workload lubm.broad_open --seed 9 --seconds 10 --rates 2,4,8,16

One process builds and warms the cell once, then offers each rate for
``--seconds`` and prints, per rate, the goodput, the latency percentiles
from the due time, and the requests still unanswered when the window
closed.  The knee is the highest rate whose goodput keeps up with the
offered rate without a backlog that grows; a cell's ``rate_qps`` is set
from it once, by hand, and never searched for by a run.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    c = run.prepare(args.workload, args.seed)
    if "rate_qps" not in c.mix:
        raise SystemExit("a knee belongs to a mix offered at a fixed rate")

    async def sweep():
        async with c.server:
            for i, rate in enumerate(float(r) for r in args.rates.split(",")):
                mix = {**c.mix, "rate_qps": rate}
                reqs = run.traffic.requests(
                    mix, c.ds, args.seed + i, run.traffic.count(mix, args.seconds))
                recs, t_open, unresolved = await run.window(
                    c.server, mix, c.cfg, reqs, args.seconds)
                close = t_open + args.seconds
                lat = [r.latency_ms for r in recs]
                print(json.dumps({
                    "rate_qps": rate,
                    "goodput_qps": sum(r.ok and r.done <= close for r in recs)
                    / args.seconds,
                    "p50_ms": run.percentile(lat, 50),
                    "p95_ms": run.percentile(lat, 95),
                    "open_at_close": sum(r.done is None or r.done > close
                                         for r in recs),
                    "requests": len(recs), "unresolved": unresolved,
                    "drain_s": max((r.done or close) for r in recs) - close,
                }), flush=True)

    asyncio.run(sweep())
    return 0


if __name__ == "__main__":
    sys.exit(main())
