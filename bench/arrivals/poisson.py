"""Open loop: Poisson arrivals at the mix's ``rate_qps``.

Each request is sent when it is due, whether or not earlier ones have been
answered.  The gaps are the exponential distribution's quantiles at fixed
points, in the seed's order: every seed offers the same gaps, so the same
load, in another order.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np


def count(mix: dict, seconds: float) -> int:
    """Requests due in a window of ``seconds``."""
    return round(mix["rate_qps"] * seconds)


def due_times(mix: dict, m: int, gen: np.random.Generator) -> np.ndarray:
    """Seconds into the window at which each of ``m`` requests is due."""
    gaps = -np.log1p(-(np.arange(m) + 0.5) / m) / mix["rate_qps"]
    return np.cumsum(gen.permutation(gaps))


async def drive(send, reqs, t_open: float, t_close: float, mix: dict,
                server: dict):
    """``send(req, due)`` each request at ``t_open + req.due_s``."""
    del t_close, mix, server
    for r in reqs:
        due = t_open + r.due_s
        wait = due - time.monotonic()
        if wait > 0:
            await asyncio.sleep(wait)
        send(r, due)
