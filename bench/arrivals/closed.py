"""Closed loop: ``clients_per_slot`` clients per replica batch slot.

``clients_per_slot x replicas x max_batch`` clients share the mix's
sequence of ``sequence`` requests, each sending its next one as soon as its
last is answered, with no think time, as the DBpedia SPARQL Benchmark and
BSBM drive an endpoint.  A request is due when it is sent.
"""
from __future__ import annotations

import asyncio
import itertools
import time

import numpy as np


def count(mix: dict, seconds: float) -> int:
    del seconds
    return mix["sequence"]


def due_times(mix: dict, m: int, gen: np.random.Generator) -> np.ndarray:
    del mix, gen
    return np.zeros(m)


async def drive(send, reqs, t_open: float, t_close: float, mix: dict,
                server: dict):
    """The clients, each awaiting ``send(req, now)`` until the close."""
    del t_open
    clients = mix["clients_per_slot"] * server["replicas"] * server["max_batch"]
    seq = itertools.cycle(reqs)

    async def client():
        while time.monotonic() < t_close:
            await asyncio.wait({send(next(seq), time.monotonic())})

    await asyncio.gather(*[client() for _ in range(clients)])
