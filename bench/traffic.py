"""The one traffic generator: reads a mix file and the run's seed.

A mix (``bench/traffic/<name>.json``) names its arrival process and its
templates::

    {"arrivals": "poisson", "rate_qps": 8.0, "template_zipf": 0.99, "tenants": 4,
     "templates": [{"name": "members", "text": "{ ?d subOrganizationOf $u . ?s memberOf ?d }",
                    "slots": {"u": {"kind": "university", "zipf": 0.99}}}, ...]}

``arrivals`` names a file ``bench/arrivals/<name>.py`` that says how many
requests a window needs, when each is due and how they are sent (an open
Poisson loop, a closed loop of clients, ...); a new process is a new file.
Templates are drawn Zipf(``template_zipf``) by their order in the file; a
``$slot`` is filled with an entity of ``kind`` drawn Zipf(``zipf``) by rank.

Every seed gets the same work in another order: the count of each template,
the multiset of constant ranks and the arrival process's multiset of gaps
are fixed by the mix and the request count; the seed orders them and picks
which entities hold which rank.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from pathlib import Path

import numpy as np

from data import Dataset, load_by_name, rng

_SLOT = re.compile(r"\$([A-Za-z_][A-Za-z0-9_]*)")


@dataclasses.dataclass(frozen=True)
class Request:
    """One request: when it is due (seconds into the window) and its text."""

    due_s: float
    text: str
    template: str
    tenant: str


@functools.cache
def arrivals(name: str, bench: Path | None = None):
    """The module ``bench/arrivals/<name>.py``."""
    return load_by_name("arrivals", name, bench)


def fill(text: str, values: dict) -> str:
    """``text`` with each ``$slot`` replaced by ``values[slot]``."""
    return _SLOT.sub(lambda mt: values[mt.group(1)], text)


def zipf_quantiles(k: int, s: float, m: int) -> np.ndarray:
    """``m`` ranks in [0, k) at the midpoints of Zipf(``s``)'s quantiles."""
    cdf = np.cumsum(1.0 / np.arange(1, k + 1) ** s)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, (np.arange(m) + 0.5) / m), k - 1)


def fixed_counts(weights: np.ndarray, m: int) -> np.ndarray:
    """Largest-remainder split of ``m`` items by ``weights``."""
    raw = weights / weights.sum() * m
    counts = np.floor(raw).astype(np.int64)
    counts[np.argsort(-(raw - counts), kind="stable")[: m - counts.sum()]] += 1
    return counts


def count(mix: dict, seconds: float) -> int:
    """Requests the mix's arrival process needs for a window of ``seconds``."""
    return arrivals(mix["arrivals"]).count(mix, seconds)


def requests(mix: dict, ds: Dataset, seed: int, m: int) -> list[Request]:
    """``m`` requests of ``mix`` in the seed's order, each with its due
    time from the mix's arrival process."""
    gen = rng(seed, 10)
    tpl = mix["templates"]
    counts = fixed_counts(
        1.0 / np.arange(1, len(tpl) + 1) ** mix["template_zipf"], m
    )
    texts: list[list[str]] = []
    for t, c in zip(tpl, counts):
        fills = {}
        for slot, spec in t.get("slots", {}).items():
            ids = ds.kinds[spec["kind"]]
            hot = (np.arange(len(ids)) if spec.get("by") == "popularity"
                   else gen.permutation(len(ids)))  # rank -> entity
            ranks = gen.permutation(zipf_quantiles(len(ids), spec["zipf"], c))
            fills[slot] = [ds.node_names[i] for i in ids[hot[ranks]]]
        texts.append([
            fill(t["text"], {k: v[j] for k, v in fills.items()})
            for j in range(c)
        ])
    which = rng(seed, 11).permutation(np.repeat(np.arange(len(tpl)), counts))
    due = arrivals(mix["arrivals"]).due_times(mix, m, rng(seed, 12))
    tenants = mix.get("tenants", 1)
    nth = np.zeros(len(tpl), np.int64)
    out = []
    for i, t in enumerate(which.tolist()):
        out.append(Request(float(due[i]), texts[t][nth[t]], tpl[t]["name"],
                           f"t{i % tenants}"))
        nth[t] += 1
    return out
