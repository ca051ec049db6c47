"""The comparison that decides ``correct``.

Every request of the window resolves to one outcome.  A sample of the
distinct query texts answered ``ok`` (drawn from the seed, every template in
it) is recomputed by :mod:`reference` on the benchmark's own copy of the
snapshot each request was served from, and every answered request with a
sampled text is compared, survivor triple by survivor triple.  An exact
comparison: each number's limit is 0, except ``compared``, which must be at
least 1.
"""
from __future__ import annotations

import numpy as np

import reference
from data import rng

SAMPLE_TEXTS = 48


def sample_texts(recs, seed: int, k: int = SAMPLE_TEXTS) -> set:
    """Up to ``k`` distinct answered texts, one of every template first."""
    by_tpl: dict = {}
    for r in recs:
        if r.ok:
            by_tpl.setdefault(r.req.template, set()).add(r.req.text)
    gen = rng(seed, 20)
    picked: set = set()
    pools = [sorted(v) for _, v in sorted(by_tpl.items())]
    for pool in pools:
        picked.add(pool[gen.integers(len(pool))])
    rest = sorted({t for pool in pools for t in pool} - picked)
    if rest and len(picked) < k:
        take = gen.permutation(len(rest))[: k - len(picked)]
        picked.update(rest[i] for i in take)
    return picked


def rows(triples: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """Survivor triples as sorted unique id rows."""
    return np.unique(triples[mask], axis=0)


class _Answers:
    """A served answer in the reference graph's row order where the snapshot
    holds the same triples (the read-only case), else as sorted rows."""

    def __init__(self, graph):
        self.graph = graph
        self._same: dict = {}

    def same(self, snap) -> bool:
        key = id(snap)
        if key not in self._same:
            t = snap.triples
            self._same[key] = (snap, t.shape == self.graph.triples.shape
                               and np.array_equal(t, self.graph.triples))
        return self._same[key][1]


def compare(recs, graph: reference.RefGraph, seed: int, *, control=False,
            unresolved: int = 0) -> dict:
    """The checks of one run: name -> {value, limit, op}.

    ``control`` answers every sampled request with the reference stopped one
    round short of its fixpoint instead of the program's answer.
    """
    texts = sample_texts(recs, seed)
    want: dict = {}
    control_mask: dict = {}
    answers = _Answers(graph)
    wrong = compared = 0
    for r in recs:
        if not r.ok or r.req.text not in texts:
            continue
        text = r.req.text
        if text not in want:
            want[text] = reference.survivors(text, graph)
        if control:
            if text not in control_mask:
                control_mask[text] = reference.survivors(
                    text, graph, stop_early=True)
            ok = np.array_equal(control_mask[text], want[text])
        elif answers.same(r.result.snapshot):
            ok = np.array_equal(r.result.survivor_mask, want[text])
        else:
            ok = np.array_equal(
                rows(r.result.snapshot.triples, r.result.survivor_mask),
                rows(graph.triples, want[text]))
        compared += 1
        wrong += not ok
    errors = sum(r.outcome == "error" for r in recs)
    return {
        "wrong_answers": {"value": wrong, "limit": 0, "op": "<="},
        "errors": {"value": errors, "limit": 0, "op": "<="},
        "unresolved": {"value": unresolved, "limit": 0, "op": "<="},
        "compared": {"value": compared, "limit": 1, "op": ">="},
    }


def passed(checks: dict) -> bool:
    return all(
        c["value"] <= c["limit"] if c["op"] == "<=" else c["value"] >= c["limit"]
        for c in checks.values()
    )
