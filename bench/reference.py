"""Plain reference for the served answer: dual-simulation pruning in numpy.

A straightforward implementation of what a request's client receives: the
survivor triples of the paper's system of inequalities (SOI).  It imports
nothing of the program under test.  The query text is parsed here, the SOI is
built here (the paper's Sect. 3.2 edge inequalities and Sect. 4 copy
inequalities for OPTIONAL and non-well-designed AND, with the "syntactically
closest" renaming), and the greatest fixpoint is found by plain round-robin
evaluation of every inequality over per-label edge lists until nothing
changes.  The graph is the benchmark's own arrays: ``triples`` ``int32 [E, 3]``
of (subject, label, object) ids, ``label_names`` and ``node_index``.

``stop_early`` is the control: the fixpoint stops one changing round short
of convergence, so it answers with a superset of the true survivors: a stale
or approximate answer where the configuration states an exact one.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

FWD, BWD = 0, 1

# --------------------------------------------------------------------- #
# query text -> tree
# --------------------------------------------------------------------- #
_TOKEN = re.compile(
    r"\s*(?:(?P<lbrace>\{)|(?P<rbrace>\})|(?P<dot>\.(?![A-Za-z0-9_]))"
    r"|(?P<kw>(?:AND|OPTIONAL|UNION)\b)"
    r"|(?P<var>\?[A-Za-z_][A-Za-z0-9_]*)"
    r"|(?P<name>[A-Za-z0-9_:/#\-\.]+))"
)


def parse(text: str):
    """``("bgp", [(s, p, o), ...])`` or ``(op, left, right)`` with ``op`` in
    AND / OPTIONAL / UNION; variables keep their leading ``?``."""
    toks = []
    pos, end = 0, len(text.rstrip())
    while pos < end:
        m = _TOKEN.match(text, pos)
        if not m:
            raise SyntaxError(f"bad token at {text[pos:pos + 30]!r}")
        pos = m.end()
        toks.append((m.lastgroup, m.group(m.lastgroup)))

    def pop(kind):
        k, v = toks.pop(0)
        if k != kind:
            raise SyntaxError(f"expected {kind}, got {k} {v!r}")
        return v

    def group():
        pop("lbrace")
        if toks[0][0] == "lbrace":
            q = expr()
            pop("rbrace")
            return q
        triples = []
        while toks[0][0] != "rbrace":
            s = toks.pop(0)[1]
            p = pop("name")
            o = toks.pop(0)[1]
            triples.append((s, p, o))
            if toks[0][0] == "dot":
                toks.pop(0)
        pop("rbrace")
        return ("bgp", triples)

    def expr():
        left = group()
        while toks and toks[0][0] == "kw":
            op = toks.pop(0)[1]
            left = (op, left, group())
        return left

    q = expr()
    if toks:
        raise SyntaxError(f"trailing tokens {toks[:3]}")
    return q


def union_free_parts(q) -> list:
    """Rewrite UNION away: AND and OPTIONAL distribute over it."""
    if q[0] == "bgp":
        return [q]
    if q[0] == "UNION":
        return union_free_parts(q[1]) + union_free_parts(q[2])
    return [(q[0], a, b) for a in union_free_parts(q[1])
            for b in union_free_parts(q[2])]


# --------------------------------------------------------------------- #
# tree -> system of inequalities
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class Soi:
    names: list  # var id -> query name ("?x") or constant name
    const: list  # var id -> constant name or None
    edges: list  # (v, label, w) pattern edges
    copies: list  # (lhs, rhs): lhs <= rhs
    mand: dict  # name -> var id of its mandatory occurrence
    opt: dict  # name -> var ids of unlinked optional occurrences


def _bgp(triples) -> Soi:
    ids: dict = {}
    names, const, edges = [], [], []

    def vid(term):
        key = term if term.startswith("?") else "<" + term
        if key not in ids:
            ids[key] = len(names)
            names.append(term)
            const.append(None if term.startswith("?") else term)
        return ids[key]

    for s, p, o in triples:
        edges.append((vid(s), p, vid(o)))
    mand = {k: i for k, i in ids.items() if k.startswith("?")}
    return Soi(names, const, edges, [], mand, {})


def _merge(s: Soi, keep: int, drop: int):
    remap, j = {}, 0
    for i in range(len(s.names)):
        if i != drop:
            remap[i] = j
            j += 1
    remap[drop] = remap[keep]
    f = remap.__getitem__
    out = Soi(
        [x for i, x in enumerate(s.names) if i != drop],
        [x for i, x in enumerate(s.const) if i != drop],
        [(f(v), a, f(w)) for v, a, w in s.edges],
        sorted({(f(a), f(b)) for a, b in s.copies if f(a) != f(b)}),
        {n: f(i) for n, i in s.mand.items()},
        {n: [f(i) for i in ids] for n, ids in s.opt.items()},
    )
    return out, remap


def _combine(a: Soi, b: Soi, optional: bool) -> Soi:
    off = len(a.names)
    out = Soi(
        a.names + b.names, a.const + b.const,
        a.edges + [(v + off, p, w + off) for v, p, w in b.edges],
        a.copies + [(x + off, y + off) for x, y in b.copies], {}, {},
    )
    mb = {n: i + off for n, i in b.mand.items()}
    ob = {n: [i + off for i in ids] for n, ids in b.opt.items()}
    merges = []
    for n in set(a.mand) | set(a.opt) | set(mb) | set(ob):
        ma, m2 = a.mand.get(n), mb.get(n)
        oa, o2 = list(a.opt.get(n, [])), list(ob.get(n, []))
        if optional:
            occ2 = ([m2] if m2 is not None else []) + o2
            if ma is not None:
                out.copies += [(i, ma) for i in occ2]
                out.mand[n] = ma
                if oa:
                    out.opt[n] = oa
            elif oa + occ2:
                out.opt[n] = oa + occ2
        elif ma is not None and m2 is not None:
            merges.append((ma, m2))
            out.mand[n] = ma
        elif ma is not None:
            out.copies += [(i, ma) for i in o2]
            out.mand[n] = ma
        elif m2 is not None:
            out.copies += [(i, m2) for i in oa]
            out.mand[n] = m2
        elif oa + o2:
            out.opt[n] = oa + o2
    trans = {i: i for i in range(len(out.names))}
    for keep, drop in merges:
        k, d = trans[keep], trans[drop]
        if k != d:
            out, remap = _merge(out, k, d)
            trans = {o: remap[c] for o, c in trans.items()}
    return out


def build_soi(q) -> Soi:
    """SOI of a union-free query tree."""
    if q[0] == "bgp":
        return _bgp(q[1])
    if q[0] == "UNION":
        raise ValueError("split UNION first (union_free_parts)")
    return _combine(build_soi(q[1]), build_soi(q[2]), q[0] == "OPTIONAL")


# --------------------------------------------------------------------- #
# the graph and the fixpoint
# --------------------------------------------------------------------- #
class RefGraph:
    """Per-label edge lists over the benchmark's own triple array."""

    def __init__(self, triples: np.ndarray, n_nodes: int, label_names,
                 node_index: dict):
        self.triples = triples
        self.n_nodes = n_nodes
        self.label_id = {n: i for i, n in enumerate(label_names)}
        self.node_index = node_index
        order = np.argsort(triples[:, 1], kind="stable")
        counts = np.bincount(triples[:, 1], minlength=len(label_names))
        starts = np.concatenate([[0], np.cumsum(counts)])
        # label -> row numbers of its triples in ``triples``
        self.rows = {
            i: order[starts[i]:starts[i + 1]] for i in range(len(label_names))
        }

    def label_rows(self, label: str) -> np.ndarray:
        i = self.label_id.get(label)
        return self.rows[i] if i is not None else np.zeros(0, np.int64)


def _image(rows, triples, frm: np.ndarray, src_col: int, dst_col: int, n):
    """Nodes reached over ``rows`` from the node set ``frm``."""
    out = np.zeros(n, bool)
    hit = frm[triples[rows, src_col]]
    out[triples[rows[hit], dst_col]] = True
    return out


def fixpoint(soi: Soi, g: RefGraph, stop_early: bool = False):
    """Greatest solution of the SOI; returns (chi bool [V, n], rounds).

    Each round evaluates every inequality once, in order, each against the
    current chi; rounds repeat until one changes nothing.  ``stop_early``
    returns the chi from before the last changing round instead.
    """
    n = g.n_nodes
    chi = np.ones((len(soi.names), n), bool)
    for i, c in enumerate(soi.const):
        if c is not None:
            chi[i] = False
            nid = g.node_index.get(c)
            if nid is not None and nid < n:
                chi[i, nid] = True
    rows = {a: g.label_rows(a) for _, a, _ in soi.edges}
    prev, rounds = chi.copy(), 0
    while True:
        before = chi.copy()
        for v, a, w in soi.edges:
            r = rows[a]
            chi[w] &= _image(r, g.triples, chi[v], 0, 2, n)
            chi[v] &= _image(r, g.triples, chi[w], 2, 0, n)
        for lhs, rhs in soi.copies:
            chi[lhs] &= chi[rhs]
        if np.array_equal(before, chi):
            break
        prev, rounds = before, rounds + 1
    return (prev if stop_early else chi), rounds


def survivors(query: str, g: RefGraph, stop_early: bool = False) -> np.ndarray:
    """Bool mask over ``g.triples``: triples some pattern edge keeps."""
    mask = np.zeros(len(g.triples), bool)
    for part in union_free_parts(parse(query)):
        soi = build_soi(part)
        chi, _ = fixpoint(soi, g, stop_early)
        for v, a, w in soi.edges:
            r = g.label_rows(a)
            keep = chi[v][g.triples[r, 0]] & chi[w][g.triples[r, 2]]
            mask[r[keep]] = True
    return mask
