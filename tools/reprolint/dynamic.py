"""Dynamic (jaxpr-level) confirmation of RL1/RL2 for the packed engines.

Static analysis sees the Python source; this module checks what XLA actually
traced.  It generalizes the PR 5 jaxpr-inspection test into a reusable
cross-check: trace each packed engine once on a small synthetic instance and
assert the ``lax.while_loop`` body

* contains none of the primitives ``bitops.pack`` / ``unpack`` lower to
  (``reduce_sum`` / ``shift_left`` / ``shift_right_*``) — fused engine only,
* never materializes a bool ``[V, n]`` chi plane
  (``convert_element_type`` to bool with rank >= 2),
* carries ``uint32`` words, not bools, as loop state.

Since ISSUE 8 the edge-list engines (sparse gs / jacobi_packed /
partitioned) get their own body check (:func:`check_edge_body`): ``y``
arrives already packed from the segmented-OR primitive, so the while body
must contain no ``reduce_sum`` (the summing half of ``bitops.pack``) and no
bool-plane convert.  Shifts remain legal there — the word-wise segor
lowering shifts freshly-reduced *words* into place and ``_edge_bits``
extracts single frontier bits; neither is a chi round-trip (DESIGN.md
Sect. 12).

Used two ways: imported by ``tests/test_dualsim_core.py`` (tier-1) and run
standalone in the CI ``reprolint`` job::

    PYTHONPATH=src python -m tools.reprolint.dynamic
"""

from __future__ import annotations

import numpy as np

FUSED_FORBIDDEN = {
    "reduce_sum",  # the sum step of bitops.pack
    "shift_left",  # pack's per-bit shifts
    "shift_right_logical",  # unpack's per-bit shifts
    "shift_right_arithmetic",
}

# Edge-list engines: shifts are load-bearing (bit extraction / word
# assembly on fresh segment-reduce output), but any reduce_sum means a
# bitops.pack snuck back into the sweep.
EDGE_FORBIDDEN = {"reduce_sum"}


def sub_jaxprs(param):
    """Yield jaxprs nested inside an equation parameter."""
    from jax.extend import core as jcore

    if isinstance(param, jcore.ClosedJaxpr):
        yield param.jaxpr
    elif isinstance(param, jcore.Jaxpr):
        yield param
    elif isinstance(param, (tuple, list)):
        for p in param:
            yield from sub_jaxprs(p)


def collect_while_eqns(jaxpr, out=None):
    """All ``while`` equations reachable without entering pallas_call."""
    if out is None:
        out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "while":
            out.append(eqn)
        for param in eqn.params.values():
            for sub in sub_jaxprs(param):
                collect_while_eqns(sub, out)
    return out


def primitive_names(jaxpr, skip=("pallas_call",)):
    """Set of primitive names in a jaxpr, recursing except into ``skip``."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name in skip:
            continue
        for param in eqn.params.values():
            for sub in sub_jaxprs(param):
                names |= primitive_names(sub, skip)
    return names


def bool_plane_converts(jaxpr, skip=("pallas_call",)):
    """``convert_element_type`` eqns producing a rank>=2 bool plane."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            aval = eqn.outvars[0].aval
            if np.dtype(aval.dtype) == np.dtype(np.bool_) and aval.ndim >= 2:
                out.append(eqn)
        if eqn.primitive.name in skip:
            continue
        for param in eqn.params.values():
            for sub in sub_jaxprs(param):
                out.extend(bool_plane_converts(sub, skip))
    return out


def _while_bodies(fn, *args):
    import jax

    jaxpr = jax.make_jaxpr(fn)(*args)
    whiles = collect_while_eqns(jaxpr.jaxpr)
    return [eqn.params["body_jaxpr"].jaxpr for eqn in whiles]


def check_carried_state(body) -> list[str]:
    """The loop carry must hold packed uint32 words and no bool plane."""
    import jax.numpy as jnp

    violations = []
    carried = [v.aval for v in body.outvars]
    if not any(a.dtype == jnp.uint32 and a.ndim == 2 for a in carried):
        violations.append(f"while carry holds no uint32 word plane: {carried}")
    if any(a.dtype == jnp.bool_ and a.ndim >= 2 for a in carried):
        violations.append(f"while carry holds a bool chi plane: {carried}")
    return violations


def check_fused_body(body) -> list[str]:
    """Fused engine: no pack/unpack primitives, no bool plane, packed carry."""
    violations = check_carried_state(body)
    used = primitive_names(body) & FUSED_FORBIDDEN
    if used:
        violations.append(f"pack/unpack primitives in fused while body: {sorted(used)}")
    converts = bool_plane_converts(body)
    if converts:
        violations.append(
            f"{len(converts)} convert_element_type(bool) plane(s) in fused while body"
        )
    return violations


def check_edge_body(body) -> list[str]:
    """Edge-list engines (ISSUE 8): packed carry, no per-sweep pack
    (``reduce_sum``), no bool chi/y plane anywhere in the while body."""
    violations = check_carried_state(body)
    used = primitive_names(body) & EDGE_FORBIDDEN
    if used:
        violations.append(
            f"per-sweep pack primitives in edge while body: {sorted(used)}"
        )
    converts = bool_plane_converts(body)
    if converts:
        violations.append(
            f"{len(converts)} convert_element_type(bool) plane(s) in edge while body"
        )
    return violations


def check_packed_engines(seed: int = 3) -> list[str]:
    """Trace every packed engine once; return all invariant violations."""
    from repro.core import dualsim, soi
    from repro.data import synth

    violations: list[str] = []

    db = synth.random_graph(70, 2, 200, seed=seed)  # 70 % 32 != 0: pad bits live
    pat = synth.random_pattern(3, 2, 3, seed=seed)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    ops = dualsim.make_packed_operands(c, db)
    bodies = _while_bodies(lambda o: dualsim.solve_packed_fused(o, impl="interpret"), ops)
    if not bodies:
        violations.append("packed_fused: no while_loop found")
    for body in bodies:
        violations.extend(f"packed_fused: {v}" for v in check_fused_body(body))

    db2 = synth.random_graph(48, 2, 120, seed=seed + 1)
    pat2 = synth.random_pattern(3, 2, 3, seed=seed + 1)
    c2 = soi.compile_soi(dualsim.pattern_graph_soi(pat2), db2)
    ops2 = dualsim.make_sparse_operands(c2, db2)
    cases = [
        ("sparse-gs/words", ops2,
         lambda o: dualsim.solve_sparse(o, mode="gs", impl="words")),
        ("sparse-gs/kernel", ops2,
         lambda o: dualsim.solve_sparse(o, mode="gs", impl="kernel")),
        ("jacobi_packed/words", ops2,
         lambda o: dualsim.solve_sparse(o, mode="jacobi_packed", impl="words")),
        ("jacobi_packed/kernel", ops2,
         lambda o: dualsim.solve_sparse(o, mode="jacobi_packed", impl="kernel")),
        ("partitioned", dualsim.make_partitioned_operands(c2, db2, n_blocks=4),
         dualsim.solve_partitioned),
    ]
    for name, case_ops, solve in cases:
        bodies = _while_bodies(solve, case_ops)
        if not bodies:
            violations.append(f"{name}: no while_loop found")
        for body in bodies:
            violations.extend(f"{name}: {v}" for v in check_edge_body(body))
    return violations


def main() -> int:
    violations = check_packed_engines()
    for v in violations:
        print(f"[reprolint.dynamic] {v}")
    if violations:
        print(f"[reprolint.dynamic] {len(violations)} violation(s)")
        return 1
    print("[reprolint.dynamic] all packed engines trace clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
