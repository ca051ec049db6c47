"""Device time of the ops under the fixpoint's ``copies`` scope (the copy
inequalities of OPTIONAL) over the device time of its ``jit__run`` program,
in %, read as ``fixpoint.edge_bits_share_pct`` is read; nothing where no op
runs under that scope."""
import re


def read(run):
    from spans import FIXPOINT_PROGRAM

    s = run.spans
    if s is None or s.scope_share("copies") <= 0:
        return None
    t = sum(v for k, v in s.module_s.items() if re.search(FIXPOINT_PROGRAM, k))
    return s.scope_share("copies") / t * 100.0 if t > 0 else None
