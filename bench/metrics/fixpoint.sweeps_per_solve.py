"""Mean sweeps of the fixpoint solves in the window, as each solve returned
them (the count ``ExecResult.sweeps`` carries)."""


def read(run):
    s = [x["sweeps"] for x in run.solves]
    return sum(s) / len(s) if s else None
