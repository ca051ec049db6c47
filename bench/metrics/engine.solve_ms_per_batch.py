"""Solve time per microbatch (constant rows, upload, fixpoint, copy back):
``stage_seconds["solve"]`` over microbatches, counted over the window."""


def read(run):
    mb = run.engine["microbatches"]
    s = run.engine["stage_seconds"].get("solve")
    return s / mb * 1e3 if mb and s is not None else None
