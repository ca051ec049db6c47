"""Host prune time per microbatch: ``stage_seconds["prune"]`` over
microbatches, counted over the window."""


def read(run):
    mb = run.engine["microbatches"]
    s = run.engine["stage_seconds"].get("prune")
    return s / mb * 1e3 if mb and s is not None else None
