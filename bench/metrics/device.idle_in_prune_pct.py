"""% of the traced window in which no device op ran while some thread was
inside the program's ``engine.prune`` span (``spans.idle_in_prune_pct``)."""


def read(run):
    from spans import METRICS

    return None if run.spans is None else METRICS["device.idle_in_prune_pct"](run.spans)
