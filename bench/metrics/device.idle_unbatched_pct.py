"""% of the traced window in which no device op ran and no thread was inside
the program's ``serve.attempt`` span: no batch dispatched
(``spans.idle_unbatched_pct``)."""


def read(run):
    from spans import METRICS

    return None if run.spans is None else METRICS["device.idle_unbatched_pct"](run.spans)
