"""95th percentile of admission-to-dispatch wait (``ServeResult.queue_ms``),
raw samples of the requests answered in the window."""


def read(run):
    from stats import percentile

    q = [r.queue_ms for r in run.answered_in_window()]
    return percentile(q, 95) if q else None
