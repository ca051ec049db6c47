"""Median latency of all requests due in the window, each from when it was
due: ``latency_p50_ms`` as the client sees it, read per layer in a cell that
does not hold it end to end (a request not answered ``ok`` ranks last)."""


def read(run):
    from stats import percentile

    lat = [r.latency_ms for r in run.recs]
    if not lat:
        return None
    v = percentile(lat, 50)
    return 1e9 if v == float("inf") else v
