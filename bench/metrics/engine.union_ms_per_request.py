"""Host time of a UNION request's parts and their merge: the summed
duration of the program's ``engine.union`` spans over their count, in ms."""


def read(run):
    spans = [] if run.spans is None else run.spans.named("engine.union")
    if not spans:
        return None
    return sum(b - a for _, a, b, _, _ in spans) / 1e6 / len(spans)
