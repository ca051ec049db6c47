"""Device time of the ops under the fixpoint's ``edge_bits`` scope over the
device time of its ``jit__run`` program, in % (``spans.edge_bits_share_pct``)."""


def read(run):
    from spans import METRICS

    return None if run.spans is None else METRICS["fixpoint.edge_bits_share_pct"](run.spans)
