"""Bytes uploaded (``plan.inputs``' ``h2d_bytes``) and copied back
(``plan.copy_back``'s ``d2h_bytes``) per ``engine.solve``, in MB
(``spans.plan_transfer_mb_per_batch``)."""


def read(run):
    from spans import METRICS

    return None if run.spans is None else METRICS["plan.transfer_mb_per_batch"](run.spans)
