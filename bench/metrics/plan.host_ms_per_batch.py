"""Host time of the program's ``plan.inputs``, ``plan.copy_back`` and
``plan.memo`` spans per ``engine.solve``, in ms
(``spans.plan_host_ms_per_batch``)."""


def read(run):
    from spans import METRICS

    return None if run.spans is None else METRICS["plan.host_ms_per_batch"](run.spans)
