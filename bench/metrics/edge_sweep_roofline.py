"""Share of the HBM roofline reached by the fixpoint programs' edge sweeps.

The bytes the algorithm needs for every operator application of every
solve in the window (``work/edge_sweep.py``), over the peak HBM bandwidth
times the device time of the fixpoint's XLA program in the trace.
"""


def read(run):
    from work.edge_sweep import FIXPOINT_PROGRAM

    if run.trace is None:
        return None
    t = run.trace.program_s(FIXPOINT_PROGRAM)
    nbytes = [x["bytes"] for x in run.solves if x["bytes"] is not None]
    if t <= 0 or not nbytes:
        return None
    return sum(nbytes) / (run.peaks["hbm_bytes_per_s"] * t) * 100.0
