"""Reduction of a profiler trace to device busy time, program time and gaps.

The run writes the trace with ``jax.profiler`` and brackets its measured
window with the host span ``bench.window``; the engine layers it calls into
are bracketed by further ``bench.<layer>`` spans (``run.py``).  From the
trace this module takes:

* the window: the first ``bench.window`` span on a host plane;
* device busy time: the union of the op intervals on each device plane's
  ``XLA Ops`` line, clipped to the window, averaged over the device planes
  that ran anything;
* the ops that took most device time, counting only ops that enclose no
  other (a ``while`` op's interval holds its body's ops);
* each XLA program's device time: its events on the ``XLA Modules`` line;
* the longest idle gaps between busy intervals, each named by the innermost
  ``bench.*`` host span that covers its midpoint (``host idle`` where none
  does).

It reads any object shaped like ``jax.profiler.ProfileData``: planes with a
``name`` and ``lines``, lines with a ``name`` and ``events``, events with a
``name``, ``start_ns`` and ``duration_ns``.
"""
from __future__ import annotations

import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


@dataclasses.dataclass
class Summary:
    """What the per-layer readers and the result line take from a trace."""

    window_s: float
    busy_s: float  # mean over the device planes that ran ops
    n_devices: int
    device_ops: list  # [(op name, seconds)], most time first
    idle_gaps: list  # [(host span name, seconds)], longest first
    module_s: dict  # XLA program name -> device seconds in the window

    def program_s(self, pattern: str) -> float:
        """Device seconds of the programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.module_s.items() if rx.search(name))


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns)


def _leaves(events):
    """Events on one line that enclose no other (a ``while`` op's interval
    holds its body's ops); ties broken by order of start."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, a, b) in enumerate(evs):
        if not (i + 1 < len(evs) and evs[i + 1][2] <= b):
            out.append((name, a, b))
    return out


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def op_name(name: str) -> str:
    """An op's short name: ``%fusion.12 = u32[...] fusion(...)`` is
    ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def program_name(name: str) -> str:
    """``jit_f(12)`` and ``jit_f`` are one program."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(profile) -> Summary:
    """Reduce one trace; raises ``ValueError`` without a window span."""
    spans = []
    device_planes = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            spans += [s for s in _events(line) if s[0].startswith(SPAN_PREFIX)]
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if not window:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    _, lo, hi = min(window, key=lambda s: s[1])
    busy_total, n_dev = 0.0, 0
    op_time: dict = {}
    module_s: dict = {}
    gaps = []
    for plane in device_planes:
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs = [e for e in _events(line) if e[2] > lo and e[1] < hi]
                ops += [(max(a, lo), min(b, hi)) for _, a, b in evs]
                for name, a, b in _leaves(evs):
                    key = op_name(name)
                    op_time[key] = op_time.get(key, 0.0) + (
                        min(b, hi) - max(a, lo)) / 1e9
            elif line.name == MODULES_LINE:
                for name, a, b in _events(line):
                    for a2, b2 in _clip([(a, b)], lo, hi):
                        key = program_name(name)
                        module_s[key] = module_s.get(key, 0.0) + (b2 - a2) / 1e9
        if not ops:
            continue
        n_dev += 1
        busy = _union(ops)
        busy_total += sum(b - a for a, b in busy) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (a + b) / 2
        cover = [s for s in spans if s[0] != WINDOW_SPAN and s[1] <= mid <= s[2]]
        label = (min(cover, key=lambda s: s[2] - s[1])[0] if cover
                 else "host idle")
        named.append([label, (b - a) / 1e9])
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / n_dev if n_dev else 0.0,
        n_devices=n_dev,
        device_ops=[[k, v] for k, v in ops_top],
        idle_gaps=named,
        module_s=module_s,
    )


def load(directory: str):
    """The ``ProfileData`` of the one ``.xplane.pb`` under ``directory``."""
    import glob

    from jax.profiler import ProfileData

    paths = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace under {directory}, found {paths}")
    return ProfileData.from_file(paths[0])
