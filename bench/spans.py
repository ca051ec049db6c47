"""Reduction of a profiler trace to the program's own spans and scopes.

The program annotates its served path with ``jax.profiler.TraceAnnotation``
spans (``serve.*``, ``engine.*``, ``plan.*``; DESIGN.md 10.7) and names the
fixpoint's device ops with ``jax.named_scope`` (``fixpoint``, ``edge_bits``,
``segor``).  From one trace this module takes, inside the ``bench.window``
span:

* the program's spans: name, interval, thread and arguments, clipped to
  the window (a thread is one line of a host plane);
* each device's busy intervals (the union of its ``XLA Ops``);
* the device time of leaf ops per named-scope path, given each op's path
  (:func:`op_scopes`);

and from those, the values of the per-layer metrics in :data:`METRICS`.

``run.py`` reduces the trace of a traced run here once its window has
closed and hands the :class:`Spans` to the metric readers as
``RunRecord.spans``; ``bench/metrics/<name>.py`` reads ``METRICS[name]``.

``jax.profiler.ProfileData`` does not show an op's named-scope path.  On a
TPU it is the ``tf_op`` stat of the op's event *metadata*
(``jit(_run)/fixpoint/jit(solve_sparse)/while/body/edge_bits/gather:``),
which :func:`op_scopes` reads from the trace file's protobuf wire format.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import re
import sys
from pathlib import Path


def _trace_module():
    """``trace.py``, under the name ``run.py`` gives it (``trace`` is a
    standard module)."""
    mod = sys.modules.get("bench_trace")
    if mod is None:
        path = Path(__file__).with_name("trace.py")
        spec = importlib.util.spec_from_file_location("bench_trace", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_trace"] = mod
        spec.loader.exec_module(mod)
    return mod


_tr = _trace_module()

PROGRAM_SPANS = ("serve.", "engine.", "plan.")
SCOPE_STAT = "tf_op"  # an op's named-scope path, in its event metadata
FIXPOINT_PROGRAM = r"^jit__run$"  # as bench/work/edge_sweep.py


@dataclasses.dataclass
class Spans:
    """The program's spans and the devices' busy time in one window."""

    window: tuple  # (start ns, end ns) of ``bench.window``
    spans: list  # [(name, start ns, end ns, thread, {argument: value})]
    device_busy: list  # per device that ran ops: [[start ns, end ns]]
    scope_s: dict  # named-scope path of leaf ops -> device seconds
    module_s: dict  # XLA program name -> device seconds

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def named(self, name: str) -> list:
        """The program's spans called ``name``."""
        return [s for s in self.spans if s[0] == name]

    def idle_s(self, *, inside: str | None = None,
               outside: str | None = None) -> float:
        """Seconds of the window in which no device ran an op and, with
        ``inside``, some thread was in a span of that name, or, with
        ``outside``, no thread was."""
        idle = subtract([self.window], _tr._union(
            [iv for dev in self.device_busy for iv in dev]))
        if inside is not None:
            idle = subtract(idle, subtract(
                [self.window], _tr._union([s[1:3] for s in self.named(inside)])))
        if outside is not None:
            idle = subtract(idle, _tr._union([s[1:3] for s in self.named(outside)]))
        return sum(b - a for a, b in idle) / 1e9

    def scope_share(self, scope: str) -> float:
        """Device seconds of leaf ops with ``scope`` in their path."""
        return sum(s for path, s in self.scope_s.items()
                   if scope in path.split("/"))


def subtract(intervals, holes) -> list:
    """Sorted disjoint ``intervals`` less the sorted disjoint ``holes``."""
    out = []
    j = 0
    for a, b in intervals:
        while j < len(holes) and holes[j][1] <= a:
            j += 1
        k = j
        while a < b and k < len(holes) and holes[k][0] < b:
            if holes[k][0] > a:
                out.append((a, holes[k][0]))
            a = max(a, holes[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


def reduce(profile, scopes: dict | None = None) -> Spans:
    """Reduce one trace; raises ``ValueError`` without a window span.

    Reads what ``trace.reduce`` reads, and events' ``stats`` where they
    have them.  ``scopes`` maps an op's event name to its named-scope path
    (:func:`op_scopes`); without it ``scope_s`` stays empty.
    """
    program, device_planes = [], []
    for plane in profile.planes:
        if _tr.DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name == _tr.WINDOW_SPAN or e.name.startswith(PROGRAM_SPANS):
                    a = float(e.start_ns)
                    program.append((e.name, a, a + float(e.duration_ns),
                                    f"{plane.name}/{i}",
                                    dict(getattr(e, "stats", ()))))
    window = [s for s in program if s[0] == _tr.WINDOW_SPAN]
    if not window:
        raise ValueError(f"no {_tr.WINDOW_SPAN} span in the trace")
    _, lo, hi, _, _ = min(window, key=lambda s: s[1])
    busy, scope_s, module_s = [], {}, {}
    for plane in device_planes:
        ops = []
        for line in plane.lines:
            evs = [ev for ev in _tr._events(line) if ev[2] > lo and ev[1] < hi]
            if line.name == _tr.MODULES_LINE:
                for name, a, b in evs:
                    key = _tr.program_name(name)
                    module_s[key] = module_s.get(key, 0.0) + (
                        min(b, hi) - max(a, lo)) / 1e9
            if line.name != _tr.OPS_LINE:
                continue
            ops += _tr._clip([(a, b) for _, a, b in evs], lo, hi)
            for name, a, b in _tr._leaves(evs):
                path = (scopes or {}).get(name)
                if path is not None:
                    scope_s[path] = scope_s.get(path, 0.0) + (
                        min(b, hi) - max(a, lo)) / 1e9
        if ops:
            busy.append(_tr._union(ops))
    spans = [(name, *iv, thread, args)
             for name, a, b, thread, args in program if name != _tr.WINDOW_SPAN
             for iv in _tr._clip([(a, b)], lo, hi)]
    return Spans((lo, hi), spans, busy, scope_s, module_s)


# --------------------------------------------------------------------- #
# per-layer metrics over one window's spans; None where nothing to read
# --------------------------------------------------------------------- #
def idle_in_prune_pct(s: Spans):
    """% of the window with no device op running while at least one thread
    is inside ``engine.prune``."""
    if not s.device_busy or not s.named("engine.prune"):
        return None
    return s.idle_s(inside="engine.prune") / s.window_s * 100.0


def idle_unbatched_pct(s: Spans):
    """% of the window with no device op running and no thread inside
    ``serve.attempt``: no batch dispatched (arrivals, flush timer,
    scheduler)."""
    if not s.device_busy or not s.named("serve.attempt"):
        return None
    return s.idle_s(outside="serve.attempt") / s.window_s * 100.0


def plan_host_ms_per_batch(s: Spans):
    """``plan.inputs`` + ``plan.copy_back`` + ``plan.memo`` time per
    ``engine.solve``, in ms."""
    solves = len(s.named("engine.solve"))
    if not solves:
        return None
    ns = sum(x[2] - x[1] for name in ("plan.inputs", "plan.copy_back",
                                      "plan.memo") for x in s.named(name))
    return ns / 1e6 / solves


def plan_transfer_mb_per_batch(s: Spans):
    """``h2d_bytes`` of ``plan.inputs`` + ``d2h_bytes`` of
    ``plan.copy_back`` per ``engine.solve``, in MB (10^6 bytes)."""
    solves = len(s.named("engine.solve"))
    if not solves:
        return None
    nbytes = sum(float(x[4].get("h2d_bytes", 0)) for x in s.named("plan.inputs"))
    nbytes += sum(float(x[4].get("d2h_bytes", 0))
                  for x in s.named("plan.copy_back"))
    return nbytes / 1e6 / solves


def edge_bits_share_pct(s: Spans):
    """Device time of leaf ops under ``edge_bits`` over the device time of
    the ``jit__run`` program, in %; nothing where no op runs under the
    program's ``fixpoint`` scope."""
    t = sum(v for k, v in s.module_s.items() if re.search(FIXPOINT_PROGRAM, k))
    if s.scope_share("fixpoint") <= 0 or t <= 0:
        return None
    return s.scope_share("edge_bits") / t * 100.0


METRICS = {
    "device.idle_in_prune_pct": idle_in_prune_pct,
    "device.idle_unbatched_pct": idle_unbatched_pct,
    "plan.host_ms_per_batch": plan_host_ms_per_batch,
    "plan.transfer_mb_per_batch": plan_transfer_mb_per_batch,
    "fixpoint.edge_bits_share_pct": edge_bits_share_pct,
}


# --------------------------------------------------------------------- #
# named-scope paths from the trace file
# --------------------------------------------------------------------- #
def op_scopes(directory: str) -> dict:
    """Each device op's event name -> its named-scope path, from the
    ``tf_op`` stat of its event metadata in the one ``.xplane.pb`` under
    ``directory``.  An op name to which two programs give different paths
    maps to none.  Empty where the trace holds no such stat (a CPU trace)."""
    import glob

    (path,) = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    with open(path, "rb") as f:
        return scopes_of(memoryview(f.read()))


# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 and stat_metadata
# = 5 (maps: key = 1, value = 2); XEventMetadata: name = 2, stats = 5
# (repeated); XStatMetadata: name = 2; XStat: metadata_id = 1, str_value =
# 5, ref_value = 7 (tsl/profiler/protobuf/xplane.proto)
def scopes_of(space) -> dict:
    """:func:`op_scopes` of a serialized ``XSpace``."""
    out: dict = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for n, v in fields if n == 2), "")
        if not _tr.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for n, entry in fields:
            if n == 5:
                kv = dict(_fields(entry))
                stat_names[kv.get(1, 0)] = _text(
                    dict(_fields(kv.get(2, b""))).get(2, b""))
        scope_id = next((k for k, v in stat_names.items() if v == SCOPE_STAT),
                        None)
        for n, entry in fields:
            if n != 4 or scope_id is None:
                continue
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            op = next((_text(v) for m, v in meta if m == 2), None)
            for m, stat in meta:
                st = dict(_fields(stat)) if m == 5 else {}
                if st.get(1) != scope_id:
                    continue
                scope = _text(st[5]) if 5 in st else stat_names.get(st.get(7))
                out[op] = scope if out.get(op, scope) == scope else None
    return {op: s for op, s in out.items() if s}


def _text(v) -> str:
    return bytes(v).decode()


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: varints
    as ints, length-delimited fields as views, fixed-width ones as None."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v
