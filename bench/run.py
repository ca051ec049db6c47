#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload lubm.broad_open --seed 7 --seconds 45 --trace 0

From the root of a checkout.  The cell (``BENCHMARK.json``) names a
configuration (``bench/configs/<name>.json``: the deployment, its scale,
its generator ``bench/generators/<name>.py`` and server settings) and a
traffic mix (``bench/traffic/<name>.json``, whose arrival process is
``bench/arrivals/<name>.py``).  The run
builds the graph from the seed, starts ``AsyncServer`` over a ``GraphDB``
with the configuration's settings, warms every (template, bucket) plan the
mix uses on every replica, then drives the mix for ``--seconds`` and times
every request on the client's side from when it was due.  Once the window
has closed and every request has resolved, a sample of the answers is
checked against the benchmark's own reference (``check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``,
``breakdown``), then ``checks``, each compared number beside its limit.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window is traced and the metrics are the cell's per-layer
metrics, each read by ``bench/metrics/<name>.py``.  The run fails, and
prints no result, where JAX finds no TPU or fewer chips than the cell asks.

``--control`` answers the sampled requests with the reference stopped one
round short of its fixpoint, in the program's place: such a run must come
out not correct.  It logs the program's own checks of the same window
first, on standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import check  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402
import spans as spans_mod  # noqa: E402
import traffic  # noqa: E402
from stats import percentile  # noqa: E402
from work import edge_sweep  # noqa: E402

DRAIN_S = 60.0  # a request may finish this long after the window closes


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _load_module(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


trace_mod = _load_module("bench_trace", BENCH / "trace.py")


# --------------------------------------------------------------------- #
# finding the pieces of a cell by name
# --------------------------------------------------------------------- #
def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(bm: dict, name: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """(workload entry, configuration, traffic mix) of cell ``name``."""
    wl = next((w for w in bm["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bm["configs"] if c["name"] == wl["config"])
    cfg = json.loads((root / entry["file"]).read_text())
    mix_file = root / BENCH.name / "traffic" / f"{wl['traffic']}.json"
    return wl, cfg, json.loads(mix_file.read_text())


def cell_metrics(bm: dict, wl: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bm[kind]
            if "workloads" not in m or wl["name"] in m["workloads"]]


@functools.cache
def reader(metric: str, root: Path = ROOT):
    """``bench/metrics/<metric>.py``'s ``read(run)``."""
    path = root / BENCH.name / "metrics" / f"{metric}.py"
    return _load_module(f"bench_metric_{metric}", path).read


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


# --------------------------------------------------------------------- #
# one request, as the client sees it
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class Rec:
    req: traffic.Request
    due: float  # monotonic seconds
    done: float | None = None
    outcome: str | None = None
    result: object = None
    queue_ms: float = 0.0

    @property
    def ok(self) -> bool:
        return self.outcome == "ok"

    @property
    def latency_ms(self) -> float:
        """From due to answer; a request not answered ``ok`` ranks last."""
        return (self.done - self.due) * 1e3 if self.ok else math.inf


def _resolved(rec: Rec, fut) -> None:
    rec.done = time.monotonic()
    res = fut.result()
    rec.outcome, rec.queue_ms = res.outcome, res.queue_ms
    rec.result = res.result


@dataclasses.dataclass
class RunRecord:
    """Everything the metric readers read, taken over the measured window."""

    recs: list
    seconds: float
    t_open: float  # monotonic window start
    engine: dict  # summed per-replica counter deltas over the window
    solves: list  # [{"key", "batch", "sweeps", "bytes"}] (traced runs)
    trace: object  # trace.Summary or None
    peaks: dict
    spans: object = None  # the program's spans, spans.Spans (traced runs)

    def answered_in_window(self) -> list:
        close = self.t_open + self.seconds
        return [r for r in self.recs if r.ok and r.done <= close]


# --------------------------------------------------------------------- #
# the system under test
# --------------------------------------------------------------------- #
def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[0]


def enable_compile_cache() -> str:
    """JAX's persistent cache in ``<checkout>/.jax_cache``, every program
    in it however fast it compiled, unless ``JAX_COMPILATION_CACHE_DIR``
    names another."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Backend compilations (cache loads excluded) and their seconds."""

    def __init__(self):
        import jax.monitoring

        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def build_db(cfg: dict, ds: data.Dataset):
    from repro.core.graph import Graph
    from repro.db import GraphDB

    # the program gets its own copy: the reference reads ``ds.triples``
    g = Graph(n_nodes=ds.n_nodes, n_labels=len(ds.label_names),
              triples=ds.triples.copy(), node_names=list(ds.node_names),
              label_names=list(ds.label_names))
    return GraphDB(g, engine=cfg["engine"], buckets=tuple(cfg["server"]["buckets"]))


def make_server(db, cfg: dict):
    from repro.serve import AsyncServer

    s = cfg["server"]
    return AsyncServer(
        db, replicas=s["replicas"], max_batch=s["max_batch"],
        max_delay_ms=s["max_delay_ms"], default_deadline_ms=s["deadline_ms"],
        max_queue=s["max_queue"], watchdog_budget_ms=s["watchdog_budget_ms"],
    )


def warm_texts(mix: dict, ds: data.Dataset, server_cfg: dict) -> list[list[str]]:
    """One batch of texts per (template, bucket) the mix can reach.

    A template with slots reaches every bucket up to ``max_batch`` (a batch
    holds as many distinct constant tuples); one without, or with UNION,
    always solves alone (bucket 1).
    """
    out = []
    for t in mix["templates"]:
        slots = t.get("slots", {})
        buckets = [b for b in server_cfg["buckets"] if b <= server_cfg["max_batch"]]
        if not slots or "UNION" in t["text"]:
            buckets = [1]
        for b in buckets:
            texts = []
            for j in range(b):
                fill = {k: ds.node_names[ds.kinds[v["kind"]][j]]
                        for k, v in slots.items()}
                texts.append(traffic.fill(t["text"], fill))
            out.append(texts)
    return out


def warm_up(server, batches) -> None:
    """Every batch once on every replica: its plan built, its programs
    compiled or loaded from the cache, and run."""
    for rep in server.router.replicas:
        eng = rep.engine
        for texts in batches:
            res = eng.execute_prepared([eng.prepare(t) for t in texts])
            if len(res) != len(texts):
                raise RuntimeError(f"warm-up of {texts[0]!r} lost answers")


@contextlib.contextmanager
def plan_build_clock():
    """Seconds of every plan built inside the block (host SOI build,
    operand layout and upload), as a list that fills as plans are built."""
    from repro.engine import plan

    orig = plan.CompiledPlan.__init__
    seconds: list = []

    def timed(self, *a, **kw):
        t = time.monotonic()
        orig(self, *a, **kw)
        seconds.append(time.monotonic() - t)

    plan.CompiledPlan.__init__ = timed
    try:
        yield seconds
    finally:
        plan.CompiledPlan.__init__ = orig


def engine_totals(server) -> dict:
    out = {"requests": 0, "microbatches": 0, "warm_resume_solves": 0,
           "stage_seconds": {}, "engines": {}, "replica_microbatches": []}
    for m in server.router.stats():
        out["replica_microbatches"].append(m.microbatches)
        for k, v in m.engine_counts.items():
            out["engines"][k] = out["engines"].get(k, 0) + v
        out["requests"] += m.requests
        out["microbatches"] += m.microbatches
        out["warm_resume_solves"] += m.warm_resume_solves
        for k, v in m.stage_seconds.items():
            out["stage_seconds"][k] = out["stage_seconds"].get(k, 0.0) + v
    return out


def engine_delta(a: dict, b: dict) -> dict:
    out = {k: b[k] - a[k] for k in ("requests", "microbatches",
                                    "warm_resume_solves")}
    for key in ("stage_seconds", "engines"):
        out[key] = {k: v - a[key].get(k, 0) for k, v in b[key].items()}
    out["replica_microbatches"] = [
        y - x for x, y in zip(a["replica_microbatches"], b["replica_microbatches"])]
    return out


# --------------------------------------------------------------------- #
# spans around the engine layers (traced runs only)
# --------------------------------------------------------------------- #
class Spans:
    """``bench.<layer>`` host spans in the profiler's trace, around the
    program's calls into each layer, and a record of every solve."""

    def __init__(self, soi_of_key: dict, ds: data.Dataset):
        self.solves: list = []
        self._soi = soi_of_key
        self._edges = np.bincount(ds.triples[:, 1], minlength=len(ds.label_names))
        self._label = {n: i for i, n in enumerate(ds.label_names)}
        self._n = ds.n_nodes
        self._undo: list = []

    def _wrap(self, owner, attr: str, span: str, after=None) -> None:
        import jax

        orig = getattr(owner, attr)

        @functools.wraps(orig)
        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(span):
                out = orig(*a, **kw)
            if after is not None:
                after(a, out)
            return out

        setattr(owner, attr, wrapped)
        self._undo.append((owner, attr, orig))

    def _solved(self, args, out) -> None:
        plan, (_, sweeps) = args[0], out
        soi = self._soi.get(plan.template.key)
        nbytes = None
        if soi is not None:
            ops = {(a, d) for _, a, _ in soi.edges if a in self._label
                   for d in (0, 1)}
            nbytes = edge_sweep.solve_bytes(
                [int(self._edges[self._label[a]]) for a, _ in ops],
                plan.batch * len(soi.names), self._n, int(sweeps))
        self.solves.append({"key": plan.template.key, "batch": plan.batch,
                            "sweeps": int(sweeps), "bytes": nbytes})

    def install(self) -> None:
        from repro.core import pruning
        from repro.engine import engine, plan

        self._wrap(engine.Engine, "execute_prepared", "bench.batch")
        self._wrap(plan.CompiledPlan, "execute", "bench.solve", self._solved)
        self._wrap(pruning, "prune_triples", "bench.prune")

    def remove(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo.clear()


def soi_by_template_key(server, mix: dict) -> dict:
    """Program template key -> the reference's SOI of that query part."""
    from repro.core import sparql
    from repro.engine.template import canonicalize

    eng = server.router.replicas[0].engine
    out = {}
    for t in mix["templates"]:
        text = traffic.fill(t["text"], {k: "x" for k in t.get("slots", {})})
        parts = sparql.union_split(eng.prepare(text)[0])
        for part, mine in zip(parts, reference.union_free_parts(
                reference.parse(text))):
            out[canonicalize(part).template.key] = reference.build_soi(mine)
    return out


# --------------------------------------------------------------------- #
# the window
# --------------------------------------------------------------------- #
async def window(server, mix, cfg, reqs, seconds: float):
    """Drive the mix; returns (records, t_open, unresolved count)."""
    recs: list = []
    futs: list = []

    def send(r: traffic.Request, due: float):
        rec = Rec(r, due)
        fut = server.submit(r.text, tenant=r.tenant)
        fut.add_done_callback(functools.partial(_resolved, rec))
        recs.append(rec)
        futs.append(fut)
        return fut

    t_open = time.monotonic()
    t_close = t_open + seconds
    await traffic.arrivals(mix["arrivals"]).drive(
        send, reqs, t_open, t_close, mix, cfg["server"])
    wait = t_close + DRAIN_S - time.monotonic()
    pending = [f for f in futs if not f.done()]
    if pending:
        await asyncio.wait(pending, timeout=max(wait, 0.0))
    return recs, t_open, sum(not f.done() for f in futs)


# --------------------------------------------------------------------- #
def log(msg: str) -> None:
    print(f"[{time.monotonic() - T_PROCESS:7.1f} s] {msg}", file=sys.stderr,
          flush=True)


@dataclasses.dataclass
class Cell:
    """A cell made ready to serve: its graph built, its server warm."""

    bm: dict
    wl: dict
    cfg: dict
    mix: dict
    dev: object
    ds: data.Dataset
    db: object
    server: object
    compiles: CompileCounter
    split: dict  # set-up seconds by part


def prepare(workload: str, seed: int, *, require_chip: bool = True,
            config_override: dict | None = None,
            mix_override: dict | None = None) -> Cell:
    """Build the cell's graph from the seed, start nothing yet, warm every
    (template, bucket) plan on every replica.

    ``config_override`` / ``mix_override`` replace top-level keys of the
    configuration and the mix: the tests shrink the scale with them, to the
    configuration's own ``tiny``.  A chip run passes neither.
    """
    bm = benchmark()
    wl, cfg, mix = cell(bm, workload)
    cfg = {**cfg, **(config_override or {})}
    mix = {**mix, **(mix_override or {})}
    import jax

    dev = require_chips(wl["chips"]) if require_chip else jax.devices()[0]
    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    log(f"{dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache_dir}")

    split = {}
    t = time.monotonic()
    ds = data.generate(cfg, seed)
    db = build_db(cfg, ds)
    split["graph_build_s"] = time.monotonic() - t
    log(f"graph: {len(ds.triples)} triples, {ds.n_nodes} nodes")
    server = make_server(db, cfg)
    t = time.monotonic()
    c0 = (compiles.n, compiles.seconds)
    with plan_build_clock() as builds:
        warm_up(server, warm_texts(mix, ds, cfg["server"]))
    warm_s = time.monotonic() - t
    split["plan_build_s"] = sum(builds)
    split["compile_s"] = compiles.seconds - c0[1]
    split["compiles"] = compiles.n - c0[0]
    split["warm_runs_s"] = warm_s - split["plan_build_s"] - split["compile_s"]
    return Cell(bm, wl, cfg, mix, dev, ds, db, server, compiles, split)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        control: bool = False, **prepare_kw) -> dict:
    """One run of one cell; returns the result object (not yet printed)."""
    import jax

    c = prepare(workload, seed, **prepare_kw)
    bm, wl, cfg, mix, dev, ds = c.bm, c.wl, c.cfg, c.mix, c.dev, c.ds
    server, compiles, split = c.server, c.compiles, c.split
    require_chip = prepare_kw.get("require_chip", True)

    reqs = traffic.requests(mix, ds, seed, traffic.count(mix, seconds))
    wrappers = Spans(soi_by_template_key(server, mix), ds) if trace else None

    async def serve():
        async with server:
            tmp = None
            if trace:
                tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
                wrappers.install()
                jax.profiler.start_trace(tmp.name)
            before = engine_totals(server)
            n_compiles = compiles.n
            t_open = time.monotonic()
            split["setup_s"] = t_open - T_PROCESS
            log("setup " + json.dumps({k: round(v, 3) for k, v in split.items()}))
            span = (jax.profiler.TraceAnnotation("bench.window") if trace
                    else contextlib.nullcontext())
            with span:
                out = await window(server, mix, cfg, reqs, seconds)
            summary = program = None
            if trace:
                jax.profiler.stop_trace()
                wrappers.remove()
                t = time.monotonic()
                profile = trace_mod.load(tmp.name)
                summary = trace_mod.reduce(profile)
                program = spans_mod.reduce(profile, spans_mod.op_scopes(tmp.name))
                tmp.cleanup()
                log(f"trace read in {time.monotonic() - t:.1f} s")
            log(f"compilations inside the window: {compiles.n - n_compiles}")
            return (out, engine_delta(before, engine_totals(server)), summary,
                    program)

    (recs, t_open, unresolved), delta, summary, program = asyncio.run(serve())
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    # the reference runs on the host, after the server and its state are gone
    del server, c
    graph = reference.RefGraph(ds.triples, ds.n_nodes, ds.label_names,
                               ds.node_index())
    t = time.monotonic()
    checks = check.compare(recs, graph, seed, unresolved=unresolved)
    log(f"reference check {time.monotonic() - t:.1f} s")
    if control:
        # the program's own reading of this window, then the control's
        for name, ch in checks.items():
            log(f"program check {name}: {ch['value']} (limit {ch['op']} "
                f"{ch['limit']})")
        checks = check.compare(recs, graph, seed, control=True,
                               unresolved=unresolved)
    record = RunRecord(recs, seconds, t_open, delta,
                       wrappers.solves if wrappers else [], summary,
                       peaks(dev.device_kind) if require_chip else
                       {"hbm_bytes_per_s": 819e9}, program)
    late = [r for r in recs if r.done is not None]
    log(f"{len(recs)} requests, {sum(r.ok for r in recs)} ok, "
        f"{len(late)} resolved; engine {json.dumps(delta)}")

    metrics = {}
    if trace:
        for m in cell_metrics(bm, wl, "per_layer"):
            v = reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(record, split["setup_s"])
        for m in cell_metrics(bm, wl, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {
        "correct": check.passed(checks),
        "attempted": len(recs),
        "failed": sum(not r.ok for r in recs),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    return result


def end_to_end(record: RunRecord, setup_s: float) -> dict:
    lat = [r.latency_ms for r in record.recs]
    out = {
        "goodput_qps": len(record.answered_in_window()) / record.seconds,
        "latency_p50_ms": percentile(lat, 50),
        "latency_p95_ms": percentile(lat, 95),
        "setup_s": setup_s,
    }
    return {k: (1e9 if math.isinf(v) else v) for k, v in out.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     control=args.control)
    except NoChip as exc:
        print(f"bench/run.py: {exc}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['op']} {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
