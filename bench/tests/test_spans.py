"""The reduction of the program's own spans and scopes (``spans.py``) on a
small synthetic trace, each metric counted by hand."""
from types import SimpleNamespace as NS

import pytest

import run
import spans

EDGE = "jit(_run)/fixpoint/jit(solve_sparse)/while/body/edge_bits/gather:"
SEGOR = "jit(_run)/fixpoint/jit(solve_sparse)/while/body/segor/pallas_call:"


def ev(name, start_us, dur_us, **stats):
    e = NS(name=name, start_ns=start_us * 1000.0, duration_ns=dur_us * 1000.0)
    if stats:
        e.stats = list(stats.items())
    return e


def line(name, *events):
    return NS(name=name, events=list(events))


def trace():
    # device busy [100, 300] + [600, 700]: idle [0,100] [300,600] [700,1000]
    tpu = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", ev("jit__run(7)", 100, 200), ev("jit_other", 600, 100)),
        line("XLA Ops",
             ev("%fusion.1 = gather", 100, 100),
             ev("%segor_blocks.2 = custom-call", 200, 100),
             ev("%fusion.3 = add", 600, 100)),
    ])
    host = NS(name="/host:CPU", lines=[
        line("python", ev("bench.window", 0, 1000),
             ev("serve.route", 40, 0, rids="1 2", replica="r0", scores="r0:1 r1:2")),
        # thread 1: one attempt [50, 500], its prune [300, 450]
        line("python",
             ev("serve.attempt", 50, 450, rids="1 2", replica="r0", attempt=1),
             ev("engine.solve", 90, 220, bucket=2),
             ev("plan.inputs", 90, 10, h2d_bytes=1000),
             ev("plan.copy_back", 290, 10, d2h_bytes=4000, sweeps=2),
             ev("plan.memo", 300, 10, bytes=500),
             ev("engine.prune", 300, 150, triples=10, survivors=3)),
        # thread 2: an attempt [380, 900], a prune [400, 650] overlapping
        # thread 1's; its memo runs past the window's end and is clipped
        line("python",
             ev("serve.attempt", 380, 520, rids=3, replica="r1", attempt=1),
             ev("engine.prune", 400, 250),
             ev("engine.solve", 660, 345),
             ev("plan.inputs", 660, 5, h2d_bytes=500),
             ev("plan.copy_back", 690, 5, d2h_bytes=2000),
             ev("plan.memo", 995, 15)),
    ])
    return NS(planes=[host, tpu])


def reduced():
    return spans.reduce(trace(), {"%fusion.1 = gather": EDGE,
                                  "%segor_blocks.2 = custom-call": SEGOR})


def test_spans_threads_arguments_and_clipping():
    s = reduced()
    assert s.window_s == pytest.approx(1e-3)
    (route,) = s.named("serve.route")
    assert route[4] == {"rids": "1 2", "replica": "r0", "scores": "r0:1 r1:2"}
    a1, a2 = s.named("serve.attempt")
    assert a1[3] != a2[3] != route[3]
    assert a1[4]["rids"] == "1 2" and a2[4]["rids"] == 3
    (_, memo) = s.named("plan.memo")
    assert (memo[1], memo[2]) == (995e3, 1000e3)  # clipped to the window
    assert s.named("engine.prune")[1][4] == {}  # an event without stats
    assert s.device_busy == [[[100e3, 300e3], [600e3, 700e3]]]
    assert s.module_s["jit__run"] == pytest.approx(200e-6)


BY_HAND = [
    # idle [300, 600] lies under the prune union [300, 650]
    ("device.idle_in_prune_pct", 30.0),
    # attempts cover [50, 900]: idle [0, 50] + [900, 1000]
    ("device.idle_unbatched_pct", 15.0),
    # inputs 10 + 5, copy back 10 + 5, memo 10 + 5 (clipped), over 2 solves
    ("plan.host_ms_per_batch", 45e-3 / 2),
    # (1000 + 500 + 4000 + 2000) bytes over 2 solves
    ("plan.transfer_mb_per_batch", 7500 / 2 / 1e6),
    # the edge_bits op's 100 us of jit__run's 200 us
    ("fixpoint.edge_bits_share_pct", 50.0),
]


@pytest.mark.parametrize("metric,value", BY_HAND)
def test_metric_by_hand(metric, value):
    assert spans.METRICS[metric](reduced()) == pytest.approx(value)


@pytest.mark.parametrize("metric,value", BY_HAND)
def test_reader_hands_the_run_spans_to_the_metric(metric, value):
    s = reduced()
    read = run.reader(metric)
    assert read(NS(spans=s)) == spans.METRICS[metric](s) == pytest.approx(value)
    assert read(NS(spans=None)) is None  # an untraced run


def test_idle_shares_fit_inside_device_idle():
    s = reduced()
    idle = s.idle_s() / s.window_s * 100.0
    assert idle == pytest.approx(70.0)
    assert (spans.METRICS["device.idle_in_prune_pct"](s)
            + spans.METRICS["device.idle_unbatched_pct"](s)) <= idle


def test_a_program_without_spans_or_scopes_reads_nothing():
    t = trace()
    t.planes[0].lines = t.planes[0].lines[:1]
    s = spans.reduce(t, {"%fusion.1 = gather": "jit(_run)/while/body/gather:"})
    assert all(f(s) is None for f in spans.METRICS.values())


def test_no_window_span_is_an_error():
    t = trace()
    t.planes[0].lines = t.planes[0].lines[1:]
    with pytest.raises(ValueError):
        spans.reduce(t)


# --------------------------------------------------------------------- #
# op scopes from the protobuf wire format
# --------------------------------------------------------------------- #
def varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num, value):
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def plane(name, stat_names, ops):
    """XPlane bytes: ``stat_names`` {id: name}; ``ops`` [(id, name, stats)]
    with stats [(metadata id, value or ("ref", id))]."""
    out = field(1, 5) + field(2, name)
    for k, (mid, op, stats) in enumerate(ops):
        body = field(1, mid) + field(2, op)
        for sid, v in stats:
            st = field(1, sid)
            st += field(7, v[1]) if isinstance(v, tuple) else field(5, v)
            body += field(5, st)
        out += field(4, field(1, mid) + field(2, body))
    for sid, nm in stat_names.items():
        out += field(5, field(1, sid) + field(2, field(1, sid) + field(2, nm)))
    out += field(3, field(2, "XLA Ops"))  # a line, skipped
    return out


def test_scopes_of_reads_tf_op_strings_and_refs():
    names = {1: "tf_op", 2: "flops", 3: "a/segor/x:"}
    dev = plane("/device:TPU:0", names, [
        (10, "%fusion.1 = gather", [(2, "5"), (1, EDGE)]),
        (11, "%segor_blocks.2 = custom-call", [(1, ("ref", 3))]),
        (12, "%copy-done = copy-done", [(2, "0")]),  # no path
        (13, "%fusion.4 = add", [(1, "p/one:")]),
        (14, "%fusion.4 = add", [(1, "p/two:")]),  # two paths: dropped
    ])
    host = plane("/host:CPU", names, [(10, "engine.solve", [(1, "host/x:")])])
    space = field(1, host) + field(1, dev) + field(4, "hostname")
    assert spans.scopes_of(memoryview(space)) == {
        "%fusion.1 = gather": EDGE, "%segor_blocks.2 = custom-call": "a/segor/x:"}
