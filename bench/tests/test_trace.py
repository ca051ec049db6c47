"""The trace reduction on a small synthetic trace, counted by hand."""
from types import SimpleNamespace as NS

import pytest

import run

reduce = run.trace_mod.reduce


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1000.0, duration_ns=dur_us * 1000.0)


def line(name, *events):
    return NS(name=name, events=list(events))


def profile(device_planes, host_events):
    host = NS(name="/host:CPU", lines=[line("python", *host_events)])
    return NS(planes=[host, *device_planes])


def test_busy_idle_modules_and_gaps():
    tpu = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", ev("jit__run(3)", 100, 300), ev("jit_other", 600, 100)),
        # overlapping ops union to [100, 250] + [300, 400] + [600, 700];
        # the op at [950, 1100] is clipped to the window's end at 1000
        line("XLA Ops",
             ev("%fusion.1 = u32[4] fusion(...)", 100, 100),
             ev("%fusion.1 = u32[4] fusion(...)", 150, 100),
             ev("%while.2 = (...) while(...)", 300, 100),
             ev("%copy.3 = u32[4] copy(...)", 600, 100),
             ev("%copy.3 = u32[4] copy(...)", 950, 150)),
    ])
    trace = profile([tpu], [
        ev("bench.window", 0, 1000),
        ev("bench.batch", 400, 300),
        ev("bench.prune", 450, 100),  # innermost at the gap [400, 600]
    ])
    s = reduce(trace)
    assert s.window_s == pytest.approx(1e-3)
    busy_us = 150 + 100 + 100 + 50
    assert s.busy_s == pytest.approx(busy_us * 1e-6)
    assert s.n_devices == 1
    assert s.module_s["jit__run"] == pytest.approx(300e-6)
    assert s.program_s(r"^jit__run$") == pytest.approx(300e-6)
    assert dict(s.device_ops)["%fusion.1"] == pytest.approx(200e-6)
    assert dict(s.device_ops)["%copy.3"] == pytest.approx(150e-6)
    # gaps: [0,100] [250,300] [400,600] [700,950]; longest first
    assert [round(g[1] * 1e6) for g in s.idle_gaps] == [250, 200, 100, 50]
    assert [g[0] for g in s.idle_gaps] == [
        "host idle", "bench.prune", "host idle", "host idle"]


def test_enclosing_ops_are_not_counted_twice():
    tpu = NS(name="/device:TPU:0", lines=[line("XLA Ops",
        ev("%while.1 = (...) while(...)", 0, 500),
        ev("%segor_blocks.2 = s32[8] custom-call(...)", 10, 200),
        ev("%fusion.3 = u32[8] fusion(...)", 300, 100))])
    s = reduce(profile([tpu], [ev("bench.window", 0, 1000)]))
    assert s.busy_s == pytest.approx(500e-6)
    assert dict(s.device_ops) == pytest.approx(
        {"%segor_blocks.2": 200e-6, "%fusion.3": 100e-6})


def test_busy_averages_over_devices_that_ran():
    a = NS(name="/device:TPU:0", lines=[line("XLA Ops", ev("%a = x", 0, 400))])
    b = NS(name="/device:TPU:1", lines=[line("XLA Ops", ev("%a = x", 0, 200))])
    idle = NS(name="/device:TPU:2", lines=[line("XLA Ops")])
    s = reduce(profile([a, b, idle], [ev("bench.window", 0, 1000)]))
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(300e-6)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        reduce(profile([], [ev("bench.prune", 0, 10)]))
