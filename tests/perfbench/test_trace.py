"""The reduction from a trace to numbers, on a small recorded trace and on
intervals made by hand."""

import json
import os

import numpy as np
import pytest

from perfbench import trace
from perfbench.trace import Line

HERE = os.path.dirname(os.path.abspath(__file__))


def line(*events):
    """``line(("name", start, dur), ...)``"""
    return Line([e[0] for e in events],
                np.array([e[1] for e in events], float),
                np.array([e[2] for e in events], float))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        fix = json.load(f)
    return {(l["plane"], l["line"]): Line(
        [fix["names"][i] for i in l["name_index"]],
        np.array(l["start_ns"], float), np.array(l["dur_ns"], float))
        for l in fix["lines"]}


def raster_busy_ns(l: Line, t0, t1, step=100.0):
    """Busy time by brute force: a grid of 0.1 us cells."""
    cells = np.zeros(int((t1 - t0) / step) + 1, bool)
    for s, d in zip(l.start, l.dur):
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            cells[int((a - t0) / step):int(np.ceil((b - t0) / step))] = True
    return cells.sum() * step


def test_union_of_overlapping_intervals():
    l = line(("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2),
             ("e", 50, 1))
    assert trace.union_ns(l) == 15 + 5 + 1
    assert trace.union_ns(line()) == 0.0


def test_idle_gaps_by_hand():
    l = line(("a", 10, 10), ("b", 15, 10), ("c", 40, 5))
    assert trace.idle_gaps(l, 0, 60) == [(0, 10), (25, 40), (45, 60)]
    assert trace.idle_gaps(l, 12, 42) == [(25, 40)]


def test_self_time_of_a_container_is_what_its_body_leaves():
    l = line(("%while.1 = x", 0, 100), ("%fusion.2 = y", 10, 20),
             ("%copy.3 = z", 40, 50), ("%fusion.2 = y", 120, 5))
    own = trace.self_seconds(l)
    assert own["%while.1 = x"] == pytest.approx(30e-9)
    assert own["%fusion.2 = y"] == pytest.approx(25e-9)
    assert own["%copy.3 = z"] == pytest.approx(50e-9)


def test_short_names():
    assert trace.short_name(
        "%copy.476 = bf16[32,16,512,128]{3,2,1,0:T(8,128)(2,1)} copy(bf16["
    ) == "copy_bf16_32_16_512_128"
    assert trace.short_name(
        "%SelfAttention_0.125 = (bf16[4,16,2048,128]{3,2,1,0}, bf16[4,16]) "
        "custom-call(bf16[4,16,2048,128] %fusion.1143)"
    ) == "SelfAttention_0:custom-call_bf16_4_16_2048_128"
    assert trace.short_name("%while.6 = (s32[]{:T(128)}, f32[8192]) while("
                            ) == "while_s32_scalar"
    assert trace.is_mosaic_call("%x.1 = bf16[4] custom-call(bf16[4] %y)")
    assert not trace.is_mosaic_call("%x.1 = bf16[4] fusion(bf16[4] %y)")


def test_recorded_window_and_busy(recorded):
    t0, t1 = trace.traced_window(recorded)
    assert t0 == pytest.approx(0.2e6) and t1 - t0 > 200e6
    b = trace.busy(recorded)
    ops = recorded["/device:TPU:0", "XLA Ops"]
    assert b["chips"] == 1
    assert b["busy_s"] * 1e9 == pytest.approx(
        raster_busy_ns(ops, t0, t1), rel=2e-3)
    assert b["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    # two or three programs of 28 ms in every 32 ms: the device is busy
    # most of the recorded window, and never more than all of it
    assert 0.80 < b["busy_s"] / b["window_s"] < 1.0


def test_recorded_program_seconds(recorded):
    seconds, runs = trace.program_seconds(recorded, "jit_step_impl")
    mods = recorded["/device:TPU:0", "XLA Modules"]
    by_hand = sum(d for n, d in zip(mods.names, mods.dur)
                  if n.startswith("jit_step_impl"))
    assert runs == 6
    assert seconds == pytest.approx(by_hand * 1e-9)
    assert seconds == pytest.approx(0.16732, abs=2e-4)   # 3 x 27.63 + 3 x 28.15
    prefill, n = trace.program_seconds(recorded, "jit_prefill_impl")
    assert n == 2 and prefill == pytest.approx(0.00936, abs=1e-4)
    assert trace.program_seconds(recorded, "jit_no_such_program") == (0.0, 0)


def test_recorded_spans_and_idle_inside_them(recorded):
    total, count, idle = trace.span_seconds(recorded, "bench:engine.step")
    assert count == 2
    assert 0.0 < idle < total
    none = trace.span_seconds(recorded, "bench:nothing")
    assert none[1] == 0 and none[0] == 0.0


def test_recorded_breakdown(recorded):
    ops = trace.top_device_ops(recorded, k=10)
    assert len(ops) == 10 and all(s > 0 for _, s in ops)
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    # the whole-pool cache copies that PR 24's trace showed lead the list
    assert any(name.startswith("copy_bf16_") for name, _ in ops[:3])
    b = trace.busy(recorded)
    gaps = trace.idle_by_host_activity(recorded)
    assert sum(s for _, s in gaps) == pytest.approx(
        b["window_s"] - b["busy_s"], rel=1e-6)
    assert len(gaps) <= 10


def test_kernel_seconds_finds_nothing_where_there_is_no_kernel(recorded):
    assert trace.kernel_seconds(recorded, trace.is_mosaic_call) == (0.0, 0)


def test_a_trace_without_a_device_plane_is_refused():
    host_only = {("/host:CPU", "main/1"): line(("bench:window", 0, 10))}
    with pytest.raises(ValueError):
        trace.busy(host_only)
