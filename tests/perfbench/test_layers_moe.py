"""The readers of ``perfbench/layers_moe.py`` on a trace made by hand:
two decode steps and one prefill of a model with routed experts, whose
operations carry the scopes and whose spans the experts' args; and on a
program that records none of it, where each returns ``None``."""

import importlib
import types

import numpy as np
import pytest

from perfbench import layers_moe, run as bench, trace
from perfbench.trace import Line

from test_layers_spans import load
from toybench import CPU_PEAKS, REPO

DEV = trace.DEVICE_PREFIX + "0"
MS = 1e6     # ns

XING_METRICS = [m["name"] for m in bench.load_manifest(REPO)["per_layer"]
                if m["name"].startswith("xing_")]


def read(name, L):
    return importlib.import_module(f"perfbench.metrics.{name}").read(L)


def line(events):
    names, start, dur = zip(*events)
    return Line(list(names), np.array(start, float) * MS,
                np.array(dur, float) * MS)


@pytest.fixture()
def layers():
    """100 ms traced; two step programs of 30 ms and one prefill of 20 ms.
    In each step: experts 12 ms, router 1, shared 2, latent attention 6,
    mixing 3, the rest unscoped."""
    ops, op_names = [], {}
    for k, t0 in enumerate((0.0, 50.0)):
        for name, scope, at, dur in (
                # XLA's rewrite keeps no scope on the grouped product
                ("%ragged-dot-none.3 = bf16[256,2048] custom-call(%a)",
                 "ragged-dot-none", 0, 11),
                ("%silu", "Layer_1_moe/moe_experts/mul", 11, 1),
                ("%router", "Layer_1_moe/moe_router/dot", 12, 1),
                ("%shared", "moe_shared/Layer_1/dot", 13, 2),
                ("%attn", "jit(step)/mla_decode/dot", 15, 6),
                ("%mix", "jit(step)/hc_mix/mul", 21, 3),
                ("%head", "lm_head/dot", 24, 6)):
            ops.append((name, t0 + at, dur))
            op_names[name] = scope + ":"
    ops.append(("%ragged-dot-none.7 = bf16[4096,2048] custom-call(%b)",
                30.0, 10))                     # the prefill's experts
    op_names[ops[-1][0]] = "ragged-dot-none:"
    ops.append(("%flash", 40.0, 10))
    op_names["%flash"] = "mla_prefill/flash_fwd:"
    lines = {
        (DEV, trace.OPS_LINE): line(ops),
        (DEV, trace.MODULES_LINE): line([
            ("jit_step_impl_6144(1)", 0.0, 30), ("jit_prefill_impl(2)", 30.0, 20),
            ("jit_step_impl_6144(1)", 50.0, 30)]),
        (trace.HOST_PLANE, "main"): line([
            (trace.WINDOW_SPAN, 0.0, 100), ("bench:engine.step", 0.0, 50),
            ("bench:engine.step", 50.0, 40)]),
    }
    spans = [
        {"name": "dkt:decode_step", "start": 0.0, "dur": 30 * MS,
         "stats": {"experts_touched": 300, "expert_tokens_max": 12}},
        {"name": "dkt:prefill", "start": 30 * MS, "dur": 20 * MS,
         "stats": {"experts_touched": 320, "expert_tokens_max": 90,
                   "prompt_tokens": 1000, "padded": 1024}},
        {"name": "dkt:decode_step", "start": 50 * MS, "dur": 30 * MS,
         "stats": {"experts_touched": 310, "expert_tokens_max": 8}},
    ]
    manifest = bench.load_manifest(REPO)
    cfg = bench.load_config(manifest, "xing4.0-29b-a4b-l6", REPO)
    ctx = types.SimpleNamespace(config=cfg, peaks=CPU_PEAKS, chips=1,
                                arch=bench.load_arch(cfg["arch"]))
    work = [{"decode_tokens": 64, "context_tokens": 128_000,
             "prefill_tokens": 1000, "flops": 3.0e11},
            {"decode_tokens": 64, "context_tokens": 128_064,
             "prefill_tokens": 0, "flops": 2.0e11}]
    return types.SimpleNamespace(
        lines=lines, trace=trace, busy=trace.busy(lines), ctx=ctx,
        work=work, served=types.SimpleNamespace(end=[1.0, 2.0]),
        t_open=0.5, t_close=2.5, slots=64, peak_bytes=14.0e9,
        numbers={"occupancy_mean": 64.0},
        spans_pass={"spans": spans, "op_names": op_names})


def test_shares_by_scope(layers):
    busy = layers.busy["busy_s"]
    assert busy == pytest.approx(0.080)
    assert read("xing_moe_device_share", layers) == pytest.approx(
        100 * (2 * 15 + 10) * 1e-3 / busy)
    assert read("xing_mla_decode_device_share", layers) == pytest.approx(
        100 * 12e-3 / busy)
    assert read("xing_hc_mix_device_share", layers) == pytest.approx(
        100 * 6e-3 / busy)
    # no operation under ``latent_write`` yet: nothing to read
    assert read("xing_latent_write_device_share", layers) is None
    ops = layers.lines[DEV, trace.OPS_LINE]
    layers.spans_pass["op_names"][ops.names[-1]] = \
        "jit(prefill)/latent_write/dynamic_update_slice:"
    assert read("xing_latent_write_device_share", layers) == pytest.approx(
        100 * 10e-3 / busy)


def test_program_counters(layers):
    assert read("xing_experts_touched_mean", layers) == 305
    assert read("xing_expert_load_max_over_mean", layers) == \
        pytest.approx(10 / (64 * 4 / 64))


def test_decode_roofline_counts_the_experts_a_step_touched(layers):
    counts = layers.ctx.arch[2]
    cfg = layers.ctx.config
    least = sum(
        (counts.non_expert_weight_bytes(cfg) + touched * 22_020_096
         + 6 * 1152 * ctx) / 819e9
        for touched, ctx in ((300, 128_000), (310, 128_064)))
    assert read("xing_decode_roofline", layers) == pytest.approx(
        100 * least / 0.060)
    # all 320 touched is what ``weight_bytes`` counts
    assert counts.non_expert_weight_bytes(cfg) + 320 * 22_020_096 \
        == counts.weight_bytes(cfg)


def test_kernel_rooflines(layers):
    experts = sum(max(n * 22_020_096 / 819e9,
                      6 * 3584 * 1024 * tokens * 4 * 5 / 197e12)
                  for n, tokens in ((300, 64), (320, 1000), (310, 64)))
    assert read("xing_moe_experts_roofline", layers) == pytest.approx(
        100 * experts / 0.034)
    latent = sum(6 * 1152 * ctx / 819e9 for ctx in (128_000, 128_064))
    assert read("xing_mla_decode_roofline", layers) == pytest.approx(
        100 * latent / 0.012)


def test_general_readers_under_the_cells_names(layers):
    assert read("xing_decode_step_device_ms", layers) == pytest.approx(30.0)
    assert read("xing_prefill_device_share", layers) == pytest.approx(25.0)
    assert read("xing_device_idle_share", layers) == pytest.approx(20.0)
    assert read("xing_engine_step_host_ms", layers) == pytest.approx(5.0)
    assert read("xing_peak_hbm_gb", layers) == pytest.approx(14.0)
    assert read("xing_slot_occupancy_mean", layers) == 64.0
    assert read("xing_serve_mfu", layers) == pytest.approx(
        100 * 5.0e11 / 0.1 / 197e12)


@pytest.mark.parametrize("name", [
    n for n in XING_METRICS
    if importlib.import_module(f"perfbench.metrics.{n}").read.__module__
    == "perfbench.layers_moe"])
def test_a_program_without_scopes_or_args_gives_nothing(layers, name):
    """A program of before this PR: no operation under a scope of the
    model, no grouped product, no experts' args on any span."""
    ops = layers.lines[DEV, trace.OPS_LINE]
    layers.lines[DEV, trace.OPS_LINE] = Line(
        [f"%fusion.{i}" for i in range(len(ops.names))], ops.start, ops.dur)
    layers.spans_pass = {
        "spans": [{**s, "stats": {k: v for k, v in s["stats"].items()
                                  if not k.startswith("expert")}}
                  for s in layers.spans_pass["spans"]],
        "op_names": {"%head": "lm_head/dot:"}}
    assert read(name, layers) is None


IDLE_IN = ("admit", "prefill", "dispatch", "fetch", "emit")


@pytest.mark.parametrize("part", IDLE_IN)
def test_idle_in_the_step_reads_as_the_span_readers_do(part):
    """On the recording of a program with the spans, the same number as
    ``cgpt-serve-backlog``'s reader of that span; on the recording of a program from
    before the spans, nothing."""
    for file_name, found in (("recorded_trace_spans.json", True),
                             ("recorded_trace.json", False)):
        _, lines = load(file_name)
        L = types.SimpleNamespace(lines=lines, trace=trace)
        got = read(f"xing_idle_in_{part}_ms", L)
        assert got == read(f"backlog_idle_in_{part}_ms", L)
        assert (got is not None) == found


def test_the_manifest_holds_the_cells_metrics():
    assert len(XING_METRICS) == 22
