"""The readers of the ``exa_*`` metrics on a trace made by hand: two
decode steps and one prefill of a model of window and full attention
layers over a share of its routed experts, whose operations carry the
scopes and whose spans the engine's args; and on a program that records
none of it, where each returns ``None``."""

import importlib
import types

import numpy as np
import pytest

from perfbench import run as bench, trace
from perfbench.trace import Line

from test_layers_spans import load
from toybench import CPU_PEAKS, REPO

DEV = trace.DEVICE_PREFIX + "0"
MS = 1e6     # ns

EXA_METRICS = [m["name"] for m in bench.load_manifest(REPO)["per_layer"]
               if m["name"].startswith("exa_")]


def read(name, L):
    return importlib.import_module(f"perfbench.metrics.{name}").read(L)


def line(events):
    names, start, dur = zip(*events)
    return Line(list(names), np.array(start, float) * MS,
                np.array(dur, float) * MS)


MOSAIC = " = bf16[1,64,512,128]{3,2,1,0} custom-call(%p), " \
    'custom_call_target="tpu_custom_call"'


@pytest.fixture()
def layers():
    """100 ms traced; two step programs of 40 ms and one prefill of 10 ms.
    In each step: the rings' read 4 ms, the full layer's read 8, the ring
    write 1, the experts 10, the router 1, the shared expert 2, the rest
    unscoped.  The prefill: the windowed kernel 2 ms and its transposes 1,
    the full layer's kernel 3, the experts 4."""
    ops, op_names = [], {}
    for t0 in (0.0, 50.0):
        for name, scope, at, dur in (
                ("%ring" + MOSAIC, "Layer_1_attn/swa_decode/pallas_call",
                 0, 4),
                ("%full" + MOSAIC, "Layer_3_attn/gqa_decode/pallas_call",
                 4, 8),
                ("%write", "Layer_1_attn/window_write/scatter", 12, 1),
                ("%gmm", "Layer_1_moe/moe_experts/jit(gmm)/pallas_call",
                 13, 10),
                ("%router", "Layer_1_moe/moe_router/dot", 23, 1),
                ("%shared", "moe_shared/Layer_1/dot", 24, 2),
                ("%head", "lm_head/dot", 26, 14)):
            ops.append((f"{name}.{int(t0)}", t0 + at, dur))
            op_names[f"{name}.{int(t0)}"] = scope + ":"
    for name, scope, at, dur in (
            ("%band" + MOSAIC, "Layer_2_attn/swa_prefill/flash_fwd", 40, 2),
            ("%swap", "Layer_2_attn/swa_prefill/transpose", 42, 1),
            ("%causal" + MOSAIC, "Layer_3_attn/gqa_prefill/flash_fwd", 43,
             3),
            ("%pgmm", "Layer_2_moe/moe_experts/jit(gmm)/pallas_call", 46,
             4)):
        ops.append((name, at, dur))
        op_names[name] = scope + ":"
    lines = {
        (DEV, trace.OPS_LINE): line(ops),
        (DEV, trace.MODULES_LINE): line([
            ("jit_step_impl_6144(1)", 0.0, 40),
            ("jit_prefill_impl(2)", 40.0, 10),
            ("jit_step_impl_6144(1)", 50.0, 40)]),
        (trace.HOST_PLANE, "main"): line([
            (trace.WINDOW_SPAN, 0.0, 100), ("bench:engine.step", 0.0, 50),
            ("bench:engine.step", 50.0, 45)]),
    }
    spans = [
        {"name": "dkt:decode_step", "start": 0.0, "dur": 40 * MS,
         "stats": {"experts_touched": 32, "expert_tokens_max": 30,
                   "live": 288, "attended_rows": 600_000,
                   "window_rows": 288 * 4 * 128}},
        {"name": "dkt:prefill", "start": 40 * MS, "dur": 10 * MS,
         "stats": {"experts_touched": 32, "expert_tokens_max": 90,
                   "prompt_tokens": 1000, "padded": 1024,
                   "band_blocks": 64 * 15 * 4}},
        {"name": "dkt:decode_step", "start": 50 * MS, "dur": 40 * MS,
         "stats": {"experts_touched": 31, "expert_tokens_max": 28,
                   "live": 287, "attended_rows": 590_000,
                   "window_rows": 287 * 4 * 128}},
    ]
    manifest = bench.load_manifest(REPO)
    cfg = bench.load_config(manifest, "k-exaone-236b-a23b-l5-e8", REPO)
    ctx = types.SimpleNamespace(
        config=cfg, peaks=CPU_PEAKS, chips=1,
        arch=bench.load_arch(cfg["arch"]),
        traffic={"engine": {"steps_per_sync": 1}})
    work = [{"decode_tokens": 288, "context_tokens": 560_000,
             "prefill_tokens": 1000, "flops": 3.0e12},
            {"decode_tokens": 287, "context_tokens": 550_000,
             "prefill_tokens": 0, "flops": 2.0e12}]
    return types.SimpleNamespace(
        lines=lines, trace=trace, busy=trace.busy(lines), ctx=ctx,
        work=work, served=types.SimpleNamespace(end=[1.0, 2.0]),
        t_open=0.5, t_close=2.5, slots=288, peak_bytes=13.5e9,
        numbers={"occupancy_mean": 288.0, "tpot_p95_ms": 40.5},
        spans_pass={"spans": spans, "op_names": op_names})


def test_the_cell_reads_fifteen_metrics():
    assert sorted(EXA_METRICS) == sorted([
        "exa_decode_step_device_ms", "exa_prefill_device_share",
        "exa_engine_step_host_ms", "exa_slot_occupancy_mean",
        "exa_idle_in_fetch_ms", "exa_device_idle_share", "exa_peak_hbm_gb",
        "exa_serve_mfu", "exa_decode_roofline", "exa_moe_device_share",
        "exa_moe_experts_roofline", "exa_swa_decode_device_share",
        "exa_gqa_decode_device_share", "exa_attn_decode_roofline",
        "exa_swa_prefill_roofline"])


def test_shares_by_scope(layers):
    busy = layers.busy["busy_s"]
    assert busy == pytest.approx(0.090)
    assert read("exa_swa_decode_device_share", layers) == pytest.approx(
        100 * 8e-3 / busy)
    assert read("exa_gqa_decode_device_share", layers) == pytest.approx(
        100 * 16e-3 / busy)
    assert read("exa_moe_device_share", layers) == pytest.approx(
        100 * (2 * 13 + 4) * 1e-3 / busy)
    assert read("exa_prefill_device_share", layers) == pytest.approx(
        100 * 10e-3 / busy)
    assert read("exa_decode_step_device_ms", layers) == pytest.approx(40.0)


def test_the_attention_roofline_reads_the_spans_positions(layers):
    """4 KiB a position a layer: the full layer's ``attended_rows`` and the
    rings' ``window_rows``, over the two reads' 24 ms."""
    positions = 600_000 + 590_000 + (288 + 287) * 4 * 128
    assert read("exa_attn_decode_roofline", layers) == pytest.approx(
        100 * positions * 4096 / 819e9 / 0.024)


def test_the_prefill_roofline_reads_the_windowed_kernel_alone(layers):
    """The band of a 1000-token prompt in four window layers, at the
    peak, over the windowed kernel's 2 ms (its transposes and the full
    layer's kernel are not its time)."""
    flops = 4 * 4 * 64 * 128 * (128 * 129 // 2 + 872 * 128)
    assert read("exa_swa_prefill_roofline", layers) == pytest.approx(
        100 * flops / 197e12 / 0.002)
    layers.spans_pass["spans"][1]["stats"]["band_blocks"] = 0
    assert read("exa_swa_prefill_roofline", layers) is None


def test_the_decode_roofline_counts_weights_experts_rings_and_cache(
        layers):
    counts = layers.ctx.arch[2]
    cfg = layers.ctx.config
    least = sum(
        (counts.non_expert_weight_bytes(cfg) + touched * 3 * 6144 * 2048 * 2
         + rows * 4 * 128 * 4096 + 4096 * ctx) / 819e9
        for touched, rows, ctx in ((32, 288, 560_000), (31, 287, 550_000)))
    assert read("exa_decode_roofline", layers) == pytest.approx(
        100 * least / 0.080)


def test_the_experts_roofline_counts_the_held_picks(layers):
    expert = 3 * 6144 * 2048
    want = sum(max(n * expert * 2 / 819e9,
                   2 * expert * tokens * 0.5 * 4 / 197e12)
               for n, tokens in ((32, 288), (32, 1000), (31, 288)))
    # the grouped products alone: 10 ms a step, 4 in the prefill
    assert read("exa_moe_experts_roofline", layers) == pytest.approx(
        100 * want / 0.024)


def test_general_readers_under_the_cells_names(layers):
    assert read("exa_slot_occupancy_mean", layers) == 288.0
    assert read("exa_peak_hbm_gb", layers) == pytest.approx(13.5)
    assert read("exa_device_idle_share", layers) == pytest.approx(10.0)
    assert read("exa_serve_mfu", layers) == pytest.approx(
        100 * 5.0e12 / 0.1 / 197e12)


def test_idle_in_fetch_reads_as_the_span_readers_do():
    for file_name, found in (("recorded_trace_spans.json", True),
                             ("recorded_trace.json", False)):
        _, lines = load(file_name)
        L = types.SimpleNamespace(lines=lines, trace=trace)
        got = read("exa_idle_in_fetch_ms", L)
        assert got == read("backlog_idle_in_fetch_ms", L)
        assert (got is not None) == found


@pytest.mark.parametrize("name", [
    "exa_swa_decode_device_share", "exa_gqa_decode_device_share",
    "exa_attn_decode_roofline", "exa_swa_prefill_roofline",
    "exa_moe_device_share", "exa_moe_experts_roofline",
    "exa_decode_roofline"])
def test_a_program_without_scopes_or_args_gives_nothing(layers, name):
    """A program of before the window model: no operation under a scope
    of the model, no experts' or windows' args on any span."""
    ops = layers.lines[DEV, trace.OPS_LINE]
    layers.lines[DEV, trace.OPS_LINE] = Line(
        [f"%fusion.{i}" for i in range(len(ops.names))], ops.start, ops.dur)
    layers.spans_pass = {
        "spans": [{**s, "stats": {k: v for k, v in s["stats"].items()
                                  if not k.startswith("expert")
                                  and k not in ("live", "window_rows",
                                                "band_blocks")}}
                  for s in layers.spans_pass["spans"]],
        "op_names": {"%head": "lm_head/dot:"}}
    assert read(name, layers) is None
