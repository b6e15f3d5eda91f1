"""The readers of the ``ling_*`` metrics on a trace made by hand: two
decode steps and one prefill of a model with KDA layers and a share of
its routed experts, whose operations carry the scopes and whose spans the
engine's args; and on a program that records none of it, where each
returns ``None``."""

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

LING_METRICS = [m["name"] for m in bench.load_manifest(REPO)["per_layer"]
                if m["name"].startswith("ling_")]


def read(name, L):
    return importlib.import_module(f"perfbench.metrics.{name}").read(L)


def line(events):
    names, start, dur = zip(*events)
    return Line(list(names), np.array(start, float) * MS,
                np.array(dur, float) * MS)


@pytest.fixture()
def layers():
    """100 ms traced; two step programs of 40 ms and one prefill of 10 ms.
    In each step: the recurrence 16 ms, its convolution 2, the experts 10,
    the router 1, the shared expert 2, the latent read 3, the rest
    unscoped.  The prefill: its recurrence 4 ms, its experts 6."""
    ops, op_names = [], {}
    for t0 in (0.0, 50.0):
        for name, scope, at, dur in (
                ("%state", "Layer_1_attn/kda_decode/mul", 0, 16),
                ("%conv", "Layer_1_attn/kda_conv/add", 16, 2),
                ("%gmm", "Layer_1_moe/moe_experts/jit(gmm)/pallas_call",
                 18, 10),
                ("%router", "Layer_1_moe/moe_router/dot", 28, 1),
                ("%shared", "moe_shared/Layer_1/dot", 29, 2),
                ("%attn", "jit(step)/mla_decode/dot", 31, 3),
                ("%head", "lm_head/dot", 34, 6)):
            ops.append((name, t0 + at, dur))
            op_names[name] = scope + ":"
    for name, scope, at, dur in (
            ("%chunks", "Layer_2_attn/kda_prefill/while", 40, 4),
            ("%pgmm", "Layer_2_moe/moe_experts/jit(gmm)/pallas_call", 44,
             6)):
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
         "stats": {"experts_touched": 370, "expert_tokens_max": 12,
                   "live": 256}},
        {"name": "dkt:prefill", "start": 40 * MS, "dur": 10 * MS,
         "stats": {"experts_touched": 384, "expert_tokens_max": 90,
                   "prompt_tokens": 1000, "padded": 1024}},
        {"name": "dkt:decode_step", "start": 50 * MS, "dur": 40 * MS,
         "stats": {"experts_touched": 380, "expert_tokens_max": 8,
                   "live": 250}},
    ]
    manifest = bench.load_manifest(REPO)
    cfg = bench.load_config(manifest, "ling-3.0-flash-vl-l7-e64", REPO)
    ctx = types.SimpleNamespace(
        config=cfg, peaks=CPU_PEAKS, chips=1,
        arch=bench.load_arch(cfg["arch"]),
        traffic={"engine": {"steps_per_sync": 1}})
    work = [{"decode_tokens": 256, "context_tokens": 460_000,
             "prefill_tokens": 1000, "flops": 3.0e11},
            {"decode_tokens": 250, "context_tokens": 450_000,
             "prefill_tokens": 0, "flops": 2.0e11}]
    return types.SimpleNamespace(
        lines=lines, trace=trace, busy=trace.busy(lines), ctx=ctx,
        work=work, served=types.SimpleNamespace(end=[1.0, 2.0]),
        t_open=0.5, t_close=2.5, slots=256, peak_bytes=12.0e9,
        numbers={"occupancy_mean": 253.0, "tpot_p95_ms": 31.5},
        spans_pass={"spans": spans, "op_names": op_names})


def test_the_cell_reads_fourteen_metrics():
    """The fourteen that the cell was defined with."""
    assert {"ling_decode_step_device_ms", "ling_prefill_device_share",
            "ling_engine_step_host_ms", "ling_slot_occupancy_mean",
            "ling_device_idle_share", "ling_peak_hbm_gb", "ling_serve_mfu",
            "ling_decode_roofline", "ling_kda_decode_device_share",
            "ling_kda_decode_roofline", "ling_kda_prefill_device_share",
            "ling_moe_device_share", "ling_moe_experts_roofline",
            "ling_experts_touched_mean"} <= set(LING_METRICS)


def test_every_layer_it_shares_with_xing_is_read_under_its_name():
    """Each ``xing_*`` metric of a layer this model runs too has a
    ``ling_*`` twin: the step's host idle, the gaps between tokens, the
    experts' load, the latent attention and its cache write.  Only the
    multi-stream residual (``hc_mix``) is not run here."""
    xing = {m["name"][len("xing_"):]
            for m in bench.load_manifest(REPO)["per_layer"]
            if m["name"].startswith("xing_")}
    ling = {n[len("ling_"):] for n in LING_METRICS}
    assert xing - ling == {"hc_mix_device_share"}
    assert len(LING_METRICS) == 24


def test_shares_by_scope(layers):
    busy = layers.busy["busy_s"]
    assert busy == pytest.approx(0.090)
    assert read("ling_kda_decode_device_share", layers) == pytest.approx(
        100 * 32e-3 / busy)
    assert read("ling_kda_prefill_device_share", layers) == pytest.approx(
        100 * 4e-3 / busy)
    assert read("ling_moe_device_share", layers) == pytest.approx(
        100 * (2 * 13 + 6) * 1e-3 / busy)
    assert read("ling_prefill_device_share", layers) == pytest.approx(
        100 * 10e-3 / busy)
    assert read("ling_decode_step_device_ms", layers) == pytest.approx(40.0)


def test_the_kda_decode_roofline_counts_the_live_rows_state(layers):
    counts = layers.ctx.arch[2]
    cfg = layers.ctx.config
    per_row = 2 * 6 * (2 * 2**20 + 3 * 3 * 4096 * 2)
    assert counts.kda_step_bytes_per_row(cfg) == per_row
    assert read("ling_kda_decode_roofline", layers) == pytest.approx(
        100 * (256 + 250) * per_row / 819e9 / 0.032)


def test_the_decode_roofline_counts_weights_state_and_latent(layers):
    counts = layers.ctx.arch[2]
    cfg = layers.ctx.config
    least = sum(
        (counts.non_expert_weight_bytes(cfg) + touched * 3 * 2560 * 768 * 2
         + rows * counts.kda_step_bytes_per_row(cfg) + 1152 * ctx) / 819e9
        for touched, rows, ctx in ((370, 256, 460_000),
                                   (380, 250, 450_000)))
    assert read("ling_decode_roofline", layers) == pytest.approx(
        100 * least / 0.080)


def test_the_experts_roofline_counts_the_held_picks(layers):
    expert = 3 * 2560 * 768
    want = sum(max(n * expert * 2 / 819e9,
                   2 * expert * tokens * 1.0 * 6 / 197e12)
               for n, tokens in ((370, 256), (384, 1000), (380, 256)))
    # the grouped products alone: 10 ms a step, 6 in the prefill
    assert read("ling_moe_experts_roofline", layers) == pytest.approx(
        100 * want / 0.026)
    assert read("ling_experts_touched_mean", layers) == 375


def test_the_latent_layer_and_the_experts_load(layers):
    busy = layers.busy["busy_s"]
    assert read("ling_mla_decode_device_share", layers) == pytest.approx(
        100 * 6e-3 / busy)
    # one latent layer of seven: 1152 B a cached token, read once
    latent = 1152 * (460_000 + 450_000) / 819e9
    assert read("ling_mla_decode_roofline", layers) == pytest.approx(
        100 * latent / 0.006)
    # the mean expert gets 256 rows x 8 picks / 512 experts = 4
    assert read("ling_expert_load_max_over_mean", layers) == \
        pytest.approx((12 + 8) / 2 / 4)
    # no operation under ``latent_write`` yet: nothing to read
    assert read("ling_latent_write_device_share", layers) is None
    layers.spans_pass["op_names"]["%attn"] = \
        "jit(step)/latent_write/dynamic_update_slice:"
    assert read("ling_latent_write_device_share", layers) == pytest.approx(
        100 * 6e-3 / busy)


@pytest.mark.parametrize("part", ("admit", "prefill", "dispatch", "fetch",
                                  "emit"))
def test_idle_in_the_step_reads_as_the_span_readers_do(part):
    """On the recording of a program with the spans, the same number as
    ``cgpt-serve-backlog``'s reader of that span; on the recording of a
    program from before the spans, nothing."""
    for file_name, found in (("recorded_trace_spans.json", True),
                             ("recorded_trace.json", False)):
        _, lines = load(file_name)
        L = types.SimpleNamespace(lines=lines, trace=trace)
        got = read(f"ling_idle_in_{part}_ms", L)
        assert got == read(f"backlog_idle_in_{part}_ms", L)
        assert (got is not None) == found


def test_general_readers_under_the_cells_names(layers):
    assert read("ling_slot_occupancy_mean", layers) == 253.0
    assert read("ling_peak_hbm_gb", layers) == pytest.approx(12.0)
    assert read("ling_device_idle_share", layers) == pytest.approx(10.0)
    assert read("ling_serve_mfu", layers) == pytest.approx(
        100 * 5.0e11 / 0.1 / 197e12)
    assert read("ling_tpot_p95_ms", layers) == 31.5


@pytest.mark.parametrize("name", [
    "ling_kda_decode_device_share", "ling_kda_decode_roofline",
    "ling_kda_prefill_device_share", "ling_moe_device_share",
    "ling_moe_experts_roofline", "ling_experts_touched_mean",
    "ling_decode_roofline", "ling_expert_load_max_over_mean",
    "ling_mla_decode_device_share", "ling_mla_decode_roofline",
    "ling_latent_write_device_share"])
def test_a_program_without_scopes_or_args_gives_nothing(layers, name):
    """A program of before this PR: no operation under a scope of the
    model, no experts' args or live rows on any span."""
    ops = layers.lines[DEV, trace.OPS_LINE]
    layers.lines[DEV, trace.OPS_LINE] = Line(
        [f"%fusion.{i}" for i in range(len(ops.names))], ops.start, ops.dur)
    layers.spans_pass = {
        "spans": [{**s, "stats": {k: v for k, v in s["stats"].items()
                                  if not k.startswith("expert")
                                  and k != "live"}}
                  for s in layers.spans_pass["spans"]],
        "op_names": {"%head": "lm_head/dot:"}}
    assert read(name, layers) is None
