"""Per-layer readers on the recorded trace: known numbers where there is
something to read, ``None`` (never 0) where there is not."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from perfbench import run as bench, trace
from perfbench.trace import Line

from toybench import CPU_PEAKS, REPO

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def layers():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        fix = json.load(f)
    lines = {(l["plane"], l["line"]): Line(
        [fix["names"][i] for i in l["name_index"]],
        np.array(l["start_ns"], float), np.array(l["dur_ns"], float))
        for l in fix["lines"]}
    manifest = bench.load_manifest(REPO)
    cfg = bench.load_config(manifest, "cerebras-gpt-1.3b", REPO)
    ctx = types.SimpleNamespace(config=cfg, peaks=CPU_PEAKS, chips=1,
                                arch=bench.load_arch(cfg["arch"]))
    # two engine steps, 30 live requests at 400 tokens of context each
    work = [{"decode_tokens": 30, "context_tokens": 12000,
             "prefill_tokens": 300, "flops": 1.0e11}] * 2
    served = types.SimpleNamespace(end=[1.0, 2.0])
    numbers = {"lateness_p95_ms": 90.0, "tpot_p95_ms": 120.0,
               "occupancy_mean": 30.0}
    return types.SimpleNamespace(
        lines=lines, trace=trace, busy=trace.busy(lines), ctx=ctx, work=work,
        served=served,
        t_open=0.5, t_close=2.5, numbers=numbers, peak_bytes=13.2e9,
        steps=2, flops_step=1.0e13, batch=4, seq_len=2048)


def read(name, L):
    return importlib.import_module(f"perfbench.metrics.{name}").read(L)


def test_decode_step_device_ms_is_program_time_per_step(layers):
    # six decode programs of 27.6 to 28.2 ms inside two spans
    assert read("steady_decode_step_device_ms", layers) == pytest.approx(
        167.32 / 2, abs=0.2)
    assert read("backlog_decode_step_device_ms", layers) == \
        read("steady_decode_step_device_ms", layers)


def test_prefill_share_and_idle_share(layers):
    busy = trace.busy(layers.lines)
    assert read("steady_prefill_device_share", layers) == pytest.approx(
        100 * 0.00936 / busy["busy_s"], rel=0.02)
    idle = read("steady_device_idle_share", layers)
    assert idle == pytest.approx(
        100 * (1 - busy["busy_s"] / busy["window_s"]))
    assert 0 < idle < 20


def test_decode_roofline_by_hand(layers):
    # least time of a step: (2.62 GB of weights + 12000 x 196 608 B) / 819 GB/s
    least = 2 * (2 * 1_310_885_888 + 12000 * 196_608) / 819e9
    assert read("steady_decode_roofline", layers) == pytest.approx(
        100 * least / 0.16732, rel=2e-3)
    assert read("steady_decode_roofline", layers) < 100


def test_serve_mfu_and_host_counts(layers):
    window = trace.busy(layers.lines)["window_s"]
    assert read("steady_serve_mfu", layers) == pytest.approx(
        100 * 2.0e11 / window / 197e12)
    assert read("steady_slot_occupancy_mean", layers) == 30.0
    assert read("steady_generator_lateness_p95_ms", layers) == 90.0
    assert read("backlog_tpot_p95_ms", layers) == 120.0
    assert read("steady_peak_hbm_gb", layers) == pytest.approx(13.2)
    assert 0 < read("steady_engine_step_host_ms", layers) < 20


@pytest.mark.parametrize("name", ["flash_attn_roofline"])
def test_a_reader_that_finds_nothing_returns_nothing(layers, name):
    # the recorded trace is a serving one: it holds no Mosaic call
    assert read(name, layers) is None


def test_train_readers_on_the_same_trace(layers):
    busy = trace.busy(layers.lines)
    assert read("train_step_device_mfu", layers) == pytest.approx(
        100 * 2 * 1.0e13 / busy["busy_s"] / 197e12)
    assert read("train_host_ms_per_step", layers) == pytest.approx(
        1e3 * (busy["window_s"] - busy["busy_s"]) / 2)
    assert read("train_peak_hbm_gb", layers) == pytest.approx(13.2)
    assert read("train_device_idle_share", layers) == \
        read("steady_device_idle_share", layers)
