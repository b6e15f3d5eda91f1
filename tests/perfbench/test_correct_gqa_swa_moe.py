"""``correct`` for the window + full grouped-query attention, sigmoid-
expert arch, as ``test_correct_kda_mla_moe.py`` has it for the KDA one:
true for a toy cell driven through ``run_cell``, false for the float8
control (the plain reference put in the program's place), for the faults
planted in that reference (the routed experts left out, the window left
out, a rotary on the full layer, the prompt's padding in the rings).

The toy cell (a window of 16 that its prompts and answers cross) is added
to a copy of the benchmark the way a PR adds one: new files
(``tiny_swa/``) and appended entries, on top of the toy cells of
``toybench.add_toy_cells``.  Its limits are its own readings on the CPU.
"""

import json
import os
import shutil
import types

import numpy as np
import pytest

from perfbench import run as bench
from perfbench.kinds import serve

from toybench import CPU_PEAKS, HERE

CELL = "tiny-swa-backlog"


@pytest.fixture(scope="module")
def toy_tree(toy_tree, tmp_path_factory):
    """The session's toy tree, copied, with the toy window cell added."""
    tree = str(tmp_path_factory.mktemp("bench_swa"))
    shutil.copy(os.path.join(toy_tree, "BENCHMARK.json"), tree)
    shutil.copytree(os.path.join(toy_tree, "perfbench"),
                    os.path.join(tree, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for part in ("configs", "traffic", "limits"):
        for name in os.listdir(os.path.join(HERE, "tiny_swa", part)):
            dst = os.path.join(tree, "perfbench", part, name)
            assert not os.path.exists(dst), dst
            shutil.copy(os.path.join(HERE, "tiny_swa", part, name), dst)
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append(
        {"name": "tiny-swa", "source": "tests", "reduced": [], "why": "toy",
         "file": "perfbench/configs/tiny-swa.json"})
    m["workloads"].append(
        {"name": CELL, "config": "tiny-swa", "traffic": CELL, "chips": 1,
         "why": "toy backlog"})
    for e in m["end_to_end"]:
        if e["name"] == "serve_tokens_per_s":
            e["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(m, f)
    return tree


def context(tree, seed, seconds=1.0):
    manifest = bench.load_manifest(tree)
    cell = bench.find(manifest["workloads"], CELL, "workload")
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    return bench.make_context(manifest, cell, args, CPU_PEAKS, tree)


@pytest.fixture(scope="module")
def swa_run(toy_tree):
    ctx = context(toy_tree, 2**31 + 43)
    return ctx, serve.run(ctx)


def test_toy_swa_cell_is_correct(swa_run):
    ctx, out = swa_run
    assert out["checks"].correct, out["checks"].lines()
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    rows = {r["name"]: r for r in out["checks"].rows}
    assert rows["slots_idle_in_window"]["value"] == 0
    assert rows["served_logit_worst_gap"]["tokens"] >= 200


FAULTS = ["f32+no_routed", "f32+no_window", "f32+rope_on_full",
          "f32+pad_in_ring"]


@pytest.fixture(scope="module")
def readings(swa_run):
    """The float8 control and each fault planted in the reference, as
    ``tools/served_readings.py`` reads them on the chip: each stands in
    the program's place and goes through the run's own limit (the float32
    pass that judges them is computed once)."""
    from perfbench.tools import served_readings

    ctx, out = swa_run
    return dict(served_readings.readings(ctx, out, ["fp8"] + FAULTS))


def test_swa_control_in_fp8_is_not_correct(readings):
    checks = readings["fp8"]
    row, = checks.rows
    assert row["value"] >= 3 * row["limit"] and not checks.correct


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_planted_in_the_reference_is_not_correct(readings, fault):
    checks = readings[fault]
    row, = checks.rows
    assert not checks.correct
    assert row["value"] > 2 * row["limit"]


def test_the_reference_abstains_on_tied_tokens_in_float32_alone(toy_tree):
    """``route_tie_margin``: a token whose 4th and 5th chosen score lie
    closer than that in any expert layer gets a row of zeros from the
    float32 reference; the other rows are what they were, the control's
    rows are never zeroed."""
    ctx = context(toy_tree, 5)
    _, reference, _, _ = ctx.arch
    cfg = {**ctx.config, "route_tie_margin": 0.0}
    seqs = [np.arange(3, 43, dtype=np.int32) % cfg["vocab_size"]]
    rows = [np.arange(8, 40)]
    plain, = reference.served_logits(cfg, 5, "bfloat16", seqs, rows, "f32")
    assert (np.abs(plain).max(axis=1) > 0).all()
    tied_cfg = {**cfg, "route_tie_margin": 0.02}
    got, = reference.served_logits(tied_cfg, 5, "bfloat16", seqs, rows,
                                   "f32")
    tied = np.abs(got).max(axis=1) == 0
    assert 2 <= tied.sum() <= len(tied) - 2
    np.testing.assert_array_equal(got[~tied], plain[~tied])
    low, = reference.served_logits(tied_cfg, 5, "bfloat16", seqs, rows,
                                   "fp8")
    assert (np.abs(low).max(axis=1) > 0).all()
