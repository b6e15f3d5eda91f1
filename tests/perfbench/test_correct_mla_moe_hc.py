"""``correct`` for the latent-attention, sparse-expert arch, as
``test_correct.py`` has it for the GPT-2 block: true for a toy cell driven
through ``run_cell``, false for the float8 control (the plain reference
put in the program's place) and for a fault planted in the program, the
shared expert left out.

The toy cell is added to a copy of the benchmark the way a PR adds one:
new files (``tiny_moe/``) and appended entries, on top of the toy cells of
``toybench.add_toy_cells``.  Its limits are its own readings on the CPU.
"""

import json
import os
import shutil
import types

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import common, run as bench
from perfbench.kinds import serve

from toybench import CPU_PEAKS, HERE


@pytest.fixture(scope="module")
def toy_tree(toy_tree, tmp_path_factory):
    """The session's toy tree, copied, with the toy expert cell added."""
    tree = str(tmp_path_factory.mktemp("bench_moe"))
    shutil.copy(os.path.join(toy_tree, "BENCHMARK.json"), tree)
    shutil.copytree(os.path.join(toy_tree, "perfbench"),
                    os.path.join(tree, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for part in ("configs", "traffic", "limits"):
        for name in os.listdir(os.path.join(HERE, "tiny_moe", part)):
            dst = os.path.join(tree, "perfbench", part, name)
            assert not os.path.exists(dst), dst
            shutil.copy(os.path.join(HERE, "tiny_moe", part, name), dst)
    path = os.path.join(tree, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append(
        {"name": "tiny-moe", "source": "tests", "reduced": [], "why": "toy",
         "file": "perfbench/configs/tiny-moe.json"})
    m["workloads"].append(
        {"name": "tiny-moe-backlog", "config": "tiny-moe",
         "traffic": "tiny-moe-backlog", "chips": 1, "why": "toy backlog"})
    for e in m["end_to_end"]:
        if e["name"] == "serve_tokens_per_s":
            e["workloads"].append("tiny-moe-backlog")
    with open(path, "w") as f:
        json.dump(m, f)
    return tree


def checks_of(line):
    return {c["name"]: c for c in line["checks"]}


def context(tree, cell_name, seed, seconds):
    manifest = bench.load_manifest(tree)
    cell = bench.find(manifest["workloads"], cell_name, "workload")
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    return bench.make_context(manifest, cell, args, CPU_PEAKS, tree)


@pytest.fixture(scope="module")
def moe_run(toy_tree):
    ctx = context(toy_tree, "tiny-moe-backlog", 2**31 + 29, 1.0)
    return ctx, serve.run(ctx)


def test_toy_moe_cell_is_correct(moe_run):
    ctx, out = moe_run
    assert out["checks"].correct, out["checks"].lines()
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    rows = {r["name"]: r for r in out["checks"].rows}
    assert rows["slots_idle_in_window"]["value"] == 0
    assert rows["served_logit_worst_gap"]["tokens"] >= 200


def test_a_backlog_run_says_how_much_of_the_backlog_is_left(moe_run):
    ctx, out = moe_run
    left = out["end_to_end"]["backlog_left_share_min"]
    assert 0 < left < 1
    # not a metric and not compared: it is in the line's ``window``
    assert "backlog_left_share_min" not in ctx.limits


def test_a_backlog_cut_too_short_idles_a_slot_and_is_not_correct(toy_tree):
    """The toy with two requests more than it has slots: the queue is dry
    at once, and the first request to finish leaves its slot idle."""
    ctx = context(toy_tree, "tiny-moe-backlog", 2**31 + 30, 1.0)
    slots = sum(ctx.traffic["engine"]["buckets"].values())
    ctx.traffic = {**ctx.traffic, "requests": slots + 2}
    out = serve.run(ctx)
    rows = {r["name"]: r for r in out["checks"].rows}
    assert rows["slots_idle_in_window"]["value"] > 0
    assert not rows["slots_idle_in_window"]["ok"]
    assert not out["checks"].correct
    assert out["end_to_end"]["backlog_left_share_min"] == 0


def test_moe_control_in_fp8_is_not_correct(moe_run):
    ctx, out = moe_run
    _, reference, _, _ = ctx.arch
    L = out["layers"]
    checks = common.Checks(ctx.limits)
    serve.check_served(checks, reference, ctx.config, ctx.seed, L["picked"],
                       L["served"], precision="fp8")
    row, = checks.rows
    # by four times the limit, as the real cell's control has to
    assert row["value"] >= 4 * row["limit"] and not checks.correct


def test_the_shared_expert_left_out_is_not_correct(toy_tree, monkeypatch):
    """Planted in the program, underneath the timed path: every expert
    layer adds its routed experts' part alone."""
    from distkeras_tpu.models import latent_moe

    class Silent(latent_moe.SwiGLU):
        def __call__(self, x):
            y = super().__call__(x)
            return jnp.zeros_like(y) if self.name == "shared" else y

    monkeypatch.setattr(latent_moe, "SwiGLU", Silent)
    line = bench.run_cell("tiny-moe-backlog", 11, 1.0, 0, CPU_PEAKS,
                          repo=toy_tree)
    assert line["correct"] is False
    row = checks_of(line)["served_logit_worst_gap"]
    assert not row["ok"] and row["value"] >= 4 * row["limit"]
    assert all(r["ok"] for name, r in checks_of(line).items()
               if name != "served_logit_worst_gap")


@pytest.mark.parametrize("fault,seen", [("f32+no_routed", True),
                                        ("f32+expert0_zeroed", False)])
def test_a_fault_in_the_routed_experts_is_read(moe_run, fault, seen):
    """Planted in the reference that stands in the program's place, as
    ``tools/served_readings.py`` reads them on the chip.  The routed
    experts' sum left out is not ``correct`` (on every one of six toy
    seeds); one expert's output zeroed moves the reading (to between one
    and five times the sound run's gap, by which tokens the timed window
    served), but a worst-token limit does not always see it (four toy
    seeds of six; not on the chip: PERF.md section 7)."""
    from perfbench.tools import served_readings

    ctx, out = moe_run
    _, reference, _, _ = ctx.arch
    whole = reference.served_logits
    (spelling, checks), = served_readings.readings(ctx, out, [fault])
    assert reference.served_logits is whole and spelling == fault
    row, = checks.rows
    sound = checks_of({"checks": out["checks"].rows})[
        "served_logit_worst_gap"]["value"]
    assert row["value"] != sound
    if seen:
        assert row["value"] > row["limit"] and not checks.correct


def test_the_reference_abstains_on_tied_tokens_in_float32_alone(toy_tree):
    """``route_tie_margin``: a token whose last chosen and first not
    chosen score lie closer than that in any expert layer gets a row of
    zeros from the float32 reference, so any served token reads a gap of
    0 there; the other rows are what they were, and the control's and
    the faults' rows are never zeroed."""
    ctx = context(toy_tree, "tiny-moe-backlog", 5, 1.0)
    _, reference, _, _ = ctx.arch
    cfg = {**ctx.config, "route_tie_margin": 0.0}
    seqs = [np.arange(3, 43, dtype=np.int32) % cfg["vocab_size"]]
    rows = [np.arange(8, 40)]
    plain, = reference.served_logits(cfg, 5, "bfloat16", seqs, rows, "f32")
    assert (np.abs(plain).max(axis=1) > 0).all()
    tied_cfg = {**cfg, "route_tie_margin": 0.05}
    got, = reference.served_logits(tied_cfg, 5, "bfloat16", seqs, rows,
                                   "f32")
    tied = np.abs(got).max(axis=1) == 0
    assert 2 <= tied.sum() <= len(tied) - 2
    np.testing.assert_array_equal(got[~tied], plain[~tied])
    # the margins are the reference's own: the same rows from the layers
    x = reference.embed(seqs[0], reference._f32(
        reference.weights.global_weights(
            cfg, reference.weights.seed_key(5), "bfloat16")), cfg)
    want = np.zeros(len(seqs[0]), bool)
    for i in range(cfg["num_hidden_layers"]):
        dense = reference.weights.is_dense(cfg, i)
        w = reference.weights.layer_weights(
            cfg, reference.weights.seed_key(5), i, "bfloat16", dense)
        x, margin = reference.layer_and_margin(x, w, cfg, dense)
        want |= np.asarray(margin) < 0.05
    np.testing.assert_array_equal(tied, want[rows[0]])
    low, = reference.served_logits(tied_cfg, 5, "bfloat16", seqs, rows,
                                   "fp8")
    assert (np.abs(low).max(axis=1) > 0).all()
