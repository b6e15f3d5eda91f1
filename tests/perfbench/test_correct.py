"""``correct`` comes out true for the toy cells and false for the control
and for each planted fault.

The toy cells are driven through ``run_cell``: everything of a run except
the look for a chip.  The control is the plain reference put in the
program's place and computed in float8_e4m3fn, the precision below the
bfloat16 that the configurations state; the faults are planted in the
program, underneath the timed path.  The limits are the toy's own
(``tiny/limits``): at this size the readings differ from the chip's, which
PERF.md gives for the real cells.
"""

import json
import types

import numpy as np
import pytest

from perfbench import common, run as bench, trafficgen
from perfbench.kinds import serve, train_single

from toybench import CPU_PEAKS


def checks_of(line):
    return {c["name"]: c for c in line["checks"]}


def context(tree, cell_name, seed, seconds):
    manifest = bench.load_manifest(tree)
    cell = bench.find(manifest["workloads"], cell_name, "workload")
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    return bench.make_context(manifest, cell, args, CPU_PEAKS, tree)


# ---- training ----------------------------------------------------------

@pytest.fixture(scope="module")
def train_run(toy_tree):
    ctx = context(toy_tree, "tiny-train", 2**31 + 17, 0.5)
    return ctx, train_single.run(ctx)


def test_toy_train_cell_is_correct(train_run):
    _, out = train_run
    assert out["checks"].correct, out["checks"].lines()
    assert out["attempted"] > 0 and out["end_to_end"]["train_mfu"] > 0


@pytest.mark.parametrize("kw", [{"precision": "fp8"},
                                {"fault": "half_batch"}],
                         ids=["control_fp8", "fault_half_batch"])
def test_train_control_and_fault_in_the_reference_are_not_correct(
        train_run, kw):
    ctx, out = train_run
    _, reference, _, _ = ctx.arch
    L = out["layers"]
    got = reference.train_readings(
        ctx.config, ctx.seed, L["batches"],
        ctx.traffic["trainer_args"]["learning_rate"], **kw)
    checks = common.Checks(ctx.limits)
    train_single.compare(got, L["reference"], checks)
    assert not checks.correct, checks.lines()


def test_the_feed_hands_out_whole_segments_and_counts_them(toy_tree):
    ctx = context(toy_tree, "tiny-train", 2**31 + 19, 0.0)
    traffic, vocab = ctx.traffic, ctx.config["vocab_size"]
    meter = types.SimpleNamespace(compiles=0)
    feed = train_single.build_feed(traffic, ctx.seed, vocab, 0.0, None,
                                   meter)
    n, b = traffic["segment_batches"], traffic["batch_size"]
    first, = list(feed.epoch_segments(0))
    assert len(first) == n * b
    want = np.concatenate([trafficgen.lm_batch(traffic, ctx.seed, i, vocab)
                           for i in range(n)])
    np.testing.assert_array_equal(first["features"], want[:, :-1])
    np.testing.assert_array_equal(first["label"], want[:, 1:])
    assert len({row.tobytes() for row in want}) == len(want)
    assert len(list(feed.epoch_segments(1))) == traffic["warm_segments"]
    # with no seconds to fill, the window still holds one segment
    assert len(list(feed.epoch_segments(2))) == 1
    assert feed.made == [1, traffic["warm_segments"], 1]
    assert len(feed.asked) == 3 and feed.asked == sorted(feed.asked)
    np.testing.assert_array_equal(np.concatenate(feed.check_batches), want)


def test_a_trainer_that_stops_handing_its_state_over_is_refused(
        toy_tree, monkeypatch):
    from distkeras_tpu import trainers

    real = trainers.SingleTrainer._train

    def silent(self, *a, **kw):
        self._maybe_save = lambda state, cursor: None
        return real(self, *a, **kw)

    monkeypatch.setattr(trainers.SingleTrainer, "_train", silent)
    ctx = context(toy_tree, "tiny-train", 3, 0.1)
    with pytest.raises(RuntimeError, match="_maybe_save"):
        train_single.run(ctx)


def _break_step(monkeypatch, breaker):
    from distkeras_tpu import trainers

    real = trainers.make_train_step

    def broken_builder(*a, **kw):
        return breaker(real(*a, **kw))

    monkeypatch.setattr(trainers, "make_train_step", broken_builder)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        toy_tree, monkeypatch, capsys):
    def breaker(step):
        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return unchanged

    _break_step(monkeypatch, breaker)
    line = bench.run_cell("tiny-train", 5, 0.3, 0, CPU_PEAKS, repo=toy_tree)
    assert line["correct"] is False
    rows = checks_of(line)
    assert rows["moment_norm_worst_leaf_gap"]["value"] == pytest.approx(1.0)
    assert rows["change_norm_worst_leaf_gap"]["value"] == pytest.approx(1.0)
    # each number compared is printed beside its limit on standard error
    assert "check moment_norm_worst_leaf_gap:" in capsys.readouterr().err


def test_half_of_the_batch_left_out_is_not_correct(toy_tree, monkeypatch):
    def breaker(step):
        def half(state, batch):
            n = next(iter(batch.values())).shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half

    _break_step(monkeypatch, breaker)
    line = bench.run_cell("tiny-train", 6, 0.3, 0, CPU_PEAKS, repo=toy_tree)
    assert line["correct"] is False
    assert not checks_of(line)["moment_norm_worst_leaf_gap"]["ok"]


# ---- serving -----------------------------------------------------------

@pytest.fixture(scope="module")
def serve_run(toy_tree):
    ctx = context(toy_tree, "tiny-steady", 2**31 + 23, 1.0)
    return ctx, serve.run(ctx)


def test_toy_serve_cell_is_correct(serve_run):
    ctx, out = serve_run
    assert out["checks"].correct, out["checks"].lines()
    e = out["end_to_end"]
    assert out["failed"] == 0 and out["attempted"] == round(
        ctx.traffic["rate_per_s"] * 1.0)
    assert e["ttft_requests"] == out["attempted"]
    assert e["tokens_in_window"] > 0 and e["gaps"] > 0
    rows = {r["name"]: r for r in out["checks"].rows}
    assert rows["served_logit_worst_gap"]["not_first"] >= 0
    # every bucket class that finished a request is in the sample
    assert rows["served_logit_worst_gap"]["requests"] >= 3
    assert rows["served_logit_worst_gap"]["tokens"] >= 200


def test_serve_control_in_fp8_is_not_correct(serve_run):
    ctx, out = serve_run
    _, reference, _, _ = ctx.arch
    L = out["layers"]
    # the control is read on the run's own sample and held to the run's
    # own limit; the toy's traffic asks for a sample of 30 requests
    # (``check_requests``) because its logits are a fifth as wide as the
    # real model's and five requests often hold no token that fp8 moves
    checks = common.Checks(ctx.limits)
    serve.check_served(checks, reference, ctx.config, ctx.seed, L["picked"],
                       L["served"], precision="fp8")
    row, = checks.rows
    assert row["tokens"] >= 200 and row["not_first"] > 0
    assert not checks.correct, checks.lines()


def test_tokens_are_placed_on_the_steps_that_produced_them(serve_run):
    _, out = serve_run
    served = out["layers"]["served"]
    for r in served.requests:
        if r.index not in served.results:
            continue
        res, f = served.results[r.index]
        times = served.deliveries(r.index)
        assert len(times) == len(res["tokens"])
        assert times == sorted(times)
        if "error" not in res:
            assert len(res["tokens"]) == r.budget
            assert times[-1] == served.end[f]
            # one token a step after the first: no stamp is shared by more
            # than the first two tokens
            assert len(set(times)) >= len(times) - 1


def test_a_token_altered_where_it_is_produced_is_not_correct(
        toy_tree, monkeypatch):
    from distkeras_tpu.serving import DecodeEngine

    real = DecodeEngine._finish

    def altered(self, pool, slot):
        res = real(self, pool, slot)
        toks = np.array(res["tokens"])
        toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % self.vocab_size
        res["tokens"] = toks
        return res

    monkeypatch.setattr(DecodeEngine, "_finish", altered)
    line = bench.run_cell("tiny-steady", 9, 1.0, 0, CPU_PEAKS, repo=toy_tree)
    assert line["correct"] is False
    assert not checks_of(line)["served_logit_worst_gap"]["ok"]
    # the numbers compared come last in the result's line
    assert list(line)[-1] == "checks"
    assert json.loads(json.dumps(line))["checks"][0]["limit"] == 0.004


def test_run_refuses_a_machine_without_the_chip(monkeypatch):
    import jax

    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit):
        bench.require_chips(1)
