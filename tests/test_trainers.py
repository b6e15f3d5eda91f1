"""Trainer family: convergence smoke tests on learnable synthetic data,
faithful-vs-fast fidelity equivalence, staleness telemetry, mesh placement
(SURVEY.md §4: the rebuild's analogue of the reference's MNIST-notebook
integration tests, run on the 8-virtual-device CPU mesh)."""

import jax
import numpy as np
import pytest

from distkeras_tpu.data import datasets
from distkeras_tpu.models import model_config
from distkeras_tpu.trainers import (
    ADAG,
    AEASGD,
    DOWNPOUR,
    AveragingTrainer,
    DynSGD,
    EAMSGD,
    EnsembleTrainer,
    SingleTrainer,
    SyncTrainer,
)

MLP = model_config("mlp", (8,), num_classes=4, hidden=(32,))
DATA = datasets.synthetic_classification(2048, (8,), 4, seed=0)


def _first_last(history_key, trainer):
    h = trainer.history[history_key]
    return h[0], h[-1]


def test_single_trainer_converges():
    t = SingleTrainer(MLP, worker_optimizer="adam", learning_rate=3e-3,
                      batch_size=64, num_epoch=3)
    variables = t.train(DATA)
    first, last = _first_last("epoch_loss", t)
    assert last < first * 0.7, t.history
    assert t.training_time > 0
    assert "params" in variables


def test_sync_trainer_uses_mesh_and_converges(devices):
    t = SyncTrainer(MLP, num_workers=8, worker_optimizer="adam",
                    learning_rate=3e-3, batch_size=16, num_epoch=3)
    t.train(DATA)
    first, last = _first_last("epoch_loss", t)
    assert last < first * 0.7, t.history
    assert t.num_workers == 8


@pytest.mark.parametrize("cls", [DOWNPOUR, ADAG, DynSGD, AEASGD, EAMSGD])
@pytest.mark.parametrize("fidelity", ["faithful", "fast"])
def test_async_family_converges(cls, fidelity):
    kwargs = dict(num_workers=4, communication_window=4, batch_size=32,
                  num_epoch=3, learning_rate=0.05, fidelity=fidelity)
    if cls in (AEASGD, EAMSGD):
        kwargs["rho"] = 5.0
        kwargs["learning_rate"] = 0.02
    t = cls(MLP, **kwargs)
    t.train(DATA)
    losses = t.history["round_loss"]
    assert losses[-1] < losses[0] * 0.8, (cls.__name__, losses[:3],
                                          losses[-3:])
    # staleness telemetry: every round records a permutation of 0..W-1
    stal = np.asarray(t.history["staleness"])
    assert stal.shape[1] == 4
    assert np.array_equal(np.sort(stal[0]), np.arange(4))


def test_faithful_and_fast_center_match_for_linear_rules():
    """One round of DOWNPOUR: the fast path's center must equal the
    faithful path's exactly (the sum of deltas is order-free)."""
    results = {}
    for fidelity in ("faithful", "fast"):
        t = DOWNPOUR(MLP, num_workers=4, communication_window=2,
                     batch_size=32, num_epoch=1, learning_rate=0.05,
                     fidelity=fidelity, seed=3)
        # limit to exactly one round of data
        sub = DATA.take(4 * 2 * 32)
        t.train(sub)
        results[fidelity] = jax.device_get(
            t.trained_variables["params"])
    flat_a = jax.tree_util.tree_leaves(results["faithful"])
    flat_b = jax.tree_util.tree_leaves(results["fast"])
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def test_elastic_fast_faithful_gap_bounded():
    """Quantify the elastic fast-vs-faithful gap (VERDICT.md round-1 Weak
    #3: the fast path is exact-in-expectation only for the elastic family
    — only pull timing differs).  On identical data/seed the trained
    parameters must agree within a small relative L2 bound, and both must
    converge."""
    results = {}
    for fidelity in ("faithful", "fast"):
        t = AEASGD(MLP, num_workers=4, communication_window=2,
                   batch_size=32, num_epoch=2, rho=2.5,
                   learning_rate=0.02, fidelity=fidelity, seed=5)
        t.train(DATA.take(1024))
        results[fidelity] = t
    for t in results.values():
        losses = t.history["round_loss"]
        assert losses[-1] < losses[0], losses
    fa = jax.tree_util.tree_leaves(
        results["faithful"].trained_variables["params"])
    fb = jax.tree_util.tree_leaves(
        results["fast"].trained_variables["params"])
    num = np.sqrt(sum(float(np.sum((a - b) ** 2))
                      for a, b in zip(fa, fb)))
    den = np.sqrt(sum(float(np.sum(np.square(a))) for a in fa))
    rel_gap = num / den
    # pull-timing skew is O(alpha) per round; empirically ~1e-2 here
    assert rel_gap < 0.05, rel_gap


def test_dynsgd_staleness_scaling_changes_result():
    """DynSGD must differ from DOWNPOUR on identical data/seed (staleness
    scaling is real)."""
    common = dict(num_workers=4, communication_window=2, batch_size=32,
                  num_epoch=1, learning_rate=0.05, seed=0)
    a = DOWNPOUR(MLP, **common)
    b = DynSGD(MLP, **common)
    a.train(DATA.take(1024))
    b.train(DATA.take(1024))
    la = jax.tree_util.tree_leaves(a.trained_variables["params"])
    lb = jax.tree_util.tree_leaves(b.trained_variables["params"])
    assert any(not np.allclose(x, y) for x, y in zip(la, lb))


def test_ensemble_trainer_returns_list():
    t = EnsembleTrainer(MLP, num_models=2, worker_optimizer="adam",
                        learning_rate=3e-3, batch_size=32, num_epoch=1)
    models = t.train(DATA)
    assert isinstance(models, list) and len(models) == 2
    la = jax.tree_util.tree_leaves(models[0]["params"])
    lb = jax.tree_util.tree_leaves(models[1]["params"])
    assert any(not np.allclose(x, y) for x, y in zip(la, lb))


def test_averaging_trainer_averages():
    t = AveragingTrainer(MLP, num_workers=2, worker_optimizer="adam",
                         learning_rate=3e-3, batch_size=32, num_epoch=1)
    variables = t.train(DATA)
    assert "params" in variables


def test_async_trainer_with_dropout_model():
    """Dropout rngs flow per worker (distinct streams)."""
    cfg = model_config("mlp", (8,), num_classes=4, hidden=(32,),
                       dropout_rate=0.3)
    t = ADAG(cfg, num_workers=2, communication_window=2, batch_size=32,
             num_epoch=1, learning_rate=0.05)
    t.train(DATA.take(512))
    assert len(t.history["round_loss"]) >= 1


def test_errors_on_tiny_dataset():
    t = ADAG(MLP, num_workers=4, communication_window=8, batch_size=64,
             num_epoch=1)
    with pytest.raises(ValueError):
        t.train(DATA.take(128))


def test_member_parallel_ensemble_on_mesh():
    """Members train concurrently inside one vmapped program sharded
    over the 8-device mesh (round-1 ran them sequentially)."""
    t = EnsembleTrainer(MLP, num_models=8, worker_optimizer="adam",
                        learning_rate=5e-3, batch_size=16, num_epoch=2)
    models = t.train(DATA)
    assert len(models) == 8
    assert len(t.history["member_loss"][-1]) == 8
    first, last = t.history["epoch_loss"][0], t.history["epoch_loss"][-1]
    assert last < first, t.history["epoch_loss"]
    # distinct inits -> distinct members
    la = jax.tree_util.tree_leaves(models[0]["params"])
    lb = jax.tree_util.tree_leaves(models[7]["params"])
    assert any(not np.allclose(x, y) for x, y in zip(la, lb))


def test_learning_rate_schedules():
    from distkeras_tpu.workers import resolve_schedule

    sched = resolve_schedule({"schedule": "cosine", "init_value": 0.1,
                              "decay_steps": 10})
    assert abs(float(sched(0)) - 0.1) < 1e-7
    assert float(sched(10)) < 1e-7
    with pytest.raises(KeyError):
        resolve_schedule({"schedule": "nope"})

    # end-to-end: a dict schedule through a trainer converges
    data = datasets.synthetic_classification(512, (8,), 4, seed=0)
    cfg = model_config("mlp", (8,), num_classes=4, hidden=(16,))
    t = SingleTrainer(cfg, worker_optimizer="momentum", batch_size=32,
                      num_epoch=3,
                      learning_rate={"schedule": "warmup_cosine",
                                     "init_value": 0.0,
                                     "peak_value": 0.1,
                                     "warmup_steps": 8,
                                     "decay_steps": 48})
    t.train(data)
    losses = t.history["epoch_loss"]
    assert losses[-1] < losses[0], losses

    # the elastic family needs a scalar lr for alpha = lr * rho
    with pytest.raises(ValueError, match="scalar learning_rate"):
        AEASGD(cfg, num_workers=2,
               learning_rate={"schedule": "cosine", "init_value": 0.1,
                              "decay_steps": 10}).allocate_rule()


def test_numpy_scalar_learning_rate_passes_through():
    from distkeras_tpu.workers import resolve_optimizer, resolve_schedule
    import jax.numpy as jnp

    assert resolve_schedule(np.float32(1e-3)) == np.float32(1e-3)
    resolve_optimizer("adam", np.float32(1e-3))
    resolve_optimizer("sgd", jnp.asarray(1e-2))  # 0-d array scalar
    t = AEASGD(MLP, num_workers=2, learning_rate=np.float32(0.01))
    assert abs(t.alpha - 0.05) < 1e-7  # rho=5.0 default


def test_profile_dir_writes_trace(tmp_path):
    data = datasets.synthetic_classification(128, (8,), 4, seed=0)
    t = SingleTrainer(MLP, batch_size=32, num_epoch=1,
                      learning_rate=0.05, profile_dir=str(tmp_path))
    t.train(data)
    profiles = list(tmp_path.rglob("*.xplane.pb"))
    assert profiles, list(tmp_path.rglob("*"))


def test_lr_law_guardrail():
    """VERDICT r4 #7: the measured per-family lr laws (PARITY.md) are
    enforced by the library, not just documented — a config whose
    effective per-round lr exceeds the measured stability scale warns
    (with the law and the fix), lr_law='scale' applies the law, and
    lr_law='off' silences it."""
    import warnings

    cfg = model_config("mlp", (4,), num_classes=2, hidden=(4,))

    def caught(make):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            make()
        return [str(x.message) for x in w
                if issubclass(x.category, UserWarning)]

    # DOWNPOUR at the PARITY collapse config (W*w = 16, lr 0.05) warns
    msgs = caught(lambda: DOWNPOUR(
        cfg, num_workers=4, communication_window=4,
        learning_rate=0.05))
    assert len(msgs) == 1 and "num_workers * communication_window" \
        in msgs[0], msgs
    # every family's law names its own factor
    assert "num_workers" in caught(lambda: ADAG(
        cfg, num_workers=8, learning_rate=0.05))[0]
    assert "communication_window" in caught(lambda: DynSGD(
        cfg, communication_window=8, learning_rate=0.05))[0]
    assert "momentum" in caught(lambda: EAMSGD(
        cfg, num_workers=2, learning_rate=0.05))[0]
    # the elastic exchange is lr-neutral (measured): AEASGD never warns
    assert caught(lambda: AEASGD(
        cfg, num_workers=8, communication_window=8,
        learning_rate=0.05)) == []
    # law-scaled configs are quiet
    assert caught(lambda: DOWNPOUR(
        cfg, num_workers=4, communication_window=4,
        learning_rate=0.05 / 16)) == []
    # scale applies the family law; off silences
    t = DOWNPOUR(cfg, num_workers=4, communication_window=4,
                          learning_rate=0.05, lr_law="scale")
    assert abs(t.learning_rate - 0.05 / 16) < 1e-12
    assert caught(lambda: DOWNPOUR(
        cfg, num_workers=4, communication_window=4,
        learning_rate=0.05, lr_law="off")) == []
    with pytest.raises(ValueError, match="lr_law"):
        DOWNPOUR(cfg, lr_law="sometimes")


def test_commit_overlap_pipelined_round():
    """VERDICT r4 #2: commit_overlap=True pipelines round k-1's commit
    scan against round k's window (one jitted program, independent
    subgraphs).  Semantics: uniform +W staleness, which must (a) be
    reported in the telemetry, (b) still converge on par with the
    in-order emulator, and (c) end every epoch fully flushed."""
    common = dict(num_workers=4, communication_window=2, batch_size=32,
                  num_epoch=3, learning_rate=0.0125, seed=0)
    from distkeras_tpu.evaluators import evaluate_model

    base = ADAG(MLP, **common)
    acc_base = evaluate_model(base.model, base.train(DATA),
                              DATA)["accuracy"]
    over = ADAG(MLP, commit_overlap=True, **common)
    acc_over = evaluate_model(over.model, over.train(DATA),
                              DATA)["accuracy"]
    # same data/budget: the +W staleness costs at most a few points
    assert acc_over >= acc_base - 0.05, (acc_over, acc_base)
    # telemetry reports the TRUE commit depth: one full round behind
    assert sorted(over.history["staleness"][0]) == [4, 5, 6, 7]
    assert sorted(base.history["staleness"][0]) == [0, 1, 2, 3]
    # the trained center includes the final (flushed) round: the PS
    # clock counts every commit
    rounds = len(over.history["round_loss"])
    assert int(over.parameter_server_state.clock) == 4 * rounds

    # staleness-aware rule runs too (staleness_offset path)
    dyn = DynSGD(MLP, commit_overlap=True, **common)
    dyn.train(DATA)
    assert sorted(dyn.history["staleness"][0]) == [4, 5, 6, 7]


def test_commit_overlap_validation():
    """The pipeline exists only where it is semantically sound: the
    elastic family's commit reads the committing worker's current
    locals (read-modify-write against the window — nothing to
    overlap), checkpointing would snapshot a center missing the
    pending round, and the fast/host fidelities have no separate
    commit phase."""
    common = dict(num_workers=2, communication_window=2, batch_size=32,
                  num_epoch=1, learning_rate=0.01)
    with pytest.raises(ValueError, match="elastic|delta"):
        AEASGD(MLP, commit_overlap=True, **common).train(DATA)
    with pytest.raises(ValueError, match="fidelity"):
        DOWNPOUR(MLP, commit_overlap=True, fidelity="fast", **common)
    with pytest.raises(ValueError, match="checkpoint"):
        DOWNPOUR(MLP, commit_overlap=True, checkpoint_every_rounds=2,
                 **common)
    with pytest.raises(ValueError, match="resume"):
        DOWNPOUR(MLP, commit_overlap=True, **common).train(
            DATA, resume_from="/tmp/nonexistent")


def test_single_trainer_spans_in_a_profiler_session(tmp_path):
    """A traced ``SingleTrainer.train()`` shows where its host loop
    spends a chunk: the five ``dkt:`` spans, with the epoch and the
    chunk's steps as stats, inside ``dkt:train``."""
    from profiled import Profiled

    t = SingleTrainer(MLP, batch_size=64, num_epoch=2)
    with Profiled(tmp_path) as prof:
        t.train(DATA)
    (root,) = prof.named("train")
    assert root["stats"] == {"trainer": "SingleTrainer"}
    steps = 2048 // 64
    for name in ("segment_wait", "stack_and_put", "chunk_dispatch",
                 "loss_fetch", "epoch_end"):
        spans = prof.named(name)
        assert spans, name
        assert all(prof.parent(s) is root for s in spans), name
        assert sorted({s["stats"]["epoch"] for s in spans}) == [0, 1], name
    # one segment an epoch (and the pull that finds the epoch's end),
    # one chunk of 32 steps (SCAN_CHUNK is 64)
    assert len(prof.named("segment_wait")) == 4
    for name in ("chunk_dispatch", "loss_fetch", "epoch_end"):
        assert [s["stats"]["steps"] for s in prof.named(name)] == \
            [steps, steps], name
    d, f = prof.named("chunk_dispatch")[0], prof.named("loss_fetch")[0]
    assert d["end"] <= f["start"]
