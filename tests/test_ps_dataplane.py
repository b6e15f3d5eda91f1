"""On-chip compiled PS data plane (ISSUE 12 + 16): mesh-tier parity
against the emulated closed form, the one-compile-per-(round-shape x
comm-config) guard, the partition-rule resolver, the tier registry's
validation surface, and the ISSUE 16 comm-compression / async-dispatch
contracts:

* on-chip codec law parity vs the host ``compression.py`` oracles —
  int8 ``q`` is BITWISE equal and the scale matches to rtol 1e-6 (the
  host codec computes ``amax/127`` in float64, the device in float32);
  the bf16 delta cast is the exact ``Bf16Codec`` law;
* the int8 round end-to-end equals the closed-form oracle "fast round
  run from the dequantized center, delta folded into the exact
  center" to the standard 2e-5 parity tolerance (exact because the
  on-chip ``segment_max`` + ``pmax`` reproduces the global per-leaf
  ``max|x|`` bit-for-bit);
* the metrics ring + async driver is byte-identical to the eager
  ``sync=True`` oracle under ``metrics_every in {1, 4}``.

Parity runs on the MLP: matmuls are batching-stable on CPU, so the
mesh tier's per-device window must match the emulated tier's vmapped
window to float tolerance.  (Convs are NOT batching-stable on the CPU
backend — the flagship smoke documents that.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import mesh as mesh_lib
from distkeras_tpu import telemetry
from distkeras_tpu.data import datasets
from distkeras_tpu.models import model_config
from distkeras_tpu.parallel import ps_dataplane
from distkeras_tpu.parallel.ps_emulator import (
    commit_permutation,
    flush_pending,
    make_pipelined_round_fn,
    make_round_fn,
)
from distkeras_tpu.parallel.tiers import TIERS, resolve_tier, tiers_with
from distkeras_tpu.parallel.update_rules import RULES
from distkeras_tpu.trainers import AEASGD, DOWNPOUR
from distkeras_tpu.workers import (
    TrainState,
    make_train_step,
    resolve_optimizer,
)
from jax.sharding import PartitionSpec as P

MLP = model_config("mlp", (8,), num_classes=4, hidden=(32,))
DATA = datasets.synthetic_classification(2048, (8,), 4, seed=0)


def _setup(rule_name, W, rounds=3, window=2, batch=4):
    """Shared harness: model, rule, seeded batches/permutations, and
    fresh emulated + mesh states started from the same center."""
    import flax.linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.Dense(16)(x)
            x = nn.relu(x)
            return nn.Dense(4)(x)

    model = Tiny()
    tx = resolve_optimizer("momentum", 0.05)
    rule = RULES[rule_name]()
    variables = model.init(jax.random.key(0), jnp.ones((2, 8)))
    center = variables["params"]
    step = make_train_step(model, "sparse_categorical_crossentropy", tx)

    def make_worker(rng):
        return TrainState.create({"params": center}, tx, rng)

    keys = jax.random.split(jax.random.key(1), W)
    rngd = np.random.RandomState(0)
    batches = [
        {"features": jnp.asarray(rngd.randn(W, window, batch, 8),
                                 jnp.float32),
         "label": jnp.asarray(rngd.randint(0, 4, (W, window, batch)),
                              jnp.int32)}
        for _ in range(rounds)]
    pkey = jax.random.key(2)
    perms = []
    for _ in range(rounds):
        pkey, sub = jax.random.split(pkey)
        perms.append(commit_permutation(sub, W))
    ws = jax.vmap(make_worker)(keys)
    ps = rule.init_state(center)
    return rule, step, center, ws, ps, batches, perms, make_worker, keys


def _assert_tree_close(a, b, msg=""):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=2e-5, atol=1e-6, err_msg=msg)


@pytest.mark.parametrize("rule_name", ["downpour", "adag", "dynsgd"])
@pytest.mark.parametrize("W", [2, 4])
def test_mesh_round_matches_fast(rule_name, W):
    (rule, step, center, ws, ps, batches, perms, make_worker,
     keys) = _setup(rule_name, W)
    rf = jax.jit(make_round_fn(rule, step, "fast"))
    ref_metrics = []
    for b, p in zip(batches, perms):
        ps, ws, met = rf(ps, ws, b, p)
        ref_metrics.append(jax.device_get(met))

    placement = mesh_lib.place_workers(W)
    dp = ps_dataplane.MeshDataplane(rule, step, placement.mesh, center)
    mps, mws = dp.to_device(rule.init_state(center),
                            jax.vmap(make_worker)(keys))
    row = mesh_lib.batch_sharding(placement.mesh)
    rep = mesh_lib.replicated_sharding(placement.mesh)
    drv = ps_dataplane.MeshRoundDriver(dp, mps, mws, sync=True)
    for (b, p), ref in zip(zip(batches, perms), ref_metrics):
        drv.dispatch(jax.device_put(b, row), jax.device_put(p, rep))
        (met,) = drv.poll()
        _assert_tree_close(ref["loss"], met["loss"], rule_name)
        _assert_tree_close(ref["grad_norm"], met["grad_norm"],
                           rule_name)
        np.testing.assert_array_equal(np.asarray(ref["staleness"]),
                                      np.asarray(met["staleness"]))
    mps = drv.mps
    assert int(mps.clock) == int(ps.clock)
    _assert_tree_close(ps.center, dp.center(mps), rule_name)
    # exported state round-trips into the public PSState shape
    exported = dp.export_ps_state(mps)
    _assert_tree_close(ps.center, exported.center)
    assert int(exported.clock) == int(ps.clock)


@pytest.mark.parametrize("rule_name", ["downpour", "adag", "dynsgd"])
@pytest.mark.parametrize("W", [2, 4])
def test_mesh_pipelined_matches_emulated(rule_name, W):
    """The +W-offset pipelined contract, including the final
    ``flush_pending`` drain of the carried commit."""
    (rule, step, center, ws, ps, batches, perms, make_worker,
     keys) = _setup(rule_name, W)
    rf = jax.jit(make_pipelined_round_fn(rule, step))
    pend = jax.tree_util.tree_map(jnp.zeros_like, ws.params)
    pperm, valid = jnp.arange(W), jnp.asarray(False)
    ref_metrics = []
    for b, p in zip(batches, perms):
        ps, ws, met, pend, pperm, valid = rf(ps, ws, b, p, pend,
                                             pperm, valid)
        ref_metrics.append(jax.device_get(met))
    ps = flush_pending(rule, ps, pend, pperm, W)

    placement = mesh_lib.place_workers(W)
    dp = ps_dataplane.MeshDataplane(rule, step, placement.mesh, center,
                                    pipelined=True)
    mps, mws = dp.to_device(rule.init_state(center),
                            jax.vmap(make_worker)(keys))
    row = mesh_lib.batch_sharding(placement.mesh)
    rep = mesh_lib.replicated_sharding(placement.mesh)
    drv = ps_dataplane.MeshRoundDriver(dp, mps, mws, sync=True)
    for (b, p), ref in zip(zip(batches, perms), ref_metrics):
        drv.dispatch(jax.device_put(b, row), jax.device_put(p, rep))
        (met,) = drv.poll()
        _assert_tree_close(ref["loss"], met["loss"], rule_name)
        np.testing.assert_array_equal(np.asarray(ref["staleness"]),
                                      np.asarray(met["staleness"]))
    drv.flush_pipeline()
    mps = drv.mps
    assert int(mps.clock) == int(ps.clock)
    _assert_tree_close(ps.center, dp.center(mps), rule_name)


def test_one_compiled_program_per_round_shape():
    """The public trace counter proves the whole round is ONE compiled
    program reused across rounds; a new worker count is a new shape
    and exactly one more trace."""
    tel = telemetry.enable()
    try:
        for i, W in enumerate((4, 2)):
            (rule, step, center, ws, ps, batches, perms, make_worker,
             keys) = _setup("dynsgd", W)
            placement = mesh_lib.place_workers(W)
            dp = ps_dataplane.MeshDataplane(rule, step, placement.mesh,
                                            center)
            mps, mws = dp.to_device(rule.init_state(center),
                                    jax.vmap(make_worker)(keys))
            row = mesh_lib.batch_sharding(placement.mesh)
            rep = mesh_lib.replicated_sharding(placement.mesh)
            ring = dp.init_ring()
            for r, (b, p) in enumerate(zip(batches, perms)):
                mps, mws, ring = dp.round(mps, mws,
                                          jax.device_put(b, row),
                                          jax.device_put(p, rep),
                                          ring, dp.slot_index(r))
            counters = tel.metrics.snapshot()["counters"]
            key = 'ps_round_compiles_total{fidelity="mesh"}'
            assert counters.get(key) == i + 1, counters
    finally:
        telemetry.disable()


def test_one_compiled_program_per_comm_config():
    """Each comm knob combination is its own program (the knobs change
    the lowered collectives), but cycling the metrics ring slot — a
    traced replicated scalar — must NOT retrace."""
    tel = telemetry.enable()
    try:
        (rule, step, center, ws, ps, batches, perms, make_worker,
         keys) = _setup("downpour", 2, rounds=3)
        placement = mesh_lib.place_workers(2)
        row = mesh_lib.batch_sharding(placement.mesh)
        rep = mesh_lib.replicated_sharding(placement.mesh)
        configs = [{}, {"comm_dtype": "bfloat16"},
                   {"comm_codec": "int8"},
                   {"comm_dtype": "bfloat16", "comm_codec": "int8",
                    "metrics_every": 2}]
        for i, kw in enumerate(configs):
            dp = ps_dataplane.MeshDataplane(rule, step, placement.mesh,
                                            center, **kw)
            mps, mws = dp.to_device(rule.init_state(center),
                                    jax.vmap(make_worker)(keys))
            ring = dp.init_ring()
            for r, (b, p) in enumerate(zip(batches, perms)):
                mps, mws, ring = dp.round(
                    mps, mws, jax.device_put(b, row),
                    jax.device_put(p, rep), ring, dp.slot_index(r))
            counters = tel.metrics.snapshot()["counters"]
            key = 'ps_round_compiles_total{fidelity="mesh"}'
            assert counters.get(key) == i + 1, counters
    finally:
        telemetry.disable()


def test_trainer_mesh_matches_fast_end_to_end():
    def run(fidelity, **kw):
        t = DOWNPOUR(MLP, fidelity=fidelity, num_workers=4,
                     communication_window=4, batch_size=32,
                     num_epoch=1, learning_rate=0.005, seed=3, **kw)
        return t, t.train(DATA)

    tf_, vf = run("fast")
    tm, vm = run("mesh")
    _assert_tree_close(vf["params"], vm["params"])
    assert tf_.history["staleness"] == tm.history["staleness"]
    np.testing.assert_allclose(tf_.history["round_loss"],
                               tm.history["round_loss"],
                               rtol=2e-5, atol=1e-6)
    _assert_tree_close(tf_.parameter_server_state.center,
                       tm.parameter_server_state.center)
    assert int(tf_.parameter_server_state.clock) == \
        int(tm.parameter_server_state.clock)


def test_trainer_mesh_overlap_matches_faithful_pipelined():
    def run(fidelity):
        t = DOWNPOUR(MLP, fidelity=fidelity, num_workers=4,
                     communication_window=4, batch_size=32,
                     num_epoch=1, learning_rate=0.005, seed=3,
                     commit_overlap=True)
        return t, t.train(DATA)

    tf_, vf = run("faithful")
    tm, vm = run("mesh")
    _assert_tree_close(vf["params"], vm["params"])
    assert tf_.history["staleness"] == tm.history["staleness"]


# ---- ISSUE 16: on-chip comm compression -------------------------------

def test_int8_law_matches_host_codec():
    """The on-chip quantizer IS the ``Int8Codec`` law: ``q`` bitwise
    equal; scale to rtol 1e-6 (f32 vs the host codec's f64 ``amax/127``
    — the one documented divergence)."""
    from distkeras_tpu.parallel.compression import Int8Codec

    rng = np.random.RandomState(3)
    cases = [rng.randn(257).astype(np.float32) * 0.37,
             np.zeros(16, np.float32),           # all-zero -> scale 1.0
             np.asarray([127.0, -127.0, 1e-8], np.float32)]
    for arr in cases:
        q, s = jax.device_get(
            ps_dataplane.quantize_int8(jnp.asarray(arr)))
        enc = Int8Codec().encode_leaf(arr)
        np.testing.assert_array_equal(q, np.frombuffer(enc["q"],
                                                       np.int8))
        np.testing.assert_allclose(float(s), enc["s"], rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(ps_dataplane.dequantize_int8(jnp.asarray(q), s)),
            np.frombuffer(enc["q"], np.int8).astype(np.float32)
            * enc["s"], rtol=1e-6)


def test_bf16_cast_matches_host_codec():
    """The delta wire narrowing is the exact ``Bf16Codec`` cast law
    (round-to-nearest-even)."""
    from distkeras_tpu.parallel.compression import Bf16Codec

    arr = (np.random.RandomState(4).randn(513) * 0.11).astype(
        np.float32)
    dev = np.asarray(
        jnp.asarray(arr).astype(jnp.bfloat16).astype(jnp.float32))
    codec = Bf16Codec()
    host = codec.decode_leaf(codec.encode_leaf(arr), arr.shape,
                             np.float32)
    np.testing.assert_array_equal(dev, host)


@pytest.mark.parametrize("rule_name", ["downpour", "dynsgd"])
def test_mesh_int8_round_matches_quantized_oracle(rule_name):
    """End-to-end int8 arm vs the closed-form oracle: each round the
    workers see ``Cq`` (the per-leaf int8 round-trip of the exact
    center — exact because on-chip ``segment_max`` + ``pmax`` computes
    the same global per-leaf ``max|x|``), and the resulting delta folds
    into the EXACT center.  So ``C' = C + (fast_round(center=Cq) - Cq)``
    to the standard 2e-5 parity tolerance."""
    W = 4
    (rule, step, center, ws, ps, batches, perms, make_worker,
     keys) = _setup(rule_name, W)
    placement = mesh_lib.place_workers(W)
    row = mesh_lib.batch_sharding(placement.mesh)
    rep = mesh_lib.replicated_sharding(placement.mesh)
    dp = ps_dataplane.MeshDataplane(rule, step, placement.mesh, center,
                                    comm_codec="int8")
    mps, mws = dp.to_device(rule.init_state(center),
                            jax.vmap(make_worker)(keys))
    drv = ps_dataplane.MeshRoundDriver(dp, mps, mws, sync=True)

    rf = jax.jit(make_round_fn(rule, step, "fast"))
    quant_rt = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: ps_dataplane.dequantize_int8(
            *ps_dataplane.quantize_int8(x)), t))
    ps_ref, ws_ref = ps, jax.vmap(make_worker)(keys)
    for b, p in zip(batches, perms):
        drv.dispatch(jax.device_put(b, row), jax.device_put(p, rep))
        cq = quant_rt(ps_ref.center)
        ps_q, ws_ref, met_ref = rf(ps_ref._replace(center=cq), ws_ref,
                                   b, p)
        new_center = jax.tree_util.tree_map(
            lambda c, pq, q: c + (pq - q), ps_ref.center, ps_q.center,
            cq)
        ps_ref = ps_q._replace(center=new_center)
        (met,) = drv.poll()
        _assert_tree_close(met_ref["loss"], met["loss"], rule_name)
        np.testing.assert_array_equal(
            np.asarray(met_ref["staleness"]), met["staleness"])
    assert int(drv.mps.clock) == int(ps_ref.clock)
    _assert_tree_close(ps_ref.center, dp.center(drv.mps), rule_name)


def test_mesh_bf16_round_close_to_f32():
    """The bf16 delta wire reduces IN bf16 (the wire is the
    reduction), so end-to-end tolerance vs the f32 arm is the bf16
    mantissa (~3 decimal digits) scaled by the per-round delta — much
    looser than the 2e-5 parity bar, and documented as such."""
    W = 4
    (rule, step, center, ws, ps, batches, perms, make_worker,
     keys) = _setup("downpour", W)
    placement = mesh_lib.place_workers(W)
    row = mesh_lib.batch_sharding(placement.mesh)
    rep = mesh_lib.replicated_sharding(placement.mesh)
    finals = {}
    for dt in ("float32", "bfloat16"):
        dp = ps_dataplane.MeshDataplane(rule, step, placement.mesh,
                                        center, comm_dtype=dt)
        mps, mws = dp.to_device(rule.init_state(center),
                                jax.vmap(make_worker)(keys))
        drv = ps_dataplane.MeshRoundDriver(dp, mps, mws, sync=True)
        for b, p in zip(batches, perms):
            drv.dispatch(jax.device_put(b, row),
                         jax.device_put(p, rep))
        assert int(drv.mps.clock) == W * len(batches)
        finals[dt] = jax.device_get(dp.center(drv.mps))
    for la, lb in zip(jax.tree_util.tree_leaves(finals["float32"]),
                      jax.tree_util.tree_leaves(finals["bfloat16"])):
        np.testing.assert_allclose(la, lb, rtol=0, atol=5e-3)


@pytest.mark.parametrize("metrics_every", [1, 4])
def test_async_driver_byte_identical_to_sync(metrics_every):
    """Tentpole 3 acceptance: ring contents under ``metrics_every`` in
    {1, 4} match the per-round fetch EXACTLY, and the async driver's
    end state is byte-identical to the synchronous oracle (same
    programs, same buffers — only the fetch schedule differs).  With
    rounds=3 and metrics_every=4 the ring never fills, so ``drain()``
    also covers the partial-ring path."""
    W, rounds = 4, 3
    (rule, step, center, ws, ps, batches, perms, make_worker,
     keys) = _setup("dynsgd", W, rounds=rounds)
    placement = mesh_lib.place_workers(W)
    row = mesh_lib.batch_sharding(placement.mesh)
    rep = mesh_lib.replicated_sharding(placement.mesh)

    def run(sync, me):
        dp = ps_dataplane.MeshDataplane(rule, step, placement.mesh,
                                        center, metrics_every=me)
        mps, mws = dp.to_device(rule.init_state(center),
                                jax.vmap(make_worker)(keys))
        drv = ps_dataplane.MeshRoundDriver(dp, mps, mws, sync=sync)
        got = []
        for b, p in zip(batches, perms):
            drv.dispatch(jax.device_put(b, row),
                         jax.device_put(p, rep))
            got += drv.poll()
        got += drv.drain()
        return dp, drv, got

    dp_s, drv_s, met_s = run(True, 1)
    dp_a, drv_a, met_a = run(False, metrics_every)
    assert len(met_s) == len(met_a) == rounds
    for a, b in zip(met_s, met_a):
        for k in ("loss", "grad_norm", "staleness"):
            np.testing.assert_array_equal(a[k], b[k])
    for la, lb in zip(
            jax.tree_util.tree_leaves(dp_s.center(drv_s.mps)),
            jax.tree_util.tree_leaves(dp_a.center(drv_a.mps))):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert int(drv_s.mps.clock) == int(drv_a.mps.clock)


def test_comm_bytes_accounting_and_telemetry():
    """Static wire accounting: both knobs shrink their collective and
    the saving lands on ``ps_round_comm_bytes_saved_total`` once per
    dispatched round; the driver's ring reads land on
    ``ps_metrics_fetches_total`` (1 per ``metrics_every`` rounds plus
    the final partial drain)."""
    W, rounds = 2, 3
    (rule, step, center, ws, ps, batches, perms, make_worker,
     keys) = _setup("downpour", W, rounds=rounds)
    placement = mesh_lib.place_workers(W)
    row = mesh_lib.batch_sharding(placement.mesh)
    rep = mesh_lib.replicated_sharding(placement.mesh)

    f32 = ps_dataplane.MeshDataplane(rule, step, placement.mesh,
                                     center)
    assert f32.comm_bytes_saved_per_round == 0
    both = ps_dataplane.MeshDataplane(
        rule, step, placement.mesh, center, comm_dtype="bfloat16",
        comm_codec="int8", metrics_every=2)
    assert both.comm_bytes_per_round["gather"] < \
        f32.comm_bytes_per_round["gather"]
    assert both.comm_bytes_per_round["scatter"] < \
        f32.comm_bytes_per_round["scatter"]
    assert both.comm_bytes_saved_per_round > 0

    tel = telemetry.enable()
    try:
        mps, mws = both.to_device(rule.init_state(center),
                                  jax.vmap(make_worker)(keys))
        drv = ps_dataplane.MeshRoundDriver(both, mps, mws)
        for b, p in zip(batches, perms):
            drv.dispatch(jax.device_put(b, row),
                         jax.device_put(p, rep))
        drv.drain()
        counters = tel.metrics.snapshot()["counters"]
        saved_key = ('ps_round_comm_bytes_saved_total'
                     '{fidelity="mesh"}')
        assert counters[saved_key] == \
            rounds * both.comm_bytes_saved_per_round, counters
        # 3 rounds @ metrics_every=2: one full ring + one partial
        assert counters["ps_metrics_fetches_total"] == 2, counters
    finally:
        telemetry.disable()


@pytest.mark.parametrize("W", [2, 4])
def test_comm_bytes_exact_identities_between_arms(W):
    """The wire laws, byte for byte (the center is all-f32, so they
    are exact): the int8 center gather is a quarter of the f32 gather
    plus the per-leaf scale side channel, the bf16 delta scatter is
    half the f32 scatter, each knob leaves the other collective alone,
    and the bytes a knob reports saved are exactly the difference."""
    (rule, step, center, *_rest) = _setup("downpour", W)
    mesh = mesh_lib.place_workers(W).mesh

    def arm(**kw):
        return ps_dataplane.MeshDataplane(rule, step, mesh, center,
                                          **kw)

    f32, bf16, int8 = (arm(), arm(comm_dtype="bfloat16"),
                       arm(comm_codec="int8"))
    fb, bb, ib = (a.comm_bytes_per_round for a in (f32, bf16, int8))
    n_leaves = len(f32.spec.groups["float32"].indices)
    side = (n_leaves + 1) * 4 * W
    assert ib["gather"] - side == fb["gather"] // 4
    assert bb["scatter"] == fb["scatter"] // 2
    assert ib["scatter"] == fb["scatter"]
    assert bb["gather"] == fb["gather"]
    assert f32.comm_bytes_saved_per_round == 0
    assert int8.comm_bytes_saved_per_round == \
        fb["gather"] - ib["gather"]
    assert bf16.comm_bytes_saved_per_round == \
        fb["scatter"] - bb["scatter"]


@pytest.mark.parametrize("pipelined,label", [
    (False, "mesh"), (True, "mesh_pipelined")])
def test_one_compile_per_rule_under_its_fidelity_label(pipelined,
                                                       label):
    """Two rules, three rounds each: the round is traced once per
    rule and counted under the label of the form it was built in —
    the plain round never lands on the pipelined label nor the
    pipelined one on the plain."""
    W = 2
    tel = telemetry.enable()
    try:
        for rule_name in ("downpour", "dynsgd"):
            (rule, step, center, ws, ps, batches, perms, make_worker,
             keys) = _setup(rule_name, W)
            placement = mesh_lib.place_workers(W)
            row = mesh_lib.batch_sharding(placement.mesh)
            rep = mesh_lib.replicated_sharding(placement.mesh)
            dp = ps_dataplane.MeshDataplane(
                rule, step, placement.mesh, center,
                pipelined=pipelined)
            mps, mws = dp.to_device(rule.init_state(center),
                                    jax.vmap(make_worker)(keys))
            drv = ps_dataplane.MeshRoundDriver(dp, mps, mws,
                                               sync=True)
            for b, p in zip(batches, perms):
                drv.dispatch(jax.device_put(b, row),
                             jax.device_put(p, rep))
            if pipelined:
                drv.flush_pipeline()
        compiles = {
            k: v for k, v in tel.metrics.snapshot()["counters"].items()
            if k.startswith("ps_round_compiles_total")}
        assert compiles == {
            f'ps_round_compiles_total{{fidelity="{label}"}}': 2}
    finally:
        telemetry.disable()


def test_comm_knob_validation():
    (rule, step, center, *_rest) = _setup("downpour", 2)
    placement = mesh_lib.place_workers(2)
    with pytest.raises(ValueError, match="comm_dtype"):
        ps_dataplane.MeshDataplane(rule, step, placement.mesh, center,
                                   comm_dtype="float16")
    with pytest.raises(ValueError, match="comm_codec"):
        ps_dataplane.MeshDataplane(rule, step, placement.mesh, center,
                                   comm_codec="int4")
    with pytest.raises(ValueError, match="metrics_every"):
        ps_dataplane.MeshDataplane(rule, step, placement.mesh, center,
                                   metrics_every=0)


def test_trainer_comm_knobs_need_comm_compression_tier():
    """Non-default comm knobs on a tier without the capability must
    raise, naming the tiers that DO lower comm compression."""
    for kw in ({"comm_dtype": "bfloat16"}, {"comm_codec": "int8"},
               {"metrics_every": 4}):
        with pytest.raises(ValueError, match="mesh"):
            DOWNPOUR(MLP, fidelity="fast", num_workers=2,
                     learning_rate=0.005, **kw)
    # default values are fine everywhere
    DOWNPOUR(MLP, fidelity="fast", num_workers=2, learning_rate=0.005,
             comm_dtype="float32", comm_codec=None, metrics_every=1)


def test_trainer_mesh_metrics_every_history_identical():
    """Batching the metrics fetch must not change WHAT is recorded —
    only when it crosses to the host."""
    def run(**kw):
        t = DOWNPOUR(MLP, fidelity="mesh", num_workers=4,
                     communication_window=4, batch_size=32,
                     num_epoch=1, learning_rate=0.005, seed=3, **kw)
        v = t.train(DATA)
        return t, v

    t1, v1 = run()
    t4, v4 = run(metrics_every=4)
    assert t1.history["staleness"] == t4.history["staleness"]
    np.testing.assert_array_equal(t1.history["round_loss"],
                                  t4.history["round_loss"])
    for la, lb in zip(jax.tree_util.tree_leaves(v1["params"]),
                      jax.tree_util.tree_leaves(v4["params"])):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_trainer_mesh_int8_trains():
    """The compressed arm end-to-end through the trainer: loss stays
    finite and the run completes (parity is covered at the dataplane
    level; the trainer path exercises knob plumbing + driver)."""
    t = DOWNPOUR(MLP, fidelity="mesh", num_workers=4,
                 communication_window=4, batch_size=32, num_epoch=1,
                 learning_rate=0.005, seed=3, comm_codec="int8",
                 comm_dtype="bfloat16")
    t.train(DATA)
    assert np.isfinite(t.history["round_loss"]).all()


def test_trainer_mesh_trains_a_conv_model():
    """The mesh round carries a convolutional model end to end: every
    round's loss is finite and the clock counts every commit.  (No
    parity here: XLA's CPU convolutions are not batching-stable, so a
    window computed per device and one vmapped over workers agree to
    the noise floor only; the MLP tests above hold the round's
    semantics.)"""
    cfg = model_config("convnet", (8, 8, 3), num_classes=4,
                       widths=(8,), dense=16)
    data = datasets.synthetic_classification(128, (8, 8, 3), 4, seed=0)
    t = DOWNPOUR(cfg, fidelity="mesh", num_workers=2,
                 communication_window=2, batch_size=8, num_epoch=1,
                 learning_rate=0.005, worker_optimizer="momentum",
                 seed=3)
    t.train(data)
    losses = t.history["round_loss"]
    assert len(losses) == 128 // (2 * 2 * 8)
    assert np.isfinite(losses).all()
    assert int(t.parameter_server_state.clock) == 2 * len(losses)


# ---- partition-rule resolver ------------------------------------------

def test_match_partition_rules_regex_and_scalars():
    tree = {"dense": {"kernel": jnp.zeros((4, 8)),
                      "bias": jnp.zeros((8,))},
            "scale": jnp.zeros(())}
    specs = ps_dataplane.match_partition_rules(
        ((r".*bias", P()), (r".*", P(mesh_lib.WORKER_AXIS))), tree)
    assert specs["dense"]["kernel"] == P(mesh_lib.WORKER_AXIS)
    assert specs["dense"]["bias"] == P()
    assert specs["scale"] == P()  # scalars never shard


def test_match_partition_rules_unmatched_leaf_raises():
    with pytest.raises(ValueError, match="dense/kernel"):
        ps_dataplane.match_partition_rules(
            ((r"nothing", P()),), {"dense": {"kernel": jnp.zeros((4,))}})


# ---- tier registry + trainer validation -------------------------------

def test_tier_registry():
    assert set(TIERS) == {"host", "faithful", "fast", "mesh"}
    assert resolve_tier("mesh").data_plane == "mesh"
    with pytest.raises(ValueError, match="valid lowering tiers"):
        resolve_tier("bogus")
    assert tiers_with("deterministic") == ["faithful", "fast", "mesh"]
    assert tiers_with("concurrent") == ["host"]
    assert tiers_with("comm_compression") == ["mesh"]


def test_unknown_fidelity_lists_tiers():
    with pytest.raises(ValueError, match="valid lowering tiers"):
        DOWNPOUR(MLP, fidelity="bogus", num_workers=2,
                 learning_rate=0.005)


def test_mesh_tier_rejects_checkpointing():
    t = DOWNPOUR(MLP, fidelity="mesh", num_workers=2, batch_size=32,
                 communication_window=2, num_epoch=1,
                 learning_rate=0.005, checkpoint_dir="/tmp/never")
    with pytest.raises(NotImplementedError, match="checkpointing "
                                                  "tiers"):
        t.train(DATA)


def test_mesh_tier_rejects_model_parallel():
    with pytest.raises(ValueError, match="tensor-parallel tiers"):
        DOWNPOUR(MLP, fidelity="mesh", num_workers=2, model_parallel=2,
                 learning_rate=0.005)


def test_mesh_tier_needs_one_device_per_worker():
    t = DOWNPOUR(MLP, fidelity="mesh", num_workers=16, batch_size=8,
                 communication_window=2, num_epoch=1,
                 learning_rate=0.003)
    with pytest.raises(ValueError, match="does not fit"):
        t.train(DATA)


def test_mesh_tier_rejects_elastic_family():
    t = AEASGD(MLP, fidelity="mesh", num_workers=2, batch_size=32,
               communication_window=2, num_epoch=1,
               learning_rate=0.005)
    with pytest.raises(ValueError, match="elastic"):
        t.train(DATA)
