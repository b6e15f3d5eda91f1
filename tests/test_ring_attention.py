"""Ring attention (sequence parallelism) vs dense attention — exactness
of the online-softmax ring accumulation, gradients through the ring
(reverse ppermute), and the full sequence-parallel TransformerLM."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distkeras_tpu.models import ModelSpec, model_config
from distkeras_tpu.models.transformer import dense_causal_attention
from distkeras_tpu.ops.losses import resolve_loss
from distkeras_tpu.parallel.ring_attention import (
    ring_attention,
    sequence_sharded_apply,
)

SEQ = "seq"


def _mesh(n=4):
    devices = jax.devices()
    if len(devices) < n:
        pytest.skip(f"needs {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]), (SEQ,))


def _qkv(b=2, t=32, h=2, d=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def _dense_full_attention(q, k, v, *, scale):
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("q_chunk", [None, 4])
def test_ring_matches_dense(causal, q_chunk):
    mesh = _mesh()
    q, k, v = _qkv()
    scale = q.shape[-1] ** -0.5
    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name=SEQ, causal=causal,
                          q_chunk=q_chunk),
        mesh=mesh, in_specs=(P(None, SEQ), P(None, SEQ), P(None, SEQ)),
        out_specs=P(None, SEQ))
    got = np.asarray(jax.jit(ring)(q, k, v))
    ref_fn = (dense_causal_attention if causal
              else _dense_full_attention)
    want = np.asarray(ref_fn(q, k, v, scale=scale))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_indivisible_q_chunk_raises():
    mesh = _mesh()
    q, k, v = _qkv()  # t_local = 8 per device
    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name=SEQ, q_chunk=3),
        mesh=mesh, in_specs=(P(None, SEQ), P(None, SEQ), P(None, SEQ)),
        out_specs=P(None, SEQ))
    with pytest.raises(ValueError, match="q_chunk"):
        jax.jit(ring)(q, k, v)


@pytest.mark.parametrize("q_chunk", [None, 2])
def test_ring_gradients_match_dense(q_chunk):
    mesh = _mesh()
    q, k, v = _qkv(seed=1)
    probe = jax.random.normal(jax.random.key(9), q.shape)

    def ring_loss(q, k, v):
        out = jax.shard_map(
            functools.partial(ring_attention, axis_name=SEQ,
                              q_chunk=q_chunk),
            mesh=mesh,
            in_specs=(P(None, SEQ), P(None, SEQ), P(None, SEQ)),
            out_specs=P(None, SEQ))(q, k, v)
        return jnp.sum(out * probe)

    def dense_loss(q, k, v):
        out = dense_causal_attention(q, k, v,
                                     scale=q.shape[-1] ** -0.5)
        return jnp.sum(out * probe)

    got = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)


def _lm_spec(**over):
    cfg = dict(vocab_size=64, num_layers=2, d_model=32, num_heads=2,
               max_len=64, dtype="float32")
    cfg.update(over)
    return ModelSpec.from_config(
        model_config("transformer_lm", (32,), input_dtype="int32", **cfg))


def test_sequence_parallel_transformer_matches_dense():
    """Same params, dense single-device vs ring over 4 sequence shards."""
    mesh = _mesh()
    dense_model = _lm_spec().build()
    seq_model = _lm_spec(seq_axis=SEQ).build()

    tokens = jax.random.randint(jax.random.key(2), (2, 32), 0, 64)
    variables = dense_model.init(jax.random.key(3), tokens)

    want = np.asarray(dense_model.apply(variables, tokens))
    sp_apply = sequence_sharded_apply(
        lambda vs, toks: seq_model.apply(vs, toks), mesh, SEQ)
    got = np.asarray(jax.jit(sp_apply)(variables, tokens))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_sequence_parallel_training_grads_match_dense():
    """A full LM training gradient (xent over next tokens) computed
    sequence-parallel equals the dense gradient — the correctness basis
    for long-context training."""
    mesh = _mesh()
    dense_model = _lm_spec().build()
    seq_model = _lm_spec(seq_axis=SEQ).build()
    loss_fn = resolve_loss("sparse_categorical_crossentropy")

    data = jax.random.randint(jax.random.key(4), (2, 33), 0, 64)
    tokens, targets = data[:, :-1], data[:, 1:]
    variables = dense_model.init(jax.random.key(5), tokens)

    def dense_loss(vs):
        logits = dense_model.apply(vs, tokens)
        return loss_fn(logits, targets).mean()

    def seq_loss(vs):
        def shard_loss(vs, toks, tgt):
            logits = seq_model.apply(vs, toks)
            local = loss_fn(logits, tgt).mean()
            return jax.lax.pmean(local, SEQ)

        sharded = jax.shard_map(
            shard_loss, mesh=mesh,
            in_specs=(P(), P(None, SEQ), P(None, SEQ)),
            out_specs=P())
        return sharded(vs, tokens, targets)

    want_l, want_g = jax.jit(jax.value_and_grad(dense_loss))(variables)
    got_l, got_g = jax.jit(jax.value_and_grad(seq_loss))(variables)
    np.testing.assert_allclose(float(got_l), float(want_l), rtol=1e-5)
    flat_w, _ = jax.tree_util.tree_flatten(want_g)
    flat_g, _ = jax.tree_util.tree_flatten(got_g)
    for g, w in zip(flat_g, flat_w):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("q_chunk", [None, 4, 8])
def test_blockwise_matches_dense(causal, q_chunk):
    """Device-local blockwise (flash-style) attention — the ring
    machinery with no ring — is exact vs dense, fwd and grad."""
    from distkeras_tpu.parallel.ring_attention import blockwise_attention

    q, k, v = _qkv()
    scale = q.shape[-1] ** -0.5
    dense = (dense_causal_attention if causal
             else _dense_full_attention)
    want = np.asarray(dense(q, k, v, scale=scale))
    got = np.asarray(jax.jit(functools.partial(
        blockwise_attention, causal=causal, q_chunk=q_chunk))(q, k, v))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def loss_block(q, k, v):
        o = blockwise_attention(q, k, v, causal=causal,
                                q_chunk=q_chunk)
        return (o * o).sum()

    def loss_dense(q, k, v):
        o = dense(q, k, v, scale=scale)
        return (o * o).sum()

    got_g = jax.jit(jax.grad(loss_block, argnums=(0, 1, 2)))(q, k, v)
    want_g = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-4)


def test_transformer_blockwise_matches_dense():
    """TransformerLM(blockwise_attn=True) — the JSON-able spelling —
    equals the dense-attention twin on one device."""
    dense_model = _lm_spec().build()
    block_spec = _lm_spec(blockwise_attn=True, attn_q_chunk=8)
    import json

    # the knob must survive a config round-trip (it is how checkpoints
    # and trainers carry it)
    block_model = ModelSpec.from_config(
        json.loads(json.dumps(block_spec.to_config()))).build()
    tokens = jax.random.randint(jax.random.key(11), (2, 32), 0, 64)
    variables = dense_model.init(jax.random.key(12), tokens)
    want = np.asarray(dense_model.apply(variables, tokens))
    got = np.asarray(jax.jit(
        lambda vs, t: block_model.apply(vs, t))(variables, tokens))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_blockwise_lm_trains_through_async_ps():
    """Integration: the emulated async-PS family trains a
    blockwise-attention TransformerLM (vmapped worker states over the
    flash path's custom VJP + lax.map) — the single-chip long-context
    model composes with every trainer arm."""
    from distkeras_tpu.data import datasets
    from distkeras_tpu.trainers import ADAG

    data = datasets.lm_synth(256, seq_len=16, vocab_size=32, seed=0)
    cfg = model_config("transformer_lm", (16,), input_dtype="int32",
                       vocab_size=32, num_layers=1, d_model=32,
                       num_heads=4, max_len=16, dtype="float32",
                       blockwise_attn=True, attn_q_chunk=8)
    t = ADAG(cfg, loss="sparse_categorical_crossentropy",
             num_workers=4, communication_window=2, batch_size=8,
             num_epoch=2, learning_rate=3e-3, worker_optimizer="adam",
             seed=0)
    t.train(data)
    h = t.history["epoch_loss"]
    assert np.isfinite(h).all()
    assert h[-1] < h[0], h


def test_transformer_attn_q_chunk_matches_dense():
    """TransformerLM(seq_axis=..., attn_q_chunk=...) — chunked ring
    attention through the full model equals the dense twin."""
    mesh = _mesh()
    dense_model = _lm_spec().build()
    seq_model = _lm_spec(seq_axis=SEQ, attn_q_chunk=4).build()

    tokens = jax.random.randint(jax.random.key(8), (2, 32), 0, 64)
    variables = dense_model.init(jax.random.key(9), tokens)
    want = np.asarray(dense_model.apply(variables, tokens))
    sp_apply = sequence_sharded_apply(
        lambda vs, toks: seq_model.apply(vs, toks), mesh, SEQ)
    got = np.asarray(jax.jit(sp_apply)(variables, tokens))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ---- impl="flash": Pallas hop kernels under the ring ----


@pytest.mark.parametrize("causal", [True, False])
def test_flash_impl_ring_matches_dense(causal):
    """ring_attention(impl='flash') — per-hop Pallas kernels with the
    online-softmax state carried across hops — equals dense attention.
    The Pallas interpreter needs jax.shard_map(check_vma=False) (JAX
    interpreter limitation; sequence_sharded_apply already does)."""
    mesh = _mesh()
    q, k, v = _qkv()
    scale = q.shape[-1] ** -0.5
    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name=SEQ, causal=causal,
                          impl="flash", block_q=8, block_k=8),
        mesh=mesh, in_specs=(P(None, SEQ),) * 3,
        out_specs=P(None, SEQ), check_vma=False)
    got = np.asarray(jax.jit(ring)(q, k, v))
    ref_fn = (dense_causal_attention if causal
              else _dense_full_attention)
    want = np.asarray(ref_fn(q, k, v, scale=scale))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_impl_ring_gradients_match_dense():
    mesh = _mesh()
    q, k, v = _qkv()
    scale = q.shape[-1] ** -0.5
    probe = jax.random.normal(jax.random.key(21), q.shape, jnp.float32)
    ring = jax.shard_map(
        functools.partial(ring_attention, axis_name=SEQ, causal=True,
                          impl="flash", block_q=8, block_k=8),
        mesh=mesh, in_specs=(P(None, SEQ),) * 3,
        out_specs=P(None, SEQ), check_vma=False)
    gf = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(ring(q, k, v) * probe),
        (0, 1, 2)))(q, k, v)
    gr = jax.grad(
        lambda q, k, v: jnp.sum(
            dense_causal_attention(q, k, v, scale=scale) * probe),
        (0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-5, atol=3e-5, err_msg=name)


def test_flash_impl_degenerate_ring_single_device():
    """axis_name=None, impl='flash': the n=1 ring runs the hop kernels
    device-locally and matches dense — the kernels' single-chip
    smoke path (compiled on real TPU, interpreted off it)."""
    q, k, v = _qkv()
    got = ring_attention(q, k, v, axis_name=None, impl="flash",
                         block_q=8, block_k=16)
    want = dense_causal_attention(q, k, v, scale=q.shape[-1] ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_impl_unknown_rejected():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="impl"):
        ring_attention(q, k, v, axis_name=None, impl="mosaic")
