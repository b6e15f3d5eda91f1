"""The attention kernels a window layer and a grouped full layer are read
through: ``flash_attention(window=)`` over the band's blocks alone, with
fewer key heads than query heads, and ``decode_attention`` on a
lane-dense cache ``[B, L, KVH * D]``; each against plain ``jnp`` on the
CPU (the Pallas interpreter)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops import attention


def _band_reference(q, k, v, window, scale):
    """``[B, T, H, D]`` queries, ``[B, T, KVH, D]`` keys and values: causal
    attention over the last ``window`` keys (all keys for None), in
    float32 at full precision."""
    t, h, kvh = q.shape[1], q.shape[2], k.shape[2]
    k = jnp.repeat(k, h // kvh, axis=2).astype(jnp.float32)
    v = jnp.repeat(v, h // kvh, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def _qkv(seed, t, h, kvh, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (2, t, h, d)).astype(dtype),
            jax.random.normal(ks[1], (2, t, kvh, d)).astype(dtype),
            jax.random.normal(ks[2], (2, t, kvh, d)).astype(dtype))


# float32 operands; what is left is the order of the online softmax's
# sums, which moves the fifth decimal at most here
TOL = 2e-5


@pytest.mark.parametrize("t,window,blocks", [
    (256, 128, (None, None)),     # the default 128 blocks: two a q block
    (384, 64, (128, 128)),        # a band inside one block or across two
    (256, 100, (64, 128)),        # q blocks narrower than key blocks
    (192, 300, (64, 64)),         # a window past the sequence: causal
    (128, 1, (32, 32)),           # the diagonal alone
], ids=["w128", "w64", "w100_q64", "w300", "w1"])
def test_windowed_flash_equals_the_band_of_dense_attention(t, window,
                                                           blocks):
    q, k, v = _qkv(0, t, 4, 2, 32)
    got = attention.flash_attention(q, k, v, window=window,
                                    block_q=blocks[0], block_k=blocks[1],
                                    interpret=True)
    want = _band_reference(q, k, v, window, 32 ** -0.5)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_grouped_heads_without_a_window_are_causal_attention():
    q, k, v = _qkv(1, 256, 8, 2, 32)
    got = attention.flash_attention(q, k, v, interpret=True, block_q=64,
                                    block_k=128)
    np.testing.assert_allclose(got, _band_reference(q, k, v, None,
                                                    32 ** -0.5),
                               atol=TOL, rtol=TOL)


def test_the_band_holds_the_blocks_that_meet_it():
    """``band_blocks`` and ``band_width`` against a count of every (q
    block, key block) pair that holds a (query, key) pair of the band."""
    for t, bq, bk, w in [(4096, 128, 128, 128), (1024, 256, 128, 128),
                         (512, 128, 256, 64), (640, 128, 128, 1000)]:
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        seen = (j <= i) & (i - j < w)
        meets = seen.reshape(t // bq, bq, t // bk, bk).any(axis=(1, 3))
        assert attention.band_blocks(t, bq, bk, w) == meets.sum()
        assert attention.band_width(t, bq, bk, w) == meets.sum(1).max()


def test_a_windowed_call_records_the_blocks_it_computes():
    q, k, v = _qkv(2, 512, 4, 1, 32)
    with attention.recording_band_blocks() as band:
        jax.jit(lambda q, k, v: attention.flash_attention(
            q, k, v, window=128, interpret=True)).lower(q, k, v)
    # 2 rows x 4 heads x (1 + 2 + 2 + 2) blocks of 128
    assert band == [2 * 4 * 7]
    with attention.recording_band_blocks() as band:
        jax.jit(lambda q, k, v: attention.flash_attention(
            q, k, v, interpret=True)).lower(q, k, v)
    assert band == []


def _lane_dense_reference(q, k, v, lengths, scale):
    """``q [B, KVH, G, D]``, ``k``/``v [B, L, KVH * D]``, the first
    ``lengths[b]`` positions of row b, in float32."""
    b, kvh, g, d = q.shape
    kh = k.reshape(b, -1, kvh, d).astype(jnp.float32)
    vh = v.reshape(b, -1, kvh, d).astype(jnp.float32)
    s = jnp.einsum("bcgd,blcd->bcgl", q.astype(jnp.float32), kh,
                   precision=jax.lax.Precision.HIGHEST) * scale
    live = np.arange(k.shape[1])[None, :] < np.asarray(lengths)[:, None]
    vh = jnp.where(live[:, :, None, None], vh, 0.0)
    p = jax.nn.softmax(jnp.where(live[:, None, None], s, -jnp.inf), -1)
    out = jnp.einsum("bcgl,blcd->bcgd", p, vh,
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.where(np.asarray(lengths)[:, None, None, None] > 0, out, 0.0)


@pytest.mark.parametrize("kvh,g,length,lengths", [
    (8, 8, 512, [0, 1, 127, 128, 129, 300, 511, 512]),   # the full layer
    (8, 8, 128, [0, 1, 64, 127, 128, 128, 5, 77]),       # a ring of 128
    (2, 4, 256, [3, 256, 129, 0]),
], ids=["kvh8_512", "kvh8_ring", "kvh2_256"])
def test_decode_attention_reads_a_lane_dense_cache(kvh, g, length, lengths):
    d = 128
    ks = jax.random.split(jax.random.key(3), 3)
    b = len(lengths)
    q = jax.random.normal(ks[0], (b, kvh, g, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, length, kvh * d), jnp.float32)
    v = jax.random.normal(ks[2], (b, length, kvh * d), jnp.float32)
    # positions past a row's length hold NaN: read, they would poison it
    dead = np.arange(length)[None, :, None] >= np.array(lengths)[:, None,
                                                                  None]
    v = jnp.where(dead, jnp.nan, v)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = attention.decode_attention(q, k, v, lengths, scale=d ** -0.5,
                                     block=128, interpret=True)
    np.testing.assert_allclose(
        got, _lane_dense_reference(q, k, v, lengths, d ** -0.5),
        atol=TOL, rtol=TOL)


def test_the_rule_takes_a_lane_dense_leaf_at_any_count_of_heads(
        monkeypatch):
    """Eight cached heads of 128 in bfloat16: lane-dense, the kernel's;
    as ``[B, L, KVH, D]`` XLA's read as before (half a tile of
    sublanes), and off the chip neither."""
    q = jnp.zeros((4, 8, 8, 128), jnp.bfloat16)
    lane = jnp.zeros((4, 256, 1024), jnp.bfloat16)
    heads = jnp.zeros((4, 256, 8, 128), jnp.bfloat16)
    n = jnp.ones((4,), jnp.int32)
    assert not attention.decode_attention_applies(q, lane, lane, n)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert attention.decode_attention_applies(q, lane, lane, n)
    assert not attention.decode_attention_applies(q, heads, heads, n)
    assert not attention.decode_attention_applies(q, lane, lane, None)
    assert not attention.decode_attention_applies(
        q, lane[:, :200], lane[:, :200], n)
    assert not attention.decode_attention_applies(
        q[..., :64], lane[..., :512], lane[..., :512], n)
