"""Hand-written Pallas TPU flash attention (forward + backward kernels).

The device-local blockwise path (``parallel.ring_attention.
blockwise_attention``) already avoids materializing the ``[T, T]``
attention matrix, but it is *composed* from XLA ops: ``lax.map`` over q
chunks dispatches one fused region per chunk, and every intermediate
(logits block, probabilities, correction factors) round-trips through
XLA's layout choices.  This module is the same online-softmax algorithm
as ONE Mosaic kernel per pass: the q block, the running max/denominator
and the output accumulator stay resident in VMEM across all k blocks,
k/v blocks stream through the Pallas grid pipeline (double-buffered HBM
fetches overlapping the MXU matmuls), and causally-dead blocks are
skipped by grid predication rather than masked arithmetic.

Numerics match ``models.transformer.dense_causal_attention`` up to
reduction order: logit/softmax statistics accumulate in f32; the
probabilities are cast back to the input dtype for the P·V / dS·K
matmuls exactly as the dense path's ``probs.astype(q.dtype)`` does.

The backward pass is the standard flash decomposition (recompute
probabilities from the saved logsumexp): one kernel accumulates dQ with
k/v blocks streaming, one accumulates dK/dV with q blocks streaming, and
the softmax-jacobian diagonal ``D = rowsum(dO * O)`` is precomputed
outside the kernels (one cheap fused elementwise-reduce).

No counterpart in the reference: it has no op layer at all (SURVEY.md §1
"no ops/kernel layer" — Keras/Theano supplied kernels), let alone an
attention one.  A/B against the scan-composed blockwise path is in
PERF.md §17.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# Measured v5e optimum of the round-4 sweep at T=2048 (PERF.md §17).
_DEFAULT_BLOCK_Q = 512
_DEFAULT_BLOCK_K = 1024
# A windowed forward's blocks: a band of 128 keys then spans two key
# blocks a q block, half of them live
_WINDOW_BLOCK = 128

# 3 parallel grid dims (batch, head, q block) + 1 sequential reduction
# dim (k or q block stream) that the VMEM accumulators persist across.
_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


_PARAMS = pltpu.CompilerParams(dimension_semantics=_SEMANTICS)


def _causal_mask(i, j, bq, bk):
    """[bq, bk] boolean: query row i*bq+r attends key col j*bk+c."""
    rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows >= cols


def _j_last(i, bq, bk, n_k, causal):
    """Index of the last k block the i-th q block attends to."""
    if not causal:
        return n_k - 1
    # int32 throughout: x64 mode must not promote in-kernel index math
    return jnp.minimum(((i * bq + bq - 1) // bk).astype(jnp.int32),
                       jnp.int32(n_k - 1))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale, causal, n_k):
    i, j = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Blocks entirely above the causal diagonal contribute nothing.
    @pl.when(jnp.logical_or(not causal, j * bk <= i * bq + bq - 1))
    def _():
        q = q_ref[0, 0]                                    # [bq, D]
        k = k_ref[0, 0]                                    # [bk, D]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, bk]
        if causal:
            logits = jnp.where(_causal_mask(i, j, bq, bk), logits,
                               _NEG)
        m_prev, l_prev = m_scr[:], l_scr[:]                # [bq, 1]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)                        # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                     # [bq, 1]
        m_scr[:] = m_new
        l_scr[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, D]

    @pl.when(j == _j_last(i, bq, bk, n_k, causal))
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(l)).astype(jnp.float32)


def _band(i, bq, bk, n_k, window):
    """``(first, last)`` key block that the i-th q block needs under a
    causal band of ``window`` keys (query i sees keys ``i - window < j <=
    i``); ``i`` an int, an int array or a grid index."""
    xp = np if isinstance(i, np.ndarray) else jnp   # shapes, or the grid
    lo = xp.maximum(i * bq - window + 1, 0) // bk
    hi = xp.minimum((i * bq + bq - 1) // bk, n_k - 1)
    return lo.astype(xp.int32), hi.astype(xp.int32)


def band_blocks(t: int, bq: int, bk: int, window: int) -> int:
    """Key blocks the band holds over every q block of a length-``t``
    sequence, for one (row, head): the blocks the windowed forward
    kernel computes."""
    lo, hi = _band(np.arange(t // bq), bq, bk, t // bk, window)
    return int(np.sum(hi - lo + 1))


def band_width(t: int, bq: int, bk: int, window: int) -> int:
    """The most key blocks the band holds for one q block: the windowed
    grid's last axis."""
    lo, hi = _band(np.arange(t // bq), bq, bk, t // bk, window)
    return int(np.max(hi - lo + 1))


def _band_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                     acc_scr, *, scale, window, n_k, n_band):
    """``_fwd_kernel`` over the band alone: grid step ``j`` of q block
    ``i`` is key block ``first + j`` of the band (``_band``), the steps
    past its last block compute nothing, and the band's edges are masked.
    A row whose keys all lie in a later block of the band takes the
    masked logits of an earlier one first; the correction ``exp(m_prev -
    m)`` of its first true key wipes them."""
    i, j = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    lo, hi = _band(i, bq, bk, n_k, window)
    kb = lo + j

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(kb <= hi)
    def _():
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, bk]
        rows = i * bq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = kb * bk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(jnp.logical_and(rows >= cols, rows - cols < window),
                      s, _NEG)
        m, l, alpha, p = _softmax_step(s, m_scr[:], l_scr[:], 1)
        acc_scr[:] = alpha * acc_scr[:] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, Dv]
        m_scr[:], l_scr[:] = m, l

    @pl.when(j == n_band - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(l)).astype(jnp.float32)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dq_ref,
               dq_scr, *, scale, causal, n_k):
    i, j = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(jnp.logical_or(not causal, j * bk <= i * bq + bq - 1))
    def _():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        do = do_ref[0, 0]                                  # [bq, D]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            mask = _causal_mask(i, j, bq, bk)
            logits = jnp.where(mask, logits, _NEG)
        p = jnp.exp(logits - lse_ref[0, 0])                # [bq, bk]
        if causal:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        ds = p * (dp - dsum_ref[0, 0]) * scale             # [bq, bk]
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, D]

    @pl.when(j == _j_last(i, bq, bk, n_k, causal))
    def _():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                n_q):
    j, i = pl.program_id(2), pl.program_id(3)
    bk, bq = k_ref.shape[2], q_ref.shape[2]

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(jnp.logical_or(not causal, i * bq + bq - 1 >= j * bk))
    def _():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        do = do_ref[0, 0]                                  # [bq, D]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, bk]
        if causal:
            mask = _causal_mask(i, j, bq, bk)
            logits = jnp.where(mask, logits, _NEG)
        p = jnp.exp(logits - lse_ref[0, 0])                # [bq, bk]
        if causal:
            p = jnp.where(mask, p, 0.0)
        pt = p.astype(do.dtype)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        ds = (p * (dp - dsum_ref[0, 0]) * scale).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, D]

    @pl.when(i == n_q - 1)
    def _():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _auto_block(t, target):
    """Largest divisor of ``t`` that is <= ``target`` (t <= target
    short-circuits to t).  Sequence lengths are multiples of 128 in
    practice, so this lands on an MXU-friendly size (e.g. T=768 ->
    384, T=1280 -> 640); degenerate T degrades gracefully."""
    b = min(target, t)
    while t % b:
        b -= 1
    return b


def _blocks_pair(t, tk, block_q, block_k):
    """(block_q, block_k) for q length ``t`` and k length ``tk``:
    defaults auto-clamp to the largest divisor <= the measured
    optimum; explicit values are clamped to the length and must then
    divide it."""
    bq = _auto_block(t, _DEFAULT_BLOCK_Q) if block_q is None \
        else min(block_q, t)
    bk = _auto_block(tk, _DEFAULT_BLOCK_K) if block_k is None \
        else min(block_k, tk)
    if t % bq or tk % bk:
        raise ValueError(
            f"lengths ({t}, {tk}) must be divisible by "
            f"block_q={bq} and block_k={bk} (pass block_q/block_k="
            f"None to auto-pick divisors)")
    return bq, bk


def _blocks(t, block_q, block_k):
    return _blocks_pair(t, t, block_q, block_k)


def _check_mosaic_alignment(bq, bk, t, tk):
    """Compiled Mosaic requires lane/sublane-aligned tiles; an
    unaligned auto-picked block (e.g. prime or odd T, where the
    largest divisor degrades toward 1) fails deep in the compiler
    with an opaque tiling error.  Catch it here with an actionable
    one.  The interpreter path accepts any block, so this only runs
    when compiling (interpret=False)."""
    if bq % 8 or bk % 8:
        raise ValueError(
            f"sequence lengths ({t}, {tk}) have no MXU-aligned "
            f"divisor <= the block targets (picked block_q={bq}, "
            f"block_k={bk}); compiled Mosaic needs blocks that are "
            "multiples of 8 (ideally 128).  Pad the sequence to a "
            "multiple of 128, or pass explicit aligned "
            "block_q/block_k that divide it.")


def _qblk(bq, d):
    """BlockSpec for a per-(b, h, i) q-shaped operand on [B, H, T, D]."""
    return pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0),
                        memory_space=pltpu.VMEM)


def _kblk(bk, d, g=1):
    """BlockSpec for a per-(b, h, j) k-shaped operand on [B, H, T, D];
    ``g`` > 1: the operand has H / g heads, query head h reading head
    h // g (grouped-query attention)."""
    if g == 1:
        return pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0),
                            memory_space=pltpu.VMEM)
    return pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h // g, j, 0),
                        memory_space=pltpu.VMEM)


def _rowblk(bq):
    """BlockSpec for a per-(b, h, i) row statistic on [B, H, T, 1]."""
    return pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0),
                        memory_space=pltpu.VMEM)


def _fwd_call(q, k, v, scale, causal, bq, bk, interpret, window=None):
    b, h, t, d = q.shape
    dv = v.shape[-1]    # values (and the output) may be narrower than q/k
    g = h // k.shape[1]     # query heads a key head
    n_q, n_k = t // bq, t // bk
    if window is None:
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                                   n_k=n_k)
        grid = (b, h, n_q, n_k)
        kv_specs = [_kblk(bk, d, g), _kblk(bk, dv, g)]
    else:
        n_band = band_width(t, bq, bk, window)
        kernel = functools.partial(_band_fwd_kernel, scale=scale,
                                   window=window, n_k=n_k, n_band=n_band)
        grid = (b, h, n_q, n_band)

        def band_blk(width):
            # past the band's last block a step names that block again,
            # which is in VMEM already: no copy is issued for it
            def index(b_, h_, i, j):
                lo, hi = _band(i, bq, bk, n_k, window)
                return (b_, h_ // g, jnp.minimum(lo + j, hi), 0)

            return pl.BlockSpec((1, 1, bk, width), index,
                                memory_space=pltpu.VMEM)

        kv_specs = [band_blk(d), band_blk(dv)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[_qblk(bq, d)] + kv_specs,
        out_specs=[_qblk(bq, dv), _rowblk(bq)],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        compiler_params=None if interpret else _PARAMS,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


def _bwd_call(q, k, v, do, lse, dsum, scale, causal, bq, bk,
              interpret):
    b, h, t, d = q.shape
    n_q, n_k = t // bq, t // bk
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          n_k=n_k),
        grid=(b, h, n_q, n_k),
        in_specs=[_qblk(bq, d), _kblk(bk, d), _kblk(bk, d),
                  _qblk(bq, d), _rowblk(bq), _rowblk(bq)],
        out_specs=[_qblk(bq, d)],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=None if interpret else _PARAMS,
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, dsum)[0]

    # dK/dV: the k block is the resident operand, q blocks stream.
    kspec = pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0),
                         memory_space=pltpu.VMEM)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM)
    rspec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          n_q=n_q),
        grid=(b, h, n_k, n_q),
        in_specs=[qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, t, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=None if interpret else _PARAMS,
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, dsum)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhtd(q, k, v, scale, causal, bq, bk, interpret):
    out, _ = _fwd_call(q, k, v, scale, causal, bq, bk, interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, bq, bk, interpret):
    out, lse = _fwd_call(q, k, v, scale, causal, bq, bk, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, bq, bk, interpret, residuals, dout):
    q, k, v, out, lse = residuals
    # Softmax-jacobian diagonal, one fused elementwise-reduce in XLA.
    dsum = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1, keepdims=True)                 # [B, H, T, 1]
    dq, dk, dv = _bwd_call(q, k, v, dout.astype(q.dtype), lse, dsum,
                           scale, causal, bq, bk, interpret)
    return dq, dk, dv


_flash_bhtd.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float | None = None, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None,
                    window: int | None = None) -> jax.Array:
    """Pallas-kernel attention: ``[B, T, H, D] -> [B, T, H, D]``.
    ``v`` may have a head size of its own (``[B, T, H, Dv]``, as the
    expanded form of latent attention has: 192 for q/k, 128 for v); the
    result is then ``[B, T, H, Dv]`` and comes from the forward kernel
    alone, which computes nothing it does not need: that spelling
    serves (prefill) and has no backward pass.  So do two more: ``k``
    and ``v`` with fewer heads than ``q`` (``KVH`` dividing ``H``: query
    head h reads key head ``h // (H / KVH)``, grouped-query attention,
    with no repeated copy), and a ``window`` (causal only): query i
    attends to keys ``i - window < j <= i``, and the grid walks only the
    key blocks of that band, so that blocks wholly outside it are
    neither computed nor copied in; the band's edge blocks are masked.
    Its blocks default to 128 positions (a divisor of T no larger).

    Same contract as ``models.transformer.dense_causal_attention`` and
    ``parallel.ring_attention.blockwise_attention``; differentiable via
    hand-written backward kernels (first-order only).  ``block_q``/
    ``block_k`` default to the measured v5e optimum (512/1024) clamped
    to the largest divisor of T, so any sequence length works; an
    explicit value is first clamped down to T (a block cannot exceed
    the sequence) and must then divide T — anything else raises.
    ``interpret`` defaults to auto: the Pallas interpreter off-TPU so
    tests run anywhere, compiled Mosaic on TPU.
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    if scale is None:
        scale = q.shape[-1] ** -0.5
    t, h, kvh = q.shape[1], q.shape[2], k.shape[2]
    if h % kvh or v.shape[2] != kvh:
        raise ValueError(f"{h} query heads cannot share {kvh} key heads "
                         f"({v.shape[2]} value heads)")
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window} needs causal attention "
                             "and at least one key")
        block_q = _auto_block(t, _WINDOW_BLOCK) if block_q is None \
            else block_q
        block_k = _auto_block(t, _WINDOW_BLOCK) if block_k is None \
            else block_k
    bq, bk = _blocks(t, block_q, block_k)
    if not interpret:
        _check_mosaic_alignment(bq, bk, t, t)
    # [B, T, H, D] -> [B, H, T, D]: one transpose each way per pass —
    # negligible (O(T)) next to attention's O(T^2), and it gives the
    # kernels their natural (rows = time, lanes = head_dim) layout.
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    if window is None and kvh == h and v.shape[-1] == q.shape[-1]:
        out = _flash_bhtd(qt, kt, vt, float(scale), bool(causal), bq, bk,
                          bool(interpret))
    else:
        if window is not None:
            recorded = getattr(_recording, "band", None)
            if recorded is not None:
                recorded.append(q.shape[0] * h
                                * band_blocks(t, bq, bk, window))
        out = _fwd_call(qt, kt, vt, float(scale), bool(causal), bq, bk,
                        bool(interpret), window)[0]
    return jnp.swapaxes(out, 1, 2)


def flash_attn_fn(causal: bool = True, block_q: int | None = None,
                  block_k: int | None = None):
    """An ``AttnFn`` (``TransformerLM.attn_fn`` signature) running the
    Pallas flash kernels.  Block defaults are the measured v5e optimum
    of the round-4 sweep at T=2048 (PERF.md §17: 512/1024 -> 10.1 ms
    fwd+bwd vs 16.8 ms scan-blockwise, 17.8 ms dense), auto-clamped to
    divisors of T."""
    return functools.partial(flash_attention, causal=causal,
                             block_q=block_q, block_k=block_k)


# ---------------------------------------------------------------------
# Ring-hop kernels: the same online-softmax kernels with (a) the
# softmax state (m, l, acc) carried IN and OUT instead of finalized,
# and (b) global position offsets for q and k supplied as scalars —
# one call processes one ring hop's K/V block against the local q
# block, so sequence parallelism (parallel.ring_attention) can run
# the Pallas path per hop while the ring carries the state between
# devices.  Offsets are SMEM scalar inputs because they are traced
# values inside the ring's lax.scan (the hop source rotates).
# ---------------------------------------------------------------------


def _off_mask(qo, ko, i, j, bq, bk):
    """[bq, bk] causal mask in GLOBAL positions (qo/ko are scalars)."""
    rows = qo + i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ko + j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows >= cols


def _hop_fwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, m_in_ref,
                    l_in_ref, acc_in_ref, m_ref, l_ref, acc_ref,
                    m_scr, l_scr, acc_scr, *, scale, causal, n_k):
    i, j = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    qo, ko = qo_ref[0], ko_ref[0]

    @pl.when(j == 0)
    def _():
        m_scr[:] = m_in_ref[0, 0]
        l_scr[:] = l_in_ref[0, 0]
        acc_scr[:] = acc_in_ref[0, 0]

    # a block contributes unless causally dead in global positions
    alive = jnp.logical_or(
        not causal, ko + j * bk <= qo + i * bq + bq - 1)

    @pl.when(alive)
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            logits = jnp.where(_off_mask(qo, ko, i, j, bq, bk),
                               logits, _NEG)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev,
                            jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _():
        m_ref[0, 0] = m_scr[:]
        l_ref[0, 0] = l_scr[:]
        acc_ref[0, 0] = acc_scr[:]


def _hop_dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, dsum_ref, dq_ref, dq_scr, *, scale,
                   causal, n_k):
    i, j = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    qo, ko = qo_ref[0], ko_ref[0]

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    alive = jnp.logical_or(
        not causal, ko + j * bk <= qo + i * bq + bq - 1)

    @pl.when(alive)
    def _():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        do = do_ref[0, 0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            mask = _off_mask(qo, ko, i, j, bq, bk)
            logits = jnp.where(mask, logits, _NEG)
        p = jnp.exp(logits - lse_ref[0, 0])
        if causal:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dsum_ref[0, 0]) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_k - 1)
    def _():
        dq_ref[0, 0] = dq_scr[:]


def _hop_dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, dsum_ref, dk_ref, dv_ref, dk_scr,
                    dv_scr, *, scale, causal, n_q):
    j, i = pl.program_id(2), pl.program_id(3)
    bk, bq = k_ref.shape[2], q_ref.shape[2]
    qo, ko = qo_ref[0], ko_ref[0]

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    alive = jnp.logical_or(
        not causal, qo + i * bq + bq - 1 >= ko + j * bk)

    @pl.when(alive)
    def _():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        do = do_ref[0, 0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            mask = _off_mask(qo, ko, i, j, bq, bk)
            logits = jnp.where(mask, logits, _NEG)
        p = jnp.exp(logits - lse_ref[0, 0])
        if causal:
            p = jnp.where(mask, p, 0.0)
        pt = p.astype(do.dtype)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - dsum_ref[0, 0]) * scale).astype(q.dtype)
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _():
        dk_ref[0, 0] = dk_scr[:]
        dv_ref[0, 0] = dv_scr[:]


def _struct(vma, shape):
    """f32 ShapeDtypeStruct, tagged varying-over-``vma`` mesh axes
    when given (required for pallas outputs under shard_map's
    check_vma)."""
    if vma is None:
        return jax.ShapeDtypeStruct(shape, jnp.float32)
    return jax.ShapeDtypeStruct(shape, jnp.float32, vma=frozenset(vma))


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def flash_hop_fwd(q, k, v, m, l, acc, *, q_offset, k_offset,
                  scale, causal=True, block_q=None, block_k=None,
                  vma=None, interpret=None):
    """One ring hop of flash attention, state carried.

    All arrays are [B, H, T, D]-layout blocks local to this device:
    ``q`` is the resident query block; ``k``/``v`` the visiting hop's
    K/V block; ``m``/``l`` [B, H, T, 1] and ``acc`` [B, H, T, D] the
    running online-softmax state (f32).  ``q_offset``/``k_offset`` are
    the blocks' global time positions (traced scalars are fine).
    Returns the updated ``(m, l, acc)``.  The caller finalizes with
    ``out = acc / max(l, eps)`` and ``lse = m + log l`` after the last
    hop.
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    b, h, t, d = q.shape
    tk = k.shape[2]
    bq, bk = _blocks_pair(t, tk, block_q, block_k)
    if not interpret:
        _check_mosaic_alignment(bq, bk, t, tk)
    n_q, n_k = t // bq, tk // bk
    kernel = functools.partial(_hop_fwd_kernel, scale=scale,
                               causal=causal, n_k=n_k)
    qo = jnp.asarray(q_offset, jnp.int32).reshape(1)
    ko = jnp.asarray(k_offset, jnp.int32).reshape(1)
    out = functools.partial(_struct, vma)
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[_scalar_spec(), _scalar_spec(),
                  _qblk(bq, d), _kblk(bk, d), _kblk(bk, d),
                  _qblk(bq, 1), _qblk(bq, 1), _qblk(bq, d)],
        out_specs=[_qblk(bq, 1), _qblk(bq, 1), _qblk(bq, d)],
        out_shape=[out((b, h, t, 1)), out((b, h, t, 1)),
                   out((b, h, t, d))],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=None if interpret else _PARAMS,
        interpret=interpret,
        name="flash_hop_fwd",
    )(qo, ko, q, k, v, m, l, acc)


def flash_hop_bwd(q, k, v, do, lse, dsum, *, q_offset, k_offset,
                  scale, causal=True, block_q=None, block_k=None,
                  vma=None, interpret=None):
    """One ring hop of the flash backward: partial ``(dq, dk, dv)``
    for this (local q)×(visiting k/v) pair, to be accumulated by the
    caller (dq locally; dk/dv riding the ring with their block).
    ``lse`` [B, H, T, 1] is the FINAL logsumexp; ``dsum`` [B, H, T, 1]
    is rowsum(dO·O)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    b, h, t, d = q.shape
    tk = k.shape[2]
    bq, bk = _blocks_pair(t, tk, block_q, block_k)
    if not interpret:
        _check_mosaic_alignment(bq, bk, t, tk)
    n_q, n_k = t // bq, tk // bk
    qo = jnp.asarray(q_offset, jnp.int32).reshape(1)
    ko = jnp.asarray(k_offset, jnp.int32).reshape(1)
    dq = pl.pallas_call(
        functools.partial(_hop_dq_kernel, scale=scale, causal=causal,
                          n_k=n_k),
        grid=(b, h, n_q, n_k),
        in_specs=[_scalar_spec(), _scalar_spec(),
                  _qblk(bq, d), _kblk(bk, d), _kblk(bk, d),
                  _qblk(bq, d), _qblk(bq, 1), _qblk(bq, 1)],
        out_specs=[_qblk(bq, d)],
        out_shape=[_struct(vma, (b, h, t, d))],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=None if interpret else _PARAMS,
        interpret=interpret,
        name="flash_hop_dq",
    )(qo, ko, q, k, v, do, lse, dsum)[0]

    kspec = pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0),
                         memory_space=pltpu.VMEM)
    qspec = pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM)
    rspec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0),
                         memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_hop_dkv_kernel, scale=scale, causal=causal,
                          n_q=n_q),
        grid=(b, h, n_k, n_q),
        in_specs=[_scalar_spec(), _scalar_spec(),
                  qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=[kspec, kspec],
        out_shape=[_struct(vma, (b, h, tk, d)),
                   _struct(vma, (b, h, tk, d))],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=None if interpret else _PARAMS,
        interpret=interpret,
        name="flash_hop_dkv",
    )(qo, ko, q, k, v, do, lse, dsum)
    return dq, dk, dv


# ---------------------------------------------------------------------
# Decode attention: one query token a batch row against that row's own
# cache, read only as far as the row is long.  The XLA read it stands in
# for (two contractions over ``[B, L, ...]`` under a position mask)
# streams every row of every slot's envelope through the chip whatever
# the slots hold; this kernel's grid walks (row, cache block), skips the
# blocks at or beyond ``lengths[b]`` and names, for a skipped step, the
# block that is already in VMEM, so that the pipeline issues no copy
# for it.
# ---------------------------------------------------------------------

#: bytes of cache one grid step brings in at most (keys and values of a
#: block): large enough that the step's fixed cost is small beside the
#: copy, small enough that a row's last block holds few dead positions.
#: On a v5e (PR 30's chip runs, PERF.md section 6): 128 positions of 16
#: heads of 128 (1 MiB) and 512 of a 640-wide latent leaf (0.6 MiB) read
#: ragged pools fastest; twice that is up to 8 % slower, four times up
#: to 19 %.
_DECODE_BLOCK_BYTES = 1 << 20

_recording = threading.local()


@contextlib.contextmanager
def _recording_into(name):
    prev = getattr(_recording, name, None)
    setattr(_recording, name, [])
    try:
        yield getattr(_recording, name)
    finally:
        setattr(_recording, name, prev)


def recording_decode_blocks():
    """Collects, while a program is traced inside it (this thread), the
    cache block of every ``decode_attention`` call: the host's way to
    know whether the kernel is in a program it traced, and in what
    blocks it reads."""
    return _recording_into("blocks")


def recording_band_blocks():
    """Collects, while a program is traced inside it (this thread), the
    key blocks that each windowed ``flash_attention`` call computes
    (rows x heads x ``band_blocks``): the host's count of the band's
    work in a program, from shapes alone."""
    return _recording_into("band")


def decode_block(cache_len: int, row_bytes: int) -> int | None:
    """The cache block of ``decode_attention``: the largest power-of-two
    multiple of 128 rows that divides ``cache_len`` and brings in at
    most ``_DECODE_BLOCK_BYTES`` a step (``row_bytes``: keys and values
    of one cached position); ``None`` where 128 does not divide."""
    if cache_len % 128:
        return None
    block = 128
    while (cache_len % (2 * block) == 0
           and 2 * block * row_bytes <= _DECODE_BLOCK_BYTES):
        block *= 2
    return block


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def decode_attention_applies(q, k, v, lengths, *, v_width=None) -> bool:
    """Whether a slot-mode step that was given its rows' ``lengths``
    should read its cache through ``decode_attention``: on a TPU, a
    cache in the activations' floating type, heads (or the one shared
    leaf) a multiple of 128 wide, whose default layout is row-major, an
    envelope of whole 128-row blocks, and cached heads that fill whole
    tiles of sublanes (16 in bfloat16) or are one shared leaf, so that
    ``[L, KVH, D]`` is ``[L * KVH, D]`` without a byte moved.  A cache
    whose heads lie side by side on the lanes (``[B, L, KVH * D]``, keys
    and values) is taken at any ``KVH``: the kernel reads each head's
    columns where they lie."""
    kvh = q.shape[1]
    if v is not None and k.ndim == 3:      # the lane-dense grouped leaf
        return (lengths is not None
                and _on_tpu()
                and k.dtype == q.dtype == v.dtype
                and jnp.issubdtype(k.dtype, jnp.floating)
                and q.shape[-1] % 128 == 0
                and k.shape[-1] == v.shape[-1] == kvh * q.shape[-1]
                and k.shape[1] % 128 == 0)
    dv = v_width if v is None else v.shape[-1]
    tile = 32 // k.dtype.itemsize
    return (lengths is not None
            and _on_tpu()
            and k.dtype == q.dtype
            and (v is None or v.dtype == q.dtype)
            and jnp.issubdtype(k.dtype, jnp.floating)
            and k.shape[-1] % 128 == 0 and dv % 128 == 0
            and k.shape[1] % 128 == 0
            and (kvh == 1 or kvh % tile == 0))


def _softmax_step(s, m_prev, l_prev, axis):
    """One block of the online softmax over ``axis`` of float32 logits
    ``s``: ``(m, l, alpha, p)``."""
    m = jnp.maximum(m_prev, s.max(axis=axis, keepdims=True))
    alpha = jnp.exp(m_prev - m)
    p = jnp.exp(s - m)
    return m, alpha * l_prev + p.sum(axis=axis, keepdims=True), alpha, p


def _decode_kernel(len_ref, row_ref, blk_ref, q_ref, k_ref, *rest,
                   scale, bk, kvh, g, dv):
    """One (row, cache block) step.  The block is taken as ``bk * KVH``
    keys of one width, position-major as it lies (``[bk * KVH, Dk]``:
    the last two axes of ``[bk, KVH, Dk]`` folded, no byte moved), the
    row's ``H = KVH * G`` queries as ``[H, Dk]``: both products are
    matrix products, ``[H, Dk] x [bk * KVH, Dk]^T`` and ``[H, bk * KVH]
    x [bk * KVH, Dv]``, and a query sees the keys of its own cached head
    alone by a mask on the logits (``KVH > 1``; the products it wastes
    cost less than the copy of the block, the vector unit's work on
    ``[bk, KVH, D]`` as it lies more: PERF.md, PR 30).  ``rest``: the
    values' ref unless they are the keys' first ``dv`` columns, then
    the output and the scratch."""
    v_ref = rest[0] if len(rest) == 5 else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    b, j = pl.program_id(0), pl.program_id(1)
    n_keys = len_ref[b] * kvh                # live keys of the row
    first = j * (bk * kvh)                   # this block's first key

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(partial):
        q, k = q_ref[0], k_ref[0]               # [H, Dk], [bk * KVH, Dk]
        v = None if v_ref is None else v_ref[0]
        if partial:
            # the row's last block: what lies beyond the length is
            # masked out of the logits and zeroed in the values (a
            # probability of 0 times a NaN is a NaN)
            live = first + lax.broadcasted_iota(
                jnp.int32, (k.shape[0], 1), 0) < n_keys
            if v is None:
                k = jnp.where(live, k, jnp.zeros_like(k))
            else:
                v = jnp.where(live, v, jnp.zeros_like(v))
        if v is None:
            v = k[:, :dv]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        keep = None
        if partial or kvh > 1:
            cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if partial:
            keep = first + cols < n_keys
        if kvh > 1:
            own = cols % kvh == lax.broadcasted_iota(
                jnp.int32, s.shape, 0) // g
            keep = own if keep is None else jnp.logical_and(keep, own)
        if keep is not None:
            s = jnp.where(keep, s, _NEG)
        m, l, alpha, p = _softmax_step(s, m_scr[...], l_scr[...], 1)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...], l_scr[...] = m, l

    last = first + bk * kvh
    pl.when(last <= n_keys)(lambda: block(False))
    pl.when(jnp.logical_and(first < n_keys,
                            last > n_keys))(lambda: block(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_scr[...]
        o_ref[0] = jnp.where(l > 0.0, acc_scr[...] / jnp.where(
            l > 0.0, l, 1.0), 0.0).astype(o_ref.dtype)


def _decode_lane_kernel(len_ref, row_ref, blk_ref, q_ref, k_ref, v_ref,
                        o_ref, m_scr, l_scr, acc_scr, *, scale, bk, kvh, d):
    """``_decode_kernel`` for a cache whose heads lie side by side on the
    lanes: the block is ``[bk, KVH * D]`` of keys and of values, cached
    head ``c`` their columns ``c D .. (c + 1) D`` (a slice of whole lane
    tiles); the row's queries ``[KVH, G, D]``, group ``c`` against head
    ``c`` alone, so that no product is wasted."""
    b, j = pl.program_id(0), pl.program_id(1)
    n_keys = len_ref[b]                      # live positions of the row
    first = j * bk                           # this block's first position

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(partial):
        k, v = k_ref[0], v_ref[0]               # [bk, KVH * D]
        if partial:
            # past the row's length: masked out of the logits, zeroed in
            # the values (a probability of 0 times a NaN is a NaN)
            live = first + lax.broadcasted_iota(jnp.int32, (bk, 1), 0) \
                < n_keys
            v = jnp.where(live, v, jnp.zeros_like(v))
        heads = [slice(c * d, (c + 1) * d) for c in range(kvh)]
        s = jnp.concatenate([
            lax.dot_general(q_ref[0, c], k[:, cols],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
            for c, cols in enumerate(heads)], axis=0) * scale  # [H, bk]
        if partial:
            s = jnp.where(first + lax.broadcasted_iota(jnp.int32, s.shape, 1)
                          < n_keys, s, _NEG)
        m, l, alpha, p = _softmax_step(s, m_scr[...], l_scr[...], 1)
        g = p.shape[0] // kvh
        acc_scr[...] = alpha * acc_scr[...] + jnp.concatenate([
            jnp.dot(p[c * g:(c + 1) * g].astype(v.dtype), v[:, cols],
                    preferred_element_type=jnp.float32)
            for c, cols in enumerate(heads)], axis=0)        # [H, D]
        m_scr[...], l_scr[...] = m, l

    last = first + bk
    pl.when(last <= n_keys)(lambda: block(False))
    pl.when(jnp.logical_and(first < n_keys,
                            last > n_keys))(lambda: block(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_scr[...]
        o_ref[0] = jnp.where(l > 0.0, acc_scr[...] / jnp.where(
            l > 0.0, l, 1.0), 0.0).astype(o_ref.dtype)


def _fetch_schedule(lengths, n_blocks: int, bk: int):
    """For every grid step (row-major over rows x blocks) the (row,
    block) whose cache block it wants in VMEM: its own where the block
    holds a live row, else the last live one before it (the first live
    one, for the steps before any), which is the block already there."""
    b = lengths.shape[0]
    live = (jnp.arange(n_blocks, dtype=jnp.int32)[None, :] * bk
            < lengths[:, None]).reshape(-1)
    step = jnp.arange(b * n_blocks, dtype=jnp.int32)
    last = lax.cummax(jnp.where(live, step, -1))
    first = jnp.min(jnp.where(live, step, b * n_blocks - 1))
    fetch = jnp.where(last < 0, first, last)
    return fetch // n_blocks, fetch % n_blocks


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array | None,
                     lengths: jax.Array, *, scale: float,
                     v_width: int | None = None, block: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """Attention of one query token a row over the first ``lengths[b]``
    positions of that row's cache, in grouped form: ``q [B, KVH, G, Dk]``,
    ``k [B, L, KVH, Dk]``, ``v [B, L, KVH, Dv]``, ``lengths [B]`` int32
    -> ``[B, KVH, G, Dv]``.  ``v=None``: the values are the keys' first
    ``v_width`` columns (a latent leaf ``[c_kv | k_rope | pad]`` whose
    value is ``c_kv``), and the leaf is read once for both products.
    ``k`` and ``v`` may also be lane-dense, ``[B, L, KVH * D]``, cached
    head ``c`` in columns ``c D .. (c + 1) D`` (``_decode_lane_kernel``).

    A Pallas TPU kernel over (row, cache block of ``block`` positions,
    by default ``decode_block``'s).  Blocks at or beyond a row's length
    are neither computed nor copied in; the last live block is masked by
    position; a row of length 0 reads nothing and gives zeros.  Logits,
    softmax, normaliser and accumulator are float32, the probabilities
    are rounded to the cache's type for their matrix product as the XLA
    read's are.  One body for every ``(KVH, G)``: see ``_decode_kernel``.
    ``interpret`` as in ``flash_attention``."""
    bsz, kvh, g, dk = q.shape
    length = k.shape[1]
    lane_dense = v is not None and k.ndim == 3
    dv = v_width if v is None else v.shape[-1] // (kvh if lane_dense else 1)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    row_bytes = (dk + (0 if v is None else dv)) * kvh * k.dtype.itemsize
    bk = block or decode_block(length, row_bytes)
    if not bk or length % bk:
        raise ValueError(
            f"cache length {length} is not a whole number of blocks of "
            f"{bk or 128} positions")
    recorded = getattr(_recording, "blocks", None)
    if recorded is not None:
        recorded.append(bk)
    n_blocks = length // bk
    h = kvh * g
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, length)
    rows, blks = _fetch_schedule(lengths, n_blocks, bk)

    def cached(positions, width):
        # the block a step wants, by the schedule
        return pl.BlockSpec(
            (1, positions, width),
            lambda b, j, ln, r, c: (r[b * n_blocks + j],
                                    c[b * n_blocks + j], 0))

    def per_row(width):
        return pl.BlockSpec((1, h, width), lambda b, j, ln, r, c: (b, 0, 0))

    if lane_dense:
        operands = [q, k, v]
        in_specs = [pl.BlockSpec((1, kvh, g, dk),
                                 lambda b, j, ln, r, c: (b, 0, 0, 0)),
                    cached(bk, kvh * dk), cached(bk, kvh * dv)]
        kernel = functools.partial(_decode_lane_kernel, scale=scale, bk=bk,
                                   kvh=kvh, d=dk)
    else:
        # the cache as keys of one width, position-major: the last two
        # axes of [L, KVH, D] folded, which moves no byte where KVH fills
        # whole tiles of sublanes (or is 1: a latent leaf)
        operands = [q.reshape(bsz, h, dk), k.reshape(bsz, length * kvh, dk)]
        in_specs = [per_row(dk), cached(bk * kvh, dk)]
        if v is not None:
            operands.append(v.reshape(bsz, length * kvh, dv))
            in_specs.append(cached(bk * kvh, dv))
        kernel = functools.partial(_decode_kernel, scale=scale, bk=bk,
                                   kvh=kvh, g=g, dv=dv)
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        # two buffers a cached operand, and room for the body's own
        vmem_limit_bytes=4 * bk * row_bytes + (16 << 20))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(bsz, n_blocks),
            in_specs=in_specs,
            out_specs=per_row(dv),
            scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bsz, h, dv), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="decode_attention",
    )(lengths, rows, blks, *operands)
    return out.reshape(bsz, kvh, g, dv)
