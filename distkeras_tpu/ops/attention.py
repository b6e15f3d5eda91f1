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

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# Measured v5e optimum of the round-4 sweep at T=2048 (PERF.md §17).
_DEFAULT_BLOCK_Q = 512
_DEFAULT_BLOCK_K = 1024

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


def _kblk(bk, d):
    """BlockSpec for a per-(b, h, j) k-shaped operand on [B, H, T, D]."""
    return pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0),
                        memory_space=pltpu.VMEM)


def _rowblk(bq):
    """BlockSpec for a per-(b, h, i) row statistic on [B, H, T, 1]."""
    return pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0),
                        memory_space=pltpu.VMEM)


def _fwd_call(q, k, v, scale, causal, bq, bk, interpret):
    b, h, t, d = q.shape
    dv = v.shape[-1]    # values (and the output) may be narrower than q/k
    n_q, n_k = t // bq, t // bk
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[_qblk(bq, d), _kblk(bk, d), _kblk(bk, dv)],
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
                    interpret: bool | None = None) -> jax.Array:
    """Pallas-kernel attention: ``[B, T, H, D] -> [B, T, H, D]``.
    ``v`` may have a head size of its own (``[B, T, H, Dv]``, as the
    expanded form of latent attention has: 192 for q/k, 128 for v); the
    result is then ``[B, T, H, Dv]`` and comes from the forward kernel
    alone, which computes nothing it does not need: that spelling
    serves (prefill) and has no backward pass.

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
    bq, bk = _blocks(q.shape[1], block_q, block_k)
    if not interpret:
        _check_mosaic_alignment(bq, bk, q.shape[1], q.shape[1])
    # [B, T, H, D] -> [B, H, T, D]: one transpose each way per pass —
    # negligible (O(T)) next to attention's O(T^2), and it gives the
    # kernels their natural (rows = time, lanes = head_dim) layout.
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    call = _flash_bhtd if v.shape[-1] == q.shape[-1] else \
        (lambda *a: _fwd_call(*a)[0])
    out = call(qt, kt, vt, float(scale), bool(causal), bq, bk,
               bool(interpret))
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



