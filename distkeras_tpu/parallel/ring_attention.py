"""Ring attention: exact attention over a sequence-sharded mesh axis.

The reference has no long-context story at all (SURVEY.md §5 "long-context
/ sequence parallelism: absent" — its longest-sequence workload, the IMDB
BiLSTM, handles sequences whole per worker).  The TPU rebuild makes
sequence parallelism first-class: shard the time axis of ``q``/``k``/``v``
across a mesh axis, keep the query block resident, and rotate the
key/value blocks around the ring with ``lax.ppermute`` — one hop per
scan step, N hops total (the final hop restores the original block
placement, keeping the scan carry uniform) — accumulating exact softmax
attention with the online (flash-style) running max / denominator.  The
ICI traffic per step is one K/V block, which overlaps with the block's
matmuls on TPU.

Memory: O(T_local) per device in both directions.  The forward pass
holds only the online-softmax accumulators and never materializes a
[T_local, T_global] attention matrix; the backward pass is a custom
reverse-ring VJP (the flash-attention backward) that saves just
``(q, k, v, out, logsumexp)`` and recomputes each block's probabilities
in a second ring pass, with the dK/dV accumulators traveling alongside
their K/V blocks so each arrives home after N hops.  No per-step
residual stacks anywhere — peak memory is independent of the ring size.

This is an SPMD op: call it inside ``jax.shard_map`` (or use
``ring_attn_fn`` as the ``attn_fn`` of a ``TransformerLM`` whose
``seq_axis`` names the mesh axis).  First-order differentiable; the
gradients are tested against dense attention (tests/test_ring_attention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# numpy, not jnp: a module-level jnp constant would initialize the XLA
# backend at import time, breaking jax.distributed.initialize callers
_NEG = np.float32(-1e30)


def _ring(axis_name: str | None):
    """The one-hop-backward permutation (block s lands on device s-1).
    ``axis_name=None`` is the DEVICE-LOCAL degenerate ring (n=1, no
    hops): the same online-softmax / recompute machinery runs as a
    single-chip blockwise (flash-style) attention."""
    if axis_name is None:
        return 1, 0, None
    n = lax.axis_size(axis_name)
    return n, lax.axis_index(axis_name), [(i, (i - 1) % n)
                                          for i in range(n)]


def _vary(axis_name, trees):
    """Mark zero-initialized scan carries as device-varying (scan's
    carry typing must agree with the computed, varying outputs)."""
    if axis_name is None:
        return tuple(trees)
    return tuple(lax.pcast(x, (axis_name,), to="varying") for x in trees)


def _block_mask(src, t_local, q_pos):
    k_pos = src * t_local + jnp.arange(t_local)
    return (q_pos[:, None] >= k_pos[None, :])[None, None]


def _chunks(q_chunk, t_local):
    """Validated (n_chunks, chunk_len) for within-device q blocking."""
    if q_chunk is None or q_chunk >= t_local:
        return 1, t_local
    if q_chunk < 1 or t_local % q_chunk:
        raise ValueError(
            f"q_chunk={q_chunk} must be a positive divisor of the "
            f"local sequence length {t_local}")
    return t_local // q_chunk, q_chunk


def _chunk_q_major(x, n_c, qc):
    """[B, T, ...] -> chunk-major [n_c, B, qc, ...]."""
    b = x.shape[0]
    return jnp.moveaxis(x.reshape(b, n_c, qc, *x.shape[2:]), 1, 0)


def _chunk_bh_major(x, n_c, qc):
    """[B, H, T] -> chunk-major [n_c, B, H, qc]."""
    b, h = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, h, n_c, qc), 2, 0)


def _pos_chunks(me, t_local, n_c, qc):
    """Global q positions of this device's block, chunked [n_c, qc]."""
    return (me * t_local + jnp.arange(t_local)).reshape(n_c, qc)


def _forward_scan_flash(q, k, v, axis_name, scale, causal, block_q,
                        block_k):
    """Ring forward with the Pallas hop kernels (ops.attention.
    flash_hop_fwd): the online-softmax state (m, l, acc) lives in
    [B, H, T_local, ...] layout and is updated by ONE Mosaic kernel
    per hop while the K/V blocks rotate; only the final hop's state is
    normalized.  Same math as the XLA-composed scan up to reduction
    order (unit-tested both ways)."""
    from distkeras_tpu.ops.attention import flash_hop_fwd

    b, t_local, h, d = q.shape
    n, me, ring = _ring(axis_name)
    me = jnp.int32(me)
    qt = jnp.swapaxes(q, 1, 2)                      # [B, H, T, D]

    vma = None if axis_name is None else frozenset({axis_name})

    def body(carry, s):
        k_blk, v_blk, m, l, acc = carry             # k/v in BHTD
        src = (me + s) % n
        m, l, acc = flash_hop_fwd(
            qt, k_blk, v_blk, m, l, acc,
            q_offset=me * t_local, k_offset=src * t_local,
            scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, vma=vma)
        if ring is not None:
            k_blk = lax.ppermute(k_blk, axis_name, ring)
            v_blk = lax.ppermute(v_blk, axis_name, ring)
        return (k_blk, v_blk, m, l, acc), None

    init = (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            *_vary(axis_name, (
                jnp.full((b, h, t_local, 1), _NEG, jnp.float32),
                jnp.zeros((b, h, t_local, 1), jnp.float32),
                jnp.zeros((b, h, t_local, d), jnp.float32))))
    (_, _, m, l, acc), _ = lax.scan(body, init, jnp.arange(n))
    l = jnp.maximum(l, 1e-30)
    out = jnp.swapaxes(acc / l, 1, 2)               # [B, T, H, D]
    return out, (m + jnp.log(l))[..., 0]            # lse [B, H, T]


def _forward_scan(q, k, v, axis_name, scale, causal, q_chunk=None):
    """Online-softmax ring forward.  Returns ``(out32 [B,T,H,D],
    L [B,H,T])`` where ``L = m + log(l)`` is the per-row logsumexp the
    backward pass needs to re-normalize recomputed probabilities.

    ``q_chunk`` blocks the within-device q dimension (flash-style):
    each ring hop processes q in chunks of that length sequentially
    (``lax.map``), bounding the transient logits block to
    ``[B, H, q_chunk, T_local]`` instead of ``[B, H, T_local,
    T_local]``.  All accumulators stay chunk-major for the whole ring
    scan and are unblocked once at the end."""
    q32 = q.astype(jnp.float32)
    b, t_local, h, d = q32.shape
    n, me, ring = _ring(axis_name)
    n_c, qc = _chunks(q_chunk, t_local)
    # chunk-major layouts: q [n_c, B, qc, H, D]; bookkeeping
    # [n_c, B, H, qc(, D)]; positions [n_c, qc]
    q_ch = _chunk_q_major(q32, n_c, qc)
    pos_ch = _pos_chunks(me, t_local, n_c, qc)

    def body(carry, s):
        k_blk, v_blk, m, l, acc = carry
        k32 = k_blk.astype(jnp.float32)
        v32 = v_blk.astype(jnp.float32)
        src = (me + s) % n

        def chunk(args):
            q_c, pos_c, m_c, l_c, acc_c = args
            logits = jnp.einsum("bqhd,bkhd->bhqk", q_c, k32) * scale
            if causal:
                mask = _block_mask(src, t_local, pos_c)
                logits = jnp.where(mask, logits, _NEG)
            m_new = jnp.maximum(m_c, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            if causal:
                p = p * mask  # exact zeros for masked entries
            corr = jnp.exp(m_c - m_new)
            l_c = l_c * corr + p.sum(axis=-1)
            acc_c = acc_c * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v32)
            return m_new, l_c, acc_c

        m, l, acc = lax.map(chunk, (q_ch, pos_ch, m, l, acc))
        # Rotate (the hop after the last step restores the original
        # placement, which keeps the scan carry shape uniform).  The
        # device-local mode (ring=None, n=1) has nowhere to rotate to.
        if ring is not None:
            k_blk = lax.ppermute(k_blk, axis_name, ring)
            v_blk = lax.ppermute(v_blk, axis_name, ring)
        return (k_blk, v_blk, m, l, acc), None

    init = (k, v, *_vary(axis_name, (
        jnp.full((n_c, b, h, qc), _NEG, jnp.float32),
        jnp.zeros((n_c, b, h, qc), jnp.float32),
        jnp.zeros((n_c, b, h, qc, d), jnp.float32))))
    (_, _, m, l, acc), _ = lax.scan(body, init, jnp.arange(n))
    # un-chunk: [n_c, B, H, qc(, D)] -> [B, H, T(, D)]
    m = jnp.moveaxis(m, 0, 2).reshape(b, h, t_local)
    l = jnp.moveaxis(l, 0, 2).reshape(b, h, t_local)
    acc = jnp.moveaxis(acc, 0, 2).reshape(b, h, t_local, d)
    l = jnp.maximum(l, 1e-30)
    out = jnp.einsum("bhqd->bqhd", acc / l[..., None])
    return out, m + jnp.log(l)


def _bwd_flash(axis_name, scale, causal, block_q, block_k, residuals,
               dout):
    """Reverse ring with the Pallas hop kernels: per hop,
    ``flash_hop_bwd`` emits this (q block)x(visiting k/v block) pair's
    partial gradients; dq accumulates locally, dk/dv accumulate on
    f32 carries that rotate WITH their k/v blocks (home after n hops).
    """
    from distkeras_tpu.ops.attention import flash_hop_bwd

    q, k, v, out, lse = residuals
    b, t_local, h, d = q.shape
    n, me, ring = _ring(axis_name)
    me = jnp.int32(me)
    qt = jnp.swapaxes(q, 1, 2)
    dot = jnp.swapaxes(dout, 1, 2).astype(q.dtype)
    out_t = jnp.swapaxes(out, 1, 2).astype(jnp.float32)
    dsum = jnp.sum(dot.astype(jnp.float32) * out_t, axis=-1,
                   keepdims=True)                   # [B, H, T, 1]
    lse4 = lse[..., None]                           # [B, H, T, 1]

    vma = None if axis_name is None else frozenset({axis_name})

    def body(carry, s):
        k_blk, v_blk, dk, dv, dq = carry
        src = (me + s) % n
        dq_p, dk_p, dv_p = flash_hop_bwd(
            qt, k_blk, v_blk, dot, lse4, dsum,
            q_offset=me * t_local, k_offset=src * t_local,
            scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, vma=vma)
        dq = dq + dq_p
        dk = dk + dk_p
        dv = dv + dv_p
        if ring is not None:
            k_blk = lax.ppermute(k_blk, axis_name, ring)
            v_blk = lax.ppermute(v_blk, axis_name, ring)
            dk = lax.ppermute(dk, axis_name, ring)
            dv = lax.ppermute(dv, axis_name, ring)
        return (k_blk, v_blk, dk, dv, dq), None

    zeros = jnp.zeros((b, h, t_local, d), jnp.float32)
    init = (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            *_vary(axis_name, (zeros, zeros, zeros)))
    (_, _, dk, dv, dq), _ = lax.scan(body, init, jnp.arange(n))
    return (jnp.swapaxes(dq, 1, 2).astype(q.dtype),
            jnp.swapaxes(dk, 1, 2).astype(k.dtype),
            jnp.swapaxes(dv, 1, 2).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8,
                                                    9))
def _ring_attention_f32(q, k, v, axis_name, scale, causal, q_chunk,
                        impl, block_q, block_k):
    if impl == "flash":
        out, _ = _forward_scan_flash(q, k, v, axis_name, scale,
                                     causal, block_q, block_k)
        return out
    out, _ = _forward_scan(q, k, v, axis_name, scale, causal, q_chunk)
    return out


def _fwd(q, k, v, axis_name, scale, causal, q_chunk, impl, block_q,
         block_k):
    if impl == "flash":
        out, lse = _forward_scan_flash(q, k, v, axis_name, scale,
                                       causal, block_q, block_k)
    else:
        out, lse = _forward_scan(q, k, v, axis_name, scale, causal,
                                 q_chunk)
    return out, (q, k, v, out, lse)


def _bwd_dispatch(axis_name, scale, causal, q_chunk, impl, block_q,
                  block_k, residuals, dout):
    if impl == "flash":
        return _bwd_flash(axis_name, scale, causal, block_q, block_k,
                          residuals, dout)
    return _bwd(axis_name, scale, causal, q_chunk, residuals, dout)


def _bwd(axis_name, scale, causal, q_chunk, residuals, dout):
    """Reverse ring: the flash-attention backward, with dK/dV
    accumulators traveling *with* their K/V blocks around the ring so
    each returns home after N hops having collected every device's
    contribution.  Per-device memory is O(T_local) — no per-step
    residual stacks (the motivation for the custom VJP).  ``q_chunk``
    blocks the q dimension within each hop exactly as the forward does
    (an inner ``lax.scan`` carrying the dK/dV accumulation across
    chunks)."""
    q, k, v, out, lse = residuals
    q32 = q.astype(jnp.float32)
    dout32 = dout.astype(jnp.float32)
    b, t_local, h, d = q32.shape
    n, me, ring = _ring(axis_name)
    n_c, qc = _chunks(q_chunk, t_local)
    # D_i = rowsum(dO_i * O_i), the softmax-jacobian diagonal term
    D = jnp.einsum("bqhd,bqhd->bhq", dout32, out.astype(jnp.float32))
    # chunk-major per-q tensors
    q_ch = _chunk_q_major(q32, n_c, qc)
    dout_ch = _chunk_q_major(dout32, n_c, qc)
    lse_ch = _chunk_bh_major(lse, n_c, qc)
    d_ch = _chunk_bh_major(D, n_c, qc)
    pos_ch = _pos_chunks(me, t_local, n_c, qc)

    def body(carry, s):
        k_blk, v_blk, dk, dv, dq = carry
        k32 = k_blk.astype(jnp.float32)
        v32 = v_blk.astype(jnp.float32)
        src = (me + s) % n

        def chunk(kv_carry, args):
            dk_a, dv_a = kv_carry
            q_c, pos_c, dout_c, lse_c, d_c, dq_c = args
            logits = jnp.einsum("bqhd,bkhd->bhqk", q_c, k32) * scale
            if causal:
                # mask BEFORE exp (as the forward does): a masked
                # future-key logit can exceed lse by enough to overflow
                # exp; relying on inf * False == 0 would pin
                # correctness to a lowering detail
                mask = _block_mask(src, t_local, pos_c)
                logits = jnp.where(mask, logits, _NEG)
            p = jnp.exp(logits - lse_c[..., None])  # normalized probs
            if causal:
                p = p * mask  # exact zeros
            dv_a = dv_a + jnp.einsum("bhqk,bqhd->bkhd", p, dout_c)
            dp = jnp.einsum("bqhd,bkhd->bhqk", dout_c, v32)
            ds = p * (dp - d_c[..., None]) * scale
            dq_c = dq_c + jnp.einsum("bhqk,bkhd->bqhd", ds, k32)
            dk_a = dk_a + jnp.einsum("bhqk,bqhd->bkhd", ds, q_c)
            return (dk_a, dv_a), dq_c

        (dk, dv), dq = lax.scan(
            chunk, (dk, dv),
            (q_ch, pos_ch, dout_ch, lse_ch, d_ch, dq))
        if ring is not None:
            k_blk = lax.ppermute(k_blk, axis_name, ring)
            v_blk = lax.ppermute(v_blk, axis_name, ring)
            dk = lax.ppermute(dk, axis_name, ring)
            dv = lax.ppermute(dv, axis_name, ring)
        return (k_blk, v_blk, dk, dv, dq), None

    zeros_kv = jnp.zeros((b, t_local, h, d), jnp.float32)
    dq0 = jnp.zeros((n_c, b, qc, h, d), jnp.float32)
    init = (k, v, *_vary(axis_name, (zeros_kv, zeros_kv, dq0)))
    (_, _, dk, dv, dq), _ = lax.scan(body, init, jnp.arange(n))
    dq = jnp.moveaxis(dq, 0, 1).reshape(b, t_local, h, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_ring_attention_f32.defvjp(_fwd, _bwd_dispatch)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str, scale: float | None = None,
                   causal: bool = True,
                   q_chunk: int | None = None,
                   impl: str = "xla",
                   block_q: int | None = None,
                   block_k: int | None = None) -> jax.Array:
    """Exact (flash-accumulated) attention over a ring of devices.

    Args:
      q, k, v: local sequence blocks ``[B, T_local, H, D]`` — the global
        time axis is sharded over ``axis_name`` in mesh order, so device
        ``i`` holds global positions ``[i*T_local, (i+1)*T_local)``.
      axis_name: the mesh axis the sequence is sharded over.
      scale: logit scale; defaults to ``D ** -0.5``.
      causal: apply a causal mask in *global* positions.
      q_chunk: optional within-device q block length (must divide
        ``T_local``).  Default (None) computes each ring hop's full
        ``[T_local, T_local]`` logits block at once; setting it
        processes q in chunks of this length sequentially, bounding the
        transient block to ``[q_chunk, T_local]`` — the flash-style
        memory/throughput trade for long local sequences.  Numerics are
        identical up to f32 reduction order.

    Returns:
      Attention output ``[B, T_local, H, D]`` in ``q.dtype`` (all
      accumulation in f32).

    Differentiation uses a custom reverse-ring VJP (flash backward:
    probabilities recomputed from the saved logsumexp, dK/dV
    accumulators riding the ring) with O(T_local) residual memory per
    device, honoring ``q_chunk``.  First-order only — higher-order
    autodiff through this op is not defined.

    ``impl="flash"`` runs each hop's block computation as the Pallas
    hop kernels (``ops.attention.flash_hop_fwd``/``flash_hop_bwd``;
    ``block_q``/``block_k`` as in ``flash_attention``) instead of the
    XLA-composed online softmax — the kernel path's VMEM-resident
    accumulators and K/V streaming inside each hop, with the ring
    still carrying the state between devices.  Math is identical up
    to f32 reduction order; ``q_chunk`` applies to the XLA impl only.
    """
    if impl not in ("xla", "flash"):
        raise ValueError(f"impl must be 'xla' or 'flash'; got {impl!r}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out = _ring_attention_f32(
        q, k, v, axis_name, float(scale), bool(causal),
        None if q_chunk is None else int(q_chunk), impl,
        None if block_q is None else int(block_q),
        None if block_k is None else int(block_k))
    return out.astype(q.dtype)


def ring_attn_fn(axis_name: str, causal: bool = True,
                 q_chunk: int | None = None, impl: str = "xla",
                 block_q: int | None = None,
                 block_k: int | None = None):
    """An ``AttnFn`` (``TransformerLM.attn_fn`` signature) bound to a
    mesh axis: ``fn(q, k, v, *, scale)``."""
    return functools.partial(ring_attention, axis_name=axis_name,
                             causal=causal, q_chunk=q_chunk,
                             impl=impl, block_q=block_q,
                             block_k=block_k)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        scale: float | None = None, causal: bool = True,
                        q_chunk: int | None = None) -> jax.Array:
    """Single-device flash-style attention: the ring machinery with no
    ring (n=1, no collectives).  The online-softmax q-chunking bounds
    the transient logits block to ``[B, H, q_chunk, T]`` and the custom
    VJP recomputes per-chunk probabilities from the saved logsumexp, so
    the ``[T, T]`` attention matrix is never materialized in either
    pass — the device-local answer to the dense path's quadratic HBM
    traffic at long T (PERF.md §13).  Numerics match
    ``dense_causal_attention`` up to f32 reduction order."""
    return ring_attention(q, k, v, axis_name=None, scale=scale,
                          causal=causal, q_chunk=q_chunk)


def blockwise_attn_fn(causal: bool = True, q_chunk: int | None = 128):
    """An ``AttnFn`` for ``TransformerLM(attn_fn=...)`` running
    device-local blockwise attention.  ``q_chunk=128`` is the measured
    optimum of the round-4 sweep on the v5e (PERF.md §13: 64/128/256/
    512 -> 0.370/0.388/0.325/0.231 6ND MFU at T=2048)."""
    return functools.partial(blockwise_attention, causal=causal,
                             q_chunk=q_chunk)


def sequence_sharded_apply(fn, mesh, seq_axis: str, *,
                           num_seq_args: int = 1):
    """Wrap ``fn(params, *arrays)`` in a ``shard_map`` that shards axis 1
    (time) of each array argument over ``seq_axis`` and replicates
    ``params`` — the standard harness for running a ``seq_axis``-enabled
    model (e.g. ``TransformerLM(seq_axis=...)``) sequence-parallel.

    ``num_seq_args`` array arguments follow ``params``; outputs are
    returned sequence-sharded (time axis 1).
    """
    from jax.sharding import PartitionSpec as P

    seq_spec = P(None, seq_axis)
    in_specs = (P(),) + (seq_spec,) * num_seq_args
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=seq_spec, check_vma=False)
