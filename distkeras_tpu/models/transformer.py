"""Decoder-only Transformer LM — the long-context flagship.

The reference has no long-sequence story (SURVEY.md §5: "long-context /
sequence parallelism: absent"); the TPU rebuild makes it first-class.  The
attention op is pluggable: dense causal attention on a single device, or
ring attention over a mesh axis (``distkeras_tpu.parallel.ring_attention``)
when ``seq_axis`` is set and the caller shards the time dimension
(``parallel.ring_attention.sequence_sharded_apply``).

By default (``attn="auto"``) the device-local attention spelling is
selected per shape from the measured recipe (PERF.md §17): Pallas flash
kernels at T >= 2048 (on TPU), the scan-composed blockwise path at
T=1024-class shapes, dense below — so an untuned model gets the fastest
measured execution for its sequence length.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distkeras_tpu.models.core import register_model
from distkeras_tpu.parallel.moe import expert_capacity, routing

AttnFn = Callable[..., jnp.ndarray]

_ATTN_CHOICES = ("auto", "dense", "blockwise", "flash")


def _committed_platform(x) -> Optional[str]:
    """Platform of the devices ``x`` is committed to, when knowable.

    Eager calls on placed arrays resolve against the ACTUAL placement
    (ADVICE r5: a CPU-forced debugging run on a TPU host must not pick
    the Pallas path).  Under ``jit`` the input is a tracer with no
    committed devices; returns None so callers fall back to the
    repo-wide ``jax.devices()[0]`` convention — the default backend's
    first device, which is where an unpinned trace executes."""
    try:
        platforms = {d.platform for d in x.devices()}
        if len(platforms) == 1:
            return platforms.pop()
    except Exception:
        pass
    return None


def dense_causal_attention(q, k, v, *, scale):
    """Plain causal attention: [B, T, H, D] -> [B, T, H, D]."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = q.shape[1]
    causal = jnp.tril(jnp.ones((t, t), dtype=bool))
    logits = jnp.where(causal[None, None], logits, -1e30)
    probs = nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _quantize_kv(x):
    """Symmetric per-(batch, position, head) int8 quantization of a
    K/V chunk: returns ``(int8 values, f32 scales [..., 1])``.  The
    scale is the row's abs-max over head_dim / 127, so dequantization
    (``int8 * scale``) is error-bounded by amax/254 per element."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = amax / 127.0
    q = jnp.where(scale > 0.0, xf / jnp.maximum(scale, 1e-30), 0.0)
    return jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8), scale


def _decode_read() -> Callable:
    """``ops.attention.decode_attention`` under a ``jax.jit`` of its own,
    for the layers of ONE step program: they hand the kernel the same
    shapes, so its body is traced and lowered once a program and not
    once a layer (0.1 s of the host each: the 72 calls in the three step
    programs of a 24-layer model were 9 s of a warm start).  Over a new
    function every time, because ``jit``'s cache goes by the function: a
    program traced later must still run what ``decode_attention`` does
    at trace time (``recording_decode_blocks``)."""
    from distkeras_tpu.ops import attention

    def decode_attention(q, k, v, lengths, *, scale):
        return attention.decode_attention(q, k, v, lengths, scale=scale)

    return jax.jit(decode_attention, static_argnames=("scale",))


class SelfAttention(nn.Module):
    """``cache_len > 0`` switches on autoregressive decode mode: K/V
    projections of every token seen so far persist in a ``"cache"``
    variable collection (``cached_key``/``cached_value`` sized
    ``[B, cache_len, KVH, D]``, plus an insertion ``cache_index``), and
    each call appends its T tokens and attends back over the whole
    prefix.  Position outside heads is the order a TPU v5e computes
    the slot-mode step in: declared ``[B, KVH, L, D]`` (as it was until
    PR 27) the compiled step copied every leaf whole into
    ``B, L, KVH, D`` order on the way in and back on the way out, 53 of
    84 ms a step in the serving cells (ledger, PR 26), because the
    per-row scatter asks for that order and both einsums read it at
    full rate (compile for a described ``v5e:2x2`` and chip runs of
    PR 27, PERF.md section 6).  Declared so, row-major is that order
    and no program that is handed the cache re-lays it out (heads of
    128; a TPU lays a leaf with heads of 64 out another way by default,
    and copies it under either declaration: PERF.md section 7).  A
    multi-token call (prefill) with an ``attn_fn`` runs the
    chunk through that kernel instead of the dense cache read — exact
    iff the cache was empty (poisoned loud otherwise).  No counterpart
    in the reference — it predates autoregressive serving entirely
    (SURVEY.md §0: MLP/CNN-era workloads; predictors are one batched
    forward).

    ``num_kv_heads`` (GQA): K/V project to fewer heads than Q; groups
    of ``num_heads/num_kv_heads`` query heads share a K/V head.  The
    decode-time win is the KV cache — its size and per-token HBM read
    shrink by the group factor (PERF.md §18: decode is cache+weight
    bandwidth-bound).  Training-path attention repeats K/V up to the
    full head count (the kernels expect matched heads).

    ``kv_cache_dtype="int8"`` stores the cache quantized (symmetric
    per-position-per-head scales in f32) — halving the bf16 cache's
    HBM traffic — and dequantizes on read.

    ``slot_pos`` (call-time, ``[B]`` int32) switches the T=1 step to
    SLOT mode for continuous-batching serving (``serving.DecodeEngine``):
    each batch row is an independent request at its OWN cache position,
    so the K/V write is a per-row scatter at ``slot_pos[b]`` and the
    causal mask is per-row (``k <= slot_pos[b]``).  The scalar
    ``cache_index`` is left untouched — slot state lives with the
    engine, which admits/evicts rows between steps.
    """

    num_heads: int
    dtype: jnp.dtype
    attn_fn: Optional[AttnFn] = None
    cache_len: int = 0
    num_kv_heads: Optional[int] = None
    kv_cache_dtype: Optional[str] = None
    #: ``ops.attention.decode_attention`` as ``TransformerLM.__call__``
    #: wrapped it for the layers of one program (``_decode_read``); None:
    #: the function itself
    decode_read: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, slot_pos=None, lengths=None):
        import jax.lax as lax

        d_model = x.shape[-1]
        if d_model % self.num_heads:
            raise ValueError(
                f"d_model={d_model} not divisible by "
                f"num_heads={self.num_heads}")
        head_dim = d_model // self.num_heads
        kvh = self.num_kv_heads or self.num_heads
        if self.num_heads % kvh:
            raise ValueError(
                f"num_heads={self.num_heads} not divisible by "
                f"num_kv_heads={kvh}")
        group = self.num_heads // kvh
        dense = lambda name, heads: nn.DenseGeneral(  # noqa: E731
            (heads, head_dim), dtype=self.dtype, name=name)
        q = dense("query", self.num_heads)(x)
        k = dense("key", kvh)(x)
        v = dense("value", kvh)(x)
        scale = head_dim ** -0.5
        if self.cache_len > 0:
            b, t = x.shape[0], x.shape[1]
            quant = self.kv_cache_dtype == "int8"
            store = jnp.int8 if quant else k.dtype
            # [B, L, KVH, D]: the order the compiled slot-mode step
            # works in on a v5e (the scatter asks for it, the einsums
            # read it at full rate), so the default row-major layout is
            # already the one every program computes in and none
            # copies a whole leaf to change it (PR 27: the decode
            # programs of an engine step fell from 84 to 26.5 ms)
            shape = (b, self.cache_len, kvh, head_dim)
            ck = self.variable("cache", "cached_key", jnp.zeros, shape,
                               store)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               shape, store)
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.zeros((), jnp.int32))
            idx = ci.value
            if slot_pos is not None and t != 1:
                raise ValueError(
                    "slot_pos is the continuous-batching T=1 step "
                    f"contract (per-row cache positions); got a T={t} "
                    "chunk — prefill a slot through the scalar-index "
                    "path instead")
            rows = jnp.arange(b)

            def write(cache, chunk):
                # chunk: [B, T, KVH, ...] into cache [B, L, KVH, ...]
                if slot_pos is not None:
                    # per-row scatter: row b writes its single token at
                    # its OWN position (OOB positions drop the update;
                    # the ok-poison below keeps that loud)
                    return cache.at[rows, slot_pos].set(chunk[:, 0])
                return lax.dynamic_update_slice(cache, chunk,
                                                (0, idx, 0, 0))

            with jax.named_scope("kv_write"):
                if quant:
                    sshape = (b, self.cache_len, kvh, 1)
                    ks = self.variable("cache", "key_scale", jnp.zeros,
                                       sshape, jnp.float32)
                    vs = self.variable("cache", "value_scale",
                                       jnp.zeros, sshape, jnp.float32)
                    k_w, k_s = _quantize_kv(k)
                    v_w, v_s = _quantize_kv(v)
                    ks.value = write(ks.value, k_s)
                    vs.value = write(vs.value, v_s)
                else:
                    k_w, v_w = k, v
                ck.value = write(ck.value, k_w)
                cv.value = write(cv.value, v_w)
            # Overflow is a traced condition (cache_index is dynamic),
            # so it cannot raise; dynamic_update_slice would silently
            # CLAMP the write and corrupt the cache.  Poison the
            # output with NaN instead — loud under jit, and it
            # propagates to any downstream logit/metric.
            if slot_pos is not None:
                ok = slot_pos + t <= self.cache_len        # [B]
            else:
                ci.value = idx + t
                ok = idx + t <= self.cache_len
            if t > 1 and self.attn_fn is not None:
                # Prefill through the block-attention kernel: causal
                # attention WITHIN the chunk, on the raw (pre-
                # quantization) projections.  Exact iff the cache was
                # empty (idx == 0) — which generate()'s prompt pass
                # guarantees; a mid-stream multi-token chunk needs
                # cross-chunk attention, so poison that loud too.
                kf, vf = k, v
                if group > 1:
                    kf = jnp.repeat(kf, group, axis=2)
                    vf = jnp.repeat(vf, group, axis=2)
                out = self.attn_fn(q, kf, vf, scale=scale)
                ok = jnp.logical_and(ok, idx == 0)
            else:
                # q rows sit at global positions idx..idx+t-1; causal
                # mask over the full cache (future slots are zeros AND
                # masked).  The grouped einsum attends each query-head
                # group to its shared K/V head without materializing a
                # repeated cache.  For the int8 cache
                # the per-row scales FACTOR OUT of both
                # contractions (they are constant over the contracted
                # d axis / ride the k axis), so the quantized cache
                # feeds the einsum through a fusable cast — never a
                # materialized dequantized copy (the round-5 measured
                # pitfall: dequantize-then-einsum was SLOWER than the
                # bf16 cache, PERF.md §18 addendum).
                from distkeras_tpu.ops import attention

                with jax.named_scope("attn_decode"):
                    keys, vals = ck.value, cv.value
                    # a slot-mode step's q in the kernel's grouped form:
                    # a reshape, T being 1 there
                    qk = q.reshape(b, kvh, -1, head_dim)
                    if t == 1 and attention.decode_attention_applies(
                            qk, keys, vals, lengths):
                        # each row's live blocks only; a finished
                        # slot's row (length 0) reads nothing and gives
                        # zeros
                        read = self.decode_read or attention.decode_attention
                        out = read(qk, keys, vals, lengths, scale=scale)
                    else:
                        if quant:
                            keys = keys.astype(q.dtype)
                            vals = vals.astype(q.dtype)
                        if slot_pos is not None:
                            q_pos = slot_pos[:, None]               # [B, 1]
                        else:
                            q_pos = (idx + jnp.arange(t))[None, :]  # [1, t]
                        k_pos = jnp.arange(self.cache_len)
                        # [B|1, t, L]: per-row causal horizon in slot mode
                        mask = k_pos[None, None, :] <= q_pos[:, :, None]
                        qg = q.reshape(b, t, kvh, group, head_dim)
                        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, keys) \
                            * scale

                        def per_key(scales):
                            # [B, L, KVH, 1] -> [B, KVH, 1, 1, L]: one
                            # factor a cached row, over (g, q)
                            return jnp.swapaxes(
                                scales[..., 0], 1, 2)[:, :, None, None, :]

                        if quant:
                            logits = logits * per_key(ks.value)
                        logits = jnp.where(mask[:, None, None], logits,
                                           -1e30)
                        probs = nn.softmax(logits.astype(jnp.float32),
                                           axis=-1).astype(q.dtype)
                        if quant:
                            probs = (probs.astype(jnp.float32)
                                     * per_key(vs.value)).astype(q.dtype)
                        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vals)
                    out = out.reshape(b, t, self.num_heads, head_dim)
            if jnp.ndim(ok):          # slot mode: per-row poison only
                ok = ok[:, None, None, None]
            out = jnp.where(ok, out, jnp.nan)
        else:
            attn = self.attn_fn or dense_causal_attention
            if group > 1:
                # attention fns expect matched head counts; GQA's win
                # is the serving-time cache, so training repeats K/V
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            out = attn(q, k, v, scale=scale)
        return nn.DenseGeneral(d_model, axis=(-2, -1), dtype=self.dtype,
                               name="out")(out)


class MoEFFN(nn.Module):
    """Mixture-of-experts FFN in the dense einsum (GShard/Mesh-TF)
    form: every expert-dim op is a batched matmul over ``E``, so
    sharding the parameters' leading expert axis (see
    ``parallel.tensor_parallel.TRANSFORMER_TP_RULES``) makes GSPMD
    derive the expert-parallel communication — no ``shard_map``
    needed, and the same module runs replicated on one device.

    Routing reuses ``parallel.moe._routing`` (top-k, capacity
    bucketing, f32 bookkeeping).  The load-balancing auxiliary loss is
    sown into the ``"losses"`` collection, which
    ``workers.make_train_step`` adds to the objective."""

    num_experts: int
    mlp_ratio: int
    dtype: jnp.dtype
    capacity_factor: float = 1.25
    top_k: int = 1
    aux_loss_weight: float = 0.01

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        h = d * self.mlp_ratio
        e = self.num_experts
        if not 1 <= self.top_k <= e:
            raise ValueError(
                f"expert_top_k={self.top_k} out of range [1, {e}]")
        tokens = x.reshape(b * t, d)
        capacity = expert_capacity(b * t, e, self.capacity_factor,
                                   self.top_k)
        router = self.param(
            "router", nn.initializers.normal(d ** -0.5), (d, e))
        w_in = self.param(
            "w_in", nn.initializers.normal(d ** -0.5), (e, d, h))
        b_in = self.param("b_in", nn.initializers.zeros, (e, h))
        w_out = self.param(
            "w_out", nn.initializers.normal(h ** -0.5), (e, h, d))
        b_out = self.param("b_out", nn.initializers.zeros, (e, d))

        dispatch, combine, aux = routing(
            tokens.astype(self.dtype), router, e, capacity, self.top_k)
        expert_in = jnp.einsum("tec,td->ecd", dispatch,
                               tokens.astype(self.dtype))
        hidden = nn.gelu(
            jnp.einsum("ecd,edh->ech", expert_in,
                       w_in.astype(self.dtype))
            + b_in.astype(self.dtype)[:, None])
        out = (jnp.einsum("ech,ehd->ecd", hidden,
                          w_out.astype(self.dtype))
               + b_out.astype(self.dtype)[:, None])
        y = jnp.einsum("tec,ecd->td", combine, out)
        self.sow("losses", "moe_load_balance",
                 self.aux_loss_weight * aux.load_balance_loss)
        return y.reshape(b, t, d)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int
    dtype: jnp.dtype
    attn_fn: Optional[AttnFn] = None
    num_experts: int = 0  # 0 = dense MLP; >0 = MoE FFN
    expert_capacity_factor: float = 1.25
    expert_top_k: int = 1
    cache_len: int = 0  # >0 = autoregressive decode (KV cache)
    num_kv_heads: Optional[int] = None
    kv_cache_dtype: Optional[str] = None
    decode_read: Optional[Callable] = None  # see SelfAttention

    @nn.compact
    def __call__(self, x, slot_pos=None, lengths=None):
        d_model = x.shape[-1]
        y = nn.LayerNorm(dtype=self.dtype)(x)
        x = x + SelfAttention(self.num_heads, self.dtype, self.attn_fn,
                              cache_len=self.cache_len,
                              num_kv_heads=self.num_kv_heads,
                              kv_cache_dtype=self.kv_cache_dtype,
                              decode_read=self.decode_read)(
                                  y, slot_pos, lengths)
        y = nn.LayerNorm(dtype=self.dtype)(x)
        if self.num_experts > 0:
            y = MoEFFN(self.num_experts, self.mlp_ratio, self.dtype,
                       self.expert_capacity_factor, self.expert_top_k,
                       name="moe")(y)
        else:
            with jax.named_scope("mlp"):
                y = nn.Dense(d_model * self.mlp_ratio,
                             dtype=self.dtype)(y)
                y = nn.gelu(y)
                y = nn.Dense(d_model, dtype=self.dtype)(y)
        return x + y


class _BlockScanBody(nn.Module):
    """``nn.scan``-compatible wrapper: ``(carry, _) -> (carry, None)``
    around one ``Block`` so the layer stack's parameters materialize as
    one stacked pytree (leading axis = layers) — the homogeneous form
    pipeline parallelism slices per stage (``parallel.pipeline``)."""

    num_heads: int
    mlp_ratio: int
    dtype: Any = jnp.bfloat16
    num_kv_heads: Optional[int] = None

    @nn.compact
    def __call__(self, carry, _):
        return Block(self.num_heads, self.mlp_ratio, self.dtype,
                     num_kv_heads=self.num_kv_heads,
                     name="layer")(carry), None


@register_model("transformer_lm")
class TransformerLM(nn.Module):
    """``seq_axis``: name of a mesh axis the *time* dimension is sharded
    over.  When set, the module is an SPMD program to be applied inside
    ``jax.shard_map`` (see ``parallel.ring_attention.sequence_sharded_
    apply``): positions are offset by the device's ring index and
    attention defaults to ``ring_attention`` over that axis.  Every other
    sublayer is position-wise, so nothing else changes — the same
    parameters run dense or sequence-parallel."""

    vocab_size: int = 32000
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    mlp_ratio: int = 4
    max_len: int = 2048
    dtype: str = "bfloat16"
    attn_fn: Optional[AttnFn] = None  # None -> auto / dense / ring
    seq_axis: Optional[str] = None
    # within-device q block length for ring/blockwise attention (None =
    # full block); see parallel.ring_attention.ring_attention(q_chunk=)
    attn_q_chunk: Optional[int] = None
    #: device-local attention spelling.  The default ``"auto"`` applies
    #: the measured per-shape recipe (PERF.md §17): ``"flash"`` at
    #: T >= 2048 (on TPU, where the Mosaic kernels run; elsewhere the
    #: blockwise path substitutes), ``"blockwise"`` at T=1024-class
    #: shapes, ``"dense"`` below — the regime boundary tracks the
    #: quadratic term's share of the step (§17 addendum), so small B·H
    #: long-T shapes sit exactly where the measured rows put them.
    #: T must be a multiple of 128 for the blocked spellings (else
    #: auto falls back to dense).  Explicit values force one spelling;
    #: the ``flash_attn``/``blockwise_attn`` booleans and ``attn_fn``
    #: (strongest) override this field.  Under ``scan_blocks`` /
    #: ``decode`` T=1 steps, auto resolves to dense.
    #: The Mosaic kernels carry no partitioning rule: under a
    #: multi-device GSPMD ``jit`` (``SyncTrainer`` on more than one
    #: chip) JAX refuses them at lowering, so pin ``attn="blockwise"``
    #: there; ``shard_map`` programs (the ``mesh`` PS tier, ring
    #: attention) and single-device programs take them as they are.
    attn: str = "auto"
    #: single-device flash-style attention (JSON-able spelling of
    #: attn_fn=blockwise_attn_fn(...)): online-softmax q-chunking, the
    #: [T, T] logits never materialize — the long-T device-local path
    #: (PERF.md §13).  q chunk length = attn_q_chunk (default 128, the
    #: measured v5e optimum).
    blockwise_attn: bool = False
    #: hand-written Pallas flash-attention kernels (JSON-able spelling
    #: of attn_fn=ops.attention.flash_attn_fn()): same online-
    #: softmax algorithm as blockwise_attn but as one Mosaic kernel per
    #: pass — accumulators VMEM-resident, k/v blocks pipelined, causal
    #: blocks grid-skipped.  The fastest long-T path on the v5e
    #: (PERF.md §17).  Always uses the kernel's measured block defaults
    #: (512/1024, auto-clamped to divisors of T); attn_q_chunk applies
    #: to the blockwise/ring paths only — its tuned values (~128) sit
    #: in the kernel's WORST regime, so it is deliberately not reused
    #: here.  To tune blocks, pass attn_fn=flash_attn_fn(block_q=...).
    flash_attn: bool = False
    #: GQA (grouped-query attention): number of K/V heads; must divide
    #: num_heads.  None = one K/V head per query head (MHA).  Shrinks
    #: the decode-time KV cache — the dominant per-token HBM read at
    #: batch (PERF.md §18) — by num_heads/num_kv_heads; training-path
    #: kernels see K/V repeated to the full head count.
    num_kv_heads: Optional[int] = None
    #: storage dtype of the serving KV cache (decode=True only).
    #: None = the activation dtype; "int8" = symmetric per-position-
    #: per-head quantization (f32 scales) — halves the bf16 cache's
    #: per-token HBM traffic at an error bounded by amax/254 per
    #: element (tolerance-tested in tests/test_generate.py).
    kv_cache_dtype: Optional[str] = None
    # >0 replaces every block's MLP with a mixture-of-experts FFN
    # (dense einsum form — shard the expert axes via the TP rules for
    # expert parallelism); the load-balance aux loss rides the
    # "losses" collection into the training objective
    num_experts: int = 0
    expert_capacity_factor: float = 1.25
    expert_top_k: int = 1
    #: stack the layer parameters [num_layers, ...] via nn.scan (same
    #: math per layer; different param-tree layout).  Required by the
    #: pipeline-parallel trainer path, which shards the layer stack's
    #: leading axis across stages.  Incompatible with attn_fn/seq_axis/
    #: MoE (those paths keep per-layer modules); attn="auto" resolves
    #: to dense under scan.
    scan_blocks: bool = False
    #: rematerialize each Block in the backward pass
    #: (``jax.checkpoint``): activations inside a block are recomputed
    #: from its input instead of stored, trading ~1 extra forward of
    #: FLOPs for O(layers) less activation memory — the lever that
    #: fits batches past the HBM envelope at long T (measured: b8 at
    #: T=8192 OOMs by 2.4 GB without it; PERF.md §19).
    remat_blocks: bool = False
    #: autoregressive decode mode for serving (``models.generate``):
    #: every attention layer keeps a ``max_len``-slot KV cache in the
    #: ``"cache"`` variable collection and calls append to it, so the
    #: prompt is processed once and each new token costs one T=1 step.
    #: Apply with ``mutable=["cache"]`` and thread the returned cache.
    #: Returns logits for the LAST input position only ([B, 1, V]) —
    #: the one generation consumes; full-vocab f32 logits over a whole
    #: prompt would dominate prefill activations for nothing (pass
    #: ``last_index`` to select a different single position — the
    #: right-padded-prompt contract of ``serving.DecodeEngine``).  Same
    #: parameters as the training-mode model (``decode`` changes
    #: execution, not the param tree).  The attention spelling
    #: (attn/flash_attn/blockwise_attn/attn_fn) selects the PREFILL
    #: attention: a multi-token chunk at cache position 0 runs through
    #: that kernel instead of a dense read of the whole cache (the
    #: round-4 gap: a T=4096 prompt paid O(T·max_len) dense prefill
    #: while training the same shape got the flash kernels).  T=1
    #: steps always use the cached dense row.  Incompatible with
    #: seq_axis / scan_blocks.
    decode: bool = False
    #: size of the per-layer KV cache in decode mode (default:
    #: ``max_len``).  PERF.md §18 proved every T=1 step pays for the
    #: STATIC cache envelope, not the live prefix — so a serving slot
    #: pool whose requests fit 512 positions should carry a 512-slot
    #: cache even when the model's position table (``max_len``) is
    #: 2048.  Must be <= max_len (positions are still embedded from
    #: the full table, so the params are unchanged).  Decode-only.
    cache_envelope: Optional[int] = None

    def decode_clone(self) -> "TransformerLM":
        """The model as ``generate()`` and ``DecodeEngine`` run it (the
        decode contract, ``models.generate.DECODE_CONTRACT``)."""
        if self.scan_blocks:
            raise ValueError(
                "generate() cannot serve scan_blocks=True models: the "
                "stacked param layout differs from the per-layer one the "
                "decode path walks.  Un-stack the params (or train "
                "without scan_blocks) to serve this model.")
        if self.num_experts > 0:
            raise ValueError(
                "generate() cannot serve this model's capacity-bucketed "
                "MoEFFN: over a T=1 decode step its routing diverges "
                "from the full-forward routing (different tokens "
                "overflow and drop), so cached decode would silently "
                "differ from what the trained model predicts.  Serve "
                "it via the dense full-forward path (predictors); the "
                "experts that ARE served are the dropless ones of "
                "models.latent_moe.LatentMoELM.")
        # The attention spellings (attn="auto"/flash_attn/blockwise_attn)
        # are KEPT: decode mode uses them as the prefill kernel, so a long
        # prompt runs the same flash/blockwise path training uses instead
        # of a dense O(T·max_len) read of the cache; each generated token
        # is a cached T=1 step either way.  Custom attn_fn and ring
        # (seq_axis) are cleared — their contracts are training-path
        # shapes.  remat_blocks off: decode never runs a backward pass,
        # so rematerializing every step is pure overhead (ADVICE r4).
        return self.clone(decode=True, attn_fn=None, seq_axis=None,
                          remat_blocks=False)

    def dense_prefill_clone(self) -> "TransformerLM":
        """A clone whose multi-token chunks read the cache densely:
        exact at any offset, where the blocked prefill kernels are
        exact only from an empty cache."""
        return self.clone(attn="dense", attn_fn=None, flash_attn=False,
                          blockwise_attn=False)

    def _local_attn_fn(self, t: int,
                       platform: Optional[str] = None) -> Optional[AttnFn]:
        """Resolve the device-local attention spelling for sequence
        length ``t`` (None = dense).  Precedence: attn_fn > the
        boolean spellings > ``attn`` (whose "auto" applies the
        measured PERF.md §17 recipe).

        ``platform`` is where the computation runs — taken from the
        devices the input is committed to when that is knowable
        (eager calls on placed arrays), else the repo-wide
        ``jax.devices()[0]`` convention: under ``jit`` the input is a
        tracer with no committed devices, and the default backend's
        first device is where an unpinned trace executes.  A
        CPU-forced debugging run on a TPU host therefore resolves
        "auto" against CPU when the arrays are committed there; pin
        ``attn=`` explicitly to override either way."""
        if self.attn_fn is not None:
            return self.attn_fn
        spelling = self.attn
        if self.flash_attn:
            spelling = "flash"
        elif self.blockwise_attn:
            spelling = "blockwise"
        if spelling == "auto":
            # measured recipe: flash at T>=2048 (TPU), blockwise at
            # T=1024-class, dense below; blocked spellings need
            # 128-aligned T (Mosaic tiling / chunk divisibility)
            if t < 1024 or t % 128:
                return None
            if platform is None:
                platform = jax.devices()[0].platform
            if t >= 2048 and platform == "tpu":
                spelling = "flash"
            else:
                spelling = "blockwise"
        if spelling == "dense":
            return None
        if spelling == "flash":
            from distkeras_tpu.ops.attention import flash_attn_fn

            return flash_attn_fn()
        from distkeras_tpu.parallel.ring_attention import \
            blockwise_attn_fn

        return blockwise_attn_fn(q_chunk=self.attn_q_chunk or 128)

    @nn.compact
    def __call__(self, tokens, train: bool = False, *,
                 slot_pos=None, lengths=None, last_index=None,
                 logits_all: bool = False):
        import jax.lax as lax

        dtype = jnp.dtype(self.dtype)
        tokens = tokens.astype(jnp.int32)
        t = tokens.shape[1]
        platform = _committed_platform(tokens)
        if self.attn not in _ATTN_CHOICES:
            raise ValueError(
                f"attn={self.attn!r} not one of {_ATTN_CHOICES}")
        if self.kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_cache_dtype={self.kv_cache_dtype!r} must be None "
                "(activation dtype) or 'int8'")
        attn_fn = self.attn_fn
        cache_len = 0
        if self.decode:
            if self.seq_axis is not None or self.scan_blocks:
                raise ValueError(
                    "decode=True is the KV-cache serving path: "
                    "seq_axis/scan_blocks do not apply (the attention "
                    "spellings select the PREFILL kernel; generated "
                    "tokens are cached T=1 steps)")
            if self.num_experts > 0:
                raise ValueError(
                    "decode=True cannot serve this model's capacity-"
                    "bucketed MoEFFN: its routing over a short decode "
                    "step diverges from the full-forward routing the "
                    "model trained with (different tokens overflow and "
                    "drop) — serve it via the dense full-forward path "
                    "(predictors); dropless experts are served by "
                    "models.latent_moe.LatentMoELM")
            cache_len = self.cache_envelope or self.max_len
            if not 0 < cache_len <= self.max_len:
                raise ValueError(
                    f"cache_envelope={self.cache_envelope} outside "
                    f"(0, max_len={self.max_len}]: the envelope is a "
                    "slot-pool cache SIZE; positions still embed from "
                    "the max_len table")
            if t > cache_len:
                raise ValueError(
                    f"decode chunk length {t} exceeds the cache size "
                    f"{cache_len}")
        if self.cache_envelope is not None and not self.decode:
            raise ValueError(
                "cache_envelope sizes the decode-mode KV cache; it "
                "has no meaning without decode=True")
        if (slot_pos is not None or last_index is not None
                or logits_all) and not self.decode:
            raise ValueError(
                "slot_pos/last_index/logits_all are decode-mode "
                "serving contracts (per-slot cache positions / "
                "right-padded prompt logit row / speculative verify); "
                "set decode=True")
        if logits_all and last_index is not None:
            raise ValueError(
                "logits_all returns every position's logits; "
                "last_index selects one — pass at most one of them")
        if lengths is not None and slot_pos is None:
            raise ValueError(
                "lengths says how far each slot_pos row's cache is "
                "live; it has no meaning without slot_pos")
        if slot_pos is not None and t != 1:
            raise ValueError(
                "slot_pos advances every live slot by ONE token; got "
                f"a T={t} chunk — prefill new slots through the "
                "scalar-index path (serving.DecodeEngine does)")
        if self.blockwise_attn and self.flash_attn:
            raise ValueError(
                "blockwise_attn and flash_attn are mutually exclusive "
                "spellings of the device-local flash-style attention "
                "path")
        if self.seq_axis is not None and (
                self.blockwise_attn or self.flash_attn
                or self.attn != "auto"):
            raise ValueError(
                "blockwise_attn/flash_attn/attn are device-local "
                "attention spellings; with seq_axis the attention is "
                "ring attention over the mesh — use attn_q_chunk to "
                "bound its within-device blocks instead")
        if self.seq_axis is not None:
            from distkeras_tpu.parallel.ring_attention import ring_attn_fn

            t_global = t * lax.axis_size(self.seq_axis)
            positions = (lax.axis_index(self.seq_axis) * t
                         + jnp.arange(t))[None, :]
            if attn_fn is None:
                attn_fn = ring_attn_fn(self.seq_axis,
                                       q_chunk=self.attn_q_chunk)
        elif self.decode:
            t_global = t  # chunk length; prefix bound checked above
            pos_var = self.variable("cache", "pos_index",
                                    lambda: jnp.zeros((), jnp.int32))
            if slot_pos is not None:
                # continuous batching: each slot is at its OWN
                # position; the engine owns slot state, so the scalar
                # pos_index is left untouched
                positions = slot_pos[:, None]
            else:
                positions = (pos_var.value + jnp.arange(t))[None, :]
                pos_var.value = pos_var.value + t
            # multi-token chunks (prefill) run the resolved kernel
            # inside SelfAttention; T=1 steps use the cached row.
            # Serving prompts have ARBITRARY lengths and the blocked
            # kernels reject unaligned ones (q_chunk divisibility /
            # Mosaic tiling), so every spelling falls back to the
            # dense cache read off the 128-aligned grid — a slower
            # prefill must never be a serving error.  A custom
            # attn_fn is honored as given (the caller owns its
            # shape contract; generate() clears it).
            if t > 1 and (self.attn_fn is not None or t % 128 == 0):
                attn_fn = self._local_attn_fn(t, platform)
            else:
                attn_fn = None
        else:
            t_global = t
            positions = jnp.arange(t)[None, :]
            if not self.scan_blocks:
                attn_fn = self._local_attn_fn(t, platform)
        if t_global > self.max_len:
            raise ValueError(
                f"sequence length {t_global} exceeds "
                f"max_len={self.max_len}")
        x = nn.Embed(self.vocab_size, self.d_model, dtype=dtype)(tokens)
        pos = nn.Embed(self.max_len, self.d_model, dtype=dtype,
                       name="pos_embed")(positions)
        x = x + pos
        if self.scan_blocks:
            if (self.num_experts > 0 or self.attn_fn is not None
                    or self.seq_axis is not None or self.blockwise_attn
                    or self.flash_attn or self.remat_blocks
                    or self.attn not in ("auto", "dense")):
                raise ValueError(
                    "scan_blocks=True supports the dense-attention, "
                    "dense-FFN transformer only (MoE / custom attn / "
                    "seq_axis / remat_blocks keep per-layer modules)")
            scanned = nn.scan(
                _BlockScanBody,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                length=self.num_layers)(
                    self.num_heads, self.mlp_ratio, dtype,
                    num_kv_heads=self.num_kv_heads,
                    name="blocks")
            x, _ = scanned(x, None)
        else:
            block_cls = nn.remat(Block) if self.remat_blocks else Block
            decode_read = _decode_read() if lengths is not None else None
            for i in range(self.num_layers):
                # explicit names keep the param tree identical whether
                # or not remat wraps the block (nn.remat's auto-name
                # would be CheckpointBlock_i) — remat_blocks can be
                # toggled on existing checkpoints
                x = block_cls(self.num_heads, self.mlp_ratio, dtype,
                              attn_fn, self.num_experts,
                              self.expert_capacity_factor,
                              self.expert_top_k,
                              cache_len=cache_len,
                              num_kv_heads=self.num_kv_heads,
                              kv_cache_dtype=self.kv_cache_dtype,
                              decode_read=decode_read,
                              name=f"Block_{i}")(x, slot_pos, lengths)
        if self.decode:
            # serving returns next-token logits only: the f32
            # full-vocab lm_head over every prompt position would be
            # the prefill's dominant activation for nothing (only the
            # last row seeds generation).  last_index selects a
            # different single row — the right-padded-prompt prefill
            # contract (pad rows trail the real last token, so -1
            # would read a pad position's logits).
            if last_index is not None:
                x = lax.dynamic_slice_in_dim(x, last_index, 1, 1)
            elif not logits_all:
                x = x[:, -1:]
            # logits_all: the speculative-verify contract — every
            # position's logits survive to the lm_head (T is the
            # small proposal window k+1 there, so the full-vocab f32
            # head stays cheap)
        x = nn.LayerNorm(dtype=dtype)(x)
        return nn.Dense(self.vocab_size, dtype=jnp.float32,
                        name="lm_head")(x)
