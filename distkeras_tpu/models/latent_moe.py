"""A decoder whose three sublayer mechanisms differ from ``TransformerLM``'s:

* **latent attention (MLA)**: queries and keys/values go through low-rank
  projections; what is cached for a token is one ``kv_lora_rank``-wide
  latent (after its RMSNorm) plus one ``qk_rope_head_dim``-wide rotated
  key shared by all heads, ``[B, L, kv_lora_rank + qk_rope_head_dim]`` a
  layer.  A multi-token chunk on an empty cache (prefill) runs the
  *expanded* form: the latent is projected up to per-head keys and
  values and the chunk goes through the block-attention kernel.  Every
  other call (the T=1 slot-mode step, a chunk in mid-stream) runs the
  *absorbed* form: the up-projections are folded into the query and the
  output, so attention reads the latent cache as it is and never
  expands it.
* **sparse experts without a capacity**: sigmoid scores with a selection
  bias, top-k renormalised and scaled, a shared expert beside them
  (``parallel.moe.sigmoid_topk`` / ``dropless_experts``).  The layer is
  told which experts it holds (``experts_held``), routes over all of
  them and computes its own experts' part.
* **a constrained multi-stream residual** (hyper-connections,
  arXiv:2409.19606, with the residual mixing projected onto the doubly
  stochastic matrices by Sinkhorn iterations, arXiv:2512.24880): the
  stream is ``hc_mult`` copies wide, each sublayer reads a per-token
  mixture of the copies and writes back through per-token weights.

It implements the same decode contract as ``TransformerLM`` (``decode``,
``cache_envelope``, ``max_len``, ``vocab_size``, ``slot_pos`` /
``last_index`` / ``logits_all``, cache leaves ``[B, L, ...]`` plus scalar
indices, ``decode_clone()`` / ``dense_prefill_clone()``), so
``generate()`` and ``serving.DecodeEngine`` serve it unchanged.
Besides, each expert layer sows the tokens it routed to each expert into
the ``"expert_load"`` collection, which the engine reads when it is made
mutable.  Serving only: there is no auxiliary loss and no training rule
for the selection bias here.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distkeras_tpu import layouts
from distkeras_tpu.models.core import register_model
from distkeras_tpu.models.layers import (RMSNorm, SwiGLU,
                                         apply_rotary_interleaved,
                                         rms_norm, rotary_angles,
                                         rotary_inv_freq, yarn_mscale)
from distkeras_tpu.models.transformer import (_committed_platform,
                                              dense_causal_attention)
from distkeras_tpu.parallel import moe


def sinkhorn(logits, iters: int, eps: float):
    """``exp(logits)`` ``[..., n, n]`` pushed towards the doubly
    stochastic matrices: ``iters`` rounds of dividing each row, then
    each column, by its sum (+ ``eps``)."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


class StreamMixer(nn.Module):
    """The per-token read, write and residual weights of one sublayer
    over an ``n``-copy stream ``X`` ``[B, T, n, d]`` (float32):
    ``pre [B, T, n]``, ``post [B, T, n]`` and the doubly stochastic
    ``res [B, T, n, n]``.  All of it runs in float32 at full matmul
    precision."""

    iters: int
    eps: float
    clamp: tuple
    norm_eps: float

    @nn.compact
    def __call__(self, x):
        b, t, n, d = x.shape
        width = 2 * n + n * n
        gain = self.param("norm", nn.initializers.ones, (n * d,))
        phi = self.param("phi", nn.initializers.normal(0.01),
                         (n * d, width))
        # one gate each for the read, the write and the residual part
        a = self.param("a", nn.initializers.constant(0.01), (3,))
        bias = self.param("b", nn.initializers.zeros, (width,))
        flat = rms_norm(x.reshape(b, t, n * d), gain, self.norm_eps)
        a_wide = jnp.repeat(a.astype(jnp.float32),
                            np.array([n, n, n * n]))
        h = jnp.dot(flat, phi.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST) * a_wide \
            + bias.astype(jnp.float32)
        pre = jax.nn.sigmoid(h[..., :n])
        post = 2.0 * jax.nn.sigmoid(h[..., n:2 * n])
        res = sinkhorn(
            jnp.clip(h[..., 2 * n:], *self.clamp).reshape(b, t, n, n),
            self.iters, self.eps)
        return pre, post, res


class LatentAttention(nn.Module):
    """Multi-head latent attention; see the module docstring.

    ``expanded_fn`` is the block-attention kernel of a multi-token chunk
    (``None`` = always the absorbed read of the cache, exact at any
    offset).  The cache leaf ``cached_latent`` is ``[B, L, c + r]``: the
    normed latent then the rotated shared key (then zeros up to a
    multiple of 128, where ``c + r`` is wider than that)."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    #: arguments of ``layers.rotary_inv_freq`` after the width
    rope: tuple
    softmax_scale: float
    norm_eps: float
    dtype: jnp.dtype
    cache_len: int = 0
    expanded_fn: Optional[object] = None

    @nn.compact
    def __call__(self, x, positions, slot_pos=None):
        b, t, d = x.shape
        hn, c, r = self.num_heads, self.kv_lora_rank, self.qk_rope_head_dim
        nope, dv = self.qk_nope_head_dim, self.v_head_dim
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        c_q = RMSNorm(self.norm_eps, self.dtype, name="q_norm")(
            dense(self.q_lora_rank, "q_down")(x))
        q = nn.DenseGeneral((hn, nope + r), use_bias=False,
                            dtype=self.dtype, name="q_up")(c_q)
        down = dense(c + r, "kv_down")(x)
        c_kv = RMSNorm(self.norm_eps, self.dtype, name="kv_norm")(
            down[..., :c])
        theta, factor, fast, slow, original = self.rope
        cos, sin = rotary_angles(positions, rotary_inv_freq(
            r, theta, factor=factor, beta_fast=fast, beta_slow=slow,
            original_max_len=original))               # [B|1, T, r / 2]
        q_nope = q[..., :nope]
        q_rope = apply_rotary_interleaved(
            q[..., nope:], cos[:, :, None], sin[:, :, None])
        k_rope = apply_rotary_interleaved(down[..., c:], cos, sin)
        # [B, T, c + r (+ pad)]: the cache leaf at the width that the
        # pools' programs work on in place; the zeros change neither
        # product
        pad = layouts.lane_padded(c + r) - (c + r)
        latent = jnp.concatenate(
            [c_kv, k_rope] + [jnp.zeros((b, t, pad), c_kv.dtype)] * bool(pad),
            axis=-1)
        w_ukv = self.param("kv_up", nn.initializers.lecun_normal(),
                           (c, hn, nope + dv)).astype(self.dtype)

        def expanded(attn):
            kv = jnp.einsum("btc,chk->bthk", c_kv, w_ukv)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope[:, :, None], (b, t, hn, r))],
                axis=-1)
            qf = jnp.concatenate([q_nope, q_rope], axis=-1)
            return attn(qf, k, kv[..., nope:], scale=self.softmax_scale)

        if self.cache_len == 0:
            out = expanded(self.expanded_fn or dense_causal_attention)
        else:
            cl = self.variable("cache", "cached_latent", jnp.zeros,
                               (b, self.cache_len, c + r + pad),
                               latent.dtype)
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.zeros((), jnp.int32))
            idx = ci.value
            if slot_pos is not None and t != 1:
                raise ValueError(
                    "slot_pos is the continuous-batching T=1 step "
                    f"contract (per-row cache positions); got a T={t} "
                    "chunk")
            with jax.named_scope("latent_write"):
                if slot_pos is not None:
                    cl.value = cl.value.at[jnp.arange(b), slot_pos].set(
                        latent[:, 0])
                else:
                    cl.value = lax.dynamic_update_slice(
                        cl.value, latent, (0, idx, 0))
            # an overflowing write would be clamped in silence: poison
            # the output instead (as SelfAttention does)
            if slot_pos is not None:
                ok = slot_pos + t <= self.cache_len
            else:
                ci.value = idx + t
                ok = idx + t <= self.cache_len
            if t > 1 and self.expanded_fn is not None:
                # exact iff the cache was empty: the chunk attends to
                # itself alone
                with jax.named_scope("mla_prefill"):
                    out = expanded(self.expanded_fn)
                ok = jnp.logical_and(ok, idx == 0)
            else:
                q_lat = jnp.einsum("bthk,chk->bthc", q_nope,
                                   w_ukv[..., :nope])
                q_abs = jnp.concatenate(
                    [q_lat, q_rope]
                    + [jnp.zeros((b, t, hn, pad), q_lat.dtype)] * bool(pad),
                    axis=-1)
                with jax.named_scope("mla_decode"):
                    cache = cl.value
                    if slot_pos is not None:
                        q_pos = slot_pos[:, None]
                    else:
                        q_pos = (idx + jnp.arange(t))[None, :]
                    mask = jnp.arange(self.cache_len)[None, None, :] \
                        <= q_pos[:, :, None]                 # [B|1, t, L]
                    logits = jnp.einsum("bthc,blc->bhtl", q_abs, cache) \
                        * self.softmax_scale
                    logits = jnp.where(mask[:, None], logits, -1e30)
                    probs = nn.softmax(logits.astype(jnp.float32),
                                       axis=-1).astype(cache.dtype)
                    # over the whole leaf, the rotated key's columns
                    # dropped after: a slice of the cache first would
                    # be a copy of it
                    o_lat = jnp.einsum("bhtl,blc->bthc", probs,
                                       cache)[..., :c]
                out = jnp.einsum("bthc,chk->bthk", o_lat,
                                 w_ukv[..., nope:])
            if jnp.ndim(ok):
                ok = ok[:, None, None, None]
            out = jnp.where(ok, out, jnp.nan)
        return nn.DenseGeneral(d, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, name="out")(out)


class DroplessMoE(nn.Module):
    """Routed SwiGLU experts (no capacity, no dropped token) beside a
    shared expert.  ``held = (first, count)``: the contiguous range of
    experts whose weights this layer has; the router covers all
    ``num_experts``.  The router's input is taken in float32."""

    num_experts: int
    top_k: int
    width: int
    shared_width: int
    scale: float
    normalize: bool
    dtype: jnp.dtype
    held: tuple

    @nn.compact
    def __call__(self, x, x_f32):
        b, t, d = x.shape
        first, count = self.held
        e, h = self.num_experts, self.width
        router = self.param("router", nn.initializers.normal(d ** -0.5),
                            (d, e))
        bias = self.param("bias", nn.initializers.zeros, (e,))
        w_in = self.param("w_in", nn.initializers.normal(d ** -0.5),
                          (count, d, 2 * h))
        w_out = self.param("w_out", nn.initializers.normal(h ** -0.5),
                           (count, h, d))
        with jax.named_scope("moe_router"):
            idx, w = moe.sigmoid_topk(
                x_f32.reshape(b * t, d), router, bias, self.top_k,
                normalize=self.normalize, scale=self.scale)
            self.sow("expert_load", "tokens", moe.expert_load(idx, e),
                     reduce_fn=lambda _, new: new,
                     init_fn=lambda: jnp.zeros((e,), jnp.int32))
        y = moe.dropless_experts(
            x.reshape(b * t, d), idx, w, w_in.astype(self.dtype),
            w_out.astype(self.dtype), first=first).reshape(b, t, d)
        if self.shared_width:
            with jax.named_scope("moe_shared"):
                y = y + SwiGLU(self.shared_width, self.dtype,
                               name="shared")(x)
        return y


@register_model("latent_moe_lm")
class LatentMoELM(nn.Module):
    """See the module docstring.  Field names follow the mechanisms, not
    any one model's configuration file."""

    vocab_size: int = 32000
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    q_lora_rank: int = 64
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    #: width of the dense SwiGLU of the first ``first_dense_layers``
    dense_width: int = 512
    first_dense_layers: int = 1
    num_experts: int = 8
    experts_per_token: int = 2
    expert_width: int = 128
    #: the shared expert is one SwiGLU of ``num_shared_experts`` widths
    num_shared_experts: int = 1
    routed_scaling: float = 1.0
    norm_topk_prob: bool = True
    #: ``(first, count)``: the experts whose weights this chip holds;
    #: None = all of them
    experts_held: Optional[tuple] = None
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: YaRN: 1 = a plain rotary table
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    rope_original_max_len: int = 4096
    max_len: int = 2048
    dtype: str = "bfloat16"
    #: True: a multi-token chunk reads the latent cache as a step does
    #: (the absorbed form, exact at any offset).  False: a chunk whose
    #: length is a multiple of 128 runs the expanded form on an empty
    #: cache, through the Pallas kernel on a TPU and XLA's dense
    #: attention elsewhere; any other chunk is absorbed.
    absorbed_only: bool = False
    decode: bool = False
    cache_envelope: Optional[int] = None

    def decode_clone(self):
        """The model as ``generate()`` and ``DecodeEngine`` run it."""
        return self.clone(decode=True)

    def dense_prefill_clone(self):
        """A clone whose multi-token chunks read the cache (exact in
        mid-stream: chunked prefill, speculative verify)."""
        return self.clone(absorbed_only=True)

    def _expanded_fn(self, t: int, platform: Optional[str]):
        if self.absorbed_only or t % 128:
            return None
        if (platform or jax.devices()[0].platform) == "tpu":
            from distkeras_tpu.ops.attention import flash_attn_fn

            return flash_attn_fn()
        return dense_causal_attention

    @nn.compact
    def __call__(self, tokens, train: bool = False, *,
                 slot_pos=None, last_index=None,
                 logits_all: bool = False):
        dtype = jnp.dtype(self.dtype)
        tokens = tokens.astype(jnp.int32)
        t = tokens.shape[1]
        held = self.experts_held or (0, self.num_experts)
        if not (0 <= held[0] and held[1] >= 1
                and held[0] + held[1] <= self.num_experts):
            raise ValueError(
                f"experts_held={self.experts_held} is not a range "
                f"(first, count) inside [0, {self.num_experts})")
        cache_len = 0
        if self.decode:
            cache_len = self.cache_envelope or self.max_len
            if not 0 < cache_len <= self.max_len:
                raise ValueError(
                    f"cache_envelope={self.cache_envelope} outside "
                    f"(0, max_len={self.max_len}]")
            if t > cache_len:
                raise ValueError(
                    f"decode chunk length {t} exceeds the cache size "
                    f"{cache_len}")
        elif (self.cache_envelope is not None or slot_pos is not None
              or last_index is not None or logits_all):
            raise ValueError(
                "cache_envelope/slot_pos/last_index/logits_all are "
                "decode-mode serving contracts; set decode=True")
        if logits_all and last_index is not None:
            raise ValueError(
                "logits_all returns every position's logits; "
                "last_index selects one — pass at most one of them")
        if slot_pos is not None and t != 1:
            raise ValueError(
                "slot_pos advances every live slot by ONE token; got "
                f"a T={t} chunk")
        if t > self.max_len:
            raise ValueError(
                f"sequence length {t} exceeds max_len={self.max_len}")
        expanded_fn = None
        if self.decode:
            pos_var = self.variable("cache", "pos_index",
                                    lambda: jnp.zeros((), jnp.int32))
            if slot_pos is not None:
                positions = slot_pos[:, None]
            else:
                positions = (pos_var.value + jnp.arange(t))[None, :]
                pos_var.value = pos_var.value + t
            if t > 1:
                expanded_fn = self._expanded_fn(
                    t, _committed_platform(tokens))
        else:
            positions = jnp.arange(t)[None, :]

        rope = (self.rope_theta, self.rope_factor, self.rope_beta_fast,
                self.rope_beta_slow, self.rope_original_max_len)
        # the table itself is scaled by mscale / mscale_all_dim, the
        # logits by mscale_all_dim squared
        table_scale = yarn_mscale(self.rope_factor, self.rope_mscale) \
            / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        if table_scale != 1.0:
            raise ValueError(
                "a rotary table scaled by mscale / mscale_all_dim != 1 "
                "is not implemented")
        softmax_scale = (self.qk_nope_head_dim
                         + self.qk_rope_head_dim) ** -0.5 \
            * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2

        n = self.hc_mult
        emb = nn.Embed(self.vocab_size, self.d_model, dtype=dtype)(tokens)
        # the stream: n copies of the embedding, kept in float32
        x = jnp.broadcast_to(emb.astype(jnp.float32)[:, :, None],
                             emb.shape[:2] + (n, self.d_model))

        def sublayer(name, fn, x):
            with jax.named_scope("hc_mix"):
                pre, post, res = StreamMixer(
                    self.hc_sinkhorn_iters, self.hc_eps,
                    tuple(self.hc_clamp), self.rms_eps,
                    name=f"{name}_hc")(x)
                inp = jnp.einsum("btn,btnd->btd", pre, x)
            normed = RMSNorm(self.rms_eps, jnp.float32,
                             name=f"{name}_norm")(inp)
            out = fn(normed.astype(dtype), normed)
            with jax.named_scope("hc_mix"):
                return jnp.einsum("btij,btjd->btid", res, x) \
                    + post[..., None] * out.astype(jnp.float32)[:, :, None]

        for i in range(self.num_layers):
            attn = LatentAttention(
                self.num_heads, self.q_lora_rank, self.kv_lora_rank,
                self.qk_nope_head_dim, self.qk_rope_head_dim,
                self.v_head_dim, rope, softmax_scale, self.rms_eps,
                dtype, cache_len=cache_len, expanded_fn=expanded_fn,
                name=f"Layer_{i}_attn")
            x = sublayer(f"Layer_{i}_attn",
                         lambda h, _: attn(h, positions, slot_pos), x)
            if i < self.first_dense_layers:
                mlp = SwiGLU(self.dense_width, dtype, name=f"Layer_{i}_mlp")

                def ffn(h, _, mlp=mlp):
                    with jax.named_scope("mlp"):
                        return mlp(h)
            else:
                ffn = DroplessMoE(
                    self.num_experts, self.experts_per_token,
                    self.expert_width,
                    self.num_shared_experts * self.expert_width,
                    self.routed_scaling, self.norm_topk_prob, dtype,
                    tuple(held), name=f"Layer_{i}_moe")
            x = sublayer(f"Layer_{i}_ffn", ffn, x)
        x = x.sum(axis=2)
        if self.decode:
            if last_index is not None:
                x = lax.dynamic_slice_in_dim(x, last_index, 1, 1)
            elif not logits_all:
                x = x[:, -1:]
        x = RMSNorm(self.rms_eps, jnp.float32, name="final_norm")(x)
        return nn.Dense(self.vocab_size, use_bias=False,
                        dtype=jnp.float32, name="lm_head")(x)
