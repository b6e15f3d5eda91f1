"""A decoder that interleaves linear-attention layers with latent
attention, over sparse experts:

* **Kimi Delta Attention (KDA)** in most layers (``ops.linear_attention``):
  ``q``, ``k``, ``v`` through a short causal convolution and SiLU, ``q``
  and ``k`` L2-normalised, a per-channel decay gate and a per-head
  ``beta``; the gated delta rule carries a ``[d_k, d_v]`` state a head in
  float32, and the output is RMS-normed over all heads and gated by
  ``sigmoid(x W_g)``.  What such a layer caches is not indexed by
  position: the state and the convolution's last inputs, cache leaves
  ``recurrent_state`` ``[B, H, d_k, d_v]`` and ``recurrent_conv``
  ``[B, (width - 1) 3 H d_k]`` (``models.generate.RECURRENT_PREFIX``).
* **latent attention (MLA)** in every ``layer_group_size``-th layer
  (``latent_moe.LatentAttention``, here with the query straight from
  ``x``, no low rank, and a head-wise output gate).
* **sparse experts** (``latent_moe.DroplessMoE``) after the first
  ``first_dense_layers`` dense SwiGLU layers, with grouped selection.

Each sublayer is pre-norm (RMSNorm) with a plain residual, kept in
float32.  It implements ``models.generate.DECODE_CONTRACT``: a prompt
padded past its end (``last_index``) leaves the state where its last
true token put it, so ``generate()`` and ``serving.DecodeEngine`` serve
it; the engine's arms that rewind or share cache positions refuse it.
Serving only: no training rule for the selection bias.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.models.core import register_model
from distkeras_tpu.models.generate import (RECURRENT_PREFIX,
                                           decode_cache_len,
                                           experts_held_range)
from distkeras_tpu.models.latent_moe import (DroplessMoE, LatentAttention,
                                             expanded_attention_fn)
from distkeras_tpu.models.layers import RMSNorm, SwiGLU, rms_norm
from distkeras_tpu.models.transformer import _committed_platform
from distkeras_tpu.ops import linear_attention as la


class KimiDeltaAttention(nn.Module):
    """One KDA layer; see the module docstring.  ``decode``: the state
    and the convolution's tail live in the ``"cache"`` collection and
    every call starts from them."""

    num_heads: int
    head_dim: int
    conv_width: int
    lower_bound: float
    norm_eps: float
    dtype: jnp.dtype
    decode: bool = False

    @nn.compact
    def __call__(self, x, last_index=None):
        b, t, d = x.shape
        h, dk = self.num_heads, self.head_dim
        hd = h * dk
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, name=name)
        qkv = dense(3 * hd, "qkv")(x)
        f = dense(hd, "f")(x)
        beta_in = dense(h, "b")(x)
        out_gate = dense(hd, "g")(x)
        conv_w = self.param("conv", nn.initializers.normal(0.5),
                            (self.conv_width, 3 * hd))
        a_log = self.param("A_log", nn.initializers.zeros, (h,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (hd,))
        norm = self.param("o_norm", nn.initializers.ones, (hd,))
        tail_shape = (b, (self.conv_width - 1) * 3 * hd)
        if self.decode:
            conv_var = self.variable("cache", RECURRENT_PREFIX + "conv",
                                     jnp.zeros, tail_shape, qkv.dtype)
            state_var = self.variable("cache", RECURRENT_PREFIX + "state",
                                      jnp.zeros, (b, h, dk, dk),
                                      jnp.float32)
            conv0, state0 = conv_var.value, state_var.value
        else:
            conv0 = jnp.zeros(tail_shape, qkv.dtype)
            state0 = jnp.zeros((b, h, dk, dk), jnp.float32)
        with jax.named_scope("kda_conv"):
            y, tail = la.causal_conv(qkv, conv_w, conv0, last_index)
            y = jax.nn.silu(y).reshape(b, t, 3, h, dk)
            q = la.l2_normalize(y[:, :, 0]) * dk ** -0.5
            k = la.l2_normalize(y[:, :, 1])
            v = y[:, :, 2]
            g = la.kda_gate(f.reshape(b, t, h, dk), a_log,
                            dt_bias.reshape(h, dk), self.lower_bound)
            beta = jax.nn.sigmoid(beta_in.astype(jnp.float32))
            if last_index is not None:
                # the prompt's padding: the state passes it unchanged
                live = jnp.arange(t) <= last_index
                g = jnp.where(live[None, :, None, None], g, 0.0)
                beta = jnp.where(live[None, :, None], beta, 0.0)
        if t == 1:
            step = la.kda_step_kernel if la.kda_step_kernel_applies(
                state0) else la.kda_step
            with jax.named_scope("kda_decode"):
                state, o = step(state0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0])
                o = o[:, None]
        else:
            with jax.named_scope("kda_prefill"):
                o, state = la.kda_chunked(q, k, v, g, beta, state0)
        if self.decode:
            conv_var.value = tail
            state_var.value = state
        o = rms_norm(o.reshape(b, t, hd), norm, self.norm_eps) \
            * jax.nn.sigmoid(out_gate.astype(jnp.float32))
        return dense(d, "o")(o.astype(self.dtype))


@register_model("hybrid_moe_lm")
class HybridMoELM(nn.Module):
    """See the module docstring.  Layer ``i`` is latent attention where
    ``(i + 1) % layer_group_size == 0``, KDA elsewhere.  Field names
    follow the mechanisms, not any one model's configuration file."""

    vocab_size: int = 32000
    num_layers: int = 6
    d_model: int = 256
    layer_group_size: int = 6
    kda_heads: int = 4
    kda_head_dim: int = 32
    conv_width: int = 4
    kda_lower_bound: float = -5.0
    num_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    rope_theta: float = 10000.0
    dense_width: int = 512
    first_dense_layers: int = 1
    num_experts: int = 16
    experts_per_token: int = 2
    expert_width: int = 64
    shared_width: int = 64
    routed_scaling: float = 1.0
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    #: ``(first, count)``: the experts whose weights this chip holds;
    #: None = all of them
    experts_held: Optional[tuple] = None
    #: per layer, the routed and the shared experts' SwiGLU clamp (0 =
    #: none); empty = none anywhere
    expert_limits: tuple = ()
    shared_limits: tuple = ()
    rms_eps: float = 1e-6
    max_len: int = 2048
    dtype: str = "bfloat16"
    decode: bool = False
    cache_envelope: Optional[int] = None

    def decode_clone(self):
        """The model as ``generate()`` and ``DecodeEngine`` run it."""
        return self.clone(decode=True)

    def dense_prefill_clone(self):
        """Refused: a chunk at an offset would have to rewind the KDA
        layers' states, which the engine's arms that ask for this clone
        (chunked prefill, speculative verify) refuse to do as well."""
        leaves = [f"Layer_{i}_attn/{RECURRENT_PREFIX}{name}"
                  for i in range(self.num_layers) if not self.is_latent(i)
                  for name in ("state", "conv")]
        raise ValueError(
            f"no multi-token chunk at an offset for this model: its cache "
            f"leaves {leaves} are recurrent states with no position axis "
            "(models.generate.DECODE_CONTRACT)")

    def is_latent(self, i: int) -> bool:
        return (i + 1) % self.layer_group_size == 0

    @nn.compact
    def __call__(self, tokens, train: bool = False, *,
                 slot_pos=None, lengths=None, last_index=None,
                 logits_all: bool = False):
        dtype = jnp.dtype(self.dtype)
        tokens = tokens.astype(jnp.int32)
        t = tokens.shape[1]
        held = experts_held_range(self.experts_held, self.num_experts)
        if self.num_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"{self.num_experts} experts in n_group={self.n_group} "
                f"groups, topk_group={self.topk_group} of them kept")
        cache_len = decode_cache_len(
            self, t, slot_pos=slot_pos, lengths=lengths,
            last_index=last_index, logits_all=logits_all)
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
                expanded_fn = expanded_attention_fn(
                    False, t, _committed_platform(tokens))
        else:
            positions = jnp.arange(t)[None, :]
        # plain rotary (no YaRN), so the logits are not rescaled
        rope = (self.rope_theta, 1.0, 32.0, 1.0, self.max_len)
        softmax_scale = (self.qk_nope_head_dim
                         + self.qk_rope_head_dim) ** -0.5
        limits = lambda seq, i: float(seq[i]) if seq else 0.0  # noqa: E731

        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=dtype)(tokens).astype(jnp.float32)
        for i in range(self.num_layers):
            h = RMSNorm(self.rms_eps, jnp.float32,
                        name=f"Layer_{i}_attn_norm")(x)
            if self.is_latent(i):
                out = LatentAttention(
                    self.num_heads, None, self.kv_lora_rank,
                    self.qk_nope_head_dim, self.qk_rope_head_dim,
                    self.v_head_dim, rope, softmax_scale, self.rms_eps,
                    dtype, cache_len=cache_len, expanded_fn=expanded_fn,
                    head_gate=True, name=f"Layer_{i}_attn")(
                        h.astype(dtype), positions, slot_pos, lengths)
            else:
                out = KimiDeltaAttention(
                    self.kda_heads, self.kda_head_dim, self.conv_width,
                    self.kda_lower_bound, self.rms_eps, dtype,
                    decode=self.decode, name=f"Layer_{i}_attn")(
                        h.astype(dtype), last_index if t > 1 else None)
            x = x + out.astype(jnp.float32)
            h = RMSNorm(self.rms_eps, jnp.float32,
                        name=f"Layer_{i}_ffn_norm")(x)
            if i < self.first_dense_layers:
                with jax.named_scope("mlp"):
                    y = SwiGLU(self.dense_width, dtype,
                               name=f"Layer_{i}_mlp")(h.astype(dtype))
            else:
                y = DroplessMoE(
                    self.num_experts, self.experts_per_token,
                    self.expert_width, self.shared_width,
                    self.routed_scaling, self.norm_topk_prob, dtype,
                    tuple(held), n_group=self.n_group,
                    topk_group=self.topk_group,
                    limit=limits(self.expert_limits, i),
                    shared_limit=limits(self.shared_limits, i),
                    name=f"Layer_{i}_moe")(h.astype(dtype), h)
            x = x + y.astype(jnp.float32)
        if self.decode:
            if last_index is not None:
                x = lax.dynamic_slice_in_dim(x, last_index, 1, 1)
            elif not logits_all:
                x = x[:, -1:]
        x = RMSNorm(self.rms_eps, jnp.float32, name="final_norm")(x)
        return nn.Dense(self.vocab_size, use_bias=False,
                        dtype=jnp.float32, name="lm_head")(x)
