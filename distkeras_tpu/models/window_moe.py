"""A decoder whose layers attend over a sliding window or over the whole
sequence, over sparse experts (the block of K-EXAONE and EXAONE 4.0):

* **attention**: ``num_heads`` query heads share ``num_kv_heads`` cached
  heads (grouped-query attention); queries and keys are RMS-normed over
  the head (QK-norm).  A layer with a window ``W > 0``
  (``layer_windows``) rotates them (rotary, rotate-half, ``rope_theta``)
  and a query attends to its last ``W`` positions (``i - W < j <= i``);
  a layer with window 0 attends causally to every position, with no
  rotary.
* **norms after the sublayers**: ``x = x + RMSNorm(Attn(x))``, then
  ``x = x + RMSNorm(FFN(x))``, no norm before a sublayer; a final
  RMSNorm before the untied head.  The stream is kept in float32.
* **feed-forward**: a dense SwiGLU or sparse experts per layer
  (``layer_sparse``), the experts ``latent_moe.DroplessMoE``'s: sigmoid
  scores with a selection bias, top-k renormalised and scaled, a shared
  expert beside them, ``experts_held`` the share this chip computes.

Cache.  A full layer keeps its keys and values lane-dense,
``cached_key`` / ``cached_value`` ``[B, L, KVH * D]``: as ``[B, L, 8,
128]`` in bfloat16 a TPU pads eight heads to sixteen sublanes, twice the
bytes.  A window layer keeps a ring of its last ``W`` positions,
``window_key`` / ``window_value`` ``[B, W, KVH * D]``
(``models.generate.WINDOW_PREFIX``): position ``p`` in row ``p mod W``,
keys cached rotated, so that the order of the ring's rows is nothing to
the softmax.  The slot-mode step reads both through
``ops.attention.decode_attention`` where ``decode_attention_applies``
takes them (a ring ``min(lengths, W)`` rows deep); a prefill (a chunk on
an empty cache) runs ``ops.attention.flash_attention`` (``window=W`` in
a window layer) where its length is a multiple of 128 on a TPU, XLA's
attention elsewhere, and installs the prompt's last ``min(len, W)`` true
positions into each ring, none of the padding past ``last_index``.  It
implements ``models.generate.DECODE_CONTRACT``; the engine's arms that
rewind or share cache positions refuse it.  Serving only: no training
rule for the selection bias.
"""

from __future__ import annotations

from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.models.core import register_model
from distkeras_tpu.models.generate import (WINDOW_PREFIX, decode_cache_len,
                                           experts_held_range)
from distkeras_tpu.models.latent_moe import DroplessMoE
from distkeras_tpu.models.layers import (RMSNorm, SwiGLU, apply_rotary_half,
                                         rms_norm, rotary_angles,
                                         rotary_inv_freq)
from distkeras_tpu.models.transformer import _committed_platform, _decode_read


class WindowAttention(nn.Module):
    """One attention layer; see the module docstring.  ``cache_len`` > 0
    is decode mode.  ``chunk_fn``: the block-attention kernel of a
    multi-token chunk (``ops.attention.flash_attention``), None for XLA's
    attention; ``decode_read``: ``decode_attention`` as the model wrapped
    it for one program (``transformer._decode_read``)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    #: the positions a query attends over; 0: all of them, no rotary
    window: int
    rope_theta: float
    norm_eps: float
    dtype: jnp.dtype
    cache_len: int = 0
    chunk_fn: Optional[Callable] = None
    decode_read: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, positions, slot_pos=None, lengths=None,
                 last_index=None):
        from distkeras_tpu.ops import attention

        b, t, d_model = x.shape
        h, kvh, hd, w = (self.num_heads, self.num_kv_heads, self.head_dim,
                         self.window)
        g = h // kvh

        def proj(heads, name):
            return nn.DenseGeneral((heads, hd), use_bias=False,
                                   dtype=self.dtype, name=name)(x)

        def qk_norm(y, name):
            gain = self.param(name, nn.initializers.ones, (hd,))
            return rms_norm(y, gain, self.norm_eps).astype(self.dtype)

        q = qk_norm(proj(h, "q"), "q_norm")
        k = qk_norm(proj(kvh, "k"), "k_norm")
        v = proj(kvh, "v")
        if w:
            cos, sin = rotary_angles(positions,
                                     rotary_inv_freq(hd, self.rope_theta))
            q = apply_rotary_half(q, cos[:, :, None], sin[:, :, None])
            k = apply_rotary_half(k, cos[:, :, None], sin[:, :, None])
        scale = hd ** -0.5
        scope = "swa" if w else "gqa"

        def chunk_attention():
            # a chunk attending to itself alone: causal, banded in a
            # window layer
            if self.chunk_fn is not None:
                return self.chunk_fn(q, k, v, scale=scale, window=w or None)
            s = jnp.einsum("bqcgd,bkcd->bcgqk", q.reshape(b, t, kvh, g, hd),
                           k, preferred_element_type=jnp.float32) * scale
            i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
            seen = j <= i
            if w:
                seen = jnp.logical_and(seen, i - j < w)
            p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            return jnp.einsum("bcgqk,bkcd->bqcgd", p.astype(v.dtype),
                              v).reshape(b, t, h, hd)

        if not self.cache_len:
            with jax.named_scope(f"{scope}_prefill"):
                out = chunk_attention()
        else:
            rows = w or self.cache_len
            names = (WINDOW_PREFIX + "key", WINDOW_PREFIX + "value") if w \
                else ("cached_key", "cached_value")
            ck, cv = (self.variable("cache", n, jnp.zeros,
                                    (b, rows, kvh * hd), k.dtype)
                      for n in names)
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.zeros((), jnp.int32))
            idx = ci.value
            k_new, v_new = k.reshape(b, t, kvh * hd), v.reshape(b, t, kvh * hd)
            pos = slot_pos if slot_pos is not None else idx

            def install(leaf, new):
                if t == 1:
                    at = pos % w if w else pos
                    if slot_pos is not None:
                        return leaf.at[jnp.arange(b), at].set(new[:, 0])
                    return lax.dynamic_update_slice(leaf, new, (0, at, 0))
                if not w:
                    return lax.dynamic_update_slice(leaf, new, (0, idx, 0))
                # ring row r: the prompt's last true position p = r mod W
                # (none where the prompt is shorter than that)
                last = idx + (t - 1 if last_index is None else last_index)
                src = last - (last - jnp.arange(w)) % w - idx
                kept = jnp.take(new, jnp.maximum(src, 0), axis=1)
                return jnp.where((src >= 0)[None, :, None], kept, 0)

            with jax.named_scope("window_write" if w else "kv_write"):
                ck.value = install(ck.value, k_new)
                cv.value = install(cv.value, v_new)
            # an overflowing position would be dropped or clamped in
            # silence: poison the output instead (as SelfAttention does)
            ok = pos + t <= self.cache_len
            if slot_pos is None:
                ci.value = idx + t
            if t > 1:
                # exact iff the cache was empty: the chunk attends to
                # itself alone
                with jax.named_scope(f"{scope}_prefill"):
                    out = chunk_attention()
                ok = jnp.logical_and(ok, idx == 0)
            else:
                with jax.named_scope(f"{scope}_decode"):
                    keys, vals = ck.value, cv.value
                    qg = q.reshape(b, kvh, g, hd)
                    n = lengths if lengths is None or not w \
                        else jnp.minimum(lengths, w)
                    if attention.decode_attention_applies(qg, keys, vals, n):
                        read = self.decode_read or attention.decode_attention
                        out = read(qg, keys, vals, n, scale=scale)
                    else:
                        at = jnp.reshape(pos, (-1, 1))          # [B|1, 1]
                        live = jnp.arange(rows)[None, :] < (
                            jnp.minimum(at + 1, w) if w else at + 1)
                        s = jnp.einsum(
                            "bcgd,blcd->bcgl", qg,
                            keys.reshape(b, rows, kvh, hd),
                            preferred_element_type=jnp.float32) * scale
                        p = jax.nn.softmax(
                            jnp.where(live[:, None, None], s, -1e30),
                            axis=-1)
                        out = jnp.einsum("bcgl,blcd->bcgd",
                                         p.astype(vals.dtype),
                                         vals.reshape(b, rows, kvh, hd))
                    out = out.reshape(b, 1, h, hd)
            if jnp.ndim(ok):
                ok = ok[:, None, None, None]
            out = jnp.where(ok, out, jnp.nan)
        return nn.DenseGeneral(d_model, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, name="o")(out)


@register_model("window_moe_lm")
class WindowMoELM(nn.Module):
    """See the module docstring.  Field names follow the mechanisms, not
    any one model's configuration file."""

    vocab_size: int = 32000
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 32
    #: per layer, the window a query attends over; 0: all positions
    layer_windows: tuple = (16, 16, 16, 0)
    rope_theta: float = 1e6
    dense_width: int = 512
    #: per layer, sparse experts (True) or a dense SwiGLU
    layer_sparse: tuple = (False, True, True, True)
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
    rms_eps: float = 1e-5
    max_len: int = 2048
    dtype: str = "bfloat16"
    decode: bool = False
    cache_envelope: Optional[int] = None

    def decode_clone(self):
        """The model as ``generate()`` and ``DecodeEngine`` run it."""
        return self.clone(decode=True)

    def dense_prefill_clone(self):
        """Refused: a chunk at an offset would need the positions that a
        ring has already let go, which the engine's arms that ask for
        this clone (chunked prefill, speculative verify) refuse to do as
        well."""
        leaves = [f"Layer_{i}_attn/{WINDOW_PREFIX}{name}"
                  for i, w in enumerate(self.layer_windows) if w
                  for name in ("key", "value")]
        raise ValueError(
            f"no multi-token chunk at an offset for this model: its cache "
            f"leaves {leaves} are rings of a window's last positions "
            "(models.generate.DECODE_CONTRACT)")

    @nn.compact
    def __call__(self, tokens, train: bool = False, *,
                 slot_pos=None, lengths=None, last_index=None,
                 logits_all: bool = False):
        dtype = jnp.dtype(self.dtype)
        tokens = tokens.astype(jnp.int32)
        t = tokens.shape[1]
        held = experts_held_range(self.experts_held, self.num_experts)
        if not (len(self.layer_windows) == len(self.layer_sparse)
                == self.num_layers):
            raise ValueError(
                f"layer_windows {self.layer_windows} and layer_sparse "
                f"{self.layer_sparse} need one entry a layer "
                f"({self.num_layers})")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads cannot share "
                f"{self.num_kv_heads} cached heads")
        cache_len = decode_cache_len(
            self, t, slot_pos=slot_pos, lengths=lengths,
            last_index=last_index, logits_all=logits_all)
        decode_read = None
        if self.decode:
            pos_var = self.variable("cache", "pos_index",
                                    lambda: jnp.zeros((), jnp.int32))
            if slot_pos is not None:
                positions = slot_pos[:, None]
            else:
                positions = (pos_var.value + jnp.arange(t))[None, :]
                pos_var.value = pos_var.value + t
            decode_read = _decode_read()
        else:
            positions = jnp.arange(t)[None, :]
        chunk_fn = None
        if t > 1 and t % 128 == 0 and (
                _committed_platform(tokens)
                or jax.devices()[0].platform) == "tpu":
            from distkeras_tpu.ops.attention import flash_attention

            chunk_fn = flash_attention

        x = nn.Embed(self.vocab_size, self.d_model,
                     dtype=dtype)(tokens).astype(jnp.float32)
        for i in range(self.num_layers):
            a = WindowAttention(
                self.num_heads, self.num_kv_heads, self.head_dim,
                int(self.layer_windows[i]), self.rope_theta, self.rms_eps,
                dtype, cache_len=cache_len, chunk_fn=chunk_fn,
                decode_read=decode_read, name=f"Layer_{i}_attn")(
                    x.astype(dtype), positions, slot_pos, lengths,
                    last_index if t > 1 else None)
            x = x + RMSNorm(self.rms_eps, jnp.float32,
                            name=f"Layer_{i}_attn_norm")(a)
            if self.layer_sparse[i]:
                y = DroplessMoE(
                    self.num_experts, self.experts_per_token,
                    self.expert_width, self.shared_width,
                    self.routed_scaling, self.norm_topk_prob, dtype,
                    tuple(held), n_group=self.n_group,
                    topk_group=self.topk_group,
                    name=f"Layer_{i}_moe")(x.astype(dtype), x)
            else:
                with jax.named_scope("mlp"):
                    y = SwiGLU(self.dense_width, dtype,
                               name=f"Layer_{i}_mlp")(x.astype(dtype))
            x = x + RMSNorm(self.rms_eps, jnp.float32,
                            name=f"Layer_{i}_ffn_norm")(y)
        if self.decode:
            if last_index is not None:
                x = lax.dynamic_slice_in_dim(x, last_index, 1, 1)
            elif not logits_all:
                x = x[:, -1:]
        x = RMSNorm(self.rms_eps, jnp.float32, name="final_norm")(x)
        return nn.Dense(self.vocab_size, use_bias=False,
                        dtype=jnp.float32, name="lm_head")(x)
