"""Seeded weights of a decoder of sliding-window and full grouped-query
attention over sparse experts, in the benchmark's own layout.

As ``weights/kda_mla_moe.py``: the program's adapter renames these
leaves, the plain reference calls the same generator again layer by
layer, and every leaf is drawn.  Layer ``i`` attends over a window of
``sliding_windows[i]`` positions (0: all of them); its feed-forward is
``mlp_layer_types[i]``: a dense SwiGLU or the experts.

The standard deviations are the benchmark's (no initializer is published
with the configuration), made so that the comparison that decides
``correct`` can tell a sound run from a wrong one:

* the stream is of order 1 an element (embedding 1.0); a sublayer's
  output is RMS-normed before it is added (the block's post-sublayer
  norms), so each adds about 1 whatever its own scale;
* attention's queries and keys are RMS-normed over the head, so a logit
  ``q . k / sqrt(128)`` is of order 1: a window of 128 keys and a
  context of thousands weigh their keys differently enough that a
  window left out, a rotary put on the full layer or a ring that holds
  the prompt's padding moves what the layer writes;
* a routed expert adds about 0.2 (``ROUTED_EXPERT_RMS``) before its
  weight (2.5 over the chosen, about 0.3) beside the shared expert's 0.5,
  half of its hidden units the layer's, the same in every expert (as
  ``mla_moe_hc``): large enough that the routed part computed here is
  missed when it is left out (a chip here holds a sixteenth of the
  experts, so a token meets one of its eight choices here one time in
  two), small enough that a choice that bfloat16's rounding flips near a
  tie moves the normed sum little; at 1.0 such flips moved the toy's
  later tokens as far as rounding every product to float8 did.
* the router reads the stream itself (no norm before a sublayer), of
  rms 2 to 3 in the expert layers, so its weights are drawn at
  ``ROUTER_GAIN`` (0.4) of a unit: logits of about 1, where the
  sigmoid is steep enough that the 8th and 9th of 128 scores lie about
  0.01 apart.  At a whole unit the chosen scores sat near 1, where they
  crowd, and the reference abstained on 94 % of the served tokens
  (``route_tie_margin``; a run of the cell on a v5e).
"""

import jax
import jax.numpy as jnp

from perfbench.weights.gpt2 import _draw, seed_key  # noqa: F401

HEAD_STD = 0.02
ATTN_OUT_RMS = 1.0
FFN_OUT_RMS = 0.5
ROUTED_EXPERT_RMS = 0.2
ROUTED_COMMON_UNITS = 0.5
ROUTER_GAIN = 0.4
_ACT_RMS = 0.6            # rms of silu(g) * u for unit normal g, u


def _unit(fan_in: int) -> float:
    return fan_in ** -0.5


def window_of(cfg: dict, i: int) -> int:
    return int(cfg["sliding_windows"][i])


def is_dense(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "dense"


def kind_of(cfg: dict, i: int) -> tuple:
    """``(window, dense)`` of layer ``i``."""
    return window_of(cfg, i), is_dense(cfg, i)


def experts_held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held", (0, cfg["num_experts"])))


def global_weights(cfg: dict, key, dtype) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.fold_in(key, 1_000_003)
    return {
        "wte": _draw(k, 0, (v, d), 1.0, dtype),
        "lnf_g": _draw(k, 1, (d,), 0.1, dtype, mean=1.0),
        "head_w": _draw(k, 2, (d, v), HEAD_STD, dtype),
    }


def layer_weights(cfg: dict, key, i, dtype, kind: tuple) -> dict:
    """Layer ``i`` (may be traced) of ``kind`` = ``kind_of(cfg, i)``: its
    attention (``wq [d, H, D]``, ``wk`` / ``wv [d, KVH, D]``, the heads'
    norms, ``wo [H, D, d]``), the two post-sublayer norms, then the dense
    feed-forward or the expert layer (routed experts: ``we_in``
    ``[E_held, d, 2 h]`` is gate then up)."""
    _, dense = kind
    d, h, kvh, hd = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    k = jax.random.fold_in(key, i)
    w = {
        "wq": _draw(k, 1, (d, h, hd), _unit(d), dtype),
        "wk": _draw(k, 2, (d, kvh, hd), _unit(d), dtype),
        "wv": _draw(k, 3, (d, kvh, hd), _unit(d), dtype),
        "qn_g": _draw(k, 4, (hd,), 0.1, dtype, mean=1.0),
        "kn_g": _draw(k, 5, (hd,), 0.1, dtype, mean=1.0),
        "wo": _draw(k, 6, (h, hd, d), ATTN_OUT_RMS * _unit(h * hd), dtype),
        "ln_attn_g": _draw(k, 7, (d,), 0.1, dtype, mean=1.0),
        "ln_ffn_g": _draw(k, 8, (d,), 0.1, dtype, mean=1.0),
    }

    def down(width, out_rms):
        return out_rms / _ACT_RMS * _unit(width)

    if dense:
        inner = cfg["intermediate_size"]
        w.update(
            w_gate=_draw(k, 20, (d, inner), _unit(d), dtype),
            w_up=_draw(k, 21, (d, inner), _unit(d), dtype),
            w_down=_draw(k, 22, (inner, d), down(inner, FFN_OUT_RMS),
                         dtype))
        return w
    e, eh = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared = cfg["num_shared_experts"] * eh
    first, count = experts_held(cfg)
    # drawn for all experts' indices, so that a share holds the same
    # numbers as the whole layer's experts first .. first + count
    ke_in = jax.random.fold_in(k, 40)
    ke_out = jax.random.fold_in(k, 41)
    held = jnp.arange(first, first + count)

    def per_expert(kk, shape, std):
        return jax.vmap(lambda j: (std * jax.random.normal(
            jax.random.fold_in(kk, j), shape, jnp.float32)).astype(dtype)
        )(held)

    common = jnp.arange(eh) < int(ROUTED_COMMON_UNITS * eh)
    w.update(
        router=_draw(k, 30, (d, e), ROUTER_GAIN * _unit(d), dtype),
        e_bias=_draw(k, 31, (e,), 0.01, dtype),
        we_in=jnp.where(
            jnp.tile(common, 2),
            _draw(k, 32, (d, 2 * eh), _unit(d), dtype),
            per_expert(ke_in, (d, 2 * eh), _unit(d))),
        we_down=jnp.where(
            common[:, None],
            _draw(k, 33, (eh, d), down(eh, ROUTED_EXPERT_RMS), dtype),
            per_expert(ke_out, (eh, d), down(eh, ROUTED_EXPERT_RMS))),
        ws_gate=_draw(k, 34, (d, shared), _unit(d), dtype),
        ws_up=_draw(k, 35, (d, shared), _unit(d), dtype),
        ws_down=_draw(k, 36, (shared, d), down(shared, FFN_OUT_RMS),
                      dtype))
    return w


def make(cfg: dict, seed: int, dtype) -> dict:
    """All weights on the device from the seed, in ``dtype``: one compiled
    program for each kind of layer, run once a layer, and one for the
    rest."""
    dtype = jnp.dtype(dtype)
    key = seed_key(seed)
    layer = jax.jit(lambda k, i, kind: layer_weights(cfg, k, i, dtype,
                                                     kind),
                    static_argnums=2)
    rest = jax.jit(lambda k: global_weights(cfg, k, dtype))
    return {"globals": rest(key),
            "layers": [layer(key, i, kind_of(cfg, i))
                       for i in range(cfg["num_hidden_layers"])]}
