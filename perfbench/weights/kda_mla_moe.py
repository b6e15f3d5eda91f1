"""Seeded weights of a decoder whose layers are KDA linear attention or
latent attention (MLA) over sparse experts, in the benchmark's own layout.

As ``weights/mla_moe_hc.py``: the program's adapter renames these leaves,
the plain reference calls the same generator again layer by layer, and
every leaf is drawn.  Layer ``i`` is MLA where ``(i + 1) %
layer_group_size == 0`` (``is_latent``), KDA elsewhere; a dense SwiGLU in
the first ``first_k_dense_replace`` layers, experts after.

The standard deviations are the benchmark's (no initializer is published
with the configuration), made so that the comparison that decides
``correct`` can tell a sound run from a wrong one:

* the stream is of order 1 an element (embedding 1.0), and what each
  sublayer adds is of order one half of that, so that rounding every
  product to float8 moves the final hidden state by a tenth or more;
* the KDA decays span short and long memories: ``dt_bias`` is drawn a
  channel about -4 (sd 2), so that ``-g`` runs from about 1e-4 to 2 a
  token, and ``x W_f`` moves it by a token's own share (sd 0.5).  A
  channel that remembers hundreds of tokens carries the prompt into the
  answer, so a state lost between prefill and decode, a decay left out
  or a prompt's padding taken into the state moves what the layer
  writes; the output's RMSNorm makes it of order 1 whatever the state's
  size;
* a routed expert adds about 0.2 (``ROUTED_EXPERT_RMS``), half of its
  hidden units the layer's, the same in every expert (as
  ``mla_moe_hc``): a chip here holds an eighth of the experts, so a token
  meets one of its eight choices here in the mean, and the routed part
  computed here has to be big enough to be missed when it is left out.
"""

import jax
import jax.numpy as jnp

from perfbench.weights.gpt2 import _draw, seed_key  # noqa: F401

HEAD_STD = 0.02
ATTN_OUT_RMS = 0.5
FFN_OUT_RMS = 0.5
ROUTED_EXPERT_RMS = 0.2
ROUTED_COMMON_UNITS = 0.5
_ACT_RMS = 0.6            # rms of silu(g) * u for unit normal g, u
_GATE_RMS = 0.54          # rms of sigmoid(z) for unit normal z
DT_BIAS_MEAN, DT_BIAS_STD = -4.0, 2.0


def _unit(fan_in: int) -> float:
    return fan_in ** -0.5


def is_latent(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["layer_group_size"] == 0


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


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


def _kda(k, cfg, dtype) -> dict:
    d, h, dk = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["head_dim"]
    hd = h * dk
    return {
        # q | k | v, each hd wide
        "w_qkv": _draw(k, 1, (d, 3 * hd), _unit(d), dtype),
        "conv_w": _draw(k, 2, (cfg["short_conv_kernel_size"], 3 * hd),
                        0.5, dtype),
        "w_f": _draw(k, 3, (d, hd), 0.5 * _unit(d), dtype),
        "a_log": _draw(k, 4, (h,), 0.2, dtype),
        "dt_bias": _draw(k, 5, (hd,), DT_BIAS_STD, dtype,
                         mean=DT_BIAS_MEAN),
        "w_b": _draw(k, 6, (d, h), _unit(d), dtype),
        "w_g": _draw(k, 7, (d, hd), _unit(d), dtype),
        "on_g": _draw(k, 8, (hd,), 0.1, dtype, mean=1.0),
        "w_o": _draw(k, 9, (hd, d), ATTN_OUT_RMS / _GATE_RMS * _unit(hd),
                     dtype),
    }


def _mla(k, cfg, dtype) -> dict:
    d, hn = cfg["hidden_size"], cfg["num_attention_heads"]
    c, nope, rope, dv = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return {
        "wq": _draw(k, 11, (d, hn, nope + rope), _unit(d), dtype),
        "wdkv": _draw(k, 12, (d, c + rope), _unit(d), dtype),
        "kvn_g": _draw(k, 13, (c,), 0.1, dtype, mean=1.0),
        "wukv": _draw(k, 14, (c, hn, nope + dv), _unit(c), dtype),
        "w_hg": _draw(k, 15, (d, hn), _unit(d), dtype),
        # attention's mean of unit values has rms near one half, the gate
        # about 0.54
        "wo": _draw(k, 16, (hn, dv, d),
                    ATTN_OUT_RMS / 0.5 / _GATE_RMS * _unit(hn * dv), dtype),
    }


def layer_weights(cfg: dict, key, i, dtype, kind: tuple) -> dict:
    """Layer ``i`` (may be traced) of ``kind`` = ``(is_latent(cfg, i),
    is_dense(cfg, i))``: its attention (KDA or MLA), then the dense
    feed-forward or the expert layer.  Routed experts: ``we_in``
    ``[E_held, d, 2 h]`` is gate then up."""
    latent, dense = kind
    d = cfg["hidden_size"]
    k = jax.random.fold_in(key, i)
    w = {"ln1_g": _draw(k, 0, (d,), 0.1, dtype, mean=1.0),
         "ln2_g": _draw(k, 10, (d,), 0.1, dtype, mean=1.0)}
    w.update(_mla(k, cfg, dtype) if latent else _kda(k, cfg, dtype))

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
    e, h = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared = cfg["moe_shared_expert_intermediate_size"]
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

    common = jnp.arange(h) < int(ROUTED_COMMON_UNITS * h)
    w.update(
        router=_draw(k, 30, (d, e), _unit(d), dtype),
        e_bias=_draw(k, 31, (e,), 0.01, dtype),
        we_in=jnp.where(
            jnp.tile(common, 2),
            _draw(k, 32, (d, 2 * h), _unit(d), dtype),
            per_expert(ke_in, (d, 2 * h), _unit(d))),
        we_down=jnp.where(
            common[:, None],
            _draw(k, 33, (h, d), down(h, ROUTED_EXPERT_RMS), dtype),
            per_expert(ke_out, (h, d), down(h, ROUTED_EXPERT_RMS))),
        ws_gate=_draw(k, 34, (d, shared), _unit(d), dtype),
        ws_up=_draw(k, 35, (d, shared), _unit(d), dtype),
        ws_down=_draw(k, 36, (shared, d), down(shared, FFN_OUT_RMS),
                      dtype))
    return w


def kind_of(cfg: dict, i: int) -> tuple:
    return is_latent(cfg, i), is_dense(cfg, i)


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
