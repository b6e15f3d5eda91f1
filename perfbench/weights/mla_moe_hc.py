"""Seeded weights of a latent-attention, sparse-expert decoder with a
multi-stream constrained residual, in the benchmark's own layout.

As ``weights/gpt2.py``: the program's adapter renames these leaves, the
plain reference calls the same generator again layer by layer, and every
leaf is drawn, none a constant.

The standard deviations are the benchmark's choice (no initializer is
published with the configuration) and are made so that the comparison
that decides ``correct`` can tell a sound run from a wrong one:

* the stream is of order 1 an element (embedding 1.0), and what
  attention, the dense feed-forward and the shared expert each add is of
  order one half of that, so that rounding every product to float8 moves
  the final hidden state by a tenth or more;
* one routed expert adds about 0.05, and half of its hidden units are
  the layer's, drawn once and the same in every expert of the layer
  (``ROUTED_COMMON_UNITS``), so that an expert's output is a common part
  plus its own, of equal size.  Top-k routing is discontinuous: the
  bfloat16 program reaches the router with a rounding error that moves a
  score by 1e-3 (first expert layer) to 2.5e-3 (fifth), where a token's
  4th and 5th score lie 0.013 apart in the median, and 15 % of the
  tokens get another 4th expert than float32 gives them in some layer,
  whatever the program does (the program against the plain reference
  on the CPU at these widths, 2048 tokens; PERF.md section 6).  Such a
  token moves by the difference of two experts' *own* parts (the
  weights of the chosen sum to ``routed_scaling_factor`` whichever are
  chosen, so the common part stays): its widest gap read 0.106 where
  the other tokens read at most 0.038, and the reference abstains on
  the tokens that can be hit (``route_tie_margin`` of the configuration:
  97 % of them at 0.003, with 56 % of all tokens).  A fault that loses
  experts' output loses the common part with it: all routed experts
  left out reads 0.56 to 0.80 there, three times the limit or more (on
  the chip: PERF.md section 2).  The history: with independent experts
  of 0.04 each one sound run in ten on the chip read 0.25, a third of
  the float8 control's; at 0.015 none did, and neither did a run with
  the routed experts left out, which the review of PR 28 refused.  What
  the comparison still cannot see is one expert taken for another (two
  experts differ by their own halves, which is what a tie moves too) or
  one expert of 64 zeroed (0.09 here); the float32 tests at toy size
  check both exactly.
"""

import jax
import jax.numpy as jnp

from perfbench.weights.gpt2 import _draw, seed_key  # noqa: F401


def _unit(fan_in: int) -> float:
    """The deviation that keeps a unit-rms input at unit rms."""
    return fan_in ** -0.5


HEAD_STD = 0.02           # logits of deviation 1.2, as the GPT-2 cells'
ATTN_OUT_RMS = 0.5        # what a sublayer adds to a stream of order 1
FFN_OUT_RMS = 0.5
ROUTED_EXPERT_RMS = 0.05
ROUTED_COMMON_UNITS = 0.5
_ACT_RMS = 0.6            # rms of silu(g) * u for unit normal g, u


def _sizes(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], n=cfg["hc_mult"],
        heads=cfg["num_attention_heads"], rq=cfg["q_lora_rank"],
        c=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        inner=cfg["intermediate_size"], h=cfg["moe_intermediate_size"],
        experts=cfg["n_routed_experts"],
        shared=cfg["n_shared_experts"] * cfg["moe_intermediate_size"])


def experts_held(cfg: dict) -> tuple:
    """``(first, count)`` of the routed experts whose weights exist here:
    all of them unless the configuration states a share."""
    return tuple(cfg.get("experts_held", (0, cfg["n_routed_experts"])))


def global_weights(cfg: dict, key, dtype) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    k = jax.random.fold_in(key, 1_000_003)
    return {
        "wte": _draw(k, 0, (v, d), 1.0, dtype),
        "lnf_g": _draw(k, 1, (d,), 0.1, dtype, mean=1.0),
        "head_w": _draw(k, 2, (d, v), HEAD_STD, dtype),
    }


def _mixer(k, base: int, s: dict, dtype) -> dict:
    n, nd = s["n"], s["n"] * s["d"]
    width = 2 * n + n * n
    return {
        "norm": _draw(k, base, (nd,), 0.1, dtype, mean=1.0),
        # x~ phi has deviation 12; the gates a (0.01, as published) make
        # that a token's 0.12 beside the biases
        "phi": _draw(k, base + 1, (nd, width), 12.0 * _unit(nd), dtype),
        "a": _draw(k, base + 2, (3,), 0.002, dtype, mean=0.01),
        # read | write | residual: sigmoid(b) spread about one half, the
        # residual's logits over a few units
        "b": jnp.concatenate([
            _draw(k, base + 3, (2 * n,), 0.5, dtype),
            _draw(k, base + 4, (n * n,), 1.0, dtype)]),
    }


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def layer_weights(cfg: dict, key, i, dtype, dense: bool) -> dict:
    """Layer ``i`` (may be traced): attention with its mixer, then the
    dense feed-forward (``dense``, which is ``is_dense(cfg, i)``) or the
    expert layer, with its mixer.  Routed experts: ``we_in``
    ``[E, d, 2 h]`` is gate then up."""
    s = _sizes(cfg)
    d, hn = s["d"], s["heads"]
    k = jax.random.fold_in(key, i)
    w = {
        "hc_attn": _mixer(k, 100, s, dtype),
        "hc_ffn": _mixer(k, 200, s, dtype),
        "ln1_g": _draw(k, 0, (d,), 0.1, dtype, mean=1.0),
        "wdq": _draw(k, 1, (d, s["rq"]), _unit(d), dtype),
        "qn_g": _draw(k, 2, (s["rq"],), 0.1, dtype, mean=1.0),
        "wuq": _draw(k, 3, (s["rq"], hn, s["nope"] + s["rope"]),
                     _unit(s["rq"]), dtype),
        "wdkv": _draw(k, 4, (d, s["c"] + s["rope"]), _unit(d), dtype),
        "kvn_g": _draw(k, 5, (s["c"],), 0.1, dtype, mean=1.0),
        "wukv": _draw(k, 6, (s["c"], hn, s["nope"] + s["dv"]),
                      _unit(s["c"]), dtype),
        # attention's mean of unit values has rms near one half
        "wo": _draw(k, 7, (hn, s["dv"], d),
                    ATTN_OUT_RMS / 0.5 * _unit(hn * s["dv"]), dtype),
        "ln2_g": _draw(k, 8, (d,), 0.1, dtype, mean=1.0),
    }

    def down(width, out_rms):
        return out_rms / _ACT_RMS * _unit(width)

    if dense:
        w.update(
            w_gate=_draw(k, 9, (d, s["inner"]), _unit(d), dtype),
            w_up=_draw(k, 10, (d, s["inner"]), _unit(d), dtype),
            w_down=_draw(k, 11, (s["inner"], d),
                         down(s["inner"], FFN_OUT_RMS), dtype))
        return w
    e, h = s["experts"], s["h"]
    first, count = experts_held(cfg)
    # drawn for all experts' indices, so that a share holds the same
    # numbers as the whole layer's experts first .. first + count
    ke_in = jax.random.fold_in(k, 20)
    ke_out = jax.random.fold_in(k, 21)
    held = jnp.arange(first, first + count)

    def per_expert(kk, shape, std):
        return jax.vmap(lambda j: (std * jax.random.normal(
            jax.random.fold_in(kk, j), shape, jnp.float32)).astype(dtype)
        )(held)

    # the first ROUTED_COMMON_UNITS of an expert's hidden units are the
    # layer's, the same in every expert (gate and up columns, down rows)
    common = jnp.arange(h) < int(ROUTED_COMMON_UNITS * h)
    w.update(
        router=_draw(k, 12, (d, e), _unit(d), dtype),
        e_bias=_draw(k, 13, (e,), 0.01, dtype),
        we_in=jnp.where(
            jnp.tile(common, 2),
            _draw(k, 17, (d, 2 * h), _unit(d), dtype),
            per_expert(ke_in, (d, 2 * h), _unit(d))),
        we_down=jnp.where(
            common[:, None],
            _draw(k, 18, (h, d), down(h, ROUTED_EXPERT_RMS), dtype),
            per_expert(ke_out, (h, d), down(h, ROUTED_EXPERT_RMS))),
        ws_gate=_draw(k, 14, (d, s["shared"]), _unit(d), dtype),
        ws_up=_draw(k, 15, (d, s["shared"]), _unit(d), dtype),
        ws_down=_draw(k, 16, (s["shared"], d),
                      down(s["shared"], FFN_OUT_RMS), dtype))
    return w


def make(cfg: dict, seed: int, dtype) -> dict:
    """All weights on the device from the seed, in ``dtype``: one compiled
    program for a dense layer and one for an expert layer, each run once
    a layer, and one for the rest."""
    dtype = jnp.dtype(dtype)
    key = seed_key(seed)
    layer = jax.jit(lambda k, i, dense: layer_weights(cfg, k, i, dtype,
                                                      dense),
                    static_argnums=2)
    rest = jax.jit(lambda k: global_weights(cfg, k, dtype))
    return {"globals": rest(key),
            "layers": [layer(key, i, is_dense(cfg, i))
                       for i in range(cfg["num_hidden_layers"])]}
