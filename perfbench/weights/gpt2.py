"""Seeded weights of a GPT-2-shaped decoder, in the benchmark's own layout.

The program's adapter (``perfbench/adapters/gpt2.py``) renames these
leaves into the program's tree; the plain reference
(``perfbench/reference/gpt2.py``) calls the same generator again, layer
by layer, after the program's state is freed.  Neither takes anything
the other has made: both start from ``--seed``.

Every leaf is drawn (none is a constant), so that every leaf of the
model matters to the logits and the comparison that decides ``correct``
covers all of them.  Standard deviations follow GPT-2's initializer:
0.02 everywhere, the two residual output projections scaled by
1/sqrt(2 L).
"""

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int):
    """A key from any whole number: ``--seed`` may exceed 32 signed bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _draw(key, i, shape, std, dtype, mean=0.0):
    x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return (mean + std * x).astype(dtype)


def global_weights(cfg: dict, key, dtype) -> dict:
    d, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    k = jax.random.fold_in(key, 1_000_003)
    return {
        "wte": _draw(k, 0, (v, d), STD, dtype),
        "wpe": _draw(k, 1, (p, d), STD / 2, dtype),
        "lnf_g": _draw(k, 2, (d,), 0.1, dtype, mean=1.0),
        "lnf_b": _draw(k, 3, (d,), STD, dtype),
        "head_b": _draw(k, 4, (v,), STD, dtype),
    }


def layer_weights(cfg: dict, key, i: int, dtype) -> dict:
    d, h, inner = cfg["n_embd"], cfg["n_head"], cfg["n_inner"]
    hd = d // h
    out_std = STD / (2 * cfg["n_layer_published"]) ** 0.5
    k = jax.random.fold_in(key, i)
    return {
        "ln1_g": _draw(k, 0, (d,), 0.1, dtype, mean=1.0),
        "ln1_b": _draw(k, 1, (d,), STD, dtype),
        "wq": _draw(k, 2, (d, h, hd), STD, dtype),
        "bq": _draw(k, 3, (h, hd), STD, dtype),
        "wk": _draw(k, 4, (d, h, hd), STD, dtype),
        "bk": _draw(k, 5, (h, hd), STD, dtype),
        "wv": _draw(k, 6, (d, h, hd), STD, dtype),
        "bv": _draw(k, 7, (h, hd), STD, dtype),
        "wo": _draw(k, 8, (h, hd, d), out_std, dtype),
        "bo": _draw(k, 9, (d,), STD, dtype),
        "ln2_g": _draw(k, 10, (d,), 0.1, dtype, mean=1.0),
        "ln2_b": _draw(k, 11, (d,), STD, dtype),
        "w1": _draw(k, 12, (d, inner), STD, dtype),
        "b1": _draw(k, 13, (inner,), STD, dtype),
        "w2": _draw(k, 14, (inner, d), out_std, dtype),
        "b2": _draw(k, 15, (d,), STD, dtype),
    }


def make(cfg: dict, seed: int, dtype) -> dict:
    """All weights on the device from the seed, in ``dtype``: one compiled
    program for a layer, run once for each, and one for the rest.  (One
    program for the whole model took two minutes to compile at 24 layers.)"""
    dtype = jnp.dtype(dtype)
    key = seed_key(seed)
    layer = jax.jit(lambda k, i: layer_weights(cfg, k, i, dtype))
    rest = jax.jit(lambda k: global_weights(cfg, k, dtype))
    return {"globals": rest(key),
            "layers": [layer(key, i) for i in range(cfg["n_layer"])]}
