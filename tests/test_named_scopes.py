"""The names the program gives its device work (PR 26): a
``jax.named_scope`` ends up in every operation's ``op_name``, which is
what xprof, Perfetto and ``perfbench/layers_spans.py`` group by; a Pallas
kernel's ``name`` is the name of its custom call.  Scopes are metadata
only, so the lowered text is where they can be checked on the CPU."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from distkeras_tpu.models import ModelSpec, model_config
from distkeras_tpu.ops import attention
from distkeras_tpu.serving import DecodeEngine
from distkeras_tpu.workers import (TrainState, make_train_step,
                                   make_window_runner, resolve_optimizer)

jax.config.update("jax_platforms", "cpu")

MAXLEN, VOCAB = 32, 37


def _names(text: str, scope: str) -> bool:
    """Whether a location of the lowered text has ``scope`` as one
    component of its path, bare or inside a transform's brackets:
    ``.../kv_write/scatter``, ``jvp(forward_loss)``, ``sample``."""
    return re.search(
        rf'"(?:[^"\n]*[/(])?{scope}(?:[/)][^"\n]*)?"', text) is not None


@functools.lru_cache(maxsize=None)
def _lowered(program: str) -> str:
    spec = model_config("transformer_lm", (MAXLEN,), input_dtype="int32",
                        vocab_size=VOCAB, num_layers=1, d_model=32,
                        num_heads=2, max_len=MAXLEN, dtype="float32")
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((2, MAXLEN), jnp.int32))
    if program == "train_step":
        tx = resolve_optimizer("adam", 1e-3)
        state = TrainState.create(variables, tx, jax.random.key(1))
        step = make_train_step(model, "sparse_categorical_crossentropy", tx)
        batch = {"features": jnp.zeros((2, 4, MAXLEN), jnp.int32),
                 "label": jnp.zeros((2, 4, MAXLEN), jnp.int32)}
        lowered = jax.jit(make_window_runner(step)).lower(state, batch)
    else:
        eng = DecodeEngine(model, variables, slots=3, buckets=[16],
                           prefill_align=4)
        pool = eng._pools[0]
        rng = jax.random.key(0)
        if program == "decode_step":
            lowered = pool.step_fn.lower(eng.variables, pool.cache,
                                         pool.state, rng)
        else:
            lowered = pool.prefill_fn.lower(
                eng.variables, pool.cache, pool.state,
                jnp.zeros((1, 8), jnp.int32), 0, 4, 3, -1, rng)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("program,scope", [
    ("decode_step", "kv_write"), ("decode_step", "attn_decode"),
    ("decode_step", "mlp"), ("decode_step", "lm_head"),
    ("decode_step", "sample"),
    ("prefill", "kv_write"), ("prefill", "mlp"), ("prefill", "lm_head"),
    ("prefill", "sample"), ("prefill", "prefill_install"),
    ("train_step", "forward_loss"), ("train_step", "backward"),
    ("train_step", "optimizer_update"), ("train_step", "mlp"),
    ("train_step", "lm_head"),
])
def test_lowered_program_names_the_scope(program, scope):
    assert _names(_lowered(program), scope), \
        f"no operation of {program} is under {scope}"


def test_decode_program_is_named_for_its_pool():
    assert "module @jit_step_impl_16 " in _lowered("decode_step")


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_flash_kernels_are_named(kernel):
    q = jnp.ones((1, 256, 2, 128), jnp.float32)

    def loss(q, k, v):
        return attention.flash_attention(q, k, v, interpret=True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).as_text(debug_info=True)
    assert _names(text, kernel)


@pytest.mark.parametrize("kernel,call", [
    ("flash_hop_fwd", "fwd"), ("flash_hop_dq", "bwd"),
    ("flash_hop_dkv", "bwd")])
def test_ring_hop_kernels_are_named(kernel, call):
    b, t, h, d = 1, 128, 1, 128
    x = jnp.ones((b, h, t, d), jnp.float32)
    row = jnp.zeros((b, h, t, 1), jnp.float32)
    if call == "fwd":
        fn = functools.partial(attention.flash_hop_fwd, q_offset=0,
                               k_offset=0, scale=1.0, interpret=True)
        text = jax.jit(fn).lower(x, x, x, row, row, x).as_text(
            debug_info=True)
    else:
        fn = functools.partial(attention.flash_hop_bwd, q_offset=0,
                               k_offset=0, scale=1.0, interpret=True)
        text = jax.jit(fn).lower(x, x, x, x, row, row).as_text(
            debug_info=True)
    assert _names(text, kernel)
