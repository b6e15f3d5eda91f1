"""``ops.linear_attention.kda_step_kernel``, the one-token KDA step as a
Pallas TPU kernel, interpreted on the CPU: against ``kda_step`` and the
recurrence written out token by token in float64; the rule that chooses
it (``kda_step_kernel_applies``); and ``HybridMoELM`` served through it,
traced as on a TPU, with the engine's count of the rows it updated.

What the chip's compiler makes of it is checked in ``tests/test_layouts.py``
(the file that loads the TPU's compiler); its speed only on the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import build_model, generate
from distkeras_tpu.ops import attention
from distkeras_tpu.ops import linear_attention as la
from distkeras_tpu.serving import DecodeEngine
from perfbench.adapters import kda_mla_moe as adapter
from perfbench.reference import kda_mla_moe as reference
from perfbench.weights import kda_mla_moe as weights

jax.config.update("jax_platforms", "cpu")


def _inputs(b, t, h, d, seed=0):
    """``t`` tokens of ``b`` rows: gates over (-5, 0) with some at the
    lower bound, ``beta`` over [0, 1] with both ends, a state that is not
    zero."""
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    q = la.l2_normalize(f(b, t, h, d)) * d ** -0.5
    k = la.l2_normalize(f(b, t, h, d))
    g = r.uniform(-5.0, 0.0, (b, t, h, d))
    g[:, :, 0, ::7] = -5.0
    beta = r.uniform(0.0, 1.0, (b, t, h))
    beta[0, :, 0], beta[1, :, 0] = 0.0, 1.0
    return (q, k, f(b, t, h, d), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32), f(b, h, d, d))


def _by_hand(q, k, v, g, beta, s):
    """``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
    v_t^T``, ``o_t = S_t^T q_t``, token by token in float64."""
    q, k, v, g, beta, s = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, s))
    eye = np.eye(q.shape[-1])
    o = np.zeros(v.shape)
    for t in range(q.shape[1]):
        kk, bt = k[:, t], beta[:, t, :, None, None]
        s = (eye - bt * kk[..., :, None] * kk[..., None, :]) \
            @ (np.exp(g[:, t])[..., None] * s) \
            + bt * kk[..., :, None] * v[:, t][..., None, :]
        o[:, t] = np.einsum("bhk,bhkv->bhv", q[:, t], s)
    return o, s


def _close(got, want):
    want = np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


def test_the_kernel_is_kda_step_and_the_recurrence_token_by_token():
    """13 rows (blocks of 4 rows of 4 heads: the last block is cut), heads
    of 128, three tokens each from the state the last one left: the
    kernel's state and outputs are ``kda_step``'s and the float64
    recurrence's, to 1e-5."""
    b, t, h, d = 13, 3, 4, 128
    assert b % la._step_block(b, h, d, d)[0]
    q, k, v, g, beta, s0 = _inputs(b, t, h, d)
    want_o, want_s = _by_hand(q, k, v, g, beta, s0)
    s_kernel = s_step = s0
    for i in range(t):
        x = (q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i])
        s_kernel, o_kernel = la.kda_step_kernel(s_kernel, *x)
        s_step, o_step = la.kda_step(s_step, *x)
        assert o_kernel.dtype == s_kernel.dtype == jnp.float32
        _close(o_kernel, o_step)
        _close(s_kernel, s_step)
        _close(o_kernel, want_o[:, i])
    _close(s_kernel, want_s)


def test_the_kernel_takes_any_tiling_of_rows_and_heads():
    b, h, d = 5, 16, 128
    q, k, v, g, beta, s0 = _inputs(b, 1, h, d, seed=1)
    x = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    want_s, want_o = la.kda_step(s0, *x)
    for rows, heads in [(1, 16), (2, 8), (4, 16)]:
        s, o = la.kda_step_kernel(s0, *x, rows=rows, heads=heads)
        _close(s, want_s)
        _close(o, want_o)


def test_a_token_with_no_beta_and_no_decay_leaves_the_state_bit_for_bit():
    q, k, v, g, beta, s0 = _inputs(3, 1, 2, 128, seed=2)
    s, _ = la.kda_step_kernel(s0, q[:, 0], k[:, 0], v[:, 0],
                              jnp.zeros_like(g[:, 0]),
                              jnp.zeros_like(beta[:, 0]))
    np.testing.assert_array_equal(s, s0)


def test_the_rule_takes_a_float32_state_of_whole_tiles_on_a_tpu(
        monkeypatch):
    state = jax.ShapeDtypeStruct((256, 32, 128, 128), jnp.float32)
    assert not la.kda_step_kernel_applies(state)         # the CPU
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert la.kda_step_kernel_applies(state)
    for refused in [(256, 32, 128, 128, jnp.bfloat16),   # not float32
                    (2, 4, 32, 32, jnp.float32),         # d_v 32
                    (2, 2, 16, 16, jnp.float32),         # the toy's
                    (2, 4, 12, 128, jnp.float32)]:       # d_k 12
        *shape, dtype = refused
        assert not la.kda_step_kernel_applies(
            jax.ShapeDtypeStruct(shape, dtype))


# ---- the model served through it ---------------------------------------

VOCAB, MAXLEN, SEED, ALIGN = 211, 96, 2**31 + 11, 16
with open(os.path.join(os.path.dirname(__file__), "perfbench", "tiny_kda",
                       "configs", "tiny-kda.json")) as f:
    CFG = {**json.load(f), "vocab_size": VOCAB, "n_positions": MAXLEN,
           "dtype_as_run": "float32", "weights_as_run": "float32"}
REQUESTS = [(5, 6), (19, 3), (9, 7)]


def _served(cfg, monkeypatch, on_tpu):
    """Three requests through two slots and ``generate()``, the programs
    traced as on a TPU where ``on_tpu``: the tokens, the ``decode_step``
    spans' ``kda_kernel_rows`` and the kernel's calls while tracing."""
    w = weights.make(cfg, SEED, "float32")
    model = build_model(adapter.program_model(cfg, MAXLEN))
    variables = adapter.program_variables(w)
    calls = []
    real = la.kda_step_kernel
    monkeypatch.setattr(la, "kda_step_kernel",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    if on_tpu:
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
               for n, _ in REQUESTS]
    tel = telemetry.enable()
    try:
        eng = DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                           prefill_align=ALIGN)
        served = [r["tokens"] for r in eng.run(
            [{"prompt": p, "max_new_tokens": n}
             for p, (_, n) in zip(prompts, REQUESTS)])]
        eng.close()
        rows = [e["args"]["kda_kernel_rows"] for e in tel.tracer.events()
                if e["name"] == "decode_step"]
    finally:
        telemetry.disable()
    generated = [np.asarray(generate(model, variables, p[None],
                                     max_new_tokens=n))[0, len(p):]
                 for p, (_, n) in zip(prompts, REQUESTS)]
    monkeypatch.undo()
    gaps = []
    for p, toks in zip(prompts, served):
        seq = np.concatenate([p, np.asarray(toks, np.int32)])
        logits = np.asarray(reference.forward(w, seq, cfg))
        best = logits[len(p) - 1:len(seq) - 1]
        gaps.append((best.max(1) - best[np.arange(len(toks)), toks]).max())
    return served, generated, rows, len(calls), max(gaps)


@pytest.mark.parametrize("head_dim", [16, 128],
                         ids=["refused_heads_of_16", "heads_of_128"])
def test_the_model_is_served_through_the_kernel_where_the_rule_takes_it(
        monkeypatch, head_dim):
    """Traced as on a TPU, the toy (heads of 16) keeps the ``jnp`` step:
    no call of the kernel, ``kda_kernel_rows`` 0, the tokens those of the
    CPU's own programs.  With heads of 128 the engine's step and
    ``generate()``'s run the kernel (interpreted) in both KDA layers:
    ``kda_kernel_rows`` is 2 slots x 2 layers a step, and the tokens are
    still the reference's first choices and the CPU programs' own."""
    cfg = {**CFG, "head_dim": head_dim}
    served, generated, rows, calls, gap = _served(cfg, monkeypatch, True)
    base_served, base_generated, base_rows, base_calls, _ = _served(
        cfg, monkeypatch, False)
    assert base_calls == 0 and set(base_rows) == {0}
    kernel = head_dim == 128
    assert (calls > 0) == kernel
    assert rows and set(rows) == {2 * 2 if kernel else 0}
    assert gap <= 2e-4
    for got, want in zip(served + generated, base_served + base_generated):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(served, generated):
        np.testing.assert_array_equal(got, want)
