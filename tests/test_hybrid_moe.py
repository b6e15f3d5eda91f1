"""``models.hybrid_moe.HybridMoELM`` (KDA linear-attention layers with a
float32 recurrent state beside latent attention, over grouped sparse
experts) against the benchmark's plain reference at a toy size, through
the same entry points as the other served families: ``generate()`` and
``DecodeEngine``; and the pieces it brought: the two forms of the KDA
recurrence, grouped selection, the SwiGLU clamp, the engine's recurrent
leaves.

Toy widths only here; the published widths run in the benchmark's cell
(``ling-serve-backlog``) on the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import build_model, generate, latent_moe
from distkeras_tpu.models.generate import recurrent_leaves
from distkeras_tpu.models.layers import SwiGLU
from distkeras_tpu.ops import linear_attention as la
from distkeras_tpu.parallel import moe
from distkeras_tpu.serving import DecodeEngine
from perfbench.adapters import kda_mla_moe as adapter
from perfbench.reference import kda_mla_moe as reference
from perfbench.weights import kda_mla_moe as weights

jax.config.update("jax_platforms", "cpu")

VOCAB, MAXLEN, SEED, ALIGN = 211, 96, 2**31 + 7, 16
# the benchmark's toy configuration of this arch, in float32
with open(os.path.join(os.path.dirname(__file__), "perfbench", "tiny_kda",
                       "configs", "tiny-kda.json")) as f:
    CFG = {**json.load(f), "vocab_size": VOCAB, "n_positions": MAXLEN,
           "dtype_as_run": "float32", "weights_as_run": "float32"}
# float32 program against the float32 reference: what is left is the
# order of summation (the chunked recurrence, the absorbed latent read,
# the grouped product); the logits are of order 1
TOL = 2e-4


@pytest.fixture(scope="module")
def toy():
    w = weights.make(CFG, SEED, "float32")
    model = build_model(adapter.program_model(CFG, MAXLEN))
    return model, adapter.program_variables(w), w


def _prompts(lengths):
    rng = np.random.default_rng(0)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


def test_the_adapter_fills_the_programs_own_tree(toy):
    model, variables, _ = toy
    init = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.shape, t)
    assert shapes(init["params"]) == shapes(variables["params"])


def test_the_layers_are_kda_then_latent_by_the_group_size(toy):
    model, variables, _ = toy
    kinds = ["qkv" in variables["params"][f"Layer_{i}_attn"]
             for i in range(CFG["num_hidden_layers"])]
    assert kinds == [True, True, False]     # layer_group_size 3


def test_full_forward_is_the_references(toy):
    model, variables, w = toy
    seq = _prompts((40,))[0]
    got = np.asarray(model.apply(variables, seq[None]))[0]
    want = np.asarray(reference.forward(w, seq, CFG))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=TOL)


def test_a_padded_prefill_then_slot_steps_is_the_references_forward(toy):
    """The prompt right-padded past a chunk edge (its last row picked by
    ``last_index``): the padding leaves the state and the convolution's
    tail where the prompt's last token put them."""
    model, variables, w = toy
    dec = model.decode_clone()
    seq = _prompts((40,))[0]
    want = np.asarray(reference.forward(w, seq, CFG))
    padded = np.full((1, 32), 7, np.int32)
    padded[0, :19] = seq[:19]
    logits, st = dec.apply(variables, padded, mutable=["cache"],
                           last_index=18)
    np.testing.assert_allclose(np.asarray(logits)[0, 0], want[18], atol=TOL)
    cache = st["cache"]
    state = cache["Layer_0_attn"]["recurrent_state"]
    assert state.shape == (1, 2, 16, 16) and state.dtype == jnp.float32
    step = jax.jit(lambda cache, tok, pos: dec.apply(
        {**variables, "cache": cache}, tok, mutable=["cache"],
        slot_pos=pos))
    for pos in range(19, 40):
        logits, st = step(cache, seq[None, pos:pos + 1], jnp.array([pos]))
        cache = st["cache"]
        np.testing.assert_allclose(np.asarray(logits)[0, 0], want[pos],
                                   atol=TOL)


def _reference_gaps(w, prompt, tokens):
    """How far below the reference's best each served token's logit lies."""
    seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = np.asarray(reference.forward(w, seq, CFG))
    rows = logits[len(prompt) - 1:len(seq) - 1]
    return rows.max(axis=1) - rows[np.arange(len(tokens)), tokens]


REQUESTS = [(5, 9), (17, 4), (30, 12), (3, 7), (21, 3), (11, 10), (8, 5)]


@pytest.mark.parametrize("steps_per_sync", [1, 3])
def test_the_engine_serves_the_references_first_choices(toy,
                                                        steps_per_sync):
    """Two slots for seven requests: slots finish, are readmitted beside
    live ones, prompts are no multiple of ``prefill_align``.  Every served
    token is the reference's first choice, and the tokens are
    ``generate()``'s."""
    model, variables, w = toy
    prompts = _prompts([n for n, _ in REQUESTS])
    eng = DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                       prefill_align=ALIGN, steps_per_sync=steps_per_sync)
    out = list(eng.run([{"prompt": p, "max_new_tokens": b}
                        for p, (_, b) in zip(prompts, REQUESTS)]))
    for p, (_, b), res in zip(prompts, REQUESTS, out):
        assert len(res["tokens"]) == b
        assert _reference_gaps(w, p, res["tokens"]).max() <= TOL
        g = np.asarray(generate(model, variables, p[None],
                                max_new_tokens=b))[0, len(p):]
        assert list(res["tokens"]) == g.tolist()
    (report,) = eng.pool_report()
    per_slot = sum(
        int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
        for leaf in recurrent_leaves(eng._pools[0].cache).values())
    assert report["recurrent_bytes"] == 2 * per_slot > 0
    eng.close()


def test_generate_serves_the_references_first_choices(toy):
    model, variables, w = toy
    prompt = _prompts((13,))[0]
    out = np.asarray(generate(model, variables, prompt[None],
                              max_new_tokens=20))[0, 13:]
    assert _reference_gaps(w, prompt, out).max() <= TOL


@pytest.mark.parametrize("arm", [
    {"kv_pages": 24}, {"prefix_cache_bytes": 1 << 20},
    {"prefill_chunk": ALIGN}, {"speculative": {"proposer": "ngram"}}],
    ids=["paged", "prefix", "chunked", "speculative"])
def test_the_arms_that_rewind_or_share_positions_refuse_it(toy, arm):
    model, variables, _ = toy
    with pytest.raises(ValueError, match="recurrent_state"):
        DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                     prefill_align=ALIGN, **arm)


def test_the_cache_declares_its_recurrent_leaves(toy):
    model, variables, _ = toy
    dec = model.decode_clone()
    shapes = jax.eval_shape(
        lambda v: dec.apply(v, jnp.zeros((2, 1), jnp.int32),
                            mutable=["cache"]), variables)[1]["cache"]
    leaves = recurrent_leaves(shapes)
    assert sorted(leaves) == [
        f"Layer_{i}_attn/recurrent_{kind}" for i in (0, 1)
        for kind in ("conv", "state")]
    assert leaves["Layer_0_attn/recurrent_conv"].shape == (2, 3 * 3 * 32)


def test_a_chunk_at_an_offset_is_refused_naming_the_leaves(toy):
    model, variables, _ = toy
    dec = model.decode_clone()
    shapes = jax.eval_shape(
        lambda v: dec.apply(v, jnp.zeros((2, 1), jnp.int32),
                            mutable=["cache"]), variables)[1]["cache"]
    with pytest.raises(ValueError) as err:
        dec.dense_prefill_clone()
    for path in recurrent_leaves(shapes):
        assert path in str(err.value)


# ---- the two forms of the recurrence ---------------------------------

def _token_by_token(q, k, v, g, beta, s0):
    def body(s, x):
        return la.kda_step(s, *x)

    move = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    s, o = jax.lax.scan(body, s0, tuple(map(move, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1), s


def _inputs(t, g_low, g_high, seed=0, b=2, h=3, dk=8):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)  # noqa: E731
    q = la.l2_normalize(f(b, t, h, dk)) * dk ** -0.5
    k = la.l2_normalize(f(b, t, h, dk))
    g = jnp.asarray(r.uniform(g_low, g_high, (b, t, h, dk)), jnp.float32)
    beta = jnp.asarray(r.uniform(0, 1, (b, t, h)), jnp.float32)
    return q, k, f(b, t, h, dk), g, beta, f(b, h, dk, dk)


@pytest.mark.parametrize("t", [1, 15, 16, 17, 32, 45])
@pytest.mark.parametrize("gates", [(-5.0, 0.0), (-5.0, -4.999),
                                   (-1e-3, 0.0)],
                         ids=["spread", "at_the_lower_bound", "near_zero"])
def test_the_chunked_form_is_the_token_by_token_recurrence(t, gates):
    """At and across chunk edges, from a state that is not zero, and with
    every gate at the lower bound (the largest cumulated decay a chunk
    can hold)."""
    q, k, v, g, beta, s0 = _inputs(t, *gates)
    want_o, want_s = _token_by_token(q, k, v, g, beta, s0)
    got_o, got_s = la.kda_chunked(q, k, v, g, beta, s0)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=1e-5)


def test_a_token_with_no_beta_and_no_decay_leaves_the_state():
    q, k, v, g, beta, s0 = _inputs(20, -5.0, 0.0)
    g = g.at[:, 12:].set(0.0)
    beta = beta.at[:, 12:].set(0.0)
    _, whole = la.kda_chunked(q, k, v, g, beta, s0)
    _, head = la.kda_chunked(q[:, :12], k[:, :12], v[:, :12], g[:, :12],
                             beta[:, :12], s0)
    np.testing.assert_allclose(whole, head, atol=1e-6)


def test_the_convolution_tail_is_the_last_true_inputs():
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(1, 9, 4)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 4)), jnp.float32)
    zero = jnp.zeros((1, 12), jnp.float32)
    y, tail = la.causal_conv(x, w, zero, last_index=5)
    np.testing.assert_array_equal(tail, x[:, 3:6].reshape(1, 12))
    # by hand: y_t = sum_j w[j] x_{t - 3 + j}
    padded = np.concatenate([np.zeros((3, 4)), np.asarray(x[0])])
    want = sum(padded[j:j + 9] * np.asarray(w[j]) for j in range(4))
    np.testing.assert_allclose(y[0], want, atol=1e-5)
    # one token on from that tail is the tenth row of the whole
    y1, tail1 = la.causal_conv(x[:, 6:7], w, tail)
    np.testing.assert_allclose(y1[0, 0], want[6], atol=1e-5)
    np.testing.assert_array_equal(tail1, x[:, 4:7].reshape(1, 12))


# ---- grouped selection, shares of the experts, the clamp --------------

def _brute_force(scores, bias, k, n_group, topk_group):
    t, e = scores.shape
    size = e // n_group
    out = []
    for row, s in enumerate(np.asarray(scores + bias)):
        groups = [np.sort(s[g * size:(g + 1) * size])[-2:].sum()
                  for g in range(n_group)]
        kept = np.argsort(groups)[::-1][:topk_group]
        allowed = np.concatenate([np.arange(g * size, (g + 1) * size)
                                  for g in kept])
        out.append(sorted(allowed[np.argsort(s[allowed])[::-1][:k]]))
    return np.array(out)


def test_grouped_selection_is_the_brute_force_choice():
    r = np.random.default_rng(5)
    x = jnp.asarray(r.normal(size=(64, 32)), jnp.float32)
    router = jnp.asarray(r.normal(size=(32, 64)) * 0.2, jnp.float32)
    bias = jnp.asarray(r.normal(size=(64,)) * 0.01, jnp.float32)
    idx, w = moe.sigmoid_topk(x, router, bias, 6, scale=2.5, n_group=8,
                              topk_group=3)
    scores = jax.nn.sigmoid(jnp.dot(x, router,
                                    precision=jax.lax.Precision.HIGHEST))
    np.testing.assert_array_equal(np.sort(np.asarray(idx), axis=1),
                                  _brute_force(scores, bias, 6, 8, 3))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), 1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(axis=1, keepdims=True), rtol=1e-6)


def test_a_group_scores_by_its_two_best_even_when_they_tie():
    """Each column of the router twice: every score has a twin in its
    group, and a group's two best are equal.  The choice is the one that
    a top-2 of each group gives."""
    r = np.random.default_rng(6)
    x = jnp.asarray(r.normal(size=(32, 16)), jnp.float32)
    half = jnp.asarray(r.normal(size=(16, 16)) * 0.3, jnp.float32)
    router = jnp.repeat(half, 2, axis=1)                  # [16, 32]
    bias = jnp.zeros((32,), jnp.float32)
    idx, _ = moe.sigmoid_topk(x, router, bias, 4, n_group=4, topk_group=2)
    scores = jax.nn.sigmoid(x @ router)
    groups = jax.lax.top_k(scores.reshape(32, 4, 8), 2)[0].sum(-1)
    kept = jax.lax.top_k(groups, 2)[1]
    allowed = np.zeros((32, 4), bool)
    allowed[np.arange(32)[:, None], np.asarray(kept)] = True
    assert np.take_along_axis(np.repeat(allowed, 8, axis=1),
                              np.asarray(idx), 1).all()


def _one_group(x, router, bias, top_k, *, normalize=True, scale=1.0):
    """``sigmoid_topk`` as it was before grouped selection."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def test_one_group_traces_to_the_program_it_was():
    args = (jax.ShapeDtypeStruct((16, 8), jnp.float32),
            jax.ShapeDtypeStruct((8, 12), jnp.float32),
            jax.ShapeDtypeStruct((12,), jnp.float32))
    now = jax.make_jaxpr(lambda *a: moe.sigmoid_topk(*a, 4, scale=2.0))
    was = jax.make_jaxpr(lambda *a: _one_group(*a, 4, scale=2.0))
    assert str(now(*args)) == str(was(*args))


def test_the_shares_of_grouped_experts_add_up_to_the_whole_layer():
    """Eight shares of 4 of 32 experts in 4 groups, each routing over all
    of them: their routed parts, and the shared expert once, are the
    uncut layer."""
    r = np.random.default_rng(8)
    x = jnp.asarray(r.normal(size=(1, 24, 16)), jnp.float32)

    def layer(held):
        return latent_moe.DroplessMoE(32, 4, 8, 8, 2.5, True, jnp.float32,
                                      held, n_group=4, topk_group=2)

    whole = layer((0, 32))
    v = whole.init(jax.random.key(0), x, x)
    p = v["params"]

    def share(first, count, shared):
        sp = {**p, "w_in": p["w_in"][first:first + count],
              "w_out": p["w_out"][first:first + count]}
        if not shared:
            sp["shared"] = jax.tree_util.tree_map(jnp.zeros_like,
                                                  p["shared"])
        return layer((first, count)).apply({"params": sp}, x, x)

    parts = sum(share(4 * j, 4, j == 0) for j in range(8))
    np.testing.assert_allclose(parts, whole.apply(v, x, x), atol=1e-5)


def test_the_swiglu_limit_clamps_as_gpt_oss():
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(5, 8)) * 4, jnp.float32)
    free = SwiGLU(16, jnp.float32)
    v = free.init(jax.random.key(1), x)
    p = v["params"]
    g, u = x @ p["gate"]["kernel"], x @ p["up"]["kernel"]
    assert float(g.max()) > 1.0 and float(jnp.abs(u).max()) > 1.0
    g, u = jnp.minimum(g, 1.0), jnp.clip(u, -1.0, 1.0)
    want = (jax.nn.silu(g) * u) @ p["down"]["kernel"]
    got = SwiGLU(16, jnp.float32, limit=1.0).apply(v, x)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # the routed experts clamp the same way
    w_in = jnp.concatenate([p["gate"]["kernel"], p["up"]["kernel"]],
                           axis=1)[None]
    idx = jnp.zeros((5, 1), jnp.int32)
    y = moe.dropless_experts(x, idx, jnp.ones((5, 1)), w_in,
                             p["down"]["kernel"][None], limit=1.0)
    np.testing.assert_allclose(y, want, atol=1e-5)
