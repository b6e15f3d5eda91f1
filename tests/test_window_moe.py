"""``models.window_moe.WindowMoELM`` (sliding-window layers beside full
grouped-query attention, post-sublayer norms, sigmoid-routed experts)
against the benchmark's plain reference at a toy size, through the same
entry points as the other served families: ``generate()`` and
``DecodeEngine``; and what it brought to the engine: the window rings.

Toy widths only here; the published widths run in the benchmark's cell
(``exa-serve-backlog``) on the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import build_model, generate, window_moe
from distkeras_tpu.models.generate import window_leaves
from distkeras_tpu.ops import attention
from distkeras_tpu.serving import DecodeEngine
from perfbench.adapters import gqa_swa_moe as adapter
from perfbench.counts import gqa_swa_moe as counts
from perfbench.reference import gqa_swa_moe as reference
from perfbench.weights import gqa_swa_moe as weights

jax.config.update("jax_platforms", "cpu")

VOCAB, MAXLEN, SEED, ALIGN = 211, 96, 2**31 + 11, 16
# the benchmark's toy configuration of this arch (a window of 16), in
# float32
with open(os.path.join(os.path.dirname(__file__), "perfbench", "tiny_swa",
                       "configs", "tiny-swa.json")) as f:
    CFG = {**json.load(f), "vocab_size": VOCAB, "n_positions": MAXLEN,
           "dtype_as_run": "float32", "weights_as_run": "float32"}
W = CFG["sliding_window"]
# float32 program against the float32 reference: what is left is the
# order of summation (the grouped and banded products, the grouped
# experts); the logits are of order 1
TOL = 2e-4


@pytest.fixture(scope="module")
def toy():
    w = weights.make(CFG, SEED, "float32")
    model = build_model(adapter.program_model(CFG, MAXLEN))
    return model, adapter.program_variables(w), w


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


def test_the_adapter_fills_the_programs_own_tree(toy):
    model, variables, _ = toy
    init = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.shape, t)
    assert shapes(init["params"]) == shapes(variables["params"])


def test_the_counts_are_the_programs_parameters(toy):
    _, variables, _ = toy
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(variables))
    assert counts.total_params(CFG) == n


def test_full_forward_is_the_references(toy):
    model, variables, w = toy
    seq = _prompts((60,))[0]
    got = np.asarray(model.apply(variables, seq[None]))[0]
    want = np.asarray(reference.forward(w, seq, CFG))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("prompt", [5, 37], ids=["short", "long"])
def test_a_padded_prefill_then_slot_steps_is_the_references_forward(
        toy, prompt):
    """The prompt right-padded past the window's edge (its last row picked
    by ``last_index``), then slot steps on past ``W``: every logit is the
    reference's full forward's."""
    model, variables, w = toy
    dec = model.decode_clone()
    seq = _prompts((56,), seed=prompt)[0]
    want = np.asarray(reference.forward(w, seq, CFG))
    padded = np.full((1, 48), 7, np.int32)
    padded[0, :prompt] = seq[:prompt]
    logits, st = dec.apply(variables, padded, mutable=["cache"],
                           last_index=prompt - 1)
    np.testing.assert_allclose(np.asarray(logits)[0, 0], want[prompt - 1],
                               atol=TOL)
    cache = st["cache"]
    step = jax.jit(lambda cache, tok, pos: dec.apply(
        {**variables, "cache": cache}, tok, mutable=["cache"],
        slot_pos=pos))
    for pos in range(prompt, 56):
        logits, st = step(cache, seq[None, pos:pos + 1], jnp.array([pos]))
        cache = st["cache"]
        np.testing.assert_allclose(np.asarray(logits)[0, 0], want[pos],
                                   atol=TOL)


@pytest.mark.parametrize("prompt", [5, 37], ids=["short", "long"])
def test_a_prefill_installs_the_prompts_last_true_positions(toy, prompt):
    """Padded or not, the rings hold the prompt's last ``min(len, W)``
    positions in rows ``p mod W`` (zeros where the prompt is shorter):
    the keys that the unpadded prompt's own prefill computes there (to
    float32's rounding past the first layer: a longer chunk sums in
    another order)."""
    model, variables, _ = toy
    dec = model.decode_clone()
    seq = _prompts((prompt,), seed=1)[0]
    padded = np.full((1, 48), 3, np.int32)
    padded[0, :prompt] = seq
    _, st = dec.apply(variables, padded, mutable=["cache"],
                      last_index=prompt - 1)
    _, bare = dec.apply(variables, seq[None], mutable=["cache"])
    keys = np.asarray(bare["cache"]["Layer_3_attn"]["cached_key"])[0]
    for i in (0, 1, 2, 4):
        ring = np.asarray(st["cache"][f"Layer_{i}_attn"]["window_key"])[0]
        want = np.asarray(bare["cache"][f"Layer_{i}_attn"]["window_key"])[0]
        np.testing.assert_allclose(ring, want, atol=1e-5)
        live = [p % W for p in range(max(0, prompt - W), prompt)]
        dead = sorted(set(range(W)) - set(live))
        assert np.abs(ring[live]).min() > 0 and not ring[dead].any()
    assert not keys[prompt:].any() and np.abs(keys[:prompt]).max() > 0


REQUESTS = [(5, 20), (17, 4), (40, 12), (3, 7), (21, 30)]


@pytest.fixture(scope="module")
def expected(toy):
    """Each request's ``generate()`` tokens, and how far below the
    reference's best each of their logits lies."""
    model, variables, w = toy
    # one length for every sequence: a row's logits see no later token
    forward = jax.jit(lambda w, seq: reference.forward(w, seq, CFG))
    out = []
    for p in _prompts([n for n, _ in REQUESTS]):
        b = dict(REQUESTS)[len(p)]
        tokens = np.asarray(generate(model, variables, p[None],
                                     max_new_tokens=b))[0, len(p):]
        seq = np.zeros(MAXLEN, np.int32)
        seq[:len(p) + b] = np.concatenate([p, tokens])
        rows = np.asarray(forward(w, seq))[len(p) - 1:len(p) + b - 1]
        out.append((p, tokens.tolist(),
                    rows.max(axis=1) - rows[np.arange(b), tokens]))
    return out


def test_generate_serves_the_references_first_choices(expected):
    for _, _, gaps in expected:
        assert gaps.max() <= TOL


@pytest.mark.parametrize("steps_per_sync", [1, 3])
def test_the_engine_serves_generates_tokens(toy, expected, steps_per_sync):
    """Two slots for five requests whose prompts and answers cross the
    window: slots finish, are readmitted beside live ones, prompts are no
    multiple of ``prefill_align``.  The tokens are ``generate()``'s, which
    are the reference's first choices."""
    model, variables, _ = toy
    eng = DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                       prefill_align=ALIGN, steps_per_sync=steps_per_sync)
    out = list(eng.run([{"prompt": p, "max_new_tokens": len(t)}
                        for p, t, _ in expected]))
    assert [list(r["tokens"]) for r in out] == [t for _, t, _ in expected]
    (report,) = eng.pool_report()
    # two slots x four window layers x keys and values x W x 2 heads of 16
    assert report["window_bytes"] == 2 * 4 * 2 * W * 32 * 4
    assert report["recurrent_bytes"] == 0
    eng.close()


@pytest.mark.parametrize("arm", [
    {"kv_pages": 24}, {"prefix_cache_bytes": 1 << 20},
    {"prefill_chunk": ALIGN}, {"speculative": {"proposer": "ngram"}}],
    ids=["paged", "prefix", "chunked", "speculative"])
def test_the_arms_that_rewind_or_share_positions_refuse_it(toy, arm):
    model, variables, _ = toy
    with pytest.raises(ValueError, match="Layer_0_attn/window_key"):
        DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                     prefill_align=ALIGN, **arm)


def test_the_cache_declares_rings_and_lane_dense_leaves(toy):
    model, variables, _ = toy
    dec = model.decode_clone()
    shapes = jax.eval_shape(
        lambda v: dec.apply(v, jnp.zeros((2, 1), jnp.int32),
                            mutable=["cache"]), variables)[1]["cache"]
    rings = window_leaves(shapes)
    assert sorted(rings) == [f"Layer_{i}_attn/window_{kind}"
                             for i in (0, 1, 2, 4)
                             for kind in ("key", "value")]
    assert {r.shape for r in rings.values()} == {(2, W, 32)}
    assert shapes["Layer_3_attn"]["cached_key"].shape == (2, MAXLEN, 32)
    with pytest.raises(ValueError) as err:
        dec.dense_prefill_clone()
    for path in rings:
        assert path in str(err.value)


def test_the_shares_of_the_experts_add_up_to_the_whole_layer(toy):
    """Sixteen shares of one expert each, as sixteen chips hold a layer:
    their routed parts, with the shared expert counted once, are the
    uncut reference layer's feed-forward (the post-sublayer norm comes
    after the sum)."""
    _, _, w = toy
    x = jnp.asarray(np.random.default_rng(4).normal(size=(24, 64)),
                    jnp.float32)
    whole_cfg = {**CFG, "experts_held": [0, 16]}
    layer = weights.layer_weights(whole_cfg, weights.seed_key(SEED), 1,
                                  jnp.float32, weights.kind_of(CFG, 1))
    whole, _ = reference.experts(x, layer, whole_cfg, "f32")
    shared = reference._swiglu(x, layer["ws_gate"], layer["ws_up"],
                               layer["ws_down"], "f32")
    parts = 0.0
    for e in range(16):
        share = {**layer, "we_in": layer["we_in"][e:e + 1],
                 "we_down": layer["we_down"][e:e + 1]}
        y, _ = reference.experts(x, share, {**CFG, "experts_held": [e, 1]},
                                 "f32")
        parts = parts + y - shared
    np.testing.assert_allclose(parts + shared, whole, atol=1e-4)
    # and the program's share of eight is the reference's
    held = {**layer, "we_in": layer["we_in"][:8],
            "we_down": layer["we_down"][:8]}
    np.testing.assert_allclose(
        reference.experts(x, held, CFG, "f32")[0],
        reference.experts(x, w["layers"][1], CFG, "f32")[0], atol=1e-5)


# ---- through the kernels, interpreted on the CPU -------------------------

KERNEL_CFG = {**CFG, "head_dim": 128, "sliding_window": 128,
              "sliding_windows": [128, 128, 128, 0, 128], "n_positions": 256}


def test_the_kernels_serve_generates_tokens(monkeypatch):
    """Heads of 128 and a window of 128, traced as on a TPU: the prefill
    runs the windowed flash kernel (every padded length a multiple of
    128), the step reads the full layer and the rings through
    ``decode_attention``; the tokens are ``generate()``'s (XLA's reads),
    and the spans count what the kernels read and computed."""
    w = weights.make(KERNEL_CFG, SEED, "float32")
    model = build_model(adapter.program_model(KERNEL_CFG, 256))
    variables = adapter.program_variables(w)
    prompts = _prompts((140, 124), seed=2)
    budgets = (6, 9)
    want = [np.asarray(generate(model, variables, p[None],
                                max_new_tokens=b))[0, len(p):].tolist()
            for p, b in zip(prompts, budgets)]
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(window_moe, "_committed_platform", lambda x: "tpu")
    tel = telemetry.enable()
    try:
        eng = DecodeEngine(model, variables, slots=2, buckets=[256],
                           prefill_align=128)
        out = list(eng.run([{"prompt": p, "max_new_tokens": b}
                            for p, b in zip(prompts, budgets)]))
        events = tel.tracer.events()
    finally:
        telemetry.disable()
    assert [list(r["tokens"]) for r in out] == want
    steps = [e["args"] for e in events if e["name"] == "decode_step"]
    prefills = [e["args"] for e in events if e["name"] == "prefill"]
    assert steps and all(a["window_rows"] > 0 and a["attended_rows"] > 0
                         for a in steps)
    # two live rows read at most 128 rows of 4 rings each, one of them
    # past the window's edge
    assert 4 * 128 < max(a["window_rows"] for a in steps) <= 2 * 4 * 128
    # padded to 128 (one q block: one key block) or to 256 (two: one and
    # two key blocks), x 4 heads x 4 window layers
    assert sorted(a["band_blocks"] for a in prefills) == [16, 48]
