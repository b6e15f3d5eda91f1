"""``models.latent_moe.LatentMoELM`` (latent attention, dropless sparse
experts beside a shared one, a four-stream constrained residual) against
the benchmark's plain reference at a toy size, through the same entry
points as ``TransformerLM``: ``generate()`` and ``DecodeEngine``.

Toy widths only here; the published widths run in the benchmark's cell
(``xing-serve-backlog``) on the chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import build_model, generate, latent_moe
from distkeras_tpu.models.generate import _decode_model
from distkeras_tpu.parallel import moe
from distkeras_tpu.serving import DecodeEngine
from perfbench.adapters import mla_moe_hc as adapter
from perfbench.reference import mla_moe_hc as reference
from perfbench.weights import mla_moe_hc as weights

jax.config.update("jax_platforms", "cpu")

VOCAB, MAXLEN, SEED = 211, 256, 2**31 + 5
# the benchmark's toy configuration of this arch, in float32
with open(os.path.join(os.path.dirname(__file__), "perfbench", "tiny_moe",
                       "configs", "tiny-moe.json")) as f:
    CFG = {**json.load(f), "vocab_size": VOCAB, "n_positions": MAXLEN,
           "dtype_as_run": "float32", "weights_as_run": "float32"}
# float32 program against the float32 reference: what is left is the
# order of summation (the absorbed form folds W_uk into the query, the
# grouped product sums an expert's rows in another order); the logits
# are of order 1, so 2e-5 is twenty times what was read (1e-6) and a
# thousandth of what the float8 control moves them by (test below)
TOL = 2e-5


@pytest.fixture(scope="module")
def toy():
    w = weights.make(CFG, SEED, "float32")
    model = build_model(adapter.program_model(CFG, MAXLEN))
    return model, adapter.program_variables(w), w


def _prompts(lengths=(5, 17, 30)):
    rng = np.random.default_rng(0)
    return [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]


def test_the_adapter_fills_the_programs_own_tree(toy):
    model, variables, _ = toy
    init = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))
    shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.shape, t)
    assert shapes(init["params"]) == shapes(variables["params"])


def test_full_forward_is_the_references(toy):
    model, variables, w = toy
    seq = _prompts((40,))[0]
    got = np.asarray(model.apply(variables, seq[None]))[0]
    want = np.asarray(reference.forward(w, seq, CFG))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("prefill,padded_len",
                         [("expanded", 128), ("absorbed", 16)])
def test_prefill_then_slot_mode_decode_is_the_references_forward(
        toy, prefill, padded_len):
    """The prompt in one chunk (right-padded, its last row picked by
    ``last_index``; to a multiple of 128 it runs the expanded form, to
    any other length the absorbed one), then T=1 steps at per-row
    positions: the absorbed form, reading the latent cache."""
    model, variables, w = toy
    dec = model.decode_clone()
    seq = _prompts((24,))[0]
    want = np.asarray(reference.forward(w, seq, CFG))
    padded = np.zeros((1, padded_len), np.int32)
    padded[0, :13] = seq[:13]
    logits, st = dec.apply(variables, padded, mutable=["cache"],
                           last_index=12)
    np.testing.assert_allclose(np.asarray(logits)[0, 0], want[12],
                               atol=TOL)
    cache = st["cache"]
    leaf = cache["Layer_0_attn"]["cached_latent"]
    assert leaf.shape == (1, MAXLEN, 16 + 8)
    step = jax.jit(lambda cache, tok, pos: dec.apply(
        {**variables, "cache": cache}, tok, mutable=["cache"],
        slot_pos=pos))
    for pos in range(13, 24):
        logits, st = step(cache, seq[None, pos:pos + 1], jnp.array([pos]))
        cache = st["cache"]
        np.testing.assert_allclose(np.asarray(logits)[0, 0], want[pos],
                                   atol=TOL)


def test_absorbed_is_expanded_and_the_flash_kernel_is_both(
        toy, monkeypatch):
    """One 128-token chunk on an empty cache, three ways: the expanded
    form through XLA, the same through the Pallas kernel that a TPU runs
    (q/k 24 wide, v 16; interpreted here), and the absorbed read of the
    cache."""
    from distkeras_tpu.ops.attention import flash_attn_fn

    model, variables, _ = toy
    dec = model.decode_clone()
    seq = np.random.default_rng(1).integers(0, VOCAB, (1, 128))

    def logits(dec):
        return np.asarray(dec.apply(variables, seq, mutable=["cache"],
                                    logits_all=True)[0])

    expanded = logits(dec)
    np.testing.assert_allclose(logits(dec.dense_prefill_clone()), expanded,
                               atol=TOL)
    monkeypatch.setattr(latent_moe, "dense_causal_attention",
                        flash_attn_fn())
    np.testing.assert_allclose(logits(dec), expanded, atol=TOL)


def test_a_chunk_in_mid_stream_reads_the_cache(toy):
    """``dense_prefill_clone()``: exact at any offset, where the expanded
    form poisons its output."""
    model, variables, w = toy
    seq = _prompts((32,))[0]
    want = np.asarray(reference.forward(w, seq, CFG))
    dec = model.decode_clone()
    _, st = dec.apply(variables, seq[None, :16], mutable=["cache"])
    mid, _ = dec.dense_prefill_clone().apply(
        {**variables, "cache": st["cache"]}, seq[None, 16:],
        mutable=["cache"], logits_all=True)
    np.testing.assert_allclose(np.asarray(mid)[0], want[16:], atol=TOL)
    bad, _ = dec.apply(
        {**variables, "cache": st["cache"]},
        np.zeros((1, 128), np.int32), mutable=["cache"])
    assert np.isnan(np.asarray(bad)).all()


# ---- the engine --------------------------------------------------------

ARMS = {
    "plain": {},
    "paged": {"kv_pages": 12, "page_size": 16},
    "prefix_store": {"prefix_cache_bytes": 1 << 20},
    "chunked_prefill": {"prefill_chunk": 16},
    "speculative": {"speculative": {"k": 2}},
    "paged_prefix_store": {"prefix_cache_bytes": 1 << 20, "kv_pages": 12,
                           "page_size": 16},
}


@pytest.fixture(scope="module")
def generated(toy):
    model, variables, _ = toy
    return [np.asarray(generate(model, variables, p[None],
                                max_new_tokens=8))[0, len(p):]
            for p in _prompts()]


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_every_arm_of_the_engine_serves_generates_tokens(
        toy, generated, arm):
    """Every arm is generic over ``[B, L, ...]`` cache leaves: none
    refuses the latent cache, and each serves byte for byte what
    ``generate()`` does."""
    model, variables, _ = toy
    eng = DecodeEngine(model, variables, slots=2, buckets=[64],
                       prefill_align=16, **ARMS[arm])
    out = list(eng.run([{"prompt": p, "max_new_tokens": 8}
                        for p in _prompts()]))
    for res, want in zip(out, generated):
        assert "error" not in res
        np.testing.assert_array_equal(np.asarray(res["tokens"]), want)
    eng.close()


def test_served_tokens_are_the_references_first_choice(toy):
    model, variables, _ = toy
    prompts = _prompts((13, 40))
    eng = DecodeEngine(model, variables, slots=2, buckets=[64],
                       prefill_align=8)
    out = list(eng.run([{"prompt": p, "max_new_tokens": 10}
                        for p in prompts]))
    seqs = [np.concatenate([p, np.asarray(r["tokens"])])
            for p, r in zip(prompts, out)]
    rows = [len(p) - 1 + np.arange(10) for p in prompts]
    logits = reference.served_logits(CFG, SEED, "float32", seqs, rows,
                                     pad_to=16)
    for lg, res in zip(logits, out):
        toks = np.asarray(res["tokens"])
        assert (lg.max(-1) - lg[np.arange(10), toks]).max() <= TOL
    # the float8 control moves the same logits a thousand times further
    low = reference.served_logits(CFG, SEED, "float32", seqs, rows, "fp8",
                                  pad_to=16)
    assert max(np.abs(a - b).max() for a, b in zip(logits, low)) > 0.02


def test_the_prefix_wire_codec_carries_latent_blocks(toy, generated):
    model, variables, _ = toy
    from distkeras_tpu.serving import pack_kv_blocks, unpack_kv_blocks

    kw = dict(slots=2, buckets=[64], prefill_align=8,
              prefix_cache_bytes=1 << 20)
    prompt = _prompts()[2]          # 30 tokens: three whole blocks
    a = DecodeEngine(model, variables, **kw)
    list(a.run([{"prompt": prompt, "max_new_tokens": 8}]))
    export = a.export_prefix(prompt)
    assert export is not None and len(export["blocks"]) == 3
    b = DecodeEngine(model, variables, **kw)
    got = b.import_prefix(prompt, unpack_kv_blocks(
        b"".join(pack_kv_blocks(export)))["blocks"])
    assert got == 3
    res, = b.run([{"prompt": prompt, "max_new_tokens": 8}])
    np.testing.assert_array_equal(np.asarray(res["tokens"]), generated[2])
    assert b.prefix_stats()["hits"] == 1


def test_expert_load_histogram_and_span_args(toy):
    model, variables, _ = toy
    tel = telemetry.enable()
    try:
        eng = DecodeEngine(model, variables, slots=2, buckets=[32],
                           prefill_align=8)
        assert eng.expert_load() is None
        list(eng.run([{"prompt": p, "max_new_tokens": 4}
                      for p in _prompts((5, 9))]))
        load = eng.expert_load()
        events = tel.tracer.events()
    finally:
        telemetry.disable()
    # one expert layer of eight experts; a prefill routes its 8 or 16
    # padded rows, each of the 3 steps its 2 slots' rows, to 2 experts
    assert load.shape == (1, 8)
    np.testing.assert_array_equal(load.sum(axis=1),
                                  [(8 + 16 + 3 * 2) * 2])
    steps = [e for e in events if e["name"] == "decode_step"]
    assert len(steps) == 3
    for e in steps + [e for e in events if e["name"] == "prefill"]:
        assert 1 <= e["args"]["expert_tokens_max"]
        assert 2 <= e["args"]["experts_touched"] <= 8


def test_a_model_without_routed_experts_has_no_expert_load():
    from distkeras_tpu.models import model_config

    cfg = model_config("transformer_lm", (16,), input_dtype="int32",
                       vocab_size=37, num_layers=1, d_model=32,
                       num_heads=2, max_len=16, dtype="float32")
    model = build_model(cfg)
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    eng = DecodeEngine(model, variables, slots=1)
    list(eng.run([{"prompt": np.arange(4, dtype=np.int32),
                   "max_new_tokens": 3}]))
    assert eng.expert_load() is None


def test_the_decode_contract_is_what_is_asked_of_a_model(toy):
    from distkeras_tpu.models import MLP
    from distkeras_tpu.models.generate import DECODE_CONTRACT

    model, _, _ = toy
    assert all(hasattr(model, a) for a in DECODE_CONTRACT)
    assert _decode_model(model).decode is True
    with pytest.raises(TypeError, match="decode contract"):
        _decode_model(MLP())


# ---- the pieces --------------------------------------------------------

def test_sinkhorn_output_is_doubly_stochastic():
    logits = jnp.clip(10.0 * jax.random.normal(jax.random.key(3),
                                               (64, 4, 4)), -30, 30)
    m = np.asarray(latent_moe.sinkhorn(logits, 20, 1e-6))
    assert (m >= 0).all()
    # the columns were divided last; the rows are as near to 1 as
    # twenty rounds bring them at logits of this spread
    np.testing.assert_allclose(m.sum(axis=-2), 1.0, atol=1e-5)
    assert np.abs(m.sum(axis=-1) - 1.0).max() < 0.2
    gentle = np.asarray(latent_moe.sinkhorn(
        jax.random.normal(jax.random.key(4), (64, 4, 4)), 20, 1e-6))
    np.testing.assert_allclose(gentle.sum(axis=-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(gentle.sum(axis=-2), 1.0, atol=1e-5)


def _loop_over_experts(x, idx, w, w_in, w_out):
    h = w_out.shape[1]
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        for j, e in enumerate(idx[t].astype(int)):
            gu = x[t] @ w_in[e]
            act = gu[:h] / (1 + np.exp(-gu[:h])) * gu[h:]
            y[t] += w[t, j] * (act @ w_out[e])
    return y


def test_dropless_layer_drops_nothing_when_one_expert_gets_every_token():
    """A selection bias of 10 on expert 3: every one of 96 tokens picks
    it, 48 times what a capacity factor of 1.25 over 8 experts would
    let through, and every assignment is computed."""
    t, d, h, e, k = 96, 32, 16, 8, 2
    ks = jax.random.split(jax.random.key(7), 4)
    x = jax.random.normal(ks[0], (t, d))
    router = jax.random.normal(ks[1], (d, e)) * d ** -0.5
    w_in = jax.random.normal(ks[2], (e, d, 2 * h)) * d ** -0.5
    w_out = jax.random.normal(ks[3], (e, h, d)) * h ** -0.5
    bias = jnp.zeros((e,)).at[3].set(10.0)
    idx, w = moe.sigmoid_topk(x, router, bias, k, scale=2.0)
    load = np.asarray(moe.expert_load(idx, e))
    assert load[3] == t and load.sum() == t * k
    # the bias chooses and does not weigh: the weights sum to the scale
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.0, rtol=1e-6)
    got = np.asarray(moe.dropless_experts(x, idx, w, w_in, w_out))
    want = _loop_over_experts(*(np.asarray(a, np.float64) for a in
                                (x, idx, w, w_in, w_out)))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_grouped_matmul_kernel_is_ragged_dot():
    """The TPU's path (the megablox kernel, here in interpret mode) against
    ``jax.lax.ragged_dot``, which the CPU runs: an empty group, groups
    that straddle the 128-row tiles, rows that belong to no group."""
    m, k, n = 200, 128, 256
    ks = jax.random.split(jax.random.key(9), 2)
    rows = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (4, k, n)) * 0.1
    sizes = jnp.array([37, 0, 120, 30], jnp.int32)
    got = moe._gmm(rows, w, sizes, jnp.float32, moe._gmm_tiling(k, n, 4),
                   interpret=True)
    want = jax.lax.ragged_dot(rows, w, sizes)
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got)[:187],
                               np.asarray(want)[:187], atol=1e-5)
    # rows in tiles of 128, the whole contraction, 4 MiB of an expert
    assert moe._gmm_tiling(3584, 2048, 2) == (128, 3584, 512)
    assert moe._gmm_tiling(1024, 3584, 2) == (128, 1024, 1792)
    assert moe._gmm_tiling(64, 64, 4) is None       # toy widths: ragged_dot


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Four chips hold experts {0..15}, {16..31}, {32..47}, {48..63} of a
    64-expert layer (toy widths).  Each routes over all 64 and computes
    its own experts' part plus the shared expert, which every chip
    computes alike and is counted once; together they are the uncut
    reference's layer."""
    cfg = {**CFG, "n_routed_experts": 64, "num_experts_per_tok": 4,
           "first_k_dense_replace": 0, "num_hidden_layers": 1}
    key = weights.seed_key(11)
    whole = weights.layer_weights(cfg, key, 0, jnp.float32, False)
    x = jax.random.normal(jax.random.key(5), (40, 64))
    f32 = lambda w: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), w)
    want = np.asarray(reference.experts(x, f32(whole), cfg, "f32")[0])
    shared = np.asarray(reference._swiglu(
        x, whole["ws_gate"], whole["ws_up"], whole["ws_down"], "f32"))
    total = np.zeros_like(want)
    for first in (0, 16, 32, 48):
        share_cfg = {**cfg, "experts_held": [first, 16]}
        w = weights.layer_weights(share_cfg, key, 0, jnp.float32, False)
        np.testing.assert_array_equal(
            np.asarray(w["we_in"]),
            np.asarray(whole["we_in"][first:first + 16]))
        layer = latent_moe.DroplessMoE(64, 4, 32, 32, 2.0, True,
                                       jnp.float32, (first, 16))
        params = {"router": w["router"], "bias": w["e_bias"],
                  "w_in": w["we_in"], "w_out": w["we_down"],
                  "shared": {n: {"kernel": w[f"ws_{n}"]}
                             for n in ("gate", "up", "down")}}
        part = np.asarray(layer.apply({"params": params}, x[None],
                                      x[None]))[0]
        # the reference given the same share computes the same part
        np.testing.assert_allclose(
            part, np.asarray(reference.experts(x, f32(w), share_cfg,
                                               "f32")[0]), atol=TOL)
        total += part - shared
    np.testing.assert_allclose(total + shared, want, atol=TOL)
    assert np.abs(want - shared).max() > 0.01   # the routed part counts


def test_the_new_scopes_are_on_the_lowered_step(toy):
    model, variables, _ = toy
    dec = model.decode_clone()
    cache = jax.eval_shape(
        lambda v: dec.apply(v, jnp.zeros((2, 1), jnp.int32),
                            mutable=["cache"]), variables)[1]["cache"]

    def step(v, cache, tok, pos):
        return dec.apply({**v, "cache": cache}, tok, slot_pos=pos,
                         mutable=["cache"])

    text = jax.jit(step).lower(
        variables, cache, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    for scope in ("mla_decode", "latent_write", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_combine",
                  "moe_shared", "hc_mix", "mlp"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope

    def prefill(v, tokens):
        return dec.apply(v, tokens, mutable=["cache"])

    text = jax.jit(prefill).lower(
        variables, jnp.zeros((1, 128), jnp.int32)).as_text(debug_info=True)
    assert "mla_prefill/" in text
