"""The KV cache is declared in the order the decode step computes in,
``[B, L, KVH, D]`` (PR 27), so that no program of a
``serving.DecodeEngine`` that is handed a pool copies a leaf of it
whole; the tokens are what they were.

On the CPU the declared shapes, the counts of compilations, the tokens
and, program by program, the whole-leaf copies of the compiled HLO can
be checked; what the chip's compiler makes of the same programs is
checked by compiling for a described v5e, which the last tests of this
file do, and no other file (one process holds the TPU's compiler)."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import layouts, paging, telemetry
from distkeras_tpu.models import ModelSpec, generate, model_config
from distkeras_tpu.models.generate import _decode_model, decode_step
from distkeras_tpu.serving import DecodeEngine

jax.config.update("jax_platforms", "cpu")

MAXLEN, VOCAB, ALIGN = 32, 37, 4
SDS = jax.ShapeDtypeStruct

MODELS = {
    "mha": {},
    "int8": {"kv_cache_dtype": "int8"},
    "gqa": {"num_heads": 4, "num_kv_heads": 2},
    "gqa_int8": {"num_heads": 4, "num_kv_heads": 2,
                 "kv_cache_dtype": "int8"},
}


def _model(num_layers=2, **kw):
    kw = {"num_heads": 2, **kw}
    spec = model_config("transformer_lm", (MAXLEN,), input_dtype="int32",
                        vocab_size=VOCAB, num_layers=num_layers,
                        d_model=32, max_len=MAXLEN, dtype="float32", **kw)
    model = ModelSpec.from_config(spec).build()
    return model, model.init(jax.random.key(0),
                             jnp.zeros((2, MAXLEN), jnp.int32))


def _requests(lengths=(5, 9, 3, 7, 5, 11), budgets=(4, 7, 3, 6, 5, 8)):
    rng = np.random.default_rng(3)
    return [{"prompt": rng.integers(0, VOCAB, (t,)).astype(np.int32),
             "max_new_tokens": n, "i": i}
            for i, (t, n) in enumerate(zip(lengths, budgets))]


def _kv_leaves(tree):
    return [l for l in jax.tree_util.tree_leaves(tree) if l.shape]


# ---- the counter of whole-leaf copies ---------------------------------

LEAF = "bf16[32,16,512,128]"
TILED = "{3,1,2,0:T(8,128)(2,1)}"
IN_VMEM = "{3,1,2,0:T(8,128)(2,1)S(1)}"
ROW_MAJOR = "{3,2,1,0:T(8,128)(2,1)}"


@pytest.mark.parametrize("line,relayouts,moves", [
    (f"  %copy.3 = {LEAF}{TILED} copy(%p), metadata={{}}", 1, 0),
    (f"  ROOT %copy.4 = {LEAF}{{3,2,1,0}} copy(%param_0.5)", 1, 0),
    (f"  %copy-start.5 = ({LEAF}{IN_VMEM}, {LEAF}{ROW_MAJOR}, "
     "u32[]{:S(2)}) copy-start(%c)", 1, 0),
    (f"  %copy-start.3 = ({LEAF}{IN_VMEM}, {LEAF}{TILED}, "
     "u32[]{:S(2)}) copy-start(%c)", 0, 1),
    (f"  %copy-start.4 = ({LEAF}{TILED}, {LEAF}{IN_VMEM}, "
     "u32[]{:S(2)}) copy-start(%fusion.2)", 0, 1),
    (f"  %copy-done.3 = {LEAF}{IN_VMEM} copy-done(%copy-start.3)", 0, 0),
    ("  %copy.9 = bf16[32,16,128]{2,1,0} copy(%q)", 0, 0),
    ("  %copy.8 = s32[]{:T(128)} copy(%cache_index)", 0, 0),
    (f"  %fusion.2 = {LEAF}{IN_VMEM} fusion(%copy-done.3, %copy.13), "
     "kind=kCustom, calls=%fused_computation.2", 0, 0),
    (f"  %t = {LEAF}{TILED} transpose(%copy.1), dimensions={{0,2,1,3}}",
     0, 0),
])
def test_whole_leaf_copies_reads_one_line(line, relayouts, moves):
    shapes = {"k": SDS((32, 16, 512, 128), jnp.bfloat16),
              "index": SDS((), jnp.int32)}
    assert layouts.whole_leaf_copies(line, shapes) == relayouts
    assert layouts.whole_leaf_copies(line, shapes, moves=True) == moves


def test_whole_leaf_copies_of_a_compiled_program():
    """A program that must keep its argument makes a second instance of
    it; one that is handed it, and only reads, makes none."""
    x = jnp.zeros((4, 2, 16, 8))

    def bump(x):
        return x.at[0, 0, 0, 0].set(1.0)

    kept = jax.jit(bump).lower(x).compile().as_text()
    assert layouts.whole_leaf_copies(kept, [x]) == 1
    read = jax.jit(lambda x: x.sum()).lower(x).compile().as_text()
    assert layouts.whole_leaf_copies(read, [x]) == 0


# ---- the declared order -----------------------------------------------

@pytest.mark.parametrize("name", sorted(MODELS))
def test_cache_is_declared_position_outside_heads(name):
    kw = {"num_heads": 2, **MODELS[name]}
    kvh = kw.get("num_kv_heads", kw["num_heads"])
    model, variables = _model(**MODELS[name])
    dec = _decode_model(model)
    cache = jax.eval_shape(
        lambda v: dec.apply(v, jnp.zeros((3, 1), jnp.int32),
                            mutable=["cache"]),
        {"params": variables["params"]})[1]["cache"]
    att = cache["Block_0"]["SelfAttention_0"]
    d = 32 // kw["num_heads"]
    assert att["cached_key"].shape == (3, MAXLEN, kvh, d)
    assert att["cached_value"].shape == (3, MAXLEN, kvh, d)
    if "kv_cache_dtype" in kw:
        assert att["key_scale"].shape == (3, MAXLEN, kvh, 1)
        assert att["value_scale"].shape == (3, MAXLEN, kvh, 1)
        assert att["cached_key"].dtype == jnp.int8
    # the page pool and the prefix store's blocks follow it
    pages = paging.build_pool(cache, 5, ALIGN)
    assert pages[0].shape == (6, ALIGN, kvh, d)


def test_prefix_blocks_follow_the_declared_order():
    model, variables = _model()
    eng = DecodeEngine(model, variables, slots=2, buckets=[32],
                       prefill_align=ALIGN, prefix_cache_bytes=1 << 20)
    reqs = _requests((9, 9), (3, 3))
    list(eng.run(reqs[:1]))
    export = eng.export_prefix(reqs[0]["prompt"])
    assert export["n_blocks"] == 2
    assert {b.shape for blk in export["blocks"] for b in blk} \
        == {(1, ALIGN, 2, 16)}
    eng.close()


# ---- the engine's pools -----------------------------------------------

@pytest.fixture(scope="module", params=[True, False],
                ids=["donated", "kept"])
def engine(request):
    """An engine with every kind of program of an envelope pool that
    takes the cache: step, one-shot prefill, chunk prefill, prefix copy
    and extract; run once, so that each has been traced."""
    model, variables = _model()
    tel = telemetry.enable()
    try:
        eng = DecodeEngine(model, variables, slots=3, buckets=[16, 32],
                           prefill_align=ALIGN, donate=request.param,
                           prefix_cache_bytes=1 << 20, prefill_chunk=8)
        instants = [e for e in tel.tracer.events()
                    if e.get("name") == "pool_layout"]
    finally:
        telemetry.disable()
    yield eng, instants
    eng.close()


def test_pool_layout_is_recorded_once_a_pool(engine):
    eng, instants = engine
    assert sorted(e["args"]["bucket"] for e in instants) == [16, 32]
    for e, pool in zip(sorted(instants,
                              key=lambda e: e["args"]["bucket"]),
                       eng._pools):
        assert e["args"]["layout"] == layouts.describe(pool.cache)
        assert e["args"]["layout"].startswith(
            f"float32[3, {pool.env}, 2, 16] major_to_minor=(0, 1, 2, 3)")


@pytest.mark.parametrize("program", ["step", "prefill", "chunk",
                                     "prefix_copy", "prefix_extract"])
def test_a_program_handed_the_pool_copies_no_leaf_of_it(engine, program):
    """Handed the pool, a program works on it in place; made to keep
    its argument, it makes one second instance of each leaf it writes,
    and no more (the extract writes none)."""
    eng, _ = engine
    pool = eng._pools[0]
    key = eng._key
    segs = [SDS((1, ALIGN) + l.shape[2:], l.dtype)
            for l in _kv_leaves(pool.cache_tmpl)]
    lowered = {
        "step": lambda: pool.step_fn.lower(
            eng.variables, pool.cache, pool.state, key),
        "prefill": lambda: pool.prefill_fn.lower(
            eng.variables, pool.cache, pool.state,
            SDS((1, 8), jnp.int32), 0, 4, 3, -1, key),
        "chunk": lambda: pool.chunk_fn.lower(
            eng.variables, pool.cache, pool.state,
            SDS((1, 8), jnp.int32), 0, 0, 3, True, 3, -1, key),
        "prefix_copy": lambda: pool.copy_fn.lower(
            pool.cache, segs, 0, 0),
        "prefix_extract": lambda: pool.extract_fn.lower(
            pool.cache, 0, 0),
    }[program]
    text = lowered().compile().as_text()
    kept = 0 if eng._donate or program == "prefix_extract" \
        else len(segs)
    assert layouts.whole_leaf_copies(text, pool.cache_tmpl) == kept


def test_pool_serves_through_every_program(engine):
    eng, _ = engine
    reqs = _requests()
    # a shared prefix, so that the copy and extract programs run too
    reqs[1]["prompt"][:8] = reqs[5]["prompt"][:8]
    got = {r["i"]: r for r in eng.run(reqs)}
    assert not [r for r in got.values() if "error" in r]
    list(eng.run(reqs))
    assert eng.prefix_stats()["hits"] > 0
    counts = eng.compile_counts
    assert counts["step", 16] == 1 and counts["step", 32] == 1
    assert set(counts.values()) == {1}, counts


def test_pool_report_counts_without_tracing_anything():
    model, variables = _model()
    eng = DecodeEngine(model, variables, slots=2, buckets=[16, 32],
                       prefill_align=ALIGN, donate=True)
    list(eng.run(_requests((5, 7), (3, 3))))
    counts = eng.compile_counts
    small, large = eng.pool_report()
    assert small["bucket"] == 16 and small["donated"] is True
    assert small["layout"] == layouts.describe(eng._pools[0].cache)
    assert small["programs"] == {
        "step": {"relayouts": 0, "moves": 0},
        "prefill_8": {"relayouts": 0, "moves": 0}}
    # the 32 pool saw no traffic: nothing of it is traced for a report
    assert large["bucket"] == 32 and large["programs"] == {}
    assert eng.compile_counts == counts
    eng.close()


@pytest.mark.parametrize("donate", [True, False], ids=["donated", "kept"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_tokens_are_generates_whatever_the_order(name, donate):
    """Greedy tokens of a slot-mode run equal ``generate()``'s, request
    by request, as the parent's did: bf16-class and int8 caches, all
    heads and grouped, handed over and kept."""
    model, variables = _model(**MODELS[name])
    eng = DecodeEngine(model, variables, slots=3, buckets=[16, 32],
                       prefill_align=ALIGN, steps_per_sync=2,
                       donate=donate)
    reqs = _requests()
    got = {r["i"]: r["tokens"] for r in eng.run(reqs)}
    for r in reqs:
        want = np.asarray(generate(
            model, variables, r["prompt"][None, :],
            max_new_tokens=r["max_new_tokens"]))[0, len(r["prompt"]):]
        assert np.array_equal(got[r["i"]], want), (name, r["i"])
    assert eng.compile_counts["step", 16] == 1
    eng.close()


_BACKEND_COMPILES = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **_: _BACKEND_COMPILES.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)


@pytest.mark.parametrize("arm", ["plain", "segmented", "draft", "paged"])
@pytest.mark.parametrize("donate", [True, False], ids=["donated", "kept"])
def test_each_program_is_compiled_once_not_once_a_state(arm, donate):
    """``_traces`` counts traces; ``jit`` can compile one trace twice,
    for arguments that are committed to a device and for ones that are
    not.  This PR's first build (the pool in a layout read from the
    compiler) did on the chip: its step program handed the slot state
    back uncommitted and its prefills committed, so each prefill met
    both, five compilations inside the benchmark's window.  As the
    benchmark does: every program once, a request at a time, then the
    whole load, which must compile nothing."""
    model, variables = _model(num_layers=1)
    kw = {"segmented": {"prefix_cache_bytes": 1 << 20, "prefill_chunk": 8},
          "draft": {"speculative": {
              "proposer": "draft", "k": 3, "draft_model": model,
              "draft_variables": variables}},
          "paged": {"kv_pages": 24}}.get(arm, {})
    eng = DecodeEngine(model, variables, slots=3, buckets=[16, 32],
                       prefill_align=ALIGN, donate=donate, **kw)
    reqs = _requests((5, 9, 5, 12, 3, 14), (4, 4, 4, 4, 4, 12))
    for r in reqs + reqs:  # the second time round, prefixes hit
        list(eng.run([r]))
    counts = eng.compile_counts
    before = len(_BACKEND_COMPILES)
    for _ in range(2):
        list(eng.run(reqs))
    assert len(_BACKEND_COMPILES) == before
    assert eng.compile_counts == counts
    eng.close()


@pytest.mark.parametrize("proposer", ["ngram", "draft"])
def test_speculative_programs_copy_no_leaf(proposer):
    """The verify program and the draft pool's batched propose, handed
    their pools, work on them in place; the tokens are generate()'s."""
    model, variables = _model()
    spec = {"proposer": proposer, "k": 3}
    if proposer == "draft":
        spec.update(draft_model=model, draft_variables=variables)
    else:
        spec["ngram"] = 2
    eng = DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                       prefill_align=ALIGN, speculative=spec, donate=True)
    reqs = _requests((5, 9, 6), (6, 6, 6))
    got = {r["i"]: r["tokens"] for r in eng.run(reqs)}
    for r in reqs:
        want = np.asarray(generate(
            model, variables, r["prompt"][None, :],
            max_new_tokens=r["max_new_tokens"]))[0, len(r["prompt"]):]
        assert np.array_equal(got[r["i"]], want)
    (pool,) = eng._pools
    verify = pool.spec["verify_fns"][4].lower(
        eng.variables, pool.cache, SDS((1, 4), jnp.int32), 0, 0
    ).compile().as_text()
    assert layouts.whole_leaf_copies(verify, pool.cache_tmpl) == 0
    if proposer == "draft":
        d = pool.spec
        propose = d["propose_fn"].lower(
            eng._spec["draft_variables"], d["cache"], d["dtok"],
            d["dpos"], np.ones((2,), bool)).compile().as_text()
        assert layouts.whole_leaf_copies(propose, d["cache"]) == 0
    eng.close()


def test_paged_engine_has_no_envelope_pool_to_report():
    model, variables = _model()
    eng = DecodeEngine(model, variables, slots=2, buckets=[16],
                       prefill_align=ALIGN, kv_pages=16)
    assert eng._pools[0].cache is None
    assert eng.pool_report() == []
    eng.close()


# ---- compiled for a described v5e, no chip ----------------------------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def v5e_pool(one_chip):
    """One layer at the serving cells' widths and their 512 pool
    (32 slots x 512 x 16 heads x 128, bfloat16), as shapes on the
    described chip: ``(dec, params, cache, on_chip)``."""
    cfg = model_config("transformer_lm", (2048,), input_dtype="int32",
                       vocab_size=50257, num_layers=1, d_model=2048,
                       num_heads=16, max_len=2048, dtype="bfloat16")
    base = _decode_model(cfg)
    dec = base.clone(cache_envelope=512)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: SDS(x.shape, x.dtype, sharding=one_chip), tree)

    params = {"params": jax.eval_shape(
        lambda: base.clone(decode=False).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]}
    cache = jax.eval_shape(
        lambda v: dec.apply(v, jnp.zeros((32, 1), jnp.int32),
                            mutable=["cache"]), params)[1]["cache"]
    return dec, on_chip(params), on_chip(cache), on_chip


def test_v5e_step_copies_no_leaf(v5e_pool):
    dec, params, cache, on_chip = v5e_pool

    def step(params, cache, tok, pos):
        return decode_step(dec, params, cache, tok, slot_pos=pos,
                           temperature=0.0, top_k=None, top_p=None,
                           rng=None)

    tok = on_chip(SDS((32,), jnp.int32))
    text = jax.jit(step, donate_argnums=1).lower(
        params, cache, tok, tok).compile().as_text()
    assert _kv_leaves(cache)[0].shape == (32, 512, 16, 128)
    assert layouts.whole_leaf_copies(text, cache) == 0


def test_v5e_prefill_installs_one_slot_in_place(v5e_pool):
    dec, params, cache, on_chip = v5e_pool

    def prefill(params, cache, prompt, slot):
        _, st = dec.apply(params, prompt, mutable=["cache"],
                          last_index=3)
        return jax.tree_util.tree_map(
            lambda c, n: c if not n.shape else
            jax.lax.dynamic_update_slice(
                c, n, (slot,) + (0,) * (n.ndim - 1)),
            cache, st["cache"])

    text = jax.jit(prefill, donate_argnums=1).lower(
        params, cache, on_chip(SDS((1, 512), jnp.int32)),
        on_chip(SDS((), jnp.int32))).compile().as_text()
    assert layouts.whole_leaf_copies(text, cache) == 0


def test_v5e_heads_outside_position_is_relaid_out_every_step(one_chip):
    """What the parent paid, on the same write and the same two
    contractions with the cache declared ``[B, KVH, L, D]``: the per-row
    scatter asks for position outside heads, so each of K and V is
    copied whole on the way in and on the way out."""
    b, h, length, d = 32, 16, 512, 128

    def step(ck, cv, q, k, v, pos):
        rows = jnp.arange(b)
        ck = ck.at[rows, :, pos, :].set(k)
        cv = cv.at[rows, :, pos, :].set(v)
        logits = jnp.einsum("bhd,bhkd->bhk", q, ck)
        probs = jax.nn.softmax(logits.astype(jnp.float32),
                               axis=-1).astype(q.dtype)
        return ck, cv, jnp.einsum("bhk,bhkd->bhd", probs, cv)

    pool = SDS((b, h, length, d), jnp.bfloat16, sharding=one_chip)
    row = SDS((b, h, d), jnp.bfloat16, sharding=one_chip)
    pos = SDS((b,), jnp.int32, sharding=one_chip)
    text = jax.jit(step, donate_argnums=(0, 1)).lower(
        pool, pool, row, row, row, pos).compile().as_text()
    assert layouts.whole_leaf_copies(text, [pool]) == 4


# ---- the latent cache, compiled for the described v5e -------------------

@pytest.fixture(scope="module")
def v5e_latent_pool(one_chip):
    """One dense and one expert layer at the published widths of the
    benchmark's latent-attention configuration (d 3584, 32 heads, latent
    512 + 64, 64 experts of 1024), a pool of 16 slots x 1024 tokens, as
    shapes on the described chip: ``(dec, params, cache, on_chip)``."""
    cfg = model_config(
        "latent_moe_lm", (1024,), input_dtype="int32", vocab_size=8192,
        num_layers=2, d_model=3584, num_heads=32, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, dense_width=9216, first_dense_layers=1,
        num_experts=64, experts_per_token=4, expert_width=1024,
        routed_scaling=2.0, rope_factor=64.0, rope_mscale=1.0,
        rope_mscale_all_dim=1.0, max_len=1024, dtype="bfloat16")
    dec = _decode_model(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: SDS(x.shape, x.dtype, sharding=one_chip), tree)

    params = {"params": jax.eval_shape(
        lambda: dec.clone(decode=False).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]}
    cache = jax.eval_shape(
        lambda v: dec.apply(v, jnp.zeros((16, 1), jnp.int32),
                            mutable=["cache"]), params)[1]["cache"]
    return dec, on_chip(params), on_chip(cache), on_chip


@pytest.fixture()
def traced_for_the_chip(monkeypatch):
    """The code that asks ``jax.devices()[0]`` where it runs (the flash
    kernels' ``interpret``, the grouped product's kernel) sees the CPU
    here; while a test traces a program for the described chip it is told
    the chip."""
    import types

    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [chip])


def test_v5e_latent_step_copies_no_leaf(v5e_latent_pool,
                                        traced_for_the_chip):
    """The latent leaf is 512 + 64 wide and padded to 640: row-major is
    then the TPU's default layout of it, and the absorbed step (a per-row
    scatter, two contractions over the leaf as it lies) copies none."""
    dec, params, cache, on_chip = v5e_latent_pool

    def step(params, cache, tok, pos):
        return decode_step(dec, params, cache, tok, slot_pos=pos,
                           temperature=0.0, top_k=None, top_p=None,
                           rng=None)

    tok = on_chip(SDS((16,), jnp.int32))
    text = jax.jit(step, donate_argnums=1).lower(
        params, cache, tok, tok).compile().as_text()
    assert _kv_leaves(cache)[0].shape == (16, 1024, 640)
    assert layouts.whole_leaf_copies(text, cache) == 0
    # the grouped products are the megablox kernel, under its scope
    assert text.count("moe_experts/jit(gmm)/pallas_call") >= 2
    assert "ragged-dot" not in text


def test_v5e_latent_prefill_runs_the_flash_kernel_at_192_and_128(
        v5e_latent_pool, traced_for_the_chip):
    """The expanded prefill: q and k 192 wide, v 128, through the forward
    kernel as it is (nothing padded), installed into one slot in place."""
    dec, params, cache, on_chip = v5e_latent_pool

    def prefill(params, cache, prompt, slot):
        _, st = dec.apply(params, prompt, mutable=["cache"],
                          last_index=3)
        return jax.tree_util.tree_map(
            lambda c, n: c if not n.shape else
            jax.lax.dynamic_update_slice(
                c, n, (slot,) + (0,) * (n.ndim - 1)),
            cache, st["cache"])

    text = jax.jit(prefill, donate_argnums=1).lower(
        params, cache, on_chip(SDS((1, 512), jnp.int32)),
        on_chip(SDS((), jnp.int32))).compile().as_text()
    assert layouts.whole_leaf_copies(text, cache) == 0
    assert "flash_fwd" in text


def test_lane_padded_is_the_next_multiple_of_128_past_one_tile():
    """What the latent leaf is declared at (576 -> 640); a leaf inside one
    tile of lanes (the toys' 24) and a multiple of 128 stay as they are."""
    assert [layouts.lane_padded(w) for w in (24, 128, 576, 640, 641)] == \
        [24, 128, 640, 640, 768]


def test_v5e_a_leaf_576_wide_is_relaid_out_every_step(one_chip):
    """Why the pad: the same write and contractions on an unpadded
    ``[B, L, 576]`` leaf.  576 is no multiple of the 128 lanes, the TPU's
    default layout of the leaf puts positions last, and the step copies it
    whole on the way in and on the way out."""
    b, length, width, h = 16, 1024, 576, 32

    def step(cache, q, new, pos):
        cache = cache.at[jnp.arange(b), pos].set(new)
        logits = jnp.einsum("bhc,blc->bhl", q, cache)
        probs = jax.nn.softmax(logits.astype(jnp.float32),
                               axis=-1).astype(q.dtype)
        return cache, jnp.einsum("bhl,blc->bhc", probs, cache)

    pool = SDS((b, length, width), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(step, donate_argnums=0).lower(
        pool, SDS((b, h, width), jnp.bfloat16, sharding=one_chip),
        SDS((b, width), jnp.bfloat16, sharding=one_chip),
        SDS((b,), jnp.int32, sharding=one_chip)).compile().as_text()
    assert layouts.whole_leaf_copies(text, [pool]) == 2


# ---- the decode kernel in the step programs (PR 30) ---------------------

def _kernel_step(dec, params, cache, on_chip, slots):
    """The slot-mode step as the engine's ``step_core`` calls it, with
    the rows' live lengths beside their positions, compiled for the
    described chip: the optimised HLO."""
    def step(params, cache, tok, pos, lengths):
        return decode_step(dec, params, cache, tok, slot_pos=pos,
                           lengths=lengths, temperature=0.0, top_k=None,
                           top_p=None, rng=None)

    tok = on_chip(SDS((slots,), jnp.int32))
    return jax.jit(step, donate_argnums=1).lower(
        params, cache, tok, tok, tok).compile().as_text()


def _under_scope(text, scope):
    """The optimised HLO's instructions whose ``op_name`` holds
    ``scope``, as ``(line, what comes after the scope)``."""
    out = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and f"/{scope}/" in m.group(1):
            out.append((line, m.group(1).split(f"/{scope}/", 1)[1]))
    return out


def _computations_over(text, leaf):
    """``(name, result)`` of every fused computation of the optimised HLO
    that takes an array of a cache leaf's shape: where a contraction over
    ``[B, L, ...]`` would show, with a result smaller than the leaf."""
    dims = ",".join(map(str, leaf.shape))
    sig = re.compile(rf"^%(\S+) \((.*\[{dims}\].*)\) -> (\S+) {{$")
    return [(m.group(1), m.group(3)) for m in
            map(sig.match, map(str.strip, text.splitlines())) if m]


def test_v5e_kernel_reads_a_cgpt_pool_in_place(one_chip,
                                               traced_for_the_chip):
    """The kernel at the ``cgpt`` cells' 512 pool (32 slots x 512 x 16
    heads of 128): after the step's per-row scatter into the donated K
    and V the custom call takes both leaves as they lie, ``[L, 16, 128]``
    folded to ``[L * 16, 128]`` without a copy."""
    from distkeras_tpu.ops import attention

    def step(ck, cv, q, k, v, pos, lengths):
        rows = jnp.arange(32)
        ck, cv = ck.at[rows, pos].set(k), cv.at[rows, pos].set(v)
        assert attention.decode_attention_applies(q, ck, cv, lengths)
        return ck, cv, attention.decode_attention(
            q, ck, cv, lengths, scale=128 ** -0.5)

    pool = SDS((32, 512, 16, 128), jnp.bfloat16, sharding=one_chip)
    row = SDS((32, 16, 128), jnp.bfloat16, sharding=one_chip)
    ints = SDS((32,), jnp.int32, sharding=one_chip)
    text = jax.jit(step, donate_argnums=(0, 1)).lower(
        pool, pool, SDS((32, 16, 1, 128), jnp.bfloat16, sharding=one_chip),
        row, row, ints, ints).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert layouts.whole_leaf_copies(text, [pool, pool]) == 0
    over = _computations_over(text, pool)
    assert [r for _, r in over] == ["bf16[32,512,16,128]"] * 2, over
    assert "input_output_alias" in text


def test_v5e_step_reads_its_cache_through_the_kernel(v5e_pool,
                                                     traced_for_the_chip):
    """The real ``cgpt`` step, handed its rows' lengths as ``step_core``
    hands them: one kernel call a layer under ``attn_decode``, no
    contraction under that scope or over a ``[B, L, 16, 128]`` leaf
    outside the kernel (the scatter's two fusions alone take one), no
    copy, the pool donated in place."""
    dec, params, cache, on_chip = v5e_pool
    text = _kernel_step(dec, params, cache, on_chip, 32)
    scoped = _under_scope(text, "attn_decode")
    calls = [tail for line, tail in scoped if "custom-call(" in line]
    assert len(calls) == 1, calls
    assert not any("dot_general" in tail for _, tail in scoped)
    assert layouts.whole_leaf_copies(text, cache) == 0
    over = _computations_over(text, _kv_leaves(cache)[0])
    assert [r for _, r in over] == ["bf16[32,512,16,128]"] * 2, over
    assert "input_output_alias" in text


def test_v5e_latent_step_reads_its_cache_through_the_kernel(
        v5e_latent_pool, traced_for_the_chip):
    """The same for the latent step: one kernel call a layer under
    ``mla_decode`` (the scope the benchmark's readers find its time by),
    fed the ``[B, L, 640]`` leaf without an axis of one head, no copy, no
    contraction over the leaf outside the kernel."""
    dec, params, cache, on_chip = v5e_latent_pool
    text = _kernel_step(dec, params, cache, on_chip, 16)
    calls = [tail for line, tail in _under_scope(text, "mla_decode")
             if "custom-call(" in line]
    assert len(calls) == 2, calls
    assert layouts.whole_leaf_copies(text, cache) == 0
    assert not any("dot_general" in tail
                   for _, tail in _under_scope(text, "mla_decode"))
    over = _computations_over(text, _kv_leaves(cache)[0])
    assert [r for _, r in over] == ["bf16[16,1024,640]"] * 2, over
    assert text.count("moe_experts/jit(gmm)/pallas_call") >= 2


# ---- the decode kernel under the engine, interpreted on the CPU ---------

# a latent leaf of 128 + 16 columns padded to 256 under four heads, the
# values its first 128 columns: the smallest the kernel's rule takes
KERNEL_TOY = dict(
    num_layers=2, d_model=64, num_heads=4, q_lora_rank=32,
    kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=16,
    v_head_dim=16, dense_width=64, first_dense_layers=1, num_experts=4,
    experts_per_token=2, expert_width=32, hc_sinkhorn_iters=4)


def _gpt2_toy(**kw):
    """A ``TransformerLM`` of two layers, by default one the kernel's rule
    takes: float32 (a sublane tile of 8), 8 heads of 128."""
    kw = {"d_model": 1024, "num_heads": 8, "dtype": "float32", **kw}
    spec = model_config("transformer_lm", (256,), input_dtype="int32",
                        vocab_size=VOCAB, num_layers=2, max_len=256, **kw)
    model = ModelSpec.from_config(spec).build()
    return model, model.init(jax.random.key(0),
                             jnp.zeros((1, 8), jnp.int32))


@pytest.fixture(scope="module")
def kernel_toys():
    """One toy of each served family that the kernel's rule takes."""
    spec = model_config("latent_moe_lm", (256,), input_dtype="int32",
                        vocab_size=VOCAB, max_len=256, dtype="float32",
                        **KERNEL_TOY)
    model = ModelSpec.from_config(spec).build()
    return {"latent": (model, model.init(jax.random.key(1),
                                         jnp.zeros((1, 8), jnp.int32))),
            "gpt2": _gpt2_toy()}


def _served_by_the_kernel(monkeypatch, model, variables, reqs, **kw):
    """``reqs`` through an engine whose step programs are traced as on a
    TPU (the kernel itself interpreted, this being the CPU): the tokens
    by request, and the ``decode_step`` spans."""
    from distkeras_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    tel = telemetry.enable()
    try:
        eng = DecodeEngine(model, variables, slots=3, buckets=[128, 256],
                           prefill_align=4, **kw)
        out = {r["i"]: r["tokens"] for r in eng.run(reqs)}
        eng.close()
        steps = [e["args"] for e in tel.tracer.events()
                 if e["name"] == "decode_step"]
    finally:
        telemetry.disable()
    return out, steps


def _assert_generates_tokens(model, variables, reqs, out):
    for r in reqs:
        want = np.asarray(generate(
            model, variables, r["prompt"][None],
            max_new_tokens=r["max_new_tokens"]))[0, len(r["prompt"]):]
        np.testing.assert_array_equal(out[r["i"]], want)


@pytest.mark.parametrize("steps_per_sync", [1, 3])
@pytest.mark.parametrize("family", ["latent", "gpt2"])
def test_the_kernel_serves_generates_tokens(kernel_toys, monkeypatch,
                                            family, steps_per_sync):
    """Both families at toy size (``LatentAttention`` and
    ``SelfAttention`` call the one kernel), budgets that leave finished
    slots' dead rows beside live ones in most steps (and a request of the
    larger pool): the tokens are ``generate()``'s, the steps' spans count
    the rows the kernel's grid read."""
    model, variables = kernel_toys[family]
    reqs = _requests(lengths=(5, 9, 3, 120, 7, 5, 11),
                     budgets=(4, 9, 2, 12, 6, 3, 8))
    out, steps = _served_by_the_kernel(
        monkeypatch, model, variables, reqs, steps_per_sync=steps_per_sync)
    _assert_generates_tokens(model, variables, reqs, out)
    assert {a["bucket"] for a in steps} == {128, 256}
    for args in steps:
        # whole blocks of 128 positions, at most the live slots' envelopes
        assert args["envelope_rows"] == 3 * args["bucket"] * steps_per_sync
        assert 0 < args["attended_rows"] <= \
            args["live"] * args["bucket"] * steps_per_sync
        assert args["attended_rows"] % 128 == 0


@pytest.mark.parametrize("family", ["latent", "gpt2"])
def test_a_done_slots_row_changes_no_live_slots_tokens(kernel_toys,
                                                       monkeypatch, family):
    """One request served alone, then beside two that finish long before
    it (their slots' rows are then attended over no position): the same
    tokens."""
    model, variables = kernel_toys[family]
    long, *short = _requests(lengths=(9, 5, 7), budgets=(14, 2, 3))
    alone, _ = _served_by_the_kernel(monkeypatch, model, variables, [long])
    beside, _ = _served_by_the_kernel(monkeypatch, model, variables,
                                      [long] + short)
    np.testing.assert_array_equal(alone[long["i"]], beside[long["i"]])


def test_the_kernels_call_is_traced_once_a_program(monkeypatch):
    """A trace of the kernel's call costs the host a tenth of a second
    (0.1 s x 24 layers x 3 programs were 14 % of a serving cell's
    set-up): the layers of a step program share one, and a program
    traced later (another pool, another engine) gets its own, so that
    each is recorded (``attended_rows > 0`` in both)."""
    from distkeras_tpu.ops import attention

    calls = []
    real = attention.decode_attention

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(attention, "decode_attention", counted)
    model, variables = _gpt2_toy()                  # two layers
    for reqs in (_requests(lengths=(5,), budgets=(3,)),
                 _requests(lengths=(5, 120), budgets=(3, 12))):
        before = len(calls)
        _, steps = _served_by_the_kernel(monkeypatch, model, variables,
                                         reqs)
        pools = {a["bucket"] for a in steps}
        assert len(calls) - before == len(pools) == len(reqs)
        assert all(a["attended_rows"] > 0 for a in steps)


@pytest.mark.parametrize("refused", [
    {"kv_cache_dtype": "int8"},                  # no floating cache
    {"dtype": "bfloat16"},     # 8 cached heads: half a tile of sublanes
    {"d_model": 512},                            # heads of 64
], ids=["int8", "bf16_8_heads", "heads_of_64"])
def test_self_attention_keeps_the_xla_read_where_the_rule_refuses(
        monkeypatch, refused):
    """What ``decode_attention_applies`` refuses stays XLA's read inside
    the model too: traced as on a TPU, the step programs hold no kernel
    and the tokens are ``generate()``'s."""
    model, variables = _gpt2_toy(**refused)
    reqs = _requests(lengths=(5, 9), budgets=(3, 5))
    out, steps = _served_by_the_kernel(monkeypatch, model, variables, reqs)
    _assert_generates_tokens(model, variables, reqs, out)
    assert steps and all(a["attended_rows"] == 0 for a in steps)


def test_off_the_chip_the_xla_read_stays(kernel_toys):
    """Nothing steered: the CPU's step programs hold no kernel and their
    spans say so."""
    model, variables = kernel_toys["latent"]
    tel = telemetry.enable()
    try:
        eng = DecodeEngine(model, variables, slots=2, buckets=[128],
                           prefill_align=4)
        list(eng.run(_requests(lengths=(5, 9), budgets=(3, 2))))
        steps = [e["args"] for e in tel.tracer.events()
                 if e["name"] == "decode_step"]
    finally:
        telemetry.disable()
    assert steps and all(
        a["attended_rows"] == 0 and a["envelope_rows"] == 2 * 128
        for a in steps)
