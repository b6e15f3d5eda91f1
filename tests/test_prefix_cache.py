"""Shared-prefix KV cache + chunked prefill (ISSUE 8): every reuse
and scheduling optimization must be INVISIBLE in the tokens — seeded
greedy decode with the prefix store on (across admission orders,
partial-align matches, and evict-then-readmit) and with chunked
prefill on is byte-identical to the plain engine — while the
scheduler properties (decode steps interleave with a long prefill;
deadlines fire between chunks; a weight swap invalidates the store)
hold observably."""

import jax
import numpy as np
import pytest

from distkeras_tpu import flight_recorder, telemetry
from distkeras_tpu.models import ModelSpec, generate, model_config
from distkeras_tpu.serving import DecodeEngine

jax.config.update("jax_platforms", "cpu")

MAXLEN, VOCAB = 32, 37


def _model(num_layers=1, **kw):
    spec = model_config("transformer_lm", (MAXLEN,),
                        input_dtype="int32", vocab_size=VOCAB,
                        num_layers=num_layers, d_model=32, num_heads=2,
                        max_len=MAXLEN, dtype="float32", **kw)
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           np.zeros((2, MAXLEN), np.int32))
    return model, variables


def _shared_prompts(n=4, shared=12, tail=6, seed=7):
    """``n`` prompts sharing a ``shared``-token head (the system-
    prompt workload the prefix store exists for)."""
    rng = np.random.default_rng(seed)
    head = rng.integers(0, VOCAB, (shared,)).astype(np.int32)
    return [np.concatenate([head, rng.integers(0, VOCAB, (tail,))
                            .astype(np.int32)]) for _ in range(n)]


def _want(model, variables, prompt, n_new):
    return np.asarray(generate(model, variables, prompt[None, :],
                               max_new_tokens=n_new))[0, len(prompt):]


def _engine(model, variables, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("prefill_align", 4)
    kw.setdefault("buckets", (MAXLEN,))
    return DecodeEngine(model, variables, **kw)


def _drain(eng, prompts, n_new=5, tag="r"):
    """Submit all, run to empty, return tokens keyed by prompt index
    (any engine error fails the test)."""
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=n_new, request_id=f"{tag}{i}")
    out = {}
    while eng.has_work():
        for r in eng.step():
            assert "error" not in r, r
            out[int(r["request_id"][len(tag):])] = \
                np.asarray(r["tokens"])
    return out


# ---- parity: the optimization must be invisible -----------------------


def test_prefix_cache_parity_across_admission_orders():
    """Greedy tokens with the store on == the solo ``generate``
    reference, for the warm-up wave, a reshuffled wave, and a steady-
    state wave that actually HITS (per ``prefix_stats``)."""
    model, variables = _model()
    prompts = _shared_prompts()
    refs = [_want(model, variables, p, 5) for p in prompts]
    with _engine(model, variables,
                 prefix_cache_bytes=1 << 24) as eng:
        for tag, order in (("a", range(len(prompts))),
                           ("b", reversed(range(len(prompts)))),
                           ("c", range(len(prompts)))):
            order = list(order)
            got = _drain(eng, [prompts[i] for i in order], tag=tag)
            for k, i in enumerate(order):
                np.testing.assert_array_equal(got[k], refs[i])
        st = eng.prefix_stats()
    assert st["enabled"] and st["hits"] >= len(prompts), st
    # every hit skipped whole aligned blocks of real prefill compute
    assert st["tokens_saved"] >= st["hits"] * 4, st


def test_partial_align_match_reuses_only_whole_blocks():
    """A prompt sharing 9 tokens with a cached one matches exactly
    2 whole 4-blocks (8 tokens) — the ragged remainder is prefilled —
    and still decodes byte-identically."""
    model, variables = _model()
    rng = np.random.default_rng(11)
    a = rng.integers(0, VOCAB, (13,)).astype(np.int32)
    b = np.concatenate([a[:9],
                        rng.integers(0, VOCAB, (5,)).astype(np.int32)])
    with _engine(model, variables, slots=1,
                 prefix_cache_bytes=1 << 24) as eng:
        (got_a,) = _drain(eng, [a], tag="a").values()
        saved0 = eng.prefix_stats()["tokens_saved"]
        (got_b,) = _drain(eng, [b], tag="b").values()
        st = eng.prefix_stats()
    np.testing.assert_array_equal(got_a, _want(model, variables, a, 5))
    np.testing.assert_array_equal(got_b, _want(model, variables, b, 5))
    assert st["hits"] == 1
    assert st["tokens_saved"] - saved0 == 8, st


def test_evict_then_readmit_parity_under_tiny_budget():
    """A budget too small for the workload forces LRU eviction; the
    evicted prefix re-admits (cold) with identical tokens."""
    model, variables = _model()
    prompts = _shared_prompts()
    refs = [_want(model, variables, p, 5) for p in prompts]
    with _engine(model, variables, prefix_cache_bytes=2100) as eng:
        for tag in ("a", "b"):
            got = _drain(eng, prompts, tag=tag)
            for i, r in enumerate(refs):
                np.testing.assert_array_equal(got[i], r)
        st = eng.prefix_stats()
    assert st["evictions"] > 0, st
    assert st["bytes"] <= 2100, st


def test_chunked_prefill_parity_with_and_without_store():
    model, variables = _model()
    prompts = _shared_prompts(n=3, shared=12, tail=10, seed=5)
    refs = [_want(model, variables, p, 4) for p in prompts]
    for kw in ({"prefill_chunk": 4},
               {"prefill_chunk": 8, "prefix_cache_bytes": 1 << 24}):
        with _engine(model, variables, **kw) as eng:
            for tag in ("a", "b"):
                got = _drain(eng, prompts, n_new=4, tag=tag)
                for i, r in enumerate(refs):
                    np.testing.assert_array_equal(got[i], r, err_msg=
                                                  f"{kw} wave {tag}")


def test_multilayer_parity_with_prefix_and_chunks():
    """Two layers: the per-layer segment extract/copy composes across
    the cache pytree, not just a single layer's leaves."""
    model, variables = _model(num_layers=2)
    prompts = _shared_prompts(n=3)
    refs = [_want(model, variables, p, 4) for p in prompts]
    with _engine(model, variables, slots=2, prefill_chunk=8,
                 prefix_cache_bytes=1 << 24) as eng:
        for tag in ("a", "b"):
            got = _drain(eng, prompts, n_new=4, tag=tag)
            for i, r in enumerate(refs):
                np.testing.assert_array_equal(got[i], r)
        assert eng.prefix_stats()["hits"] >= len(prompts)


def test_instant_finish_paths_under_prefix_and_chunk():
    """max_new=1 and instant-eos terminate correctly when the first
    token comes out of a chunked (possibly prefix-seeded) prefill."""
    model, variables = _model()
    (p,) = _shared_prompts(n=1, shared=12, tail=3)
    first = int(_want(model, variables, p, 1)[0])
    with _engine(model, variables, prefill_chunk=4,
                 prefix_cache_bytes=1 << 24) as eng:
        got = _drain(eng, [p, p], n_new=1, tag="a")
        for v in got.values():
            assert v.tolist() == [first]
        eng.submit(p, max_new_tokens=6, request_id="eos",
                   eos_id=first)
        while eng.has_work():
            for r in eng.step():
                assert "error" not in r
                assert r["tokens"].tolist() == [first]


# ---- scheduling properties --------------------------------------------


def test_decode_steps_interleave_with_a_long_chunked_prefill():
    """THE Sarathi property: while a max-length prompt chunk-prefills,
    the other slot keeps producing tokens — on the trace, decode_step
    spans appear BETWEEN the long request's prefill_chunk spans, and
    at most one chunk runs per engine step."""
    tel = telemetry.enable()
    try:
        model, variables = _model()
        rng = np.random.default_rng(3)
        short = rng.integers(0, VOCAB, (5,)).astype(np.int32)
        long = rng.integers(0, VOCAB, (30,)).astype(np.int32)
        with _engine(model, variables, slots=2,
                     prefill_chunk=8) as eng:
            eng.submit(short, max_new_tokens=12, request_id="short")
            eng.step()  # short's single chunk runs; it starts decoding
            eng.submit(long, max_new_tokens=2, request_id="long")
            while eng.has_work():
                eng.step()
            got_long = None
        ev = [e for e in tel.tracer.events()
              if e["name"] in ("prefill_chunk", "decode_step")]
        chunk_idx = [i for i, e in enumerate(ev)
                     if e["name"] == "prefill_chunk"
                     and e["args"].get("request_id") == "long"]
        assert len(chunk_idx) == 4  # 32 padded / 8 per chunk
        between = [e["name"] for e in ev[chunk_idx[0]:chunk_idx[-1]]]
        assert "decode_step" in between, between
    finally:
        telemetry.disable()


def test_chunked_outputs_match_reference_while_interleaved():
    model, variables = _model()
    rng = np.random.default_rng(3)
    short = rng.integers(0, VOCAB, (5,)).astype(np.int32)
    long = rng.integers(0, VOCAB, (30,)).astype(np.int32)
    out = {}
    with _engine(model, variables, slots=2, prefill_chunk=8) as eng:
        eng.submit(short, max_new_tokens=12, request_id="short")
        eng.step()
        eng.submit(long, max_new_tokens=2, request_id="long")
        while eng.has_work():
            for r in eng.step():
                assert "error" not in r, r
                out[r["request_id"]] = np.asarray(r["tokens"])
    np.testing.assert_array_equal(out["short"],
                                  _want(model, variables, short, 12))
    np.testing.assert_array_equal(out["long"],
                                  _want(model, variables, long, 2))


def test_deadline_expiry_fires_between_prefill_chunks():
    """ISSUE 8 fix: a chunked long prompt cannot ride out its own
    deadline — expiry is re-checked between chunks, frees the slot,
    and the engine keeps serving."""
    model, variables = _model()
    rng = np.random.default_rng(9)
    long = rng.integers(0, VOCAB, (28,)).astype(np.int32)
    with _engine(model, variables, slots=1, prefill_chunk=4) as eng:
        eng.submit(long, max_new_tokens=4, request_id="doomed",
                   deadline=60.0)
        results = eng.step()  # admits + runs the first chunk only
        assert results == []
        pool = eng._pools[0]
        assert pool.prefilling  # mid-prefill, several chunks left
        (slot,) = pool.prefilling
        pool.reqs[slot].deadline = telemetry.now() - 1.0  # backdate
        results = eng.step()
        assert [r.get("error") for r in results] == \
            ["deadline_exceeded"]
        assert not pool.prefilling and pool.reqs[slot] is None
        # the slot is immediately reusable, with correct tokens
        (p,) = _shared_prompts(n=1)
        got = _drain(eng, [p], n_new=3, tag="x")
        np.testing.assert_array_equal(got[0],
                                      _want(model, variables, p, 3))


def test_swap_variables_invalidates_the_prefix_store(tmp_path):
    """ISSUE 8 regression: stale KV under new weights is silently
    wrong, so a swap clears the store (counter + flight event) and
    post-swap outputs are byte-identical to a COLD engine built on
    the new weights."""
    tel = telemetry.enable()
    fr = flight_recorder.start(tmp_path / "fdr")
    try:
        model, variables = _model()
        prompts = _shared_prompts()
        v2 = jax.tree_util.tree_map(lambda x: x * 1.01, variables)
        with _engine(model, variables, prefill_chunk=8,
                     prefix_cache_bytes=1 << 24) as eng:
            _drain(eng, prompts, tag="warm")
            assert eng.prefix_stats()["nodes"] > 0
            eng.swap_variables(v2)
            st = eng.prefix_stats()
            assert st["nodes"] == 0 and st["bytes"] == 0
            assert st["invalidations"] == 1
            got = _drain(eng, prompts, tag="post")
        with _engine(model, v2, prefill_chunk=8,
                     prefix_cache_bytes=1 << 24) as cold:
            ref = _drain(cold, prompts, tag="cold")
        for i in range(len(prompts)):
            np.testing.assert_array_equal(got[i], ref[i])
        assert tel.metrics.sum_counter(
            "serving_prefix_invalidations_total") == 1
        ev = [e for e in fr.read_events()
              if e["kind"] == "prefix_invalidate"]
        assert len(ev) == 1 and ev[0]["reason"] == "weight_swap"
        assert ev[0]["nodes"] > 0
    finally:
        flight_recorder.stop()
        telemetry.disable()


def test_mid_flight_swap_never_donates_stale_kv():
    """A request admitted BEFORE a swap finishes on hybrid KV — its
    prefix must not be donated into the (post-swap) store, or the
    next matching prompt would silently decode on stale rows."""
    model, variables = _model()
    (p,) = _shared_prompts(n=1)
    v2 = jax.tree_util.tree_map(lambda x: x * 1.01, variables)
    with _engine(model, variables, slots=1,
                 prefix_cache_bytes=1 << 24) as eng:
        eng.submit(p, max_new_tokens=6, request_id="inflight")
        eng.step()          # admitted + prefilled under v1
        eng.swap_variables(v2)
        while eng.has_work():
            eng.step()      # finishes under v2: hybrid KV
        st = eng.prefix_stats()
        assert st["nodes"] == 0, st  # nothing donated
        got = _drain(eng, [p], n_new=5, tag="x")
    np.testing.assert_array_equal(got[0], _want(model, v2, p, 5))


# ---- bounded compiled set + telemetry ---------------------------------


def test_chunk_program_set_is_bounded_steady_state():
    """Chunk programs trace once per (bucket, width); the steady-state
    wave compiles NOTHING new (the §23 discipline extended to the
    segmented path)."""
    tel = telemetry.enable()
    try:
        model, variables = _model()
        prompts = _shared_prompts(n=3, shared=12, tail=10, seed=5)
        with _engine(model, variables, prefill_chunk=8,
                     prefix_cache_bytes=1 << 24) as eng:
            # wave a = all misses (chunk path); wave b = hits (copy +
            # short tail-chunk path): together they warm every program
            _drain(eng, prompts, tag="a")
            _drain(eng, prompts, tag="b")
            m = tel.metrics
            chunks = m.collect("compiles_total", kind="chunk_prefill")
            assert chunks
            for labels, c in chunks:
                assert c.value == 1, labels
            assert m.collect("compiles_total", kind="prefix_copy")
            before = {k: v for k, v in m.snapshot()["counters"].items()
                      if k.startswith("compiles_total")}
            _drain(eng, prompts, tag="c")
            _drain(eng, list(reversed(prompts)), tag="d")
            after = {k: v for k, v in m.snapshot()["counters"].items()
                     if k.startswith("compiles_total")}
        assert before == after, (
            "steady-state segmented serving compiled something new")
    finally:
        telemetry.disable()


def test_prefix_counters_and_hit_rate_gauge():
    tel = telemetry.enable()
    try:
        model, variables = _model()
        prompts = _shared_prompts()
        with _engine(model, variables,
                     prefix_cache_bytes=1 << 24) as eng:
            _drain(eng, prompts, tag="a")
            _drain(eng, prompts, tag="b")
        m = tel.metrics
        hits = m.sum_counter("serving_prefix_hits_total")
        misses = m.sum_counter("serving_prefix_misses_total")
        saved = m.sum_counter("serving_prefill_tokens_saved_total")
        assert hits >= len(prompts) and misses >= 1
        assert saved >= hits * 4
        (gauge,) = [g for (labels, g)
                    in m.collect("serving_prefix_hit_rate")]
        assert gauge.value == pytest.approx(hits / (hits + misses))
    finally:
        telemetry.disable()


@pytest.mark.parametrize("chunk", [None, 4],
                         ids=["prefix", "prefix+chunk"])
def test_steady_state_wave_skips_most_of_its_prefill(chunk):
    """Tokens NOT prefilled, as a count: once the store holds the
    shared head, a wave of the same system-prompt traffic skips at
    least half of all its prompt tokens — with chunked prefill on or
    off."""
    model, variables = _model()
    prompts = _shared_prompts()
    kw = {} if chunk is None else {"prefill_chunk": chunk}
    with _engine(model, variables, prefix_cache_bytes=1 << 24,
                 **kw) as eng:
        _drain(eng, prompts, tag="warm")
        before = eng.prefix_stats()["tokens_saved"]
        _drain(eng, prompts, tag="steady")
        saved = eng.prefix_stats()["tokens_saved"] - before
    assert saved / sum(len(p) for p in prompts) >= 0.5


@pytest.mark.parametrize("chunk,want_steps", [(None, 1), (8, 4)],
                         ids=["unchunked", "chunked"])
def test_long_prefill_spans_one_step_or_one_per_chunk(chunk,
                                                      want_steps):
    """Engine steps between a long prompt's submit and its first
    token, beside a live decoding slot: one when the prefill is
    monolithic, one per chunk (32 padded / 8) when it is chunked."""
    model, variables = _model()
    rng = np.random.default_rng(3)
    short = rng.integers(0, VOCAB, (5,)).astype(np.int32)
    long = rng.integers(0, VOCAB, (30,)).astype(np.int32)
    kw = {} if chunk is None else {"prefill_chunk": chunk}
    with _engine(model, variables, slots=2, **kw) as eng:
        eng.submit(short, max_new_tokens=12, request_id="short")
        eng.step()  # short prefills; it decodes from here on
        eng.submit(long, max_new_tokens=1, request_id="long")
        steps, out = 0, {}
        while "long" not in out:
            for r in eng.step():
                assert "error" not in r, r
                out[r["request_id"]] = np.asarray(r["tokens"])
            steps += 1
        while eng.has_work():
            for r in eng.step():
                out[r["request_id"]] = np.asarray(r["tokens"])
    assert steps == want_steps
    np.testing.assert_array_equal(out["long"],
                                  _want(model, variables, long, 1))
    np.testing.assert_array_equal(out["short"],
                                  _want(model, variables, short, 12))


# ---- knob validation --------------------------------------------------


def test_knob_validation():
    model, variables = _model()
    with pytest.raises(ValueError, match="prefill_align"):
        _engine(model, variables, prefill_chunk=3)
    with pytest.raises(ValueError, match="prefill_align"):
        _engine(model, variables, prefill_chunk=0)
    with pytest.raises(ValueError, match="prefix_cache_bytes"):
        _engine(model, variables, prefix_cache_bytes=0)
