"""Continuous-batching decode engine (``serving.DecodeEngine``): slot
reuse over a persistent KV-cache pool must be INVISIBLE in the tokens —
greedy results equal ``models.generate`` per request, independent of
admission order and of which (dirty) slot a request lands in — and
steady-state serving must compile a bounded program set (the §23
claim)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import ModelSpec, generate, model_config
from distkeras_tpu.serving import DecodeEngine, ShedError
from profiled import Profiled

jax.config.update("jax_platforms", "cpu")

MAXLEN, VOCAB = 32, 37


def _model(num_layers=1, **kw):
    # one layer keeps the many per-test engine compiles cheap; the
    # dirty-slot test runs two layers to cover the multi-layer cache
    # pytree merge
    spec = model_config("transformer_lm", (MAXLEN,),
                        input_dtype="int32", vocab_size=VOCAB,
                        num_layers=num_layers, d_model=32, num_heads=2,
                        max_len=MAXLEN, dtype="float32", **kw)
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((2, MAXLEN), jnp.int32))
    return model, variables


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (t,)).astype(np.int32)
            for t in lengths]


def _want(model, variables, prompt, n_new, **kw):
    return np.asarray(generate(model, variables, prompt[None, :],
                               max_new_tokens=n_new, **kw)
                      )[0, len(prompt):]


def test_engine_matches_generate_per_request_any_admission_order():
    """Each request's greedy tokens equal a solo generate() run — the
    slot pool, right-padded prefill, and neighbors are invisible —
    and reversing the admission order changes nothing."""
    model, variables = _model()
    prompts = _prompts([5, 9, 3, 7, 5, 11, 4, 6])
    n_new = [4, 7, 3, 6, 5, 8, 2, 7]
    reqs = [{"prompt": p, "max_new_tokens": n, "i": i}
            for i, (p, n) in enumerate(zip(prompts, n_new))]
    eng = DecodeEngine(model, variables, slots=3, buckets=[16, 32],
                       prefill_align=4, steps_per_sync=2)
    fwd = {r["i"]: r["tokens"] for r in eng.run(reqs)}
    rev = {r["i"]: r["tokens"] for r in eng.run(list(reversed(reqs)),
                                                ordered=False)}
    for i, (p, n) in enumerate(zip(prompts, n_new)):
        want = _want(model, variables, p, n)
        np.testing.assert_array_equal(fwd[i], want)
        np.testing.assert_array_equal(rev[i], want)


def test_dirty_slot_readmission_is_clean():
    """More requests than slots forces every slot through
    evict -> readmit with a DIRTY cache; prefill replaces the whole
    envelope, so the reused slot's tokens still match generate()."""
    model, variables = _model(num_layers=2)
    prompts = _prompts([6, 6, 9, 4, 7, 5, 8, 6, 5], seed=7)
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4,
                       max_new_tokens=5)
    out = list(eng.run([{"prompt": p, "i": i}
                        for i, p in enumerate(prompts)]))
    assert len(out) == 9  # 9 requests through 2 slots: 7 readmissions
    for r in out:
        np.testing.assert_array_equal(
            r["tokens"], _want(model, variables, prompts[r["i"]], 5))


def test_per_slot_eos_and_max_new_stop():
    """Slots stop independently: an eos-finished row is evicted (its
    tokens end AT the eos) while its neighbors keep decoding to their
    own max_new_tokens caps."""
    model, variables = _model()
    prompts = _prompts([5, 5], seed=6)
    base = [_want(model, variables, p, 8) for p in prompts]
    # an eos row 0 emits but row 1 never does (same device as the
    # generate() eos test: rows must stop independently)
    cand = [int(t) for t in base[0] if t not in base[1]]
    assert cand, "degenerate sample; adjust seed"
    eos = cand[0]
    stop = int(np.argwhere(base[0] == eos)[0][0])
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4)
    res = {r["request_id"]: r for r in eng.run(
        [{"prompt": prompts[0], "max_new_tokens": 8, "eos_id": eos},
         {"prompt": prompts[1], "max_new_tokens": 8, "eos_id": eos},
         {"prompt": prompts[1], "max_new_tokens": 3}])}
    np.testing.assert_array_equal(res[0]["tokens"],
                                  base[0][:stop + 1])
    np.testing.assert_array_equal(res[1]["tokens"], base[1])
    np.testing.assert_array_equal(res[2]["tokens"], base[1][:3])


def test_max_new_tokens_one_and_instant_eos_finish_at_prefill():
    model, variables = _model()
    (p,) = _prompts([5], seed=9)
    first = int(_want(model, variables, p, 1)[0])
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4)
    res = list(eng.run([{"prompt": p, "max_new_tokens": 1},
                        {"prompt": p, "max_new_tokens": 8,
                         "eos_id": first}]))
    np.testing.assert_array_equal(res[0]["tokens"], [first])
    np.testing.assert_array_equal(res[1]["tokens"], [first])


def test_compile_count_guard_steady_state():
    """The §23 bounded-program-set claim, pinned via the PUBLIC
    telemetry counter ``compiles_total{kind,bucket[,padded]}`` (ISSUE 2:
    compile events are registry metrics, not private engine state): one
    step program per bucket + one prefill program per (bucket, padded
    length); a second ragged workload in a DIFFERENT arrival order
    triggers ZERO new traces."""
    from distkeras_tpu import telemetry

    tel = telemetry.enable()
    try:
        model, variables = _model()
        eng = DecodeEngine(model, variables, slots=2, buckets=[16, 32],
                           prefill_align=8, max_new_tokens=4)
        lengths = [3, 9, 5, 14, 7, 2, 11, 8]
        eng_reqs = lambda ls: [{"prompt": p}  # noqa: E731
                               for p in _prompts(ls, seed=11)]
        list(eng.run(eng_reqs(lengths)))
        m = tel.metrics
        # bounded set: one step trace per bucket...
        assert m.counter("compiles_total", kind="step",
                         bucket=16).value == 1
        assert m.counter("compiles_total", kind="step",
                         bucket=32).value == 1
        # ...and one prefill trace per (bucket, padded length), padded
        # lengths multiples of prefill_align within the bucket
        prefills = m.collect("compiles_total", kind="prefill")
        assert prefills
        for labels, c in prefills:
            assert c.value == 1, labels
        shapes = {(int(l["bucket"]), int(l["padded"]))
                  for l, _ in prefills}
        assert shapes <= {(16, 8), (16, 16), (32, 8), (32, 16),
                          (32, 24), (32, 32)}
        counters_before = {
            k: v for k, v in m.snapshot()["counters"].items()
            if k.startswith("compiles_total")}
        # ragged re-arrivals, shuffled: nothing new compiles
        list(eng.run(eng_reqs(list(reversed(lengths)))))
        list(eng.run(eng_reqs([7, 7, 3, 9, 2])))
        counters_after = {
            k: v for k, v in m.snapshot()["counters"].items()
            if k.startswith("compiles_total")}
        assert counters_after == counters_before
    finally:
        telemetry.disable()


def test_bucket_routing_and_rejection():
    """A request lands in the smallest envelope that fits its padded
    prompt + budget (cheapest static cache, §18 law); an unservable
    request fails at submit() time, naming no compiled flush."""
    model, variables = _model()
    eng = DecodeEngine(model, variables, slots=2, buckets=[16, 32],
                       prefill_align=4, max_new_tokens=4)
    assert eng._route(5, 4).env == 16
    assert eng._route(13, 4).env == 32   # 13+4 > 16
    assert eng._route(5, 20).env == 32   # budget overflows 16
    with pytest.raises(ValueError, match="no bucket"):
        eng.submit(np.zeros(30, np.int32), max_new_tokens=8)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros(4, np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(np.zeros((2, 4), np.int32))
    with pytest.raises(ValueError, match="eos_id"):
        eng.submit(np.zeros(4, np.int32), max_new_tokens=2,
                   eos_id=VOCAB)


def test_gqa_int8_cache_compose_with_engine():
    """The serving levers stack: GQA + int8 slot pools still match the
    same model's generate() greedy tokens."""
    model, variables = _model(num_kv_heads=1, kv_cache_dtype="int8")
    prompts = _prompts([5, 8, 6], seed=13)
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4,
                       steps_per_sync=3, max_new_tokens=6)
    for r in eng.run([{"prompt": p, "i": i}
                      for i, p in enumerate(prompts)]):
        np.testing.assert_array_equal(
            r["tokens"], _want(model, variables, prompts[r["i"]], 6))


def test_sampling_reproducible_for_fixed_seed_and_order():
    model, variables = _model()
    reqs = [{"prompt": p, "max_new_tokens": 5}
            for p in _prompts([5, 7, 5, 6], seed=17)]
    kw = dict(slots=2, prefill_align=4, temperature=0.9, top_k=8)
    eng = DecodeEngine(model, variables, seed=5, **kw)
    a = [r["tokens"] for r in eng.run(reqs)]
    eng.reset_rng()
    b = [r["tokens"] for r in eng.run(reqs)]
    for ta, tb in zip(a, b):
        np.testing.assert_array_equal(ta, tb)
    c = [r["tokens"] for r in
         DecodeEngine(model, variables, seed=6, **kw).run(reqs)]
    assert any(not np.array_equal(ta, tc) for ta, tc in zip(a, c))
    assert all((t >= 0).all() and (t < VOCAB).all() for t in a)
    with pytest.raises(RuntimeError, match="in flight"):
        eng.submit(np.zeros(4, np.int32), max_new_tokens=2)
        eng.reset_rng()


def test_as_completed_vs_ordered_delivery():
    """ordered=False yields early finishers first (a 2-token request
    admitted alongside 8-token neighbors completes before them);
    ordered=True restores submission order."""
    model, variables = _model()
    prompts = _prompts([5, 5, 5], seed=19)
    reqs = [{"prompt": prompts[0], "max_new_tokens": 8, "i": 0},
            {"prompt": prompts[1], "max_new_tokens": 2, "i": 1},
            {"prompt": prompts[2], "max_new_tokens": 8, "i": 2}]
    eng = DecodeEngine(model, variables, slots=3, prefill_align=4)
    completed = [r["i"] for r in eng.run(reqs, ordered=False)]
    assert completed[0] == 1, completed
    assert [r["i"] for r in eng.run(reqs, ordered=True)] == [0, 1, 2]


def test_slot_step_matches_scalar_decode_path():
    """Model-level contract: a slot_pos T=1 step on a [B] pool whose
    rows sit at DIFFERENT positions produces the same logits as each
    row's own scalar-index decode."""
    model, variables = _model()
    dec = model.clone(decode=True)
    params = {"params": variables["params"]}
    pa, pb = _prompts([4, 7], seed=23)
    tok = jnp.asarray([[1], [2]], jnp.int32)
    caches, want = [], []
    for p in (pa, pb):
        logits, st = dec.apply(params, jnp.asarray(p[None, :]),
                               mutable=["cache"])
        nxt, st = dec.apply({**params, "cache": st["cache"]},
                            tok[:1] if p is pa else tok[1:],
                            mutable=["cache"])
        caches.append(st["cache"])
        want.append(np.asarray(nxt[0, 0]))
    # build a 2-slot pool from the two solo caches
    pool = jax.tree_util.tree_map(
        lambda a, b: (jnp.concatenate([a, b], 0)
                      if getattr(a, "ndim", 0) >= 1 else a),
        caches[0], caches[1])
    slot_pos = jnp.asarray([len(pa), len(pb)], jnp.int32)
    got, _ = dec.apply({**params, "cache": pool}, tok,
                       slot_pos=slot_pos, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.stack(want),
                               rtol=2e-5, atol=2e-5)


def test_slot_pos_contract_validation():
    model, variables = _model()
    dec = model.clone(decode=True)
    params = {"params": variables["params"]}
    with pytest.raises(ValueError, match="slot_pos"):
        dec.apply(params, jnp.zeros((2, 3), jnp.int32),
                  slot_pos=jnp.zeros((2,), jnp.int32),
                  mutable=["cache"])
    with pytest.raises(ValueError, match="decode"):
        model.apply(variables, jnp.zeros((2, 1), jnp.int32),
                    slot_pos=jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="cache_envelope"):
        model.clone(cache_envelope=16).apply(
            variables, jnp.zeros((2, 4), jnp.int32))
    with pytest.raises(ValueError, match="cache_envelope"):
        model.clone(decode=True, cache_envelope=MAXLEN + 1).apply(
            params, jnp.zeros((1, 4), jnp.int32), mutable=["cache"])


def test_duplicate_inflight_request_id_rejected():
    """Mixed explicit/auto ids cannot silently collide and
    cross-deliver: a duplicate in-flight id is rejected at submit, and
    auto-assignment skips over in-flight explicit ids.  Finished ids
    become reusable."""
    model, variables = _model()
    (p,) = _prompts([5])
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4,
                       max_new_tokens=2)
    eng.submit(p, request_id=7)
    with pytest.raises(ValueError, match="in flight"):
        eng.submit(p, request_id=7)
    # the auto path must never hand out an id an explicit caller holds
    eng2 = DecodeEngine(model, variables, slots=2, prefill_align=4,
                        max_new_tokens=2)
    eng2.submit(p, request_id=0)          # occupies the first auto id
    auto = eng2.submit(p)
    assert auto != 0
    ids = {r["request_id"] for r in eng2.drain()}
    assert ids == {0, auto}
    # after finishing, the id is free again
    assert eng2.submit(p, request_id=0) == 0
    eng2.drain()


def test_queue_bound_overload_sheds_and_survivors_complete():
    """2x queue-bound overload: submits beyond slots + queue_bound shed
    with ShedError + serving_shed_total > 0; every ACCEPTED request
    still completes with correct greedy tokens (admission control
    degrades capacity, never correctness)."""
    from distkeras_tpu import telemetry

    tel = telemetry.enable()
    try:
        model, variables = _model()
        slots, bound = 2, 2
        eng = DecodeEngine(model, variables, slots=slots,
                           prefill_align=4, max_new_tokens=4,
                           queue_bound=bound)
        prompts = _prompts([5] * (2 * (slots + bound)), seed=31)
        accepted, shed = [], 0
        for i, p in enumerate(prompts):
            # keep slots saturated: admit only when a step would; the
            # queue alone absorbs up to `bound`, the rest shed
            try:
                accepted.append(eng.submit(p, request_id=i))
            except ShedError as e:
                assert e.reason == "queue_full"
                shed += 1
        assert shed > 0
        assert tel.metrics.sum_counter("serving_shed_total") == shed
        res = {r["request_id"]: r for r in eng.drain()}
        assert sorted(res) == sorted(accepted)
        for rid, r in res.items():
            assert "error" not in r
            np.testing.assert_array_equal(
                r["tokens"], _want(model, variables, prompts[rid], 4))
    finally:
        telemetry.disable()


def test_poisoned_request_isolated_as_error_result():
    """A request whose prefill raises is finished with an ``error``
    result; its neighbors' slots keep decoding to correct tokens and
    the engine keeps serving afterwards."""
    model, variables = _model()
    prompts = _prompts([5, 6, 7], seed=37)
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4,
                       max_new_tokens=4)
    pool = eng._pools[0]
    real_prefill = pool.prefill_fn

    def poisoned(variables, cache, state, prompt, slot, last_idx,
                 n_left0, eos_id, rng):
        if int(last_idx) == len(prompts[1]) - 1:  # request 1 only
            raise RuntimeError("poisoned prompt")
        return real_prefill(variables, cache, state, prompt, slot,
                            last_idx, n_left0, eos_id, rng)

    pool.prefill_fn = poisoned
    res = {r["request_id"]: r for r in eng.run(
        [{"prompt": p} for p in prompts])}
    assert "poisoned prompt" in res[1]["error"]
    assert len(res[1]["tokens"]) == 0 and res[1]["ttft"] is None
    for i in (0, 2):
        assert "error" not in res[i]
        np.testing.assert_array_equal(
            res[i]["tokens"], _want(model, variables, prompts[i], 4))
    # the engine is not stalled: it serves the next workload fine
    pool.prefill_fn = real_prefill
    (ok,) = list(eng.run([prompts[0]]))
    np.testing.assert_array_equal(
        ok["tokens"], _want(model, variables, prompts[0], 4))


def test_deadline_expires_queued_and_live_requests():
    """An already-expired queued request is shed at admission with an
    error result; a live request past its deadline frees its slot; a
    deadline-free neighbor finishes untouched."""
    model, variables = _model()
    prompts = _prompts([5, 5], seed=41)
    eng = DecodeEngine(model, variables, slots=1, prefill_align=4,
                       max_new_tokens=6)
    eng.submit(prompts[0], request_id=0)              # takes the slot
    eng.submit(prompts[1], request_id=1, deadline=1e-9)  # expires queued
    res = {r["request_id"]: r for r in eng.drain()}
    assert res[1]["error"] == "deadline_exceeded"
    assert "error" not in res[0]
    np.testing.assert_array_equal(
        res[0]["tokens"], _want(model, variables, prompts[0], 6))
    # live expiry: a decoding request past its deadline frees the slot
    # (backdate the deadline once admitted, the idle-worker idiom)
    from distkeras_tpu import telemetry

    eng.submit(prompts[0], request_id=2, deadline=3600.0)
    eng.step()                            # admitted into the slot
    (req,) = [q for q in eng._pools[0].reqs if q is not None]
    req.deadline = telemetry.now() - 1.0  # expired mid-decode
    (r,) = eng.drain()
    assert r["error"] == "deadline_exceeded"
    assert len(r["tokens"]) >= 1          # prefill had already landed
    with pytest.raises(ValueError, match="deadline"):
        eng.submit(prompts[0], deadline=0.0)


def test_drain_returns_every_inflight_and_close_cancels():
    """drain() returns exactly the in-flight set; close() cancels the
    remainder (error="engine_closed", nothing vanishes) and further
    submit/step raise."""
    model, variables = _model()
    prompts = _prompts([5, 6, 4, 7, 5], seed=43)
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4,
                       max_new_tokens=4)
    rids = [eng.submit(p) for p in prompts]
    drained = {r["request_id"] for r in eng.drain()}
    assert drained == set(rids)
    assert not eng.has_work()
    # now cancel mid-flight: 2 in slots (after one step) + 2 queued
    rids = [eng.submit(p, request_id=100 + i)
            for i, p in enumerate(prompts[:4])]
    eng.step()
    cancelled = eng.close()
    assert {r["request_id"] for r in cancelled} == set(rids)
    assert all(r["error"] == "engine_closed" for r in cancelled)
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(prompts[0])
    with pytest.raises(RuntimeError, match="closed"):
        eng.step()
    assert eng.close() == []              # idempotent


def test_streaming_continuous_backpressure_with_queue_bound():
    """StreamingGenerator(engine='continuous') over a queue_bound
    engine converts sheds into backpressure: every row still comes
    back, in order, with correct greedy tokens."""
    from distkeras_tpu.streaming import StreamingGenerator

    model, variables = _model()
    prompts = _prompts([5, 7, 5, 6, 5, 4, 6, 5], seed=47)
    gen = StreamingGenerator(
        model, variables, max_new_tokens=4, batch_size=2,
        engine="continuous",
        engine_options={"slots": 2, "prefill_align": 4,
                        "queue_bound": 1})
    out = list(gen.generate_stream(
        [{"prompt": p, "i": i} for i, p in enumerate(prompts)]))
    assert [r["i"] for r in out] == list(range(len(prompts)))
    for r in out:
        assert "generated_error" not in r
        np.testing.assert_array_equal(
            r["generated"][:4],
            _want(model, variables, prompts[r["i"]], 4))


def test_cache_envelope_bounds_chunk_and_positions():
    """A cache_envelope pool is a genuinely smaller cache: chunks
    beyond it are rejected, and decode inside it matches the
    full-envelope model (same params, positions from the same
    table)."""
    model, variables = _model()
    (p,) = _prompts([6], seed=29)
    want = _want(model, variables, p, 4)
    eng = DecodeEngine(model, variables, slots=1, buckets=[16],
                       prefill_align=4, max_new_tokens=4)
    (res,) = list(eng.run([p]))
    np.testing.assert_array_equal(res["tokens"], want)
    dec = model.clone(decode=True, cache_envelope=16)
    with pytest.raises(ValueError, match="exceeds the cache size"):
        dec.apply({"params": variables["params"]},
                  jnp.zeros((1, 20), jnp.int32), mutable=["cache"])


def test_submit_is_thread_safe_against_a_concurrent_stepper():
    """ISSUE 7 satellite: ``submit()`` from many threads while another
    thread steps the engine — the gateway's EngineReplica pattern.
    Every request is admitted exactly once and its tokens match the
    solo reference (the admission lock race this pins: queue/rid/
    dedupe mutations vs the stepping thread's admission pops)."""
    import threading
    import time

    model, variables = _model()
    eng = DecodeEngine(model, variables, slots=3, prefill_align=4,
                       max_new_tokens=4)
    prompts = _prompts([5, 7, 4, 6, 5, 3, 6, 5], seed=31)
    n_threads, per_thread = 4, 6
    results: dict = {}
    done_submitting = threading.Event()
    errors: list = []

    def stepper():
        while not done_submitting.is_set() or eng.has_work():
            for r in eng.step():
                assert r["request_id"] not in results  # exactly once
                results[r["request_id"]] = r
            time.sleep(0.001)

    def submitter(t):
        try:
            for j in range(per_thread):
                eng.submit(prompts[(t * per_thread + j) % len(prompts)],
                           request_id=f"t{t}-{j}")
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)

    step_thread = threading.Thread(target=stepper, daemon=True)
    step_thread.start()
    subs = [threading.Thread(target=submitter, args=(t,), daemon=True)
            for t in range(n_threads)]
    for s in subs:
        s.start()
    for s in subs:
        s.join(30)
    done_submitting.set()
    step_thread.join(60)
    assert not errors, errors
    assert len(results) == n_threads * per_thread
    for rid, r in results.items():
        t, j = (int(x) for x in rid[1:].split("-"))
        p = prompts[(t * per_thread + j) % len(prompts)]
        np.testing.assert_array_equal(r["tokens"],
                                      _want(model, variables, p, 4))
    eng.close()


def test_run_under_queue_bound_delivers_every_result():
    """ISSUE 7 satellite: ``run()`` over a queue_bound engine treats
    mid-iterable sheds as backpressure — completed results are
    delivered (never discarded), one result per item, in order."""
    model, variables = _model()
    prompts = _prompts([5, 7, 5, 6, 5, 4, 6, 5, 7, 5], seed=37)
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4,
                       max_new_tokens=4, queue_bound=1)
    out = list(eng.run([{"prompt": p, "i": i}
                        for i, p in enumerate(prompts)]))
    assert [r["i"] for r in out] == list(range(len(prompts)))
    for r in out:
        assert "error" not in r
        np.testing.assert_array_equal(
            r["tokens"], _want(model, variables, prompts[r["i"]], 4))
    eng.close()


def test_run_under_queue_bound_delivers_error_rows_too():
    """Deadline casualties under shed backpressure come back as
    ``error`` rows through ``run()`` — the whole iterable is accounted
    for even when nothing survives."""
    model, variables = _model()
    prompts = _prompts([5, 6, 5, 7, 5, 6], seed=41)
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4,
                       max_new_tokens=4, queue_bound=1,
                       deadline=1e-4)
    out = list(eng.run([{"prompt": p, "i": i}
                        for i, p in enumerate(prompts)]))
    assert [r["i"] for r in out] == list(range(len(prompts)))
    assert any(r.get("error") == "deadline_exceeded" for r in out)
    for r in out:
        if r.get("error") is None:
            np.testing.assert_array_equal(
                r["tokens"],
                _want(model, variables, prompts[r["i"]], 4))
    eng.close()


# ---- the step's span tree and a request's times (PR 26) ---------------

def _traced_step(tmp_path):
    """One profiled ``step()`` of a warm engine: three admissions into
    the three slots of one pool, then one decode of all three."""
    model, variables = _model()
    eng = DecodeEngine(model, variables, slots=3, buckets=[16],
                       prefill_align=4, steps_per_sync=2)
    prompts = _prompts([5, 9, 3, 7])
    for p in prompts:  # compile outside the profiled step
        eng.submit(p, max_new_tokens=4)
    while eng.has_work():
        eng.step()
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=4, request_id=f"r{i}")
    with Profiled(tmp_path) as prof:
        out = eng.step()
    while eng.has_work():
        out += eng.step()
    return prof, prompts, out


def test_one_traced_step_yields_the_span_tree(tmp_path):
    prof, prompts, _ = _traced_step(tmp_path)
    (root,) = prof.named("engine_step")
    # the occupancy as it stood on entry: four queued, nothing live
    assert root["stats"] == {"live": 0, "queued": 4, "parked": 0}
    first, second = prof.named("admit")     # both passes
    assert prof.parent(first) is root and prof.parent(second) is root
    prefills = prof.named("prefill")
    assert [p["stats"] for p in prefills] == [
        {"bucket": 16, "slot": i, "padded": -(-len(prompts[i]) // 4) * 4,
         "prompt_tokens": len(prompts[i]), "request_id": f"r{i}"}
        for i in range(3)]
    for p, d, s in zip(prefills, prof.named("prefill_dispatch"),
                       prof.named("first_token_sync")):
        assert prof.parent(p) is first
        assert prof.parent(d) is p and prof.parent(s) is p
        assert d["end"] <= s["start"]
    (decode,) = prof.named("decode_step")
    assert prof.parent(decode) is root
    # the CPU's step holds XLA's read of the whole envelope: no row
    # counted as the decode kernel's, 3 slots x 16 x 2 sub-steps held;
    # no KDA layer, so no state row updated by the KDA step kernel
    assert decode["stats"] == {"bucket": 16, "steps": 2, "live": 3,
                               "attended_rows": 0, "envelope_rows": 96,
                               "kda_kernel_rows": 0}
    (dispatch,) = prof.named("decode_dispatch")
    (fetch,) = prof.named("decode_fetch")
    assert prof.parent(dispatch) is decode and prof.parent(fetch) is decode
    (emit,) = prof.named("emit")
    (sweep,) = prof.named("sweep")
    assert prof.parent(emit) is root and prof.parent(sweep) is root
    assert first["end"] <= decode["start"] <= fetch["end"] \
        <= emit["start"] <= sweep["start"] <= second["start"]
    # nothing of the step lies outside these names
    assert {s["name"] for s in prof.spans} == {
        "dkt:" + n for n in (
            "engine_step", "admit", "prefill", "prefill_dispatch",
            "first_token_sync", "decode_step", "decode_dispatch",
            "decode_fetch", "emit", "sweep")}


def _check_times(res):
    assert len(res["t_tokens"]) == len(res["tokens"])
    assert all(isinstance(t, float) for t in res["t_tokens"])
    assert res["t_tokens"] == sorted(res["t_tokens"])
    if res["t_tokens"]:
        assert res["t_submit"] <= res["t_admit"] <= res["t_tokens"][0]
        assert res["t_tokens"][0] == res["t_first"]
        assert res["t_tokens"][-1] <= res["t_finish"]
    else:
        assert res["t_first"] is None


@pytest.mark.parametrize("engine_kw", [
    {"steps_per_sync": 2},
    {"steps_per_sync": 2, "prefix_cache_bytes": 1 << 20,
     "prefill_chunk": 4},
    {"steps_per_sync": 2, "kv_pages": 24},
    {"speculative": {"proposer": "ngram", "k": 2, "ngram": 2}},
], ids=["one_shot", "chunked", "paged", "speculative"])
def test_every_token_is_stamped_where_it_reaches_the_host(engine_kw):
    """Every path that appends a token stamps it: a result's
    ``t_tokens`` has one time a token, between admission and finish."""
    model, variables = _model()
    prompts = _prompts([5, 9, 3, 7, 6, 11])
    eng = DecodeEngine(model, variables, slots=2, buckets=[16, 32],
                       prefill_align=4, **engine_kw)
    out = list(eng.run([{"prompt": p, "max_new_tokens": 5 + i % 3}
                        for i, p in enumerate(prompts)]))
    assert len(out) == len(prompts)
    for res in out:
        assert "error" not in res
        _check_times(res)
        np.testing.assert_array_equal(
            res["tokens"],
            _want(model, variables, res["prompt"], len(res["tokens"])))


def test_error_results_carry_the_time_fields_too():
    model, variables = _model()
    eng = DecodeEngine(model, variables, slots=1, buckets=[16],
                       prefill_align=4)
    a, b = _prompts([5, 6])
    eng.submit(a, max_new_tokens=8, request_id="live")
    eng.submit(b, max_new_tokens=8, request_id="queued")
    eng.step()                     # "live" holds the one slot
    by_id = {r["request_id"]: r for r in eng.close()}
    assert by_id["live"]["error"] == by_id["queued"]["error"] \
        == "engine_closed"
    _check_times(by_id["live"])
    assert len(by_id["live"]["tokens"]) >= 1
    # never admitted: both keys are there, and empty
    assert by_id["queued"]["t_admit"] is None
    assert by_id["queued"]["t_tokens"] == []
    _check_times(by_id["queued"])
