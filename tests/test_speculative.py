"""Speculative decoding (``speculative`` + the engine's verify path,
ISSUE 15): proposers only ever SUGGEST tokens — the greedy acceptance
rule makes every output byte-identical to the non-speculative baseline
on BOTH the envelope and paged engines, across admission orders,
eos/max_new stops inside an accepted window, rollbacks, preemption,
deadline expiry, and weight swaps — while the compile guard pins a
bounded program set and the acceptance telemetry feeds the
``spec_accept_rate`` SLO signal."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import speculative, telemetry
from distkeras_tpu.gateway import EngineReplica, ServingGateway
from distkeras_tpu.models import ModelSpec, generate, model_config
from distkeras_tpu.serving import DecodeEngine

jax.config.update("jax_platforms", "cpu")

MAXLEN, VOCAB = 32, 37


def _model(seed=0, num_layers=1, vocab_size=VOCAB, **kw):
    spec = model_config("transformer_lm", (MAXLEN,),
                        input_dtype="int32", vocab_size=vocab_size,
                        num_layers=num_layers, d_model=32, num_heads=2,
                        max_len=MAXLEN, dtype="float32", **kw)
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(seed),
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


def _self_draft(model, variables, k=3):
    # draft == target: every proposal is the target's own greedy
    # token, so acceptance is total and every commit is k+1 wide —
    # the hardest exercise of the multi-token commit path
    return {"proposer": "draft", "k": k, "draft_model": model,
            "draft_variables": variables}


# ---------------------------------------------------------------------
# unit: proposers and the acceptance rule


def test_ngram_propose_matches_most_recent_occurrence():
    led = np.array([5, 1, 2, 9, 4, 5, 1, 2], np.int32)
    # tail [1, 2] matched at s=1 -> proposes what followed: [9, 4, 5]
    np.testing.assert_array_equal(
        speculative.ngram_propose(led, 3, 2), [9, 4, 5])
    # recency wins: a later duplicate of the tail shadows s=1
    led2 = np.array([1, 2, 7, 3, 1, 2, 8, 1, 2], np.int32)
    np.testing.assert_array_equal(
        speculative.ngram_propose(led2, 2, 2), [8, 1])
    # no earlier occurrence / ledger shorter than the pattern: empty
    assert len(speculative.ngram_propose(
        np.array([1, 2, 3, 4], np.int32), 3, 2)) == 0
    assert len(speculative.ngram_propose(
        np.array([1, 2], np.int32), 3, 2)) == 0


def test_accept_length_is_longest_matching_prefix():
    g = np.array([4, 5, 6, 7], np.int32)
    assert speculative.accept_length(np.array([4, 5, 6]), g) == 3
    assert speculative.accept_length(np.array([4, 5, 9]), g) == 2
    assert speculative.accept_length(np.array([9, 5, 6]), g) == 0
    assert speculative.accept_length(np.empty((0,), np.int32), g) == 0


def test_config_validation():
    model, variables = _model()
    with pytest.raises(ValueError, match="unknown keys"):
        speculative.normalize({"proposer": "ngram", "nope": 1},
                              vocab_size=VOCAB, max_len=MAXLEN)
    with pytest.raises(ValueError, match="proposer"):
        speculative.normalize({"proposer": "medusa"},
                              vocab_size=VOCAB, max_len=MAXLEN)
    with pytest.raises(ValueError, match="k must be"):
        speculative.normalize({"k": 0}, vocab_size=VOCAB,
                              max_len=MAXLEN)
    with pytest.raises(ValueError, match="draft_model"):
        speculative.normalize({"proposer": "draft"},
                              vocab_size=VOCAB, max_len=MAXLEN)
    with pytest.raises(ValueError, match="vocab_size"):
        speculative.normalize(
            {"proposer": "draft", "draft_model": _model(
                vocab_size=VOCAB + 1)[0], "draft_variables": variables},
            vocab_size=VOCAB, max_len=MAXLEN)
    # engine knob coupling: greedy-only, one-token sync quantum
    with pytest.raises(ValueError, match="temperature"):
        DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                     temperature=0.7,
                     speculative={"proposer": "ngram"})
    with pytest.raises(ValueError, match="steps_per_sync"):
        DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                     steps_per_sync=2,
                     speculative={"proposer": "ngram"})
    # per-request opt-IN needs an engine-level config to opt into
    eng = DecodeEngine(model, variables, slots=2, buckets=[MAXLEN])
    with pytest.raises(ValueError, match="speculative"):
        eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                   speculative=True)
    eng.close()


# ---------------------------------------------------------------------
# parity: byte-identical to the baseline on both engine arms


def test_envelope_ngram_parity_any_admission_order():
    model, variables = _model()
    rng = np.random.default_rng(7)
    prompts = []
    for i in range(6):
        base = rng.integers(0, VOCAB, (4,)).astype(np.int32)
        prompts.append(np.tile(base, 3)[:10].astype(np.int32))
    reqs = [{"prompt": p, "max_new_tokens": 12, "i": i}
            for i, p in enumerate(prompts)]
    eng = DecodeEngine(model, variables, slots=3, buckets=[MAXLEN],
                       prefill_align=4,
                       speculative={"proposer": "ngram", "k": 3})
    fwd = {r["i"]: r["tokens"] for r in eng.run(reqs)}
    rev = {r["i"]: r["tokens"] for r in eng.run(list(reversed(reqs)),
                                                ordered=False)}
    for i, p in enumerate(prompts):
        want = _want(model, variables, p, 12)
        np.testing.assert_array_equal(fwd[i], want)
        np.testing.assert_array_equal(rev[i], want)
    eng.close()


def test_envelope_draft_parity_full_and_partial_acceptance():
    model, variables = _model()
    dmodel, dvars = _model(seed=1)  # disagreeing draft: rollbacks
    prompts = _prompts([5, 9, 3, 7, 6, 11])
    reqs = [{"prompt": p, "max_new_tokens": 8, "i": i}
            for i, p in enumerate(prompts)]
    for draft, full in [(_self_draft(model, variables), True),
                        ({"proposer": "draft", "k": 3,
                          "draft_model": dmodel,
                          "draft_variables": dvars}, False)]:
        eng = DecodeEngine(model, variables, slots=3, buckets=[MAXLEN],
                           prefill_align=4, speculative=draft)
        got = {r["i"]: r["tokens"] for r in eng.run(reqs)}
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(
                got[i], _want(model, variables, p, 8))
        st = eng.spec_stats()
        assert st["proposed"] > 0
        if full:
            assert st["accept_rate"] == 1.0
        eng.close()


def test_paged_parity_and_page_accounting():
    model, variables = _model()
    prompts = _prompts([5, 9, 3, 7, 6, 11])
    reqs = [{"prompt": p, "max_new_tokens": 8, "i": i}
            for i, p in enumerate(prompts)]
    for spec in ({"proposer": "ngram", "k": 3},
                 _self_draft(model, variables)):
        eng = DecodeEngine(model, variables, slots=3, buckets=[MAXLEN],
                           prefill_align=4, kv_pages=24,
                           speculative=spec)
        got = {r["i"]: r["tokens"] for r in eng.run(reqs)}
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(
                got[i], _want(model, variables, p, 8))
        # every page earned by speculative growth came back
        assert eng.free_pages() == 24
        eng.close()


def _count_steps(eng, reqs):
    """Drive ``reqs`` to completion by hand; returns (engine steps
    taken, tokens by request index)."""
    for r in reqs:
        eng.submit(r["prompt"], max_new_tokens=r["max_new_tokens"],
                   meta={"i": r["i"]})
    steps, out = 0, {}
    while eng.has_work():
        for r in eng.step():
            assert r.get("error") is None, r
            out[r["i"]] = np.asarray(r["tokens"])
        steps += 1
    return steps, out


@pytest.mark.parametrize("kv_pages", [None, 24],
                         ids=["envelope", "paged"])
def test_self_draft_takes_strictly_fewer_steps_than_baseline(kv_pages):
    """What the program guarantees about step counts: a draft that
    shares the target's weights is always accepted, every verify
    commits ``k + 1`` tokens, so the same requests finish in strictly
    fewer engine steps than without speculation — same tokens."""
    model, variables = _model()
    reqs = [{"prompt": p, "max_new_tokens": 12, "i": i}
            for i, p in enumerate(_prompts([5, 9, 3]))]
    kw = dict(slots=3, buckets=[MAXLEN], prefill_align=4)
    if kv_pages is not None:
        kw["kv_pages"] = kv_pages
    with DecodeEngine(model, variables, **kw) as eng:
        base_steps, base = _count_steps(eng, reqs)
    with DecodeEngine(model, variables,
                      speculative=_self_draft(model, variables),
                      **kw) as eng:
        spec_steps, got = _count_steps(eng, reqs)
        assert eng.spec_stats()["accept_rate"] == 1.0
    assert spec_steps < base_steps
    for i in base:
        np.testing.assert_array_equal(got[i], base[i])


def test_ngram_earns_acceptance_on_tiled_prompts_at_no_extra_steps():
    """Prompt-lookup drafting on a context that repeats itself: some
    proposals are accepted, so the run takes no more steps than the
    baseline (an accepted token is a step saved; a rejected window
    costs none), the tokens are the baseline's, and the acceptance
    gauge is published."""
    model, variables = _model()
    rng = np.random.default_rng(7)
    reqs = [{"prompt": np.tile(
        rng.integers(0, VOCAB, (4,)).astype(np.int32), 3)[:10],
        "max_new_tokens": 20, "i": i} for i in range(6)]
    kw = dict(slots=3, buckets=[MAXLEN], prefill_align=4)
    with DecodeEngine(model, variables, **kw) as eng:
        base_steps, base = _count_steps(eng, reqs)
    tel = telemetry.enable()
    try:
        with DecodeEngine(model, variables,
                          speculative={"proposer": "ngram", "k": 3},
                          **kw) as eng:
            spec_steps, got = _count_steps(eng, reqs)
            st = eng.spec_stats()
        gauges = tel.metrics.snapshot()["gauges"]
    finally:
        telemetry.disable()
    assert st["proposed"] > 0 and st["accept_rate"] > 0.02, st
    assert spec_steps <= base_steps
    assert gauges["serving_spec_accept_rate"] == \
        pytest.approx(st["accept_rate"])
    for i in base:
        np.testing.assert_array_equal(got[i], base[i])


def test_eos_inside_accepted_window_stops_mid_window():
    model, variables = _model()
    p = _prompts([9], seed=7)[0]
    free = _want(model, variables, p, 8)
    eos = int(free[3])  # fires mid-window under a k=3 proposal
    stop = int(np.argwhere(free == eos)[0][0])
    for kw in ({}, {"kv_pages": 24}):
        eng = DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                           prefill_align=4,
                           speculative=_self_draft(model, variables),
                           **kw)
        r = list(eng.run([{"prompt": p, "max_new_tokens": 8,
                           "eos_id": eos}]))[0]
        # the accepted tail PAST the eos is discarded, tokens end AT it
        np.testing.assert_array_equal(r["tokens"], free[:stop + 1])
        eng.close()


def test_max_new_clamp_stops_mid_window():
    model, variables = _model()
    p = _prompts([9], seed=7)[0]
    free = _want(model, variables, p, 8)
    for kw in ({}, {"kv_pages": 24}):
        eng = DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                           prefill_align=4,
                           speculative=_self_draft(model, variables),
                           **kw)
        # 3 new tokens with k+1 = 4-wide commits: the clamp lands
        # inside the first accepted window
        r = list(eng.run([{"prompt": p, "max_new_tokens": 3}]))[0]
        np.testing.assert_array_equal(r["tokens"], free[:3])
        assert len(r["tokens"]) == 3
        eng.close()


# ---------------------------------------------------------------------
# composition: scheduling, deadlines, swaps, preemption


def test_per_request_opt_out_is_baseline():
    model, variables = _model()
    prompts = _prompts([5, 9, 3])
    eng = DecodeEngine(model, variables, slots=3, buckets=[MAXLEN],
                       prefill_align=4,
                       speculative=_self_draft(model, variables))
    got = {r["i"]: r["tokens"]
           for r in eng.run([{"prompt": p, "max_new_tokens": 6,
                              "speculative": False, "i": i}
                             for i, p in enumerate(prompts)])}
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            got[i], _want(model, variables, p, 6))
    assert eng.spec_stats()["proposed"] == 0  # everyone opted out
    eng.close()


def test_deadline_expiry_mid_flight_frees_the_slot():
    model, variables = _model()
    prompts = _prompts([5, 9])
    eng = DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                       prefill_align=4,
                       speculative=_self_draft(model, variables))
    eng.submit(prompts[0], max_new_tokens=24, deadline=0.02,
               meta={"i": 0})
    eng.submit(prompts[1], max_new_tokens=6, meta={"i": 1})
    out = list(eng.step())
    time.sleep(0.05)  # expires while speculation is mid-stream
    while eng.has_work():
        out.extend(eng.step())
    res = {r["i"]: r for r in out}
    assert res[0]["error"] == "deadline_exceeded"
    assert "error" not in res[1]
    np.testing.assert_array_equal(
        res[1]["tokens"], _want(model, variables, prompts[1], 6))
    eng.close()


def test_weight_swap_invalidates_in_flight_drafts():
    """Swap weights while a draft is mid-stream: the spec arm must
    match a baseline arm that swaps at the SAME committed-token
    boundary — the stale draft is invalidated, never verified against
    the new weights' cache."""
    model, variables = _model()
    _, variables2 = _model(seed=2)
    p = _prompts([7], seed=5)[0]

    eng = DecodeEngine(model, variables, slots=1, buckets=[MAXLEN],
                       prefill_align=4,
                       speculative=_self_draft(model, variables))
    eng.submit(p, max_new_tokens=12, meta={"i": 0})
    out = list(eng.step())  # prefill: first token
    out.extend(eng.step())  # one speculative quantum (k+1 commits)
    c = len(eng._pools[0].reqs[0].tokens)
    assert c > 1  # the draft really was mid-stream
    eng.swap_variables(variables2)
    while eng.has_work():
        out.extend(eng.step())
    got = out[0]["tokens"]

    base = DecodeEngine(model, variables, slots=1, buckets=[MAXLEN],
                        prefill_align=4)
    base.submit(p, max_new_tokens=12, meta={"i": 0})
    bout = []
    while True:  # one committed token per step: lands exactly on c
        bout.extend(base.step())
        req = base._pools[0].reqs[0]
        if req is not None and len(req.tokens) >= c:
            break
    base.swap_variables(variables2)
    while base.has_work():
        bout.extend(base.step())
    np.testing.assert_array_equal(got, bout[0]["tokens"])
    eng.close()
    base.close()


def test_paged_preemption_with_speculation_is_byte_identical():
    """The seeded preemption drill under speculation: the victim's
    draft state is recompute-class, so preempt -> readmit -> re-draft
    still lands the envelope-identical tokens."""
    model, variables = _model()
    pl = _prompts([9, 9, 5])
    tel = telemetry.enable()
    try:
        eng = DecodeEngine(model, variables, slots=3, buckets=[32],
                           prefill_align=4, kv_pages=8,
                           speculative=_self_draft(model, variables))
        eng.submit(pl[0], max_new_tokens=12, priority=0,
                   meta={"i": 0})
        eng.submit(pl[1], max_new_tokens=12, priority=0,
                   meta={"i": 1})
        out = list(eng.step())
        eng.submit(pl[2], max_new_tokens=10, priority=2,
                   meta={"i": 2})
        while eng.has_work():
            out.extend(eng.step())
        res = {r["i"]: r for r in out}
        for i, n in [(0, 12), (1, 12), (2, 10)]:
            assert "error" not in res[i]
            np.testing.assert_array_equal(
                res[i]["tokens"], _want(model, variables, pl[i], n))
        snap = tel.metrics.snapshot()["counters"]
        assert sum(v for k, v in snap.items()
                   if k.startswith("serving_preemptions_total")) >= 1
        assert (snap["serving_pages_allocated_total"]
                == snap["serving_pages_freed_total"])
        assert eng.free_pages() == 8
    finally:
        telemetry.disable()
        eng.close()


# ---------------------------------------------------------------------
# guard rails: compile pin + telemetry surfaces


def test_compile_guard_pins_speculative_program_set():
    model, variables = _model()
    prompts = _prompts([5, 9, 3, 7, 6, 11])
    reqs = [{"prompt": p, "max_new_tokens": 8, "i": i}
            for i, p in enumerate(prompts)]
    eng = DecodeEngine(model, variables, slots=3, buckets=[MAXLEN],
                       prefill_align=4,
                       speculative=_self_draft(model, variables))
    list(eng.run(reqs))
    counts = dict(eng.compile_counts)
    # the spec program set is exactly {verify x 2 widths, draft}
    assert ("verify", MAXLEN, 1) in counts
    assert ("verify", MAXLEN, 4) in counts
    assert ("draft_step", MAXLEN) in counts
    list(eng.run(list(reversed(reqs)), ordered=False))
    assert dict(eng.compile_counts) == counts  # steady state: no new
    eng.close()


def test_spec_telemetry_counters_and_slo_signal():
    model, variables = _model()
    prompts = _prompts([5, 9, 3])
    tel = telemetry.enable()
    try:
        eng = DecodeEngine(model, variables, slots=3, buckets=[MAXLEN],
                           prefill_align=4,
                           speculative=_self_draft(model, variables))
        list(eng.run([{"prompt": p, "max_new_tokens": 8}
                      for p in prompts]))
        eng.close()
        reg = tel.metrics
        prop = reg.sum_counter("serving_spec_proposed_total")
        acc = reg.sum_counter("serving_spec_accepted_total")
        assert prop > 0 and acc == prop  # draft == target
        snap = reg.snapshot()
        assert any(k.startswith("serving_spec_accept_len")
                   for k in snap["histograms"])
        w = telemetry.SLOWatchdog(reg)
        v = w.evaluate()
        assert v["signals"]["spec_accept_rate"] == pytest.approx(1.0)
        assert "spec_accept_rate" not in v["breaches"]
    finally:
        telemetry.disable()


def test_spec_accept_rate_slo_breaches_low():
    reg = telemetry.MetricsRegistry()
    reg.counter("serving_spec_proposed_total", bucket=32).inc(100)
    reg.counter("serving_spec_accepted_total", bucket=32).inc(3)
    v = telemetry.SLOWatchdog(reg).evaluate()
    assert v["signals"]["spec_accept_rate"] == pytest.approx(0.03)
    # 0.03 <= critical_at 0.05 on an INVERTED signal
    assert v["breaches"]["spec_accept_rate"]["level"] == "critical"


def test_gateway_forwards_speculative_only_when_set():
    model, variables = _model()
    eng = DecodeEngine(model, variables, slots=2, buckets=[MAXLEN],
                       prefill_align=4,
                       speculative=_self_draft(model, variables))
    prompts = _prompts([5, 9])
    with ServingGateway([EngineReplica(eng)]) as gw:
        rid = gw.submit(prompts[0], max_new_tokens=6,
                        speculative=False)
        r = gw.result(rid, timeout=60)
        np.testing.assert_array_equal(
            r["tokens"], _want(model, variables, prompts[0], 6))
        assert eng.spec_stats()["proposed"] == 0  # opt-out forwarded
        # unset: engine default (on); the key never rides into meta
        out = list(gw.run([{"prompt": prompts[1],
                            "max_new_tokens": 6, "i": 1}]))
        np.testing.assert_array_equal(
            out[0]["tokens"], _want(model, variables, prompts[1], 6))
        assert eng.spec_stats()["proposed"] > 0
    eng.close()
