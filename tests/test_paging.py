"""Block-paged KV allocator + QoS scheduler (``paging`` + the engine's
``kv_pages`` arm, ISSUE 13): the paged lowering gathers slot pages into
the exact envelope layout and runs the UNCHANGED legacy programs, so
greedy tokens must be BYTE-IDENTICAL to the envelope pools — across
admission orders, through preempt→swap→readmit cycles, and under
weight swaps — while the allocator enforces priority classes and
per-tenant quotas and the compile guard pins a bounded paged program
set."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.models import ModelSpec, generate, model_config
from distkeras_tpu.paging import PageAllocator, pages_for
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


# ---------------------------------------------------------------------
# allocator unit surface
# ---------------------------------------------------------------------


def test_allocator_freelist_and_quota():
    a = PageAllocator(6, 4, tenant_quota={"t0": 3})
    assert a.n_free == 6 and pages_for(9, 4) == 3
    p0 = a.alloc(3, "t0")
    assert p0 == [1, 2, 3]  # deterministic pop order
    assert a.alloc(1, "t0") is None          # quota, not capacity
    assert not a.fits_quota(1, "t0") and a.fits_quota(3, "t1")
    p1 = a.alloc(2, "t1")                    # unlisted tenant: unbounded
    assert p1 == [4, 5] and a.n_free == 1
    a.free(p0, "t0")
    assert a.n_free == 4 and a.fits_quota(3, "t0")
    assert a.stats()["allocated_total"] == 5
    assert a.stats()["freed_total"] == 3


# ---------------------------------------------------------------------
# parity: the tentpole acceptance bar
# ---------------------------------------------------------------------


def test_paged_matches_envelope_any_admission_order():
    """Byte-identical greedy tokens, envelope pool vs paged pool, for
    the same ragged workload in BOTH admission orders — the gather →
    legacy-program → scatter lowering is structurally exact."""
    model, variables = _model()
    prompts = _prompts([5, 9, 3, 7, 5, 11, 4, 6])
    n_new = [4, 7, 3, 6, 5, 8, 2, 7]
    reqs = [{"prompt": p, "max_new_tokens": n, "i": i}
            for i, (p, n) in enumerate(zip(prompts, n_new))]
    kw = dict(slots=3, buckets=[16, 32], prefill_align=4,
              steps_per_sync=2)
    env = DecodeEngine(model, variables, **kw)
    base = {r["i"]: r["tokens"] for r in env.run(reqs)}
    pag = DecodeEngine(model, variables, kv_pages=24, **kw)
    fwd = {r["i"]: r["tokens"] for r in pag.run(reqs)}
    rev = {r["i"]: r["tokens"] for r in pag.run(list(reversed(reqs)),
                                                ordered=False)}
    for i in base:
        np.testing.assert_array_equal(fwd[i], base[i])
        np.testing.assert_array_equal(rev[i], base[i])
    assert pag.free_pages() == 24  # everything returned to the pool
    assert env.free_pages() is None


def test_preempt_swap_readmit_is_byte_identical():
    """The seeded preemption drill: a late high-priority arrival is
    admitted by preempting low-priority work (pages swapped to host),
    the victim readmits page-exact, and EVERY request still produces
    the envelope-identical greedy tokens."""
    model, variables = _model()
    pl = _prompts([9, 9, 5])
    tel = telemetry.enable()
    try:
        eng = DecodeEngine(model, variables, slots=3, buckets=[32],
                           prefill_align=4, steps_per_sync=2,
                           kv_pages=8)
        eng.submit(pl[0], max_new_tokens=12, priority=0,
                   meta={"i": 0})
        eng.submit(pl[1], max_new_tokens=12, priority=0,
                   meta={"i": 1})
        out = list(eng.step())  # both low-pri admitted + decoding
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
        assert snap.get("serving_readmissions_total", 0) >= 1
        assert snap.get("serving_pages_swapped_total", 0) >= 1
        # ledger balance: every allocated page came back
        assert (snap["serving_pages_allocated_total"]
                == snap["serving_pages_freed_total"])
        assert eng.free_pages() == 8
    finally:
        telemetry.disable()


def test_recompute_preemption_finishes_every_request():
    """``preemption="recompute"`` re-prefills prompt + generated as an
    extended prompt instead of holding host bytes; the drill still
    completes every request with its full token budget."""
    model, variables = _model()
    pl = _prompts([9, 9, 5])
    eng = DecodeEngine(model, variables, slots=3, buckets=[32],
                       prefill_align=4, steps_per_sync=2, kv_pages=8,
                       preemption="recompute")
    eng.submit(pl[0], max_new_tokens=12, priority=0, meta={"i": 0})
    eng.submit(pl[1], max_new_tokens=12, priority=0, meta={"i": 1})
    out = list(eng.step())
    eng.submit(pl[2], max_new_tokens=10, priority=2, meta={"i": 2})
    while eng.has_work():
        out.extend(eng.step())
    res = {r["i"]: r for r in out}
    for i, n in [(0, 12), (1, 12), (2, 10)]:
        assert "error" not in res[i], res[i].get("error")
        assert len(res[i]["tokens"]) == n
    # the high-priority arrival (never preempted) is exact
    np.testing.assert_array_equal(res[2]["tokens"],
                                  _want(model, variables, pl[2], 10))


def test_preemption_none_sheds_the_grower():
    """With preemption off, pool exhaustion sheds the growing request
    as ``error="kv_pages_exhausted"`` instead of parking it.  Each
    request's WORST-CASE footprint fits the pool alone (so admission
    accepts both), but jointly they exhaust it mid-decode."""
    model, variables = _model()
    pl = _prompts([9, 9])
    eng = DecodeEngine(model, variables, slots=2, buckets=[32],
                       prefill_align=4, steps_per_sync=2, kv_pages=6,
                       preemption="none")
    eng.submit(pl[0], max_new_tokens=7, meta={"i": 0})
    eng.submit(pl[1], max_new_tokens=7, meta={"i": 1})
    out = []
    while eng.has_work():
        out.extend(eng.step())
    assert len(out) == 2
    res = {r["i"]: r for r in out}
    errs = [r for r in out if "error" in r]
    assert errs and all(r["error"] == "kv_pages_exhausted"
                        for r in errs)
    # the shed request's pages freed room for the survivor, whose
    # tokens are still envelope-exact
    ok = [r for r in out if "error" not in r]
    for r in ok:
        np.testing.assert_array_equal(
            r["tokens"], _want(model, variables, pl[r["i"]], 7))
    assert eng.free_pages() == 6


# ---------------------------------------------------------------------
# prefix store + paging are one mechanism
# ---------------------------------------------------------------------


def test_paged_prefix_and_chunked_prefill_parity():
    """Prefix hits install straight into pages (segment shape == page
    shape) and chunked prefill runs through the page tables; greedy
    tokens still match solo generate()."""
    model, variables = _model()
    rng = np.random.default_rng(5)
    shared = rng.integers(0, VOCAB, (12,)).astype(np.int32)
    ps = [np.concatenate([shared,
                          rng.integers(0, VOCAB, (k,)
                                       ).astype(np.int32)])
          for k in [3, 5, 2, 6]]
    eng = DecodeEngine(model, variables, slots=2, buckets=[32],
                       prefill_align=4, steps_per_sync=2, kv_pages=16,
                       prefix_cache_bytes=1 << 20, prefill_chunk=8)
    outs = list(eng.run([{"prompt": p, "max_new_tokens": 6, "i": i}
                         for i, p in enumerate(ps)]))
    for r in outs:
        np.testing.assert_array_equal(
            r["tokens"], _want(model, variables, ps[r["i"]], 6))
    st = eng.prefix_stats()
    assert st["hits"] > 0  # later arrivals reused donated pages
    assert eng.free_pages() == 16


def test_weight_swap_invalidates_parked_swap_kv():
    """A ``swap_variables`` while a request is parked invalidates its
    host-swapped KV exactly like the prefix store: readmission
    degrades to recompute under the NEW weights and the request still
    finishes cleanly (never resumes stale KV)."""
    model, variables = _model()
    variables2 = model.init(jax.random.key(9),
                            jnp.zeros((2, MAXLEN), jnp.int32))
    pl = _prompts([9, 9, 5])
    eng = DecodeEngine(model, variables, slots=3, buckets=[32],
                       prefill_align=4, steps_per_sync=2, kv_pages=8)
    eng.submit(pl[0], max_new_tokens=12, priority=0, meta={"i": 0})
    eng.submit(pl[1], max_new_tokens=12, priority=0, meta={"i": 1})
    out = list(eng.step())
    eng.submit(pl[2], max_new_tokens=10, priority=2, meta={"i": 2})
    out.extend(eng.step())  # growth/admission preempts a low-pri
    assert eng.paging_stats()["parked"] >= 1
    eng.swap_variables(variables2)
    while eng.has_work():
        out.extend(eng.step())
    res = {r["i"]: r for r in out}
    for i in (0, 1, 2):
        assert "error" not in res[i], res[i].get("error")
    assert eng.free_pages() == 8


# ---------------------------------------------------------------------
# QoS semantics
# ---------------------------------------------------------------------


def test_tenant_quota_blocks_only_the_hog():
    """A tenant at its page quota waits while OTHER tenants keep
    admitting through the same pool — quota blocks are skipped, not
    head-of-line."""
    model, variables = _model()
    pl = _prompts([5, 5, 5])
    eng = DecodeEngine(model, variables, slots=3, buckets=[32],
                       prefill_align=4, steps_per_sync=2, kv_pages=12,
                       tenant_quota={"hog": 3})
    eng.submit(pl[0], max_new_tokens=4, tenant="hog", meta={"i": 0})
    eng.submit(pl[1], max_new_tokens=4, tenant="hog", meta={"i": 1})
    eng.submit(pl[2], max_new_tokens=4, tenant="other", meta={"i": 2})
    out = []
    while eng.has_work():
        out.extend(eng.step())
    res = {r["i"]: r for r in out}
    for i in (0, 1, 2):
        assert "error" not in res[i]
        np.testing.assert_array_equal(
            res[i]["tokens"], _want(model, variables, pl[i], 4))
    used = eng.paging_stats()["tenants"]
    assert used == {}  # all quota returned


def test_parked_deadline_expires_into_an_error_result():
    """The satellite deadline fix: a preempted request's deadline
    keeps ticking while parked and expires into the same
    ``deadline_exceeded`` error row as a queued request.  The parked
    deadline is backdated directly so the test is deterministic under
    arbitrary compile-time skew."""
    model, variables = _model()
    pl = _prompts([9, 9, 5])
    eng = DecodeEngine(model, variables, slots=3, buckets=[32],
                       prefill_align=4, steps_per_sync=2, kv_pages=8)
    eng.submit(pl[0], max_new_tokens=12, priority=0, deadline=60.0,
               meta={"i": 0})
    eng.submit(pl[1], max_new_tokens=12, priority=0, deadline=60.0,
               meta={"i": 1})
    out = list(eng.step())
    # the high-priority arrival preempts a low-pri request when its
    # page table grows past the free pool (not at admission)
    eng.submit(pl[2], max_new_tokens=10, priority=2, meta={"i": 2})
    for _ in range(8):
        out.extend(eng.step())
        if eng.paging_stats()["parked"] >= 1:
            break
    assert eng.paging_stats()["parked"] >= 1
    for req in eng._parked:  # expire IN PLACE while parked
        req.deadline = telemetry.now() - 1.0
    while eng.has_work():
        out.extend(eng.step())
    res = {r["i"]: r for r in out}
    assert "error" not in res[2]
    np.testing.assert_array_equal(
        res[2]["tokens"], _want(model, variables, pl[2], 10))
    expired = [r for r in (res[0], res[1]) if "error" in r]
    assert expired and all(r["error"] == "deadline_exceeded"
                           for r in expired)
    assert eng.free_pages() == 8


def test_submit_validation_paged():
    model, variables = _model()
    eng = DecodeEngine(model, variables, slots=2, buckets=[32],
                       prefill_align=4, kv_pages=4,
                       tenant_quota={"small": 2})
    p = _prompts([5])[0]
    with pytest.raises(ValueError, match="kv_pages"):
        eng.submit(p, max_new_tokens=20)  # worst case: 8 pages > 4
    with pytest.raises(ValueError, match="tenant_quota"):
        eng.submit(p, max_new_tokens=4, tenant="small")
    with pytest.raises(ValueError, match="priority"):
        eng.submit(p, max_new_tokens=2, priority=3)
    with pytest.raises(ValueError, match="priority"):
        eng.submit(p, max_new_tokens=2, priority="high")


def test_knob_validation():
    model, variables = _model()
    with pytest.raises(ValueError, match="kv_pages"):
        DecodeEngine(model, variables, kv_pages=0)
    with pytest.raises(ValueError, match="page_size"):
        DecodeEngine(model, variables, kv_pages=4, page_size=0)
    with pytest.raises(ValueError, match="whole number of pages"):
        DecodeEngine(model, variables, buckets=[32], kv_pages=4,
                     page_size=5)
    with pytest.raises(ValueError, match="prefill_align"):
        DecodeEngine(model, variables, kv_pages=4, prefill_align=4,
                     page_size=8, prefix_cache_bytes=1 << 20)
    with pytest.raises(ValueError, match="preemption"):
        DecodeEngine(model, variables, kv_pages=4, prefill_align=4,
                     preemption="maybe")
    with pytest.raises(ValueError, match="recompute_below"):
        DecodeEngine(model, variables, kv_pages=4, prefill_align=4,
                     recompute_below=-1)
    with pytest.raises(ValueError, match="tenant_quota"):
        DecodeEngine(model, variables, kv_pages=4, prefill_align=4,
                     tenant_quota=0)


# ---------------------------------------------------------------------
# compile guard: the paged program set is bounded too
# ---------------------------------------------------------------------


def test_paged_compile_guard_steady_state():
    """One ``paged_step`` trace per bucket, one ``paged_prefill`` per
    (bucket, padded length); re-running ragged workloads in shuffled
    orders — preemptions included — compiles NOTHING new."""
    tel = telemetry.enable()
    try:
        model, variables = _model()
        eng = DecodeEngine(model, variables, slots=2,
                           buckets=[16, 32], prefill_align=8,
                           max_new_tokens=4, kv_pages=10)
        mk = lambda ls, seed: [{"prompt": p}  # noqa: E731
                               for p in _prompts(ls, seed=seed)]
        list(eng.run(mk([3, 9, 5, 14, 7, 2, 11, 8], 11)))
        m = tel.metrics
        assert m.counter("compiles_total", kind="paged_step",
                         bucket=16).value == 1
        assert m.counter("compiles_total", kind="paged_step",
                         bucket=32).value == 1
        for labels, c in m.collect("compiles_total",
                                   kind="paged_prefill"):
            assert c.value == 1, labels
        # the legacy kinds never trace on a paged engine
        assert not m.collect("compiles_total", kind="step")
        assert not m.collect("compiles_total", kind="prefill")
        before = {k: v for k, v
                  in m.snapshot()["counters"].items()
                  if k.startswith("compiles_total")}
        list(eng.run(mk([8, 11, 2, 7, 14, 5, 9, 3], 12)))
        list(eng.run(mk([7, 7, 3, 9, 2], 13)))
        after = {k: v for k, v
                 in m.snapshot()["counters"].items()
                 if k.startswith("compiles_total")}
        assert after == before
    finally:
        telemetry.disable()


# ---------------------------------------------------------------------
# capacity: what per-page billing buys at one KV budget
# ---------------------------------------------------------------------


@pytest.mark.parametrize("budget_slots", [2, 3])
def test_paged_holds_more_live_slots_in_the_same_kv_positions(
        budget_slots):
    """One KV budget, counted in cached token positions: the envelope
    arm spends it as ``budget_slots`` whole envelopes, the paged arm
    as that many positions in pages.  Under a mostly-short workload
    the envelope arm peaks at exactly its slot count and the paged arm
    strictly above it — with the same tokens."""
    tel = telemetry.enable()
    try:
        model, variables = _model()
        page = 4
        kv_pages = budget_slots * MAXLEN // page
        lengths = [22, 4, 3, 5, 4, 3, 5, 4, 3, 5]
        reqs = [{"prompt": p, "max_new_tokens": 3, "i": i}
                for i, p in enumerate(_prompts(lengths, seed=5))]
        occ = tel.metrics.gauge("serving_slot_occupancy",
                                bucket=MAXLEN)

        def drive(**kw):
            eng = DecodeEngine(model, variables, buckets=[MAXLEN],
                               prefill_align=page, **kw)
            for r in reqs:
                eng.submit(r["prompt"], max_new_tokens=3,
                           meta={"i": r["i"]})
            peak, out = 0, {}
            while eng.has_work():
                for r in eng.step():
                    assert r.get("error") is None, r
                    out[r["i"]] = r["tokens"]
                peak = max(peak, int(occ.value))
            eng.close()
            return peak, out

        env_peak, env_tok = drive(slots=budget_slots)
        pag_peak, pag_tok = drive(slots=8, kv_pages=kv_pages,
                                  page_size=page)
        assert kv_pages * page == budget_slots * MAXLEN
        assert env_peak == budget_slots
        assert pag_peak > env_peak
        for i in env_tok:
            np.testing.assert_array_equal(pag_tok[i], env_tok[i])
    finally:
        telemetry.disable()
