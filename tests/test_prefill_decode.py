"""Disaggregated prefill/decode (ISSUE 19): the KV page interchange
(``export_prefix``/``import_prefix`` + the ``"kv"`` wire codec), the
two-stage ``PrefillDecodeRouter``, the page-headroom routing fix in
``ServingGateway``, the ``prefill_heavy`` trace tenant, and the new
tail-latency SLO signals.

The correctness bar everywhere is the engine's own: a request admitted
on a decode replica with imported KV blocks must produce the same
greedy tokens as a solo ``DecodeEngine`` / ``models.generate`` run —
byte-identical, exactly once, through kills and requeues."""

import socket
import time

import jax
import numpy as np
import pytest

from distkeras_tpu import flight_recorder, telemetry
from distkeras_tpu.analysis import racecheck
from distkeras_tpu.gateway import (EngineReplica, PrefillDecodeRouter,
                                   RemoteReplica, ReplicaServer,
                                   ServingGateway)
from distkeras_tpu.models import ModelSpec, generate, model_config
from distkeras_tpu.parallel import transport
from distkeras_tpu.parallel.faults import ChaosTransport
from distkeras_tpu.serving import (DecodeEngine, pack_kv_blocks,
                                   unpack_kv_blocks)

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True)
def _racecheck():
    racecheck.enable()
    yield
    reports = racecheck.disable()
    assert not reports, "\n".join(str(r) for r in reports)


MAXLEN, VOCAB, ALIGN = 32, 37, 4


@pytest.fixture(scope="module")
def mv():
    spec = model_config("transformer_lm", (MAXLEN,),
                        input_dtype="int32", vocab_size=VOCAB,
                        num_layers=1, d_model=32, num_heads=2,
                        max_len=MAXLEN, dtype="float32")
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           np.zeros((2, MAXLEN), np.int32))
    return model, variables


def _prompts(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (t,)).astype(np.int32)
            for t in lengths]


def _want(mv, prompt, n_new):
    model, variables = mv
    return np.asarray(generate(model, variables, prompt[None, :],
                               max_new_tokens=n_new))[0, len(prompt):]


def _engine(mv, **kw):
    model, variables = mv
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_align", ALIGN)
    kw.setdefault("max_new_tokens", 5)
    kw.setdefault("prefix_cache_bytes", 1 << 22)
    return DecodeEngine(model, variables, **kw)


# ---- the KV page-block wire codec -------------------------------------


def test_kv_codec_socket_roundtrip():
    """``pack_kv_blocks`` gather-sent over a REAL socket and received
    with ``recv_msg_into`` reproduces every leaf byte-for-byte —
    shapes, dtypes (including an ml_dtypes one), block structure."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    leaves = [
        lambda: rng.normal(size=(1, 2, ALIGN, 8)).astype(np.float32),
        lambda: rng.normal(size=(1, 2, ALIGN, 8)).astype(
            ml_dtypes.bfloat16),
        lambda: rng.integers(0, 99, (1, ALIGN)).astype(np.int32),
    ]
    export = {"prompt": np.arange(3 * ALIGN, dtype=np.int32),
              "n_blocks": 3, "weights_ver": 7,
              "blocks": [[mk() for mk in leaves] for _ in range(3)]}
    a, b = socket.socketpair()
    try:
        transport.send_msg_gather(a, *pack_kv_blocks(export))
        got = unpack_kv_blocks(transport.recv_msg_into(b))
    finally:
        a.close()
        b.close()
    np.testing.assert_array_equal(got["prompt"], export["prompt"])
    assert got["n_blocks"] == 3 and got["weights_ver"] == 7
    for want_blk, got_blk in zip(export["blocks"], got["blocks"]):
        for w, g in zip(want_blk, got_blk):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(
                np.asarray(g).view(np.uint8),
                np.asarray(w).view(np.uint8))


def test_kv_codec_rejects_garbage():
    with pytest.raises(ValueError):
        unpack_kv_blocks(memoryview(b"Xjunk"))


# ---- export -> import -> byte-identical admission ---------------------


@pytest.mark.parametrize("paged", [False, True],
                         ids=["envelope", "paged"])
def test_export_import_parity(mv, paged):
    """Blocks exported from a prefill-role engine and imported into a
    fresh decode-role engine admit the request through the prefix-hit
    path: tokens byte-identical to ``models.generate``, on BOTH the
    envelope and the paged engine."""
    prompt = _prompts([13])[0]
    src = _engine(mv, prefill_chunk=ALIGN)
    list(src.run([{"prompt": prompt, "max_new_tokens": 4}]))
    export = src.export_prefix(prompt)
    assert export is not None
    assert export["n_blocks"] == len(prompt) // ALIGN

    kw = dict(kv_pages=32, page_size=ALIGN) if paged else {}
    dst = _engine(mv, **kw)
    assert dst.match_blocks(prompt) == 0
    installed = dst.import_prefix(prompt, export["blocks"],
                                  export["weights_ver"])
    assert installed == export["n_blocks"]
    assert dst.match_blocks(prompt) == export["n_blocks"]
    [res] = list(dst.run([{"prompt": prompt, "max_new_tokens": 4}]))
    np.testing.assert_array_equal(res["tokens"], _want(mv, prompt, 4))


def test_export_import_parity_through_wire_codec(mv):
    """Same parity bar with the blocks round-tripped through the wire
    codec bytes (what actually crosses the socket)."""
    prompt = _prompts([9], seed=5)[0]
    src = _engine(mv)
    list(src.run([{"prompt": prompt, "max_new_tokens": 5}]))
    export = src.export_prefix(prompt)
    body = b"".join(bytes(p) for p in pack_kv_blocks(export))
    got = unpack_kv_blocks(memoryview(body))
    dst = _engine(mv)
    assert dst.import_prefix(got["prompt"], got["blocks"],
                             got["weights_ver"]) == got["n_blocks"]
    [res] = list(dst.run([{"prompt": prompt, "max_new_tokens": 5}]))
    np.testing.assert_array_equal(res["tokens"], _want(mv, prompt, 5))


def test_import_prefix_guards(mv):
    """Stale-weights imports are refused; re-imports of blocks the
    store already holds install nothing (the cluster-tier probe's
    contract: ``match_blocks`` says what shipping would add)."""
    prompt = _prompts([8], seed=7)[0]
    src = _engine(mv)
    list(src.run([{"prompt": prompt, "max_new_tokens": 3}]))
    export = src.export_prefix(prompt)
    dst = _engine(mv)
    assert dst.import_prefix(prompt, export["blocks"],
                             weights_ver=export["weights_ver"] + 1) == 0
    assert dst.match_blocks(prompt) == 0
    assert dst.import_prefix(prompt, export["blocks"],
                             export["weights_ver"]) == 2
    # second ship: everything already local, nothing installed
    assert dst.import_prefix(prompt, export["blocks"],
                             export["weights_ver"]) == 0


# ---- the two-stage router ---------------------------------------------


def test_router_end_to_end_parity_and_counters(mv, tmp_path):
    """Mixed short/long prompts through 1 prefill + 2 decode replicas
    (one paged, one envelope): every result byte-identical, pages
    shipped counted, zero requeues, healthz ok."""
    tel = telemetry.enable()
    try:
        router = PrefillDecodeRouter(
            [EngineReplica(_engine(mv, prefill_chunk=ALIGN),
                           name="p0")],
            [EngineReplica(_engine(mv, kv_pages=32, page_size=ALIGN),
                           name="d0"),
             EngineReplica(_engine(mv), name="d1")],
            block_size=ALIGN)
        with router:
            work = [(p, 3 + i % 3) for i, p in enumerate(
                _prompts([3, 12, 7, 13, 2, 9], seed=11))]
            rids = [router.submit(p, max_new_tokens=n)
                    for p, n in work]
            results = [router.result(r, timeout=120) for r in rids]
            hz = router.healthz()
        # compile stalls on these UNWARMED engines legitimately land
        # in the inter-token histogram and can trip the SLO rollup, so
        # pin pool liveness, not the SLO verdict
        assert hz["alive"] == {"prefill": 1, "decode": 2}, hz
        assert len({r["request_id"] for r in results}) == len(work)
        for (p, n), r in zip(work, results):
            assert r.get("error") is None, r
            np.testing.assert_array_equal(r["tokens"],
                                          _want(mv, p, n))
        counters = tel.metrics.snapshot()["counters"]
        assert counters["serving_kv_pages_shipped_total"] > 0
        assert counters["serving_handoff_requeue_total"] == 0
    finally:
        telemetry.disable()


def test_monolithic_gateway_never_touches_the_handoff_path(mv):
    """The same prompts through a plain ``ServingGateway`` over
    engines with the same prefix stores: right tokens, and not one
    page shipped nor a handoff requeued — the counters are the
    router's alone."""
    tel = telemetry.enable()
    try:
        work = [(p, 3 + i % 3) for i, p in enumerate(
            _prompts([3, 12, 7, 12], seed=11))]
        with ServingGateway(
                [EngineReplica(_engine(mv), name=f"m{i}")
                 for i in range(2)], policy="least_loaded") as gw:
            rids = [gw.submit(p, max_new_tokens=n) for p, n in work]
            results = [gw.result(r, timeout=120) for r in rids]
        for (p, n), r in zip(work, results):
            assert r.get("error") is None, r
            np.testing.assert_array_equal(r["tokens"],
                                          _want(mv, p, n))
        counters = tel.metrics.snapshot()["counters"]
        assert counters.get("serving_kv_pages_shipped_total", 0) == 0
        assert counters.get("serving_handoff_requeue_total", 0) == 0
    finally:
        telemetry.disable()


def test_router_survives_dead_prefill_pool(mv):
    """A dead prefill pool degrades to decode-side recompute — same
    tokens, no lost request."""
    prefill = EngineReplica(_engine(mv), name="p0")
    router = PrefillDecodeRouter(
        [prefill], [EngineReplica(_engine(mv), name="d0")],
        block_size=ALIGN, retries=1, backoff_base=0.001)
    with router:
        prefill.kill()
        p = _prompts([10], seed=2)[0]
        res = router.result(router.submit(p, max_new_tokens=4),
                            timeout=120)
        assert res.get("error") is None, res
        np.testing.assert_array_equal(res["tokens"], _want(mv, p, 4))
        hz = router.healthz()
        assert hz["alive"]["prefill"] == 0, hz
        assert hz["state"] in ("degraded", "critical"), hz


def test_chaos_kill_decode_mid_handoff_exactly_once(mv, tmp_path):
    """The ISSUE 19 chaos bar: socket decode replicas under seeded
    ``ChaosTransport``, one killed with handoffs in flight.  Every
    request completes exactly once with byte-identical tokens, and the
    requeue path fired (counter + flight events)."""
    tel = telemetry.enable()
    flight_recorder.start(tmp_path / "fdr")
    servers = [ReplicaServer(EngineReplica(
        _engine(mv, slots=1), name=f"s{i}")).start() for i in range(3)]
    try:
        remotes = [RemoteReplica("127.0.0.1", s.address[1],
                                 name=f"s{i}")
                   for i, s in enumerate(servers)]
        ports = {servers[1].address[1], servers[2].address[1]}
        work = [(p, 3) for p in _prompts([12, 9, 13, 8, 11, 10],
                                         seed=13)]
        with ChaosTransport(seed=11, reset_rate=0.1,
                            max_injections=3, skip_ops=4,
                            target_ports=ports):
            router = PrefillDecodeRouter(
                [remotes[0]], [remotes[1], remotes[2]],
                block_size=ALIGN, retries=8, backoff_base=0.005)
            with router:
                rids = [router.submit(p, max_new_tokens=n)
                        for p, n in work]
                time.sleep(0.05)  # let handoffs reach the victim
                servers[1].kill()
                results = [router.result(r, timeout=300)
                           for r in rids]
        assert len({r["request_id"] for r in results}) == len(work)
        for (p, n), r in zip(work, results):
            assert r.get("error") is None, r
            np.testing.assert_array_equal(r["tokens"],
                                          _want(mv, p, n))
        counters = tel.metrics.snapshot()["counters"]
        assert counters["serving_handoff_requeue_total"] >= 1, counters
        events = flight_recorder.active().read_events()
        assert any(e["kind"] == "handoff_requeue" for e in events)
    finally:
        for s in servers:
            s.stop()
        flight_recorder.stop()
        telemetry.disable()


# ---- page-headroom routing (the satellite bugfix) ---------------------


class _PagedStub:
    """Replica stub with a page pool: records what it served."""

    def __init__(self, name, free, load=0):
        self.name = name
        self._free = free
        self._load = load
        self.alive = True
        self.dispatched: list = []

    def start(self):
        return self

    def load(self):
        return self._load

    def free_pages(self):
        return self._free

    def dispatch(self, spec, on_result):
        self.dispatched.append(spec)
        on_result({"request_id": spec["request_id"],
                   "tokens": np.asarray([1], np.int32)})

    def health(self):
        return {"alive": True, "state": "ok", "load": self._load}


def test_gateway_skips_page_exhausted_replicas():
    """``free_pages() == 0`` makes a replica ineligible for fresh
    paged admissions even when it is the least loaded..."""
    empty = _PagedStub("empty", free=0, load=0)
    roomy = _PagedStub("roomy", free=64, load=5)
    with ServingGateway([empty, roomy], policy="least_loaded") as gw:
        for _ in range(4):
            gw.result(gw.submit([1, 2, 3]), timeout=5)
    assert len(roomy.dispatched) == 4 and not empty.dispatched


def test_gateway_handoff_still_lands_on_exhausted_replica():
    """...but a decode-only handoff is exempt (its pages were already
    accounted by the KV import), and when EVERY replica is exhausted
    fresh admissions fall through to the engine's own back-pressure
    instead of erroring."""
    empty = _PagedStub("empty", free=0, load=0)
    roomy = _PagedStub("roomy", free=64, load=5)
    with ServingGateway([empty, roomy], policy="least_loaded") as gw:
        gw.result(gw.submit([1, 2, 3], handoff=True), timeout=5)
    assert len(empty.dispatched) == 1
    # the routing flag rides to the replica (EngineReplica._exec
    # drops it before the engine's submit — stubs see it verbatim)
    assert empty.dispatched[0].get("handoff") is True

    both_empty = [_PagedStub("a", free=0), _PagedStub("b", free=0)]
    with ServingGateway(both_empty, policy="least_loaded") as gw:
        assert gw.result(gw.submit([1, 2]),
                         timeout=5).get("error") is None
    assert sum(len(s.dispatched) for s in both_empty) == 1


# ---- simulator: the prefill_heavy tenant ------------------------------


def test_trace_prefill_heavy_tenant_shape():
    from distkeras_tpu.simulator import TraceSpec, generate_trace

    spec = TraceSpec(duration_s=60.0, mean_qps=4.0, seed=5,
                     prompt_median=8.0, prompt_sigma=0.3,
                     prompt_min=3, prompt_max=400,
                     output_alpha=2.0, output_min=4, output_max=64,
                     heavy_prompt_median=128.0,
                     heavy_prompt_sigma=0.25, heavy_output_max=8,
                     tenants=(("steady", 1.0, 1),
                              ("flood", 1.0, 1, "prefill_heavy")))
    arrivals = generate_trace(spec).arrivals
    heavy = [a for a in arrivals if a.tenant == "flood"]
    plain = [a for a in arrivals if a.tenant == "steady"]
    assert len(heavy) > 10 and len(plain) > 10
    # long lognormal prompts, short clipped outputs
    assert (np.median([len(a.prompt) for a in heavy])
            > 4 * np.median([len(a.prompt) for a in plain]))
    assert all(a.max_new <= 8 for a in heavy)
    assert any(a.max_new > 8 for a in plain)


def test_trace_heavy_class_preserves_seed_purity():
    """A quad tenant with the DEFAULT class draws nothing extra: the
    trace is byte-identical to the plain-triple spec's."""
    import dataclasses

    from distkeras_tpu.simulator import TraceSpec, generate_trace

    base = TraceSpec(duration_s=30.0, mean_qps=5.0, seed=9,
                     tenants=(("t0", 2.0, 1), ("t1", 1.0, 2)))
    quad = dataclasses.replace(
        base, tenants=(("t0", 2.0, 1, "default"), ("t1", 1.0, 2)))
    a, b = generate_trace(base).arrivals, generate_trace(quad).arrivals
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.t, x.max_new, x.session, x.tenant, x.priority) == \
            (y.t, y.max_new, y.session, y.tenant, y.priority)
        np.testing.assert_array_equal(x.prompt, y.prompt)

    with pytest.raises(ValueError, match="unknown tenant class"):
        dataclasses.replace(
            base, tenants=(("t0", 1.0, 1, "decode_heavy"),))


# ---- SLO signals ------------------------------------------------------


def test_tail_latency_slo_signals():
    """``ttft_p99`` and ``inter_token_p99`` surface through the
    watchdog once their histograms see traffic, with default
    thresholds registered."""
    for sig in ("ttft_p99", "inter_token_p99"):
        assert sig in telemetry.DEFAULT_SLO_THRESHOLDS
    reg = telemetry.MetricsRegistry()
    w = telemetry.SLOWatchdog(reg)
    assert "inter_token_p99" not in w.evaluate()["signals"]
    for _ in range(100):
        reg.histogram("serving_ttft_seconds").observe(0.008)
        reg.histogram("serving_inter_token_seconds").observe(0.5)
    v = w.evaluate()
    assert 0 < v["signals"]["ttft_p99"] < 2.0
    assert v["signals"]["inter_token_p99"] >= 0.5
    # 0.5s cadence >= the degraded_at threshold (0.25)
    assert "inter_token_p99" in v["breaches"]
    assert v["state"] in ("degraded", "critical")
