"""Unified telemetry (ISSUE 2): registry thread-safety under racing
PS-style threads, Perfetto-format trace validity, the opt-in /metrics
endpoint, and the two acceptance runs — an async host-PS (socket)
training producing ONE Perfetto-loadable trace with PS commit spans and
per-worker round spans on distinct thread tracks, and a mixed-length
``DecodeEngine`` run whose metrics snapshot holds queue-depth /
slot-occupancy gauges, a TTFT histogram, and per-bucket compile
counters."""

import json
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import telemetry
from profiled import Profiled

jax.config.update("jax_platforms", "cpu")


@pytest.fixture
def tel():
    t = telemetry.enable(ring_capacity=100_000)
    yield t
    telemetry.disable()


# ---- registry ----------------------------------------------------------

def test_registry_get_or_create_and_kind_conflicts():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("a_total", bucket=16)
    assert reg.counter("a_total", bucket=16) is c
    assert reg.counter("a_total", bucket=32) is not c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("a_total", bucket=16)
    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    s = reg.series("loss")
    s.append(1.0)
    s.extend([0.5, 0.25])
    assert s.values() == [1.0, 0.5, 0.25] and len(s) == 3


def test_histogram_buckets_percentiles_and_validation():
    h = telemetry.Histogram(buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4 and snap["min"] == 0.005 \
        and snap["max"] == 5.0
    assert snap["buckets"] == {0.01: 1, 0.1: 2, 1.0: 3}
    assert h.percentile(0.5) == 0.1
    assert h.percentile(1.0) == 5.0  # beyond the last edge -> max
    assert telemetry.Histogram(buckets=(1, 2, 3)).percentile(0.5) \
        is None
    with pytest.raises(ValueError, match="strictly increasing"):
        telemetry.Histogram(buckets=(1.0, 1.0))
    with pytest.raises(ValueError, match="strictly increasing"):
        telemetry.Histogram(buckets=())


def test_registry_thread_safety_racing_ps_arm_shape():
    """The racing host-PS access pattern: N 'worker' threads and N
    'handler' threads hammer one counter, one histogram, and one
    series while a reader concurrently snapshots — final totals must
    be exact (no lost updates), snapshots must never crash."""
    reg = telemetry.MetricsRegistry()
    n_threads, n_ops = 8, 500
    stop = threading.Event()
    snaps = []

    def writer(i):
        c = reg.counter("commits_total")
        h = reg.histogram("staleness",
                          buckets=telemetry.STALENESS_BUCKETS)
        for k in range(n_ops):
            c.inc()
            h.observe(k % 7)
            reg.series("round_loss").append((i, k))
            # half the threads also race the get-or-create path
            if i % 2:
                reg.counter("wire_bytes", direction="rx").inc(10)

    def reader():
        while not stop.is_set():
            snaps.append(reg.snapshot())
            reg.prometheus_text()

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    r = threading.Thread(target=reader)
    r.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    r.join()
    total = n_threads * n_ops
    assert reg.counter("commits_total").value == total
    assert reg.histogram("staleness").count == total
    assert len(reg.series("round_loss")) == total
    assert reg.counter("wire_bytes", direction="rx").value == \
        (n_threads // 2) * n_ops * 10
    # concurrent snapshots were internally consistent and monotone
    counts = [s["counters"].get("commits_total", 0) for s in snaps]
    assert counts == sorted(counts)


def test_prometheus_text_and_jsonl_export(tmp_path):
    reg = telemetry.MetricsRegistry()
    reg.counter("reqs_total", bucket=16).inc(3)
    reg.gauge("depth").set(2)
    reg.histogram("lat_seconds", buckets=(0.1, 1.0)).observe(0.05)
    reg.series("epoch_loss").append(0.5)
    txt = reg.prometheus_text()
    assert "# TYPE reqs_total counter" in txt
    assert 'reqs_total{bucket="16"} 3' in txt
    assert 'lat_seconds_bucket{le="0.1"} 1' in txt
    assert 'lat_seconds_bucket{le="+Inf"} 1' in txt
    assert "lat_seconds_count 1" in txt
    assert "epoch_loss_observations 1" in txt
    path = reg.write_jsonl(tmp_path / "m.jsonl")
    recs = {r["key"]: r for r in map(json.loads, open(path))}
    assert recs['reqs_total{bucket="16"}']["value"] == 3
    assert recs["epoch_loss"]["values"] == [0.5]
    assert recs["lat_seconds"]["count"] == 1


def test_http_metrics_endpoint():
    reg = telemetry.MetricsRegistry()
    reg.counter("up_total").inc()
    host, port = reg.serve(port=0)
    try:
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=10).read().decode()
        assert "up_total 1" in body
        snap = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/metrics.json", timeout=10).read())
        assert snap["counters"]["up_total"] == 1
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://{host}:{port}/nope",
                                   timeout=10)
    finally:
        reg.stop_serving()


def test_disabled_fast_path_is_inert():
    telemetry.disable()
    assert not telemetry.enabled()
    m = telemetry.metrics()
    # shared no-op handles: no state, no allocation per call site
    assert m.counter("a") is m.counter("b") is m.gauge("c")
    m.counter("a").inc()
    m.histogram("h").observe(1.0)
    assert m.snapshot()["counters"] == {}
    with telemetry.span("x", k=1):
        pass
    telemetry.instant("e")
    assert telemetry.tracer().events() == []
    # no span pushes trace context while disabled (wire header stays 0 B)
    with telemetry.span("y"):
        assert telemetry.current_trace() is None


# ---- the span's second sink: the profiler's own trace -----------------

def test_disabled_span_lands_in_an_open_profiler_session(tmp_path):
    """With telemetry disabled, the profiler session alone is the
    switch: the span is in the ``.xplane.pb`` as ``dkt:x`` with its
    args as stats, and the ring takes nothing."""
    telemetry.disable()
    with Profiled(tmp_path) as p:
        with telemetry.span("x", k=1, who="w"):
            with telemetry.span("inner"):
                pass
    (x,) = p.named("x")
    assert x["stats"] == {"k": 1, "who": "w"}
    (inner,) = p.named("inner")
    assert p.parent(inner) is x and inner["stats"] == {}
    assert telemetry.tracer().events() == []


def test_span_without_session_or_enable_records_nothing(tmp_path):
    telemetry.disable()
    with telemetry.span("before", k=1):
        pass
    assert telemetry.tracer().events() == [] and len(telemetry.tracer()) == 0
    # a span left open across the session's start, and one opened
    # inside it and left after its stop, are dropped whole
    early = telemetry.span("early")
    early.__enter__()
    with Profiled(tmp_path) as p:
        early.__exit__(None, None, None)
        late = telemetry.span("late")
        late.__enter__()
    late.__exit__(None, None, None)
    assert p.spans == []


def test_enabled_span_feeds_both_sinks(tmp_path, tel):
    with Profiled(tmp_path) as p:
        with telemetry.span("both", k=2):
            pass
        telemetry.instant("ring_only")
        telemetry.complete("ring_only_too", telemetry.now())
    assert [s["name"] for s in p.spans] == ["dkt:both"]
    assert p.spans[0]["stats"] == {"k": 2}
    ring = {e["name"]: e for e in tel.tracer.events()}
    assert set(ring) == {"both", "ring_only", "ring_only_too"}
    assert ring["both"]["args"]["k"] == 2  # the ring's name: no prefix


# ---- tracer / Perfetto format -----------------------------------------

def check_perfetto_valid(trace: dict) -> None:
    """The validity contract: required ``ph``/``ts``/``pid``/``tid``
    fields on every timed event, non-negative durations, per-thread
    monotone completion timestamps (events append at span exit), a
    thread-name metadata record per thread track, and flow-event
    pairing — every flow-end ("f") matches exactly ONE flow-start
    ("s") by (name, cat, id).  Orphan starts are legal: a chaos-eaten
    message has a sender but never reaches a handler."""
    import collections

    events = trace["traceEvents"]
    assert events, "empty trace"
    named_tids = {e["tid"] for e in events
                  if e.get("ph") == "M"
                  and e.get("name") == "thread_name"}
    ends: dict[int, float] = {}
    flow_starts: collections.Counter = collections.Counter()
    flow_ends = []
    for e in events:
        assert e.get("ph") in ("X", "i", "M", "s", "f"), e
        assert isinstance(e.get("pid"), int)
        assert isinstance(e.get("tid"), int)
        if e["ph"] == "M":
            continue
        assert isinstance(e["ts"], float) and e["ts"] >= 0
        assert e["tid"] in named_tids
        if e["ph"] == "X":
            assert e["dur"] >= 0
            end = e["ts"] + e["dur"]
            assert end >= ends.get(e["tid"], 0.0)
            ends[e["tid"]] = end
        elif e["ph"] in ("s", "f"):
            assert isinstance(e.get("id"), str) and e.get("cat"), e
            key = (e["name"], e["cat"], e["id"])
            if e["ph"] == "s":
                flow_starts[key] += 1
            else:
                assert e.get("bp") == "e", e
                flow_ends.append(key)
    for key in flow_ends:
        assert flow_starts.get(key, 0) == 1, (
            f"flow-end {key} has {flow_starts.get(key, 0)} matching "
            f"starts (want exactly 1)")
    json.loads(json.dumps(trace))  # serializable as-is


def test_tracer_ring_bound_and_span_args(tel):
    small = telemetry.Tracer(capacity=4)
    for i in range(10):
        with small.span("s", i=i):
            pass
    evs = small.events()
    assert len(evs) == 4 and [e["args"]["i"] for e in evs] == \
        [6, 7, 8, 9]
    with pytest.raises(RuntimeError):
        with tel.span("fails"):
            raise RuntimeError("boom")
    err = [e for e in tel.tracer.events() if e["name"] == "fails"]
    assert err[0]["args"]["error"] == "RuntimeError"


def test_chrome_trace_multithreaded_perfetto_validity(tmp_path, tel):
    def work(i):
        for k in range(5):
            with tel.span("outer", worker=i):
                with tel.span("inner", k=k):
                    pass
            tel.instant("tick", worker=i)

    threads = [threading.Thread(target=work, args=(i,),
                                name=f"worker-{i}") for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with tel.span("main"):
        pass
    path = tel.tracer.write_chrome_trace(tmp_path / "trace.json")
    trace = json.load(open(path))
    check_perfetto_valid(trace)
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"worker-0", "worker-1", "worker-2"} <= names
    spans_by_tid = {}
    for e in trace["traceEvents"]:
        if e.get("ph") == "X":
            spans_by_tid.setdefault(e["tid"], []).append(e)
    assert len(spans_by_tid) == 4  # 3 workers + main


# ---- acceptance: host-PS socket run on one timeline -------------------

def test_host_ps_socket_run_single_perfetto_trace(tmp_path, tel):
    """One async host-PS training run (socket fidelity) -> one
    Perfetto-loadable trace with PS commit spans and per-worker round
    spans on DISTINCT thread tracks, plus commit-rate counter and
    staleness histogram in the same registry."""
    from distkeras_tpu.data import datasets
    from distkeras_tpu.models import model_config
    from distkeras_tpu.trainers import DOWNPOUR

    mlp = model_config("mlp", (8,), num_classes=4, hidden=(16,))
    data = datasets.synthetic_classification(1024, (8,), 4, seed=0)
    t = DOWNPOUR(mlp, fidelity="host", transport="socket",
                 num_workers=3, communication_window=2, batch_size=16,
                 num_epoch=1, learning_rate=0.01,
                 worker_optimizer="adam")
    t.train(data)

    path = tel.tracer.write_chrome_trace(tmp_path / "host_ps.json")
    trace = json.load(open(path))
    check_perfetto_valid(trace)

    commit_tids = {e["tid"] for e in trace["traceEvents"]
                   if e.get("ph") == "X" and e["name"] == "ps_commit"}
    round_spans = [e for e in trace["traceEvents"]
                   if e.get("ph") == "X"
                   and e["name"] == "worker_round"]
    round_tids = {e["tid"] for e in round_spans}
    # every worker thread has its own round track...
    assert {e["args"]["worker"] for e in round_spans} == {0, 1, 2}
    assert len(round_tids) == 3
    # ...and socket commits run on PS handler threads, not on them
    assert commit_tids and commit_tids.isdisjoint(round_tids)

    n_rounds = len(t.history["round_loss"])
    assert tel.metrics.counter("ps_commits_total").value == n_rounds
    assert tel.metrics.histogram("ps_commit_staleness").count == \
        n_rounds
    assert tel.metrics.counter("ps_wire_bytes_total",
                               direction="rx").value > 0
    assert tel.metrics.counter("ps_wire_bytes_total",
                               direction="tx").value > 0
    # the trainer's history stayed intact alongside (the view reads
    # the trainer's own registry, not the global one)
    assert len(t.history["staleness"][-1]) == n_rounds


# ---- acceptance: DecodeEngine metrics snapshot ------------------------

def _lm(max_len=32, vocab=37):
    from distkeras_tpu.models import ModelSpec, model_config

    spec = model_config("transformer_lm", (max_len,),
                        input_dtype="int32", vocab_size=vocab,
                        num_layers=1, d_model=32, num_heads=2,
                        max_len=max_len, dtype="float32")
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((2, max_len), jnp.int32))
    return model, variables


def test_engine_mixed_run_metrics_snapshot_and_derived_keys(tel):
    """Mixed-length DecodeEngine run -> snapshot holds queue-depth and
    slot-occupancy gauges, a TTFT histogram, and per-bucket compile
    counters; results carry engine-owned ``ttft``/``latency`` derived
    from the unified clock (meta keys of the same name lose)."""
    from distkeras_tpu.serving import DecodeEngine

    model, variables = _lm()
    eng = DecodeEngine(model, variables, slots=2, buckets=[16, 32],
                       prefill_align=4, max_new_tokens=4)
    rng = np.random.default_rng(3)
    reqs = [{"prompt": rng.integers(0, 37, (t,)).astype(np.int32),
             "ttft": "meta-must-lose", "i": i}
            for i, t in enumerate([5, 9, 3, 14, 7])]
    results = list(eng.run(reqs))
    assert len(results) == 5
    for r in results:
        assert isinstance(r["ttft"], float)      # engine key wins
        assert r["i"] in range(5)                # other meta survives
        assert r["t_submit"] <= r["t_first"] <= r["t_finish"]
        assert r["ttft"] == pytest.approx(r["t_first"] - r["t_submit"])
        assert r["latency"] == pytest.approx(
            r["t_finish"] - r["t_submit"])
        assert 0 <= r["ttft"] <= r["latency"]

    snap = tel.metrics.snapshot()
    for env in (16, 32):
        assert f'serving_queue_depth{{bucket="{env}"}}' \
            in snap["gauges"]
        assert f'serving_slot_occupancy{{bucket="{env}"}}' \
            in snap["gauges"]
        # drained engine: both levels ended at zero
        assert snap["gauges"][
            f'serving_slot_occupancy{{bucket="{env}"}}'] == 0
        assert tel.metrics.counter("compiles_total", kind="step",
                                   bucket=env).value == 1
        assert tel.metrics.sum_counter("compiles_total",
                                       kind="prefill",
                                       bucket=env) >= 1
    ttft = snap["histograms"]["serving_ttft_seconds"]
    assert ttft["count"] == 5
    lat = snap["histograms"]["serving_latency_seconds"]
    assert lat["count"] == 5 and lat["sum"] >= ttft["sum"]
    assert tel.metrics.sum_counter("serving_tokens_total") == \
        sum(len(r["tokens"]) for r in results)
    # timeline side: prefill/decode_step spans + evict instants
    names = {e["name"] for e in tel.tracer.events()}
    assert {"prefill", "decode_step", "evict"} <= names


def test_engine_timing_fields_without_telemetry_enabled():
    """The unified clock + derived keys are engine contract, not a
    telemetry feature: with telemetry DISABLED the timing fields are
    still present, ordered, and on one clock."""
    telemetry.disable()
    from distkeras_tpu.serving import DecodeEngine

    model, variables = _lm()
    eng = DecodeEngine(model, variables, slots=2, prefill_align=4,
                       max_new_tokens=3)
    (r,) = list(eng.run([np.arange(5, dtype=np.int32)]))
    assert r["t_submit"] <= r["t_first"] <= r["t_finish"]
    assert r["ttft"] == pytest.approx(r["t_first"] - r["t_submit"])
    assert r["latency"] == pytest.approx(r["t_finish"] - r["t_submit"])


# ---- label escaping / bound port / healthz (ISSUE 6 satellites) -------

def test_prometheus_label_value_escaping():
    """Hostile label values (quotes, backslashes, newlines) must not
    corrupt the exposition format — and plain values must render
    byte-identically to before."""
    reg = telemetry.MetricsRegistry()
    reg.counter("reqs_total", bucket=16).inc(3)
    reg.counter("errs_total", path='say "hi"\\n').inc()
    reg.counter("errs_total", path="a\nb").inc(2)
    txt = reg.prometheus_text()
    assert 'reqs_total{bucket="16"} 3' in txt  # plain path unchanged
    assert 'errs_total{path="say \\"hi\\"\\\\n"} 1' in txt
    assert 'errs_total{path="a\\nb"} 2' in txt
    # one line per sample: the raw newline never split a line
    for line in txt.splitlines():
        if line and not line.startswith("#"):
            assert line.rsplit(" ", 1)[1].replace(".", "").isdigit()


def test_serve_bound_port_error_names_port():
    reg = telemetry.MetricsRegistry()
    host, port = reg.serve(port=0)
    other = telemetry.MetricsRegistry()
    try:
        with pytest.raises(OSError, match=f"{port}.*already in use"):
            other.serve(host=host, port=port)
        # ...and the recovery path the message recommends works
        h2, p2 = other.serve(port=0)
        assert p2 != port
    finally:
        other.stop_serving()
        reg.stop_serving()


def _read(url):
    try:
        resp = urllib.request.urlopen(url, timeout=10)
        return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_healthz_endpoint_reports_slo_state():
    reg = telemetry.MetricsRegistry()
    reg.counter("serving_requests_total", bucket=16).inc(100)
    host, port = reg.serve(port=0)
    try:
        status, verdict = _read(f"http://{host}:{port}/healthz")
        assert status == 200 and verdict["state"] == "ok"
        # 30% sheds >= the 25% critical threshold -> HTTP 503
        reg.counter("serving_shed_total", reason="queue_full",
                    bucket=16).inc(30)
        status, verdict = _read(f"http://{host}:{port}/healthz")
        assert status == 503 and verdict["state"] == "critical"
        assert verdict["breaches"]["shed_rate"]["level"] == "critical"
    finally:
        reg.stop_serving()


# ---- SLO watchdog ------------------------------------------------------

def test_slo_watchdog_thresholds_and_transitions(tel):
    reg = telemetry.MetricsRegistry()
    with pytest.raises(ValueError, match="unknown SLO signal"):
        telemetry.SLOWatchdog(reg, thresholds={"nope": (1, 2)})
    with pytest.raises(ValueError, match="must not exceed"):
        telemetry.SLOWatchdog(reg,
                              thresholds={"retry_rate": (2.0, 1.0)})

    w = telemetry.SLOWatchdog(reg)
    assert w.evaluate() == {"state": "ok", "raw_state": "ok",
                            "signals": {},
                            "breaches": {}}  # no traffic != outage
    h = reg.histogram("ps_commit_staleness",
                      buckets=telemetry.STALENESS_BUCKETS)
    for _ in range(100):
        h.observe(20)  # p99 = 20 >= degraded_at 16, < critical 64
    v = w.evaluate()
    assert v["state"] == "degraded"
    assert v["breaches"]["staleness_p99"]["level"] == "degraded"
    for _ in range(900):
        h.observe(100)
    v = w.evaluate()
    assert v["state"] == "critical" and w.state == "critical"
    # state CHANGES drop slo_state instants on the trace (2 flips)
    flips = [e for e in tel.tracer.events()
             if e["name"] == "slo_state"]
    assert [e["args"]["state"] for e in flips] == ["degraded",
                                                  "critical"]
    assert w.last() == v

    # idle fraction needs the registered-workers denominator
    reg2 = telemetry.MetricsRegistry()
    reg2.gauge("ps_registered_workers").set(4)
    reg2.gauge("ps_idle_workers").set(3)
    v2 = telemetry.SLOWatchdog(reg2).evaluate()
    assert v2["signals"]["idle_worker_fraction"] == 0.75
    assert v2["state"] == "critical"

    # background loop + attach: registry.health() uses the attached
    # watchdog (custom thresholds visible through /healthz's path)
    w3 = telemetry.SLOWatchdog(reg2, thresholds={
        "idle_worker_fraction": (0.9, 0.95)}, interval_s=0.01)
    reg2.attach_watchdog(w3)
    assert reg2.health()["state"] == "ok"
    w3.start()
    assert w3.start() is w3  # idempotent
    final = w3.stop()
    assert final["state"] == "ok"


def test_prefix_hit_rate_slo_signal_breaches_low(tel):
    """ISSUE 8: ``prefix_hit_rate`` is an INVERTED signal — a LOW
    rate (store thrash / post-swap cold start) is the breach, never a
    high one — with the threshold validation inverted to match."""
    reg = telemetry.MetricsRegistry()
    w = telemetry.SLOWatchdog(reg)
    assert w.evaluate()["state"] == "ok"  # no lookups != outage
    hits = reg.counter("serving_prefix_hits_total", bucket=32)
    miss = reg.counter("serving_prefix_misses_total", bucket=32)
    hits.inc(90)
    miss.inc(10)  # 0.90 hit rate: healthy
    v = w.evaluate()
    assert v["signals"]["prefix_hit_rate"] == pytest.approx(0.90)
    assert "prefix_hit_rate" not in v["breaches"]
    miss.inc(900)  # rate collapses to 0.09 <= degraded_at 0.10
    v = w.evaluate()
    assert v["breaches"]["prefix_hit_rate"]["level"] == "degraded"
    miss.inc(8000)  # ~0.01 <= critical_at 0.01
    v = w.evaluate()
    assert v["state"] == "critical"
    assert v["breaches"]["prefix_hit_rate"]["level"] == "critical"
    # custom thresholds: inverted pairs validate the inverted way
    telemetry.SLOWatchdog(reg, thresholds={
        "prefix_hit_rate": (0.5, 0.2)})  # degraded ABOVE critical: ok
    with pytest.raises(ValueError, match="breaches LOW"):
        telemetry.SLOWatchdog(reg, thresholds={
            "prefix_hit_rate": (0.2, 0.5)})


def test_mfu_gap_slo_signal():
    """ISSUE 17: ``mfu_gap`` = 1 - observed/roofline off the driver's
    attribution gauges — a big gap (round running far below its
    roofline floor) degrades the verdict."""
    reg = telemetry.MetricsRegistry()
    w = telemetry.SLOWatchdog(reg)
    assert "mfu_gap" not in w.evaluate()["signals"]  # gauges absent
    obs = reg.gauge("mfu_observed")
    roof = reg.gauge("mfu_roofline")
    obs.set(0.40)
    roof.set(0.50)  # gap 0.2 < degraded_at 0.5: healthy
    v = w.evaluate()
    assert v["signals"]["mfu_gap"] == pytest.approx(0.2)
    assert "mfu_gap" not in v["breaches"]
    obs.set(0.20)  # gap 0.6 >= 0.5: degraded
    v = w.evaluate()
    assert v["breaches"]["mfu_gap"]["level"] == "degraded"
    obs.set(0.02)  # gap 0.96 >= critical_at 0.9
    v = w.evaluate()
    assert v["breaches"]["mfu_gap"]["level"] == "critical"
    obs.set(0.60)  # observed ABOVE the roofline estimate: clamped to 0
    assert w.evaluate()["signals"]["mfu_gap"] == 0.0
    roof.set(0.0)  # degenerate roofline: signal absent, not fabricated
    assert "mfu_gap" not in w.evaluate()["signals"]


# ---- trace context + wire header --------------------------------------

def test_trace_context_nesting_and_wire_header(tel):
    from distkeras_tpu.parallel import transport

    assert telemetry.current_trace() is None
    assert transport.trace_header() == b""  # tracing off: ZERO bytes
    with telemetry.span("root") as root:
        trace_id, span_id = telemetry.current_trace()
        assert trace_id == span_id == root.span_id  # root id IS trace
        with telemetry.span("child") as child:
            t2, s2 = telemetry.current_trace()
            assert t2 == trace_id and s2 == child.span_id != span_id
            hdr = transport.trace_header()
            assert len(hdr) == transport.TRACE_HEADER_LEN == 17
            link, rest = transport.split_trace_header(
                hdr + b"c" + b"payload")
            assert link == (t2, s2) and bytes(rest) == b"cpayload"
        assert telemetry.current_trace() == (trace_id, span_id)
    assert telemetry.current_trace() is None
    # an untraced body passes through unmodified
    link, rest = transport.split_trace_header(b"p")
    assert link is None and rest == b"p"
    # span ids are process-unique and stamped into exported args
    evs = {e["name"]: e for e in tel.tracer.events()}
    assert evs["child"]["args"]["trace_id"] == \
        evs["root"]["args"]["span_id"]
    assert evs["child"]["args"]["span_id"] != \
        evs["root"]["args"]["span_id"]


def test_merge_traces_clock_shift_and_pid_collision():
    def tr(pid, wall, mono, ts):
        return {"traceEvents": [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": f"p{pid}"}},
            {"name": "s1", "ph": "X", "ts": ts, "dur": 5.0,
             "pid": pid, "tid": 1, "args": {}}],
            "wallAnchor": {"wall_s": wall, "mono_s": mono,
                           "pid": pid}}

    # same wall instant, different perf_counter origins: process B's
    # mono clock reads 2s lower, so its events shift +2s in the merge
    merged = telemetry.merge_traces(tr(1, 1000.0, 50.0, 50.0 * 1e6),
                                    tr(1, 1000.0, 48.0, 48.0 * 1e6))
    spans = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    assert spans[0]["ts"] == pytest.approx(spans[1]["ts"])
    # colliding pid: the second dump got a synthetic process track
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert len(pids) == 2 and 1 in pids
    # metadata sorts first so Perfetto names tracks before events
    assert merged["traceEvents"][0]["ph"] == "M"


# ---- flight recorder ---------------------------------------------------

def test_flight_recorder_rotation_retention_and_torn_tail(tmp_path):
    from distkeras_tpu.flight_recorder import FlightRecorder

    with pytest.raises(ValueError, match=">= 1"):
        FlightRecorder(tmp_path, segment_events=0)
    fr = FlightRecorder(tmp_path / "ring", segment_events=4,
                        segments=2)
    for i in range(20):
        fr.record("tick", i=i)
    fr.close()
    fr.close()  # idempotent
    # ring bound: 2 sealed segments x 4 events survive of the 20
    events = fr.read_events()
    assert [e["i"] for e in events] == list(range(12, 20))
    assert all(e["kind"] == "tick" and "wall_s" in e and "pid" in e
               for e in events)
    # the caller's own fields never collide with recorder stamps
    fr2 = FlightRecorder(tmp_path / "ring2")
    fr2.record("commit", seq=41)
    assert fr2.read_events()[0]["seq"] == 41
    # torn final line (crashed writer): parsed up to the tear
    with open(fr2._open_path(fr2._segment_n), "a") as f:
        f.write('{"kind": "torn", "wal')
    assert [e["kind"] for e in fr2.read_events()] == ["commit"]
    # windowing: last N seconds ending at the newest event
    assert fr2.last(60.0) == fr2.read_events()
    assert fr2.last(0.0, until_wall_s=0.0) == []


def test_flight_recorder_module_globals_and_disabled_noop(tmp_path):
    from distkeras_tpu import flight_recorder

    flight_recorder.stop()
    assert flight_recorder.active() is None
    flight_recorder.record("ignored", x=1)  # no recorder: no-op
    flight_recorder.flush()
    fr = flight_recorder.start(tmp_path / "fdr")
    try:
        assert flight_recorder.active() is fr
        flight_recorder.record("seen", x=2)
        flight_recorder.flush(fsync=True)
        assert [e["kind"] for e in fr.read_events()] == ["seen"]
    finally:
        flight_recorder.stop()
    assert flight_recorder.active() is None
    # stopping sealed the live segment atomically
    assert list((tmp_path / "fdr").glob("*.jsonl"))
    assert not list((tmp_path / "fdr").glob("*.open"))


# ---- acceptance: chaos + kill/restart, traced and flight-recorded -----

def test_chaos_kill_restart_traced_flight_and_postmortem(tmp_path, tel):
    """THE observability acceptance scenario (ISSUE 6): a chaos-enabled
    socket training run whose external PS is killed and warm-restarted
    mid-stream, observed end to end —

    * the Perfetto trace validates WITH flow-event pairing: every
      surviving commit's server ``ps_rpc`` handler span carries a
      ``link_span`` that resolves to exactly one client-side wire span
      (chaos-eaten sends leave legal orphan flow-starts);
    * the flight recorder survives the crash with the whole story —
      commits, snapshots, chaos injections, client retries, the
      ``ps_kill`` marker, the ``ps_restart`` marker — and the max
      commit seq per worker it recorded up to the restart marker
      equals the restarted server's dedupe state exactly;
    * ``scripts/postmortem.py``'s reconstruction finds the kill as the
      crash marker (its exact snapshot ``acked_match`` law on a fully
      sequential schedule is proven by ``postmortem.py --smoke``);
    * the trainer's history carries the run's SLO verdict.
    """
    import importlib.util
    import pathlib
    import time

    from distkeras_tpu import flight_recorder
    from distkeras_tpu.data import datasets
    from distkeras_tpu.flight_recorder import FlightRecorder
    from distkeras_tpu.models import ModelSpec, model_config
    from distkeras_tpu.parallel.faults import ChaosTransport
    from distkeras_tpu.parallel.host_ps import (HostParameterServer,
                                                PSServer)
    from distkeras_tpu.parallel.update_rules import DownpourRule
    from distkeras_tpu.trainers import DOWNPOUR

    mlp = model_config("mlp", (8,), num_classes=4, hidden=(16,))
    data = datasets.synthetic_classification(1024, (8,), 4, seed=0)
    model = ModelSpec.from_config(mlp).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.float32))
    center = jax.tree_util.tree_map(np.asarray, variables["params"])

    flight_dir = tmp_path / "flight"
    snap = tmp_path / "ps.snap"
    flight_recorder.start(flight_dir)
    ps = HostParameterServer(DownpourRule(), center,
                             snapshot_path=snap, snapshot_every=1)
    srv = PSServer(ps, center).start()
    port = srv.address[1]
    box = {}

    def killer():
        while srv.ps.num_commits < 5:
            time.sleep(0.002)
        srv.kill()
        # Let any commit already inside the handler finish its apply +
        # snapshot before the restart loads the file: every commit
        # RECORDED before the restart marker is then durably in the
        # snapshot the restart resumes from.  (A commit CAN race the
        # kill marker itself — real crash semantics — which is why the
        # cross-check below anchors at the restart, not the kill.)
        time.sleep(0.25)
        for _ in range(50):
            try:
                box["srv2"] = PSServer.restart_from(
                    snap, DownpourRule(), center, port=port)
                return
            except OSError:
                time.sleep(0.05)
        raise OSError(f"could not rebind port {port}")

    k = threading.Thread(target=killer)
    k.start()
    try:
        with ChaosTransport(seed=7, reset_rate=0.05, max_injections=2,
                            skip_ops=8):
            t = DOWNPOUR(mlp, fidelity="host", transport="socket",
                         num_workers=1, communication_window=2,
                         batch_size=16, num_epoch=1,
                         learning_rate=0.01, worker_optimizer="adam",
                         worker_retries=12,
                         ps_address=("127.0.0.1", port))
            t.train(data, initial_variables=variables)
    finally:
        k.join()
        flight_recorder.stop()
    srv2 = box["srv2"]
    srv2.stop()

    # the outage really happened, the worker rode through it, and the
    # run closed with an SLO verdict in the history
    assert srv2.ps.num_commits > 5
    assert t.history.get("worker_round_retries"), (
        "the kill was invisible to the worker — test proved nothing")
    assert t.history["slo_health"][-1] in ("ok", "degraded", "critical")

    # -- trace: flow pairing + server->client span linking --------------
    path = tel.tracer.write_chrome_trace(tmp_path / "trace.json")
    trace = json.load(open(path))
    check_perfetto_valid(trace)  # includes the flow-pairing contract
    evs = trace["traceEvents"]
    client_spans = {e["args"]["span_id"] for e in evs
                    if e.get("ph") == "X"
                    and e["name"] in ("ps_client_pull",
                                      "ps_client_commit")}
    rpc = [e for e in evs if e.get("ph") == "X"
           and e["name"] == "ps_rpc"]
    linked = [e for e in rpc if "link_span" in e["args"]]
    assert linked, "no handler span recorded a client link"
    for e in linked:
        assert e["args"]["link_span"] in client_spans, e
    assert any(e.get("ph") == "f" for e in evs)  # arrows really drawn

    # -- flight recorder: the whole crash story survived ----------------
    events = FlightRecorder(flight_dir).read_events()
    kinds = {e["kind"] for e in events}
    assert {"commit", "snapshot", "retry",
            "ps_kill", "ps_restart"} <= kinds, kinds
    assert "chaos" in kinds, "no chaos injection fired"

    # the postmortem law, anchored at the restart marker: the max seq
    # the flight ring recorded per worker up to the restart equals the
    # dedupe state the restarted server resumed with
    restart_ev = [e for e in events if e["kind"] == "ps_restart"][-1]
    acked: dict = {}
    for e in events:
        if e["kind"] in ("commit", "commit_dedup") \
                and e["wall_s"] <= restart_ev["wall_s"]:
            w = str(e["worker"])
            acked[w] = max(acked.get(w, -1), int(e["seq"]))
    assert acked == {w: int(s)
                     for w, s in restart_ev["last_acked"].items()}

    # -- scripts/postmortem.py reconstructs the same crash --------------
    pm_path = (pathlib.Path(__file__).resolve().parent.parent
               / "scripts" / "postmortem.py")
    spec = importlib.util.spec_from_file_location("_dkt_pm", pm_path)
    pm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm)
    report = pm.reconstruct(str(flight_dir), seconds=300.0)
    assert report["crash"]["kind"] == "ps_kill"
    assert report["kinds"].get("commit", 0) >= 5
    # flight-acked at the KILL can trail the restart state by whatever
    # was mid-handler when the crash hit, but can never lead it
    for w, s in report["flight_last_acked"].items():
        assert int(s) <= acked[w]
    assert "postmortem" in pm.render(report)


def test_slo_violation_seconds_accrue_by_state(tel):
    """ISSUE 18: every evaluation closes out the time spent in the
    previously committed non-ok state onto
    ``slo_violation_seconds_total{state}`` — the drill's
    violation-minutes metric is a pure time integral, testable with an
    injected clock."""
    reg = telemetry.MetricsRegistry()
    w = telemetry.SLOWatchdog(
        reg, thresholds={"queue_depth": (3.0, 10.0)},
        sustain_secs=0.0)  # edge-trigger: transitions commit at once
    q = reg.gauge("serving_queue_depth", bucket=16)

    def acc(state):
        return reg.counter("slo_violation_seconds_total",
                           state=state).value

    assert w.evaluate(now_s=0.0)["state"] == "ok"
    q.set(5.0)
    assert w.evaluate(now_s=10.0)["state"] == "degraded"
    assert acc("degraded") == 0.0  # the 0..10 span was spent ok
    assert w.evaluate(now_s=12.0)["state"] == "degraded"
    assert acc("degraded") == pytest.approx(2.0)
    q.set(20.0)
    assert w.evaluate(now_s=15.0)["state"] == "critical"
    assert acc("degraded") == pytest.approx(5.0)  # closed on the flip
    assert w.evaluate(now_s=18.0)["state"] == "critical"
    q.set(0.0)
    assert w.evaluate(now_s=20.0)["state"] == "ok"
    assert acc("critical") == pytest.approx(5.0)
    assert w.evaluate(now_s=25.0)["state"] == "ok"
    # ok time never accrues; the totals are final
    assert acc("degraded") == pytest.approx(5.0)
    assert acc("critical") == pytest.approx(5.0)
