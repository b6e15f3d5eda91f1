"""The traffic/chaos simulator (ISSUE 18): trace generators are pure
functions of the seed with the declared statistics, replay delivers
exactly-once against a gateway, the stepped-rate search finds the knee
of a known queue, and the capacity model's fit/required() arithmetic
holds.

All but the last test run against FAKE gateways (a deterministic
FIFO queue), so the suite tests the simulator's own contracts in
milliseconds-to-seconds; the last one is the full-stack closed-loop
drill — real engines behind a real gateway, the autoscaler, a replica
kill and a fault window on a training tenant's wire — held to its
ledger of counts."""

import dataclasses
import importlib.util
import threading
from pathlib import Path

import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.simulator import (Arrival, CapacityModel,
                                     CapacityPoint, ChaosSchedule,
                                     ReplicaPool, TraceSpec,
                                     declared_length_quantiles,
                                     generate_trace, in_crowd,
                                     peak_rate, rate_at, replay,
                                     run_drill, stepped_rate_search)

# ---- trace generation --------------------------------------------------


def _spec(**kw):
    kw.setdefault("duration_s", 20.0)
    kw.setdefault("mean_qps", 40.0)
    return TraceSpec(**kw)


def test_trace_is_a_pure_function_of_the_seed():
    spec = _spec(diurnal_amplitude=0.3,
                 flash_crowds=((5.0, 8.0, 2.0),),
                 tenants=(("free", 0.7, 0), ("paid", 0.3, 2)))
    a = generate_trace(spec).arrivals
    b = generate_trace(spec).arrivals
    assert len(a) == len(b) > 100
    for x, y in zip(a, b):
        assert x.t == y.t and x.max_new == y.max_new
        assert x.session == y.session and x.tenant == y.tenant
        np.testing.assert_array_equal(x.prompt, y.prompt)
    c = generate_trace(dataclasses.replace(spec, seed=1)).arrivals
    assert [x.t for x in a] != [x.t for x in c]


def test_diurnal_rate_integral_matches_the_mean():
    """Period == duration, so the sinusoid integrates to zero and the
    realized arrival count must match mean_qps * duration (Poisson
    noise bounded: sd(2400) ~ 49, the 10% tolerance is ~5 sd)."""
    spec = _spec(duration_s=60.0, mean_qps=40.0,
                 diurnal_amplitude=0.6)
    n = len(generate_trace(spec).arrivals)
    assert n == pytest.approx(2400, rel=0.10)
    # and the analytic curve peaks/troughs where the phase says
    assert rate_at(spec, 15.0) == pytest.approx(64.0)
    assert rate_at(spec, 45.0) == pytest.approx(16.0)
    assert peak_rate(spec) == pytest.approx(64.0)


def test_flash_crowd_densifies_its_window():
    spec = _spec(duration_s=30.0, mean_qps=30.0,
                 flash_crowds=((10.0, 20.0, 3.0),))
    ts = [a.t for a in generate_trace(spec).arrivals]
    inside = sum(10.0 <= t < 20.0 for t in ts)
    before = sum(t < 10.0 for t in ts)
    assert inside == pytest.approx(3 * before, rel=0.25)
    assert in_crowd(spec, 15.0) and not in_crowd(spec, 5.0)


def test_heavy_tails_match_the_declared_quantiles():
    """Empirical p50/p99 of the generated lengths track the analytic
    lognormal / Pareto quantiles (clips pushed far out so they never
    bite the p99)."""
    spec = _spec(duration_s=30.0, mean_qps=300.0, prompt_median=24.0,
                 prompt_sigma=0.6, prompt_min=4, prompt_max=4096,
                 output_alpha=2.0, output_min=4, output_max=100000)
    arr = generate_trace(spec).arrivals
    assert len(arr) > 5000
    want = declared_length_quantiles(spec)
    plens = np.array([len(a.prompt) for a in arr], float)
    outs = np.array([a.max_new for a in arr], float)
    assert np.percentile(plens, 50) == pytest.approx(
        want["prompt_p50"], rel=0.10)
    assert np.percentile(plens, 99) == pytest.approx(
        want["prompt_p99"], rel=0.15)
    assert np.percentile(outs, 50) == pytest.approx(
        want["output_p50"], rel=0.10)
    assert np.percentile(outs, 99) == pytest.approx(
        want["output_p99"], rel=0.30)
    # declared ratio arithmetic: p99/p50 = 50**(1/alpha) for Pareto
    assert want["output_p99"] / want["output_p50"] == pytest.approx(
        50.0 ** (1 / spec.output_alpha))


def test_sessions_share_their_group_prefix():
    spec = _spec(sessions=10, prefix_groups=3, prefix_len=4,
                 prompt_min=6)
    arr = generate_trace(spec).arrivals
    by_session = {}
    for a in arr:
        head = tuple(a.prompt[:4].tolist())
        by_session.setdefault(a.session, set()).add(head)
    # one prefix per session, drawn from <= prefix_groups distinct
    assert all(len(heads) == 1 for heads in by_session.values())
    distinct = {next(iter(h)) for h in by_session.values()}
    assert 1 <= len(distinct) <= 3
    assert all(len(a.prompt) >= 6 for a in arr)


def test_tenant_shares_and_priorities():
    spec = _spec(duration_s=40.0,
                 tenants=(("free", 0.7, 0), ("paid", 0.3, 2)))
    arr = generate_trace(spec).arrivals
    frac = sum(a.tenant == "paid" for a in arr) / len(arr)
    assert frac == pytest.approx(0.3, abs=0.05)
    prios = {a.tenant: a.priority for a in arr}
    assert prios == {"free": 0, "paid": 2}


def test_spec_validation():
    with pytest.raises(ValueError, match="must be > 0"):
        _spec(mean_qps=0.0)
    with pytest.raises(ValueError, match="diurnal_amplitude"):
        _spec(diurnal_amplitude=1.0)
    with pytest.raises(ValueError, match="flash crowd"):
        _spec(flash_crowds=((5.0, 4.0, 2.0),))
    with pytest.raises(ValueError, match="prefix_len"):
        _spec(prefix_len=8, prompt_min=8)
    with pytest.raises(ValueError, match="session_zipf"):
        _spec(session_zipf=1.0)
    with pytest.raises(ValueError, match="positive shares"):
        _spec(tenants=(("a", 0.0, 0),))


# ---- replay against a deterministic queue ------------------------------


class _QueueGateway:
    """Single FIFO server at ``service_rate`` req/s on the wall clock
    — the textbook queue whose saturation knee the search must find."""

    def __init__(self, service_rate: float, replicas: int = 1):
        self._dt = 1.0 / float(service_rate)
        self._next_free = 0.0
        self._due: dict = {}
        self._n = 0
        self._replicas = replicas

    def submit(self, prompt, *, max_new_tokens, session=None,
               tenant=None, priority=0):
        nw = telemetry.now()
        start = max(nw, self._next_free)
        self._next_free = start + self._dt
        rid = f"r{self._n}"
        self._n += 1
        self._due[rid] = start + self._dt
        return rid

    def try_result(self, rid):
        due = self._due[rid]
        if telemetry.now() < due:
            return None
        del self._due[rid]
        return {"request_id": rid, "tokens": [0], "t_first": due,
                "error": None}

    def alive_replicas(self) -> int:
        return self._replicas


def test_replay_delivers_exactly_once():
    spec = _spec(duration_s=1.0, mean_qps=40.0)
    trace = generate_trace(spec)
    rep = replay(trace, _QueueGateway(400.0), slo_ttft_s=0.5,
                 drain_timeout_s=5.0)
    assert rep["arrivals"] == len(trace.arrivals)
    assert rep["completed"] == rep["arrivals"]
    assert rep["undrained"] == rep["errors"] == rep["duplicates"] == 0
    assert rep["slo_attainment"] == 1.0 and rep["slo_miss"] == 0
    assert rep["ttft_p95_s"] is not None
    rids = [r["request_id"] for r in rep["results"]]
    assert len(set(rids)) == len(rids)


def test_stepped_rate_search_finds_the_queue_knee():
    """A 50 req/s FIFO server must sustain the 40-rung and fail the
    160-rung — and the capped flag stays False because a rung failed.
    Margins are wide on purpose (rho 0.8 vs 3.2, SLO 15 services
    deep) so OS scheduling jitter cannot flip a rung."""
    out = stepped_rate_search(
        _QueueGateway(50.0), _spec(duration_s=1.0, mean_qps=1.0),
        slo_ttft_s=0.3, ladder=(10.0, 20.0, 40.0, 160.0),
        min_arrivals=8, max_segment_s=0.5, drain_timeout_s=5.0,
        config={"replicas": 1})
    assert out["sustainable_qps"] == 40.0 and not out["capped"]
    assert out["point"].config == {"replicas": 1}
    assert [r["ok"] for r in out["rungs"]] == [True, True, True,
                                              False]
    # a ladder the system outruns reports capped=True
    out2 = stepped_rate_search(
        _QueueGateway(400.0), _spec(duration_s=1.0, mean_qps=1.0),
        slo_ttft_s=0.25, ladder=(5.0, 10.0), min_arrivals=5,
        max_segment_s=0.5, drain_timeout_s=5.0)
    assert out2["capped"] and out2["sustainable_qps"] == 10.0


# ---- capacity model ----------------------------------------------------


def test_capacity_model_fit_and_required():
    pts = [CapacityPoint({"replicas": 1}, 40.0, 1.0, 0.01),
           CapacityPoint({"replicas": 2}, 80.0, 1.0, 0.01)]
    m = CapacityModel(pts)
    assert m.capacity(3) == pytest.approx(120.0)
    assert m.required(39.0) == 1
    assert m.required(41.0) == 2
    assert m.required(41.0, headroom=2.0) == 3  # 82 needs 3x40
    assert m.required(1e9, max_replicas=8) == 8  # unreachable: cap
    d = m.describe()
    assert d["slope"] == pytest.approx(40.0)
    assert len(d["points"]) == 2
    # single point: conservative proportional-through-origin
    m1 = CapacityModel(pts[:1])
    assert m1.capacity(2) == pytest.approx(80.0)
    with pytest.raises(ValueError, match=">= 1 point"):
        CapacityModel([])


# ---- chaos schedule + replica pool -------------------------------------


def test_chaos_schedule_kills_fire_once_at_their_time():
    killed = []
    sched = ChaosSchedule(kills=((0.0, "r0"),))
    sched.register_kill("r0", lambda: killed.append("r0"))
    assert sched.clock() == 0.0  # pre-start: the clock is parked
    sched.start()
    assert sched.poll() == ["r0"] and killed == ["r0"]
    assert sched.poll() == []  # once, not every poll
    with pytest.raises(KeyError, match="never registered"):
        ChaosSchedule(kills=((0.0, "ghost"),)).start().poll()
    with pytest.raises(ValueError, match=">= 0"):
        ChaosSchedule(kills=((-1.0, "r0"),))


def test_chaos_schedule_wires_windows_into_the_transport():
    sched = ChaosSchedule(windows=((1.0, 2.0, ("reset", "delay")),))
    ct = sched.chaos_transport(seed=7, reset_rate=0.0,
                               truncate_rate=0.0, delay_rate=0.0)
    assert ct.windows == sched.windows
    # one clock for faults AND kills (same bound method)
    assert ct._clock.__self__ is sched


class _PoolGateway:
    def __init__(self):
        self.names = ["r0"]

    def add_replica(self, rep):
        self.names.append(rep.name)

    def remove_replica(self, name):
        self.names.remove(name)

    def alive_replicas(self):
        return len(self.names)


def test_replica_pool_spawns_spares_and_drains_lifo():
    class _Rep:
        def __init__(self, name):
            self.name = name

    gw = _PoolGateway()
    pool = ReplicaPool(gw, spares=[_Rep("s1"), _Rep("s2")])
    assert pool.replica_count() == 1 and pool.spares_left() == 2
    assert pool.spawn_replica() == "s2"  # LIFO off the spare stack
    assert pool.spawn_replica() == "s1"
    assert gw.names == ["r0", "s2", "s1"]
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.spawn_replica()
    assert pool.drain_replica() == "s1"  # most recently spawned
    assert gw.names == ["r0", "s2"]
    assert pool.replica_count() == 2


# ---- drill episode accounting ------------------------------------------


def test_run_drill_opens_and_closes_deficit_episodes():
    """Target jumps to 2 inside the crowd; a stub autoscaler heals on
    its second tick — the drill must record exactly one episode,
    closed, and report converged."""
    model = CapacityModel(
        [CapacityPoint({"replicas": 1}, 40.0, 1.0, 0.01),
         CapacityPoint({"replicas": 2}, 80.0, 1.0, 0.01)])
    spec = _spec(duration_s=0.8, mean_qps=30.0,
                 flash_crowds=((0.2, 0.8, 2.0),))
    gw = _QueueGateway(500.0)

    class _Scaler:
        class watchdog:
            state = "ok"

        def step(self):
            if in_crowd(spec, (telemetry.now() - t0[0])):
                gw._replicas = 2

    t0 = [telemetry.now()]
    out = run_drill(generate_trace(spec), gw, _Scaler(), model,
                    tick_interval_s=0.05, max_replicas=2,
                    drain_timeout_s=5.0)
    assert out["episodes"] and out["converged"]
    assert all(e["closed"] and e["target"] == 2
               for e in out["episodes"])
    assert out["replay"]["undrained"] == 0
    assert any(s["target"] == 2 and s["actual"] == 2
               for s in out["samples"])


def test_run_drill_reports_an_unhealed_deficit_as_unconverged():
    model = CapacityModel(
        [CapacityPoint({"replicas": 1}, 10.0, 1.0, 0.01)])
    spec = _spec(duration_s=0.4, mean_qps=30.0)  # needs 3, has 1

    class _Inert:
        class watchdog:
            state = "critical"

        def step(self):
            pass

    out = run_drill(generate_trace(spec), _QueueGateway(500.0),
                    _Inert(), model, tick_interval_s=0.05,
                    max_replicas=4, drain_timeout_s=5.0)
    assert not out["converged"]
    assert [e["closed"] for e in out["episodes"]] == [False]


# ---- the full-stack drill: its ledger of counts ------------------------


def test_full_stack_drill_ledger(tmp_path):
    """A burst over two real ``EngineReplica``s behind a
    ``ServingGateway``, one of them killed inside the burst, the
    ``Autoscaler`` refilling from a ``ReplicaPool``, and a socket-PS
    training tenant whose wire sits in a reset/delay fault window the
    whole time.  What the run must count, whatever it took: one kill;
    faults injected; every arrival answered once, without error, by
    the tokens a solo engine yields; two replicas alive and
    the SLO state ``ok`` at the end; the trainer's commits equal to
    its rounds; and a flight ring the postmortem can read the story
    from.  The burst offers several times what the replicas can serve,
    so the queue-depth breach that refills the fleet does not depend
    on the machine's speed."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu import flight_recorder
    from distkeras_tpu.data import datasets
    from distkeras_tpu.gateway import EngineReplica, ServingGateway
    from distkeras_tpu.models import ModelSpec, model_config
    from distkeras_tpu.serving import DecodeEngine
    from distkeras_tpu.trainers import DOWNPOUR

    spec = model_config("transformer_lm", (64,), input_dtype="int32",
                        vocab_size=61, num_layers=2, d_model=64,
                        num_heads=2, max_len=64, dtype="float32")
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((2, 8), jnp.int32))

    def replica(name):
        eng = DecodeEngine(model, variables, slots=1, prefill_align=8,
                           max_new_tokens=16)
        list(eng.run([{"prompt": np.zeros((8,), np.int32),
                       "max_new_tokens": 2}]))
        return EngineReplica(eng, name=name)

    mlp = model_config("mlp", (8,), num_classes=4, hidden=(16,))
    data = datasets.synthetic_classification(160, (8,), 4, seed=0)
    train = {"runs": 0, "errors": []}
    stop = threading.Event()

    def train_once():
        t = DOWNPOUR(mlp, fidelity="host", transport="socket",
                     num_workers=2, communication_window=2,
                     batch_size=16, num_epoch=1, learning_rate=0.01,
                     worker_optimizer="adam", worker_retries=14)
        t.train(data)
        rounds = len(t.history["round_loss"])
        commits = t.parameter_server_state.num_commits
        train["runs"] += 1
        if commits != rounds or "worker_failures" in t.history:
            train["errors"].append((commits, rounds, dict(t.history)))

    def tenant():
        while not stop.is_set():
            try:
                train_once()
            except Exception as e:  # surfaced by the assertion below
                train["errors"].append(repr(e))
                return
            stop.wait(0.2)

    train_once()  # compile the tenant's step outside the fault window
    trace_spec = _spec(
        duration_s=1.0, mean_qps=8.0, seed=3, prompt_median=6.0,
        prompt_sigma=0.3, prompt_min=4, prompt_max=8,
        output_alpha=1.6, output_min=8, output_max=16, vocab=61,
        sessions=6, flash_crowds=((0.3, 0.7, 25.0),),
        tenants=(("free", 0.7, 0), ("paid", 0.3, 2)))
    trace = generate_trace(trace_spec)
    cap = CapacityModel([CapacityPoint({"replicas": 1}, 50.0, 1.0, 0.1)])
    schedule = ChaosSchedule(
        windows=((0.0, 600.0, ("reset", "delay")),),
        kills=((0.45, "r0"),))
    r0, r1, spare = replica("r0"), replica("r1"), replica("s0")
    schedule.register_kill("r0", r0.kill)

    tel = telemetry.enable()
    flight_recorder.start(tmp_path / "fdr")
    try:
        thresholds = {
            k: ((-1.0, -2.0)
                if k in telemetry.LOWER_IS_WORSE_SLO_SIGNALS
                else (1e9, 2e9))
            for k in telemetry.DEFAULT_SLO_THRESHOLDS}
        thresholds["queue_depth"] = (3.0, 1e9)
        watchdog = telemetry.SLOWatchdog(tel.metrics,
                                         thresholds=thresholds)
        with ServingGateway([r0, r1], policy="least_loaded",
                            retries=8, backoff_base=0.01) as gw:
            pool = ReplicaPool(gw, [spare])
            scaler = telemetry.Autoscaler(
                watchdog, spawn_replica=pool.spawn_replica,
                drain_replica=pool.drain_replica,
                replica_count=pool.replica_count, min_replicas=1,
                max_replicas=2, cooldown_s=0.0, idle_sustain_s=3600.0,
                gateway_scale_signals=("queue_depth",), busy=gw.busy)
            with schedule.chaos_transport(
                    seed=13, delay_s=0.005, window_rate=0.35,
                    max_injections=10) as ct:
                worker = threading.Thread(target=tenant, daemon=True)
                worker.start()
                drill = run_drill(
                    trace, gw, scaler, cap,
                    schedule=schedule, tick_interval_s=0.05,
                    max_replicas=2, drain_timeout_s=120.0)
                stop.set()
                worker.join(60)
                assert not worker.is_alive()
            final = watchdog.evaluate()
            end_replicas = gw.alive_replicas()
        counters = tel.metrics.snapshot()["counters"]
        events = flight_recorder.active().read_events()
    finally:
        flight_recorder.stop()
        telemetry.disable()

    def csum(name):
        return sum(v for k, v in counters.items()
                   if k == name or k.startswith(name + "{"))

    rep = drill["replay"]
    assert csum("sim_kills_total") == 1
    assert csum("chaos_window_injected_total") > 0, dict(ct.counts)
    assert rep["completed"] == rep["arrivals"] == len(trace.arrivals)
    assert rep["errors"] == rep["duplicates"] == rep["undrained"] == 0
    rids = [r["request_id"] for r in rep["results"]]
    assert len(set(rids)) == len(rids) == rep["arrivals"]
    assert drill["converged"], drill["episodes"]
    assert end_replicas == 2
    assert final["state"] == "ok", final
    assert train["runs"] >= 2 and not train["errors"], train
    served = sorted(rep["results"], key=lambda r: r["sim_t"])[:10]
    solo = DecodeEngine(model, variables, slots=1, prefill_align=8,
                        max_new_tokens=16)
    for r, ref in zip(served, solo.run(
            [{"prompt": np.asarray(r["prompt"], np.int32),
              "max_new_tokens": len(r["tokens"])} for r in served])):
        np.testing.assert_array_equal(np.asarray(r["tokens"]),
                                      np.asarray(ref["tokens"]))
    solo.close()
    pm_spec = importlib.util.spec_from_file_location(
        "postmortem", Path(__file__).resolve().parent.parent
        / "scripts" / "postmortem.py")
    postmortem = importlib.util.module_from_spec(pm_spec)
    pm_spec.loader.exec_module(postmortem)
    kinds = {s["kind"] for s in postmortem.drill_story(events)}
    assert {"sim_phase", "sim_kill", "slo_state"} <= kinds, kinds
