"""Production traffic + chaos simulator (ISSUE 18).

Every perf script in this repo pumps one synthetic shape at a fixed
rate; the reference system's core claim is surviving *real* cluster
conditions.  This module closes that gap with a workload harness that
replays parameterized production traces against the full serving stack
and a capacity model fitted from the telemetry it produces:

* ``TraceSpec`` / ``generate_trace`` — a seeded trace generator:
  diurnal rate cycles, flash crowds, heavy-tailed prompt lengths
  (lognormal) and output lengths (Pareto), session-sticky users
  sharing per-group system prefixes (Zipf-distributed session
  popularity), and mixed tenant/priority classes.  The whole arrival +
  length + session + tenant stream is a pure function of
  ``TraceSpec.seed``: the non-homogeneous Poisson process is drawn by
  thinning against the analytic ``rate_at`` curve with one pinned rng,
  fixed draw order per arrival.
* ``replay`` — paces a trace against a ``ServingGateway`` in wall time
  (``time_scale`` compresses or dilates), polling results without ever
  blocking the offered-load clock, while a ``ChaosSchedule`` fires
  wall-clock fault windows (via ``ChaosTransport(windows=...)``) and
  replica/PS ``kill()``s phase-aligned with the load curve —
  fault-during-flash-crowd is the scenario that matters.
* ``stepped_rate_search`` / ``CapacityModel`` — sustainable QPS at a
  fixed TTFT SLO per configuration, found by walking a geometric rate
  ladder until attainment breaks; the fitted model answers
  ``required(qps)`` — the replica target a closed-loop drill holds the
  ``telemetry.Autoscaler`` to.
* ``run_drill`` — the closed-loop acceptance scenario: the autoscaler
  must track ``required(rate_at(t))`` as the curve moves, with
  convergence seconds (``sim_drill_convergence_seconds_total``) and
  the watchdog's ``slo_violation_seconds_total`` as its counters
  (``tests/test_simulator.py::test_full_stack_drill_ledger`` runs it
  over real engines).

The replay loop is deliberately single-threaded — submissions, result
polling, chaos kills, and autoscaler ticks interleave in ONE pacing
loop — so the simulator itself holds no locks and adds no
nondeterminism beyond the stack under test.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Mapping, Optional, Sequence

import numpy as np

from distkeras_tpu import flight_recorder, telemetry
from distkeras_tpu.parallel.faults import (ChaosTransport,
                                           _validate_windows)

__all__ = [
    "TraceSpec", "Arrival", "Trace", "generate_trace", "rate_at",
    "peak_rate", "in_crowd", "declared_length_quantiles",
    "ChaosSchedule", "ReplicaPool", "replay", "stepped_rate_search",
    "CapacityPoint", "CapacityModel", "run_drill",
]

#: standard-normal quantile for p99 — the lognormal length model's
#: declared p99 is ``median * exp(sigma * Z99)``
_Z99 = 2.3263478740408408

#: per-tenant length models a ``TraceSpec`` tenant quad may select
#: (the optional 4th tuple element); ``prefill_heavy`` is the long-
#: prompt / short-output flood class the disaggregated serving drill
#: generates natively
TENANT_CLASSES = ("default", "prefill_heavy")


# ---------------------------------------------------------------------
# trace specification + generation
# ---------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TraceSpec:
    """Parameterized production-trace shape.  Everything downstream —
    arrivals, lengths, sessions, tenants — derives from ``seed``
    alone, so a trace is replayable and a chaos drill reproducible.

    Rate curve: ``mean_qps`` modulated by a sinusoidal diurnal cycle
    (``diurnal_amplitude`` in [0, 1); period defaults to the trace
    duration so the integral over the trace matches the requested mean
    exactly) and multiplied inside each flash-crowd window
    ``(t_start, t_end, multiplier)``.

    Lengths: prompts are lognormal (``prompt_median`` tokens median,
    ``prompt_sigma`` log-space sigma) clipped to
    [``prompt_min``, ``prompt_max``]; outputs are Pareto type I
    (``output_min`` scale, ``output_alpha`` tail index — smaller alpha
    = heavier tail; declared p99/p50 ratio is ``50**(1/alpha)``)
    clipped to [``output_min``, ``output_max``].

    Sessions: ``sessions`` users with Zipf(``session_zipf``)
    popularity; each session belongs to one of ``prefix_groups``
    groups sharing a ``prefix_len``-token system prefix (the
    prefix-cache workload shape).

    Tenants: ``(name, share, priority)`` triples — or ``(name, share,
    priority, tenant_class)`` quads — with shares normalized and
    priority riding into the engine QoS scheduler (0..2).  The
    optional class picks the tenant's length model: ``"default"``
    uses the spec-wide prompt/output models above;
    ``"prefill_heavy"`` draws long lognormal prompts
    (``heavy_prompt_median`` / ``heavy_prompt_sigma``) with outputs
    clipped to ``heavy_output_max`` — the prefill-flood workload the
    disaggregated serving drill rides on.  Heavy arrivals take their
    EXTRA length draws after the tenant draw, so a spec without
    heavy tenants generates a byte-identical trace per seed.
    """

    duration_s: float
    mean_qps: float
    seed: int = 0
    diurnal_amplitude: float = 0.0
    diurnal_period_s: Optional[float] = None
    flash_crowds: tuple = ()
    prompt_median: float = 24.0
    prompt_sigma: float = 0.6
    prompt_min: int = 4
    prompt_max: int = 512
    output_alpha: float = 2.0
    output_min: int = 4
    output_max: int = 256
    vocab: int = 1000
    sessions: int = 50
    session_zipf: float = 1.5
    prefix_groups: int = 4
    prefix_len: int = 2
    tenants: tuple = (("default", 1.0, 1),)
    heavy_prompt_median: float = 192.0
    heavy_prompt_sigma: float = 0.35
    heavy_output_max: int = 16

    def __post_init__(self):
        if self.duration_s <= 0 or self.mean_qps <= 0:
            raise ValueError("duration_s and mean_qps must be > 0")
        if not 0.0 <= self.diurnal_amplitude < 1.0:
            raise ValueError(
                f"diurnal_amplitude={self.diurnal_amplitude} outside "
                f"[0, 1) (the rate must stay positive)")
        for w in self.flash_crowds:
            t0, t1, mult = w
            if not (0.0 <= t0 < t1) or mult <= 0:
                raise ValueError(f"bad flash crowd {w!r}")
        if self.prompt_min < 1 or self.prompt_max < self.prompt_min:
            raise ValueError("need 1 <= prompt_min <= prompt_max")
        if self.prefix_len >= self.prompt_min:
            raise ValueError(
                f"prefix_len={self.prefix_len} must be below "
                f"prompt_min={self.prompt_min} (every prompt carries "
                f"its group prefix plus at least one own token)")
        if self.output_alpha <= 0 or self.output_min < 1:
            raise ValueError("need output_alpha > 0, output_min >= 1")
        if self.output_max < self.output_min:
            raise ValueError("need output_min <= output_max")
        if self.session_zipf <= 1.0:
            raise ValueError("session_zipf must be > 1")
        if self.sessions < 1 or self.prefix_groups < 1:
            raise ValueError("need sessions >= 1, prefix_groups >= 1")
        if not self.tenants:
            raise ValueError("tenants need positive shares")
        for ten in self.tenants:
            if len(ten) not in (3, 4):
                raise ValueError(
                    f"tenant {ten!r} must be (name, share, priority) "
                    f"or (name, share, priority, tenant_class)")
            if ten[1] <= 0:
                raise ValueError("tenants need positive shares")
            if len(ten) == 4 and ten[3] not in TENANT_CLASSES:
                raise ValueError(
                    f"unknown tenant class {ten[3]!r}; choose from "
                    f"{TENANT_CLASSES}")
        if self.heavy_prompt_median < 1:
            raise ValueError("heavy_prompt_median must be >= 1")
        if self.heavy_output_max < self.output_min:
            raise ValueError(
                f"heavy_output_max={self.heavy_output_max} below "
                f"output_min={self.output_min}")


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One trace row: arrival time (trace seconds) plus the request."""

    t: float
    prompt: np.ndarray
    max_new: int
    session: str
    tenant: str
    priority: int


@dataclasses.dataclass(frozen=True)
class Trace:
    spec: TraceSpec
    arrivals: tuple


def rate_at(spec: TraceSpec, t: float) -> float:
    """The analytic offered-rate curve (QPS) at trace time ``t``."""
    period = spec.diurnal_period_s or spec.duration_s
    r = spec.mean_qps * (
        1.0 + spec.diurnal_amplitude
        * math.sin(2.0 * math.pi * t / period))
    for t0, t1, mult in spec.flash_crowds:
        if t0 <= t < t1:
            r *= mult
    return r


def peak_rate(spec: TraceSpec) -> float:
    """An upper bound on ``rate_at`` over the trace — the thinning
    envelope (loose is fine: it only costs rejected candidate
    draws, never correctness)."""
    r = spec.mean_qps * (1.0 + spec.diurnal_amplitude)
    for _, _, mult in spec.flash_crowds:
        r *= max(1.0, mult)
    return r


def in_crowd(spec: TraceSpec, t: float) -> bool:
    return any(t0 <= t < t1 for t0, t1, _ in spec.flash_crowds)


def declared_length_quantiles(spec: TraceSpec) -> dict:
    """The analytic (pre-clipping) p50/p99 of the two length models —
    what the generated stream must reproduce (the heavy-tail
    regression test's reference)."""
    pm = float(spec.prompt_median)
    return {
        "prompt_p50": pm,
        "prompt_p99": pm * math.exp(spec.prompt_sigma * _Z99),
        "output_p50": spec.output_min * 0.5 ** (-1 / spec.output_alpha),
        "output_p99": spec.output_min * 0.01 ** (-1 / spec.output_alpha),
    }


def generate_trace(spec: TraceSpec) -> Trace:
    """Materialize the arrival stream: a non-homogeneous Poisson
    process (thinning against ``rate_at``) with per-arrival length /
    session / tenant draws in a FIXED order from ONE rng, so the whole
    trace is a pure function of ``spec.seed``."""
    rng = np.random.default_rng(spec.seed)
    # group prefixes + session->group assignment are drawn first so
    # they are independent of trace length
    prefixes = rng.integers(0, spec.vocab,
                            size=(spec.prefix_groups, spec.prefix_len))
    session_group = rng.integers(0, spec.prefix_groups,
                                 size=spec.sessions)
    shares = np.array([ten[1] for ten in spec.tenants], float)
    cum = np.cumsum(shares / shares.sum())
    peak = peak_rate(spec)
    arrivals = []
    t = 0.0
    while True:
        t += float(rng.exponential(1.0 / peak))
        if t >= spec.duration_s:
            break
        u = float(rng.random())  # thinning draw — consumed always
        if u * peak >= rate_at(spec, t):
            continue
        plen = int(np.clip(
            round(spec.prompt_median
                  * math.exp(float(rng.normal(0.0, spec.prompt_sigma)))),
            spec.prompt_min, spec.prompt_max))
        nnew = int(np.clip(
            round(spec.output_min * (1.0 + float(rng.pareto(
                spec.output_alpha)))),
            spec.output_min, spec.output_max))
        sess = int((int(rng.zipf(spec.session_zipf)) - 1) % spec.sessions)
        ti = int(np.searchsorted(cum, float(rng.random()),
                                 side="right"))
        ti = min(ti, len(spec.tenants) - 1)
        ten = spec.tenants[ti]
        if len(ten) == 4 and ten[3] == "prefill_heavy":
            # heavy-class REDRAW: two extra rng values consumed only
            # on heavy arrivals, so a spec without heavy tenants
            # replays byte-identically under the same seed
            plen = int(np.clip(
                round(spec.heavy_prompt_median * math.exp(float(
                    rng.normal(0.0, spec.heavy_prompt_sigma)))),
                spec.prompt_min, spec.prompt_max))
            nnew = int(np.clip(
                round(spec.output_min * (1.0 + float(rng.pareto(
                    spec.output_alpha)))),
                spec.output_min, spec.heavy_output_max))
        tail = rng.integers(0, spec.vocab,
                            size=plen - spec.prefix_len)
        prompt = np.concatenate(
            [prefixes[int(session_group[sess])], tail]).astype(np.int32)
        name, prio = ten[0], ten[2]
        arrivals.append(Arrival(t=t, prompt=prompt, max_new=nnew,
                                session=f"s{sess}", tenant=str(name),
                                priority=int(prio)))
    return Trace(spec=spec, arrivals=tuple(arrivals))


# ---------------------------------------------------------------------
# chaos schedule: wall-clock faults phase-aligned to the load curve
# ---------------------------------------------------------------------


class ChaosSchedule:
    """Wall-clock chaos phases in TRACE time.  One schedule owns the
    sim clock: ``replay`` anchors it at t=0 of the trace, the
    ``ChaosTransport`` built by :meth:`chaos_transport` reads the same
    clock for its fault ``windows``, and :meth:`poll` fires registered
    ``kill()``s when their trace time comes — so "kill a replica
    mid-flash-crowd" is literally a timestamp inside the crowd window.

    Args:
      windows: ``[(t_start, t_end, kinds)]`` transport-fault phases in
        trace seconds (validated here, handed to ``ChaosTransport``).
      kills: ``[(t, target)]`` — at trace time ``t`` call the zero-arg
        function registered for ``target`` (``register_kill``), once.
      time_scale: wall seconds per trace second (match ``replay``'s).
    """

    def __init__(self, *, windows=(), kills=(),
                 time_scale: float = 1.0):
        self.windows = _validate_windows(windows)
        self.kills = tuple(sorted(
            (float(t), str(name)) for t, name in kills))
        if any(t < 0 for t, _ in self.kills):
            raise ValueError("kill times must be >= 0")
        self.time_scale = float(time_scale)
        self._kill_fns: dict[str, Callable[[], None]] = {}
        self._fired: set[int] = set()
        self._t0: Optional[float] = None

    def register_kill(self, name: str,
                      fn: Callable[[], None]) -> None:
        self._kill_fns[str(name)] = fn

    def start(self, t0: Optional[float] = None) -> "ChaosSchedule":
        """Anchor trace t=0 at ``t0`` (a ``telemetry.now()`` stamp;
        default: now).  ``replay`` calls this with its own anchor so
        windows and kills share the pacing loop's clock."""
        self._t0 = telemetry.now() if t0 is None else float(t0)
        return self

    def clock(self) -> float:
        """Current trace time (0.0 before :meth:`start`)."""
        if self._t0 is None:
            return 0.0
        return (telemetry.now() - self._t0) / self.time_scale

    def chaos_transport(self, seed: int = 0, **kw) -> ChaosTransport:
        """A ``ChaosTransport`` whose wall-clock fault windows run on
        THIS schedule's trace clock (plus any op-counter schedule
        passed through ``kw``)."""
        return ChaosTransport(seed, windows=self.windows,
                              clock=self.clock, **kw)

    def poll(self) -> list[str]:
        """Fire every kill whose trace time has arrived (once each);
        returns the targets fired this call.  An unregistered target
        raises — a drill with a missing kill hook is a bug, not a
        no-op."""
        t = self.clock()
        fired = []
        for i, (kt, name) in enumerate(self.kills):
            if i in self._fired or t < kt:
                continue
            self._fired.add(i)
            fn = self._kill_fns.get(name)
            if fn is None:
                raise KeyError(
                    f"kill target {name!r} was never registered")
            telemetry.metrics().counter("sim_kills_total",
                                        target=name).inc()
            flight_recorder.record("sim_kill", target=name, sim_t=kt)
            fn()
            fired.append(name)
        return fired


class ReplicaPool:
    """Pre-warmed spare replicas behind ``Autoscaler`` verbs.  A real
    spawn pays replica construction + weight warm; the drill pays that
    cost up front (spares are built before the trace starts) so
    ``spawn_replica`` measures the *control loop's* convergence, not
    JIT warmup.  LIFO drain returns the most recently spawned."""

    def __init__(self, gateway, spares: Sequence = ()):
        self.gateway = gateway
        self._spares = list(spares)
        self._spawned: list[str] = []

    def spawn_replica(self) -> str:
        if not self._spares:
            raise RuntimeError("replica pool exhausted (no spares)")
        rep = self._spares.pop()
        self.gateway.add_replica(rep)
        self._spawned.append(rep.name)
        return rep.name

    def drain_replica(self) -> str:
        if not self._spawned:
            raise RuntimeError("no pool-spawned replica to drain")
        name = self._spawned.pop()
        self.gateway.remove_replica(name)
        return name

    def replica_count(self) -> int:
        return self.gateway.alive_replicas()

    def spares_left(self) -> int:
        return len(self._spares)


# ---------------------------------------------------------------------
# replay: pace a trace against a gateway
# ---------------------------------------------------------------------


def _percentile(xs: list, q: float) -> Optional[float]:
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, float), q))


def replay(trace: Trace, gateway, *, time_scale: float = 1.0,
           schedule: Optional[ChaosSchedule] = None,
           slo_ttft_s: Optional[float] = None,
           on_tick: Optional[Callable[[float], None]] = None,
           tick_interval_s: float = 0.1,
           drain_timeout_s: float = 60.0,
           label: str = "replay") -> dict:
    """Replay ``trace`` against ``gateway`` in (scaled) wall time.

    One single-threaded pacing loop: sleep to each arrival's wall
    deadline, submit it, and between submissions poll completed
    results (``gateway.try_result`` — non-blocking, so a slow request
    never stalls the offered load), fire due chaos kills
    (``schedule.poll``), and call ``on_tick(sim_t)`` roughly every
    ``tick_interval_s`` wall seconds (the drill's autoscaler tick).
    After the last arrival the loop drains until every request has a
    result or ``drain_timeout_s`` passes.

    TTFT is measured on the simulator's clock — first token time minus
    the wall moment THIS loop submitted — so gateway queueing and
    failover retries count against the SLO, exactly as a user would
    experience them.

    Returns a report: offered/completed/error/duplicate counts, SLO
    attainment (completed-ok-within-TTFT / arrivals), ttft p50/p95,
    the wall duration, and the raw per-request results.
    """
    spec = trace.spec
    m = telemetry.metrics()
    t0 = telemetry.now()
    if schedule is not None:
        schedule.start(t0)
    pending: dict = {}         # rid -> (arrival, wall submit stamp)
    results: list[dict] = []
    seen_rids: set = set()
    duplicates = errors = slo_miss = ok_within = 0
    next_tick = t0
    phase = "base"
    flight_recorder.record("sim_phase", phase=phase, sim_t=0.0)

    def service():
        """One poll round: results, kills, tick.  Never blocks."""
        nonlocal next_tick, duplicates, errors, slo_miss, ok_within
        if schedule is not None:
            schedule.poll()
        for rid in list(pending):
            res = gateway.try_result(rid)
            if res is None:
                continue
            arrival, t_sub = pending.pop(rid)
            if rid in seen_rids:
                duplicates += 1
                m.counter("sim_duplicate_results_total").inc()
            seen_rids.add(rid)
            m.counter("sim_results_total").inc()
            t_first = res.get("t_first")
            ttft = None if t_first is None else t_first - t_sub
            res = dict(res, sim_t=arrival.t, sim_ttft=ttft,
                       tenant=arrival.tenant)
            results.append(res)
            if res.get("error") is not None:
                errors += 1
            elif (slo_ttft_s is not None
                  and (ttft is None or ttft > slo_ttft_s)):
                slo_miss += 1
                m.counter("sim_slo_miss_total").inc()
            else:
                ok_within += 1
        nw = telemetry.now()
        if on_tick is not None and nw >= next_tick:
            next_tick = nw + tick_interval_s
            on_tick((nw - t0) / time_scale)

    with telemetry.span("sim_replay", label=label,
                        arrivals=len(trace.arrivals)):
        for a in trace.arrivals:
            target = t0 + a.t * time_scale
            while True:
                nw = telemetry.now()
                if nw >= target:
                    break
                service()
                _sleep(min(target - telemetry.now(), 0.005))
            ph = "crowd" if in_crowd(spec, a.t) else "base"
            if ph != phase:
                phase = ph
                flight_recorder.record("sim_phase", phase=ph,
                                       sim_t=a.t)
            m.gauge("sim_offered_qps").set(rate_at(spec, a.t))
            rid = gateway.submit(a.prompt, max_new_tokens=a.max_new,
                                 session=a.session, tenant=a.tenant,
                                 priority=a.priority)
            m.counter("sim_arrivals_total", tenant=a.tenant).inc()
            pending[rid] = (a, telemetry.now())
        deadline = telemetry.now() + drain_timeout_s
        while pending and telemetry.now() < deadline:
            service()
            _sleep(0.002)
        service()  # a final poll so the last tick/kill lands
    wall_s = telemetry.now() - t0
    ttfts = [r["sim_ttft"] for r in results
             if r["sim_ttft"] is not None and r.get("error") is None]
    n = len(trace.arrivals)
    return {
        "arrivals": n,
        "completed": len(results),
        "undrained": len(pending),
        "errors": errors,
        "duplicates": duplicates,
        "slo_miss": slo_miss,
        "slo_attainment": (ok_within / n) if n else 1.0,
        "offered_qps": (n / (spec.duration_s * time_scale)
                        if spec.duration_s else 0.0),
        "ttft_p50_s": _percentile(ttfts, 50.0),
        "ttft_p95_s": _percentile(ttfts, 95.0),
        "wall_s": wall_s,
        "results": results,
    }


# ---------------------------------------------------------------------
# capacity: stepped-rate search + fitted model
# ---------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CapacityPoint:
    """One configuration's measured sustainable point."""

    config: Mapping
    qps: float
    attainment: float
    ttft_p95_s: Optional[float]


def stepped_rate_search(gateway, base_spec: TraceSpec, *,
                        slo_ttft_s: float,
                        attainment: float = 0.9,
                        ladder: Sequence[float] = (4, 8, 16, 32, 64,
                                                   128, 256),
                        min_arrivals: int = 16,
                        max_segment_s: float = 3.0,
                        time_scale: float = 1.0,
                        drain_timeout_s: float = 15.0,
                        config: Optional[Mapping] = None) -> dict:
    """Find the configuration's sustainable QPS at the TTFT SLO by
    walking a geometric rate ladder: each rung replays a flat-rate
    segment of ``base_spec``'s request mix and must keep error-free
    SLO attainment at or above ``attainment``; the first failing rung
    stops the walk and the previous rung is the sustainable rate.
    Segment length adapts (``min_arrivals`` at low rates, capped at
    ``max_segment_s``) so every rung sees a meaningful sample.

    Returns ``{"sustainable_qps", "point": CapacityPoint, "rungs",
    "capped"}`` — ``capped`` True when even the top rung passed (the
    ladder, not the system, was the limit).  The sustainable rate also
    lands on the ``sim_capacity_qps{**config}`` gauge.
    """
    rungs = []
    best: Optional[CapacityPoint] = None
    cfg = dict(config or {})
    for i, q in enumerate(ladder):
        seg = min(max(min_arrivals / q, 0.5), max_segment_s)
        spec = dataclasses.replace(
            base_spec, mean_qps=float(q), duration_s=seg,
            diurnal_amplitude=0.0, flash_crowds=(),
            seed=base_spec.seed + 1000 + i)
        rep = replay(generate_trace(spec), gateway,
                     time_scale=time_scale, slo_ttft_s=slo_ttft_s,
                     drain_timeout_s=drain_timeout_s,
                     label=f"capacity:q{q}")
        ok = (rep["slo_attainment"] >= attainment
              and rep["errors"] == 0 and rep["undrained"] == 0)
        rungs.append({"qps": float(q), "ok": ok,
                      "attainment": rep["slo_attainment"],
                      "ttft_p95_s": rep["ttft_p95_s"],
                      "arrivals": rep["arrivals"]})
        if not ok:
            break
        best = CapacityPoint(config=cfg, qps=float(q),
                             attainment=rep["slo_attainment"],
                             ttft_p95_s=rep["ttft_p95_s"])
    sustainable = best.qps if best is not None else 0.0
    telemetry.metrics().gauge(
        "sim_capacity_qps",
        **{k: str(v) for k, v in cfg.items()}).set(sustainable)
    return {"sustainable_qps": sustainable, "point": best,
            "rungs": rungs, "capped": bool(rungs) and rungs[-1]["ok"]}


class CapacityModel:
    """Sustainable QPS as a function of replica count, fitted from
    measured ``CapacityPoint``s (configs must carry ``"replicas"``).
    Two or more distinct replica counts fit a line (least squares);
    one point scales proportionally through the origin — the
    conservative single-point model."""

    def __init__(self, points: Sequence[CapacityPoint]):
        if not points:
            raise ValueError("CapacityModel needs >= 1 point")
        self.points = tuple(points)
        ns = np.array([float(p.config["replicas"]) for p in points])
        qs = np.array([p.qps for p in points])
        if len(set(ns.tolist())) >= 2:
            self._slope, self._intercept = np.polyfit(ns, qs, 1)
        else:
            self._slope = float(qs[0] / max(ns[0], 1.0))
            self._intercept = 0.0

    def capacity(self, replicas: int) -> float:
        """Predicted sustainable QPS with ``replicas`` replicas."""
        return float(self._slope * replicas + self._intercept)

    def required(self, qps: float, *, headroom: float = 1.0,
                 max_replicas: int = 64) -> int:
        """Smallest replica count whose predicted capacity covers
        ``qps * headroom`` (at least 1; capped at ``max_replicas``)."""
        need = qps * headroom
        for n in range(1, max_replicas + 1):
            if self.capacity(n) >= need:
                return n
        return max_replicas

    def describe(self) -> dict:
        return {"slope": float(self._slope),
                "intercept": float(self._intercept),
                "points": [{"config": dict(p.config), "qps": p.qps,
                            "attainment": p.attainment,
                            "ttft_p95_s": p.ttft_p95_s}
                           for p in self.points]}


# ---------------------------------------------------------------------
# closed-loop drill
# ---------------------------------------------------------------------


def run_drill(trace: Trace, gateway, autoscaler, model: CapacityModel,
              *, schedule: Optional[ChaosSchedule] = None,
              time_scale: float = 1.0, headroom: float = 1.0,
              slo_ttft_s: Optional[float] = None,
              tick_interval_s: float = 0.25,
              max_replicas: int = 8,
              drain_timeout_s: float = 60.0) -> dict:
    """The closed-loop acceptance scenario: replay ``trace`` while the
    ``Autoscaler`` (stepped from the pacing loop, one tick per
    ``tick_interval_s``) must hold live capacity at the fitted model's
    ``required(rate_at(t))`` as the curve moves — through the flash
    crowd AND through whatever ``schedule`` kills mid-crowd.

    Convergence accounting: whenever ``gateway.alive_replicas()``
    drops below the target the drill opens a deficit episode; when
    capacity catches back up the episode closes and its wall duration
    accrues to ``sim_drill_convergence_seconds_total`` (one
    ``drill_converged`` flight event each).  SLO-violation seconds
    accrue on the watchdog's ``slo_violation_seconds_total`` as its
    evaluations tick.

    Returns ``{"replay", "episodes", "converged", "samples"}`` —
    ``converged`` is True when every deficit episode closed before the
    trace ended.
    """
    m = telemetry.metrics()
    samples: list[dict] = []
    episodes: list[dict] = []
    open_since: list = [None, 0]  # [wall stamp, target at open]

    def on_tick(sim_t: float) -> None:
        # observe BEFORE acting: step() may heal a deficit (post-kill
        # spawn) within this very tick, and the episode must still be
        # seen open for at least one observation
        target = min(model.required(rate_at(trace.spec, sim_t),
                                    headroom=headroom), max_replicas)
        actual = gateway.alive_replicas()
        autoscaler.step()
        nw = telemetry.now()
        if actual < target and open_since[0] is None:
            open_since[0], open_since[1] = nw, target
        elif actual >= target and open_since[0] is not None:
            dur = nw - open_since[0]
            episodes.append({"seconds": dur, "sim_t": sim_t,
                             "target": open_since[1],
                             "closed": True})
            m.counter("sim_drill_convergence_seconds_total").inc(dur)
            flight_recorder.record("drill_converged", sim_t=sim_t,
                                   seconds=dur, target=open_since[1],
                                   actual=actual)
            open_since[0] = None
        samples.append({"sim_t": sim_t, "target": target,
                        "actual": actual,
                        "state": autoscaler.watchdog.state})

    rep = replay(trace, gateway, time_scale=time_scale,
                 schedule=schedule, slo_ttft_s=slo_ttft_s,
                 on_tick=on_tick, tick_interval_s=tick_interval_s,
                 drain_timeout_s=drain_timeout_s, label="drill")
    if open_since[0] is not None:
        dur = telemetry.now() - open_since[0]
        episodes.append({"seconds": dur, "sim_t": None,
                         "target": open_since[1], "closed": False})
        m.counter("sim_drill_convergence_seconds_total").inc(dur)
    converged = all(e["closed"] for e in episodes)
    return {"replay": rep, "episodes": episodes,
            "converged": converged, "samples": samples}


def _sleep(seconds: float) -> None:
    if seconds > 0:
        time.sleep(seconds)
