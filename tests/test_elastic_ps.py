"""Elastic parameter server (``parallel.elastic_ps``) + the SLO
autoscaler (ISSUE 14): every reshard verb — split, merge, migrate —
lands byte-identical to a static-K run under a seeded schedule; a
lost-ack retry across a cutover dedupes exactly-once on whatever
shard now owns each leaf; a receiver killed mid-move aborts cleanly
(source un-fenced, zero commits lost); ``ResilientPSClient`` rides
fence/stale rejections without burning its retry budget; the
``SLOWatchdog`` hysteresis and the ``Autoscaler`` decision table
(breach → action, cooldown, bounds, idle scale-down, verb-error
capture) run against injected clocks; the gateway's elastic
membership verbs admit warm and drain safe; and the DOWNPOUR socket
arm survives a K=2→3 split plus a live migration MID-TRAINING with a
final center byte-identical to an unmolested fixed-topology run.

The whole module runs under ``racecheck.enable()`` — the migration
suite must be race-clean, not just pass."""

import importlib.util
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from distkeras_tpu import flight_recorder, telemetry
from distkeras_tpu.analysis import racecheck
from distkeras_tpu.data import datasets
from distkeras_tpu.gateway import EngineReplica, ServingGateway
from distkeras_tpu.models import ModelSpec, model_config
from distkeras_tpu.parallel.elastic_ps import (
    ElasticPSClient,
    ElasticPSGroup,
    MigrationAborted,
    ShardMap,
    fetch_shard_map,
)
from distkeras_tpu.parallel.host_ps import (
    HostParameterServer,
    PSShardFencedError,
    ResilientPSClient,
    pack_params,
)
from distkeras_tpu.parallel.update_rules import (
    AdagRule,
    DownpourRule,
    DynSGDRule,
    ElasticRule,
)
from distkeras_tpu.serving import DecodeEngine
from distkeras_tpu.trainers import AEASGD, DOWNPOUR

MLP = model_config("mlp", (8,), num_classes=4, hidden=(16,))
DATA = datasets.synthetic_classification(1024, (8,), 4, seed=0)


def _init_center():
    import jax.numpy as jnp
    model = ModelSpec.from_config(MLP).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.float32))
    return jax.tree_util.tree_map(np.asarray, variables["params"])

DELTA_RULES = [DownpourRule(), AdagRule(), DynSGDRule()]


@pytest.fixture(autouse=True)
def _racecheck():
    """Every lock in elastic_ps is a racecheck factory: the whole
    suite (migration included) runs instrumented and fails on any
    race/order/deadlock report."""
    racecheck.enable()
    yield
    reports = racecheck.disable()
    assert not reports, "\n".join(str(r) for r in reports)


def _params(seed=0, shapes=((3, 4), (4,), (8, 2), (5,), (2, 2, 2))):
    rng = np.random.default_rng(seed)
    return {f"w{i}": rng.normal(size=s).astype(np.float32)
            for i, s in enumerate(shapes)}


def _schedule(n_workers=3, n_commits=12, seed=7):
    """A fixed seeded commit schedule: (worker, delta) pairs — seqs
    are stamped per worker by whoever replays it."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_commits):
        w = int(rng.integers(n_workers))
        d = {k: rng.normal(size=v.shape).astype(np.float32) * 1e-2
             for k, v in _params(0).items()}
        out.append((w, d))
    return out


def _elastic_clients(grp, template, n, retries=2, base_id=0):
    return [ResilientPSClient.for_elastic(
        [grp.addresses[0]], worker_id=base_id + w, template=template,
        retries=retries, backoff_base=1e-4, seed=w)
        for w in range(n)]


def _widest(grp):
    plan = grp.map.plan
    return max(range(len(plan)), key=lambda s: len(plan[s]))


# -- byte-identity of the reshard verbs --------------------------------


@pytest.mark.parametrize("rule", DELTA_RULES,
                         ids=lambda r: type(r).__name__)
def test_split_merge_migrate_byte_identical_to_static(rule):
    """ISSUE 14 acceptance: a seeded serial schedule interleaved with
    a split, a merge, AND a live migration lands on the same bytes as
    the unsharded reference — clocks and staleness law included (the
    children inherit the parent's clocks at the quiescent boundary,
    the merge re-unions them, the move ships them verbatim)."""
    center = _params(0)
    ref = HostParameterServer(rule, center)
    grp = ElasticPSGroup(rule, center, num_shards=2, num_servers=1)
    try:
        clients = _elastic_clients(grp, center, 3)
        for w in range(3):
            ref.pull(w)
            clients[w].pull()
        sched = _schedule()
        seqs = {w: 0 for w in range(3)}
        for i, (w, d) in enumerate(sched):
            if i == 4:
                grp.split(_widest(grp))          # K=2 -> 3
            elif i == 7:
                grp.merge(0, 1)                  # K=3 -> 2
            elif i == 9:
                dst = grp.add_server()
                grp.migrate(_widest(grp), dst)   # cross-server move
            ref.commit(w, d, seq=seqs[w])
            seqs[w] += 1
            clients[w].commit(d)
        assert pack_params(ref.center) == pack_params(grp.center)
        assert grp.num_commits == len(sched)
        for c in clients:
            c.close()
    finally:
        grp.stop()


def test_elastic_family_byte_identical_across_reshard():
    """The elastic family (whole-local-tree lerp, ``local=`` riding
    the wire) reshards exactly too: split + migrate mid-schedule, the
    center AND every worker's pulled local tree match the unsharded
    reference byte for byte."""
    rule = ElasticRule(alpha=0.3)
    center = _params(0)
    ref = HostParameterServer(rule, center)
    grp = ElasticPSGroup(rule, center, num_shards=2, num_servers=1)
    try:
        clients = _elastic_clients(grp, center, 2, base_id=10)
        locals_ref = {w: ref.pull(w) for w in range(2)}
        locals_el = {w: clients[w].pull() for w in range(2)}
        rng = np.random.default_rng(3)
        for i in range(8):
            if i == 3:
                grp.split(_widest(grp))
            elif i == 6:
                grp.migrate(0, grp.add_server())
            w = int(rng.integers(2))
            step = jax.tree_util.tree_map(
                lambda x: np.asarray(
                    x + rng.normal(size=x.shape).astype(x.dtype)
                    * 0.1), locals_ref[w])
            locals_ref[w] = ref.commit(w, step, step, seq=i)
            locals_el[w] = clients[w].commit(step, step)
        assert pack_params(ref.center) == pack_params(grp.center)
        for w in range(2):
            assert (pack_params(locals_ref[w])
                    == pack_params(locals_el[w]))
        for c in clients:
            c.close()
    finally:
        grp.stop()


# -- exactly-once across the cutover -----------------------------------


def test_lost_ack_retry_dedupes_across_cutover():
    """The lost-ack shape, aggravated: commit seq=N acks, the ack is
    'lost', the shard MIGRATES to a brand-new server, and the retry
    of seq=N against the new owner serves the cached reply byte-for-
    byte without applying twice (the per-leaf dedupe table travelled
    with the move)."""
    tel = telemetry.enable()
    try:
        center = _params(0)
        grp = ElasticPSGroup(AdagRule(), center, num_shards=2,
                             num_servers=1)
        try:
            c = ElasticPSClient(grp.addresses, worker_id=0,
                                template=center)
            c.pull()
            d = jax.tree_util.tree_map(np.ones_like, center)
            r1 = c.commit(d, seq=0)
            assert grp.num_commits == 1
            dst = grp.add_server()
            grp.migrate(0, dst)
            # the client still routes via the old map: the retired
            # source rejects carrying the NEW map — adopt and go again
            with pytest.raises(PSShardFencedError) as exc:
                c.commit(d, seq=0)
            assert exc.value.map_obj is not None
            c.apply_shard_map(exc.value.map_obj)
            r2 = c.commit(d, seq=0)  # the retry, on the new owner
            assert grp.num_commits == 1  # never applied twice
            for k in center:
                np.testing.assert_array_equal(r1[k], r2[k])
            assert tel.metrics.counter(
                "ps_commit_dedup_total").value >= 1
            c.commit(d, seq=1)  # a FRESH seq still applies
            assert grp.num_commits == 2
            c.close()
        finally:
            grp.stop()
    finally:
        telemetry.disable()


def test_fence_refresh_spares_the_retry_budget():
    """A reshard under a live ``ResilientPSClient`` costs map
    refreshes (``ps_shard_fence_refresh_total``), never transport
    retries: with retries=0 the client sails through a split AND a
    migration."""
    tel = telemetry.enable()
    try:
        center = _params(0)
        grp = ElasticPSGroup(DownpourRule(), center, num_shards=2,
                             num_servers=1)
        try:
            c = ResilientPSClient.for_elastic(
                grp.addresses, worker_id=0, template=center,
                retries=0)
            c.pull()
            d = jax.tree_util.tree_map(np.ones_like, center)
            c.commit(d)
            grp.split(_widest(grp))
            c.commit(d)
            grp.migrate(0, grp.add_server())
            c.commit(d)
            assert grp.num_commits == 3
            assert c.retry_count == 0
            assert tel.metrics.counter(
                "ps_shard_fence_refresh_total").value >= 1
            assert tel.metrics.counter(
                "ps_map_refresh_total").value >= 2
            c.close()
        finally:
            grp.stop()
    finally:
        telemetry.disable()


def test_receiver_kill_aborts_migration_cleanly(tmp_path):
    """Chaos acceptance: the RECEIVING server dies mid-move — cutover
    raises ``MigrationAborted``, the source un-fences and keeps
    serving, zero commits lost, and the abort is flight-recorded."""
    tel = telemetry.enable()
    flight_recorder.start(str(tmp_path / "flight"))
    try:
        center = _params(0)
        grp = ElasticPSGroup(AdagRule(), center, num_shards=2,
                             num_servers=1)
        try:
            c = ResilientPSClient.for_elastic(
                grp.addresses, worker_id=0, template=center,
                retries=2, backoff_base=1e-4)
            c.pull()
            d = jax.tree_util.tree_map(np.ones_like, center)
            for _ in range(3):
                c.commit(d)
            doomed = grp.add_server()
            grp.start_migration(0, doomed)
            grp.servers[doomed].kill()
            # the nastiest timing: the courier already streamed
            # everything and went QUIET before the kill, so drain
            # alone would pass — only the finalize round-trip can
            # notice the corpse before the map flips onto it
            with pytest.raises(MigrationAborted):
                grp.cutover(0, timeout=10.0)
            assert tel.metrics.counter(
                "elastic_migrations_aborted_total").value == 1
            # old topology still serves: same owner, commits land
            assert grp.map.version == 1
            for _ in range(2):
                c.commit(d)
            assert grp.num_commits == 5  # commits lost == 0
            stats = grp.shard_stats()
            assert not any(s["fenced"] for s in stats.values())
            kinds = [e["kind"] for e in
                     flight_recorder.active().read_events()]
            assert "shard_migrate_begin" in kinds
            assert "shard_migrate_abort" in kinds
            assert "shard_migrate_cutover" not in kinds
            c.close()
        finally:
            grp.stop()
    finally:
        flight_recorder.stop()
        telemetry.disable()


def test_migration_under_concurrent_load_exactly_once():
    """The race-clean migration suite: worker threads hammer commits
    while the control plane splits and live-migrates under them —
    every logical commit lands exactly once (the racecheck fixture
    holds the suite to race-free, not merely passing)."""
    center = _params(0)
    grp = ElasticPSGroup(AdagRule(), center, num_shards=2,
                         num_servers=2, placement="spread")
    n_workers, n_commits = 3, 8
    try:
        passed = threading.Barrier(n_workers + 1)
        errors: list = []

        def run(w):
            try:
                c = ResilientPSClient.for_elastic(
                    grp.addresses, worker_id=100 + w,
                    template=center, retries=4, backoff_base=1e-4,
                    seed=w)
                c.pull()
                rng = np.random.default_rng(w)
                passed.wait(timeout=30)
                for _ in range(n_commits):
                    d = {k: rng.normal(size=v.shape).astype(
                        np.float32) * 1e-3
                        for k, v in center.items()}
                    c.commit(d)
                c.close()
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append(e)

        threads = [threading.Thread(target=run, args=(w,))
                   for w in range(n_workers)]
        for t in threads:
            t.start()
        passed.wait(timeout=30)
        grp.split(_widest(grp))
        dst = grp.add_server()
        grp.migrate(0, dst)
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert grp.num_commits == n_workers * n_commits
        assert all(np.isfinite(np.asarray(v)).all()
                   for v in jax.tree_util.tree_leaves(grp.center))
    finally:
        grp.stop()


# -- the versioned map & control-plane edges ---------------------------


def test_shard_map_roundtrip_and_canonical_ids():
    m = ShardMap(3, [[2, 5], [0, 1]], [("a", 1), ("b", 2)], [0, 7])
    m2 = ShardMap.from_obj(m.to_obj())
    assert (m2.version, m2.plan, m2.owners, m2.epochs) == \
        (3, [[2, 5], [0, 1]], [("a", 1), ("b", 2)], [0, 7])
    with pytest.raises(ValueError, match="arity"):
        ShardMap(1, [[0]], [("a", 1)], [0, 1])
    # group-side renumbering law: ids sort by first leaf index
    grp = ElasticPSGroup(AdagRule(), _params(0), num_shards=3)
    try:
        firsts = [p[0] for p in grp.map.plan]
        assert firsts == sorted(firsts)
        grp.split(_widest(grp))
        firsts = [p[0] for p in grp.map.plan]
        assert firsts == sorted(firsts)
        assert grp.map.version == 2
        fetched = fetch_shard_map(*grp.addresses[0])
        assert fetched.to_obj() == grp.map.to_obj()
    finally:
        grp.stop()


def test_reshard_verb_validation():
    grp = ElasticPSGroup(AdagRule(), _params(0), num_shards=2,
                         num_servers=2, placement="spread")
    try:
        one_leaf = min(range(grp.num_shards),
                       key=lambda s: len(grp.map.plan[s]))
        if len(grp.map.plan[one_leaf]) == 1:
            with pytest.raises(ValueError, match="cannot split"):
                grp.split(one_leaf)
        with pytest.raises(ValueError, match="itself"):
            grp.merge(0, 0)
        with pytest.raises(ValueError, match="different"):
            grp.merge(0, 1)  # spread placement: distinct owners
        with pytest.raises(ValueError, match="already lives"):
            grp.migrate(0, 0)
        with pytest.raises(ValueError, match="no migration"):
            grp.cutover(0)
        dst = grp.add_server()
        grp.start_migration(0, dst)
        with pytest.raises(ValueError, match="already migrating"):
            grp.start_migration(0, dst)
        grp.cutover(0, timeout=10.0)
    finally:
        grp.stop()


# -- SLO watchdog hysteresis -------------------------------------------


def _depth_watchdog(tel, sustain):
    tel.metrics.gauge("serving_queue_depth").set(0)
    return telemetry.SLOWatchdog(
        tel.metrics, thresholds={"queue_depth": (8.0, 1e9)},
        sustain_secs=sustain)


def test_watchdog_sustain_holds_both_directions():
    """A transition (breach AND recovery) must hold for
    ``sustain_secs`` across evaluations before it commits; a single
    noisy sample flips nothing."""
    tel = telemetry.enable()
    try:
        wd = _depth_watchdog(tel, sustain=5.0)
        depth = tel.metrics.gauge("serving_queue_depth")
        assert wd.evaluate(now_s=0.0)["state"] == "ok"
        depth.set(20)
        v = wd.evaluate(now_s=1.0)   # arms the window
        assert (v["state"], v["raw_state"]) == ("ok", "degraded")
        assert wd.evaluate(now_s=4.0)["state"] == "ok"
        assert wd.evaluate(now_s=6.5)["state"] == "degraded"
        depth.set(0)                 # recovery is held too
        assert wd.evaluate(now_s=7.0)["state"] == "degraded"
        assert wd.evaluate(now_s=11.0)["state"] == "degraded"
        assert wd.evaluate(now_s=12.1)["state"] == "ok"
    finally:
        telemetry.disable()


def test_watchdog_noisy_sample_rearms_the_window():
    """A candidate that vanishes before its window elapses disarms;
    re-appearing restarts the clock from the new sighting."""
    tel = telemetry.enable()
    try:
        wd = _depth_watchdog(tel, sustain=5.0)
        depth = tel.metrics.gauge("serving_queue_depth")
        depth.set(20)
        wd.evaluate(now_s=0.0)       # pending degraded since t=0
        depth.set(0)
        assert wd.evaluate(now_s=1.0)["state"] == "ok"  # disarmed
        depth.set(20)
        wd.evaluate(now_s=2.0)       # re-armed at t=2
        assert wd.evaluate(now_s=6.9)["state"] == "ok"
        assert wd.evaluate(now_s=7.1)["state"] == "degraded"
    finally:
        telemetry.disable()


def test_watchdog_default_edge_trigger_and_validation():
    tel = telemetry.enable()
    try:
        wd = _depth_watchdog(tel, sustain=0.0)
        tel.metrics.gauge("serving_queue_depth").set(20)
        assert wd.evaluate(now_s=0.0)["state"] == "degraded"
        tel.metrics.gauge("serving_queue_depth").set(0)
        assert wd.evaluate(now_s=0.1)["state"] == "ok"
        with pytest.raises(ValueError, match="unknown SLO signal"):
            telemetry.SLOWatchdog(tel.metrics,
                                  thresholds={"nope": (1, 2)})
        with pytest.raises(ValueError, match="must not exceed"):
            telemetry.SLOWatchdog(tel.metrics,
                                  thresholds={"queue_depth": (9, 3)})
        with pytest.raises(ValueError, match="sustain_secs"):
            telemetry.SLOWatchdog(tel.metrics, sustain_secs=-1)
    finally:
        telemetry.disable()


# -- the autoscaler decision table -------------------------------------


def _breach(signal, value=0.5, level="critical"):
    return {"state": level, "raw_state": level,
            "signals": {signal: value},
            "breaches": {signal: {"value": value, "level": level,
                                  "degraded_at": 0.0,
                                  "critical_at": 0.1}}}


_QUIET = {"state": "ok", "raw_state": "ok", "signals": {},
          "breaches": {}}


def _scaler(tel, **kw):
    wd = telemetry.SLOWatchdog(tel.metrics)
    kw.setdefault("cooldown_s", 30.0)
    kw.setdefault("idle_sustain_s", 60.0)
    return telemetry.Autoscaler(wd, **kw)


def test_autoscaler_breach_to_action_and_bounds():
    tel = telemetry.enable()
    try:
        k = {"n": 2}
        sc = _scaler(tel, split_shard=lambda: None,
                     shard_count=lambda: k["n"], max_shards=4)
        d, = sc.decide(_breach("ps_lock_wait"), now_s=0.0)
        assert (d["domain"], d["action"], d["executed"]) == \
            ("ps", "split", True)
        assert d["signal"] == "ps_lock_wait" and d["reason"] is None
        k["n"] = 4  # at the bound: suppressed, reason says so
        d, = sc.decide(_breach("ps_lock_wait"), now_s=0.0)
        assert not d["executed"] and d["reason"] == "bounds"
        # a breach outside the domain's signal set decides nothing
        assert sc.decide(_breach("shed_rate"), now_s=0.0) == []
    finally:
        telemetry.disable()


def test_autoscaler_cooldown_suppresses_then_releases():
    tel = telemetry.enable()
    try:
        calls = []
        sc = _scaler(tel, split_shard=lambda: calls.append("s"),
                     shard_count=lambda: 1 + len(calls))
        d, = sc.step(_breach("ps_lock_wait"), now_s=0.0)
        assert d["executed"] and calls == ["s"]
        d, = sc.step(_breach("ps_lock_wait"), now_s=10.0)
        assert not d["executed"] and d["reason"] == "cooldown"
        assert calls == ["s"]
        d, = sc.step(_breach("ps_lock_wait"), now_s=31.0)
        assert d["executed"] and calls == ["s", "s"]
    finally:
        telemetry.disable()


def test_autoscaler_idle_scales_down_after_sustain():
    tel = telemetry.enable()
    try:
        merges = []
        sc = _scaler(tel, split_shard=lambda: None,
                     merge_shards=lambda: merges.append(1),
                     shard_count=lambda: 3, min_shards=1,
                     cooldown_s=0.0)
        sc.step(_QUIET, now_s=0.0)   # seeds the idle clock
        assert sc.decide(_QUIET, now_s=30.0) == []
        d, = sc.step(_QUIET, now_s=61.0)
        assert (d["action"], d["executed"]) == ("merge", True)
        assert merges == [1]
        # a breach resets the idle clock
        sc.step(_breach("ps_lock_wait"), now_s=62.0)
        assert sc.decide(_QUIET, now_s=100.0) == []
    finally:
        telemetry.disable()


def test_autoscaler_gateway_domain_and_verb_error(tmp_path):
    """The gateway domain spawns on queue-depth breach; a verb that
    raises is captured as ``reason="error: ..."`` — recorded, never
    fatal — and every decision lands in the counter + flight ring."""
    tel = telemetry.enable()
    flight_recorder.start(str(tmp_path / "flight"))
    try:
        def boom():
            raise RuntimeError("no capacity")

        sc = _scaler(tel, spawn_replica=boom,
                     replica_count=lambda: 1, max_replicas=3)
        d, = sc.step(_breach("queue_depth", value=300.0), now_s=0.0)
        assert (d["domain"], d["action"]) == ("gateway", "spawn")
        assert not d["executed"]
        assert d["reason"].startswith("error:")
        assert tel.metrics.counter(
            "autoscale_decisions_total", domain="gateway",
            action="spawn").value == 1
        ev = [e for e in flight_recorder.active().read_events()
              if e["kind"] == "autoscale_decision"]
        assert len(ev) == 1 and ev[0]["reason"].startswith("error:")
    finally:
        flight_recorder.stop()
        telemetry.disable()


def test_autoscaler_split_reshards_a_live_group_exactly_once():
    """Breach -> split, executed on a LIVE ``ElasticPSGroup``: the
    decision's verb is the group's own ``split``, commits issued
    before and after it all land once, the center stays byte-equal to
    the unsharded reference, and at ``max_shards`` the next breach is
    recorded but suppressed."""
    tel = telemetry.enable()
    center = _params(0)
    ref = HostParameterServer(DownpourRule(), center)
    grp = ElasticPSGroup(DownpourRule(), center, num_shards=1,
                         num_servers=1)
    try:
        sc = _scaler(tel, split_shard=lambda: grp.split(_widest(grp)),
                     shard_count=lambda: grp.num_shards, max_shards=2,
                     cooldown_s=0.0)
        (client,) = _elastic_clients(grp, center, 1)
        ref.pull(0)
        client.pull()
        sched = _schedule(n_workers=1, n_commits=6)
        for i, (_, d) in enumerate(sched):
            if i == 3:
                dec, = sc.step(_breach("ps_lock_wait"), now_s=0.0)
                assert (dec["action"], dec["executed"]) == \
                    ("split", True)
                assert grp.num_shards == 2
            ref.commit(0, d, seq=i)
            client.commit(d)
        dec, = sc.step(_breach("ps_lock_wait"), now_s=1.0)
        assert not dec["executed"] and dec["reason"] == "bounds"
        assert grp.num_shards == 2
        assert grp.num_commits == len(sched)
        assert pack_params(ref.center) == pack_params(grp.center)
        client.close()
    finally:
        grp.stop()
        telemetry.disable()


def test_autoscaler_spawns_a_serving_replica_and_queue_depth_clears():
    """The gateway domain's closed loop on real engines: a backlog on
    a one-replica gateway breaches ``queue_depth``; the decision's
    verb admits a second ``EngineReplica`` through
    ``gateway.add_replica``; the newcomer serves requests; and once
    the backlog is drained the breach is gone."""
    spec = model_config("transformer_lm", (32,), input_dtype="int32",
                        vocab_size=37, num_layers=1, d_model=32,
                        num_heads=2, max_len=32, dtype="float32")
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           np.zeros((2, 32), np.int32))

    def replica(name):
        return EngineReplica(
            DecodeEngine(model, variables, slots=2, prefill_align=4,
                         max_new_tokens=24), name=name)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 37, (6,)).astype(np.int32)
               for _ in range(28)]
    tel = telemetry.enable()
    try:
        gw = ServingGateway([replica("g0")], policy="least_loaded")
        sc = telemetry.Autoscaler(
            telemetry.SLOWatchdog(
                tel.metrics, thresholds={"queue_depth": (3.0, 1e9)}),
            spawn_replica=lambda: gw.add_replica(replica("auto0")),
            replica_count=gw.alive_replicas, min_replicas=1,
            max_replicas=2, cooldown_s=0.0, idle_sustain_s=1e9,
            gateway_scale_signals=("queue_depth",))
        with gw:
            rids = [gw.submit(p) for p in prompts[:16]]
            # the replica's driver thread moves the submissions into
            # the engine's queue on its own schedule: wait (bounded)
            # for the backlog to show on the gauge
            for _ in range(30_000):
                verdict = sc.watchdog.evaluate()
                if "queue_depth" in verdict["breaches"]:
                    break
                time.sleep(0.001)
            assert "queue_depth" in verdict["breaches"], verdict
            dec, = sc.step(verdict)
            assert (dec["domain"], dec["action"], dec["executed"]) \
                == ("gateway", "spawn", True)
            assert gw.alive_replicas() == 2
            rids += [gw.submit(p) for p in prompts[16:]]
            results = [gw.result(r, timeout=120) for r in rids]
            cleared = sc.watchdog.evaluate()
        assert [r.get("error") for r in results] == \
            [None] * len(prompts)
        assert "queue_depth" not in cleared["breaches"], cleared
        served_by_newcomer = sum(
            v for k, v in tel.metrics.snapshot()["counters"].items()
            if k.startswith("gateway_requests_total")
            and 'replica="auto0"' in k)
        assert served_by_newcomer > 0
    finally:
        telemetry.disable()


def test_autoscaler_constructor_validation():
    tel = telemetry.enable()
    try:
        wd = telemetry.SLOWatchdog(tel.metrics)
        with pytest.raises(ValueError, match="come as a pair"):
            telemetry.Autoscaler(wd, split_shard=lambda: None)
        with pytest.raises(ValueError, match="come as a pair"):
            telemetry.Autoscaler(wd, spawn_replica=lambda: None)
        with pytest.raises(ValueError, match="unknown SLO signal"):
            telemetry.Autoscaler(wd, ps_scale_signals=("bogus",))
    finally:
        telemetry.disable()


# -- gateway elastic membership ----------------------------------------


class _FakeServingReplica:
    def __init__(self, name, value=0.0):
        self.name = name
        self.alive = True
        self._vars = {"params": {"w": np.full(
            (2,), value, np.float32)}}
        self.swapped = None
        self.quiesced = False
        self.dispatched: list = []

    def start(self):
        return self

    def load(self):
        return 0

    def dispatch(self, spec, on_result):
        self.dispatched.append(spec["request_id"])
        on_result({"request_id": spec["request_id"],
                   "prompt": spec["prompt"],
                   "tokens": np.asarray([1], np.int32)})

    def health(self):
        return {"alive": self.alive, "state": "ok", "load": 0}

    def variables(self):
        return self._vars

    def swap(self, v):
        self.swapped = v
        self._vars = v

    def quiesce(self, timeout):
        self.quiesced = True
        return True


def test_gateway_add_replica_warms_from_live_peer(tmp_path):
    flight_recorder.start(str(tmp_path / "flight"))
    try:
        a = _FakeServingReplica("a", value=7.0)
        with ServingGateway([a], policy="round_robin") as gw:
            b = _FakeServingReplica("b", value=0.0)
            gw.add_replica(b)
            # admitted warm: the newcomer carries the fleet's weights
            np.testing.assert_array_equal(
                b.swapped["params"]["w"], a._vars["params"]["w"])
            assert gw.healthz()["replicas"]["b"]["alive"]
            for r in [gw.submit([1, 2]) for _ in range(4)]:
                gw.result(r, timeout=5)
            assert b.dispatched  # it takes traffic
            with pytest.raises(ValueError, match="already"):
                gw.add_replica(_FakeServingReplica("b"))
        kinds = [e["kind"] for e in
                 flight_recorder.active().read_events()]
        assert "replica_add" in kinds
    finally:
        flight_recorder.stop()


def test_gateway_remove_replica_quiesces_and_guards(tmp_path):
    flight_recorder.start(str(tmp_path / "flight"))
    try:
        a = _FakeServingReplica("a")
        b = _FakeServingReplica("b")
        with ServingGateway([a, b], policy="round_robin") as gw:
            gone = gw.remove_replica("b")
            assert gone is b and b.quiesced
            assert "b" not in gw.healthz()["replicas"]
            with pytest.raises(ValueError, match="no replica"):
                gw.remove_replica("b")
            with pytest.raises(ValueError, match="last routable"):
                gw.remove_replica("a")
            gw.result(gw.submit([1]), timeout=5)  # still serving
        kinds = [e["kind"] for e in
                 flight_recorder.active().read_events()]
        assert "replica_drain" in kinds
    finally:
        flight_recorder.stop()


# -- the scaling story -------------------------------------------------


def test_postmortem_scaling_story_replays_in_order():
    pm_path = (Path(__file__).resolve().parent.parent
               / "scripts" / "postmortem.py")
    spec = importlib.util.spec_from_file_location("_dkt_pm_el",
                                                  pm_path)
    pm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pm)
    events = [
        {"kind": "shard_migrate_cutover", "wall_s": 30.0, "shard": 0,
         "dst": ["h", 2], "epoch": 17, "version": 3,
         "latency_s": 0.004},
        {"kind": "autoscale_decision", "wall_s": 10.0, "domain": "ps",
         "action": "split", "signal": "ps_lock_wait", "value": 0.02,
         "count": 1, "executed": True, "reason": None},
        {"kind": "commit", "wall_s": 11.0, "worker": 0},  # filtered
        {"kind": "shard_split", "wall_s": 12.0, "shard": 1, "at": 2,
         "version": 2, "sizes": [2, 2]},
        {"kind": "autoscale_decision", "wall_s": 40.0,
         "domain": "gateway", "action": "spawn",
         "signal": "queue_depth", "value": 12.0, "count": 2,
         "executed": False, "reason": "cooldown"},
        {"kind": "replica_add", "wall_s": 50.0, "replica": "auto0",
         "total": 2},
    ]
    story = pm.scaling_story(events)
    assert [e["wall_s"] for e in story] == [10.0, 12.0, 30.0, 40.0,
                                            50.0]
    texts = [e["what"] for e in story]
    assert "ps: split on ps_lock_wait=0.02 executed" in texts[0]
    assert "split at leaf 2" in texts[1] and "v2" in texts[1]
    assert "cut over" in texts[2] and "epoch 17" in texts[2]
    assert "suppressed (cooldown)" in texts[3]
    assert "replica auto0 admitted (fleet now 2)" in texts[4]


# -- trainer-level zero-downtime proof ---------------------------------


def _wait_commits(grp, n, deadline_s=60.0, stop=None):
    t0 = telemetry.now()
    while grp.num_commits < n:
        if stop is not None and stop.is_set():
            return False
        if telemetry.now() - t0 > deadline_s:
            raise TimeoutError(
                f"stuck at {grp.num_commits}/{n} commits")
        import time
        time.sleep(0.002)
    return True


def _downpour(grp, **kw):
    return DOWNPOUR(MLP, fidelity="host", transport="socket",
                    num_workers=1, communication_window=2,
                    batch_size=16, num_epoch=1, learning_rate=0.01,
                    seed=0, worker_retries=10, ps_elastic=True,
                    ps_address=grp.addresses[0], **kw)


def test_trainer_mid_training_reshard_byte_identical():
    """The tentpole acceptance, end to end on the socket arm: a
    K=2→3 split and a live cross-server migration land MID-TRAINING
    under a single-worker DOWNPOUR run, and the final center is
    byte-identical to the same run against an unmolested fixed-K
    group (additive rule + inherited clocks = the reshard is
    invisible to the math)."""
    center = _init_center()
    ref_grp = ElasticPSGroup(DownpourRule(), center, num_shards=2,
                             num_servers=1)
    dut_grp = ElasticPSGroup(DownpourRule(), center, num_shards=2,
                             num_servers=1)
    try:
        ops = {}
        done = threading.Event()

        def reshard():
            if not _wait_commits(dut_grp, 2, stop=done):
                return
            ops["at_split"] = dut_grp.num_commits
            dut_grp.split(_widest(dut_grp))
            if not _wait_commits(dut_grp, 5, stop=done):
                return
            dst = dut_grp.add_server()
            dut_grp.migrate(_widest(dut_grp), dst)
            ops["migrated"] = True

        driver = threading.Thread(target=reshard)
        driver.start()
        try:
            dut = _downpour(dut_grp)
            dut.train(DATA)
        finally:
            done.set()
            driver.join(timeout=60)
        assert ops.get("migrated"), (
            "the reshard thread never completed its migration")
        ref = _downpour(ref_grp)
        ref.train(DATA)
        rounds = len(ref.history["round_loss"])
        assert ops["at_split"] < rounds  # genuinely mid-training
        assert ref_grp.num_commits == dut_grp.num_commits == rounds
        assert dut_grp.num_shards == 3
        assert pack_params(ref_grp.center) == \
            pack_params(dut_grp.center)
        assert pack_params(ref.trained_variables["params"]) == \
            pack_params(dut.trained_variables["params"])
    finally:
        ref_grp.stop()
        dut_grp.stop()


def test_aeasgd_trains_against_elastic_group_k2():
    """The elastic FAMILY (whole-tree lerp) over the elastic WIRE at
    K=2 — the composition the pre-ISSUE-14 gate forbade twice over —
    trains to a finite loss against an external group."""
    center = _init_center()
    grp = ElasticPSGroup(ElasticRule(alpha=0.5), center,
                         num_shards=2, num_servers=2,
                         placement="spread")
    try:
        t = AEASGD(MLP, fidelity="host", transport="socket",
                   num_workers=2, communication_window=2,
                   batch_size=16, num_epoch=1, seed=0,
                   worker_retries=6, ps_elastic=True,
                   ps_address=grp.addresses[0])
        t.train(DATA)
        assert np.isfinite(t.history["round_loss"][-1])
        assert grp.num_commits == len(t.history["round_loss"])
    finally:
        grp.stop()


def test_autoscaler_defers_while_the_gateway_is_busy():
    """ISSUE 18 fix: while a rolling update / migration is in flight
    (``busy()`` truthy) the autoscaler records its decision but defers
    the verb — and deferral costs one tick, NOT a cooldown window, so
    the very next quiet-gateway tick executes."""
    tel = telemetry.enable()
    try:
        calls = []
        busy = {"v": True}
        sc = _scaler(tel, spawn_replica=lambda: calls.append(1),
                     replica_count=lambda: 1 + len(calls),
                     max_replicas=4, busy=lambda: busy["v"])
        d, = sc.step(_breach("queue_depth", value=300.0), now_s=0.0)
        assert not d["executed"]
        assert d["reason"] == "deferred: busy" and calls == []
        assert tel.metrics.counter("autoscale_deferred_total",
                                   domain="gateway").value == 1
        # cooldown_s is 30 here: if the deferral had counted as an
        # action, this tick would report "cooldown" instead of acting
        busy["v"] = False
        d, = sc.step(_breach("queue_depth", value=300.0), now_s=1.0)
        assert d["executed"] and calls == [1]
        # quiesced gateway: the guard never fires on empty decisions
        assert sc.step(_QUIET, now_s=2.0) == []
        assert tel.metrics.counter("autoscale_deferred_total",
                                   domain="gateway").value == 1
    finally:
        telemetry.disable()
