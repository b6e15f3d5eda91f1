"""Hierarchical aggregation tier (ISSUE 20): GroupLeader fold law and
byte parity vs the flat topology, exactly-once under chaos on the
leader hop, leader-death degradation to direct-to-root, and the
trainer's ``ps_groups`` arm — the whole suite under the lockset race
detector."""

import threading
import time

import jax
import numpy as np
import pytest

from distkeras_tpu import flight_recorder, telemetry
from distkeras_tpu.analysis import racecheck
from distkeras_tpu.data import datasets
from distkeras_tpu.models import model_config
from distkeras_tpu.parallel.faults import ChaosTransport
from distkeras_tpu.parallel.hier_ps import (
    HIER_LEADER_BASE,
    GroupLeader,
    HierPSServer,
    LeaderRoute,
    resilient_hier_client,
)
from distkeras_tpu.parallel.host_ps import (
    HostParameterServer,
    PSClient,
    PSServer,
)
from distkeras_tpu.parallel.sharded_ps import ShardedParameterServer
from distkeras_tpu.parallel.update_rules import (
    DownpourRule,
    DynSGDRule,
    ElasticRule,
)
from distkeras_tpu.trainers import DOWNPOUR

jax.config.update("jax_platforms", "cpu")

MLP = model_config("mlp", (8,), num_classes=4, hidden=(16,))
DATA = datasets.synthetic_classification(512, (8,), 4, seed=0)


@pytest.fixture(autouse=True)
def _racecheck():
    """Leader fold/flush state is lock-heavy concurrent code: run the
    whole suite under the lockset race + deadlock detector and fail on
    any report."""
    racecheck.enable()
    yield
    reports = racecheck.disable()
    assert not reports, "\n".join(str(r) for r in reports)


def _dyadic_center(leaves=3, dim=8, seed=0):
    """Center leaves that are multiples of 2^-6: with dyadic payloads
    every f32 sum is exact in ANY association order, so byte equality
    across topologies tests the protocol, not float reassociation."""
    rng = np.random.default_rng(seed)
    return {f"w{i}": (rng.integers(-512, 512, size=(dim, dim))
                      * 2.0 ** -6).astype(np.float32)
            for i in range(leaves)}


def _dyadic_delta(center, w, r):
    val = np.float32((((w * 7 + r) % 13) - 6) * 2.0 ** -6)
    return {k: np.full_like(v, val) for k, v in center.items()}


def _expected_center(center, workers, rounds):
    out = {k: v.copy() for k, v in center.items()}
    for w in range(workers):
        for r in range(rounds):
            d = _dyadic_delta(center, w, r)
            out = {k: out[k] + d[k] for k in out}
    return out


def _run_workers(center, addrs_of, workers, rounds, client_of=None):
    """``workers`` socket threads, each pull + the seeded dyadic
    commit schedule; raises the first worker error."""
    barrier = threading.Barrier(workers)
    errs = []

    def worker(w):
        try:
            if client_of is not None:
                client = client_of(w)
            else:
                client = PSClient(*addrs_of(w), w, center)
            client.pull()
            barrier.wait()
            for r in range(rounds):
                if client_of is not None:
                    # ResilientPSClient stamps its own commit seqs
                    client.commit(_dyadic_delta(center, w, r))
                else:
                    client.commit(_dyadic_delta(center, w, r), seq=r)
            client.close()
        except Exception as e:
            errs.append(e)
            try:
                barrier.abort()
            except Exception:
                pass

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]


def _hier_topology(center, rule, groups, group_size,
                   aggregate_window=None):
    ps = HostParameterServer(rule, center)
    root = HierPSServer(ps, center).start()
    leaders = [GroupLeader(type(rule)(), center, root.address,
                           group_id=gi,
                           aggregate_window=(aggregate_window
                                             or group_size)).start()
               for gi in range(groups)]
    return ps, root, leaders


def test_flat_and_hier_centers_are_byte_identical():
    """The tentpole parity claim: the same seeded dyadic schedule
    through the flat single-root PS and the 2-leader tree lands on
    byte-identical centers, with the root applying every logical
    commit but seeing only W/g upstream messages."""
    center = _dyadic_center()
    W, G, R = 6, 2, 3
    g = W // G

    flat_ps = HostParameterServer(DownpourRule(), center)
    flat_srv = PSServer(flat_ps, center).start()
    _run_workers(center, lambda w: flat_srv.address, W, R)
    flat_srv.stop()

    ps, root, leaders = _hier_topology(center, DownpourRule(), G, g)
    _run_workers(center, lambda w: leaders[w // g].address, W, R)
    for lead in leaders:
        lead.drain()
        lead.stop()
    root.stop()

    exp = _expected_center(center, W, R)
    for k in center:
        assert (np.asarray(ps.center[k]).tobytes()
                == np.asarray(flat_ps.center[k]).tobytes()
                == exp[k].tobytes()), k
    assert ps.num_commits == flat_ps.num_commits == W * R
    assert sum(l.num_upstream for l in leaders) == W * R // g
    assert sum(l.num_commits for l in leaders) == W * R
    # the root's staleness record carries the leaders' per-worker
    # vectors — one entry per logical commit, same as flat
    assert len(ps.staleness_log) == W * R


class _CountingRoot:
    """Root PS proxy that counts what crosses the root hop: one
    message per commit call and the bytes of the tree it carries (a
    worker's delta on the flat topology, a leader's fold on the
    tree)."""

    def __init__(self, ps):
        self._ps = ps
        self._lock = threading.Lock()
        self.msgs = 0
        self.nbytes = 0

    def __getattr__(self, name):
        return getattr(self._ps, name)

    def _count(self, tree):
        with self._lock:
            self.msgs += 1
            self.nbytes += sum(np.asarray(v).nbytes
                               for v in tree.values())

    def commit(self, worker_id, payload, local=None, seq=None):
        self._count(payload)
        return self._ps.commit(worker_id, payload, local, seq=seq)

    def commit_packed(self, worker_id, payload, local=None, seq=None):
        self._count(payload)
        return self._ps.commit_packed(worker_id, payload, local,
                                      seq=seq)

    def commit_group(self, leader_id, fold, staleness, workers,
                     seq=None):
        self._count(fold)
        return self._ps.commit_group(leader_id, fold, staleness,
                                     workers, seq=seq)


@pytest.mark.parametrize("W,G", [(16, 4), (8, 4), (6, 2)])
def test_root_fan_in_is_exactly_the_group_size(W, G):
    """The root-hop arithmetic of the tree: the root applies every
    logical commit on both topologies, but sees ``W*R/g`` messages on
    the tree against ``W*R`` flat, and — the fold being one delta
    wide — exactly ``g`` times fewer bytes."""
    center = _dyadic_center()
    R, g = 2, W // G
    total = W * R

    flat_ps = HostParameterServer(DownpourRule(), center)
    flat_root = _CountingRoot(flat_ps)
    flat_srv = PSServer(flat_root, center).start()
    _run_workers(center, lambda w: flat_srv.address, W, R)
    flat_srv.stop()

    ps = HostParameterServer(DownpourRule(), center)
    hier_root = _CountingRoot(ps)
    root = HierPSServer(hier_root, center).start()
    leaders = [GroupLeader(DownpourRule(), center, root.address,
                           group_id=gi, aggregate_window=g).start()
               for gi in range(G)]
    _run_workers(center, lambda w: leaders[w // g].address, W, R)
    for lead in leaders:
        lead.drain()
        lead.stop()
    root.stop()

    assert flat_ps.num_commits == ps.num_commits == total
    assert flat_root.msgs == total
    assert (hier_root.msgs == sum(l.num_upstream for l in leaders)
            == total // g)
    assert flat_root.msgs / hier_root.msgs == g
    assert flat_root.nbytes == g * hier_root.nbytes
    for k in center:
        assert (np.asarray(ps.center[k]).tobytes()
                == np.asarray(flat_ps.center[k]).tobytes()), k


def test_dynsgd_fold_carries_staleness_vector_byte_exactly():
    """DynSGD scales each payload by 1/(staleness+1) at commit time;
    the leader must apply that scaling per CONSTITUENT with its own
    leader-local staleness before summing, and ship the staleness
    vector upstream — byte-exact against the hand-rolled law, with
    the root logging the vector."""
    center = _dyadic_center(seed=1)
    ps, root, leaders = _hier_topology(center, DynSGDRule(), 1, 3)
    lead = leaders[0]
    # all three pull at clock 0, then commit in order: worker i
    # commits at leader clock i -> staleness i
    for w in range(3):
        lead.pull(w)
    # the hand-rolled hier law: the fold accumulates from zero with
    # tree_axpy's exact association (alpha cast to the leaf dtype
    # BEFORE the multiply), then the root adds the finished fold to
    # the center — 1/3 is non-dyadic, so the association order is
    # part of the contract being pinned here
    fold = {k: np.zeros_like(v) for k, v in center.items()}
    for i in range(3):
        d = _dyadic_delta(center, i, 0)
        lead.commit(i, d, seq=0)
        a = np.float32(1.0) / np.float32(i + 1)
        fold = {k: a * d[k] + fold[k] for k in fold}
    exp = {k: center[k] + fold[k] for k in center}
    lead.drain()
    lead.stop()
    root.stop()
    for k in center:
        assert (np.asarray(ps.center[k]).tobytes()
                == exp[k].astype(np.float32).tobytes()), k
    assert list(ps.staleness_log) == [0, 1, 2]
    assert ps.num_commits == 3


def test_elastic_family_is_rejected_everywhere():
    """Hier is delta-family only: params-kind payloads have no
    closed-form sum, so the leader constructor, both servers'
    ``commit_group``, and the trainer kwarg all refuse."""
    center = _dyadic_center()
    with pytest.raises(ValueError, match="delta"):
        GroupLeader(ElasticRule(alpha=0.1), center, ("127.0.0.1", 1))
    host = HostParameterServer(ElasticRule(alpha=0.1), center)
    with pytest.raises(ValueError, match="delta"):
        host.commit_group(HIER_LEADER_BASE, center, [0], [0], seq=0)
    sharded = ShardedParameterServer(ElasticRule(alpha=0.1), center, 2)
    with pytest.raises(ValueError, match="delta"):
        sharded.commit_group(HIER_LEADER_BASE, center, [0], [0],
                             seq=0)


def test_upstream_retry_is_deduped_at_the_root():
    """A lost-ack leader retry re-sends the SAME window seq; the root
    must hand back the cached center without double-applying — the
    exactly-once hinge of the whole tier."""
    center = _dyadic_center()
    rule = DownpourRule()
    ps = HostParameterServer(rule, center)
    fold = _dyadic_delta(center, 0, 0)
    first = ps.commit_group(HIER_LEADER_BASE, fold, [0, 1], [0, 1],
                            seq=7)
    again = ps.commit_group(HIER_LEADER_BASE, fold, [0, 1], [0, 1],
                            seq=7)
    assert ps.num_commits == 2  # one window of two constituents
    for k in center:
        assert (np.asarray(first[k]).tobytes()
                == np.asarray(again[k]).tobytes())
    # sharded root: same dedupe, all shards advance exactly once
    sh = ShardedParameterServer(rule, center, 2)
    sh.commit_group(HIER_LEADER_BASE, fold, [0, 1], [0, 1], seq=3)
    sh.commit_group(HIER_LEADER_BASE, fold, [0, 1], [0, 1], seq=3)
    assert sh.num_commits == 2
    assert [s.num_commits for s in sh._shards] == [2, 2]
    # the deduped retry applied NOTHING: one window's fold, once
    for k in center:
        np.testing.assert_array_equal(
            np.asarray(sh.center[k]),
            center[k] + fold[k])


# every entry sets skip_ops itself (same sweep shape as
# test_faults.py): partition must cover the startup connects, the
# rate classes fault established exchanges
SWEEP = {
    "reset": dict(reset_rate=0.2, max_injections=4, skip_ops=6),
    "truncate": dict(truncate_rate=0.2, max_injections=4, skip_ops=6),
    "delay": dict(delay_rate=0.15, delay_s=0.02, skip_ops=6),
    "partition": dict(partition_at=0, partition_ops=6),
}


@pytest.mark.parametrize("fault", sorted(SWEEP))
def test_chaos_on_the_leader_hop_stays_exactly_once(fault):
    """``ChaosTransport(target_ports=<leader ports>)`` attacks ONLY
    the worker->leader hop of a 2-leader topology: every fault class
    must leave the run exactly-once — root logical commits == W*R and
    the final center equal to the exact dyadic sum — whether the
    workers retried in place (transient faults on a live leader) or
    degraded to direct-to-root (the partition window kills the
    probe too)."""
    center = _dyadic_center()
    W, G, R = 4, 2, 3
    g = W // G
    ps, root, leaders = _hier_topology(center, DownpourRule(), G, g)
    ports = {lead.address[1] for lead in leaders}
    with ChaosTransport(seed=11, target_ports=ports,
                        **SWEEP[fault]) as ct:
        _run_workers(
            center, None, W, R,
            client_of=lambda w: resilient_hier_client(
                leaders[w // g].address, root.address, worker_id=w,
                template=center, retries=10, seed=101 * w,
                use_seq=True))
    for lead in leaders:
        lead.drain()
        lead.stop()
    root.stop()
    assert ct.counts[fault] > 0, ct.counts  # the class really fired
    assert ps.num_commits == W * R
    exp = _expected_center(center, W, R)
    for k in center:
        assert np.asarray(ps.center[k]).tobytes() == exp[k].tobytes()


def test_leader_death_degrades_workers_to_direct_to_root(tmp_path):
    """Kill a leader mid-run: its workers fail over to the root
    within one retry (degraded, not down), the ``leader_down`` flight
    event and failover counter fire, and — because the dead leader
    was drained first — the final center is byte-identical to the
    full dyadic sum."""
    center = _dyadic_center()
    W, G, R = 4, 2, 4
    g = W // G
    flight_recorder.start(tmp_path / "fdr")
    tel = telemetry.enable()
    try:
        ps, root, leaders = _hier_topology(center, DownpourRule(),
                                           G, g)
        clients = [resilient_hier_client(
            leaders[w // g].address, root.address, worker_id=w,
            template=center, retries=10, seed=w, use_seq=True)
            for w in range(W)]
        for c in clients:
            c.pull()
        for w, c in enumerate(clients):
            for r in range(2):
                c.commit(_dyadic_delta(center, w, r))
        # flush the doomed leader's window, then crash it: nothing
        # acked is lost, so parity must hold end to end
        leaders[0].drain()
        leaders[0].kill()
        for w, c in enumerate(clients):
            for r in range(2, R):
                c.commit(_dyadic_delta(center, w, r))
        routes = [c.replicas for c in clients]
        for c in clients:
            c.close()
        for lead in leaders[1:]:
            lead.drain()
            lead.stop()
        root.stop()
    finally:
        snap = tel.metrics.snapshot()
        telemetry.disable()
        flight_recorder.stop()
    # group 0's workers failed over exactly once each; group 1's never
    assert all(r.failovers >= 1 for r in routes[:g])
    assert all(r.failovers == 0 for r in routes[g:])
    fails = sum(v for k, v in snap["counters"].items()
                if k.startswith("ps_leader_failovers_total"))
    assert fails >= g
    events = flight_recorder.FlightRecorder(
        tmp_path / "fdr").read_events()
    downs = [e for e in events if e["kind"] == "leader_down"]
    assert {e["leader_port"] for e in downs} == {
        leaders[0].address[1]}
    assert ps.num_commits == W * R
    exp = _expected_center(center, W, R)
    for k in center:
        assert np.asarray(ps.center[k]).tobytes() == exp[k].tobytes()


def test_trainer_ps_groups_arm_end_to_end():
    """The trainer's topology kwarg: a hierarchical DOWNPOUR run
    trains to a finite loss, records the fan-in history keys, and
    composes with wire compression on the worker->leader hop."""
    t = DOWNPOUR(MLP, fidelity="host", transport="socket",
                 ps_groups=[(None, [0, 1]), (None, [2, 3])],
                 num_workers=4, communication_window=2, batch_size=16,
                 num_epoch=1, learning_rate=0.01,
                 compression="int8", worker_timeout=5.0)
    t.train(DATA)
    h = t.history
    assert np.isfinite(h["epoch_loss"]).all()
    assert "worker_failures" not in h
    ups = h["ps_upstream_commits"][-1]
    assert ups > 0
    assert h["ps_fanin_reduction"][-1] == pytest.approx(2.0)
    assert h["ps_leader_failovers"][-1] == 0
    # every logical commit reached the root exactly once
    ps = t.parameter_server_state
    assert ps.num_commits == len(h["round_loss"])
    assert ps.num_commits == 2 * ups
    # the compressed wire really ran
    assert h["commit_wire_bytes"][-1] > 0
    assert h["commit_wire_bytes"][-1] < h["commit_raw_bytes"][-1]


def test_trainer_validation_rejects_bad_groupings():
    kw = dict(fidelity="host", num_workers=4,
              communication_window=2, batch_size=16, num_epoch=1,
              learning_rate=0.01)
    with pytest.raises(ValueError, match="socket"):
        DOWNPOUR(MLP, transport="inprocess",
                 ps_groups=[(None, [0, 1])], **kw)
    with pytest.raises(ValueError, match="two ps_groups"):
        DOWNPOUR(MLP, transport="socket",
                 ps_groups=[(None, [0, 1]), (None, [1, 2])], **kw)
    with pytest.raises(ValueError, match="out of range"):
        DOWNPOUR(MLP, transport="socket", ps_groups=[(None, [4])],
                 **kw)
    with pytest.raises(ValueError, match="mutually exclusive"):
        DOWNPOUR(MLP, transport="socket", ps_groups=[(None, [0])],
                 ps_replicas=[("127.0.0.1", 1)], **kw)
