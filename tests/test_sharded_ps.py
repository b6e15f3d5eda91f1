"""Sharded parameter server (``parallel.sharded_ps``): byte-balanced
plan determinism, K-vs-unsharded center parity for the delta family
under fixed seeded schedules (including through a kill/warm-restart
cycle), the shard-addressed zero-copy wire (version-delta pulls,
per-shard commit dedupe), the satellite regressions (read-only pulls,
bounded staleness log, packed-bytes reply cache + gauge), and the
trainer integration (``ps_shards=``, host-arm ``commit_overlap``)."""

import threading

import jax
import numpy as np
import pytest

from distkeras_tpu import telemetry
from distkeras_tpu.analysis import racecheck
from distkeras_tpu.data import datasets
from distkeras_tpu.models import model_config
from distkeras_tpu.parallel.host_ps import (
    HostParameterServer,
    PSClient,
    PSServer,
    ResilientPSClient,
    pack_params,
)
from distkeras_tpu.parallel.sharded_ps import (
    NEVER_PULLED,
    ShardedParameterServer,
    ShardedPSClient,
    leaf_nbytes,
    plan_shards,
)
from distkeras_tpu.parallel.update_rules import (
    AdagRule,
    DownpourRule,
    DynSGDRule,
    ElasticRule,
)
from distkeras_tpu.trainers import AEASGD, DOWNPOUR, DynSGD

MLP = model_config("mlp", (8,), num_classes=4, hidden=(16,))
DATA = datasets.synthetic_classification(1536, (8,), 4, seed=0)

DELTA_RULES = [DownpourRule(), AdagRule(), DynSGDRule()]


@pytest.fixture(autouse=True)
def _racecheck():
    """Shard/seen locks are racecheck factories: run the whole suite
    instrumented and fail on any race/order/deadlock report."""
    racecheck.enable()
    yield
    reports = racecheck.disable()
    assert not reports, "\n".join(str(r) for r in reports)


def _params(seed=0, shapes=((3, 4), (4,), (8, 2), (5,), (2, 2, 2))):
    rng = np.random.default_rng(seed)
    return {f"w{i}": rng.normal(size=s).astype(np.float32)
            for i, s in enumerate(shapes)}


def _schedule(n_workers=3, n_commits=12, seed=7):
    """A fixed seeded commit schedule: (worker, delta, seq) tuples."""
    rng = np.random.default_rng(seed)
    seqs = {w: 0 for w in range(n_workers)}
    out = []
    for i in range(n_commits):
        w = int(rng.integers(n_workers))
        d = {k: rng.normal(size=v.shape).astype(np.float32) * 1e-2
             for k, v in _params(0).items()}
        out.append((w, d, seqs[w]))
        seqs[w] += 1
    return out


def test_plan_shards_byte_balanced_and_deterministic():
    p = _params(0)
    plan = plan_shards(p, 3)
    assert plan == plan_shards(p, 3)  # pure function of the template
    leaves = jax.tree_util.tree_leaves(p)
    # every leaf exactly once, canonical order within each shard
    flat = sorted(i for idx in plan for i in idx)
    assert flat == list(range(len(leaves)))
    assert all(idx == sorted(idx) for idx in plan)
    # byte balance: no shard above twice the mean (greedy largest-first
    # bound at these shapes)
    sizes = [sum(leaves[i].nbytes for i in idx) for idx in plan]
    assert max(sizes) <= 2 * (sum(sizes) / len(sizes))
    # K above the leaf count clamps (every shard owns >= 1 leaf)
    assert len(plan_shards(p, 99)) == len(leaves)


@pytest.mark.parametrize("rule", DELTA_RULES,
                         ids=lambda r: type(r).__name__)
@pytest.mark.parametrize("k", [2, 4])
def test_sharded_center_byte_identical_to_unsharded(rule, k):
    """ISSUE 4 acceptance: under a fixed seeded commit schedule the
    K-sharded final center is byte-identical to K=1 and to the
    unsharded ``HostParameterServer`` for every delta rule (per-leaf
    additive laws shard exactly; DynSGD's per-shard staleness equals
    the global staleness under any serial full-tree schedule)."""
    center = _params(0)
    servers = [HostParameterServer(rule, center),
               ShardedParameterServer(rule, center, 1),
               ShardedParameterServer(rule, center, k)]
    for ps in servers:
        for w in range(3):
            ps.pull(w)
        for w, d, seq in _schedule():
            ps.commit(w, d, seq=seq)
    packed = [pack_params(ps.center) for ps in servers]
    assert packed[0] == packed[1] == packed[2]
    assert (servers[0].staleness_log == servers[1].staleness_log
            == servers[2].staleness_log)
    assert servers[0].num_commits == servers[2].num_commits


@pytest.mark.parametrize("rule", DELTA_RULES,
                         ids=lambda r: type(r).__name__)
def test_sharded_parity_through_kill_restart(rule, tmp_path):
    """The same schedule split by a kill/``restart_from`` cycle lands
    on the same bytes: snapshot at the cut, restart, finish."""
    center = _params(0)
    sched = _schedule()
    ref = HostParameterServer(rule, center)
    sha = ShardedParameterServer(rule, center, 4)
    for ps in (ref, sha):
        for w in range(3):
            ps.pull(w)
        for w, d, seq in sched[:6]:
            ps.commit(w, d, seq=seq)
    path = sha.save_snapshot(tmp_path / "ps.snap")
    sha2 = ShardedParameterServer.from_snapshot(rule, path)
    for w, d, seq in sched[6:]:
        ref.commit(w, d, seq=seq)
        sha2.commit(w, d, seq=seq)
    assert pack_params(ref.center) == pack_params(sha2.center)
    # the dedupe caches survived: replaying the cut's last commit is a
    # no-op on both
    w, d, seq = sched[5]
    n = sha2.num_commits
    sha2.commit(w, d, seq=seq)
    assert sha2.num_commits == n


def test_elastic_family_shards_byte_identically():
    """The old K=1 gate is lifted (ISSUE 14): the elastic family's
    per-leaf lerp shards exactly like the delta family — a serial
    schedule against K=4 lands on the same bytes as the unsharded
    server, local tree and all."""
    rule = ElasticRule(alpha=0.3)
    center = _params(0)
    ref = HostParameterServer(rule, center)
    sha = ShardedParameterServer(rule, center, 4)
    rng = np.random.default_rng(7)
    locals_ = {ps: {w: ps.pull(w) for w in range(3)}
               for ps in (ref, sha)}
    for i in range(8):
        w = int(rng.integers(3))
        step = jax.tree_util.tree_map(
            lambda x: np.asarray(
                x + rng.normal(size=x.shape).astype(x.dtype) * 0.1),
            locals_[ref][w])
        for ps in (ref, sha):
            locals_[ps][w] = ps.commit(w, step, step, seq=i)
    assert pack_params(ref.center) == pack_params(sha.center)
    for w in range(3):
        assert (pack_params(locals_[ref][w])
                == pack_params(locals_[sha][w]))


def test_elastic_family_trains_sharded():
    """End-to-end: AEASGD at ps_shards=2 (the configuration the old
    gate rejected) trains to a finite loss on the host arm."""
    t = AEASGD(MLP, fidelity="host", ps_shards=2, num_workers=2,
               communication_window=2, batch_size=16, num_epoch=1)
    t.train(DATA)
    assert np.isfinite(t.history["round_loss"][-1])


def test_pull_returns_readonly_views_no_alias():
    """Satellite regression: the in-process arm must not be able to
    mutate server state through a pulled tree (``pull`` used to hand
    out the live ``_center``)."""
    for ps in (HostParameterServer(AdagRule(), _params(0)),
               ShardedParameterServer(AdagRule(), _params(0), 2)):
        pulled = ps.pull(0)
        before = {k: np.array(v) for k, v in ps.center.items()}
        with pytest.raises(ValueError):
            pulled["w0"][...] = 99.0
        d = jax.tree_util.tree_map(np.ones_like, _params(0))
        replied = ps.commit(0, d)
        with pytest.raises(ValueError):
            replied["w0"][...] = 99.0
        for k, v in before.items():
            np.testing.assert_array_equal(np.asarray(ps.center[k]),
                                          v + 1.0)


def test_staleness_log_bounded():
    """Satellite: the log keeps a documented window instead of one int
    per commit forever; the telemetry histogram stays the unbounded-
    horizon record."""
    ps = HostParameterServer(AdagRule(), _params(0))
    ps.STALENESS_LOG_WINDOW = 8
    d = jax.tree_util.tree_map(np.zeros_like, _params(0))
    ps.pull(0)
    for i in range(40):
        ps.commit(0, d)
    assert len(ps.staleness_log) <= 8 * 5 // 4
    assert ps.num_commits == 40  # the full count is not windowed
    sps = ShardedParameterServer(AdagRule(), _params(0), 2)
    sps.STALENESS_LOG_WINDOW = 8
    sps.pull(0)
    for i in range(40):
        sps.commit(0, d)
    assert len(sps.staleness_log) <= 8 * 5 // 4


def test_reply_cache_stores_packed_bytes_with_gauge():
    """Satellite: the dedupe cache holds packed bytes (explicit,
    measurable footprint) and reports it as a gauge; dedupe hits
    still reconstruct the exact reply."""
    tel = telemetry.enable()
    try:
        ps = HostParameterServer(AdagRule(), _params(0))
        ps.pull(0)
        d = jax.tree_util.tree_map(np.ones_like, _params(0))
        reply = ps.commit(0, d, seq=0)
        seq0, packed = ps._last_reply[0]
        assert isinstance(packed, bytes) and seq0 == 0
        nbytes = leaf_nbytes(jax.tree_util.tree_leaves(reply))
        assert len(packed) == nbytes
        assert tel.metrics.gauge("ps_reply_cache_bytes").value \
            == nbytes
        again = ps.commit(0, d, seq=0)  # dedupe hit
        for k in reply:
            np.testing.assert_array_equal(np.asarray(reply[k]),
                                          np.asarray(again[k]))
        ps.retire(0)
        assert tel.metrics.gauge("ps_reply_cache_bytes").value == 0
    finally:
        telemetry.disable()


def test_version_delta_pull_skips_unchanged_shards():
    """The server ships only shards whose clock advanced past the
    client's last-seen clocks; skipped shards are served from the
    client cache and the assembled tree still equals the center."""
    center = _params(0)
    ps = ShardedParameterServer(DownpourRule(), center, 3)
    server = PSServer(ps, center).start()
    host, port = server.address
    try:
        stats = {}
        c = ShardedPSClient(host, port, 0, center, num_shards=3,
                            stats=stats)
        c.pull()  # full (all clocks NEVER_PULLED)
        assert stats["pull_shards_skipped"] == 0
        t = c.pull()  # nothing advanced: every shard skipped
        assert stats["pull_shards_skipped"] == 3
        assert stats["pull_bytes_saved"] == leaf_nbytes(
            jax.tree_util.tree_leaves(center))
        for k in center:
            np.testing.assert_array_equal(t[k],
                                          np.asarray(ps.center[k]))
        # another client's commit advances every shard: full ship again
        d = jax.tree_util.tree_map(np.ones_like, center)
        c2 = ShardedPSClient(host, port, 1, center, num_shards=3)
        c2.commit(d, seq=0)
        t2 = c.pull()
        assert stats["pull_shards_skipped"] == 3  # unchanged
        for k in center:
            np.testing.assert_array_equal(t2[k],
                                          np.asarray(ps.center[k]))
        c.close()
        c2.close()
    finally:
        server.stop()


@pytest.mark.parametrize("k,polls", [(2, 3), (4, 4)])
def test_stale_polling_reader_pays_one_pull_per_commit(k, polls):
    """A reader that polls ``polls`` times per commit is shipped the
    tree once per commit and a header the other times: the bytes saved
    are exactly ``(polls - 1) / polls`` of the naive wire, shard by
    shard, and the assembled tree is still the center."""
    center = _params(0)
    full = leaf_nbytes(jax.tree_util.tree_leaves(center))
    ps = ShardedParameterServer(DownpourRule(), center, k)
    server = PSServer(ps, center).start()
    host, port = server.address
    rounds = 2
    try:
        writer = ShardedPSClient(host, port, 0, center, num_shards=k)
        stats = {}
        reader = ShardedPSClient(host, port, 1, center, num_shards=k,
                                 stats=stats)
        writer.pull()
        reader.pull()  # the first pull is always full
        delta = jax.tree_util.tree_map(
            lambda x: 1e-3 * np.ones_like(x), center)
        for s in range(rounds):
            writer.commit(delta, seq=s)
            for _ in range(polls):
                tree = reader.pull()
        writer.close()
        reader.close()
    finally:
        server.stop()
    assert stats["pull_bytes_saved"] == rounds * (polls - 1) * full
    assert stats["pull_shards_skipped"] == rounds * (polls - 1) * k
    assert stats["pull_bytes_saved"] / (rounds * polls * full) > 0.5
    for name in center:
        np.testing.assert_array_equal(tree[name],
                                      np.asarray(ps.center[name]))


def test_psserver_restarts_sharded_from_a_sharded_snapshot():
    """``PSServer.restart_from`` on a K-sharded snapshot comes back
    K-sharded with the same bytes."""
    center = _params(0)
    sha = ShardedParameterServer(DownpourRule(), center, 2)
    for w, d, seq in _schedule():
        sha.commit(w, d, seq=seq)
    srv = PSServer.restart_from(sha.snapshot(), DownpourRule(), center)
    try:
        assert srv.ps.num_shards == 2
        assert srv.ps.num_commits == sha.num_commits
        assert pack_params(srv.ps.center) == pack_params(sha.center)
    finally:
        srv.stop()


def test_sharded_wire_commit_dedupes_per_shard():
    """A retried logical commit (same seq) is deduped shard by shard —
    the reply is byte-identical and nothing applies twice."""
    center = _params(0)
    ps = ShardedParameterServer(AdagRule(), center, 4)
    server = PSServer(ps, center).start()
    host, port = server.address
    try:
        c = ShardedPSClient(host, port, 0, center, num_shards=4)
        c.pull()
        d = jax.tree_util.tree_map(np.ones_like, center)
        r1 = c.commit(d, seq=0)
        assert ps.num_commits == 1
        r2 = c.commit(d, seq=0)  # the lost-ack retry shape
        assert ps.num_commits == 1
        for k in center:
            np.testing.assert_array_equal(r1[k], r2[k])
        c.commit(d, seq=1)
        assert ps.num_commits == 2
        c.close()
    finally:
        server.stop()


def test_resilient_client_reconnects_sharded_wire():
    """``ResilientPSClient.for_address(shards=K)`` rebuilds a
    ``ShardedPSClient`` after a connection failure; the stats dict
    accumulates across the rebuild and at-most-once holds."""
    center = _params(0)
    ps = ShardedParameterServer(AdagRule(), center, 2)
    server = PSServer(ps, center).start()
    host, port = server.address
    try:
        stats = {}
        c = ResilientPSClient.for_address(
            host, port, worker_id=0, template=center, shards=2,
            shard_stats=stats, retries=2, backoff_base=1e-4)
        c.pull()
        d = jax.tree_util.tree_map(np.ones_like, center)
        c.commit(d)
        # sever the live connection; the next op must reconnect
        c._raw._sock.close()
        c.commit(d)
        assert ps.num_commits == 2
        assert c.retry_count >= 1
        c.close()
    finally:
        server.stop()


def test_concurrent_sharded_commits_land_exactly():
    """Racing workers against per-shard locks: every commit lands on
    every shard exactly once and the center stays finite."""
    center = _params(0)
    ps = ShardedParameterServer(AdagRule(), center, 4)
    n_threads, n_commits = 4, 8

    def run(w):
        ps.pull(w)
        rng = np.random.default_rng(w)
        for i in range(n_commits):
            d = {k: rng.normal(size=v.shape).astype(np.float32) * 1e-3
                 for k, v in center.items()}
            ps.commit(w, d, seq=i)

    threads = [threading.Thread(target=run, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ps.num_commits == n_threads * n_commits
    for s in ps._shards:
        assert s.num_commits == n_threads * n_commits
    assert all(np.isfinite(np.asarray(v)).all()
               for v in ps.center.values())


def test_mismatched_shard_plan_rejected():
    center = _params(0)
    ps = ShardedParameterServer(DownpourRule(), center, 2)
    with pytest.raises(ValueError, match="clocks|shards"):
        ps.pull_since(0, [NEVER_PULLED] * 3)


@pytest.mark.parametrize("transport", ["inprocess", "socket"])
def test_trainer_sharded_host_arm_trains(transport):
    """DOWNPOUR over the sharded PS (both transports) converges and
    emits the sharded history keys on the socket arm."""
    t = DOWNPOUR(MLP, fidelity="host", transport=transport,
                 ps_shards=2, num_workers=3, communication_window=2,
                 batch_size=16, num_epoch=2, learning_rate=0.01,
                 seed=0)
    t.train(DATA)
    losses = t.history["epoch_loss"]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] + 0.1
    if transport == "socket":
        assert "pull_shards_skipped" in t.history
        assert "pull_bytes_saved" in t.history


def test_trainer_sharded_snapshot_restartable(tmp_path):
    """``ps_snapshot_every`` through a sharded run writes snapshots a
    sharded server restarts from."""
    path = tmp_path / "ps.snap"
    t = DOWNPOUR(MLP, fidelity="host", transport="socket", ps_shards=2,
                 num_workers=2, communication_window=2, batch_size=16,
                 num_epoch=1, learning_rate=0.01,
                 ps_snapshot_path=str(path), ps_snapshot_every=4)
    t.train(DATA)
    assert t.history["ps_snapshots"][-1] > 0
    restored = ShardedParameterServer.from_snapshot(DownpourRule(),
                                                    path)
    assert restored.num_shards == 2 and restored.num_commits > 0
    from distkeras_tpu.checkpoint import ps_snapshot_info

    info = ps_snapshot_info(path)
    assert info["sharded"] == 2
    assert info["num_commits"] == restored.num_commits


def test_commit_overlap_host_arm_trains_and_overlaps():
    """Host-arm ``commit_overlap`` double-buffers the worker loop (the
    exchange for window n runs under window n+1's compute): same data
    budget must converge on par with the in-order loop, and every
    commit must land (clock == recorded rounds)."""
    common = dict(fidelity="host", num_workers=2,
                  communication_window=2, batch_size=16, num_epoch=2,
                  learning_rate=0.01, seed=0)
    base = DOWNPOUR(MLP, **common)
    base.train(DATA)
    over = DOWNPOUR(MLP, commit_overlap=True, **common)
    over.train(DATA)
    assert over.parameter_server_state.num_commits == \
        len(over.history["round_loss"])
    assert over.history["epoch_loss"][-1] <= \
        base.history["epoch_loss"][-1] + 0.15
    # staleness-aware rule through the overlap path too
    dyn = DynSGD(MLP, commit_overlap=True, **common)
    dyn.train(DATA)
    assert np.isfinite(dyn.history["epoch_loss"]).all()


def test_commit_overlap_with_sharded_socket_and_retries():
    """The full composition: sharded wire + double-buffered loop +
    compute-level chaos retry — at-most-once must hold (commits ==
    recorded rounds) and training completes."""
    state = {"armed": True}

    def injector(w, epoch, r):
        if w == 0 and r == 1 and state.pop("armed", False):
            raise RuntimeError("chaos")

    t = DOWNPOUR(MLP, fidelity="host", transport="socket", ps_shards=2,
                 commit_overlap=True, num_workers=2,
                 communication_window=2, batch_size=16, num_epoch=1,
                 learning_rate=0.01, worker_retries=1,
                 fault_injector=injector)
    t.train(DATA)
    assert t.parameter_server_state.num_commits == \
        len(t.history["round_loss"])
    assert t.history["worker_round_retries"]


def test_sharded_elastic_k1_still_exact():
    """The pinned K=1 elastic server matches the unsharded one (same
    lerp law, one lock)."""
    center = _params(0)
    rule = ElasticRule(alpha=0.3)
    ref = HostParameterServer(rule, center)
    sha = ShardedParameterServer(rule, center, 1)
    local = jax.tree_util.tree_map(lambda x: x + 1.0, center)
    for ps in (ref, sha):
        ps.pull(0)
        ps.commit(0, local, local)
    assert pack_params(ref.center) == pack_params(sha.center)
