"""Host-PS ceiling quantification at ResNet-18 scale (PERF.md §12).

The reference's known scalability ceiling is the parameter server
(SURVEY.md §2.4: GIL threads, full-weight pickle per window).  The
rebuild's socket PS re-creates that architecture deliberately; this
script measures where it saturates:

Part 1 — raw PS throughput: N hammering threads, each loop = pull +
commit of a ResNet-18-sized delta (~11.2M params, ~45 MB msgpack raw)
against the real ``PSServer`` over loopback TCP.  Reports commits/sec
and payload GB/s vs thread count, raw vs int8 wire.

Part 2 — end-to-end stall fraction: DOWNPOUR(fidelity='host',
transport='socket') training ResNet-18 @32px, ``PSClient.pull/commit``
wall-time instrumented, for window in {1, 4, 16} x {raw, int8}.
Reports rows/sec and the fraction of worker wall-time spent inside the
PS exchange (the "worker-stall fraction").

Part 4 — sharded-PS A/B (PERF.md §25): ``ShardedParameterServer`` over
the shard-addressed zero-copy wire vs the single-mutex ``PSServer``
baseline, K ∈ {1, 2, 4, 8} x workers ∈ {2, 4, 8} hammering full-tree
commits at ResNet-18 scale, plus a stale-polling reader measuring the
version-delta pull's wire-byte savings.  ``--smoke`` runs a seconds-
scale arm at MLP scale with parity/savings assertions (tier-1 via
test_examples.py SMOKE_SCRIPTS).

Run on CPU (the host arm's per-thread device programs are plain convs —
no vmapped-conv slow path): the wire path is host work, and the
cross-host part starts child processes, which are pinned to the CPU
(``deploy.launch_local``):
    JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python scripts/perf_host_ps.py
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import jax
import numpy as np


def resnet18_center():
    import jax.numpy as jnp

    from distkeras_tpu.models.resnet import ResNet18

    model = ResNet18(num_classes=10, dtype="float32")
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 32, 32, 3)))
    params = jax.tree_util.tree_map(np.asarray, variables["params"])
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    return params, n


def part1_raw_throughput(center, n_params, commits=8, workers_list=(1, 2, 4, 8)):
    from distkeras_tpu.parallel.compression import resolve_codec
    from distkeras_tpu.parallel.host_ps import (HostParameterServer,
                                                PSClient, PSServer)
    from distkeras_tpu.parallel.update_rules import DownpourRule
    from distkeras_tpu.utils import tree_zeros_like

    delta = jax.tree_util.tree_map(
        lambda x: (0.001 * np.ones_like(x)), center)
    for codec_name in (None, "int8"):
        codec = resolve_codec(codec_name)
        payload = codec.encode(delta) if codec else delta
        for workers in workers_list:
            ps = HostParameterServer(DownpourRule(), center)
            server = PSServer(ps, center).start()
            host, port = server.address
            barrier = threading.Barrier(workers + 1)
            done = []

            def worker(w):
                client = PSClient(host, port, w, center,
                                  codec=codec_name)
                client.pull()
                barrier.wait()  # start together
                for s in range(commits):
                    client.commit(payload, seq=s)
                done.append(w)
                client.close()

            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(workers)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            total = commits * workers
            raw_bytes = sum(x.nbytes for x in
                            jax.tree_util.tree_leaves(delta))
            wire = (len(payload) if codec
                    else raw_bytes)  # msgpack adds only framing
            print(json.dumps({
                "bench": "ps_raw", "wire": codec_name or "raw",
                "workers": workers,
                "commits_per_sec": round(total / dt, 2),
                "payload_mb": round(wire / 1e6, 1),
                "wire_gb_per_sec": round(total * wire / dt / 1e9, 3),
            }), flush=True)
            server.stop()
            assert len(done) == workers


class _PSCallClock:
    """Context manager instrumenting ``PSClient.pull/commit`` wall time
    (worker threads race on the accumulators; lock-protected)."""

    def __init__(self):
        self.t = 0.0
        self.n = 0
        self._lock = threading.Lock()

    def __enter__(self):
        from distkeras_tpu.parallel import host_ps

        self._mod = host_ps
        self._orig = (host_ps.PSClient.pull, host_ps.PSClient.commit)

        def timed(fn):
            def inner(s, *a, **k):
                t0 = time.perf_counter()
                out = fn(s, *a, **k)
                dt = time.perf_counter() - t0
                with self._lock:
                    self.t += dt
                    self.n += 1
                return out
            return inner

        host_ps.PSClient.pull = timed(self._orig[0])
        host_ps.PSClient.commit = timed(self._orig[1])
        return self

    def __exit__(self, *exc):
        self._mod.PSClient.pull = self._orig[0]
        self._mod.PSClient.commit = self._orig[1]
        return False


def part2_e2e_stall(rows=256, workers=4):
    from distkeras_tpu.data import datasets
    from distkeras_tpu.models import model_config
    from distkeras_tpu.trainers import DOWNPOUR

    cfg = model_config("resnet", (32, 32, 3), num_classes=10,
                       stage_sizes=(2, 2, 2, 2), bottleneck=False,
                       dtype="float32")

    for codec in (None, "int8"):
        for window in (1, 4, 16):
            # at least 2 rounds per worker at every window
            rows_w = max(rows, 2 * workers * 8 * window)
            data = datasets.synthetic_classification(
                rows_w, (32, 32, 3), 10, seed=0)
            with _PSCallClock() as acc:
                t = DOWNPOUR(cfg, num_workers=workers,
                             communication_window=window,
                             batch_size=8, num_epoch=1,
                             learning_rate=0.01, seed=0,
                             fidelity="host", transport="socket",
                             compression=codec)
                t0 = time.perf_counter()
                t.train(data)
                wall = time.perf_counter() - t0
            wire = sum(t.history.get("commit_wire_bytes", []))
            out = {
                "bench": "e2e", "wire": codec or "raw",
                "window": window,
                "rows": rows_w,
                "rows_per_sec": round(rows_w / wall, 1),
                "ps_calls": acc.n,
                "stall_fraction": round(acc.t / (workers * wall), 3),
                "epoch_loss": round(t.history["epoch_loss"][-1], 3),
            }
            if wire:  # only the compressed arm tracks wire bytes
                out["commit_wire_mb"] = round(wire / 1e6, 1)
            print(json.dumps(out), flush=True)


def part3_cross_host(window=16, workers=4, rows=None):
    """Part 3 — the §12 recipe validated across REAL processes: a
    2-process jax.distributed cluster (PS on process 0, the DCN arm over
    real TCP), DOWNPOUR host/socket at ResNet-18@32px, window 16,
    raw vs int8 wire.  Reports global commits/s and per-process stall
    fraction."""
    from distkeras_tpu.deploy import run_multiprocess

    for codec in ("raw", "int8"):
        results = run_multiprocess(
            __file__, 2,
            args=["--part", "child", "--codec", codec,
                  "--window", str(window), "--workers", str(workers),
                  *(("--rows", str(rows)) if rows else ())],
            env={"JAX_PLATFORMS": "cpu",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
            timeout_s=1800.0)
        per_proc = [json.loads(r.stdout.strip().splitlines()[-1])
                    for r in results]
        wall = max(p["wall_s"] for p in per_proc)
        commits = per_proc[0]["commits"]  # telemetry is broadcast
        out = {
            "bench": "cross_host", "wire": codec, "window": window,
            "workers": workers, "processes": 2,
            "rows": per_proc[0]["rows"],
            "commits": commits,
            "commits_per_sec": round(commits / wall, 2),
            "rows_per_sec": round(per_proc[0]["rows"] / wall, 1),
            "stall_fraction_per_proc": [p["stall_fraction"]
                                        for p in per_proc],
            "epoch_loss": per_proc[0]["epoch_loss"],
        }
        wire_mb = per_proc[0].get("commit_wire_mb")
        if wire_mb:
            out["commit_wire_mb"] = wire_mb
        print(json.dumps(out), flush=True)


def _hammer_commits(center, num_shards, workers, commits,
                    use_seq=True):
    """One A/B cell: ``workers`` threads, each loop = one full-tree
    delta commit against a freshly-built server; K=1 is the single-
    mutex ``HostParameterServer`` + ``pack_params`` wire (the
    baseline), K>1 the ``ShardedParameterServer`` over the
    shard-addressed scatter-gather wire.  Returns commits/sec."""
    from distkeras_tpu.parallel.host_ps import (HostParameterServer,
                                                PSClient, PSServer)
    from distkeras_tpu.parallel.sharded_ps import (
        ShardedParameterServer, ShardedPSClient)
    from distkeras_tpu.parallel.update_rules import DownpourRule

    delta = jax.tree_util.tree_map(
        lambda x: (0.001 * np.ones_like(x)), center)
    if num_shards > 1:
        ps = ShardedParameterServer(DownpourRule(), center, num_shards)
    else:
        ps = HostParameterServer(DownpourRule(), center)
    server = PSServer(ps, center).start()
    host, port = server.address
    barrier = threading.Barrier(workers + 1)
    errs = []

    def worker(w):
        try:
            if num_shards > 1:
                client = ShardedPSClient(host, port, w, center,
                                         num_shards=num_shards)
            else:
                client = PSClient(host, port, w, center)
            client.pull()
            barrier.wait()
            for s in range(commits):
                client.commit(delta, seq=s if use_seq else None)
            client.close()
        except Exception as e:  # surfaced after join
            errs.append(e)
            try:
                barrier.abort()
            except Exception:
                pass

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(workers)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    server.stop()
    if errs:
        raise errs[0]
    return commits * workers / dt


def part4_sharded_ab(center, commits=6, shards_list=(1, 2, 4, 8),
                     workers_list=(2, 4, 8)):
    """The §25 grid: sharded commit throughput vs the single-mutex
    baseline, per (K, workers); the baseline row is K=1."""
    results = {}
    for workers in workers_list:
        for k in shards_list:
            cps = _hammer_commits(center, k, workers, commits)
            results[(k, workers)] = cps
            base = results.get((1, workers))
            print(json.dumps({
                "bench": "ps_sharded", "shards": k, "workers": workers,
                "commits_per_sec": round(cps, 2),
                "speedup_vs_mutex": (round(cps / base, 2)
                                     if base else 1.0),
            }), flush=True)
    return results


def part4_version_delta(center, num_shards=4, commit_rounds=6,
                        polls_per_round=4):
    """Stale-polling reader: a writer commits full-tree deltas while a
    reader pulls ``polls_per_round`` times per commit — the version-
    delta wire ships only shards whose clock advanced, so most polls
    cost a 2-byte header instead of the full parameter set."""
    from distkeras_tpu.parallel.host_ps import PSServer
    from distkeras_tpu.parallel.sharded_ps import (
        ShardedParameterServer, ShardedPSClient, leaf_nbytes)
    from distkeras_tpu.parallel.update_rules import DownpourRule

    delta = jax.tree_util.tree_map(
        lambda x: (0.001 * np.ones_like(x)), center)
    full_bytes = leaf_nbytes(jax.tree_util.tree_leaves(center))
    ps = ShardedParameterServer(DownpourRule(), center, num_shards)
    server = PSServer(ps, center).start()
    host, port = server.address
    writer = ShardedPSClient(host, port, 0, center,
                             num_shards=num_shards)
    stats = {}
    reader = ShardedPSClient(host, port, 1, center,
                             num_shards=num_shards, stats=stats)
    writer.pull()
    reader.pull()  # first pull is always full (empty cache)
    for s in range(commit_rounds):
        writer.commit(delta, seq=s)
        for _ in range(polls_per_round):
            reader.pull()
    polls = commit_rounds * polls_per_round
    naive = polls * full_bytes
    shipped = naive - stats["pull_bytes_saved"]
    out = {
        "bench": "ps_version_delta", "shards": num_shards,
        "polls": polls, "full_pull_mb": round(full_bytes / 1e6, 2),
        "naive_mb": round(naive / 1e6, 1),
        "shipped_mb": round(shipped / 1e6, 1),
        "bytes_saved_frac": round(stats["pull_bytes_saved"] / naive,
                                  3),
        "shards_skipped": stats["pull_shards_skipped"],
    }
    print(json.dumps(out), flush=True)
    writer.close()
    reader.close()
    server.stop()
    return out


def _smoke_center(leaves=12, rows=64):
    rng = np.random.default_rng(0)
    return {f"w{i}": rng.normal(size=(rows, 8 + i)).astype(np.float32)
            for i in range(leaves)}


def smoke():
    """Seconds-scale correctness + direction check of the sharded PS
    (tier-1; the measured §25 numbers come from the full parts)."""
    from distkeras_tpu.parallel.host_ps import (HostParameterServer,
                                                PSServer)
    from distkeras_tpu.parallel.sharded_ps import (
        ShardedParameterServer)
    from distkeras_tpu.parallel.update_rules import DownpourRule

    center = _smoke_center()
    # parity: identical serial schedule through both servers
    deltas = [jax.tree_util.tree_map(
        lambda x: ((i + 1) * 1e-3 * np.ones_like(x)), center)
        for i in range(4)]
    ref = HostParameterServer(DownpourRule(), center)
    sha = ShardedParameterServer(DownpourRule(), center, 2)
    for ps in (ref, sha):
        for w in range(2):
            ps.pull(w)
        for i, d in enumerate(deltas):
            ps.commit(i % 2, d, seq=i // 2)
    for k in center:
        np.testing.assert_array_equal(np.asarray(ref.center[k]),
                                      np.asarray(sha.center[k]))
    assert ref.staleness_log == sha.staleness_log
    print(json.dumps({"bench": "smoke_parity", "ok": True}),
          flush=True)
    # wire throughput runs (no assertion on the ratio at smoke scale)
    for k in (1, 2):
        cps = _hammer_commits(center, k, workers=2, commits=3)
        print(json.dumps({"bench": "smoke_sharded", "shards": k,
                          "commits_per_sec": round(cps, 1)}),
              flush=True)
    # version-delta pulls must actually save bytes
    out = part4_version_delta(center, num_shards=2, commit_rounds=2,
                              polls_per_round=3)
    assert out["bytes_saved_frac"] > 0.5, out
    # sharded kill/warm-restart keeps the center byte-identical
    sha2 = ShardedParameterServer.from_snapshot(DownpourRule(),
                                               sha.snapshot())
    for k in center:
        np.testing.assert_array_equal(np.asarray(sha.center[k]),
                                      np.asarray(sha2.center[k]))
    print(json.dumps({"bench": "smoke_restart", "ok": True}),
          flush=True)
    # a PSServer restarted from that snapshot serves it
    srv = PSServer.restart_from(sha.snapshot(), DownpourRule(), center)
    assert srv.ps.num_shards == 2
    srv.stop()
    print(json.dumps({"smoke": "ok"}), flush=True)


def part3_child(args):
    """One process of the cross-host arm (invoked by part3 via
    run_multiprocess)."""
    from distkeras_tpu import mesh as mesh_lib
    from distkeras_tpu.data import datasets
    from distkeras_tpu.models import model_config
    from distkeras_tpu.trainers import DOWNPOUR

    mesh_lib.initialize_cluster()
    workers = args.workers
    window = args.window
    rows = args.rows or max(512, 2 * workers * 8 * window)
    data = datasets.synthetic_classification(rows, (32, 32, 3), 10,
                                             seed=0)
    cfg = model_config("resnet", (32, 32, 3), num_classes=10,
                       stage_sizes=(2, 2, 2, 2), bottleneck=False,
                       dtype="float32")
    codec = None if args.codec == "raw" else args.codec
    local_workers = workers // jax.process_count()
    with _PSCallClock() as acc:
        t = DOWNPOUR(cfg, num_workers=workers,
                     communication_window=window, batch_size=8,
                     num_epoch=1, learning_rate=0.01, seed=0,
                     fidelity="host", transport="socket",
                     compression=codec)
        t0 = time.perf_counter()
        t.train(data)
        wall = time.perf_counter() - t0
    wire = sum(t.history.get("commit_wire_bytes", []))
    out = {
        "process": jax.process_index(),
        "rows": rows,
        "wall_s": round(wall, 3),
        "commits": len(t.history["staleness"][-1]),
        "stall_fraction": round(acc.t / (local_workers * wall), 3),
        "epoch_loss": round(t.history["epoch_loss"][-1], 3),
    }
    if wire:
        out["commit_wire_mb"] = round(wire / 1e6, 1)
    print(json.dumps(out), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--commits", type=int, default=8)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--part",
                    choices=["1", "2", "3", "4", "both", "child"],
                    default="both")
    ap.add_argument("--window", type=int, default=16)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--codec", default="raw")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale sharded-PS correctness arm "
                         "(tier-1)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    if args.part == "child":
        part3_child(args)
        return
    center, n = resnet18_center()
    print(json.dumps({"model": "resnet18", "params": n,
                      "raw_mb": round(4 * n / 1e6, 1)}), flush=True)
    if args.part in ("1", "both"):
        part1_raw_throughput(center, n, commits=args.commits)
    if args.part in ("2", "both"):
        part2_e2e_stall(rows=args.rows or 256)
    if args.part == "3":
        part3_cross_host(window=args.window, workers=args.workers,
                         rows=args.rows)
    if args.part == "4":
        part4_sharded_ab(center, commits=args.commits)
        part4_version_delta(center)


if __name__ == "__main__":
    main()
