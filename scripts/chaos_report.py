"""Chaos / recovery report — exercise the fault-tolerance layer end to
end and summarize the recovery evidence from the telemetry registry.

Four scenarios (all run by ``--smoke``, the tier-1 registration via
test_examples.py's scripts-coverage check; tune them with the flags):

1. **Chaos-scheduled SOCKET training round** — an async host-PS
   training run over the real TCP transport inside a seed-pinned
   ``ChaosTransport`` (connection resets + mid-frame truncations +
   delays).  The run must finish inside the workers' retry budget and
   stay exactly-once (applied commits == completed rounds).
2. **Engine overload + drain** — a ``DecodeEngine`` with a bounded
   admission queue under 2x queue-bound overload: excess submits shed
   (``serving_shed_total``), a poisoned request is isolated as an
   ``error`` result, and ``drain()`` returns every accepted request.
3. **Replicated-PS primary kill** (ISSUE 10) — a 2-node replica group
   loses its primary mid-training: the standby self-promotes (epoch
   2), the workers fail over, and commits lost must be ZERO; the
   kill -> promote latency and the run's commit rate are printed as
   what they are, CPU readings.
4. **Elastic reshard + receiver kill mid-move** (ISSUE 14) — an
   elastic PS group splits and live-migrates shards under a
   ``ps_elastic`` training run, then the RECEIVING server of a second
   migration is killed mid-stream: the cutover aborts cleanly, the
   old owner un-fences, and commits lost must be ZERO; the successful
   migration's latency and the commit rate are printed, CPU readings.

The report prints, per layer: injected fault counts, client retries and
backoff spent, commit/dedupe/snapshot counters, shed/error counts,
promotion latency and epoch — the "what fired, what recovered, what it
cost" summary an operator would want after a chaos day.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def chaos_training_round(seed: int, rows: int) -> dict:
    """Scenario 1: seed-pinned chaos over the socket PS arm."""
    import numpy as np

    from distkeras_tpu.data import datasets
    from distkeras_tpu.models import model_config
    from distkeras_tpu.parallel.faults import ChaosTransport
    from distkeras_tpu.trainers import DOWNPOUR

    mlp = model_config("mlp", (8,), num_classes=4, hidden=(16,))
    data = datasets.synthetic_classification(rows, (8,), 4, seed=0)
    with ChaosTransport(seed=seed, reset_rate=0.15, truncate_rate=0.1,
                        delay_rate=0.1, delay_s=0.01, skip_ops=4,
                        max_injections=5) as chaos:
        t = DOWNPOUR(mlp, fidelity="host", transport="socket",
                     num_workers=2, communication_window=2,
                     batch_size=16, num_epoch=1, learning_rate=0.01,
                     worker_optimizer="adam", worker_retries=10)
        t.train(data)
    rounds = len(t.history["round_loss"])
    commits = t.parameter_server_state.num_commits
    assert commits == rounds, (
        f"exactly-once violated under chaos: {commits} commits for "
        f"{rounds} rounds")
    assert "worker_failures" not in t.history, t.history[
        "worker_failures"]
    loss = t.history["epoch_loss"]
    assert np.isfinite(loss).all(), loss
    return {"injected": dict(chaos.counts), "rounds": rounds,
            "commits": commits,
            "retried_rounds": sum(map(len, t.history.get(
                "worker_round_retries", []))),
            "final_loss": float(loss[-1])}


def failover_round(rows: int, out_dir: str) -> dict:
    """Scenario 3 (ISSUE 10): kill the PRIMARY of a 2-node replicated
    PS group mid-training.  The standby must promote itself (epoch
    bump), every worker's ``ResilientPSClient`` must walk its replica
    list onto the new primary, and the run must finish with ZERO lost
    commits (the promoted node's commit count == completed rounds —
    the replicated dedupe table keeps retried commits exactly-once
    across the failover).  Promotion latency is measured from the
    fsynced ``ps_kill`` flight event to the successor's ``ps_promote``
    and reported beside the run's commit rate: CPU readings, gated by
    nothing."""
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu import flight_recorder
    from distkeras_tpu.data import datasets
    from distkeras_tpu.models import ModelSpec, model_config
    from distkeras_tpu.parallel.replicated_ps import make_replica_group
    from distkeras_tpu.parallel.update_rules import DownpourRule
    from distkeras_tpu.trainers import DOWNPOUR

    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    flight_dir = out / "flight"

    mlp = model_config("mlp", (8,), num_classes=4, hidden=(16,))
    data = datasets.synthetic_classification(rows, (8,), 4, seed=0)
    model = ModelSpec.from_config(mlp).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.float32))
    center = jax.tree_util.tree_map(np.asarray, variables["params"])

    flight_recorder.start(flight_dir)
    nodes = make_replica_group(DownpourRule(), center, replicas=2,
                               failover_timeout=0.5)
    try:
        def killer():
            while nodes[0].ps.num_commits < 3:
                time.sleep(0.002)
            nodes[0].kill()

        k = threading.Thread(target=killer)
        k.start()
        t0 = time.perf_counter()
        t = DOWNPOUR(mlp, fidelity="host", transport="socket",
                     num_workers=2, communication_window=2,
                     batch_size=16, num_epoch=1, learning_rate=0.01,
                     worker_optimizer="adam", worker_retries=14,
                     ps_replicas=[n.worker_address for n in nodes])
        t.train(data)
        seconds = time.perf_counter() - t0
        k.join()
        rounds = len(t.history["round_loss"])
        commits = nodes[1].ps.num_commits
        epoch = nodes[1].ps.epoch
    finally:
        for n in nodes:
            n.stop()
    events = flight_recorder.active().read_events()
    flight_recorder.stop()

    kills = [e for e in events if e["kind"] == "ps_kill"]
    promotes = [e for e in events if e["kind"] == "ps_promote"
                and e["reason"] == "failover"]
    assert kills and promotes, (
        f"failover story incomplete: {len(kills)} kills, "
        f"{len(promotes)} failover promotions")
    latency = promotes[0]["wall_s"] - kills[-1]["wall_s"]
    assert commits == rounds, (
        f"commits lost across failover: {commits} commits for "
        f"{rounds} rounds")
    assert t.history["ps_epoch"][-1] == epoch == 3, (
        t.history.get("ps_epoch"), epoch)
    assert t.history["ps_failovers"][-1] >= 1, t.history

    return {"rounds": rounds, "commits": commits, "epoch": epoch,
            "failovers": int(t.history["ps_failovers"][-1]),
            "worker_retries": sum(map(len, t.history.get(
                "worker_round_retries", []))),
            "promotion_latency_s": latency,
            "commits_per_s": commits / seconds}


def elastic_migration_round(rows: int, out_dir: str) -> dict:
    """Scenario 4 (ISSUE 14): live resharding under fire.  A 2-server
    elastic PS group serves a ``ps_elastic`` training run while an ops
    thread (a) splits a shard, (b) migrates a shard to a freshly added
    server (zero downtime — the cutover latency comes from the
    ``shard_migrate_cutover`` flight event), then (c) starts a second
    migration and KILLS the receiving server mid-stream: the cutover
    must abort cleanly (``MigrationAborted``), the old owner must
    un-fence, and the run must finish with ZERO lost commits.  The
    commit rate and the successful migration's latency are reported,
    CPU readings."""
    import threading
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu import flight_recorder
    from distkeras_tpu.data import datasets
    from distkeras_tpu.models import ModelSpec, model_config
    from distkeras_tpu.parallel.elastic_ps import (ElasticPSGroup,
                                                   MigrationAborted)
    from distkeras_tpu.parallel.update_rules import DownpourRule
    from distkeras_tpu.trainers import DOWNPOUR

    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)

    mlp = model_config("mlp", (8,), num_classes=4, hidden=(16,))
    data = datasets.synthetic_classification(rows, (8,), 4, seed=0)
    model = ModelSpec.from_config(mlp).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.float32))
    center = jax.tree_util.tree_map(np.asarray, variables["params"])

    flight_recorder.start(out / "flight")
    grp = ElasticPSGroup(DownpourRule(), center, num_shards=2,
                         num_servers=2)
    ops: dict = {"aborted": None, "error": None}
    try:
        def _wait_commits(n):
            while grp.num_commits < n:
                time.sleep(0.002)

        def driver():
            try:
                _wait_commits(2)
                plan = grp.nodes[0].map.plan
                wide = max(range(len(plan)),
                           key=lambda s: len(plan[s]))
                grp.split(wide)
                _wait_commits(4)
                dst = grp.add_server("127.0.0.1")
                grp.migrate(0, dst)
                _wait_commits(6)
                # the receiver-kill: a fresh empty server dies while
                # the courier is streaming shard 1 into it
                doomed = grp.add_server("127.0.0.1")
                grp.start_migration(1, doomed)
                grp.servers[doomed].kill()
                try:
                    grp.cutover(1, timeout=10.0)
                    ops["aborted"] = False
                except MigrationAborted:
                    ops["aborted"] = True
            except Exception as e:  # surface, don't hang the report
                ops["error"] = e

        th = threading.Thread(target=driver)
        th.start()
        t0 = time.perf_counter()
        t = DOWNPOUR(mlp, fidelity="host", transport="socket",
                     num_workers=2, communication_window=2,
                     batch_size=16, num_epoch=1, learning_rate=0.01,
                     worker_optimizer="adam", worker_retries=14,
                     ps_elastic=True, ps_address=grp.addresses[0])
        t.train(data)
        seconds = time.perf_counter() - t0
        th.join()
        rounds = len(t.history["round_loss"])
        commits = grp.num_commits
        shards = grp.num_shards
    finally:
        grp.stop()
    events = flight_recorder.active().read_events()
    flight_recorder.stop()

    if ops["error"] is not None:
        raise ops["error"]
    assert ops["aborted"], "receiver kill did not abort the cutover"
    assert commits == rounds, (
        f"commits lost across resharding: {commits} commits for "
        f"{rounds} rounds")
    assert np.isfinite(t.history["epoch_loss"]).all()
    cutovers = [e for e in events
                if e["kind"] == "shard_migrate_cutover"]
    aborts = [e for e in events if e["kind"] == "shard_migrate_abort"]
    splits = [e for e in events if e["kind"] == "shard_split"]
    assert splits and cutovers and aborts, (
        f"resharding story incomplete: {len(splits)} splits, "
        f"{len(cutovers)} cutovers, {len(aborts)} aborts")
    latency = float(cutovers[0]["latency_s"])

    return {"rounds": rounds, "commits": commits, "shards": shards,
            "migration_latency_s": latency,
            "commits_per_s": commits / seconds,
            "aborts": len(aborts)}


def engine_overload_and_drain(seed: int) -> dict:
    """Scenario 2: bounded-queue shedding + poisoned-request isolation
    + graceful drain on a tiny LM."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.models import ModelSpec, model_config
    from distkeras_tpu.serving import DecodeEngine, ShedError

    spec = model_config("transformer_lm", (32,), input_dtype="int32",
                        vocab_size=61, num_layers=1, d_model=32,
                        num_heads=2, max_len=32, dtype="float32")
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((2, 32), jnp.int32))
    slots, bound = 2, 2
    eng = DecodeEngine(model, variables, slots=slots, prefill_align=4,
                       max_new_tokens=5, queue_bound=bound)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 61, (t,)).astype(np.int32)
               for t in [5, 7, 4, 6, 5, 8, 4, 5]]  # 2x (slots + bound)
    accepted, shed = [], 0
    for i, p in enumerate(prompts):
        try:
            accepted.append(eng.submit(p, request_id=i))
        except ShedError:
            shed += 1
    assert shed > 0, "2x queue-bound overload failed to shed"

    # poison one accepted request's prefill: it must error out alone
    pool = eng._pools[0]
    real_prefill = pool.prefill_fn
    poison_len = len(prompts[accepted[-1]])

    def poisoned(variables, cache, state, prompt, slot, last_idx,
                 n_left0, eos_id, rng):
        if int(last_idx) == poison_len - 1:
            raise RuntimeError("chaos: poisoned request")
        return real_prefill(variables, cache, state, prompt, slot,
                            last_idx, n_left0, eos_id, rng)

    pool.prefill_fn = poisoned
    results = {r["request_id"]: r for r in eng.drain()}
    pool.prefill_fn = real_prefill
    assert sorted(results) == sorted(accepted), (
        "drain lost in-flight requests")
    errors = [r for r in results.values() if "error" in r]
    ok = [r for r in results.values() if "error" not in r]
    assert errors and ok, (len(errors), len(ok))
    leftovers = eng.close()
    assert leftovers == [] and not eng.has_work()
    return {"submitted": len(prompts), "accepted": len(accepted),
            "shed": shed, "errors": len(errors),
            "completed": len(ok)}


def registry_lines(tel) -> list[str]:
    """The recovery-relevant counters/histograms, straight from the
    telemetry registry."""
    lines = ["== telemetry recovery summary =="]
    snap = tel.metrics.snapshot()
    wanted = ("chaos_injected_total", "chaos_window_injected_total",
              "sim_kills_total", "slo_violation_seconds_total",
              "autoscale_deferred_total",
              "sim_drill_convergence_seconds_total",
              "ps_client_retries_total",
              "ps_commits_total", "ps_commit_dedup_total",
              "ps_snapshots_total", "ps_restarts_total",
              "ps_promotions_total", "ps_client_failovers_total",
              "ps_fenced_total", "ps_replicated_entries_total",
              "ps_shard_fence_refresh_total", "ps_map_refresh_total",
              "elastic_reshards_total",
              "elastic_migrations_aborted_total",
              "serving_shed_total", "serving_request_errors_total",
              "serving_finished_total")
    for key, value in sorted(snap["counters"].items()):
        if key.split("{")[0] in wanted:
            lines.append(f"  counter    {key:<52} {value:g}")
    for key, h in sorted(snap["histograms"].items()):
        if key.split("{")[0] == "ps_client_backoff_seconds":
            mean = h["sum"] / h["count"] if h["count"] else float("nan")
            lines.append(f"  histogram  {key:<38} n={h['count']} "
                         f"total_sleep={h['sum']:.3f}s "
                         f"mean={mean * 1e3:.1f}ms")
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU shapes (the tier-1 mode)")
    ap.add_argument("--seed", type=int, default=7,
                    help="chaos schedule seed (pins every injection)")
    ap.add_argument("--rows", type=int, default=1024,
                    help="training rows for the chaos round")
    ap.add_argument("--out", default=None,
                    help="also write the report to this file")
    ap.add_argument("--out-dir", default=None,
                    help="failover-round artifact directory "
                         "(temp default)")
    args = ap.parse_args()
    if args.smoke:
        args.rows = min(args.rows, 1024)

    import tempfile

    from distkeras_tpu import telemetry

    tel = telemetry.enable()
    fail = failover_round(args.rows, args.out_dir or tempfile.mkdtemp(
        prefix="dkt_chaos_fo_"))
    elastic = elastic_migration_round(
        args.rows, args.out_dir or tempfile.mkdtemp(
            prefix="dkt_chaos_el_"))
    train = chaos_training_round(args.seed, args.rows)
    serve = engine_overload_and_drain(args.seed)

    lines = ["distkeras_tpu chaos / recovery report",
             f"(chaos seed {args.seed} — the same seed replays the "
             "same injection schedule)",
             "== scenario 1: chaos-scheduled SOCKET training =="]
    lines += [f"  injected {k:<10} {n}"
              for k, n in sorted(train["injected"].items())]
    lines += [
        f"  rounds completed       {train['rounds']}",
        f"  commits applied        {train['commits']} "
        "(== rounds: exactly-once held)",
        f"  rounds retried         {train['retried_rounds']}",
        f"  final epoch loss       {train['final_loss']:.4f}",
        "== scenario 2: engine overload + poisoned request + drain ==",
        f"  submitted              {serve['submitted']}",
        f"  accepted               {serve['accepted']}",
        f"  shed at the door       {serve['shed']}",
        f"  isolated as error      {serve['errors']}",
        f"  completed clean        {serve['completed']} "
        "(drain returned every accepted request)",
        "== scenario 3: replicated-PS primary kill + failover ==",
        f"  rounds completed       {fail['rounds']}",
        f"  commits on successor   {fail['commits']} "
        "(== rounds: commits lost = 0)",
        f"  fencing epoch          {fail['epoch']}",
        f"  client failovers       {fail['failovers']}",
        f"  rounds retried         {fail['worker_retries']}",
        f"  promotion latency      "
        f"{fail['promotion_latency_s'] * 1e3:.1f}ms "
        "(kill -> ps_promote; a CPU reading)",
        f"  commit rate            {fail['commits_per_s']:.1f}/s "
        "(a CPU reading)",
        "== scenario 4: elastic reshard + receiver kill mid-move ==",
        f"  rounds completed       {elastic['rounds']}",
        f"  commits on group       {elastic['commits']} "
        "(== rounds: commits lost = 0 across split/migrate/abort)",
        f"  final shard count      {elastic['shards']}",
        f"  migration latency      "
        f"{elastic['migration_latency_s'] * 1e3:.1f}ms "
        "(fence -> cutover; a CPU reading)",
        f"  commit rate            {elastic['commits_per_s']:.1f}/s "
        "(a CPU reading)",
        f"  aborted moves          {elastic['aborts']} "
        "(receiver killed mid-stream; old owner un-fenced)",
    ]
    lines += registry_lines(tel)
    report = "\n".join(lines)

    if args.smoke:
        for needle in ("chaos_injected_total", "serving_shed_total",
                       "ps_client_retries_total",
                       "serving_request_errors_total",
                       "exactly-once held", "ps_promotions_total",
                       "commits lost = 0", "migration latency",
                       "old owner un-fenced"):
            assert needle in report, f"report lacks {needle}:\n{report}"
        report += "\nsmoke: ok"
    telemetry.disable()

    print(report)
    if args.out:
        pathlib.Path(args.out).write_text(report + "\n")


if __name__ == "__main__":
    main()
