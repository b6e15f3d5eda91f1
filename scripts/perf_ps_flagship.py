"""PS-family flagship throughput: one compiled PS round, per tier.

BASELINE.json's north star is *AEASGD* on ResNet-50, but every prior
flagship number timed only the bare synchronous step.  This measures
the thing the PS family actually executes on-device: one commit round
— ``communication_window`` jitted train steps per worker followed by
the ``UpdateRule`` commits in permuted order — with the same
scalar-fetch sync and analytic-FLOPs MFU as the BENCH trajectory.

``--fidelity`` picks the lowering tier (``parallel.tiers``):

* ``faithful`` / ``fast`` — the emulated round (``ps_emulator``):
  workers stacked on one program, commits scanned / closed-form.
* ``mesh`` — the on-chip compiled data plane (``ps_dataplane``): one
  SPMD shard_map program per round, center sharded over the worker
  axis, deltas reduce-scattered, state buffers donated.  Delta family
  only (aeasgd is elastic — use the emulated tiers).

``--out FILE`` writes the parsed-format BENCH record (the ``parsed``
block of a ``BENCH_r*.json`` trajectory file), headline metric
``ps_round_images_per_sec_per_chip`` for the mesh tier, so
``perf_regress.py --candidate FILE`` gates it against the trajectory.

``--smoke`` is the CPU tier-1 proof at tiny shapes: mesh-vs-emulated
center/loss parity (plain and pipelined+flush), the one-compile-per-
round-shape guard via ``ps_round_compiles_total{fidelity="mesh"}``,
and the --out record gated through ``perf_regress.evaluate`` in both
directions (pass and forced breach).

Run on the TPU:  python scripts/perf_ps_flagship.py
                 [--fidelity faithful|fast|mesh]
                 [--trainer aeasgd|adag|downpour|dynsgd]
                 [--workers 4 --window 2 --batch 32 --image 224]
                 [--overlap] [--out BENCH_cand.json]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
SCRIPTS = pathlib.Path(__file__).resolve().parent
if str(SCRIPTS) not in sys.path:
    sys.path.insert(0, str(SCRIPTS))


class _Arm:
    """One fidelity arm: device state + a drivable jitted round.

    ``mlp_dim`` swaps the ResNet for a tiny MLP over flat features —
    the smoke's strict-parity model (CPU convs are not batching-
    stable, see ``smoke()``)."""

    def __init__(self, args, fidelity: str, overlap: bool,
                 mlp_dim: int | None = None,
                 sync_metrics: bool = False):
        import jax
        import jax.numpy as jnp

        from distkeras_tpu import mesh as mesh_lib
        from distkeras_tpu.models import model_config
        from distkeras_tpu.parallel import ps_dataplane
        from distkeras_tpu.parallel.ps_emulator import (
            make_pipelined_round_fn, make_round_fn)
        from distkeras_tpu.trainers import (ADAG, AEASGD, DOWNPOUR,
                                            DynSGD)
        from distkeras_tpu.workers import TrainState, make_train_step

        cls = {"adag": ADAG, "aeasgd": AEASGD, "downpour": DOWNPOUR,
               "dynsgd": DynSGD}[args.trainer]
        if mlp_dim is not None:
            cfg = model_config("mlp", (mlp_dim,),
                               num_classes=args.classes, hidden=(32,))
        elif args.smoke:
            # one block per stage: the same code path at seconds scale
            cfg = model_config("resnet", (args.image, args.image, 3),
                               num_classes=args.classes,
                               stage_sizes=(1, 1, 1, 1),
                               bottleneck=False,
                               stem="space_to_depth")
        else:
            cfg = model_config("resnet", (args.image, args.image, 3),
                               num_classes=args.classes,
                               stage_sizes=(3, 4, 6, 3),
                               bottleneck=True,
                               stem="space_to_depth")
        t = cls(cfg, num_workers=args.workers,
                communication_window=args.window,
                batch_size=args.batch, learning_rate=args.lr,
                worker_optimizer="momentum", seed=0)

        self._rule = t.allocate_rule()
        self._W = args.workers
        self.overlap = overlap
        tx = t._tx()
        init_shape = ((2, mlp_dim) if mlp_dim is not None
                      else (2, args.image, args.image, 3))
        variables = t.model.init(jax.random.key(0),
                                 jnp.ones(init_shape, jnp.float32))
        center = variables["params"]
        model_state = {k: v for k, v in variables.items()
                       if k != "params"}

        def make_worker(rng):
            return TrainState.create(
                {"params": center, **model_state}, tx, rng)

        worker_keys = jax.random.split(jax.random.key(1), args.workers)
        ws = jax.vmap(make_worker)(worker_keys)
        ps = self._rule.init_state(center)
        step = make_train_step(t.model, t.loss, tx)

        self.dp = None
        self.n_chips = 1
        if fidelity == "mesh":
            placement = mesh_lib.place_workers(args.workers)
            if placement.mesh is None or placement.vmap_workers != 1:
                raise SystemExit(
                    f"--fidelity mesh maps one worker per device; "
                    f"num_workers={args.workers} does not fit "
                    f"{len(jax.devices())} devices (pass --devices N "
                    f"on CPU)")
            self._row = mesh_lib.batch_sharding(placement.mesh)
            self._rep = mesh_lib.replicated_sharding(placement.mesh)
            self.dp = ps_dataplane.MeshDataplane(
                self._rule, step, placement.mesh, center,
                pipelined=overlap,
                comm_dtype=getattr(args, "comm_dtype", "float32"),
                comm_codec=getattr(args, "comm_codec", None),
                metrics_every=getattr(args, "metrics_every", 1))
            mps, mws = self.dp.to_device(ps, ws)
            # async by default (the thing ISSUE 16 measures: round k+1
            # dispatched before round k's metrics land); sync_metrics
            # is the smoke's per-round parity mode
            self.driver = ps_dataplane.MeshRoundDriver(
                self.dp, mps, mws, sync=sync_metrics)
            self.n_chips = placement.mesh_workers
        else:
            self.ps, self.ws = ps, ws
            if overlap:
                self.round_jit = jax.jit(
                    make_pipelined_round_fn(self._rule, step),
                    donate_argnums=(0, 1, 4))
                self.pend = jax.tree_util.tree_map(jnp.zeros_like,
                                                   ws.params)
                self.pend_perm = jnp.arange(args.workers)
                self.valid = jnp.asarray(False)
            else:
                self.round_jit = jax.jit(
                    make_round_fn(self._rule, step, fidelity),
                    donate_argnums=(0, 1))

    def put(self, batch, perm):
        """Place one round's inputs (mesh tier: row-sharded batch,
        replicated permutation; emulated: as-is)."""
        import jax

        if self.dp is not None:
            return (jax.device_put(batch, self._row),
                    jax.device_put(perm, self._rep))
        return batch, perm

    def round(self, batch, perm):
        """One round.  Mesh tier: dispatch through the driver and
        return the latest fetched metrics (the just-run round's under
        ``sync_metrics``; possibly ``None`` early in an async run)."""
        if self.dp is not None:
            self.driver.dispatch(batch, perm)
            out = self.driver.poll()
            return out[-1] if out else None
        if self.overlap:
            (self.ps, self.ws, metrics, self.pend, self.pend_perm,
             self.valid) = self.round_jit(
                self.ps, self.ws, batch, perm, self.pend,
                self.pend_perm, self.valid)
        else:
            self.ps, self.ws, metrics = self.round_jit(
                self.ps, self.ws, batch, perm)
        return metrics

    def sync(self, metrics) -> float:
        """Block until every dispatched round has executed; return a
        loss scalar for the finite-ness health check."""
        import numpy as np

        from distkeras_tpu.profiling import host_sync

        if self.dp is not None:
            out = self.driver.drain()
            if out:
                metrics = out[-1]
            if metrics is None:
                return float("nan")
            return float(np.asarray(metrics["loss"]).reshape(-1)[0])
        return host_sync(metrics["loss"])

    def flush(self):
        """Drain the pipelined arm's carried pending commit."""
        if not self.overlap:
            return
        if self.dp is not None:
            self.driver.flush_pipeline()
        else:
            from distkeras_tpu.parallel.ps_emulator import \
                flush_pending

            self.ps = flush_pending(self._rule, self.ps, self.pend,
                                    self.pend_perm, self._W)

    def center_host(self):
        import jax

        c = (self.dp.center(self.driver.mps) if self.dp is not None
             else self.ps.center)
        return jax.device_get(c)


def measure(args, fidelity: str, overlap: bool) -> dict:
    """Warm, time ``--reps`` rounds, return the parsed BENCH record."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.profiling import peak_flops, resnet50_model_flops

    arm = _Arm(args, fidelity, overlap)
    x = jnp.ones((args.workers, args.window, args.batch,
                  args.image, args.image, 3), jnp.float32)
    y = jnp.zeros((args.workers, args.window, args.batch), jnp.int32)
    batch, perm = arm.put({"features": x, "label": y},
                          jnp.arange(args.workers))

    for _ in range(3):
        metrics = arm.round(batch, perm)
    arm.sync(metrics)
    t0 = time.perf_counter()
    for _ in range(args.reps):
        metrics = arm.round(batch, perm)
    val = arm.sync(metrics)
    dt = (time.perf_counter() - t0) / args.reps

    imgs = args.workers * args.window * args.batch
    peak, known = peak_flops(jax.devices()[0])
    # analytic MFU only where the model IS ResNet-50 (--smoke shrinks
    # the stages, so its FLOP formula would be fiction); peak_known
    # rides the record so a nominal CPU peak can't pass as measured
    mfu = None
    if peak == peak and not args.smoke:
        flops = resnet50_model_flops(imgs, args.image)
        mfu = round(flops / dt / (peak * arm.n_chips), 4)

    # mesh tier: one attribution round outside the timed window (the
    # sampled decomposition + the ledger's roofline pair, ISSUE 17)
    attrib, cost0 = {}, {}
    if fidelity == "mesh":
        arm.driver.attrib_every = 1
        arm.round(batch, perm)
        arm.sync(None)
        attrib = arm.driver.last_attrib or {}
        report = arm.dp.cost_report()
        cost0 = report[0] if report else {}

    if fidelity == "mesh":
        name = "ps_round_images_per_sec_per_chip"
        value = round(imgs / dt / arm.n_chips, 2)
        unit = "images/sec/chip"
    else:
        # legacy emulated metric: total throughput, faithful unsuffixed
        name = f"{args.trainer}_resnet50_emulated_round"
        if fidelity != "faithful":
            name += f"_{fidelity}"
        value = round(imgs / dt, 2)
        unit = "images/sec"
    if overlap:
        name += "_overlap"
    # self-describing like bench.py's records (ISSUE 16 satellite):
    # step_time_ms/mfu/comm_dtype/n_chips ride along so a BENCH file
    # holding this record needs no out-of-band context
    return {
        "metric": name, "value": value, "unit": unit,
        "fidelity": fidelity, "trainer": args.trainer,
        "mfu": mfu, "round_ms": round(dt * 1e3, 2),
        "step_time_ms": round(dt * 1e3 / args.window, 2),
        "per_step_ms": round(dt * 1e3 / args.window, 2),
        "workers": args.workers, "window": args.window,
        "batch_per_worker": args.batch,
        "global_images_per_round": imgs, "image": args.image,
        "n_chips": arm.n_chips,
        "chips": arm.n_chips,
        "comm_dtype": getattr(args, "comm_dtype", "float32"),
        "comm_codec": getattr(args, "comm_codec", None),
        "mfu_roofline": (round(attrib["mfu_roofline"], 4)
                         if "mfu_roofline" in attrib else None),
        "mfu_observed": (round(attrib["mfu_observed"], 4)
                         if "mfu_observed" in attrib else None),
        "attrib": {seg: round(attrib[seg] * 1e3, 3)
                   for seg in ("host_gap", "dispatch",
                               "device_compute", "ring_fetch")
                   if seg in attrib},
        "compile_s": (round(cost0["compile_s"], 3)
                      if "compile_s" in cost0 else None),
        "peak_known": bool(cost0.get("peak_known", known)),
        "loss_finite": bool(np.isfinite(val)),
    }


def smoke(args) -> dict:
    """Tier-1 proof: parity, compile guard, and the perf gate wired
    end to end — all at tiny CPU shapes."""
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    import perf_regress
    from distkeras_tpu import telemetry
    from distkeras_tpu.parallel.ps_emulator import commit_permutation

    tel = telemetry.enable()
    rounds = 3
    # Parity runs on a tiny MLP, NOT the ResNet: XLA CPU convolutions
    # are not batching-stable (the same window computed solo-shaped,
    # as the mesh tier's per-device program does, vs vmapped over
    # workers, as the emulated tier does, differs by ~1e-2 on logits
    # — measured, backend property), so conv centers can only agree
    # to the noise floor.  Matmuls ARE stable, so the MLP proves the
    # data plane's round semantics to 2e-5.
    dim = 24
    rng = np.random.RandomState(0)
    batches = [
        {"features": jnp.asarray(
            rng.randn(args.workers, args.window, args.batch, dim),
            jnp.float32),
         "label": jnp.asarray(
            rng.randint(0, args.classes,
                        (args.workers, args.window, args.batch)),
            jnp.int32)}
        for _ in range(rounds)]
    import jax

    pkey = jax.random.key(2)
    perms = []
    for _ in range(rounds):
        pkey, sub = jax.random.split(pkey)
        perms.append(commit_permutation(sub, args.workers))

    def assert_close(a, b, what):
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=2e-5, atol=1e-6,
                                       err_msg=what)

    for trainer in ("downpour", "dynsgd"):
        args.trainer = trainer
        ref = _Arm(args, "fast", False, mlp_dim=dim)
        got = _Arm(args, "mesh", False, mlp_dim=dim,
                   sync_metrics=True)
        for b, p in zip(batches, perms):
            mr = ref.round(*ref.put(b, p))
            mg = got.round(*got.put(b, p))
            assert_close(mr["loss"], mg["loss"], f"{trainer} loss")
        assert_close(ref.center_host(), got.center_host(),
                     f"{trainer} center")

        refp = _Arm(args, "faithful", True, mlp_dim=dim)
        gotp = _Arm(args, "mesh", True, mlp_dim=dim,
                    sync_metrics=True)
        for b, p in zip(batches, perms):
            refp.round(*refp.put(b, p))
            gotp.round(*gotp.put(b, p))
        refp.flush()
        gotp.flush()
        assert_close(refp.center_host(), gotp.center_host(),
                     f"{trainer} pipelined center")
        print(json.dumps({"parity": trainer, "ok": True}), flush=True)

    # compile guard: 3 rounds per arm, exactly ONE trace per round
    # shape (2 trainers x 1 program per fidelity label)
    comp = {k: v for k, v in tel.metrics.snapshot()["counters"].items()
            if k.startswith("ps_round_compiles_total")}
    assert comp.get('ps_round_compiles_total{fidelity="mesh"}') == 2, \
        comp
    assert comp.get(
        'ps_round_compiles_total{fidelity="mesh_pipelined"}') == 2, \
        comp

    # the measured record, gated through perf_regress both ways
    args.trainer = "downpour"
    rec = measure(args, "mesh", overlap=False)
    assert rec["loss_finite"], rec
    out_dir = pathlib.Path(tempfile.mkdtemp(prefix="dkt_flagship_"))
    cand = pathlib.Path(args.out) if args.out \
        else out_dir / "candidate.json"
    cand.write_text(json.dumps(rec))
    (out_dir / "BENCH_r01.json").write_text(json.dumps(
        {"n": 1, "cmd": "smoke", "rc": 0, "tail": "", "parsed": rec}))
    traj = perf_regress.load_trajectories(str(out_dir / "BENCH_*.json"))
    rows = perf_regress.evaluate([json.loads(cand.read_text())], traj,
                                 tolerance=0.5)
    assert [r["status"] for r in rows] == ["pass"], rows
    bad = perf_regress.evaluate(
        [{"metric": rec["metric"], "value": rec["value"] / 10.0}],
        traj, tolerance=0.5)
    assert bad[0]["status"] == "breach", bad
    print(json.dumps({"gate": rec["metric"], "pass_and_breach": True}),
          flush=True)
    telemetry.disable()
    print(json.dumps({"smoke": "ok"}))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trainer", default="aeasgd",
                    choices=["adag", "aeasgd", "downpour", "dynsgd"])
    ap.add_argument("--fidelity", default="faithful",
                    choices=["faithful", "fast", "mesh"],
                    help="lowering tier for the round program "
                         "(mesh = the SPMD compiled data plane; "
                         "delta family only)")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--window", type=int, default=2)
    ap.add_argument("--batch", type=int, default=32,
                    help="per-worker batch")
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--comm-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="mesh tier: delta reduce-scatter wire dtype")
    ap.add_argument("--comm-codec", default=None,
                    choices=[None, "int8"],
                    help="mesh tier: center re-broadcast codec")
    ap.add_argument("--metrics-every", type=int, default=1,
                    help="mesh tier: rounds per metrics-ring fetch")
    ap.add_argument("--overlap", action="store_true",
                    help="commit-pipelined round (delta family): the "
                         "commit of round k-1 rides in the same "
                         "program as window k — VERDICT r4 #2")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N virtual host devices (CPU runs; set "
                         "before jax imports)")
    ap.add_argument("--out", default=None,
                    help="write the parsed-format BENCH record here "
                         "(perf_regress.py --candidate input)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape CPU proof: parity + compile "
                         "guard + the perf gate, tier-1 mode")
    args = ap.parse_args()

    if args.smoke:
        args.devices = args.devices or 4
        args.workers, args.window, args.batch = 4, 2, 2
        args.image, args.classes, args.reps = 32, 8, 2
        # stable regime: at the default lr the tiny config is chaotic
        # and conv-batching float noise (solo-shaped device programs
        # vs the emulated tier's vmap — different accumulation order)
        # would compound to O(1) center differences
        args.lr = 1e-3
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    if args.smoke:
        smoke(args)
        return

    from distkeras_tpu.profiling import enable_compile_cache

    enable_compile_cache()
    rec = measure(args, args.fidelity, args.overlap)
    print(json.dumps(rec))
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(rec))


if __name__ == "__main__":
    main()
