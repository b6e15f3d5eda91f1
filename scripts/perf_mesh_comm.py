"""Mesh-tier comm compression A/B: f32 vs bf16 vs int8 (ISSUE 16).

Three arms of the SAME compiled PS round (``ps_dataplane``), differing
only in the wire:

* ``f32``   — baseline: f32 center all_gather + f32 delta psum_scatter
* ``bf16``  — ``comm_dtype="bfloat16"``: the delta reduce-scatter
  narrowed to bf16 (wire AND reduction)
* ``int8``  — ``comm_codec="int8"``: the center re-broadcast quantized
  on-device with per-leaf symmetric scales

Per arm it reports round/step time, the static wire bytes
(``comm_bytes_per_round``), and bytes saved vs f32; the run asserts

* codec-law parity: the on-chip quantizer is bitwise the host
  ``Int8Codec`` (``q`` exact, scale to f32-vs-f64 rtol), and
* trajectory parity: each compressed arm's center stays within the
  quantization-step bound of the f32 arm's center (both lossy wires
  perturb the PULLED center, never the stored shards).

The model is deliberately comm-heavy (one wide MLP layer, window=1,
small batch), so the collective — not the matmul — dominates the
round; that is the regime the knobs exist for.  On CPU the collectives
are emulated memcpy loops: the int8 arm's honest 1-byte gather wins,
while bf16 arithmetic is software-emulated and typically LOSES — both
are recorded as-is (PERF.md §31); on a real TPU ICI both shrink.

Headline gating (``perf_regress``): the bytes-saved counter becomes a
rate candidate via ``from_registry`` and the step time a
lower-is-better candidate via ``evaluate`` — both checked in both
directions (pass + forced breach) in ``--smoke``, which runs the whole
A/B at tiny shapes and is registered in SMOKE_SCRIPTS.

Run:  python scripts/perf_mesh_comm.py [--devices 4] [--dim 2048]
          [--reps 5] [--out CAND.json] [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
SCRIPTS = pathlib.Path(__file__).resolve().parent
if str(SCRIPTS) not in sys.path:
    sys.path.insert(0, str(SCRIPTS))

ARMS = (("f32", "float32", None),
        ("bf16", "bfloat16", None),
        ("int8", "float32", "int8"))


def _measure_arm(args, comm_dtype, comm_codec):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu import mesh as mesh_lib
    from distkeras_tpu.models import build_model, model_config
    from distkeras_tpu.parallel import ps_dataplane
    from distkeras_tpu.parallel.ps_emulator import commit_permutation
    from distkeras_tpu.parallel.update_rules import RULES
    from distkeras_tpu.workers import (TrainState, make_train_step,
                                       resolve_optimizer)

    W = args.workers
    model = build_model(model_config(
        "mlp", (args.dim,), num_classes=args.classes,
        hidden=(args.dim,)))
    tx = resolve_optimizer("momentum", args.lr)
    center = model.init(jax.random.key(0),
                        jnp.ones((2, args.dim), jnp.float32))["params"]
    rule = RULES["downpour"]()
    step = make_train_step(model, "sparse_categorical_crossentropy",
                           tx)

    placement = mesh_lib.place_workers(W)
    if placement.mesh is None or placement.vmap_workers != 1:
        raise SystemExit(
            f"needs one device per worker; {W} workers vs "
            f"{len(jax.devices())} devices (pass --devices N on CPU)")
    dp = ps_dataplane.MeshDataplane(
        rule, step, placement.mesh, center, comm_dtype=comm_dtype,
        comm_codec=comm_codec)

    def make_worker(rng):
        return TrainState.create({"params": center}, tx, rng)

    mps, mws = dp.to_device(
        rule.init_state(center),
        jax.vmap(make_worker)(jax.random.split(jax.random.key(1), W)))
    row = mesh_lib.batch_sharding(placement.mesh)
    rep = mesh_lib.replicated_sharding(placement.mesh)
    rng = np.random.RandomState(0)
    batch = jax.device_put(
        {"features": jnp.asarray(
            rng.randn(W, args.window, args.batch, args.dim),
            jnp.float32),
         "label": jnp.asarray(
            rng.randint(0, args.classes,
                        (W, args.window, args.batch)), jnp.int32)},
        row)
    perm = jax.device_put(commit_permutation(jax.random.key(2), W),
                          rep)

    driver = ps_dataplane.MeshRoundDriver(dp, mps, mws)
    driver.dispatch(batch, perm)
    driver.drain()  # warm: compile + first execution
    t0 = time.perf_counter()
    for _ in range(args.reps):
        driver.dispatch(batch, perm)
    metrics = driver.drain()
    dt = (time.perf_counter() - t0) / args.reps

    losses = np.concatenate([m["loss"] for m in metrics])
    center_host = jax.device_get(dp.center(driver.mps))
    return {
        "comm_dtype": comm_dtype, "comm_codec": comm_codec,
        "round_ms": round(dt * 1e3, 2),
        "step_time_ms": round(dt * 1e3 / args.window, 2),
        "comm_bytes_per_round": dp.comm_bytes_per_round,
        "comm_bytes_saved_per_round": dp.comm_bytes_saved_per_round,
        "loss_finite": bool(np.isfinite(losses).all()),
        "workers": W,
    }, center_host, dp


def _assert_codec_law():
    """The on-chip quantizer IS the host ``Int8Codec`` law (the parity
    oracle the wire format is defined by): ``q`` bitwise, scale to
    f32-vs-f64 rounding."""
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.parallel import ps_dataplane
    from distkeras_tpu.parallel.compression import Int8Codec

    rng = np.random.RandomState(7)
    x = (rng.randn(4097) * 0.21).astype(np.float32)
    q, s = ps_dataplane.quantize_int8(jnp.asarray(x))
    enc = Int8Codec().encode_leaf(x)
    assert np.array_equal(np.asarray(q),
                          np.frombuffer(enc["q"], np.int8))
    np.testing.assert_allclose(float(s), enc["s"], rtol=1e-6)


def run(args) -> list[dict]:
    import jax
    import numpy as np

    from distkeras_tpu import telemetry
    from distkeras_tpu.parallel import ps_dataplane

    _assert_codec_law()
    tel = telemetry.enable()
    t_wall = time.perf_counter()
    results, centers = {}, {}
    for name, dt, codec in ARMS:
        rec, center, dp = _measure_arm(args, dt, codec)
        results[name], centers[name] = rec, center
        print(json.dumps({"arm": name, **rec}), flush=True)
    seconds = time.perf_counter() - t_wall
    snap = tel.metrics.snapshot()
    telemetry.disable()

    # trajectory parity: lossy wires perturb only the PULLED center;
    # after `reps+1` rounds every leaf must sit within the accumulated
    # quantization step of the f32 trajectory.  Bound: per-round pull
    # error <= scale/2 per element, amplified through the window run —
    # 8x slack covers the optimizer's gain at lr<=0.1.
    import jax.numpy as jnp
    qstep = max(
        float(jnp.max(jnp.abs(leaf)) / 127.0)
        for leaf in jax.tree_util.tree_leaves(centers["f32"]))
    atol = 8.0 * qstep * (args.reps + 1)
    for name in ("bf16", "int8"):
        for la, lb in zip(jax.tree_util.tree_leaves(centers["f32"]),
                          jax.tree_util.tree_leaves(centers[name])):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       atol=atol, rtol=0,
                                       err_msg=f"{name} center parity")
        assert results[name]["loss_finite"], results[name]
    print(json.dumps({"parity": "ok", "atol": round(atol, 6)}),
          flush=True)

    # wire accounting sanity: the knobs actually shrink their
    # collective (static bytes, no timing noise)
    f32b = results["f32"]["comm_bytes_per_round"]
    assert results["int8"]["comm_bytes_per_round"]["gather"] < \
        f32b["gather"]
    assert results["bf16"]["comm_bytes_per_round"]["scatter"] < \
        f32b["scatter"]

    best = min(("bf16", "int8"),
               key=lambda n: results[n]["step_time_ms"])
    summary = {
        "metric": "mesh_comm_best_step_time_ms",
        "value": results[best]["step_time_ms"],
        "unit": "ms", "lower_is_better": True,
        "best_arm": best,
        "f32_step_time_ms": results["f32"]["step_time_ms"],
        "speedup_vs_f32": round(
            results["f32"]["step_time_ms"]
            / results[best]["step_time_ms"], 3),
        "bytes_saved_per_round":
            results[best]["comm_bytes_saved_per_round"],
        "workers": args.workers, "dim": args.dim,
        "device": jax.devices()[0].device_kind,
    }
    print(json.dumps(summary), flush=True)
    if not args.smoke:
        # the acceptance headline: a compressed arm beats f32 on step
        # time (CPU-honest; at tiny --smoke shapes timing is noise and
        # the claim would be dishonest, so only the full run asserts)
        assert summary["speedup_vs_f32"] > 1.0, summary

    # ---- perf_regress gating, both directions ------------------------
    import perf_regress

    out_dir = pathlib.Path(tempfile.mkdtemp(prefix="dkt_meshcomm_"))
    snap_path = out_dir / "registry.json"
    snap_path.write_text(json.dumps(snap, default=repr))
    saved_rate = perf_regress.from_registry(
        str(snap_path), "mesh_comm_bytes_saved_per_sec",
        "ps_round_comm_bytes_saved_total", seconds)
    assert saved_rate[0]["value"] > 0, saved_rate
    cands = [summary] + saved_rate
    for n in (1, 2):
        (out_dir / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "cmd": "perf_mesh_comm", "rc": 0, "tail": "",
            "parsed": cands}))  # parsed-as-LIST: mixed-metric file
    traj = perf_regress.load_trajectories(str(out_dir / "BENCH_*.json"))
    rows = perf_regress.evaluate(saved_rate, traj, tolerance=0.5)
    rows += perf_regress.evaluate([summary], traj, tolerance=0.5,
                                  lower_is_better=True)
    print(perf_regress.render(rows), flush=True)
    assert all(r["status"] == "pass" for r in rows), rows
    bad = perf_regress.evaluate(
        [{"metric": "mesh_comm_best_step_time_ms",
          "value": summary["value"] * 10.0}], traj, tolerance=0.5,
        lower_is_better=True)
    bad += perf_regress.evaluate(
        [{"metric": "mesh_comm_bytes_saved_per_sec",
          "value": saved_rate[0]["value"] / 10.0}], traj,
        tolerance=0.5)
    assert all(r["status"] == "breach" for r in bad), bad
    print(json.dumps({"gate": "pass_and_breach", "ok": True}),
          flush=True)

    records = [summary] + [
        {"metric": f"mesh_comm_{name}_step_time_ms",
         "value": rec["step_time_ms"], "unit": "ms",
         "lower_is_better": True, **rec}
        for name, rec in results.items()]
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(records))
    if args.smoke:
        print(json.dumps({"smoke": "ok"}))
    return records


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--window", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--dim", type=int, default=4096,
                    help="MLP width; params ~= dim^2 + dim*classes "
                         "(comm-heavy by design; below ~4096 the "
                         "round is compute-bound on CPU and the "
                         "compressed arms stop winning)")
    ap.add_argument("--classes", type=int, default=16)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N virtual host devices (CPU runs)")
    ap.add_argument("--out", default=None,
                    help="write the parsed-format records (a LIST) "
                         "for perf_regress.py --candidate")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no timing-win assert; tier-1 "
                         "mode")
    args = ap.parse_args()

    if args.smoke:
        args.devices = args.devices or 4
        args.workers, args.window, args.batch = 4, 1, 4
        args.dim, args.classes, args.reps = 64, 8, 2
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    run(args)


if __name__ == "__main__":
    main()
