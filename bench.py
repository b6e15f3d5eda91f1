"""Flagship benchmark: ResNet-50 training throughput + MFU.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
It measures the chip and nothing else: without a TPU whose
``device_kind`` has a row in the peak tables it exits non-zero before
compiling or timing anything (``profiling.require_tpu``).

Two arms (``--mode``):

* ``sync`` — the historical single-chip synchronous train step
  (metric ``resnet50_train_images_per_sec_per_chip``).
* ``ps-mesh`` — the compiled SPMD PS round (``fidelity="mesh"``,
  ISSUE 16): one worker per visible device, async ``MeshRoundDriver``
  dispatch, optional on-chip comm compression
  (``--comm-dtype``/``--comm-codec``).  Metric
  ``ps_round_images_per_sec_per_chip``.
* ``auto`` (default) — ``ps-mesh`` when more than one device is
  visible, else ``sync``; the bench path IS the mesh tier wherever a
  mesh exists.

The reference published no machine-readable numbers (BASELINE.md:
"published: {}"), so ``vs_baseline`` is measured MFU against the
north-star target of 0.60 MFU from BASELINE.json (vs_baseline =
MFU / 0.60) — identical semantics in both arms, with the mesh arm's
MFU accounted as analytic model FLOPs x n_chips (``profiling.train_mfu``).

MFU accounting (see PERF.md): ``mfu`` uses the *analytic model FLOPs* —
2 x MACs x 3 for a training step (ResNet-50 fwd = 4.09 GMACs = 8.18
GFLOPs/image at 224px) — NOT XLA's executed-FLOPs counter.  The two agree
within ~3% at batch <= 512 (so the number is also *measured*-honest), but
XLA's counter inflates when the compiler adds rematerialization (at batch
1024 it reports ~30% more FLOPs while images/sec drops), which would let a
slower configuration "win".  Model FLOPs per image is the denominator that
tracks useful work.  Both numbers are reported.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import attrib as attrib_lib
from distkeras_tpu import telemetry
from distkeras_tpu.profiling import (
    bench_device_config,
    device_record,
    enable_compile_cache,
    peak_bandwidth,
    peak_flops,
    resnet50_model_flops,
    time_step_chain,
    train_mfu,
)


def _model_and_step(cfg):
    from distkeras_tpu.models import ResNet50
    from distkeras_tpu.workers import make_train_step, resolve_optimizer

    # bf16 compute; space-to-depth stem re-layouts the 7x7/s2 stem conv
    # (same math/receptive field, different channel-summation order —
    # tests/test_models.py checks output parity to float tolerance via
    # s2d_stem_kernel) feeding the MXU 12 input channels instead of
    # 3 — measured ~1.5% faster end-to-end (PERF.md §9).
    model = ResNet50(num_classes=cfg["num_classes"],
                     stem="space_to_depth")
    tx = resolve_optimizer("momentum", 0.1)
    step = make_train_step(model, "categorical_crossentropy", tx)
    return model, tx, step


def run_sync(cfg) -> dict:
    from distkeras_tpu.workers import TrainState

    device = cfg["device"]
    batch, image = cfg["batch"], cfg["image"]
    model, tx, step = _model_and_step(cfg)
    x = jnp.ones((batch, image, image, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x[:2])
    state = TrainState.create(variables, tx, jax.random.key(1))
    labels = jnp.zeros((batch,), jnp.int32)
    batch_dict = {"features": x, "label": labels}

    jit_step = jax.jit(step, donate_argnums=0)
    # telemetry consumer wiring: spans are no-ops unless the caller
    # enabled telemetry (DKT_TELEMETRY_TRACE dumps the timeline)
    with telemetry.span("bench_compile", batch=batch):
        t_compile = time.perf_counter()
        compiled = jit_step.lower(state, batch_dict).compile()
        compile_s = time.perf_counter() - t_compile
    cost = compiled.cost_analysis() or {}
    xla_flops_per_step = float(cost.get("flops", 0.0))

    with telemetry.span("bench_timed_chain", n=30):
        dt, synced = time_step_chain(jit_step, state, batch_dict, n=30)

    images_per_sec = batch / dt
    model_flops_per_step = resnet50_model_flops(batch, image)
    peak, _ = peak_flops(device)
    bw, _ = peak_bandwidth(device)
    mfu = train_mfu(images_per_sec, image, device)
    # roofline floor for THIS compiled step: XLA's flops against peak
    # compute, its bytes-accessed against peak memory bandwidth
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    roof = attrib_lib.roofline(xla_flops_per_step, bytes_accessed,
                               peak, bw)
    mfu_roofline = attrib_lib.mfu(xla_flops_per_step,
                                  roof["t_roofline_s"], peak)
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(mfu / 0.60, 4),
        "mfu": round(mfu, 4),
        "mfu_roofline": (round(mfu_roofline, 4)
                         if mfu_roofline is not None else None),
        "xla_mfu": round(xla_flops_per_step / dt / peak, 4),
        "step_time_ms": round(dt * 1e3, 2),
        "compile_s": round(compile_s, 3),
        "batch": batch,
        "image": image,
        "n_chips": 1,
        "mode": "sync",
        "model_flops_per_step": model_flops_per_step,
        "xla_flops_per_step": xla_flops_per_step,
        "device": device_record(),
        "metrics_finite": bool(np.isfinite(synced)),
    }


def run_ps_mesh(cfg, comm_dtype: str, comm_codec,
                window: int = 2) -> dict:
    from distkeras_tpu import mesh as mesh_lib
    from distkeras_tpu.parallel import ps_dataplane
    from distkeras_tpu.parallel.ps_emulator import commit_permutation
    from distkeras_tpu.parallel.update_rules import RULES
    from distkeras_tpu.workers import TrainState

    device = cfg["device"]
    batch, image = cfg["batch"], cfg["image"]
    W = cfg["n_devices"]
    model, tx, step = _model_and_step(cfg)
    x = jnp.ones((2, image, image, 3), jnp.float32)
    center = model.init(jax.random.key(0), x)["params"]
    rule = RULES["downpour"]()

    placement = mesh_lib.place_workers(W)
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
    batch_dict = jax.device_put(
        {"features": jnp.ones((W, window, batch, image, image, 3),
                              jnp.float32),
         "label": jnp.zeros((W, window, batch), jnp.int32)}, row)
    perm = jax.device_put(
        commit_permutation(jax.random.key(2), W), rep)

    driver = ps_dataplane.MeshRoundDriver(dp, mps, mws)
    reps = 10
    with telemetry.span("bench_mesh_warmup", workers=W):
        driver.dispatch(batch_dict, perm)
        driver.drain()
    with telemetry.span("bench_mesh_timed_rounds", n=reps):
        t0 = time.perf_counter()
        for _ in range(reps):
            driver.dispatch(batch_dict, perm)
        metrics = driver.drain()  # blocks on the last round's ring
        dt = (time.perf_counter() - t0) / reps

    # attribution pass OUTSIDE the timed window: flip sampling on for
    # one extra round to decompose it (host_gap/dispatch/compute/fetch
    # + the mfu_observed-vs-roofline pair off the cost ledger)
    driver.attrib_every = 1
    with telemetry.span("bench_mesh_attrib", workers=W):
        driver.dispatch(batch_dict, perm)
        metrics += driver.drain()
    attrib = driver.last_attrib or {}
    report = dp.cost_report()
    cost0 = report[0] if report else {}

    images_per_round = W * window * batch
    images_per_sec_chip = images_per_round / dt / W
    mfu = train_mfu(images_per_sec_chip * W, image, device, n_chips=W)
    losses = np.concatenate([m["loss"] for m in metrics])
    return {
        "metric": "ps_round_images_per_sec_per_chip",
        "value": round(images_per_sec_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(mfu / 0.60, 4),
        "mfu": round(mfu, 4),
        "round_ms": round(dt * 1e3, 2),
        "step_time_ms": round(dt / window * 1e3, 2),
        "batch": batch,
        "image": image,
        "workers": W,
        "window": window,
        "n_chips": W,
        "mode": "ps-mesh",
        "comm_dtype": comm_dtype,
        "comm_codec": comm_codec,
        "comm_bytes_per_round": dp.comm_bytes_per_round,
        "comm_bytes_saved_per_round": dp.comm_bytes_saved_per_round,
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
        "device": device_record(),
        "metrics_finite": bool(np.isfinite(losses).all()),
    }


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--mode", default="auto",
                        choices=("auto", "sync", "ps-mesh"),
                        help="auto: ps-mesh when >1 device is visible")
    parser.add_argument("--comm-dtype", default="float32",
                        help="mesh arm delta wire dtype "
                             "(float32|bfloat16)")
    parser.add_argument("--comm-codec", default=None,
                        help="mesh arm center broadcast codec (int8)")
    args = parser.parse_args()

    enable_compile_cache()
    trace_path = os.environ.get("DKT_TELEMETRY_TRACE")
    if trace_path:
        telemetry.enable()

    cfg = bench_device_config()
    mode = args.mode
    if mode == "auto":
        mode = "ps-mesh" if cfg["n_devices"] > 1 else "sync"
    if mode == "ps-mesh":
        record = run_ps_mesh(cfg, args.comm_dtype, args.comm_codec)
    else:
        record = run_sync(cfg)
    print(json.dumps(record))
    if trace_path:
        telemetry.tracer().write_chrome_trace(trace_path)


if __name__ == "__main__":
    main()
