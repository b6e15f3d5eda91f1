"""End-to-end A/B: ResNet-50 b256 train step, fusion='none' vs
'pallas_block', interleaved reps (PERF.md §11).

Usage:  PYTHONPATH=/root/repo python scripts/perf_fused_e2e.py
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

import time

from distkeras_tpu.profiling import (enable_compile_cache, host_sync,
                                     peak_flops, resnet50_model_flops)


def timed_chain(step, state, batch, n):
    """Like profiling.time_step_chain but hands the threaded (donated)
    state back so rounds can be interleaved."""
    for _ in range(2):
        state, metrics = step(state, batch)
    host_sync(metrics)
    t0 = time.perf_counter()
    for _ in range(n):
        state, metrics = step(state, batch)
    val = host_sync(metrics)
    return (time.perf_counter() - t0) / n, val, state


def build(arm, batch, image, stem):
    """``arm``: 'none' | 'block[:stages]' | 'tail[:stages]', where
    stages is a comma-free digit string, e.g. 'block:01' = pallas_block
    fused at stages 0 and 1 only."""
    from distkeras_tpu.models import ResNet50
    from distkeras_tpu.workers import (TrainState, make_train_step,
                                       resolve_optimizer)

    kind, _, stages = arm.partition(":")
    fusion = {"none": "none", "block": "pallas_block",
              "tail": "pallas_tail"}[kind]
    fusion_stages = tuple(int(c) for c in stages) if stages else None
    model = ResNet50(num_classes=1000, stem=stem, fusion=fusion,
                     fusion_stages=fusion_stages)
    tx = resolve_optimizer("momentum", 0.1)
    x = jnp.ones((batch, image, image, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x[:2])
    state = TrainState.create(variables, tx, jax.random.key(1))
    step = jax.jit(make_train_step(model, "categorical_crossentropy", tx),
                   donate_argnums=0)
    batch_dict = {"features": x,
                  "label": jnp.zeros((batch,), jnp.int32)}
    return step, state, batch_dict


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--stem", type=str, default="space_to_depth")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--n", type=int, default=15)
    ap.add_argument("--arms", type=str, default="none,block")
    args = ap.parse_args()

    peak, _ = peak_flops(jax.devices()[0])
    flops = resnet50_model_flops(args.batch, args.image)
    arms = {}
    for fusion in args.arms.split(","):
        arms[fusion] = build(fusion, args.batch, args.image, args.stem)
    for r in range(args.rounds):
        for fusion in list(arms):
            step, state, batch = arms[fusion]
            dt, val, state = timed_chain(step, state, batch, n=args.n)
            arms[fusion] = (step, state, batch)
            print(json.dumps({
                "arm": fusion, "round": r,
                "step_ms": round(dt * 1e3, 2),
                "img_per_sec": round(args.batch / dt, 1),
                "mfu": round(flops / dt / peak, 4),
                "loss_finite": bool(jnp.isfinite(val)),
            }), flush=True)


if __name__ == "__main__":
    main()
