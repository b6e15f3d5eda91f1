"""ResNet-50 step-time sweep on the real TPU chip (PERF.md experiments).

Runs a grid of configurations of the flagship training step and prints one
JSON line per config with step time, images/sec, XLA-counted FLOPs, and
both MFU flavors (honest analytic-model-FLOPs ``mfu`` and ``xla_mfu`` —
see PERF.md §1 for why they differ).  Serialized in one process so the
single-client TPU is never contended.

Usage:  PYTHONPATH=/root/repo:$PYTHONPATH python scripts/perf_sweep.py
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from distkeras_tpu.profiling import (
    enable_compile_cache,
    peak_flops,
    resnet50_model_flops,
    time_step_chain,
)


def run_config(batch, norm, input_dtype, image=224, n_steps=20):
    from distkeras_tpu.models import ResNet50
    from distkeras_tpu.workers import (TrainState, make_train_step,
                                       resolve_optimizer)

    model = ResNet50(num_classes=1000, norm=norm)
    tx = resolve_optimizer("momentum", 0.1)
    x = jnp.ones((batch, image, image, 3), jnp.dtype(input_dtype))
    variables = model.init(jax.random.key(0), x[:2])
    state = TrainState.create(variables, tx, jax.random.key(1))
    step = make_train_step(model, "categorical_crossentropy", tx)
    bd = {"features": x, "label": jnp.zeros((batch,), jnp.int32)}

    jit_step = jax.jit(step, donate_argnums=0)
    compiled = jit_step.lower(state, bd).compile()
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0)) if cost else 0.0

    dt, _ = time_step_chain(jit_step, state, bd, n=n_steps)
    peak, known = peak_flops(jax.devices()[0])
    model_flops = resnet50_model_flops(batch, image)
    print(json.dumps({
        "batch": batch, "norm": norm, "input_dtype": input_dtype,
        "step_ms": round(dt * 1e3, 2),
        "images_per_sec": round(batch / dt, 1),
        "xla_gflops_per_image": round(flops / batch / 1e9, 2),
        "mfu": round(model_flops / dt / peak, 4) if known else None,
        "xla_mfu": round(flops / dt / peak, 4) if known else None,
    }), flush=True)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    dev = jax.devices()[0]
    print(json.dumps({"device": getattr(dev, "device_kind", str(dev)),
                      "platform": dev.platform}), flush=True)

    grid = [
        # (batch, norm, input_dtype)
        (128, "group", "float32"),
        (256, "group", "float32"),
        (512, "group", "float32"),
        (256, "group", "bfloat16"),
        (256, "batch", "float32"),
        (512, "batch", "bfloat16"),
        (1024, "batch", "bfloat16"),
    ]
    if args.quick:
        grid = grid[:2]
    for cfg in grid:
        try:
            run_config(*cfg)
        except Exception as e:  # OOM etc. — record and continue
            print(json.dumps({"batch": cfg[0], "norm": cfg[1],
                              "input_dtype": cfg[2],
                              "error": repr(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
