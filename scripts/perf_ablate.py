"""Where does the ResNet-50 step time go?  Ablation timing on the TPU.

Isolates: host-dispatch overhead (scan-K vs single step), forward vs
backward vs optimizer, norm cost, and input-resolution scaling.  Prints one
JSON line per experiment; results land in PERF.md.

Usage:  PYTHONPATH=/root/repo:$PYTHONPATH python scripts/perf_ablate.py
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from distkeras_tpu.profiling import (
    enable_compile_cache,
    host_sync,
    peak_flops,
    resnet50_model_flops,
    time_step_chain,
)


def timed(fn, *args, n=20):
    """Time a stateless (non-donating) function."""
    out = fn(*args)
    out = fn(*args)
    host_sync(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    host_sync(out)
    return (time.perf_counter() - t0) / n


def report(name, dt, batch, train=True, image=224):
    peak, known = peak_flops(jax.devices()[0])
    model_flops = resnet50_model_flops(batch, image, train=train)
    print(json.dumps({
        "exp": name, "step_ms": round(dt * 1e3, 2),
        "images_per_sec": round(batch / dt, 1),
        "honest_mfu": round(model_flops / dt / peak, 4) if known else None,
    }), flush=True)


def main():
    enable_compile_cache()
    from distkeras_tpu.models import ResNet50
    from distkeras_tpu.workers import (TrainState, make_train_step,
                                       make_window_runner,
                                       resolve_optimizer)

    batch = 256

    def build(norm="group", image=224):
        model = ResNet50(num_classes=1000, norm=norm)
        tx = resolve_optimizer("momentum", 0.1)
        x = jnp.ones((batch, image, image, 3), jnp.float32)
        variables = model.init(jax.random.key(0), x[:2])
        state = TrainState.create(variables, tx, jax.random.key(1))
        bd = {"features": x, "label": jnp.zeros((batch,), jnp.int32)}
        return model, tx, state, bd

    # 1. baseline full step
    model, tx, state, bd = build()
    step = make_train_step(model, "categorical_crossentropy", tx)
    jit_step = jax.jit(step, donate_argnums=0)
    dt, _ = time_step_chain(jit_step, state, bd)
    report("full_step_b256", dt, batch)

    # 2. scan-4 window in one dispatch (amortizes host overhead)
    model, tx, state, bd = build()
    window = make_window_runner(step)
    bd4 = {k: jnp.broadcast_to(v[None], (4, *v.shape)) for k, v in bd.items()}
    jit_win = jax.jit(window, donate_argnums=0)
    dt, _ = time_step_chain(jit_win, state, bd4)
    report("scan4_per_step_b256", dt / 4, batch)

    # 3. forward only (inference mode)
    model, tx, state, bd = build()
    fwd = jax.jit(lambda v, x: model.apply(v, x, train=False))
    dt = timed(fwd, state.variables(), bd["features"])
    report("forward_only_b256", dt, batch, train=False)

    # 4. forward + backward, no optimizer update
    model, tx, state, bd = build()
    from distkeras_tpu.ops.losses import resolve_loss
    loss_fn = resolve_loss("categorical_crossentropy")

    def grads_only(params, x, y):
        return jax.grad(
            lambda p: loss_fn(model.apply({"params": p}, x, train=True),
                              y))(params)
    jit_g = jax.jit(grads_only)
    dt = timed(jit_g, state.params, bd["features"], bd["label"])
    report("fwd_bwd_b256", dt, batch)

    # 5. norm ablation: no norm at all
    model, tx, state, bd = build(norm="none")
    step = make_train_step(model, "categorical_crossentropy", tx)
    jit_step = jax.jit(step, donate_argnums=0)
    dt, _ = time_step_chain(jit_step, state, bd)
    report("full_step_nonorm_b256", dt, batch)

    # 6. resolution scaling: 112 px
    model, tx, state, bd = build(image=112)
    step = make_train_step(model, "categorical_crossentropy", tx)
    jit_step = jax.jit(step, donate_argnums=0)
    dt, _ = time_step_chain(jit_step, state, bd)
    report("full_step_112px_b256", dt, batch, image=112)


if __name__ == "__main__":
    main()
