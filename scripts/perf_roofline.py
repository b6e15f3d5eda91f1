"""Per-op roofline attribution of the ResNet-50 training step.

VERDICT r4 Weak #1: the flagship's MFU (0.31) sits 19 points under the
estimated ~0.5 bandwidth ceiling (PERF.md §3) and no per-op accounting
ever showed WHERE the step time goes.  This script produces that table:

- enumerates every op class in the b256/224px flagship step (each
  unique conv shape, each norm/elementwise shape, pool/dense/loss),
- measures each op's fwd and fwd+bwd time ON THE CHIP (scan-chained
  with a data-dependent gate, two chain lengths differenced so the
  dispatch overhead cancels),
- computes each op's roofline bound: max(FLOPs / 197 TF/s,
  min-bytes / 820 GB/s) in bf16,
- reconciles: sum(measured per-op x count) vs the measured whole step.

Output: a markdown table (PERF.md §21) + a JSON line.

Run (real TPU): python scripts/perf_roofline.py
Smoke (CPU):    python scripts/perf_roofline.py --smoke
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
from jax import lax

PEAK = 197e12     # bf16 FLOP/s, TPU v5e (PERF.md header)
BW = 820e9        # HBM bytes/s


# ---------------------------------------------------------------------
# op inventory: ResNet-50 @ (batch, image), space_to_depth stem —
# exactly the bench.py flagship graph (models/resnet.py)
# ---------------------------------------------------------------------


def conv_inventory(image: int):
    """[(name, count, H_in, C_in, K, stride, C_out)] for the flagship.
    Spatial sizes assume image % 32 == 0 (224 or 64)."""
    s = image // 2   # after stem (stride-2-equivalent s2d conv)
    p = s // 2       # after 3x3/s2 maxpool
    ops = [("stem 4x4/s1 12->64 @%d" % s, 1, s, 12, 4, 1, 64)]
    spatial = p
    cin = 64
    for stage, (blocks, w) in enumerate(
            zip((3, 4, 6, 3), (64, 128, 256, 512))):
        cout = 4 * w
        stride = 1 if stage == 0 else 2
        out_sp = spatial // stride
        # first block (strided, with downsample projection)
        ops += [
            (f"1x1 {cin}->{w} @{spatial}", 1, spatial, cin, 1, 1, w),
            (f"3x3/s{stride} {w}->{w} @{spatial}", 1, spatial, w, 3,
             stride, w),
            (f"1x1 {w}->{cout} @{out_sp}", 1, out_sp, w, 1, 1, cout),
            (f"ds 1x1/s{stride} {cin}->{cout} @{spatial}", 1, spatial,
             cin, 1, stride, cout),
        ]
        # remaining blocks
        n = blocks - 1
        ops += [
            (f"1x1 {cout}->{w} @{out_sp}", n, out_sp, cout, 1, 1, w),
            (f"3x3 {w}->{w} @{out_sp}", n, out_sp, w, 3, 1, w),
            (f"1x1 {w}->{cout} @{out_sp} (x{n})", n, out_sp, w, 1, 1,
             cout),
        ]
        spatial, cin = out_sp, cout
    return ops


def norm_inventory(image: int):
    """[(name, count, H, C)] — every GN(+relu) site.  Residual
    add+relu sites are measured separately as 'add'."""
    p = image // 4
    ops = [("gn 64 @%d (stem)" % (image // 2), 1, image // 2, 64)]
    spatial = p
    for stage, (blocks, w) in enumerate(
            zip((3, 4, 6, 3), (64, 128, 256, 512))):
        cout = 4 * w
        out_sp = spatial // (1 if stage == 0 else 2)
        ops += [
            (f"gn {w} @{spatial}/{out_sp}", 2 * blocks,
             out_sp, w),                       # two mid-width norms
            (f"gn {cout} @{out_sp}", blocks + 1, out_sp, cout),
            (f"add+relu {cout} @{out_sp}", blocks, out_sp, cout),
        ]
        spatial = out_sp
    return ops


# ---------------------------------------------------------------------
# measurement: scan-chained, differenced
# ---------------------------------------------------------------------


def _time(go, carry0, rest, reps):
    """Best-of-reps wall time of the jitted chain (scalar-fetch sync)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = go(carry0, *rest)
        float(out)  # host fetch = the only reliable sync on this rig
        best = min(best, time.perf_counter() - t0)
    return best


def time_op(step, carry0, rest, est_ms, reps=3, target_ms=250.0,
            max_iters=4000):
    """Per-call seconds of ``step(carry, *rest) -> carry`` via two
    chain lengths: dispatch/sync overhead cancels in the difference.

    ``carry0`` is the loop-carried operand — a probe scalar for ops
    that are nonlinear in their input, or the WEIGHTS for convs (see
    ``conv_fwd_step``).  Only a scalar probe of the final carry is
    fetched (a full carry's transfer would dwarf the measurement).

    The DIFFERENCED work must dominate the dispatch jitter: the chain
    lengths are scaled
    from ``est_ms`` (the op's roofline bound — a lower bound on its
    real time, hence an upper bound on the iterations needed) so the
    difference carries ~``target_ms`` of real compute."""
    n_diff = int(min(max_iters,
                     max(24, target_ms / max(est_ms, 0.02))))
    n_lo = max(4, n_diff // 4)
    n_hi = n_lo + n_diff

    def build(n):
        @jax.jit
        def go(c0, *rest):
            def body(c, _):
                return step(c, *rest), None
            c, _ = lax.scan(body, c0, None, length=n)
            # probe element: every iteration's epsilon feeds the
            # carry multiplicatively, so one element of the final
            # carry transitively requires the whole chain
            probe = c if getattr(c, "ndim", 0) == 0 \
                else c.reshape(-1)[0]
            return probe.astype(jnp.float32)
        return go

    hi, lo = build(n_hi), build(n_lo)
    float(hi(carry0, *rest))  # compile + warm
    float(lo(carry0, *rest))
    t_hi = _time(hi, carry0, rest, reps)
    t_lo = _time(lo, carry0, rest, reps)
    return max(t_hi - t_lo, 1e-9) / (n_hi - n_lo)


def _gate(out):
    # The gate must (a) be genuinely value-dependent — `* 0 + 1` would
    # constant-fold and let XLA hoist the op out of the scan as
    # loop-invariant — and (b) depend on EVERY output element: a
    # single-element gate lets XLA's slice-sinking compute just one
    # conv window per iteration (the second broken run of this script:
    # convs "measuring" 100x under their FLOP bound while the
    # full-tensor GN stats measured true).  The full sum costs one
    # extra read-pass over the output (~bytes/BW), <10% on the
    # bandwidth-bound ops and noise on the compute-bound ones.
    return jnp.sum(out.astype(jnp.float32)) * 1e-24 + 1.0


# Convolution is BILINEAR, which defeats every scalar-gate scheme:
# with input x*s the dgrad cotangent path conv_t(r, w) references
# neither x nor s — structurally loop-invariant, hoisted (the third
# broken run measured exactly that).  So the conv chains carry the
# WEIGHTS: wc is perturbed each iteration by an output-derived epsilon
# (~1e-30, value-neutral but structurally load-bearing), making every
# conv in both passes depend on the carry.  The train loss is
# QUADRATIC in the output so the weight-grad's cotangent (2*out*r)
# also depends on wc.  Extra per-iteration cost: one fused output
# reduce + a weight-sized update — noise next to the conv itself.


def conv_fwd_step(stride):
    def step(wc, x):
        out = lax.conv_general_dilated(
            x, wc, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        eps = jnp.sum(out.astype(jnp.float32)) * 1e-30
        return wc * (1.0 + eps).astype(wc.dtype)
    return step


def conv_train_step(stride):
    # `r` is a RANDOM cotangent scaffold (an all-ones cotangent lets
    # XLA collapse the backward into reductions); it rides as an
    # ARGUMENT — a closure-captured array becomes an HLO literal
    # baked into the program (a 1.3 GB stem constant never finished
    # compiling).
    def step(wc, x, r):
        def loss(x, w):
            # output stays bf16 so the dgrad/wgrad convs run bf16
            # like the model's
            out = lax.conv_general_dilated(
                x, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            return jnp.sum(out.astype(jnp.float32) ** 2
                           * r.astype(jnp.float32))
        gx, gw = jax.grad(loss, argnums=(0, 1))(x, wc)
        eps = (jnp.sum(gx.astype(jnp.float32))
               + jnp.sum(gw.astype(jnp.float32))) * 1e-30
        return wc * (1.0 + eps).astype(wc.dtype)
    return step


def gn_steps(c, x, scale, bias):
    import math

    groups = math.gcd(32, c)

    def apply(x):
        xf = x.astype(jnp.float32)
        b, h, w_, _ = x.shape
        g = xf.reshape(b, h, w_, groups, c // groups)
        mean = g.mean(axis=(1, 2, 4), keepdims=True)
        mean2 = (g * g).mean(axis=(1, 2, 4), keepdims=True)
        inv = lax.rsqrt(jnp.maximum(mean2 - mean * mean, 0.0) + 1e-5)
        y = ((g - mean) * inv).reshape(b, h, w_, c)
        return nn_relu(y * scale + bias).astype(x.dtype)

    def fwd(s, x, scale, bias, *_):
        return _gate(apply(x * s.astype(x.dtype)))

    def train(s, x, scale, bias, r):
        g = jax.grad(lambda x: jnp.sum(
            apply(x).astype(jnp.float32)
            * r.astype(jnp.float32)))(x * s.astype(x.dtype))
        return _gate(g)
    return fwd, train


def nn_relu(x):
    return jnp.maximum(x, 0)


def add_steps():
    def fwd(s, x, y, *_):
        return _gate(nn_relu(x * s.astype(x.dtype) + y))

    def train(s, x, y, r):
        g = jax.grad(lambda x: jnp.sum(
            nn_relu(x + y).astype(jnp.float32) * r))(
                x * s.astype(x.dtype))
        return _gate(g)
    return fwd, train


# ---------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--image", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes on CPU (CI sanity, not a roofline)")
    args = ap.parse_args()
    from distkeras_tpu.profiling import enable_compile_cache, require_tpu

    on_tpu = not args.smoke
    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    else:
        enable_compile_cache()
        require_tpu()
    batch = args.batch or (256 if on_tpu else 2)
    image = args.image or (224 if on_tpu else 64)
    reps = 3 if on_tpu else 1
    target = 250.0 if on_tpu else 5.0
    key = jax.random.key(0)

    rows = []

    probe = jnp.float32(1.0)  # scalar carry for the non-conv chains

    def measure(name, count, fwd_spec, train_spec, flops_fwd,
                bytes_fwd, bytes_train, train_overhead_ms=0.0):
        (step_fwd, c_fwd, rest_fwd) = fwd_spec
        (step_train, c_train, rest_train) = train_spec
        est_fwd = max(flops_fwd / PEAK, bytes_fwd / BW) * 1e3
        est_train = max(3 * flops_fwd / PEAK, bytes_train / BW) * 1e3
        t_fwd = time_op(step_fwd, c_fwd, rest_fwd, est_fwd, reps,
                        target)
        t_train = time_op(step_train, c_train, rest_train, est_train,
                          reps, target)
        t_corr = t_train - train_overhead_ms * 1e-3
        clamped = t_corr < t_fwd
        if clamped:
            # the analytic scaffold subtraction over-shot (XLA fused
            # the dout materialization away for this shape): flag it
            # rather than silently reporting a free backward
            print(f"    [clamp] {name}: corrected train "
                  f"{t_corr*1e3:.3f} < fwd — clamped to fwd",
                  flush=True)
        t_train = max(t_corr, t_fwd)
        rows.append({
            "name": name, "count": count,
            "fwd_ms": t_fwd * 1e3, "train_ms": t_train * 1e3,
            "flops_fwd": flops_fwd,
            "bound_fwd_ms": max(flops_fwd / PEAK,
                                bytes_fwd / BW) * 1e3,
            "bound_train_ms": max(3 * flops_fwd / PEAK,
                                  bytes_train / BW) * 1e3,
        })
        print(f"  {name:38s} x{count:2d}  fwd {t_fwd*1e3:7.3f} ms  "
              f"train {t_train*1e3:7.3f} ms", flush=True)

    print(f"[roofline] conv classes (b{batch}, {image}px, bf16)",
          flush=True)
    for name, count, h, cin, k, stride, cout in conv_inventory(image):
        ho = h // stride
        x = jax.random.normal(key, (batch, h, h, cin), jnp.bfloat16)
        w = jax.random.normal(key, (k, k, cin, cout),
                              jnp.bfloat16) * 0.05
        r = jax.random.normal(key, (batch, ho, ho, cout),
                              jnp.bfloat16)
        flops = 2.0 * batch * ho * ho * cout * k * k * cin
        b_in = x.size * 2
        b_w = w.size * 2
        b_out = batch * ho * ho * cout * 2
        bytes_fwd = b_in + b_w + b_out
        # dgrad: read dout+w, write dx; wgrad: read x+dout, write dw
        bytes_train = bytes_fwd + (b_out + b_w + b_in) \
            + (b_in + b_out + b_w)
        # the quadratic-loss scaffold re-reads out and writes dout —
        # traffic the model's own backward does NOT pay (its dout
        # arrives as the next op's cotangent, and the r read stands in
        # for exactly that) — subtract it analytically
        overhead_ms = 2 * b_out / BW * 1e3
        measure(name, count,
                (conv_fwd_step(stride), w, (x,)),
                (conv_train_step(stride), w, (x, r)), flops,
                bytes_fwd, bytes_train, train_overhead_ms=overhead_ms)

    print("[roofline] norm / elementwise classes", flush=True)
    for name, count, h, c in norm_inventory(image):
        x = jax.random.normal(key, (batch, h, h, c), jnp.bfloat16)
        nbytes = x.size * 2
        r = jax.random.normal(key, x.shape, jnp.bfloat16)
        if name.startswith("add"):
            y = jax.random.normal(key, x.shape, jnp.bfloat16)
            fwd, train = add_steps()
            op_args = (x, y, r)
            bytes_fwd, bytes_train = 3 * nbytes, 3 * nbytes + 2 * nbytes
            flops = x.size * 2.0
        else:
            scale = jnp.ones((c,), jnp.float32)
            bias = jnp.zeros((c,), jnp.float32)
            fwd, train = gn_steps(c, x, scale, bias)
            op_args = (x, scale, bias, r)
            # one stats read-pass + one normalize read+write pass
            bytes_fwd = 3 * nbytes
            bytes_train = bytes_fwd + 3 * nbytes
            flops = x.size * 8.0
        measure(name, count, (fwd, probe, op_args),
                (train, probe, op_args), flops, bytes_fwd,
                bytes_train)

    # tail: maxpool, global mean, dense+loss — measured as one class
    print("[roofline] tail (pool/dense/loss)", flush=True)
    s = image // 2
    xs = jax.random.normal(key, (batch, s, s, 64), jnp.bfloat16)
    rp = jax.random.normal(key, (batch, s // 2, s // 2, 64),
                           jnp.bfloat16)
    pool_fwd = lambda g, x, rp: _gate(lax.reduce_window(  # noqa: E731
        x * g.astype(x.dtype), -jnp.inf, lax.max,
        (1, 3, 3, 1), (1, 2, 2, 1), "SAME"))
    pool_train = lambda g, x, rp: _gate(  # noqa: E731
        jax.grad(lambda x: jnp.sum(
            lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
            .astype(jnp.float32) * rp))(x * g.astype(x.dtype)))
    measure("maxpool 3x3/s2 @stem", 1,
            (pool_fwd, probe, (xs, rp)),
            (pool_train, probe, (xs, rp)),
            xs.size * 9.0, xs.size * 2 * 1.25,
            xs.size * 2 * 2.5)
    xf = jax.random.normal(key, (batch, image // 32, image // 32, 2048),
                           jnp.bfloat16)
    wd = jax.random.normal(key, (2048, 1000), jnp.float32) * 0.02

    def head_fwd(g, x, w):
        pooled = jnp.mean(x * g.astype(x.dtype), axis=(1, 2))
        return _gate(pooled.astype(jnp.float32) @ w)

    def head_train(g, x, w):
        def loss(x, w):
            pooled = jnp.mean(x, axis=(1, 2)).astype(jnp.float32)
            return jnp.sum(jax.nn.log_softmax(pooled @ w))
        gx, gw = jax.grad(loss, (0, 1))(x * g.astype(x.dtype), w)
        return _gate(gx) * _gate(gw)

    measure("meanpool+dense+loss", 1,
            (head_fwd, probe, (xf, wd)),
            (head_train, probe, (xf, wd)),
            2.0 * batch * 2048 * 1000, xf.size * 2 + wd.size * 4,
            (xf.size * 2 + wd.size * 4) * 3)

    # ---- reconcile against the whole step --------------------------
    tot_fwd = sum(r["fwd_ms"] * r["count"] for r in rows)
    tot_train = sum(r["train_ms"] * r["count"] for r in rows)
    bound_train = sum(r["bound_train_ms"] * r["count"] for r in rows)
    def bucket(r):
        if "gn" in r["name"] or "add" in r["name"]:
            return "norm"
        if "pool" in r["name"] or "dense" in r["name"]:
            return "tail"
        return "conv"

    conv_train = sum(r["train_ms"] * r["count"] for r in rows
                     if bucket(r) == "conv")
    tail_train = sum(r["train_ms"] * r["count"] for r in rows
                     if bucket(r) == "tail")
    norm_train = tot_train - conv_train - tail_train

    from distkeras_tpu.models import ResNet50
    from distkeras_tpu.profiling import (resnet50_model_flops,
                                         time_step_chain)
    from distkeras_tpu.workers import (TrainState, make_train_step,
                                       resolve_optimizer)

    model = ResNet50(num_classes=1000 if on_tpu else 10,
                     stem="space_to_depth")
    tx = resolve_optimizer("momentum", 0.1)
    x = jnp.ones((batch, image, image, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x[:2])
    state = TrainState.create(variables, tx, jax.random.key(1))
    step = make_train_step(model, "categorical_crossentropy", tx)
    batch_dict = {"features": x,
                  "label": jnp.zeros((batch,), jnp.int32)}
    jit_step = jax.jit(step, donate_argnums=0)
    dt, _ = time_step_chain(jit_step, state, batch_dict,
                            n=20 if on_tpu else 2)
    step_ms = dt * 1e3
    mfu = (resnet50_model_flops(batch, image) / dt / PEAK
           if on_tpu else None)

    print("\n| op class | n | fwd ms | train ms | roofline train ms | "
          "roofline util |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        util = r["bound_train_ms"] / r["train_ms"]
        print(f"| {r['name']} | {r['count']} | {r['fwd_ms']:.3f} | "
              f"{r['train_ms']:.3f} | {r['bound_train_ms']:.3f} | "
              f"{util:.2f} |")
    print(f"\nsum fwd {tot_fwd:.1f} ms, sum train {tot_train:.1f} ms "
          f"(conv {conv_train:.1f} + norm/elt {norm_train:.1f} + "
          f"pool/head {tail_train:.1f}); "
          f"roofline-bound sum {bound_train:.1f} ms")
    print(f"measured full step {step_ms:.1f} ms"
          + (f", MFU {mfu:.4f}" if mfu else ""))
    print(json.dumps({
        "metric": "resnet50_roofline",
        "batch": batch, "image": image,
        "sum_op_train_ms": round(tot_train, 2),
        "sum_op_conv_ms": round(conv_train, 2),
        "sum_op_norm_elt_ms": round(norm_train, 2),
        "sum_op_tail_ms": round(tail_train, 2),
        "roofline_bound_ms": round(bound_train, 2),
        "full_step_ms": round(step_ms, 2),
        "mfu": round(mfu, 4) if mfu else None,
    }))


if __name__ == "__main__":
    main()
