"""Microbenchmark: fused Pallas bottleneck kernels vs the XLA chains
they replace, at each ResNet-50 b256 stage geometry (PERF.md §11).

Compares, per stage:
  A: relu(gn(conv1x1(x)))            — fused_conv1x1_gn vs XLA chain
  B: relu(gn(conv1x1(relu(gn(y2)))) + res)
                                     — fused_bottleneck_tail vs XLA chain
each as forward-only and as a full VJP (sum-loss gradient).

Methodology: per-dispatch timing is useless here — an executable
launch costs host time of the order of the ops themselves.  Each
measurement therefore runs a
K-step ``lax.scan`` chain inside ONE jit, with a scalar carry
perturbing the weights (op A) or the input (op B) so XLA cannot hoist
or CSE the repeated computation, and reports wall/K.  For op B the
input perturbation adds one full R+W of y2 per iteration to BOTH arms
(equal absolute cost, so it dilutes — never inflates — the reported
speedup).

Usage:  PYTHONPATH=/root/repo python scripts/perf_fused.py

CAVEAT (July 2026, not re-checked on the current machine): the K-step
scan chains wrapping the Pallas custom-VJP calls compiled for >10
minutes without completing (plain per-dispatch jits of the same ops
compile in seconds).  The measurement that decided the fusion question
is the END-TO-END A/B in ``perf_fused_e2e.py`` (full train step,
100+ ms, dispatch amortized) — PERF.md Findings.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops.fused_block import (fused_bottleneck_tail,
                                           fused_conv1x1_gn)
from distkeras_tpu.ops.pallas_kernels import group_norm_reference
from distkeras_tpu.profiling import enable_compile_cache, host_sync


def chain(f, perturb_idx, args, k):
    """jit(scan): run ``f(*args)`` k times, carry a scalar from each
    output into a tiny perturbation of ``args[perturb_idx]`` so every
    iteration depends on the previous one."""

    def body(c, _):
        a = list(args)
        a[perturb_idx] = a[perturb_idx] + c.astype(a[perturb_idx].dtype)
        out = f(*a)
        leaf = out[0] if isinstance(out, tuple) else out
        return (leaf.ravel()[0].astype(jnp.float32) * 1e-20), None

    def run():
        c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=k)
        return c

    return jax.jit(run)


def timed_chain(f, perturb_idx, args, k=8, reps=3):
    fn = chain(f, perturb_idx, args, k)
    host_sync(fn())
    host_sync(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    host_sync(out)
    return (time.perf_counter() - t0) / (reps * k)


def xla_gn(y, gamma, beta, groups, relu):
    """The flax-equivalent GN lowering (E[x^2]-E[x]^2 one-pass stats,
    f32 math, bf16 out) — what the unfused model runs."""
    return group_norm_reference(y, gamma, beta, groups=groups,
                                relu=relu)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--only", type=str, default="",
                    help="comma-separated geometry-name filter "
                         "(substring match), e.g. 's1,s2'")
    args = ap.parse_args()
    n = args.batch
    rng = np.random.default_rng(0)

    stages = {
        "s1.op1": (3136, 256, 64),
        "s2.op1": (784, 512, 128),
        "s3.op1": (196, 1024, 256),
        "s4.op1": (49, 2048, 512),
        "s1.tail": (3136, 64, 256),
        "s2.tail": (784, 128, 512),
        "s3.tail": (196, 256, 1024),
        "s4.tail": (49, 512, 2048),
    }

    wanted = [s for s in args.only.split(",") if s]
    for name, (hw, cin, cout) in stages.items():
        if wanted and not any(s in name for s in wanted):
            continue
        g = 32
        x = jnp.asarray(rng.normal(size=(n, hw, cin)), jnp.bfloat16)
        w = jnp.asarray(rng.normal(size=(cin, cout)) * 0.05,
                        jnp.bfloat16)
        gamma = jnp.ones((cout,), jnp.float32)
        beta = jnp.zeros((cout,), jnp.float32)
        if name.endswith("op1"):
            def fused(x, w, gamma, beta):
                return fused_conv1x1_gn(x, w, gamma, beta, groups=g)

            def xla(x, w, gamma, beta):
                y = jnp.dot(x, w, preferred_element_type=jnp.float32)
                return xla_gn(y.astype(jnp.bfloat16), gamma, beta, g,
                              True)

            fa = (x, w, gamma, beta)
            pidx = 1  # perturb w: nothing is loop-invariant in either arm
        else:
            g2 = jnp.ones((cin,), jnp.float32)
            b2 = jnp.zeros((cin,), jnp.float32)
            res = jnp.asarray(rng.normal(size=(n, hw, cout)),
                              jnp.bfloat16)

            def fused(x, w, g2, b2, gamma, beta, res):
                return fused_bottleneck_tail(x, w, g2, b2, gamma, beta,
                                             res, groups2=g, groups3=g)

            def xla(x, w, g2, b2, gamma, beta, res):
                h = xla_gn(x, g2, b2, g, True)
                y = jnp.dot(h, w, preferred_element_type=jnp.float32)
                z = xla_gn(y.astype(jnp.bfloat16), gamma, beta, g,
                           False)
                return jnp.maximum(z + res.astype(z.dtype), 0)

            fa = (x, w, g2, b2, gamma, beta, res)
            pidx = 0  # perturb y2: equal extra R+W in both arms

        res_row = {"geom": name,
                   "shape": f"[{n},{hw},{cin}]x[{cin},{cout}]"}
        for tag, f in (("fused", fused), ("xla", xla)):
            grad = jax.grad(
                lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
                argnums=tuple(range(len(fa))))
            res_row[f"{tag}_fwd_ms"] = round(timed_chain(
                f, pidx, fa, k=args.k, reps=args.reps) * 1e3, 3)
            res_row[f"{tag}_vjp_ms"] = round(timed_chain(
                grad, pidx, fa, k=args.k, reps=args.reps) * 1e3, 3)
        res_row["fwd_speedup"] = round(
            res_row["xla_fwd_ms"] / res_row["fused_fwd_ms"], 2)
        res_row["vjp_speedup"] = round(
            res_row["xla_vjp_ms"] / res_row["fused_vjp_ms"], 2)
        print(json.dumps(res_row), flush=True)


if __name__ == "__main__":
    main()
