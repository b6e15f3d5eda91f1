"""Hand-written Pallas TPU kernels for the hot ops XLA doesn't fuse well.

``fused_group_norm`` — GroupNorm(+ReLU) in ONE pass over HBM.  PERF.md §3
measured GroupNorm at 26% of the flagship ResNet-50 step: XLA lowers
flax's GroupNorm into separate stats/normalize passes over activations
that are far too large for cache (e.g. [256, 112, 112, 64] ≈ 410 MB
bf16), so the tensor crosses HBM several times.  This kernel keeps each
image's activations resident in VMEM: one HBM read, one HBM write, with
the affine transform and optional ReLU fused in.

Layout strategy: activations are processed as ``[HW, C]`` blocks (one
image per grid step).  Per-group statistics use a ``[C, G]`` 0/1
group-mask matrix, so "sum within each group's channels" is a tiny
matmul — no lane-dimension reshapes, which Mosaic lowers poorly; the
spatial reduction is a native sublane reduction.  The backward pass is a
second single-pass kernel (standard GroupNorm VJP algebra, recomputing
x-hat from saved per-group stats), wired via ``jax.custom_vjp``.

No counterpart in the reference: it has no op layer at all (SURVEY.md §1
"no ops/kernel layer" — Keras/Theano supplied kernels).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The fp32 intermediates of a whole-image block exceed the default 16 MB
# scoped-VMEM budget at the ResNet stem ([12544, 64]); v5e has 128 MB of
# VMEM, so grant the kernels a generous slice of it.
_VMEM_LIMIT = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)


def _group_mask(channels: int, groups: int) -> np.ndarray:
    """[C, G] 0/1 matrix: mask[c, g] = 1 iff channel c belongs to group g."""
    if channels % groups:
        raise ValueError(f"channels={channels} not divisible by "
                         f"groups={groups}")
    cg = channels // groups
    mask = np.zeros((channels, groups), np.float32)
    for g in range(groups):
        mask[g * cg:(g + 1) * cg, g] = 1.0
    return mask


def _fwd_kernel(x_ref, gamma_ref, beta_ref, mask_ref, y_ref,
                mean_ref, inv_ref, *, eps: float, relu: bool,
                count: float):
    x = x_ref[0].astype(jnp.float32)                       # [HW, C]
    mask = mask_ref[:]                                     # [C, G]
    s1 = jnp.sum(x, axis=0, keepdims=True)                 # [1, C]
    s2 = jnp.sum(x * x, axis=0, keepdims=True)             # [1, C]
    g1 = jnp.dot(s1, mask, preferred_element_type=jnp.float32)  # [1, G]
    g2 = jnp.dot(s2, mask, preferred_element_type=jnp.float32)  # [1, G]
    mean_g = g1 / count
    var_g = jnp.maximum(g2 / count - mean_g * mean_g, 0.0)
    inv_g = jax.lax.rsqrt(var_g + eps)                     # [1, G]
    # broadcast per-group stats back to channels: [1, G] @ [G, C]
    mean_c = jnp.dot(mean_g, mask.T,
                     preferred_element_type=jnp.float32)   # [1, C]
    inv_c = jnp.dot(inv_g, mask.T,
                    preferred_element_type=jnp.float32)    # [1, C]
    scale = inv_c * gamma_ref[:]                           # [1, C]
    shift = beta_ref[:] - mean_c * scale
    y = x * scale + shift
    if relu:
        y = jnp.maximum(y, 0.0)
    y_ref[0] = y.astype(y_ref.dtype)
    mean_ref[0] = mean_g
    inv_ref[0] = inv_g


def _bwd_kernel(x_ref, dy_ref, gamma_ref, beta_ref, mask_ref,
                mean_ref, inv_ref, dx_ref, dgamma_ref, dbeta_ref, *,
                relu: bool, count: float):
    x = x_ref[0].astype(jnp.float32)                       # [HW, C]
    dy = dy_ref[0].astype(jnp.float32)                     # [HW, C]
    mask = mask_ref[:]                                     # [C, G]
    gamma = gamma_ref[:]                                   # [1, C]
    mean_c = jnp.dot(mean_ref[0], mask.T,
                     preferred_element_type=jnp.float32)   # [1, C]
    inv_c = jnp.dot(inv_ref[0], mask.T,
                    preferred_element_type=jnp.float32)    # [1, C]
    xhat = (x - mean_c) * inv_c                            # [HW, C]
    if relu:
        # recompute the pre-ReLU output's sign to mask the cotangent
        z = xhat * gamma + beta_ref[:]
        dy = jnp.where(z > 0, dy, 0.0)
    dgamma_ref[0] = jnp.sum(dy * xhat, axis=0, keepdims=True)  # [1, C]
    dbeta_ref[0] = jnp.sum(dy, axis=0, keepdims=True)          # [1, C]
    dyg = dy * gamma                                       # [HW, C]
    t1 = jnp.dot(jnp.sum(dyg, axis=0, keepdims=True), mask,
                 preferred_element_type=jnp.float32)       # [1, G]
    t2 = jnp.dot(jnp.sum(dyg * xhat, axis=0, keepdims=True), mask,
                 preferred_element_type=jnp.float32)       # [1, G]
    t1_c = jnp.dot(t1, mask.T, preferred_element_type=jnp.float32)
    t2_c = jnp.dot(t2, mask.T, preferred_element_type=jnp.float32)
    dx = inv_c * (dyg - t1_c / count - xhat * (t2_c / count))
    dx_ref[0] = dx.astype(dx_ref.dtype)


def _fwd_call(x3, gamma, beta, mask, *, eps, relu, interpret):
    b, hw, c = x3.shape
    groups = mask.shape[1]
    count = float(hw * (c // groups))
    kernel = functools.partial(_fwd_kernel, eps=eps, relu=relu,
                               count=count)
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, groups), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, groups), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, groups), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hw, c), x3.dtype),
            jax.ShapeDtypeStruct((b, 1, groups), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, groups), jnp.float32),
        ],
        compiler_params=None if interpret else _VMEM_LIMIT,
        interpret=interpret,
    )(x3, gamma, beta, mask)


def _bwd_call(x3, dy3, gamma, beta, mask, mean, inv, *, relu, interpret):
    b, hw, c = x3.shape
    groups = mask.shape[1]
    count = float(hw * (c // groups))
    kernel = functools.partial(_bwd_kernel, relu=relu, count=count)
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, groups), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, groups), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, groups), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hw, c), x3.dtype),
            jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
        ],
        compiler_params=None if interpret else _VMEM_LIMIT,
        interpret=interpret,
    )(x3, dy3, gamma, beta, mask, mean, inv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _group_norm_3d(x3, gamma, beta, groups, eps, relu, interpret):
    mask = jnp.asarray(_group_mask(x3.shape[-1], groups))
    y, _, _ = _fwd_call(x3, gamma, beta, mask, eps=eps, relu=relu,
                        interpret=interpret)
    return y


def _group_norm_3d_fwd(x3, gamma, beta, groups, eps, relu, interpret):
    mask = jnp.asarray(_group_mask(x3.shape[-1], groups))
    y, mean, inv = _fwd_call(x3, gamma, beta, mask, eps=eps, relu=relu,
                             interpret=interpret)
    return y, (x3, gamma, beta, mask, mean, inv)


def _group_norm_3d_bwd(groups, eps, relu, interpret, residuals, dy):
    x3, gamma, beta, mask, mean, inv = residuals
    dx, dgamma_b, dbeta_b = _bwd_call(
        x3, dy, gamma, beta, mask, mean, inv, relu=relu,
        interpret=interpret)
    dgamma = jnp.sum(dgamma_b, axis=0)  # [B, 1, C] -> [1, C]
    dbeta = jnp.sum(dbeta_b, axis=0)
    return dx, dgamma, dbeta


_group_norm_3d.defvjp(_group_norm_3d_fwd, _group_norm_3d_bwd)


def fused_group_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array, *,
                     groups: int, eps: float = 1e-6, relu: bool = False,
                     interpret: bool | None = None) -> jax.Array:
    """Single-pass GroupNorm with fused affine + optional ReLU.

    ``x``: [B, ..., C] (any number of spatial dims, channels last).
    ``gamma``/``beta``: [C] float32.  Differentiable in x/gamma/beta via
    hand-written backward kernels.  ``interpret`` selects the Pallas
    interpreter; the default (None) auto-enables it off-TPU so the op is
    runnable (slowly) everywhere.
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    shape = x.shape
    c = shape[-1]
    b = shape[0]
    hw = int(np.prod(shape[1:-1])) if len(shape) > 2 else 1
    x3 = x.reshape(b, hw, c)
    gamma2 = gamma.reshape(1, c).astype(jnp.float32)
    beta2 = beta.reshape(1, c).astype(jnp.float32)
    y3 = _group_norm_3d(x3, gamma2, beta2, groups, float(eps), bool(relu),
                        bool(interpret))
    return y3.reshape(shape)


def group_norm_reference(x, gamma, beta, *, groups, eps=1e-6,
                         relu=False):
    """Pure-jnp reference (numerics oracle for the kernel tests)."""
    shape = x.shape
    c = shape[-1]
    xf = x.astype(jnp.float32).reshape(shape[0], -1, groups, c // groups)
    mean = xf.mean(axis=(1, 3), keepdims=True)
    var = xf.var(axis=(1, 3), keepdims=True)
    xhat = ((xf - mean) / jnp.sqrt(var + eps)).reshape(shape)
    y = xhat * gamma.reshape((1,) * (len(shape) - 1) + (c,)) \
        + beta.reshape((1,) * (len(shape) - 1) + (c,))
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)
