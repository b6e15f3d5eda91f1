"""Block-granular fused Pallas kernels for the ResNet bottleneck.

PERF.md §4's post-mortem on the standalone GroupNorm kernel: on TPU you
beat the fusion *boundary*, not the op — a custom call that replaces one
op severs XLA's conv↔norm↔relu fusion clusters on both sides and loses.
These kernels therefore own a whole block region, so there is nothing
left at the boundary to sever:

``fused_conv1x1_gn``
    ``y = [relu](gn(x @ w))`` — a 1x1 convolution (spatially pointwise,
    so a plain matmul over ``[H*W, C]``) with GroupNorm statistics,
    affine, and optional ReLU computed while the sample's activations
    are resident in VMEM.  One HBM read of ``x``, one HBM write of
    ``y`` — versus conv-write + stats-read + normalize-read/write when
    the norm is a separate XLA cluster.  Covers the bottleneck's first
    1x1 conv and the downsample projection (``relu=False``).

``fused_bottleneck_tail``
    ``out = relu(gn3(relu(gn2(y2)) @ w3) + residual)`` — absorbs the
    3x3 conv's GroupNorm, the second 1x1 conv, its GroupNorm, the
    residual add, and the final ReLU in one pass: reads ``y2`` (the raw
    3x3-conv output) and ``residual`` once, writes ``out`` once.

Backward passes are hand-written kernels (``jax.custom_vjp``) that
RECOMPUTE the forward intermediates from the saved inputs inside VMEM
instead of materializing them to HBM: in this bandwidth-bound regime
(PERF.md §3: ResNet-50 on v5e sits at an arithmetic intensity well
below the chip's peak ratio) an extra MXU matmul is cheaper than an
extra HBM traversal.

Per-group reductions use the ``[C, G]`` 0/1 mask-matmul trick from
``pallas_kernels.py`` (lane-dimension reshapes lower poorly in Mosaic).
Grid is one sample per step — GroupNorm statistics are per-sample, so
the sample axis is embarrassingly parallel and Pallas double-buffers
the HBM↔VMEM streams across grid steps.

No counterpart in the reference: it has no kernel layer (SURVEY.md §1
— Keras/Theano supplied compute).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.pallas_kernels import _group_mask

# Whole-sample blocks at ResNet-50 stage 1 ([3136, 256] f32
# intermediates, several live at once in the tail backward) need more
# than the default 16 MB scoped-VMEM budget; v5e has 128 MB.
_VMEM_LIMIT = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)


def _gn_stats(y, mask, count, eps):
    """Per-group mean / inverse-stddev of ``y`` [HW, C] via the [C, G]
    group mask; returns channel-broadcast ``(mean_c, inv_c)`` [1, C]."""
    s1 = jnp.sum(y, axis=0, keepdims=True)          # [1, C]
    s2 = jnp.sum(y * y, axis=0, keepdims=True)      # [1, C]
    g1 = jnp.dot(s1, mask, preferred_element_type=jnp.float32) / count
    g2 = jnp.dot(s2, mask, preferred_element_type=jnp.float32) / count
    var = jnp.maximum(g2 - g1 * g1, 0.0)
    inv = jax.lax.rsqrt(var + eps)                  # [1, G]
    mean_c = jnp.dot(g1, mask.T, preferred_element_type=jnp.float32)
    inv_c = jnp.dot(inv, mask.T, preferred_element_type=jnp.float32)
    return mean_c, inv_c


def _gn_bwd(dz, xhat, gamma, mask, count, inv_c):
    """Standard GroupNorm VJP: cotangent w.r.t. the raw (pre-norm)
    tensor, plus per-channel dgamma/dbeta rows.  All [HW, C] f32."""
    dgamma = jnp.sum(dz * xhat, axis=0, keepdims=True)   # [1, C]
    dbeta = jnp.sum(dz, axis=0, keepdims=True)           # [1, C]
    dzg = dz * gamma                                      # [HW, C]
    t1 = jnp.dot(jnp.sum(dzg, axis=0, keepdims=True), mask,
                 preferred_element_type=jnp.float32)      # [1, G]
    t2 = jnp.dot(jnp.sum(dzg * xhat, axis=0, keepdims=True), mask,
                 preferred_element_type=jnp.float32)      # [1, G]
    t1_c = jnp.dot(t1, mask.T, preferred_element_type=jnp.float32)
    t2_c = jnp.dot(t2, mask.T, preferred_element_type=jnp.float32)
    dy_raw = inv_c * (dzg - t1_c / count - xhat * (t2_c / count))
    return dy_raw, dgamma, dbeta


# ---------------------------------------------------------------------------
# Kernel A: y = [relu](gn(x @ w))
# ---------------------------------------------------------------------------


def _conv_gn_fwd_kernel(x_ref, w_ref, gamma_ref, beta_ref, mask_ref,
                        y_ref, *, eps, relu, count):
    x = x_ref[0]                                          # [HW, Cin] bf16
    y = jnp.dot(x, w_ref[:], preferred_element_type=jnp.float32)
    mean_c, inv_c = _gn_stats(y, mask_ref[:], count, eps)
    out = (y - mean_c) * inv_c * gamma_ref[:] + beta_ref[:]
    if relu:
        out = jnp.maximum(out, 0.0)
    y_ref[0] = out.astype(y_ref.dtype)


def _conv_gn_bwd_kernel(x_ref, w_ref, gamma_ref, beta_ref, mask_ref,
                        dy_ref, dx_ref, dw_ref, dgamma_ref, dbeta_ref,
                        *, eps, relu, count):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        dgamma_ref[:] = jnp.zeros_like(dgamma_ref)
        dbeta_ref[:] = jnp.zeros_like(dbeta_ref)

    x = x_ref[0]                                          # [HW, Cin]
    w = w_ref[:]
    mask = mask_ref[:]
    gamma = gamma_ref[:]
    dz = dy_ref[0].astype(jnp.float32)                    # [HW, Cout]
    # recompute the forward in VMEM (cheaper than an HBM round-trip)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    mean_c, inv_c = _gn_stats(y, mask, count, eps)
    xhat = (y - mean_c) * inv_c
    if relu:
        z = xhat * gamma + beta_ref[:]
        dz = jnp.where(z > 0, dz, 0.0)
    dy_raw, dgamma, dbeta = _gn_bwd(dz, xhat, gamma, mask, count, inv_c)
    dgamma_ref[:] += dgamma
    dbeta_ref[:] += dbeta
    dy_b = dy_raw.astype(x.dtype)
    dx_ref[0] = jnp.dot(dy_b, w.T,
                        preferred_element_type=jnp.float32
                        ).astype(dx_ref.dtype)
    dw_ref[:] += jnp.dot(x.T, dy_b,
                         preferred_element_type=jnp.float32)


def _row_spec(c):
    return pl.BlockSpec((1, c), lambda i: (0, 0), memory_space=pltpu.VMEM)


def _mat_spec(r, c):
    return pl.BlockSpec((r, c), lambda i: (0, 0), memory_space=pltpu.VMEM)


def _sample_spec(hw, c):
    return pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _conv_gn(x3, w, gamma, beta, groups, eps, relu, interpret):
    b, hw, cin = x3.shape
    cout = w.shape[1]
    mask = jnp.asarray(_group_mask(cout, groups))
    kernel = functools.partial(_conv_gn_fwd_kernel, eps=eps, relu=relu,
                               count=float(hw * (cout // groups)))
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[_sample_spec(hw, cin), _mat_spec(cin, cout),
                  _row_spec(cout), _row_spec(cout),
                  _mat_spec(cout, groups)],
        out_specs=_sample_spec(hw, cout),
        out_shape=jax.ShapeDtypeStruct((b, hw, cout), x3.dtype),
        compiler_params=None if interpret else _VMEM_LIMIT,
        interpret=interpret,
    )(x3, w, gamma, beta, mask)


def _conv_gn_fwd(x3, w, gamma, beta, groups, eps, relu, interpret):
    y = _conv_gn(x3, w, gamma, beta, groups, eps, relu, interpret)
    return y, (x3, w, gamma, beta)


def _conv_gn_bwd(groups, eps, relu, interpret, res, dy):
    x3, w, gamma, beta = res
    b, hw, cin = x3.shape
    cout = w.shape[1]
    mask = jnp.asarray(_group_mask(cout, groups))
    kernel = functools.partial(_conv_gn_bwd_kernel, eps=eps, relu=relu,
                               count=float(hw * (cout // groups)))
    dx, dw, dgamma, dbeta = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[_sample_spec(hw, cin), _mat_spec(cin, cout),
                  _row_spec(cout), _row_spec(cout),
                  _mat_spec(cout, groups), _sample_spec(hw, cout)],
        out_specs=[_sample_spec(hw, cin), _mat_spec(cin, cout),
                   _row_spec(cout), _row_spec(cout)],
        out_shape=[
            jax.ShapeDtypeStruct((b, hw, cin), x3.dtype),
            jax.ShapeDtypeStruct((cin, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
        ],
        compiler_params=None if interpret else _VMEM_LIMIT,
        interpret=interpret,
    )(x3, w, gamma, beta, mask, dy)
    return dx, dw.astype(w.dtype), dgamma.astype(gamma.dtype), \
        dbeta.astype(beta.dtype)


_conv_gn.defvjp(_conv_gn_fwd, _conv_gn_bwd)


def fused_conv1x1_gn(x, w, gamma, beta, *, groups, eps=1e-6, relu=True,
                     interpret=None):
    """1x1-conv + GroupNorm + optional ReLU in one HBM pass.

    ``x``: [N, ..., Cin] (channels last; spatial dims flattened
    internally — a 1x1 conv is pointwise).  ``w``: [Cin, Cout].
    ``gamma``/``beta``: [Cout].  Differentiable in x/w/gamma/beta.
    ``interpret=None`` auto-enables the Pallas interpreter off-TPU.
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    shape = x.shape
    cin = shape[-1]
    b = shape[0]
    hw = int(np.prod(shape[1:-1])) if len(shape) > 2 else 1
    x3 = x.reshape(b, hw, cin)
    y3 = _conv_gn(x3, w, gamma.reshape(1, -1).astype(jnp.float32),
                  beta.reshape(1, -1).astype(jnp.float32),
                  int(groups), float(eps), bool(relu), bool(interpret))
    return y3.reshape(shape[:-1] + (w.shape[1],))


# ---------------------------------------------------------------------------
# Kernel B: out = relu(gn3(relu(gn2(y2)) @ w3) + residual)
# ---------------------------------------------------------------------------


def _tail_fwd_kernel(y2_ref, w_ref, g2_ref, b2_ref, g3_ref, b3_ref,
                     res_ref, mask2_ref, mask3_ref, out_ref, *,
                     eps, count2, count3):
    y2 = y2_ref[0].astype(jnp.float32)                    # [HW, Cm]
    mean2, inv2 = _gn_stats(y2, mask2_ref[:], count2, eps)
    h = jnp.maximum((y2 - mean2) * inv2 * g2_ref[:] + b2_ref[:], 0.0)
    y3 = jnp.dot(h.astype(y2_ref.dtype), w_ref[:],
                 preferred_element_type=jnp.float32)      # [HW, Cout]
    mean3, inv3 = _gn_stats(y3, mask3_ref[:], count3, eps)
    z = (y3 - mean3) * inv3 * g3_ref[:] + b3_ref[:]
    out = jnp.maximum(z + res_ref[0].astype(jnp.float32), 0.0)
    out_ref[0] = out.astype(out_ref.dtype)


def _tail_bwd_kernel(y2_ref, w_ref, g2_ref, b2_ref, g3_ref, b3_ref,
                     res_ref, mask2_ref, mask3_ref, dout_ref,
                     dy2_ref, dw_ref, dg2_ref, db2_ref, dg3_ref,
                     db3_ref, dres_ref, *, eps, count2, count3):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        dg2_ref[:] = jnp.zeros_like(dg2_ref)
        db2_ref[:] = jnp.zeros_like(db2_ref)
        dg3_ref[:] = jnp.zeros_like(dg3_ref)
        db3_ref[:] = jnp.zeros_like(db3_ref)

    w = w_ref[:]
    mask2, mask3 = mask2_ref[:], mask3_ref[:]
    g2, g3 = g2_ref[:], g3_ref[:]
    # recompute the forward chain in VMEM
    y2 = y2_ref[0].astype(jnp.float32)
    mean2, inv2 = _gn_stats(y2, mask2, count2, eps)
    xhat2 = (y2 - mean2) * inv2
    u = xhat2 * g2 + b2_ref[:]
    h = jnp.maximum(u, 0.0)
    hb = h.astype(y2_ref.dtype)
    y3 = jnp.dot(hb, w, preferred_element_type=jnp.float32)
    mean3, inv3 = _gn_stats(y3, mask3, count3, eps)
    xhat3 = (y3 - mean3) * inv3
    z = xhat3 * g3 + b3_ref[:] + res_ref[0].astype(jnp.float32)
    # backward
    dz = jnp.where(z > 0, dout_ref[0].astype(jnp.float32), 0.0)
    dres_ref[0] = dz.astype(dres_ref.dtype)
    dy3, dg3, db3 = _gn_bwd(dz, xhat3, g3, mask3, count3, inv3)
    dg3_ref[:] += dg3
    db3_ref[:] += db3
    dy3_b = dy3.astype(y2_ref.dtype)
    dw_ref[:] += jnp.dot(hb.T, dy3_b,
                         preferred_element_type=jnp.float32)
    dh = jnp.dot(dy3_b, w.T, preferred_element_type=jnp.float32)
    dh = jnp.where(u > 0, dh, 0.0)
    dy2, dg2, db2 = _gn_bwd(dh, xhat2, g2, mask2, count2, inv2)
    dg2_ref[:] += dg2
    db2_ref[:] += db2
    dy2_ref[0] = dy2.astype(dy2_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _tail(y2, w, g2, b2, g3, b3, res, groups2, groups3, eps, interpret):
    b, hw, cm = y2.shape
    cout = w.shape[1]
    mask2 = jnp.asarray(_group_mask(cm, groups2))
    mask3 = jnp.asarray(_group_mask(cout, groups3))
    kernel = functools.partial(
        _tail_fwd_kernel, eps=eps,
        count2=float(hw * (cm // groups2)),
        count3=float(hw * (cout // groups3)))
    return pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[_sample_spec(hw, cm), _mat_spec(cm, cout),
                  _row_spec(cm), _row_spec(cm),
                  _row_spec(cout), _row_spec(cout),
                  _sample_spec(hw, cout),
                  _mat_spec(cm, groups2), _mat_spec(cout, groups3)],
        out_specs=_sample_spec(hw, cout),
        out_shape=jax.ShapeDtypeStruct((b, hw, cout), y2.dtype),
        compiler_params=None if interpret else _VMEM_LIMIT,
        interpret=interpret,
    )(y2, w, g2, b2, g3, b3, res, mask2, mask3)


def _tail_fwd(y2, w, g2, b2, g3, b3, res, groups2, groups3, eps,
              interpret):
    out = _tail(y2, w, g2, b2, g3, b3, res, groups2, groups3, eps,
                interpret)
    return out, (y2, w, g2, b2, g3, b3, res)


def _tail_bwd(groups2, groups3, eps, interpret, saved, dout):
    y2, w, g2, b2, g3, b3, res = saved
    b, hw, cm = y2.shape
    cout = w.shape[1]
    mask2 = jnp.asarray(_group_mask(cm, groups2))
    mask3 = jnp.asarray(_group_mask(cout, groups3))
    kernel = functools.partial(
        _tail_bwd_kernel, eps=eps,
        count2=float(hw * (cm // groups2)),
        count3=float(hw * (cout // groups3)))
    dy2, dw, dg2, db2, dg3, db3, dres = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[_sample_spec(hw, cm), _mat_spec(cm, cout),
                  _row_spec(cm), _row_spec(cm),
                  _row_spec(cout), _row_spec(cout),
                  _sample_spec(hw, cout),
                  _mat_spec(cm, groups2), _mat_spec(cout, groups3),
                  _sample_spec(hw, cout)],
        out_specs=[_sample_spec(hw, cm), _mat_spec(cm, cout),
                   _row_spec(cm), _row_spec(cm),
                   _row_spec(cout), _row_spec(cout),
                   _sample_spec(hw, cout)],
        out_shape=[
            jax.ShapeDtypeStruct((b, hw, cm), y2.dtype),
            jax.ShapeDtypeStruct((cm, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cm), jnp.float32),
            jax.ShapeDtypeStruct((1, cm), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((1, cout), jnp.float32),
            jax.ShapeDtypeStruct((b, hw, cout), res.dtype),
        ],
        compiler_params=None if interpret else _VMEM_LIMIT,
        interpret=interpret,
    )(y2, w, g2, b2, g3, b3, res, mask2, mask3, dout)
    return dy2, dw.astype(w.dtype), dg2.astype(g2.dtype), \
        db2.astype(b2.dtype), dg3.astype(g3.dtype), \
        db3.astype(b3.dtype), dres


_tail.defvjp(_tail_fwd, _tail_bwd)


def fused_bottleneck_tail(y2, w, gamma2, beta2, gamma3, beta3,
                          residual, *, groups2, groups3, eps=1e-6,
                          interpret=None):
    """The bottleneck's tail — ``relu(gn3(relu(gn2(y2)) @ w) + res)`` —
    in one HBM pass.

    ``y2``: [N, ..., Cm] raw 3x3-conv output (pre-norm).  ``w``:
    [Cm, Cout].  ``residual``: [N, ..., Cout].  Differentiable in every
    tensor argument (including the residual).
    """
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    shape = y2.shape
    cm = shape[-1]
    b = shape[0]
    hw = int(np.prod(shape[1:-1])) if len(shape) > 2 else 1
    out3 = _tail(y2.reshape(b, hw, cm), w,
                 gamma2.reshape(1, -1).astype(jnp.float32),
                 beta2.reshape(1, -1).astype(jnp.float32),
                 gamma3.reshape(1, -1).astype(jnp.float32),
                 beta3.reshape(1, -1).astype(jnp.float32),
                 residual.reshape(b, hw, w.shape[1]),
                 int(groups2), int(groups3), float(eps),
                 bool(interpret))
    return out3.reshape(shape[:-1] + (w.shape[1],))


def conv1x1_gn_reference(x, w, gamma, beta, *, groups, eps=1e-6,
                         relu=True):
    """Pure-jnp oracle for ``fused_conv1x1_gn`` (bf16-faithful: matmul
    in the input dtype with f32 accumulation, norm math in f32)."""
    from distkeras_tpu.ops.pallas_kernels import group_norm_reference

    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    out = group_norm_reference(y, gamma, beta, groups=groups, eps=eps,
                               relu=relu)
    return out.astype(x.dtype)


def bottleneck_tail_reference(y2, w, gamma2, beta2, gamma3, beta3,
                              residual, *, groups2, groups3, eps=1e-6):
    """Pure-jnp oracle for ``fused_bottleneck_tail``."""
    from distkeras_tpu.ops.pallas_kernels import group_norm_reference

    h = group_norm_reference(y2.astype(jnp.float32), gamma2, beta2,
                             groups=groups2, eps=eps, relu=True)
    y3 = jnp.dot(h.astype(y2.dtype), w,
                 preferred_element_type=jnp.float32)
    z = group_norm_reference(y3, gamma3, beta3, groups=groups3,
                             eps=eps, relu=False)
    out = jnp.maximum(z + residual.astype(jnp.float32), 0.0)
    return out.astype(y2.dtype)
