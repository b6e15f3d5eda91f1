"""Serialization and pytree helpers.

TPU-native re-design of the reference's ``distkeras/utils.py`` (see SURVEY.md
§2.1 "Utils": ``serialize_keras_model`` / ``deserialize_keras_model``,
``to_dense_vector``, row helpers).  Where the reference pickles a Keras
architecture-JSON + weight list, we serialize a flax module *config* + a
msgpack-encoded parameter pytree — no pickle on the wire, no Python-object
execution on deserialize.
"""

from __future__ import annotations

import json
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization as flax_serialization

Pytree = Any

# ---------------------------------------------------------------------------
# Pytree arithmetic helpers.
#
# The async parameter-server family (SURVEY.md §2.1, parameter_servers.py)
# operates on whole weight sets: delta = weights - last_pulled,
# center += delta, etc.  We express those as pure pytree ops so update rules
# stay jittable and unit-testable.
# ---------------------------------------------------------------------------


def _host_leaf(x) -> bool:
    """True when ``x`` lives on the host as a plain numpy array (no
    tracer, no device array, no python scalar).  The host PS path runs
    these tree ops eagerly at ResNet scale, where one eager jax
    dispatch per leaf costs far more than the numpy op it wraps; numpy
    also keeps the server thread off the device entirely.  Everything
    else keeps the jnp path, so jitted update rules (and the legacy
    promotion semantics for scalars/int leaves) are untouched."""
    return isinstance(x, np.ndarray)


def _float_host(x) -> bool:
    """Host numpy leaf with a float dtype — the only leaves the
    scaled ops (axpy/lerp) take the numpy path for: a leaf-dtype
    scalar coefficient on an INT leaf would truncate (int32(0.5) == 0)
    where the jnp path promotes to float."""
    return isinstance(x, np.ndarray) and x.dtype.kind == "f"


def _binary(np_op, jnp_op):
    def op(x, y):
        if _host_leaf(x) and _host_leaf(y):
            return np_op(x, y)
        return jnp_op(x, y)
    return op


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree_util.tree_map(_binary(np.add, jnp.add), a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree_util.tree_map(_binary(np.subtract, jnp.subtract),
                                  a, b)


def tree_scale(a: Pytree, s) -> Pytree:
    return jax.tree_util.tree_map(lambda x: x * s, a)


def tree_zeros_like(a: Pytree) -> Pytree:
    return jax.tree_util.tree_map(
        lambda x: (np.zeros_like(x) if _host_leaf(x)
                   else jnp.zeros_like(x)), a)


def tree_axpy(alpha, x: Pytree, y: Pytree) -> Pytree:
    """alpha * x + y, elementwise over matching pytrees."""
    # numpy path (float leaves only): a leaf-dtype scalar keeps f32
    # leaves f32 (a bare np.asarray(alpha) would be f64 and promote
    # the whole tree)
    def op(xi, yi):
        if _float_host(xi) and _float_host(yi):
            return xi.dtype.type(alpha) * xi + yi
        return alpha * xi + yi
    return jax.tree_util.tree_map(op, x, y)


def tree_lerp(a: Pytree, b: Pytree, t) -> Pytree:
    """(1 - t) * a + t * b."""
    def op(ai, bi):
        if _float_host(ai) and _float_host(bi):
            return ai.dtype.type(1.0 - t) * ai + ai.dtype.type(t) * bi
        return (1.0 - t) * ai + t * bi
    return jax.tree_util.tree_map(op, a, b)


def tree_dot(a: Pytree, b: Pytree):
    """Sum of elementwise products across the whole pytree (a scalar)."""
    leaves = jax.tree_util.tree_map(lambda x, y: jnp.sum(x * y), a, b)
    return jax.tree_util.tree_reduce(jnp.add, leaves)


def tree_l2_norm(a: Pytree):
    return jnp.sqrt(tree_dot(a, a))


def tree_size(a: Pytree) -> int:
    """Total number of scalar parameters in the pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(a))


def tree_cast(a: Pytree, dtype) -> Pytree:
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), a)


# ---------------------------------------------------------------------------
# Model serialization.
# ---------------------------------------------------------------------------


def serialize_params(params: Pytree) -> bytes:
    """Parameter pytree -> msgpack bytes (flax canonical encoding)."""
    return flax_serialization.to_bytes(params)


def deserialize_params(template: Pytree, data: bytes) -> Pytree:
    """msgpack bytes -> parameter pytree shaped like ``template``."""
    return flax_serialization.from_bytes(template, data)


def serialize_model_config(config: Mapping[str, Any]) -> str:
    """Architecture config dict -> JSON (the analogue of Keras to_json())."""
    return json.dumps(config, sort_keys=True)


def deserialize_model_config(payload: str) -> dict:
    return json.loads(payload)


# ---------------------------------------------------------------------------
# Label / feature helpers (reference: utils.to_dense_vector, new_dataframe_row).
# ---------------------------------------------------------------------------


def to_dense_vector(label, num_classes: int) -> np.ndarray:
    """Integer label(s) -> one-hot float32 vector(s)."""
    label = np.asarray(label, dtype=np.int32)
    if label.size and (label.min() < 0 or label.max() >= num_classes):
        raise ValueError(
            f"labels must be in [0, {num_classes}), got range "
            f"[{label.min()}, {label.max()}]")
    return np.eye(num_classes, dtype=np.float32)[label]


def shuffle(arrays: Mapping[str, np.ndarray], seed: int = 0) -> dict:
    """Shuffle a column dict in unison (reference: utils.shuffle(df))."""
    n = len(next(iter(arrays.values())))
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    return {k: np.asarray(v)[perm] for k, v in arrays.items()}


def batch_iterator(arrays: Mapping[str, np.ndarray], batch_size: int,
                   drop_remainder: bool = True):
    """Yield dicts of aligned batches from a column dict."""
    n = len(next(iter(arrays.values())))
    stop = (n // batch_size) * batch_size if drop_remainder else n
    for start in range(0, stop, batch_size):
        yield {k: v[start:start + batch_size] for k, v in arrays.items()}


def pad_to_multiple(x: np.ndarray, multiple: int, axis: int = 0) -> np.ndarray:
    """Pad ``axis`` up to the next multiple (static shapes for XLA)."""
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x
    pad_width = [(0, 0)] * x.ndim
    pad_width[axis] = (0, rem)
    return np.pad(x, pad_width)
