"""Expert parallelism: a top-k gated MoE layer over a mesh axis.

Beyond the reference (SURVEY.md §2.3: "Expert parallelism: NO") —
the last of the five parallelism forms (dp/tp/sp/pp/ep).  Experts'
FFN parameters are sharded over the ``expert`` mesh axis; tokens are
routed with the einsum dispatch/combine formulation (Shazeer et al.'s
Mesh-TF layout — ``top_k=1`` is the Switch layer, ``top_k=2`` the
GShard-style router) and exchanged with ``lax.all_to_all`` over ICI:

1. router: per-token logits over all E experts, top-k gates;
2. dispatch einsum builds ``[E, C, d]`` capacity-bucketed inputs;
3. ``all_to_all`` turns token-sharding into expert-sharding — each
   device receives ITS experts' buckets from every device;
4. the local experts' FFNs run (vmapped);
5. a reverse ``all_to_all`` + combine einsum returns gated outputs to
   the tokens' home devices.

Tokens over a full expert's capacity ``C = ceil(T_local/E *
capacity_factor)`` are dropped (standard Switch behavior; the gate
residual keeps training stable) and reported via the aux outputs,
along with the load-balancing auxiliary loss from the Switch paper.

SPMD: call inside ``jax.shard_map`` with tokens sharded over
``axis_name`` and ``params`` sharded on their leading (expert) axis.
Differentiable end to end (autodiff reverses the all_to_alls).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class MoEParams(NamedTuple):
    """``router``: [d, E] (replicated).  ``w_in``: [E_local, d, h],
    ``b_in``: [E_local, h], ``w_out``: [E_local, h, d], ``b_out``:
    [E_local, d] — leading axis sharded over the expert mesh axis."""

    router: jax.Array
    w_in: jax.Array
    b_in: jax.Array
    w_out: jax.Array
    b_out: jax.Array


def init_moe_params(rng: jax.Array, d_model: int, d_hidden: int,
                    num_experts: int) -> MoEParams:
    """Global (unsharded) parameters; shard leading expert axes over
    the mesh axis when placing them."""
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_hidden)
    return MoEParams(
        router=jax.random.normal(k1, (d_model, num_experts)) * s_in,
        w_in=jax.random.normal(
            k2, (num_experts, d_model, d_hidden)) * s_in,
        b_in=jnp.zeros((num_experts, d_hidden)),
        w_out=jax.random.normal(
            k3, (num_experts, d_hidden, d_model)) * s_out,
        b_out=jnp.zeros((num_experts, d_model)),
    )


def moe_pspecs(axis: str = "expert") -> "MoEParams":
    """The ``shard_map`` in_specs for ``MoEParams``: router replicated,
    every expert stack sharded on its leading axis.  One definition so
    call sites can't drift from the field order."""
    from jax.sharding import PartitionSpec as P

    return MoEParams(P(), P(axis), P(axis), P(axis), P(axis))


class MoEAux(NamedTuple):
    load_balance_loss: jax.Array  # scalar; add (scaled) to the loss
    dropped_fraction: jax.Array   # scalar in [0, 1]


def expert_capacity(num_tokens: int, num_experts: int,
                    capacity_factor: float, top_k: int = 1) -> int:
    """Per-expert bucket size: ``ceil(T * k * factor / E)``, min 1 —
    the one capacity policy shared by ``moe_apply`` and the model-zoo
    ``MoEFFN``."""
    return max(1, math.ceil(
        num_tokens * top_k * capacity_factor / num_experts))


def routing(x, router, num_experts, capacity, top_k=1):
    """Top-k dispatch/combine tensors ([T, E, C]) + aux telemetry.

    ``top_k=1`` is the Switch layer; ``top_k=2`` the GShard-style
    routing (gates renormalized over the chosen experts; later choices
    fill capacity after earlier ones, so a token's second expert is
    dropped before its first).

    All bookkeeping runs in f32 regardless of ``x.dtype``: bf16 cumsum
    loses integer exactness past 256, which would assign two tokens the
    same capacity slot and silently merge their embeddings.  Only the
    final dispatch/combine tensors are cast back."""
    t = x.shape[0]
    logits = (x.astype(jnp.float32)
              @ router.astype(jnp.float32))      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, top_k)       # [T, k]
    # Switch (k=1) gates with the raw probability; GShard (k>1)
    # renormalizes over the chosen experts.
    gates = (top_p if top_k == 1
             else top_p / top_p.sum(axis=-1, keepdims=True))
    dispatch = jnp.zeros((t, num_experts, capacity), jnp.float32)
    combine = jnp.zeros((t, num_experts, capacity), jnp.float32)
    counts = jnp.zeros((num_experts,), jnp.float32)  # filled slots
    kept = jnp.float32(0.0)
    mask1 = None  # the j=0 mask, reused for the aux loss
    for j in range(top_k):  # static, tiny k
        mask = jax.nn.one_hot(top_i[:, j], num_experts,
                              dtype=jnp.float32)  # [T, E]
        if j == 0:
            mask1 = mask
        # position within the expert's bucket, offset by the slots
        # already filled by earlier choices
        pos = ((jnp.cumsum(mask, axis=0) - 1.0)
               + counts[None, :]) * mask
        keep = (pos < capacity).astype(jnp.float32) * mask
        d_j = keep[..., None] * jax.nn.one_hot(
            pos.astype(jnp.int32), capacity,
            dtype=jnp.float32)                   # [T, E, C]
        dispatch = dispatch + d_j
        combine = combine + d_j * gates[:, j][:, None, None]
        counts = counts + keep.sum(axis=0)  # kept only: slots stay dense
        kept = kept + keep.sum()
    # Switch aux loss on the primary choice:
    # E * sum_e( frac_tokens_e * mean_prob_e )
    lb = num_experts * jnp.sum(mask1.mean(axis=0) * probs.mean(axis=0))
    dropped = jnp.clip(1.0 - kept / (t * top_k), 0.0, 1.0)
    return (dispatch.astype(x.dtype), combine.astype(x.dtype),
            MoEAux(lb, dropped))


def moe_apply(params: MoEParams, x: jax.Array, *, axis_name: str,
              capacity_factor: float = 1.25, top_k: int = 1
              ) -> tuple[jax.Array, MoEAux]:
    """Apply the expert-parallel MoE FFN to ``x`` ``[T_local, d]``.

    ``params`` leaves other than ``router`` carry this device's
    ``E_local = E / n_devices`` experts.  ``top_k=1`` is Switch
    routing; ``top_k=2`` GShard-style (renormalized gates over the
    chosen experts).  Returns ``([T_local, d], MoEAux)``; aux values
    are means over the mesh axis.
    """
    n_dev = lax.axis_size(axis_name)
    e_local = params.w_in.shape[0]
    num_experts = e_local * n_dev
    if not 1 <= top_k <= num_experts:
        raise ValueError(
            f"top_k={top_k} out of range [1, {num_experts}]")
    t_local, d = x.shape
    capacity = expert_capacity(t_local, num_experts, capacity_factor,
                               top_k)

    dispatch, combine, aux = routing(x, params.router, num_experts,
                                     capacity, top_k)

    # [T, E, C] -> expert-major input buckets [E, C, d]
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    # Token-sharded -> expert-sharded: split the (global) expert axis
    # across devices, concatenate the senders' buckets on a new axis.
    # [E, C, d] -> [n_dev(senders), E_local, C, d]
    expert_in = expert_in.reshape(n_dev, e_local, capacity, d)
    expert_in = lax.all_to_all(expert_in, axis_name, split_axis=0,
                               concat_axis=0, tiled=False)
    # merge sender x capacity: [E_local, n_dev * C, d]
    expert_in = expert_in.transpose(1, 0, 2, 3).reshape(
        e_local, n_dev * capacity, d)

    def ffn(w_in, b_in, w_out, b_out, h):
        return jax.nn.relu(h @ w_in + b_in) @ w_out + b_out

    expert_out = jax.vmap(ffn)(params.w_in, params.b_in, params.w_out,
                               params.b_out, expert_in)

    # Back to token-sharding: inverse reshape + all_to_all.
    expert_out = expert_out.reshape(
        e_local, n_dev, capacity, d).transpose(1, 0, 2, 3)
    expert_out = lax.all_to_all(expert_out, axis_name, split_axis=0,
                                concat_axis=0, tiled=False)
    expert_out = expert_out.reshape(num_experts, capacity, d)
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out, MoEAux(
        lax.pmean(aux.load_balance_loss, axis_name),
        lax.pmean(aux.dropped_fraction, axis_name))


# ---------------------------------------------------------------------
# Dropless routing: no capacity, no dropped token.  The (token, expert)
# pairs are sorted by expert and each projection is ONE grouped matrix
# product over the experts held (``grouped_matmul``).  The same function
# serves a prefill of thousands of tokens and a decode step of a few
# dozen.  An expert-parallel deployment gives each chip a contiguous
# range of the experts: the layer routes over ALL of them and computes
# the part of the result that the experts it holds give; what the
# absent experts would add is left out (their exchange is not here).
# ---------------------------------------------------------------------

_ROW_TILE = 128               # the MXU's rows: the least a group costs
_WEIGHT_TILE_BYTES = 4 << 20  # one [tk, tn] tile of an expert, twice in VMEM


def _gmm_tiling(k: int, n: int, itemsize: int):
    """``(tm, tk, tn)`` for the grouped-matmul kernel, or None where the
    shapes do not tile (toy widths).  Rows in tiles of 128, so that a
    group of a few rows (a decode step gives an expert four) costs one
    small tile; the whole contraction in one tile (no accumulation
    loop) and as many columns as fit beside it.  On a v5e, against
    XLA's own lowering of ``ragged_dot`` (256- and 512-row tiles) at
    64 experts of 3584 x 2048 and 1024 x 3584: 1.30 against 2.16 ms and
    0.68 against 1.10 ms for a step's 256 rows (724 and 693 GB/s of the
    chip's 819), 2.13 against 3.97 ms and 1.25 against 2.16 ms for a
    2048-token prefill's 8192 rows, the same numbers to the last bit
    (PERF.md section 6, PR 28)."""
    if k % 128 or n % 128 or k * 128 * itemsize > _WEIGHT_TILE_BYTES:
        return None
    tn = max(t for t in range(128, n + 1, 128)
             if n % t == 0 and k * t * itemsize <= _WEIGHT_TILE_BYTES)
    return _ROW_TILE, k, tn


def _gmm(rows, weights, sizes, out_dtype, tiling, interpret=False):
    """The megablox grouped-matmul Mosaic kernel of
    ``jax.experimental.pallas`` over row tiles (``interpret``: for a
    test off the TPU)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m = rows.shape[0]
    pad = -m % _ROW_TILE
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, weights, sizes, preferred_element_type=out_dtype,
              tiling=tiling, interpret=interpret)
    return out[:m] if pad else out


def grouped_matmul(rows, weights, sizes, out_dtype):
    """``rows[start_g : start_g + sizes[g]] @ weights[g]`` for every group
    ``g``: ``rows`` ``[m, k]`` sorted by group, ``weights`` ``[G, k, n]``,
    ``sizes`` ``[G]`` int32; ``[m, n]`` in ``out_dtype``.  Rows past the
    last group hold nothing to read.

    On a TPU this is the megablox kernel with the tiling above;
    elsewhere, and at widths that do not tile, ``jax.lax.ragged_dot``
    (which XLA lowers to a kernel of its own on a TPU, with row tiles
    that a decode step's few rows a group fill badly)."""
    tiling = _gmm_tiling(rows.shape[1], weights.shape[2],
                         rows.dtype.itemsize)
    if tiling is None or jax.devices()[0].platform != "tpu":
        return lax.ragged_dot(rows, weights, sizes,
                              preferred_element_type=out_dtype)
    return _gmm(rows, weights, sizes, out_dtype, tiling)


def sigmoid_topk(x, router, bias, top_k: int, *, normalize: bool = True,
                 scale: float = 1.0, n_group: int = 1, topk_group: int = 1):
    """Bias-corrected sigmoid routing ("noaux_tc").

    ``x`` ``[T, d]``, ``router`` ``[d, E]`` and ``bias`` ``[E]`` are
    taken in float32 and the product runs at full float32 precision,
    so that a rounding of the activations cannot flip a choice.  The
    ``top_k`` experts are chosen by ``sigmoid(x W) + bias``; a chosen
    expert's weight is its score WITHOUT the bias, divided by the sum
    of the chosen scores (``normalize``) and multiplied by ``scale``.
    With ``n_group > 1`` the experts are ``n_group`` contiguous groups
    (DeepSeek-V3's selection): a group scores by the sum of its two best
    biased scores, the best ``topk_group`` groups are kept and the
    ``top_k`` are chosen among their experts alone.
    Returns ``(idx [T, k] int32, weights [T, k] float32)``."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        t, e = choice.shape
        groups = choice.reshape(t, n_group, e // n_group)
        # the two best of each group by two reductions: a top_k of 2
        # lowers to a sort of the whole group on a TPU
        first = jnp.argmax(groups, axis=-1)[..., None]
        second = jnp.where(jnp.arange(e // n_group) == first, -jnp.inf,
                           groups).max(axis=-1)
        group_score = groups.max(axis=-1) + second             # [T, G]
        _, kept = lax.top_k(group_score, topk_group)
        keep = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(keep, e // n_group, axis=1), choice,
                           -jnp.inf)
    _, idx = lax.top_k(choice, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def expert_load(idx, num_experts: int):
    """Tokens routed to each expert: ``[E]`` int32 from ``idx [T, k]``."""
    return jnp.zeros((num_experts,), jnp.int32).at[idx.reshape(-1)].add(1)


def dropless_experts(x, idx, weights, w_in, w_out, *, first: int = 0,
                     limit: float = 0.0):
    """The held experts' part of a routed SwiGLU layer, every
    assignment computed.  A ``limit`` above 0 clamps as gpt-oss does:
    the gate at most ``limit``, the up projection within ``+-limit``.

    ``x`` ``[T, d]``; ``idx``/``weights`` ``[T, k]`` from the router
    (over all experts); ``w_in`` ``[E_held, d, 2 h]`` (gate then up) and
    ``w_out`` ``[E_held, h, d]`` are experts ``first .. first + E_held``.
    Returns ``[T, d]`` in ``x.dtype``: ``sum_i w_i SwiGLU_i(x)`` over a
    token's chosen experts that are held here."""
    t, k = idx.shape
    held = w_in.shape[0]
    h = w_out.shape[1]
    with jax.named_scope("moe_dispatch"):
        local = idx.reshape(-1) - first
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)          # absent ones last
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
        rows = jnp.take(x, order // k, axis=0)      # [T k, d]
    with jax.named_scope("moe_experts"):
        gu = grouped_matmul(rows, w_in, sizes, x.dtype)
        gate, up = gu[:, :h], gu[:, h:]
        if limit:
            gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
        act = (jax.nn.silu(gate) * up).astype(x.dtype)
        y = grouped_matmul(act, w_out, sizes, jnp.float32)
    with jax.named_scope("moe_combine"):
        # rows past the last group belong to no expert held here: what
        # the grouped product leaves there is not read
        w = weights.reshape(-1)[order]
        y = jnp.where(here[order][:, None], y * w[:, None], 0.0)
        back = jnp.argsort(order)
        y = jnp.take(y, back, axis=0).reshape(t, k, -1).sum(axis=1)
    return y.astype(x.dtype)
