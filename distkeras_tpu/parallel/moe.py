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
