"""Pipeline parallelism: GPipe-style microbatching over a mesh axis.

Beyond the reference (SURVEY.md §2.3: "Pipeline parallelism: NO"),
completing the parallelism set (dp / tp / sp / pp / ep) the TPU mesh
makes cheap to express.  Each device on the ``stage`` axis holds ONE
stage's parameters (a homogeneous stack sharded on its leading axis);
activations flow stage-to-stage over ICI with ``lax.ppermute``, one hop
per tick, while microbatches stream in behind each other — the classic
fill-drain (GPipe) schedule with bubble fraction
``(S-1) / (M + S - 1)`` for ``S`` stages and ``M`` microbatches.

This is an SPMD program: every device runs the same tick loop
(``lax.scan``), computing its stage on whatever microbatch currently
occupies it.  Differentiable — autodiff through ``ppermute`` reverses
the ring, so the backward pass is the same pipeline running backwards;
no custom VJP is needed.

Composition: the stage axis composes with the data-parallel axis in the
same mesh (see ``__graft_entry__._dryrun_pipeline_parallel``: a
``(workers, stage)`` mesh with the batch sharded over ``workers``).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

STAGE_AXIS = "stage"


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array, *,
                   axis_name: str, num_microbatches: int) -> jax.Array:
    """Run ``x`` through S pipelined stages under ``shard_map``.

    Args:
      stage_fn: ``(params_one_stage, activation [mb, ...]) ->
        activation [mb, ...]`` — one stage's compute.  Activations must
        keep one shape across stages (homogeneous pipeline).
      stage_params: this device's slice of the stacked stage parameters
        (call under ``shard_map`` with the stack's leading axis sharded
        over ``axis_name``; the leading axis of each leaf here is 1 and
        is squeezed).
      x: this device's copy of the full local batch ``[B, ...]``;
        ``B`` must divide into ``num_microbatches``.
      axis_name: the mesh axis whose size is the number of stages.
      num_microbatches: GPipe microbatch count ``M``; larger M shrinks
        the bubble, smaller M shrinks activation working memory.

    Returns:
      ``[B, ...]`` outputs of the final stage, valid on EVERY device
      (the last stage's results are broadcast with ``psum`` so the
      caller can compute a loss without caring about stage placement).
    """
    n_stages = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[:1] != (1,):
            raise ValueError(
                f"stage_params leaves must arrive with a local leading "
                f"axis of 1 (one stage per device — shard the stack's "
                f"leading axis over {axis_name!r}); got shape "
                f"{leaf.shape} for a {n_stages}-stage pipeline")
    params = jax.tree_util.tree_map(lambda p: p[0], stage_params)

    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(
            f"batch {b} not divisible into {num_microbatches} "
            f"microbatches")
    mb = b // num_microbatches
    micro = x.reshape((num_microbatches, mb) + x.shape[1:])

    n_ticks = num_microbatches + n_stages - 1
    # Ring: stage s sends its output forward to stage s+1 each tick.
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    # Device-varying zeros from tick 0 (scan's carry typing must agree
    # with the computed, varying outputs).
    state0 = lax.pcast(jnp.zeros_like(micro[0]), (axis_name,),
                       to="varying")
    out0 = lax.pcast(jnp.zeros_like(micro), (axis_name,), to="varying")
    # The tick loop: stage 0 ingests microbatch t (while t < M), every
    # stage applies its compute, results hop one stage forward, and the
    # last stage banks microbatch t - (S-1) once the pipe has filled.

    def tick(carry, t):
        state, outs = carry
        feed = micro[jnp.minimum(t, num_microbatches - 1)]
        state = jnp.where(stage == 0, feed, state)
        y = stage_fn(params, state)
        done = t - (n_stages - 1)
        outs = jnp.where(
            (stage == n_stages - 1) & (done >= 0),
            outs.at[jnp.maximum(done, 0)].set(y), outs)
        state = lax.ppermute(y, axis_name, fwd)
        return (state, outs), None

    (_, outs), _ = lax.scan(tick, (state0, out0), jnp.arange(n_ticks))
    # Only the last stage holds real outputs; broadcast them.
    outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
    outs = lax.psum(outs, axis_name)
    return outs.reshape((b,) + outs.shape[2:])


# ---------------------------------------------------------------------------
# Trainer surface: pipelined TransformerLM (VERDICT.md r2 Missing: "PP
# is an op, not a trainer")
# ---------------------------------------------------------------------------


def lm_state_specs(state):
    """PartitionSpec tree for a ``TrainState`` of a
    ``TransformerLM(scan_blocks=True)``: the layer stack (every leaf
    under a ``blocks`` key — optimizer moments mirror the params tree,
    so the rule catches those too) shards its leading (layer) axis over
    the ``stage`` mesh axis; everything else is replicated."""

    def spec_for(path, leaf):
        del leaf
        keys = {getattr(k, "key", getattr(k, "name", None))
                for k in path}
        return P(STAGE_AXIS) if "blocks" in keys else P()

    return jax.tree_util.tree_map_with_path(spec_for, state)


def make_pp_train_step(model, loss_fn, tx, mesh: Mesh, *,
                       num_microbatches: int,
                       workers_axis: str = "workers",
                       features_col: str = "features",
                       label_col: str = "label"):
    """Build a jitted ``step(state, batch) -> (state, metrics)`` that
    trains a ``TransformerLM(scan_blocks=True)`` dp x pp over
    ``mesh = (workers, stage)``.

    Per-device SPMD under ``shard_map``: every device embeds its local
    batch rows (replicated compute along ``stage``), the layer stack —
    sharded ``num_layers/S`` layers per stage — runs through
    ``pipeline_apply``'s GPipe schedule, and the final norm/head/loss
    are computed identically on every stage device from the
    psum-broadcast pipeline output.  Gradient reductions follow the
    replication structure: everything pmean-s over ``workers`` (data
    parallelism); the pre-pipeline embeddings additionally psum over
    ``stage`` (their cotangent lands only on stage 0, which ingests the
    microbatches); the layer stack and the post-pipeline norm/head need
    no stage reduction (stage-local and stage-identical respectively).
    """
    from distkeras_tpu.models.transformer import Block

    cfg = model
    dtype = jnp.dtype(cfg.dtype)

    def forward(params, tokens):
        import flax.linen as nn

        tokens = tokens.astype(jnp.int32)
        t = tokens.shape[1]
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=dtype).apply(
            {"params": params["Embed_0"]}, tokens)
        pos = nn.Embed(cfg.max_len, cfg.d_model, dtype=dtype).apply(
            {"params": params["pos_embed"]},
            jnp.arange(t)[None, :])
        x = x + pos

        def stage_fn(stage_stack, h):
            def body(carry, layer_params):
                out = Block(cfg.num_heads, cfg.mlp_ratio, dtype).apply(
                    {"params": layer_params}, carry)
                return out, None
            h, _ = lax.scan(body, h, stage_stack)
            return h

        # local stack: [L/S, ...] -> leading 1 (pipeline_apply's
        # one-stage-per-device contract)
        stack = jax.tree_util.tree_map(lambda p: p[None],
                                       params["blocks"]["layer"])
        x = pipeline_apply(stage_fn, stack, x, axis_name=STAGE_AXIS,
                           num_microbatches=num_microbatches)
        x = nn.LayerNorm(dtype=dtype).apply(
            {"params": params["LayerNorm_0"]}, x)
        return nn.Dense(cfg.vocab_size, dtype=jnp.float32).apply(
            {"params": params["lm_head"]}, x)

    def per_device_step(state, batch):
        tokens, labels = batch[features_col], batch[label_col]

        def objective(params):
            logits = forward(params, tokens)
            return loss_fn(logits, labels)

        loss, grads = jax.value_and_grad(objective)(state.params)
        loss = lax.pmean(loss, workers_axis)

        def reduce(path, g):
            keys = {getattr(k, "key", getattr(k, "name", None))
                    for k in path}
            g = lax.pmean(g, workers_axis)
            if keys & {"Embed_0", "pos_embed"}:
                # cotangent lives only on stage 0 (the ingesting
                # stage); collect it so every replica updates alike
                g = lax.psum(g, STAGE_AXIS)
            return g

        grads = jax.tree_util.tree_map_with_path(reduce, grads)
        import optax

        updates, new_opt_state = tx.update(grads, state.opt_state,
                                           state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(step=state.step + 1,
                                  params=new_params,
                                  opt_state=new_opt_state)
        return new_state, {"loss": loss}

    def step(state, batch):
        specs = lm_state_specs(state)
        batch_specs = {k: P(workers_axis) for k in batch}
        return jax.shard_map(
            per_device_step, mesh=mesh,
            in_specs=(specs, batch_specs),
            out_specs=(specs, P()))(state, batch)

    return step
