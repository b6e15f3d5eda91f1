"""Pipeline + expert parallelism primitives, end to end.

The last two of the five parallelism forms (SURVEY.md §2.3 — neither
exists in the reference): a GPipe microbatch pipeline over a ``stage``
mesh axis, and a Switch-style MoE with all_to_all token dispatch over
an ``expert`` axis.  Each trains a small regression and reports losses
plus EP routing telemetry.

Run:  python examples/pipeline_moe.py --devices 8
      python examples/pipeline_moe.py --devices 8 --steps 50
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from _common import make_parser, parse_args_and_setup


def main():
    parser = make_parser(__doc__)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--d-model", type=int, default=16)
    args = parse_args_and_setup(parser)
    from distkeras_tpu.profiling import profiler_trace

    with profiler_trace(args.profile_dir):
        _run(args)


def _run(args):
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from distkeras_tpu.parallel import (init_moe_params, moe_apply,
                                        moe_pspecs, pipeline_apply)

    n_dev = len(jax.devices())
    d = args.d_model
    rng = np.random.default_rng(args.seed)

    # ---- pipeline: n_dev stages, tanh-dense each, fit a random map --
    mesh = Mesh(np.asarray(jax.devices()), ("stage",))
    params = {
        "w": jnp.asarray(rng.normal(scale=0.4, size=(n_dev, d, d)),
                         jnp.float32),
        "b": jnp.zeros((n_dev, d), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(32, d)), jnp.float32)
    tgt = jnp.asarray(np.tanh(np.asarray(x) @ rng.normal(
        scale=0.3, size=(d, d))), jnp.float32)

    def stage_fn(p, a):
        return jnp.tanh(a @ p["w"] + p["b"])

    pipe_loss = jax.shard_map(
        lambda p, x, t: jnp.mean(
            (pipeline_apply(stage_fn, p, x, axis_name="stage",
                            num_microbatches=4) - t) ** 2),
        mesh=mesh, in_specs=(P("stage"), P(), P()), out_specs=P())
    pp_losses = _fit(pipe_loss, params, x, tgt, args.steps, optax, jax)
    print(f"[pipeline] {n_dev} stages, 4 microbatches: loss "
          f"{pp_losses[0]:.4f} -> {pp_losses[-1]:.4f}")

    # ---- MoE: 2 experts/device, all_to_all dispatch ----------------
    mesh_e = Mesh(np.asarray(jax.devices()), ("expert",))
    mp = init_moe_params(jax.random.key(args.seed), d, 2 * d,
                         num_experts=2 * n_dev)
    xe = jnp.asarray(rng.normal(size=(n_dev * 16, d)), jnp.float32)
    te = jnp.asarray(np.sin(np.asarray(xe)), jnp.float32)

    def moe_loss(p, x, t):
        out, aux = moe_apply(p, x, axis_name="expert",
                             capacity_factor=2.0)
        return (lax.pmean(jnp.mean((out - t) ** 2), "expert")
                + 0.01 * aux.load_balance_loss)

    moe_sharded = jax.shard_map(
        moe_loss, mesh=mesh_e,
        in_specs=(moe_pspecs("expert"), P("expert"),
                  P("expert")),
        out_specs=P())
    ep_losses = _fit(moe_sharded, mp, xe, te, args.steps, optax, jax)
    print(f"[moe] {2 * n_dev} experts on {n_dev} devices: loss "
          f"{ep_losses[0]:.4f} -> {ep_losses[-1]:.4f}")

    print(json.dumps({
        "config": "pipeline_moe", "devices": n_dev,
        "pipeline_loss": [round(pp_losses[0], 5),
                          round(pp_losses[-1], 5)],
        "moe_loss": [round(ep_losses[0], 5), round(ep_losses[-1], 5)],
    }))


def _fit(loss_fn, params, x, tgt, steps, optax, jax):
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)

    @jax.jit
    def step(p, s, x, t):
        loss, g = jax.value_and_grad(loss_fn)(p, x, t)
        upd, s = tx.update(g, s)
        return optax.apply_updates(p, upd), s, loss

    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, x, tgt)
        losses.append(float(loss))
    return losses


if __name__ == "__main__":
    main()
