"""Pipeline parallelism: GPipe tick-loop parity against sequential
stage application (forward + gradients), microbatch-count invariance,
and a dp x pp training step (SURVEY.md §2.3: PP absent in reference —
beyond-reference capability)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distkeras_tpu.parallel.pipeline import pipeline_apply

D = 16  # homogeneous stage width


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _stacked_params(n_stages, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(scale=0.5, size=(n_stages, D, D)),
                         jnp.float32),
        "b": jnp.asarray(rng.normal(size=(n_stages, D)), jnp.float32),
    }


def _sequential(params, x):
    for s in range(params["w"].shape[0]):
        x = _stage_fn({"w": params["w"][s], "b": params["b"][s]}, x)
    return x


def _pipelined(mesh, n_micro):
    def fn(params, x):
        return pipeline_apply(_stage_fn, params, x, axis_name="stage",
                              num_microbatches=n_micro)

    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("stage"), P()), out_specs=P()))


@pytest.mark.parametrize("n_micro", [1, 4, 8])
def test_pipeline_matches_sequential_forward(devices, n_micro):
    n_stages = 4
    mesh = Mesh(np.asarray(jax.devices()[:n_stages]), ("stage",))
    params = _stacked_params(n_stages)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, D)),
                    jnp.float32)
    got = _pipelined(mesh, n_micro)(params, x)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_sequential(params, x)),
                               rtol=2e-6, atol=2e-6)


def test_pipeline_gradients_match_sequential(devices):
    n_stages, n_micro = 4, 4
    mesh = Mesh(np.asarray(jax.devices()[:n_stages]), ("stage",))
    params = _stacked_params(n_stages, seed=2)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(8, D)),
                    jnp.float32)
    tgt = jnp.asarray(np.random.default_rng(4).normal(size=(8, D)),
                      jnp.float32)

    pipe = _pipelined(mesh, n_micro)
    g_pipe = jax.grad(lambda p: jnp.mean((pipe(p, x) - tgt) ** 2))(
        params)
    g_seq = jax.grad(
        lambda p: jnp.mean((_sequential(p, x) - tgt) ** 2))(params)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_pipe[k]),
                                   np.asarray(g_seq[k]),
                                   rtol=2e-5, atol=2e-6)


def test_dp_pp_training_step_converges(devices):
    """(2 workers, 4 stages) mesh: batch sharded over workers, stages
    pipelined — a joint dp x pp training step optimizes."""
    import optax
    from jax import lax

    n_stages = 4
    grid = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(grid, ("workers", "stage"))
    params = _stacked_params(n_stages, seed=5)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(16, D)), jnp.float32)
    tgt = jnp.tanh(x @ jnp.ones((D, D)) * 0.1)  # learnable target

    def loss_fn(params, x, tgt):
        out = pipeline_apply(_stage_fn, params, x, axis_name="stage",
                             num_microbatches=4)
        return lax.pmean(jnp.mean((out - tgt) ** 2), "workers")

    sharded_loss = jax.shard_map(
        loss_fn, mesh=mesh,
        in_specs=(P("stage"), P("workers"), P("workers")),
        out_specs=P())

    tx = optax.adam(1e-2)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x, tgt):
        loss, g = jax.value_and_grad(sharded_loss)(params, x, tgt)
        upd, opt_state = tx.update(g, opt_state)
        return optax.apply_updates(params, upd), opt_state, loss

    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, x, tgt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses


def test_indivisible_microbatches_raise(devices):
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("stage",))
    params = _stacked_params(4)
    x = jnp.zeros((6, D), jnp.float32)
    with pytest.raises(ValueError, match="microbatch"):
        _pipelined(mesh, 4)(params, x)
