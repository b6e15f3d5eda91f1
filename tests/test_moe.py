"""Expert parallelism: all_to_all-dispatched Switch MoE parity against
a dense per-token reference, capacity-drop accounting, and a training
smoke test (SURVEY.md §2.3: EP absent in reference — beyond-reference
capability)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from distkeras_tpu.parallel.moe import (
    MoEAux,
    MoEParams,
    init_moe_params,
    moe_apply,
    moe_pspecs,
)

D, H, E = 8, 16, 8  # d_model, hidden, experts


def _params(seed=0):
    return init_moe_params(jax.random.key(seed), D, H, E)


def _dense_reference(params: MoEParams, x):
    """Per-token top-1 MoE with no capacity limit, no parallelism."""
    probs = jax.nn.softmax(x @ params.router, axis=-1)
    gate = probs.max(axis=-1)
    idx = probs.argmax(axis=-1)

    def ffn(e, tok):
        h = jax.nn.relu(tok @ params.w_in[e] + params.b_in[e])
        return h @ params.w_out[e] + params.b_out[e]

    outs = jax.vmap(lambda e, tok, g: g * ffn(e, tok))(
        idx, x, gate)
    return outs


def _ep_apply(mesh, params, x, capacity_factor):
    def fn(p, x):
        out, aux = moe_apply(p, x, axis_name="expert",
                             capacity_factor=capacity_factor)
        return out, aux

    return jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(moe_pspecs("expert"), P("expert")),
        out_specs=(P("expert"), MoEAux(P(), P()))))(params, x)


def test_ep_matches_dense_reference(devices):
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    params = _params()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(32, D)),
                    jnp.float32)
    # generous capacity: nothing dropped, so EP == dense per-token
    out, aux = _ep_apply(mesh, params, x, capacity_factor=float(E))
    assert float(aux.dropped_fraction) == 0.0
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_reference(params, x)),
                               rtol=2e-5, atol=2e-6)
    assert np.isfinite(float(aux.load_balance_loss))


def test_capacity_drops_are_reported(devices):
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    params = _params(seed=3)
    # Route EVERY token to expert 0 (router selects column 0 for any
    # all-positive input): with tight capacity most are dropped.
    params = params._replace(
        router=jnp.zeros((D, E)).at[:, 0].set(1.0))
    x = jnp.asarray(
        np.abs(np.random.default_rng(2).normal(size=(32, D))),
        jnp.float32)
    out, aux = _ep_apply(mesh, params, x, capacity_factor=1.0)
    assert float(aux.dropped_fraction) > 0.5
    # dropped tokens produce zero output (gate residual), kept ones not
    assert np.isfinite(np.asarray(out)).all()


def test_moe_trains(devices):
    """Joint router+expert training through the all_to_alls."""
    import optax

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    params = _params(seed=5)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(64, D)), jnp.float32)
    tgt = jnp.asarray(np.sin(np.asarray(x)), jnp.float32)

    from jax import lax

    def loss_fn(p, x, tgt):
        out, aux = moe_apply(p, x, axis_name="expert",
                             capacity_factor=2.0)
        local = jnp.mean((out - tgt) ** 2)
        return (lax.pmean(local, "expert")
                + 0.01 * aux.load_balance_loss)

    sharded = jax.shard_map(
        loss_fn, mesh=mesh,
        in_specs=(moe_pspecs("expert"), P("expert"),
                  P("expert")),
        out_specs=P())

    tx = optax.adam(3e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(p, opt_state, x, tgt):
        loss, g = jax.value_and_grad(sharded)(p, x, tgt)
        upd, opt_state = tx.update(g, opt_state)
        return optax.apply_updates(p, upd), opt_state, loss

    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, x, tgt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, (losses[0], losses[-1])


def test_bf16_tokens_route_exactly(devices):
    """Routing bookkeeping stays f32 even for bf16 tokens (bf16 cumsum
    would corrupt capacity slots past 256 tokens/expert)."""
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    params = _params(seed=7)
    x32 = jnp.asarray(np.random.default_rng(8).normal(size=(32, D)),
                      jnp.float32)
    out16, aux16 = _ep_apply(mesh, jax.tree_util.tree_map(
        lambda v: v.astype(jnp.bfloat16), params),
        x32.astype(jnp.bfloat16), capacity_factor=float(E))
    assert out16.dtype == jnp.bfloat16
    assert float(aux16.dropped_fraction) == 0.0
    want = _dense_reference(params, x32)
    np.testing.assert_allclose(
        np.asarray(out16, dtype=np.float32), np.asarray(want),
        rtol=0.1, atol=0.1)  # bf16 compute tolerance; routing exact


def _dense_topk_reference(params: MoEParams, x, k):
    """Per-token top-k MoE, no capacity limit, renormalized gates."""
    from jax import lax

    probs = jax.nn.softmax(x @ params.router, axis=-1)
    top_p, top_i = lax.top_k(probs, k)
    gates = top_p / top_p.sum(axis=-1, keepdims=True)

    def ffn(e, tok):
        h = jax.nn.relu(tok @ params.w_in[e] + params.b_in[e])
        return h @ params.w_out[e] + params.b_out[e]

    def one_token(tok, idxs, gs):
        return sum(gs[j] * ffn(idxs[j], tok) for j in range(k))

    return jax.vmap(one_token)(x, top_i, gates)


def test_top2_matches_dense_reference(devices):
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    params = _params(seed=11)
    x = jnp.asarray(np.random.default_rng(12).normal(size=(32, D)),
                    jnp.float32)

    def fn(p, x):
        return moe_apply(p, x, axis_name="expert",
                         capacity_factor=float(E), top_k=2)

    out, aux = jax.jit(jax.shard_map(
        fn, mesh=mesh,
        in_specs=(moe_pspecs("expert"), P("expert")),
        out_specs=(P("expert"), MoEAux(P(), P()))))(params, x)
    assert float(aux.dropped_fraction) == 0.0
    want = _dense_topk_reference(params, x, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_top2_second_choice_drops_first(devices):
    """Capacity pressure drops later choices before earlier ones: the
    kept fraction under top_k=2 is at least the top-1 kept fraction."""
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    params = _params(seed=13)
    params = params._replace(
        router=jnp.zeros((D, E)).at[:, 0].set(1.0).at[:, 1].set(0.5))
    x = jnp.asarray(
        np.abs(np.random.default_rng(14).normal(size=(32, D))),
        jnp.float32)

    def run(k):
        def fn(p, x):
            return moe_apply(p, x, axis_name="expert",
                             capacity_factor=1.0, top_k=k)

        return jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(moe_pspecs("expert"), P("expert")),
            out_specs=(P("expert"), MoEAux(P(), P()))))(params, x)

    _, aux1 = run(1)
    _, aux2 = run(2)
    assert float(aux2.dropped_fraction) > 0.0
    assert np.isfinite(float(aux2.load_balance_loss))
    # later choices fill capacity after earlier ones: the k=2 run keeps
    # at least as many assignments as the whole k=1 run (its first
    # choices alone fill at least that much)
    t = 32
    kept1 = (1.0 - float(aux1.dropped_fraction)) * t
    kept2 = (1.0 - float(aux2.dropped_fraction)) * 2 * t
    assert kept2 >= kept1 - 1e-3, (kept1, kept2)


def test_bad_top_k_raises(devices):
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("expert",))
    params = _params(seed=15)
    x = jnp.zeros((8, D), jnp.float32)

    def fn(p, x):
        return moe_apply(p, x, axis_name="expert", top_k=0)

    with np.testing.assert_raises(Exception):
        jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(moe_pspecs("expert"), P("expert")),
            out_specs=(P("expert"), MoEAux(P(), P()))))(params, x)
