"""Kimi Delta Attention (KDA, arXiv:2510.26692): the gated delta rule with
a decay for every key channel, in the two forms a served model needs.

Per head, with a state ``S`` ``[d_k, d_v]`` kept in float32::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t`` ``[d_k]`` is a log-decay (negative), ``beta_t`` a scalar in
(0, 1).  Written as ``S_t = D_t S_{t-1} + k_t u_t^T`` with
``u_t = beta_t (v_t - (D_t S_{t-1})^T k_t)``.

* ``kda_step``: one token (``T = 1``).  What a decode step runs off a
  TPU, under the scope ``kda_decode`` of the model; XLA reads the state
  twice and writes it once (the reduction ``k^T S`` cannot share a
  fusion with the update that broadcasts it back over ``S``).
* ``kda_step_kernel``: the same step as one Pallas TPU kernel that reads
  and writes each row's state once, in place.  What a decode step runs
  where ``kda_step_kernel_applies`` takes the state.
* ``kda_chunked``: a prompt, in chunks of ``CHUNK`` tokens.  Inside a
  chunk the ``u`` of every token come from one unit-lower-triangular
  solve (the WY / UT form), in plain matmuls; the state is carried from
  chunk to chunk by a ``lax.scan``.  What a prefill runs, under
  ``kda_prefill``.

A token with ``beta = 0`` and ``g = 0`` leaves the state as it found it:
that is how a prompt's padding is kept out of it.

``causal_conv`` is the short depthwise convolution in front of ``q``,
``k`` and ``v``, with the state it leaves for the next call (its last
``width - 1`` inputs, side by side).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST

#: Tokens a chunk.  Inside a chunk the products ``k_t . k_s`` carry the
#: factor ``exp(G_t - G_s)`` of the cumulated log-decays, a channel each,
#: which is split as ``exp(G_t - G_r) exp(G_r - G_s)`` around the chunk's
#: middle token ``r``.  With every ``g`` above -5 (the configurations'
#: lower bound), 16 tokens keep each factor inside ``e^{+-40}`` and every
#: other exponential of a cumulated gate above ``e^{-80}``: all normal
#: float32 numbers (whose range ends at ``e^{+-87}``).  A wider chunk, or a
#: lower bound below -5, would leave that range.
CHUNK = 16


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def causal_conv(x, w, state, last_index=None):
    """Depthwise causal convolution of ``x`` ``[B, T, C]`` by ``w``
    ``[K, C]``: ``y_t = sum_j w[j] x_{t - K + 1 + j}``, the inputs before
    the chunk taken from ``state`` ``[B, (K - 1) C]`` (zeros on an empty
    cache), oldest first, each ``C`` lanes wide.  Returns ``(y [B, T, C]
    float32, tail)``: ``tail`` is the ``K - 1`` inputs that end at
    ``last_index`` (the chunk's last token where ``None``), in the
    state's form, the state of the next call.

    The state is kept flat so that a step reads its inputs as slices of
    the lanes: as ``[B, K - 1, C]`` a TPU lays the short axis outermost,
    and a step handed the pool in row-major order would copy it whole."""
    b, t, c = x.shape
    k = w.shape[0]
    wf = w.astype(jnp.float32)
    if t == 1 and last_index is None:
        taps = [state[:, j * c:(j + 1) * c].astype(x.dtype)
                for j in range(k - 1)] + [x[:, 0]]
        y = sum(tap.astype(jnp.float32) * wf[j]
                for j, tap in enumerate(taps))
        return y[:, None], jnp.concatenate(taps[1:], axis=-1)
    xs = jnp.concatenate([state.reshape(b, k - 1, c).astype(x.dtype), x],
                         axis=1)
    y = sum(xs[:, j:j + t].astype(jnp.float32) * wf[j] for j in range(k))
    start = t if last_index is None else last_index + 1
    tail = lax.dynamic_slice_in_dim(xs, start, k - 1, axis=1)
    return y, tail.reshape(b, (k - 1) * c)


def kda_gate(f, a_log, dt_bias, lower_bound: float):
    """The per-channel log-decay ``lower_bound * sigmoid(exp(A_log) (f +
    dt_bias))``, in ``(lower_bound, 0)``: ``f`` ``[..., H, d_k]``,
    ``a_log`` ``[H]``, ``dt_bias`` ``[H, d_k]``."""
    a = jnp.exp(a_log.astype(jnp.float32))[:, None]
    return lower_bound * jax.nn.sigmoid(
        a * (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)))


def kda_step(state, q, k, v, g, beta):
    """One token of every row: ``state`` ``[B, H, d_k, d_v]`` float32,
    ``q``/``k``/``g`` ``[B, H, d_k]``, ``v`` ``[B, H, d_v]``, ``beta``
    ``[B, H]``.  Returns ``(new state, o [B, H, d_v])``."""
    s = state * jnp.exp(g)[..., None]
    kv = jnp.einsum("bhk,bhkv->bhv", k, s, precision=_HI)
    s = s + k[..., None] * (beta[..., None] * (v - kv))[..., None, :]
    return s, jnp.einsum("bhk,bhkv->bhv", q, s, precision=_HI)


#: State a grid step of ``kda_step_kernel`` takes in at least: whole
#: rows (every head of a row) until a block holds this much, so that the
#: grid's fixed cost a step is paid a few hundred times a layer, not
#: thousands.
_STEP_BLOCK_BYTES = 1 << 20


def kda_step_kernel_applies(state) -> bool:
    """Whether a decode step should run ``kda_step_kernel`` on ``state``
    ``[B, H, d_k, d_v]`` in place of ``kda_step``: on a TPU, a float32
    state whose ``d_v`` fills whole rows of 128 lanes and whose ``d_k``
    whole tiles of 8 sublanes."""
    from distkeras_tpu.ops import attention

    dk, dv = state.shape[-2:]
    return (attention._on_tpu() and state.dtype == jnp.float32
            and dv % 128 == 0 and dk % 8 == 0)


def _step_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, s_out, o_ref,
                 *, rows: int, heads: int):
    """One block of ``rows`` rows x ``heads`` heads.  A head's state
    ``[d_k, d_v]`` lies with ``d_k`` on the sublanes, so ``q``, ``k`` and
    the decay act on it as columns: a row's ``[heads, d_k]`` of each is
    turned into ``[d_k, heads]`` once, and both contractions are sums
    over sublanes on the vector unit, in float32."""

    def row(r, carry):
        decay = jnp.exp(g_ref[r]).T             # [d_k, heads]
        kc, qc = k_ref[r].T, q_ref[r].T
        v, beta = v_ref[r], b_ref[r]            # [heads, d_v], [heads, 1]
        o = []
        for h in range(heads):
            s = s_ref[r, h] * decay[:, h:h + 1]
            kv = jnp.sum(kc[:, h:h + 1] * s, axis=0, keepdims=True)
            s = s + kc[:, h:h + 1] * (beta[h:h + 1] * (v[h:h + 1] - kv))
            s_out[r, h] = s
            o.append(jnp.sum(qc[:, h:h + 1] * s, axis=0, keepdims=True))
        o_ref[r] = jnp.concatenate(o, axis=0)
        return carry

    lax.fori_loop(0, rows, row, 0)


def _step_block(b: int, h: int, dk: int, dv: int) -> tuple[int, int]:
    """``(rows, heads)`` of a block of ``kda_step_kernel``: every head,
    and whole rows up to ``_STEP_BLOCK_BYTES``."""
    row = h * dk * dv * 4
    return max(1, min(b, _STEP_BLOCK_BYTES // row)), h


@functools.partial(jax.jit, static_argnames=("rows", "heads", "interpret"))
def _step_call(state, q, k, v, g, beta, *, rows, heads, interpret):
    # one jit for every call of one shape: the kernel is traced and
    # lowered once a process, not once a layer of every program
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, dk, dv = state.shape
    vec = lambda w: pl.BlockSpec(  # noqa: E731
        (rows, heads, w), lambda i, j: (i, j, 0))
    whole = pl.BlockSpec((rows, heads, dk, dv), lambda i, j: (i, j, 0, 0))
    block = rows * heads * dk * dv * 4
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        # the state in and out, two buffers each, and room for the body
        vmem_limit_bytes=4 * block + (16 << 20))
    return pl.pallas_call(
        functools.partial(_step_kernel, rows=rows, heads=heads),
        grid=(pl.cdiv(b, rows), h // heads),
        in_specs=[vec(dk), vec(dk), vec(dv), vec(dk), vec(1), whole],
        out_specs=[whole, vec(dv)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, h, dv), jnp.float32)],
        input_output_aliases={5: 0},
        compiler_params=params,
        interpret=interpret,
        name="kda_step",
    )(q, k, v, g, beta[..., None], state)


def kda_step_kernel(state, q, k, v, g, beta, *, rows: int | None = None,
                    heads: int | None = None,
                    interpret: bool | None = None):
    """``kda_step`` as one Pallas TPU kernel: the same arguments (all
    float32) and results, each row's state read once and written once,
    into its own buffer (a donated state is updated in place).  A grid
    over blocks of ``rows`` rows x ``heads`` heads (``heads`` divides
    ``H``; by default ``_step_block``'s); the decay, both contractions
    and the update are float32 on the vector unit.  ``interpret``: the
    Pallas interpreter, by default off a TPU."""
    b, h, dk, dv = state.shape
    if rows is None:
        rows, heads = _step_block(b, h, dk, dv)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    return _step_call(state, q, k, v, g, beta, rows=rows,
                      heads=heads or h, interpret=interpret)


def kda_chunked(q, k, v, g, beta, state):
    """A multi-token chunk: ``q``/``k``/``g`` ``[B, T, H, d_k]``, ``v``
    ``[B, T, H, d_v]``, ``beta`` ``[B, T, H]`` (all float32), from
    ``state`` ``[B, H, d_k, d_v]``.  Returns ``(o [B, T, H, d_v], the
    state after the last token)``.  ``T`` is padded here to a multiple of
    ``CHUNK`` with tokens that leave the state alone."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % CHUNK
    if pad:
        widen = lambda a: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    n = (t + pad) // CHUNK

    def chunks(a):      # [B, T, H, ...] -> [N, B, H, C, ...]
        a = a.reshape((b, n, CHUNK, h) + a.shape[3:])
        return jnp.moveaxis(a, (1, 3), (0, 2))

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-2)                # G_t, inclusive, [.., C, dk]
    mid = cum[..., CHUNK // 2 - 1:CHUNK // 2, :]
    k_after = k * jnp.exp(cum - mid)            # k_t exp(G_t - G_r)
    k_before = k * jnp.exp(mid - cum)           # k_s exp(G_r - G_s)
    q_after = q * jnp.exp(cum - mid)
    rows = jnp.arange(CHUNK)
    # A[t, s] = k_s^T Diag(exp(G_t - G_s)) k_t for s < t; the same with
    # q_t, s <= t, for the outputs
    a = jnp.where(rows[:, None] > rows[None, :],
                  jnp.einsum("...tk,...sk->...ts", k_after, k_before,
                             precision=_HI), 0.0)
    within = jnp.where(rows[:, None] >= rows[None, :],
                       jnp.einsum("...tk,...sk->...ts", q_after, k_before,
                                  precision=_HI), 0.0)
    # (I + diag(beta) A) [W | U_v] = diag(beta) [k exp(G) | v]: then the
    # chunk's u = U_v - W S for the state S it starts from
    lower = jnp.eye(CHUNK, dtype=a.dtype) + beta[..., None] * a
    rhs = beta[..., None] * jnp.concatenate([k * jnp.exp(cum), v], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        lower, rhs, lower=True, unit_diagonal=True)
    w, u_v = solved[..., :dk], solved[..., dk:]
    q_dec = q * jnp.exp(cum)                    # q_t exp(G_t)
    last = cum[..., -1:, :]
    k_end = k * jnp.exp(last - cum)             # k_s exp(G_C - G_s)
    decay = jnp.exp(last[..., 0, :])[..., None]  # [.., dk, 1]

    def carry(s, xs):
        w, u_v, q_dec, within, k_end, decay = xs
        u = u_v - jnp.einsum("bhck,bhkv->bhcv", w, s, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", q_dec, s, precision=_HI) \
            + jnp.einsum("bhcs,bhsv->bhcv", within, u, precision=_HI)
        s = decay * s + jnp.einsum("bhck,bhcv->bhkv", k_end, u,
                                   precision=_HI)
        return s, o

    state, o = lax.scan(carry, state, (w, u_v, q_dec, within, k_end, decay))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, n * CHUNK, h, dv)
    return o[:, :t], state
