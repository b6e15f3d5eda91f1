"""Plain reference of the GPT-2 block as this benchmark runs it.

Straight ``jax.numpy`` in float32 with ``highest`` matmul precision: no
kernels, no cache, no batching tricks, nothing imported from the program.
It follows the published GPT-2 decoder (pre-LayerNorm, learned positions,
fused-bias projections, GELU MLP of width ``n_inner``) with the departures
that the program's ``TransformerLM`` forces, each stated in the
configuration file under ``departures``:

* LayerNorm epsilon 1e-6 (flax's default; the source says 1e-5);
* tanh-approximated GELU (``flax.linen.gelu``; the source says erf GELU);
* an output head with its own kernel and bias.  Serving initialises the
  kernel to the embedding's transpose, which is the tied head exactly;
  training lets the two drift apart, in the program and here alike.

``precision`` lowers the matmul operands for the control of "How correct
is decided": ``"fp8"`` rounds both operands of every matmul to
float8_e4m3fn (the step below bfloat16), ``"bf16"`` to bfloat16.
Accumulation stays float32 in every mode.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.weights import gpt2 as weights

_LOWER = {"fp8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}


def _mm(spec, a, b, precision):
    if precision in _LOWER:
        a = a.astype(_LOWER[precision]).astype(jnp.float32)
        b = b.astype(_LOWER[precision]).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, w, cfg, precision="f32"):
    """One decoder block on ``x`` [B, T, d] (float32)."""
    w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    eps = cfg["layer_norm_epsilon_as_run"]
    t = x.shape[1]
    hd = cfg["n_embd"] // cfg["n_head"]
    y = _layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
    q = _mm("btd,dhk->bthk", y, w["wq"], precision) + w["bq"]
    k = _mm("btd,dhk->bthk", y, w["wk"], precision) + w["bk"]
    v = _mm("btd,dhk->bthk", y, w["wv"], precision) + w["bv"]
    s = _mm("bqhk,bthk->bhqt", q, k, precision) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = _mm("bhqt,bthk->bqhk", p, v, precision)
    x = x + _mm("bqhk,hkd->bqd", a, w["wo"], precision) + w["bo"]
    y = _layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    y = _gelu_tanh(_mm("btd,di->bti", y, w["w1"], precision) + w["b1"])
    return x + _mm("bti,id->btd", y, w["w2"], precision) + w["b2"]


def embed(tokens, g):
    t = tokens.shape[1]
    return (g["wte"].astype(jnp.float32)[tokens]
            + g["wpe"].astype(jnp.float32)[None, :t])


def head(x, g, head_w, cfg, precision="f32"):
    y = _layer_norm(x, g["lnf_g"].astype(jnp.float32),
                    g["lnf_b"].astype(jnp.float32),
                    cfg["layer_norm_epsilon_as_run"])
    return (_mm("...d,dv->...v", y, head_w.astype(jnp.float32), precision)
            + g["head_b"].astype(jnp.float32))


# ---- serving: logits at chosen rows, layer by layer from the seed -------

def served_logits(cfg, seed, dtype, sequences, rows, precision="f32",
                  pad_to=512):
    """Logits ``[r_i, V]`` (on the host) at the rows ``rows[i]`` of each of ``sequences``
    (lists of token ids of any lengths).

    The weights are made again from ``seed`` in ``dtype`` (the type they
    are served in) one layer at a time, so the reference never holds the
    model whole.  Each sequence runs alone, right-padded to a multiple of
    ``pad_to``: under causal attention the padding cannot reach the rows
    read, and the few padded lengths keep the compiled programs few."""
    key = weights.seed_key(seed)
    dtype = jnp.dtype(dtype)

    # the key is an argument everywhere: closed over, it would be a
    # constant of the program and every seed would compile anew
    @jax.jit
    def first(tokens, key):
        return embed(tokens, weights.global_weights(cfg, key, dtype))

    @jax.jit
    def layer_weights(key, i):
        return weights.layer_weights(cfg, key, i, dtype)

    @functools.partial(jax.jit, donate_argnums=0)
    def layer(x, w):      # one compiled program for each padded length
        return block(x, w, cfg, precision)

    @jax.jit
    def last(x, rows, key):
        g = weights.global_weights(cfg, key, dtype)
        return head(x[0][rows], g, g["wte"].T, cfg, precision)

    xs = []
    for seq in sequences:
        t = min(-(-len(seq) // pad_to) * pad_to, cfg["n_positions"])
        padded = np.zeros((1, t), np.int32)
        padded[0, :len(seq)] = seq
        xs.append(first(padded, key))
    for i in range(cfg["n_layer"]):
        w = layer_weights(key, i)
        xs = [layer(x, w) for x in xs]
    # rows padded to one length, so that ``last`` too compiles once a length
    n_rows = -(-max(len(r) for r in rows) // 128) * 128
    out = []
    for x, r in zip(xs, rows):
        padded = np.zeros((n_rows,), np.int32)
        padded[:len(r)] = r
        out.append(np.asarray(last(x, padded, key))[:len(r)])
    return out


# ---- training: loss, gradients, Adam, the first chunk's steps ----------

def train_tree(cfg, seed, dtype="float32"):
    """All weights as one tree, the head's kernel a leaf of its own."""
    w = weights.make(cfg, seed, dtype)
    w["globals"]["head_w"] = w["globals"]["wte"].T
    return w


def loss_sum(w, tokens, cfg, precision="f32"):
    """Summed next-token cross-entropy of ``tokens`` [b, T + 1]."""
    x = embed(tokens[:, :-1], w["globals"])
    for lw in w["layers"]:
        x = block(x, lw, cfg, precision)
    logits = head(x, w["globals"], w["globals"]["head_w"], cfg, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -picked.sum()


def _tm(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def loss_and_grads_fn(cfg, precision="f32", rows_per_block=1):
    """``f(w, tokens) -> (mean loss, gradients)`` over ``tokens``
    [B, T + 1], computed in blocks of rows so that the float32 logits fit
    beside the weights."""
    fn = jax.jit(jax.value_and_grad(
        functools.partial(loss_sum, cfg=cfg, precision=precision)))
    add = jax.jit(lambda a, b: _tm(jnp.add, a, b))
    scale = jax.jit(lambda g, n: _tm(lambda a: a / n, g))

    def f(w, tokens):
        n = tokens.shape[0] * (tokens.shape[1] - 1)
        total, grads = 0.0, None
        for lo in range(0, tokens.shape[0], rows_per_block):
            l, g = fn(w, tokens[lo:lo + rows_per_block])
            total = total + l
            grads = g if grads is None else add(grads, g)
        return total / n, scale(grads, jnp.float32(n))

    return f


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"))
def adam_step(w, grads, m, v, step, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam as Kingma & Ba state it (optax.adam's defaults); ``step``
    counts from 1."""
    m = _tm(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = _tm(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    w = _tm(lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
            w, m, v)
    return w, m, v


def leaf_norms(tree) -> dict:
    """``{path: l2 norm}`` over the leaves, names as in ``LEAF_NAMES``."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda t: [jnp.linalg.norm(
        x.astype(jnp.float32).ravel()) for x in t])(
            [x for _, x in flat])
    out = {}
    for (path, _), n in zip(flat, norms):
        parts = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        out["/".join(parts)] = float(n)
    return out


def train_readings(cfg, seed, batches, lr, precision="f32", fault=None):
    """The reference's first chunk of steps, one for each of ``batches``:
    the mean of the steps' losses, the first gradient's norm by leaf, and
    after the last step the norms of Adam's first moment and of the
    parameters' change.

    ``fault="half_batch"`` plants the fault of step 3 of "How correct is
    decided" in the reference put in the program's place: the second half
    of every batch is left out and the mean taken over the rest."""
    w0 = train_tree(cfg, seed)
    w = w0
    zeros = jax.jit(lambda t: _tm(jnp.zeros_like, t))
    m, v = zeros(w), zeros(w)
    loss_and_grads = loss_and_grads_fn(cfg, precision)
    losses, grad_norms = [], None
    for i, tokens in enumerate(batches, start=1):
        tokens = jnp.asarray(tokens, jnp.int32)
        if fault == "half_batch":
            tokens = tokens[:max(1, tokens.shape[0] // 2)]
        loss, grads = loss_and_grads(w, tokens)
        losses.append(loss)
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        w, m, v = adam_step(w, grads, m, v, jnp.float32(i), jnp.float32(lr))
        del grads
    change = jax.jit(lambda a, b: _tm(jnp.subtract, a, b))(w, w0)
    return {"loss": float(np.mean([float(x) for x in losses])),
            "grad_norms": grad_norms, "moment_norms": leaf_norms(m),
            "change_norms": leaf_norms(change)}
