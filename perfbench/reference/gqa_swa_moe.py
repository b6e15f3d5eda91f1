"""Plain reference of the decoder of sliding-window and full grouped-query
attention over sparse experts, as this benchmark runs it.

Straight ``jax.numpy`` in float32 with ``highest`` matmul precision:
nothing imported from the program, no cache, no ring, no kernel.
Attention takes its queries in blocks of ``Q_BLOCK`` against every key of
the sequence under an explicit mask (the band of a window layer), and the
expert layer loops over its held experts with each token's weight (zero
where the expert was not chosen).

The equations (d = ``hidden_size``; every *assumed* choice is listed in
the configuration file under ``assumed``):

* layer: ``x = x + RMSNorm(A(x))``, ``x = x + RMSNorm(F(x))`` (norms
  after the sublayers, none before); after the last layer RMSNorm and the
  untied head.
* attention: ``q = RMSNorm_D(x W_q)`` (H heads of D), ``k = RMSNorm_D(x
  W_k)``, ``v = x W_v`` (KVH heads); in a window layer (``sliding_windows``
  > 0) q and k rotated (rotate-half, ``rope_parameters.rope_theta``) and a
  query at ``i`` sees keys ``i - W < j <= i``; in a full layer no rotary,
  causal; query head h reads key head ``h // (H / KVH)``; softmax of ``q .
  k / sqrt(D)``; ``W_o``.
* feed-forward: SwiGLU of ``intermediate_size`` where ``mlp_layer_types``
  says dense; else ``s = sigmoid(x W_r)``, the top ``num_experts_per_tok``
  of ``s + e_bias`` chosen (one group), weights ``s`` at the chosen over
  their sum times ``routed_scaling_factor``; ``sum_i w_i SwiGLU_i(x) +
  SwiGLU_shared(x)`` over the held experts (``experts_held``: what the
  others would add is left out, as in the program).

``precision`` as in ``reference/mla_moe_hc.py``: ``"fp8"`` / ``"bf16"``
round both operands of every matrix product.  After a ``+`` a planted
fault, for ``tools/served_readings.py``:

* ``no_routed``: the routed experts' sum left out;
* ``no_window``: the window layers attend to every earlier position;
* ``rope_on_full``: the full layers rotated as the window layers are;
* ``pad_in_ring``: a prefill that installed the prompt's padding (to a
  multiple of ``pad_to``, the engine's ``prefill_align``) into the
  rings.  The padding is put in after the prompt, computed as the
  program's prefill computes it (band and causal over prompt and
  padding, rotary positions going on); the full layers mask it for the
  answer, whose positions go on from the prompt's end; a window layer's
  answer token sees, as a ring of ``W`` rows would hold them, the last
  ``W`` positions of prompt and padding whose rows its own answer has
  not yet written over, and its own answer's last ``W``.

**Where the reference abstains** (``route_tie_margin`` of the
configuration, in float32 alone): a token whose 8th and 9th biased score
lie closer than the margin in any expert layer gets a row of zeros, so
that any served token reads a gap of 0 there (the program's bfloat16
rounding may give it the other choice; ``reference/mla_moe_hc.py`` says
why).
"""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.gpt2 import _mm
from perfbench.weights import gqa_swa_moe as weights

Q_BLOCK = 256

FAULTS = ("no_routed", "no_window", "rope_on_full", "pad_in_ring")


def _f32(w):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _swiglu(x, gate, up, down, precision):
    g = _mm("td,dh->th", x, gate, precision)
    u = _mm("td,dh->th", x, up, precision)
    return _mm("th,hd->td", g * jax.nn.sigmoid(g) * u, down, precision)


def _rotate_half(x, positions, theta):
    """``x`` [T, heads, D] rotated at ``positions`` [T]: channel pair
    ``(i, i + D / 2)`` by ``position * theta^(-2 i / D)``."""
    half = x.shape[-1] // 2
    inv = float(theta) ** (-np.arange(half, dtype=np.float64) * 2.0
                           / x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv,
                                                               jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def visible(qi, kj, window, seq):
    """``[q, k]`` bool: which keys (sequence indices ``kj``) the queries at
    indices ``qi`` see.  ``seq = (prompt, answer_start)``: the answer's
    first index (past the padding put in by ``pad_in_ring``; the prompt's
    end otherwise).  ``window`` 0: a full layer."""
    prompt, a0 = seq
    i, j = qi[:, None], kj[None, :]
    causal = j <= i
    answer = i >= a0
    if not window:
        # the padding is no key of the answer's
        return causal & ~(answer & (j >= prompt) & (j < a0))
    band = causal & (i - j < window)
    # an answer token m = i - a0 against the rings the prefill left: row
    # p mod W holds position p of the last W of prompt and padding until
    # the answer's own position prompt + m' writes over it, and the step
    # at position prompt + m reads rows below min(prompt + m + 1, W)
    m = i - a0
    ring = (j < a0) & (j >= a0 - window) & (m < window - 1) \
        & ((j - prompt) % window > m) \
        & (j % window < jnp.minimum(prompt + m + 1, window))
    own = (j >= a0) & (j <= i) & (i - j < window)
    return jnp.where(answer, ring | own, band)


def attention(x, w, cfg, precision, window, positions, seq, fault=""):
    """``x`` [T, d] -> [T, d]; ``positions`` [T] the rotary positions,
    ``seq`` as in ``visible``."""
    t = x.shape[0]
    h, kvh, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = _rms(_mm("td,dhk->thk", x, w["wq"], precision), w["qn_g"], eps)
    k = _rms(_mm("td,dhk->thk", x, w["wk"], precision), w["kn_g"], eps)
    v = _mm("td,dhk->thk", x, w["wv"], precision)
    if window or fault == "rope_on_full":
        theta = cfg["rope_parameters"]["rope_theta"]
        q = _rotate_half(q, positions, theta)
        k = _rotate_half(k, positions, theta)
    if fault == "no_window":
        window = 0
    q = q.reshape(t, kvh, h // kvh, hd)
    keys = jnp.arange(t)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(t, lo + Q_BLOCK)
        s = _mm("qcgd,tcd->cgqt", q[lo:hi], k, precision) * hd ** -0.5
        seen = visible(jnp.arange(lo, hi), keys, window, seq)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        outs.append(_mm("cgqt,tcd->qcgd", p, v, precision))
    o = jnp.concatenate(outs, axis=0).reshape(t, h, hd)
    return _mm("qhk,hkd->qd", o, w["wo"], precision)


def route(x, w, cfg, precision):
    """``([T, E] weights, [T] margin)``: a token's weight for each expert,
    zero where it was not chosen, and how far its last chosen biased
    score lies above the best one not chosen."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("td,de->te", x, w["router"], precision))
    best, idx = jax.lax.top_k(s + w["e_bias"], k + 1)
    chosen = jax.nn.one_hot(idx[:, :k], s.shape[1],
                            dtype=jnp.float32).sum(axis=1)
    picked = s * chosen
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    return picked * cfg["routed_scaling_factor"], \
        best[:, k - 1] - best[:, k]


def experts(x, w, cfg, precision, fault=""):
    """The expert layer on ``x`` [T, d] and the router's margins [T]."""
    h = cfg["moe_intermediate_size"]
    first, count = weights.experts_held(cfg)
    gates, margin = route(x, w, cfg, precision)
    gates = gates[:, first:first + count]
    if fault == "no_routed":
        gates = jnp.zeros_like(gates)

    def one(acc, ew):
        w_in, w_down, g = ew
        y = _swiglu(x, w_in[:, :h], w_in[:, h:], w_down, precision)
        return acc + g[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["we_in"], w["we_down"], gates.T))
    return y + _swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"],
                       precision), margin


def layer_and_margin(x, w, cfg, kind, precision="f32", positions=None,
                     seq=None):
    """One layer of ``kind`` (``weights.kind_of``) on ``x`` [T, d] (float32),
    and of an expert layer its router's margins [T] (infinite otherwise).
    ``precision`` may carry a planted fault after a ``+``."""
    precision, _, fault = precision.partition("+")
    if fault and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    window, dense = kind
    t = x.shape[0]
    positions = jnp.arange(t) if positions is None else positions
    seq = (t, t) if seq is None else seq
    w = _f32(w)
    eps = cfg["rms_norm_eps"]
    x = x + _rms(attention(x, w, cfg, precision, window, positions, seq,
                           fault), w["ln_attn_g"], eps)
    if dense:
        y = _swiglu(x, w["w_gate"], w["w_up"], w["w_down"], precision)
        margin = jnp.full((t,), jnp.inf)
    else:
        y, margin = experts(x, w, cfg, precision, fault)
    return x + _rms(y, w["ln_ffn_g"], eps), margin


def head(x, g, cfg, precision="f32"):
    precision = precision.partition("+")[0]
    y = _rms(x, g["lnf_g"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm("rd,dv->rv", y, g["head_w"].astype(jnp.float32), precision)


def forward(w, tokens, cfg, precision="f32"):
    """Logits [T, V] of one sequence from a whole tree of weights (the
    tests' toy sizes; the cells go layer by layer, below)."""
    x = w["globals"]["wte"].astype(jnp.float32)[tokens]
    for i, lw in enumerate(w["layers"]):
        x = layer_and_margin(x, lw, cfg, weights.kind_of(cfg, i),
                             precision)[0]
    return head(x, w["globals"], cfg, precision)


def _placed(seq, rows, pad_to, fault):
    """``(tokens, positions, (prompt, answer_start), rows)`` of one
    sequence as the reference runs it: with ``pad_in_ring`` the prompt's
    padding (zeros, as the engine pads) is put in after the prompt (the
    prompt is what ends at ``rows[0]``) at the positions that follow it,
    and the answer's positions go on from the prompt's end."""
    seq, rows = np.asarray(seq), np.asarray(rows)
    prompt = int(rows[0]) + 1
    pad = -prompt % pad_to if fault == "pad_in_ring" else 0
    tokens = np.concatenate([seq[:prompt], np.zeros(pad, seq.dtype),
                             seq[prompt:]])
    positions = np.concatenate([np.arange(prompt + pad),
                                np.arange(prompt, len(seq))])
    return tokens, positions, (prompt, prompt + pad), \
        rows + pad * (rows >= prompt)


def served_logits(cfg, seed, dtype, sequences, rows, precision="f32",
                  pad_to=512):
    """As ``reference/mla_moe_hc.served_logits``: logits ``[r_i, V]`` on
    the host at ``rows[i]`` of each sequence, each sequence alone and
    right-padded (to the longest one's multiple of ``pad_to``), the weights
    made again from ``seed`` in ``dtype`` one layer at a time.  In float32
    the rows of tied tokens are zeros (the module's docstring).  ``rows[i]``
    starts at the prompt's last token, which places the faults that need
    the prompt's end."""
    key = weights.seed_key(seed)
    dtype = jnp.dtype(dtype)
    fault = precision.partition("+")[2]

    @jax.jit
    def first(tokens, key):
        return weights.global_weights(cfg, key, dtype)["wte"].astype(
            jnp.float32)[tokens]

    @functools.partial(jax.jit, static_argnums=2)
    def layer_weights(key, i, kind):
        return weights.layer_weights(cfg, key, i, dtype, kind)

    @functools.partial(jax.jit, donate_argnums=0, static_argnums=3)
    def run_layer(x, tied, w, kind, positions, seq):
        x, margin = layer_and_margin(x, w, cfg, kind, precision, positions,
                                     seq)
        return x, tied | (margin < tie)

    @jax.jit
    def last(x, rows, key):
        return head(x[rows], weights.global_weights(cfg, key, dtype), cfg,
                    precision)

    placed = [_placed(s, r, pad_to, fault) for s, r in zip(sequences, rows)]
    t = -(-max(len(p[0]) for p in placed) // pad_to) * pad_to
    tie = cfg.get("route_tie_margin", 0.0) if precision == "f32" else 0.0
    xs, extra = [], []
    for tokens, positions, seq, _ in placed:
        n = len(tokens)
        padded = np.zeros((t,), np.int32)
        padded[:n] = tokens
        pos = np.arange(t, dtype=np.int32)
        pos[:n] = positions
        pos[n:] = positions[-1] + 1 + np.arange(t - n)
        xs.append((first(padded, key), jnp.zeros((t,), bool)))
        extra.append((pos, tuple(np.int32(v) for v in seq)))
    for i in range(cfg["num_hidden_layers"]):
        kind = weights.kind_of(cfg, i)
        w = layer_weights(key, i, kind)
        xs = [run_layer(x, tied, w, kind, *e) for (x, tied), e in
              zip(xs, extra)]
        del w
    n_rows = -(-max(len(p[3]) for p in placed) // 128) * 128
    out, n_tied = [], 0
    for (x, tied), p in zip(xs, placed):
        r = p[3]
        padded = np.zeros((n_rows,), np.int32)
        padded[:len(r)] = r
        logits = np.array(last(x, padded, key))[:len(r)]
        tied = np.asarray(tied)[r]
        logits[tied] = 0.0
        n_tied += int(tied.sum())
        out.append(logits)
    if tie:
        print(f"reference: abstains on {n_tied} of "
              f"{sum(len(r) for r in rows)} rows (a routing margin under "
              f"{tie})", file=sys.stderr, flush=True)
    return out
