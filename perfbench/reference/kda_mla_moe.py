"""Plain reference of the decoder of KDA linear-attention and latent-
attention layers over sparse experts, as this benchmark runs it.

Straight ``jax.numpy`` in float32 with ``highest`` matmul precision:
nothing imported from the program, no cache, no chunks.  A KDA layer runs
its recurrence token by token (a ``lax.scan`` over the sequence, the
state ``[H, d_k, d_v]`` carried), the MLA layer attends in its expanded
form, and the expert layer loops over its held experts with each token's
weight (zero where the expert was not chosen).  Only the queries of
attention are taken in blocks, so that a 6144-token sequence fits.

The equations (d = ``hidden_size``; every *assumed* choice is listed in
the configuration file under ``assumed``):

* layer: ``x = x + A(RMSNorm(x))``, ``x = x + F(RMSNorm(x))``; after the
  last layer RMSNorm and the untied head.
* KDA (H = ``num_attention_heads`` heads of ``head_dim``): ``[q|k|v] =
  SiLU(conv(x W_qkv))``, the convolution causal and depthwise of width
  ``short_conv_kernel_size`` (``y_t = sum_j w_j u_{t-K+1+j}``); ``q =
  L2norm(q) / sqrt(d_k)``, ``k = L2norm(k)``; ``g = kda_lower_bound
  sigmoid(exp(A_log) (x W_f + dt_bias))``; ``beta = sigmoid(x W_b)``;
  ``S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T``, ``o_t =
  S_t^T q_t``; out ``(RMSNorm(o) * sigmoid(x W_g)) W_o``, the norm over
  all heads at once.
* MLA: ``q = x W_q`` -> [H, nope + rope]; ``[c_kv | k_r] = x W_dkv``,
  ``c_kv = RMSNorm(c_kv)``; rotary (interleaved pairs, ``rope_theta``) on
  the query's rope part and ``k_r``, shared by all heads; ``[k_nope | v]
  = c_kv W_ukv``; causal softmax of ``(q_nope . k_nope + q_rope . k_rope)
  (nope + rope)^-0.5``; each head's output times ``sigmoid(x W_hg)``, then
  ``W_o``.
* feed-forward: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; then ``s = sigmoid(x W_r)``, the
  experts in ``n_group`` groups, a group scored by the sum of its two best
  ``s + e_bias``, the best ``topk_group`` groups kept and the top
  ``num_experts_per_tok`` of ``s + e_bias`` among them chosen, weights
  ``s`` at the chosen over their sum times ``routed_scaling_factor``;
  ``sum_i w_i SwiGLU_i(x) + SwiGLU_shared(x)`` over the held experts
  (``experts_held``: what the others would add is left out, as in the
  program).

``precision`` as in ``reference/mla_moe_hc.py``: ``"fp8"`` / ``"bf16"``
round both operands of every matrix product (the recurrence keeps its
float32 state: its precision is stated on its own, ``linear_state_dtype``).
After a ``+`` a planted fault, for ``tools/served_readings.py``:

* ``no_routed``: the routed experts' sum left out;
* ``no_decay``: the KDA decay gate left out (``g = 0``);
* ``state_reset``: the KDA state set to zero where the answer starts
  (the state that a prefill hands to the decode steps lost);
* ``pad_absorbed``: the prompt's padding to a multiple of ``pad_to``
  (the engine's ``prefill_align``) taken into every KDA layer's state and
  convolution, as a prefill that ran past the prompt's end would; the
  latent attention masks it and the answer's positions go on from the
  prompt's end, as in the program.

**Where the reference abstains** (``route_tie_margin`` of the
configuration, in float32 alone): a token whose 8th and 9th biased score
among the kept groups, or whose 4th and 5th group score, lie closer than
the margin in any expert layer gets a row of zeros, so that any served
token reads a gap of 0 there (the program's bfloat16 rounding may give it
the other choice; ``reference/mla_moe_hc.py`` says why).
"""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.gpt2 import _mm
from perfbench.weights import kda_mla_moe as weights

Q_BLOCK = 512
_HI = jax.lax.Precision.HIGHEST


def _f32(w):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _swiglu(x, gate, up, down, precision):
    g = _mm("td,dh->th", x, gate, precision)
    u = _mm("td,dh->th", x, up, precision)
    return _mm("th,hd->td", g * jax.nn.sigmoid(g) * u, down, precision)


def kda(x, w, cfg, precision, reset_at, fault=""):
    """``x`` [T, d] -> [T, d], every token through the recurrence;
    ``reset_at``: the token before which the state is zeroed (-1:
    none)."""
    t = x.shape[0]
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    hd = h * dk
    kw = cfg["short_conv_kernel_size"]
    u = _mm("td,dc->tc", x, w["w_qkv"], precision)
    u = jnp.concatenate([jnp.zeros((kw - 1, 3 * hd)), u], axis=0)
    y = sum(u[j:j + t] * w["conv_w"][j] for j in range(kw))
    y = jax.nn.silu(y).reshape(t, 3, h, dk)
    q = _l2(y[:, 0]) * dk ** -0.5
    k = _l2(y[:, 1])
    v = y[:, 2]
    f = _mm("td,dc->tc", x, w["w_f"], precision).reshape(t, h, dk)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["a_log"])[:, None] * (f + w["dt_bias"].reshape(h, dk)))
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_mm("td,dh->th", x, w["w_b"], precision))

    def token(s, inp):
        qi, ki, vi, gi, bi, i = inp
        s = jnp.where(i == reset_at, 0.0, s) * jnp.exp(gi)[:, :, None]
        kv = jnp.einsum("hk,hkv->hv", ki, s, precision=_HI)
        s = s + ki[:, :, None] * (bi[:, None] * (vi - kv))[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", qi, s, precision=_HI)

    _, o = jax.lax.scan(token, jnp.zeros((h, dk, dk)),
                        (q, k, v, g, beta, jnp.arange(t)))
    o = _rms(o.reshape(t, hd), w["on_g"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(_mm("td,dc->tc", x, w["w_g"], precision))
    return _mm("tc,cd->td", o, w["w_o"], precision)


def _rotate(x, cos, sin):
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def mla(x, w, cfg, precision, positions, live):
    """``x`` [T, d] -> [T, d], the expanded form, causal over the tokens
    that ``live`` [T] marks as keys; ``positions`` [T] are the rotary
    positions."""
    t = x.shape[0]
    c, nope, rope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["qk_rope_head_dim"]
    q = _mm("td,dhk->thk", x, w["wq"], precision)
    down = _mm("td,dc->tc", x, w["wdkv"], precision)
    c_kv = _rms(down[:, :c], w["kvn_g"], cfg["rms_norm_eps"])
    inv = float(cfg["rope_theta"]) ** (
        -np.arange(rope // 2, dtype=np.float64) * 2.0 / rope)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv,
                                                               jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    q_rope = _rotate(q[..., nope:], cos[:, None], sin[:, None])
    k_rope = _rotate(down[:, c:], cos, sin)
    kv = _mm("tc,chk->thk", c_kv, w["wukv"], precision)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + rope) ** -0.5
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(t, lo + Q_BLOCK)
        s = (_mm("qhk,thk->hqt", q[lo:hi, :, :nope], k_nope, precision)
             + _mm("qhk,tk->hqt", q_rope[lo:hi], k_rope, precision)) * scale
        seen = (jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]) \
            & live[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        outs.append(_mm("hqt,thk->qhk", p, v, precision))
    o = jnp.concatenate(outs, axis=0) * jax.nn.sigmoid(
        _mm("td,dh->th", x, w["w_hg"], precision))[:, :, None]
    return _mm("qhk,hkd->qd", o, w["wo"], precision)


def route(x, w, cfg, precision):
    """``([T, E] weights, [T] margin)``: a token's weight for each expert,
    zero where it was not chosen, and the smaller of how far its last
    chosen biased score lies above the best one not chosen (among the
    kept groups) and how far its last kept group's score lies above the
    best group dropped."""
    k, n_group, top_g = cfg["num_experts_per_tok"], cfg["n_group"], \
        cfg["topk_group"]
    s = jax.nn.sigmoid(_mm("td,de->te", x, w["router"], precision))
    biased = s + w["e_bias"]
    t, e = s.shape
    margin = jnp.full((t,), jnp.inf)
    if n_group > 1:
        grouped = jnp.sort(biased.reshape(t, n_group, e // n_group),
                           axis=-1)
        group_score = grouped[..., -1] + grouped[..., -2]
        best_g, kept = jax.lax.top_k(group_score, min(top_g + 1, n_group))
        keep = jax.nn.one_hot(kept[:, :top_g], n_group).sum(axis=1) > 0
        biased = jnp.where(jnp.repeat(keep, e // n_group, axis=1), biased,
                           -jnp.inf)
        if top_g < n_group:
            margin = best_g[:, top_g - 1] - best_g[:, top_g]
    best, idx = jax.lax.top_k(biased, k + 1)
    margin = jnp.minimum(margin, best[:, k - 1] - best[:, k])
    chosen = jax.nn.one_hot(idx[:, :k], e, dtype=jnp.float32).sum(axis=1)
    picked = s * chosen
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    return picked * cfg["routed_scaling_factor"], margin


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


FAULTS = ("no_routed", "no_decay", "state_reset", "pad_absorbed")


def layer_and_margin(x, w, cfg, kind, precision="f32", positions=None,
                     live=None, reset_at=-1):
    """One layer of ``kind`` (``weights.kind_of``) on ``x`` [T, d]
    (float32), and of an expert layer its router's margins [T] (infinite
    otherwise).  ``precision`` may carry a planted fault after a ``+``."""
    precision, _, fault = precision.partition("+")
    if fault and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}")
    latent, dense = kind
    t = x.shape[0]
    positions = jnp.arange(t) if positions is None else positions
    live = jnp.ones((t,), bool) if live is None else live
    w = _f32(w)
    eps = cfg["rms_norm_eps"]
    a = _rms(x, w["ln1_g"], eps)
    if latent:
        x = x + mla(a, w, cfg, precision, positions, live)
    else:
        x = x + kda(a, w, cfg, precision, reset_at, fault)
    a = _rms(x, w["ln2_g"], eps)
    if dense:
        return x + _swiglu(a, w["w_gate"], w["w_up"], w["w_down"],
                           precision), jnp.full((t,), jnp.inf)
    y, margin = experts(a, w, cfg, precision, fault)
    return x + y, margin


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
    """``(tokens, positions, live, rows, reset_at)`` of one sequence as
    the reference runs it: with ``pad_absorbed`` the prompt's padding is
    put in after the prompt (the prompt is what ends at ``rows[0]``),
    where the KDA layers take it and the latent attention masks it as a
    key (``live``)."""
    seq, rows = np.asarray(seq), np.asarray(rows)
    prompt = int(rows[0]) + 1
    if fault == "pad_absorbed":
        pad = -prompt % pad_to
        tokens = np.concatenate([seq[:prompt], np.zeros(pad, seq.dtype),
                                 seq[prompt:]])
        positions = np.concatenate([np.arange(prompt),
                                    np.full(pad, prompt),
                                    np.arange(prompt, len(seq))])
        live = np.arange(len(tokens)) - prompt
        live = (live < 0) | (live >= pad)
        rows = rows + pad * (rows >= prompt)
    else:
        tokens, positions = seq, np.arange(len(seq))
        live = np.ones(len(seq), bool)
    return tokens, positions, live, rows, \
        prompt if fault == "state_reset" else -1


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
    def run_layer(x, tied, w, kind, positions, live, reset_at):
        x, margin = layer_and_margin(x, w, cfg, kind, precision, positions,
                                     live, reset_at)
        return x, tied | (margin < tie)

    @jax.jit
    def last(x, rows, key):
        return head(x[rows], weights.global_weights(cfg, key, dtype), cfg,
                    precision)

    placed = [_placed(s, r, pad_to, fault) for s, r in zip(sequences, rows)]
    t = -(-max(len(p[0]) for p in placed) // pad_to) * pad_to
    tie = cfg.get("route_tie_margin", 0.0) if precision == "f32" else 0.0
    xs, extra = [], []
    for tokens, positions, live, _, reset_at in placed:
        n = len(tokens)
        padded = np.zeros((t,), np.int32)
        padded[:n] = tokens
        pos = np.zeros((t,), np.int32)
        pos[:n] = positions
        pos[n:] = np.arange(n, t)
        on = np.ones((t,), bool)
        on[:n] = live
        xs.append((first(padded, key), jnp.zeros((t,), bool)))
        extra.append((pos, on, np.int32(reset_at)))
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
