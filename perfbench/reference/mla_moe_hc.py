"""Plain reference of the latent-attention, sparse-expert decoder with a
four-stream constrained residual, as this benchmark runs it.

Straight ``jax.numpy`` in float32 with ``highest`` matmul precision:
nothing imported from the program, no cache (the full forward, attention
in its expanded form only), no batching, experts by a plain loop over all
of them with each token's weight (zero where the expert was not chosen).
Only two things are done in blocks, so that a 6144-token sequence fits:
the queries of attention, and the experts one after the other.

The equations (d = ``hidden_size``; every *assumed* choice is listed in
the configuration file under ``assumed``):

* stream: ``X`` in R^{n x d} a token, the embedding replicated ``n`` =
  ``hc_mult`` times.  Each sublayer F: ``x~ = RMSNorm(vec(X))``;
  ``H~ = a * (x~ Phi) + b`` in three parts (read n, write n, residual
  n x n, one scalar gate ``a`` a part); ``Hpre = sigmoid``, ``Hpost = 2
  sigmoid``, ``Hres = Sinkhorn(clip(.))``: ``exp``, then
  ``hc_sinkhorn_iters`` rounds of dividing rows, then columns, by their
  sums + ``hc_eps``; ``X' = Hres X + Hpost^T F(RMSNorm(Hpre X))``.  After
  the last layer the streams are summed, RMSNorm, untied head.
* attention (MLA): ``c_q = RMSNorm(x W_dq)``, ``q = c_q W_uq`` ->
  [H, nope + rope]; ``[c_kv | k_r] = x W_dkv``, ``c_kv = RMSNorm(c_kv)``;
  rotary on ``q_rope`` and ``k_r`` (pairs interleaved, YaRN frequencies),
  ``k_rope`` shared by all heads; ``[k_nope | v] = c_kv W_ukv``;
  scores ``(q_nope . k_nope + q_rope . k_rope) * (nope + rope)^-0.5 *
  m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``; causal softmax;
  ``(P v) W_o``.
* feed-forward: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; then ``s = sigmoid(x W_r)``, the top
  ``num_experts_per_tok`` of ``s + e_bias`` chosen, weights ``s`` at the
  chosen over their sum times ``routed_scaling_factor``, ``sum_i w_i
  SwiGLU_i(x) + SwiGLU_shared(x)``.  With ``experts_held`` in the
  configuration only those experts' terms are summed: what the absent
  ones would add is left out, as in the program.

``precision`` as in ``reference/gpt2.py``: ``"fp8"`` / ``"bf16"`` round
both operands of every matrix product (the router's and the mixing's
too); accumulation stays float32.  ``"f32+no_routed"`` and
``"f32+expert0_zeroed"`` are planted faults for the readings of
``tools/served_readings.py``: the routed experts' sum left out, the
first expert's output zeroed.

**Where the reference abstains.**  Top-k routing is discontinuous, and a
program in bfloat16 reaches an expert layer with a rounding error in its
stream that moves a router score by about 1e-3 in the first expert layer
and 2e-3 in the fifth (the program against this file, CPU, the published
widths; PERF.md section 6), where the k-th and the (k+1)-th score of a
token lie 0.013 apart in the median: a few tokens in a hundred get
another expert than float32 gives them, whatever the program does, and
such a token's logits are then the model's under the other choice, not
wrong.  Which tokens those can be is decided here, by the float32 scores
alone and not by what the program chose: a token whose k-th and (k+1)-th
score (bias added) lie closer than ``route_tie_margin`` of the
configuration in any expert layer is *tied*, and ``served_logits``
returns a row of zeros for it in float32, so that any served token reads
a gap of 0 there.  The lowered precisions and the faults do not abstain
(their rows stand in for the program's, and are read against the float32
rows).  With the margin at 0, or absent, nothing is tied.
"""

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.gpt2 import _mm
from perfbench.weights import mla_moe_hc as weights

Q_BLOCK = 512


def _f32(w):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _swiglu(x, gate, up, down, precision):
    g = _mm("td,dh->th", x, gate, precision)
    u = _mm("td,dh->th", x, up, precision)
    return _mm("th,hd->td", g * jax.nn.sigmoid(g) * u, down, precision)


def yarn_inv_freq(cfg):
    """Inverse frequencies [rope / 2] of the published YaRN table."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    plain = theta ** (-np.arange(dim // 2, dtype=np.float64) * 2.0 / dim)
    orig = sc["original_max_position_embeddings"]

    def correction(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    # a channel that the ramp leaves at 0 keeps its frequency, one at 1 is
    # interpolated by ``factor``
    return jnp.asarray(plain * (1.0 - ramp) + plain / sc["factor"] * ramp,
                       jnp.float32)


def softmax_scale(cfg):
    sc = cfg["rope_scaling"]
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rotate(x, cos, sin):
    """Pairs (x[2i], x[2i + 1]) of the last axis turned by angle i."""
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def mixing(x, w, cfg, precision):
    """``(Hpre [T, n], Hpost [T, n], Hres [T, n, n])`` for ``x`` [T, n, d]."""
    t, n, d = x.shape
    flat = _rms(x.reshape(t, n * d), w["norm"], cfg["rms_norm_eps"])
    a = jnp.repeat(w["a"], np.array([n, n, n * n]))
    h = _mm("tk,kj->tj", flat, w["phi"], precision) * a + w["b"]
    m = jnp.exp(jnp.clip(h[:, 2 * n:], cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"])).reshape(t, n, n)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(axis=2, keepdims=True) + cfg["hc_eps"])
        m = m / (m.sum(axis=1, keepdims=True) + cfg["hc_eps"])
    return jax.nn.sigmoid(h[:, :n]), 2.0 * jax.nn.sigmoid(h[:, n:2 * n]), m


def attention(x, w, cfg, precision):
    """``x`` [T, d] -> [T, d], causal, the expanded form."""
    t = x.shape[0]
    c, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    eps = cfg["rms_norm_eps"]
    c_q = _rms(_mm("td,dr->tr", x, w["wdq"], precision), w["qn_g"], eps)
    q = _mm("tr,rhk->thk", c_q, w["wuq"], precision)
    down = _mm("td,dc->tc", x, w["wdkv"], precision)
    c_kv = _rms(down[:, :c], w["kvn_g"], eps)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_inv_freq(cfg)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    q_rope = _rotate(q[..., nope:], cos[:, None], sin[:, None])
    k_rope = _rotate(down[:, c:], cos, sin)
    kv = _mm("tc,chk->thk", c_kv, w["wukv"], precision)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(cfg)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(t, lo + Q_BLOCK)
        s = (_mm("qhk,thk->hqt", q[lo:hi, :, :nope], k_nope, precision)
             + _mm("qhk,tk->hqt", q_rope[lo:hi], k_rope, precision)) * scale
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
        outs.append(_mm("hqt,thk->qhk", p, v, precision))
    return _mm("qhk,hkd->qd", jnp.concatenate(outs, axis=0), w["wo"],
               precision)


def route(x, w, cfg, precision):
    """``([T, E] weights, [T] margin)``: a token's weight for each expert,
    zero where it was not chosen, and how far its last chosen score lies
    above the best one not chosen."""
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


def experts(x, w, cfg, precision, shared=True, fault=""):
    """The expert layer on ``x`` [T, d]: the held experts one after the
    other, every token through each with its weight, then the shared one.
    Returns the layer's output and the router's margins."""
    h = cfg["moe_intermediate_size"]
    first, count = weights.experts_held(cfg)
    gates, margin = route(x, w, cfg, precision)
    gates = gates[:, first:first + count]
    if fault == "no_routed":
        gates = jnp.zeros_like(gates)
    elif fault == "expert0_zeroed":
        gates = gates.at[:, 0].set(0.0)
    elif fault:
        raise ValueError(f"no planted fault {fault!r}")

    def one(acc, ew):
        w_in, w_down, g = ew
        y = _swiglu(x, w_in[:, :h], w_in[:, h:], w_down, precision)
        return acc + g[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["we_in"], w["we_down"], gates.T))
    if shared:
        y = y + _swiglu(x, w["ws_gate"], w["ws_up"], w["ws_down"],
                        precision)
    return y, margin


def layer(x, w, cfg, dense, precision="f32"):
    """One decoder layer on the stream ``x`` [T, n, d] (float32)."""
    return layer_and_margin(x, w, cfg, dense, precision)[0]


def layer_and_margin(x, w, cfg, dense, precision="f32"):
    """``layer`` and, of an expert layer, its router's margins [T]
    (infinite for a dense layer).  ``precision`` may carry a planted
    fault after a ``+``."""
    precision, _, fault = precision.partition("+")
    w = _f32(w)
    eps = cfg["rms_norm_eps"]
    no_tie = jnp.full(x.shape[:1], jnp.inf)

    def sublayer(x, mix, gain, fn):
        pre, post, res = mixing(x, mix, cfg, precision)
        inp = _rms(jnp.einsum("tn,tnd->td", pre, x), gain, eps)
        out, margin = fn(inp)
        return jnp.einsum("tij,tjd->tid", res, x) \
            + post[:, :, None] * out[:, None, :], margin

    x, _ = sublayer(x, w["hc_attn"], w["ln1_g"],
                    lambda h: (attention(h, w, cfg, precision), no_tie))
    if dense:
        ffn = lambda h: (_swiglu(h, w["w_gate"], w["w_up"],  # noqa: E731
                                 w["w_down"], precision), no_tie)
    else:
        ffn = lambda h: experts(h, w, cfg, precision,  # noqa: E731
                                fault=fault)
    return sublayer(x, w["hc_ffn"], w["ln2_g"], ffn)


def embed(tokens, g, cfg):
    x = g["wte"].astype(jnp.float32)[tokens]               # [T, d]
    return jnp.broadcast_to(x[:, None], (x.shape[0], cfg["hc_mult"],
                                         x.shape[1]))


def head(x, g, cfg, precision="f32"):
    """Logits of the rows ``x`` [r, n, d] of the stream."""
    precision = precision.partition("+")[0]
    y = _rms(x.sum(axis=1), g["lnf_g"].astype(jnp.float32),
             cfg["rms_norm_eps"])
    return _mm("rd,dv->rv", y, g["head_w"].astype(jnp.float32), precision)


def forward(w, tokens, cfg, precision="f32"):
    """Logits [T, V] of one sequence from a whole tree of weights (the
    tests' toy sizes; the cells go layer by layer, below)."""
    x = embed(tokens, w["globals"], cfg)
    for i, lw in enumerate(w["layers"]):
        x = layer(x, lw, cfg, weights.is_dense(cfg, i), precision)
    return head(x, w["globals"], cfg, precision)


def served_logits(cfg, seed, dtype, sequences, rows, precision="f32",
                  pad_to=512):
    """As ``reference/gpt2.served_logits``: logits ``[r_i, V]`` on the host
    at ``rows[i]`` of each sequence, each sequence alone and right-padded
    (to the longest one's multiple of ``pad_to``), the weights made again
    from ``seed`` in ``dtype`` one layer at a time.  In float32 the rows
    of tied tokens are zeros (the module's docstring)."""
    key = weights.seed_key(seed)
    dtype = jnp.dtype(dtype)

    @jax.jit
    def first(tokens, key):
        return embed(tokens, weights.global_weights(cfg, key, dtype), cfg)

    @functools.partial(jax.jit, static_argnums=2)
    def layer_weights(key, i, dense):
        return weights.layer_weights(cfg, key, i, dtype, dense)

    @functools.partial(jax.jit, donate_argnums=0, static_argnums=3)
    def run_layer(x, tied, w, dense):
        x, margin = layer_and_margin(x, w, cfg, dense, precision)
        return x, tied | (margin < tie)

    @jax.jit
    def last(x, rows, key):
        return head(x[rows], weights.global_weights(cfg, key, dtype), cfg,
                    precision)

    # every sequence at the longest one's padded length: four programs a
    # call whatever the sample (a layer program of this model is
    # several MB serialized and the compile cache is small)
    t = min(-(-max(len(s) for s in sequences) // pad_to) * pad_to,
            cfg["n_positions"])
    tie = cfg.get("route_tie_margin", 0.0) if precision == "f32" else 0.0
    xs = []
    for seq in sequences:
        padded = np.zeros((t,), np.int32)
        padded[:len(seq)] = seq
        xs.append((first(padded, key), jnp.zeros((t,), bool)))
    for i in range(cfg["num_hidden_layers"]):
        dense = weights.is_dense(cfg, i)
        w = layer_weights(key, i, dense)
        xs = [run_layer(x, tied, w, dense) for x, tied in xs]
        del w
    n_rows = -(-max(len(r) for r in rows) // 128) * 128
    out, n_tied = [], 0
    for (x, tied), r in zip(xs, rows):
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
