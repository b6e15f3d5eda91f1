"""Autoregressive generation for the LM families (``TransformerLM``,
``LatentMoELM``: any model that implements ``DECODE_CONTRACT``) — their
serving path.

The reference predates autoregressive serving entirely (SURVEY.md §0:
MLP/CNN-era workloads; its ``predictors.py`` is one batched forward per
row partition), so this surface has no counterpart to mirror — it is
the natural completion of the rebuild's LM family: training
(``trainers``), batch scoring (``predictors.ModelPredictor``), and now
token generation.

TPU-native shape: the prompt is processed in ONE forward pass that
fills every layer's KV cache (``TransformerLM(decode=True)`` — same
parameters, ``"cache"`` variable collection), then each new token is a
T=1 step inside a ``lax.scan``, so the whole generation compiles to a
single XLA program with static shapes — no per-token Python dispatch,
no retracing across steps.  Greedy (``temperature=0``), temperature,
top-k, and top-p (nucleus) sampling; ``beam_search`` decodes the
highest-scoring continuation over the same machinery.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.models.core import ModelSpec


#: What ``generate()`` and ``serving.DecodeEngine`` ask of a model (the
#: decode contract).  ``TransformerLM`` and ``latent_moe.LatentMoELM``
#: implement it:
#:
#: * ``decode_clone()``: the model in decode mode (same parameters; a
#:   ``"cache"`` collection whose leaves are ``[B, L, ...]`` plus scalar
#:   indices), or an error that says why this model cannot be served;
#: * ``dense_prefill_clone()`` of that: a clone whose multi-token chunks
#:   read the cache, exact at any offset (chunked prefill, verify);
#: * fields ``max_len``, ``vocab_size`` and ``cache_envelope`` (settable
#:   through ``clone``: the cache's length ``L``);
#: * ``apply(variables, tokens, mutable=["cache"], slot_pos=, lengths=,
#:   last_index=, logits_all=)`` returning next-token logits
#:   ``[B, 1, V]`` (every position's with ``logits_all``).  ``lengths``
#:   (optional, ``[B]`` int32, with ``slot_pos`` only) says how many
#:   cache positions of each row the step has to attend over:
#:   ``slot_pos + 1`` for a row whose token is wanted, 0 for a row whose
#:   output nobody reads.  A model may use it to read no further (both
#:   do where ``ops.attention.decode_attention_applies`` takes their
#:   cache) or ignore it; a row of length 0 may come back as anything
#:   finite;
#: * optionally an ``"expert_load"`` collection: one ``[E]`` count of
#:   routed tokens an expert layer, read where it is made mutable.
DECODE_CONTRACT = ("decode_clone", "dense_prefill_clone", "max_len",
                   "vocab_size", "cache_envelope")


def _decode_model(model):
    if isinstance(model, Mapping):
        model = ModelSpec.from_config(model).build()
    elif isinstance(model, ModelSpec):
        model = model.build()
    missing = [a for a in DECODE_CONTRACT if not hasattr(model, a)]
    if missing:
        raise TypeError(
            "generate() serves models that implement the decode "
            "contract (TransformerLM, LatentMoELM: "
            "models.generate.DECODE_CONTRACT); "
            f"{type(model).__name__} lacks {missing}")
    return model.decode_clone()


@jax.named_scope("sample")
def _select(logits, temperature, top_k, top_p, rng):
    """Next-token choice from ``[B, V]`` logits (f32).

    Tie behavior of the ``top_p`` filter: every token whose logit
    equals the nucleus-threshold logit is kept, so exact ties can
    admit slightly more than ``top_p`` probability mass (the common
    implementation choice — the kept set is threshold-defined, not
    count-defined)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        # lax.top_k for the kth-largest threshold, not a full-vocab
        # sort — this runs once per decode step
        kth = lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    if top_p is not None:
        # nucleus: keep the smallest prefix of the sorted distribution
        # whose mass reaches top_p (the threshold token included)
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # mask tokens whose PRECEDING cumulative mass already >= top_p
        cut = jnp.sum((cum - probs) < top_p, axis=-1,
                      keepdims=True)                  # tokens kept
        kth = jnp.take_along_axis(sorted_logits, cut - 1, axis=-1)
        logits = jnp.where(logits >= kth, logits, -jnp.inf)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def stacked_expert_load(state: Mapping):
    """``[expert layers, E]`` int32 from the ``"expert_load"`` collection
    a model's apply left in ``state``; ``None`` for a model that sows
    none (every model without routed experts)."""
    leaves = jax.tree_util.tree_leaves(state.get("expert_load", {}))
    return jnp.stack(leaves) if leaves else None


def decode_step(dec, params: Mapping, cache, tok, *, slot_pos=None,
                lengths=None,
                temperature: float = 0.0, top_k: int | None = None,
                top_p: float | None = None, rng=None):
    """One cached T=1 decode step, factored OUT of ``generate``'s
    ``lax.scan`` so a host scheduler can interleave admissions between
    steps (``serving.DecodeEngine``'s continuous-batching contract).

    Args:
      dec: a decode-mode model (``_decode_model`` output or an
        equivalent ``clone(decode=True)``).
      params: ``{"params": ...}`` (cache NOT included).
      cache: the ``"cache"`` collection to advance.
      tok: ``[B]`` int32 — the token each row feeds this step.
      slot_pos: optional ``[B]`` int32 per-slot cache positions
        (continuous batching); None = the scalar-index contract.
      lengths: optional ``[B]`` int32 beside ``slot_pos``: the cache
        positions each row attends over, ``slot_pos + 1``, or 0 for a
        row whose token is thrown away (a finished slot), which then
        reads no cache at all.  A model whose step reads its cache
        through ``ops.attention.decode_attention`` (``LatentMoELM`` and
        ``TransformerLM`` on a TPU, where ``decode_attention_applies``
        takes their cache) stops at these lengths; elsewhere the step
        attends as ``slot_pos`` alone says.
      rng: key for sampling (``temperature > 0``).

    Returns ``(new_cache, next_tok, load)`` with ``next_tok`` ``[B]``
    int32 and ``load`` the step's ``stacked_expert_load`` (``None`` for
    a model without routed experts).  Jit-compatible; ``generate`` runs
    exactly this inside its scan.
    """
    logits, state = dec.apply({**params, "cache": cache}, tok[:, None],
                              slot_pos=slot_pos, lengths=lengths,
                              mutable=["cache", "expert_load"])
    nxt = _select(logits[:, -1].astype(jnp.float32), temperature,
                  top_k, top_p, rng)
    return state["cache"], nxt, stacked_expert_load(state)


def generate(model, variables: Mapping, prompt, *,
             max_new_tokens: int, temperature: float = 0.0,
             top_k: int | None = None, top_p: float | None = None,
             rng=None, eos_id: int | None = None, pad_id: int = 0):
    """Generate ``max_new_tokens`` continuations of ``prompt``.

    Args:
      model: a ``TransformerLM``, its ``ModelSpec``, or a model config
        dict.  Parameters are shared with training: pass the trained
        ``variables`` unchanged.  The model's attention spelling
        (``attn``/``flash_attn``/``blockwise_attn``) selects the
        PREFILL kernel for 128-aligned prompt lengths — a long prompt
        runs the same flash/blockwise path training used; unaligned
        prompts and every generated token use cached dense attention
        (never an error).  Custom ``attn_fn`` and ``seq_axis`` are
        training-path contracts and are cleared for serving.
      variables: ``{"params": ...}`` as returned by init/training.
      prompt: ``[B, T_prompt]`` int32 token ids (``T_prompt >= 1``).
      max_new_tokens: number of tokens to append; ``T_prompt +
        max_new_tokens`` must fit the model's ``max_len`` (the KV
        cache and position table size).
      temperature: 0 = greedy argmax; > 0 = softmax sampling.
      top_k: optional sampling restriction to the k highest logits.
      top_p: optional nucleus sampling — restrict to the smallest set
        of tokens whose probability mass reaches ``top_p`` (0, 1];
        composes with ``top_k`` (both filters apply).
      rng: ``jax.random`` key, required when ``temperature > 0``.
      eos_id: optional stop token: rows that emit it are finished —
        the ``eos_id`` itself appears in the output and every later
        position is ``pad_id``.  Shapes stay static (the scan always
        runs ``max_new_tokens`` steps; finished rows just decode
        ignored padding), which is the jit-compatible contract.
      pad_id: filler for positions after ``eos_id`` (default 0).

    Returns:
      ``[B, T_prompt + max_new_tokens]`` int32 — prompt + generated.

    Jit-compatible (wrap in ``jax.jit`` with ``max_new_tokens`` etc.
    closed over); the decode loop is a ``lax.scan`` either way.
    """
    dec = _decode_model(model)
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim != 2 or prompt.shape[1] < 1:
        raise ValueError(
            f"prompt must be [B, T_prompt>=1]; got {prompt.shape}")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1; got {max_new_tokens}")
    total = prompt.shape[1] + int(max_new_tokens)
    if total > dec.max_len:
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens "
            f"({max_new_tokens}) = {total} exceeds max_len="
            f"{dec.max_len}")
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 requires an rng key")
    if top_k is not None and not 1 <= top_k <= dec.vocab_size:
        raise ValueError(
            f"top_k={top_k} out of range [1, {dec.vocab_size}]")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p={top_p} out of range (0, 1]")
    if eos_id is not None and not 0 <= eos_id < dec.vocab_size:
        raise ValueError(
            f"eos_id={eos_id} outside vocab [0, {dec.vocab_size})")
    if eos_id is not None and not 0 <= pad_id < dec.vocab_size:
        # the pad token is fed back through the embedding on every
        # post-eos step — an OOB id would be silently gather-clamped
        raise ValueError(
            f"pad_id={pad_id} outside vocab [0, {dec.vocab_size})")
    if rng is None:
        rng = jax.random.key(0)  # unused on the greedy path
    params = {"params": variables["params"]}

    # One pass over the prompt creates and fills every layer's cache.
    logits, state = dec.apply(params, prompt, mutable=["cache"])
    rng, sub = jax.random.split(rng)
    tok = _select(logits[:, -1].astype(jnp.float32), temperature,
                  top_k, top_p, sub)
    done = (jnp.zeros(tok.shape, bool) if eos_id is None
            else tok == eos_id)

    def step(carry, _):
        cache, tok, rng, done = carry
        rng, sub = jax.random.split(rng)
        cache, nxt, _ = decode_step(dec, params, cache, tok,
                                    temperature=temperature,
                                    top_k=top_k, top_p=top_p, rng=sub)
        if eos_id is not None:
            nxt = jnp.where(done, pad_id, nxt)
            done = done | (nxt == eos_id)
        return (cache, nxt, rng, done), tok

    if max_new_tokens > 1:
        (_, last, _, _), toks = lax.scan(
            step, (state["cache"], tok, rng, done), None,
            length=max_new_tokens - 1)
        new = jnp.concatenate([toks.T, last[:, None]], axis=1)
    else:
        new = tok[:, None]
    return jnp.concatenate([prompt, new], axis=1)


def _gather_beams(tree, flat_idx):
    """Reindex the batch-leading leaves of a cache/state pytree by
    ``flat_idx`` (scalar leaves — cache_index/pos_index — are shared
    across the batch and pass through)."""
    return jax.tree_util.tree_map(
        lambda x: x[flat_idx] if getattr(x, "ndim", 0) >= 1 else x,
        tree)


def beam_search(model, variables: Mapping, prompt, *,
                max_new_tokens: int, num_beams: int = 4,
                length_penalty: float = 0.0,
                eos_id: int | None = None, pad_id: int = 0):
    """Beam-search decoding: the highest-scoring continuation under
    the model's own log-probabilities.

    Same contract as ``generate`` (KV-cache decode, one compiled
    program, static shapes) with a beam dimension folded into the
    batch: the prompt is prefetched once per beam, every step scores
    ``[B, W*V]`` candidates, keeps the top ``W``, and reorders the
    KV caches and token histories by the surviving beams' parents.
    ``num_beams=1`` reduces exactly to greedy ``generate``.

    Args:
      length_penalty: final scores are divided by
        ``(length ** length_penalty)`` (0 = pure log-prob; > 0 favors
        longer finished sequences, the usual knob when ``eos_id``
        stops beams at different lengths).
      eos_id / pad_id: as in ``generate`` — a beam that emits
        ``eos_id`` is finished: its score freezes and it emits
        ``pad_id`` from then on.

    Returns:
      ``(sequences, scores)``: ``[B, T_prompt + max_new_tokens]``
      int32 and ``[B]`` f32 — the best beam per batch row and its
      (length-penalized) cumulative log-probability.
    """
    dec = _decode_model(model)
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim != 2 or prompt.shape[1] < 1:
        raise ValueError(
            f"prompt must be [B, T_prompt>=1]; got {prompt.shape}")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1; got {max_new_tokens}")
    if num_beams < 1:
        raise ValueError(f"num_beams must be >= 1; got {num_beams}")
    if length_penalty < 0:
        raise ValueError(
            f"length_penalty must be >= 0; got {length_penalty}")
    total = prompt.shape[1] + int(max_new_tokens)
    if total > dec.max_len:
        raise ValueError(
            f"prompt ({prompt.shape[1]}) + max_new_tokens "
            f"({max_new_tokens}) = {total} exceeds max_len="
            f"{dec.max_len}")
    if eos_id is not None and not (0 <= eos_id < dec.vocab_size
                                   and 0 <= pad_id < dec.vocab_size):
        raise ValueError(
            f"eos_id={eos_id}/pad_id={pad_id} outside vocab "
            f"[0, {dec.vocab_size})")
    params = {"params": variables["params"]}
    b, w, v = prompt.shape[0], int(num_beams), dec.vocab_size
    if w > v:
        raise ValueError(f"num_beams={w} exceeds vocab_size={v}")
    n_new = int(max_new_tokens)

    # Prefill ONCE per batch row, then replicate the cache per beam
    # (identical rows would just waste (W-1)/W of the prompt FLOPs).
    logits, state = dec.apply(params, prompt, mutable=["cache"])
    cache0 = jax.tree_util.tree_map(
        lambda x: (jnp.repeat(x, w, axis=0)
                   if getattr(x, "ndim", 0) >= 1 else x),
        state["cache"])
    state = {"cache": cache0}
    logp = jax.nn.log_softmax(
        logits[:, -1].astype(jnp.float32))               # [B, V]
    # first pick: the top-W first tokens of each row's distribution
    scores, tok = lax.top_k(logp, w)                     # [B, W]
    tok = tok.astype(jnp.int32)
    # parents are all beam 0; caches are identical — no gather needed
    done = (tok == eos_id) if eos_id is not None \
        else jnp.zeros((b, w), bool)
    history = jnp.full((b, w, n_new), pad_id, jnp.int32)
    history = history.at[:, :, 0].set(tok)
    length = jnp.ones((b, w), jnp.int32)  # real tokens incl. eos

    def step(carry, t):
        cache, tok, scores, done, history, length = carry
        logits, state = dec.apply({**params, "cache": cache},
                                  tok.reshape(b * w, 1),
                                  mutable=["cache"])
        logp = jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32)).reshape(b, w, v)
        if eos_id is not None:
            # finished beams propose exactly one candidate: pad at
            # unchanged score (0 logprob), everything else -inf
            frozen = jnp.full((v,), -jnp.inf
                              ).at[pad_id].set(0.0)
            logp = jnp.where(done[..., None], frozen[None, None], logp)
        cand = scores[..., None] + logp                  # [B, W, V]
        scores, idx = lax.top_k(cand.reshape(b, w * v), w)
        parent = idx // v                                # [B, W]
        tok = (idx % v).astype(jnp.int32)
        flat_parent = (jnp.arange(b)[:, None] * w + parent).reshape(-1)
        cache = _gather_beams(state["cache"], flat_parent)
        history = jnp.take_along_axis(history, parent[..., None],
                                      axis=1)
        done = jnp.take_along_axis(done, parent, axis=1)
        length = jnp.take_along_axis(length, parent, axis=1)
        if eos_id is not None:
            tok = jnp.where(done, pad_id, tok)
            length = jnp.where(done, length, t + 1)
            done = done | (tok == eos_id)
        else:
            length = length + 1
        history = history.at[:, :, t].set(tok)
        return (cache, tok, scores, done, history, length), None

    if n_new > 1:
        (cache, tok, scores, done, history, length), _ = lax.scan(
            step, (state["cache"], tok, scores, done, history,
                   length),
            jnp.arange(1, n_new))  # noqa: F841 (cache/tok unused)

    if length_penalty > 0.0:
        final = scores / jnp.maximum(length, 1) ** length_penalty
    else:
        final = scores
    best = jnp.argmax(final, axis=1)                     # [B]
    seq = jnp.take_along_axis(
        history, best[:, None, None], axis=1)[:, 0]      # [B, n_new]
    return (jnp.concatenate([prompt, seq], axis=1),
            jnp.take_along_axis(final, best[:, None], axis=1)[:, 0])
