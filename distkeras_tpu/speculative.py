"""Speculative decoding for ``serving.DecodeEngine`` — proposers and
the acceptance rule.

PERF.md §18 measured autoregressive decode at ~94% of nominal HBM
bandwidth: there is no kernel left to win, so every further decode
token/s must come from an ALGORITHM that trades abundant FLOPs for
scarce bandwidth.  Speculative decoding (Leviathan et al. 2023) is
exactly that trade: a cheap PROPOSER guesses the next ``k`` tokens,
and one verification pass of the target model scores all ``k + 1``
positions at once — the per-token cost of the big static cache read
is amortized over every accepted token, and the greedy acceptance
rule makes the output byte-identical to plain decode by construction
(a wrong guess costs FLOPs, never correctness).

Two proposers, per ``DecodeEngine(speculative=...)``:

* ``"ngram"`` — model-free prompt-lookup drafting (Saxena 2023): the
  last ``ngram`` tokens of the slot's prompt+generated ledger are
  matched against the ledger's own history, and the tokens that
  FOLLOWED the most recent earlier occurrence are proposed.  Zero
  extra device memory, zero proposer FLOPs; it wins exactly when the
  output re-treads its context (summarization, code edits, RAG).
* ``"draft"`` — a smaller ``TransformerLM`` sharing the vocab runs
  ``k`` cached T=1 greedy steps per slot per engine step, with its
  own per-pool envelope KV cache.  Draft KV is always
  RECOMPUTE-class state: it is never swapped to host by preemption
  and is rebuilt from the token ledger (one bounded-shape prefill)
  whenever it is invalidated — admission, readmission, weight swap.

The module is engine-agnostic on purpose: ``normalize`` validates the
user-facing config dict, ``ngram_propose`` is pure host-side numpy,
and the draft program factories return jitted callables the engine
owns (trace-time compile counters stay in ``serving`` so the compile
guard sees one counter namespace).
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.generate import _decode_model, decode_step

#: accepted ``proposer`` spellings for ``DecodeEngine(speculative=)``
PROPOSERS = ("ngram", "draft")


def normalize(cfg, *, vocab_size: int, max_len: int) -> Optional[dict]:
    """Validate and normalize a ``speculative=`` engine config.

    Returns ``None`` (speculation off) or a dict with keys
    ``proposer`` (``"ngram"`` | ``"draft"``), ``k`` (proposal window,
    >= 1), ``ngram`` (match length for the n-gram proposer, >= 1),
    and — for the draft proposer — ``draft_model`` (a decode-mode
    ``TransformerLM``) plus ``draft_variables``.  The draft model
    must share the target's vocab (the acceptance rule compares token
    ids) and its ``max_len`` must cover every bucket envelope (its
    per-pool KV cache is cloned at the bucket envelope).
    """
    if cfg is None:
        return None
    if not isinstance(cfg, Mapping):
        raise ValueError(
            f"speculative must be a mapping (or None); got "
            f"{type(cfg).__name__}")
    unknown = set(cfg) - {"proposer", "k", "ngram", "draft_model",
                          "draft_variables"}
    if unknown:
        raise ValueError(
            f"speculative config has unknown keys {sorted(unknown)}; "
            "expected proposer/k/ngram/draft_model/draft_variables")
    proposer = cfg.get("proposer", "ngram")
    if proposer not in PROPOSERS:
        raise ValueError(
            f"speculative proposer must be one of {PROPOSERS}; got "
            f"{proposer!r}")
    k = int(cfg.get("k", 4))
    if k < 1:
        raise ValueError(f"speculative k must be >= 1; got {k}")
    ngram = int(cfg.get("ngram", 2))
    if ngram < 1:
        raise ValueError(
            f"speculative ngram must be >= 1; got {ngram}")
    out = {"proposer": proposer, "k": k, "ngram": ngram,
           "draft_model": None, "draft_variables": None}
    if proposer == "draft":
        if cfg.get("draft_model") is None:
            raise ValueError(
                "speculative proposer 'draft' needs a draft_model")
        if cfg.get("draft_variables") is None:
            raise ValueError(
                "speculative proposer 'draft' needs draft_variables")
        draft = _decode_model(cfg["draft_model"])
        if draft.vocab_size != vocab_size:
            raise ValueError(
                f"draft_model vocab_size={draft.vocab_size} must "
                f"equal the target's ({vocab_size}) — the acceptance "
                "rule compares token ids")
        if draft.max_len < max_len:
            raise ValueError(
                f"draft_model max_len={draft.max_len} must cover the "
                f"target's max_len={max_len} — every bucket envelope "
                "clones a draft cache at its own length")
        out["draft_model"] = draft
        out["draft_variables"] = dict(cfg["draft_variables"])
    return out


def ngram_propose(ledger: np.ndarray, k: int, n: int) -> np.ndarray:
    """Prompt-lookup drafting over one slot's token ledger.

    Matches the ledger's last ``n`` tokens against every earlier
    position (most recent occurrence wins — recency beats frequency
    for repetitive suffixes) and proposes up to ``k`` tokens that
    followed the match.  Returns an int32 array of length 0..k; an
    empty result means "no guess" and the engine falls back to the
    plain single-token verify for that slot this step.
    """
    ledger = np.asarray(ledger, np.int32)
    t = len(ledger)
    if t < n + 1:
        return np.empty((0,), np.int32)
    pat = ledger[t - n:]
    # candidate match starts: the pattern may match anywhere ending
    # strictly before the ledger tail (a match ending at the tail is
    # the pattern itself)
    for s in range(t - n - 1, -1, -1):
        if np.array_equal(ledger[s:s + n], pat):
            lo = s + n
            return ledger[lo:lo + k].copy()
    return np.empty((0,), np.int32)


def make_draft_propose(dec, env: int, k: int, pad_id: int,
                       on_trace=None):
    """Compiled batched draft proposer for one pool: ``k`` cached
    greedy T=1 steps over every slot at once (``slot_pos`` scatter,
    the engine's own step idiom).  Dead slots (``live[s]`` False)
    re-write row ``env - 1`` of the DRAFT cache — harmless by the
    eligibility bound: a live slot's draft rows never reach past
    ``env - 2`` (see ``serving`` — ``rem > k`` plus the routing
    invariant ``t_p + max_new <= env``), so the dead row is never
    read.  Greedy only: speculation requires ``temperature == 0``.

    The scan runs ``k + 1`` steps, one MORE than the proposals it
    returns: step ``k`` writes the k-th proposal's own K/V row and
    its output is discarded.  That keeps the draft-cache invariant
    "rows ``0..L-2`` written, feed token = ledger's last" true after
    EVERY commit length — including full acceptance, where the
    committed ledger reaches one past the last proposal — so the
    engine never needs a variable-length catch-up pass (which would
    break the bounded compiled-program set).

    Returns ``draft_propose(variables, cache, tok, pos, live) ->
    (cache, props)`` with ``props[k, slots]`` int32.  ``on_trace``
    runs at trace time (the engine's compile-guard counter hook).
    """

    def propose_impl(variables, cache, tok, pos, live):
        if on_trace is not None:
            on_trace()
        params = {"params": variables["params"]}

        def body(carry, _):
            cache, tok, pos = carry
            step_pos = jnp.where(live, jnp.minimum(pos, env - 1),
                                 env - 1)
            cache, nxt, _ = decode_step(dec, params, cache, tok,
                                        slot_pos=step_pos,
                                        temperature=0.0)
            nxt = jnp.where(live, nxt, pad_id)
            return (cache, nxt, pos + 1), nxt

        (cache, _, _), props = jax.lax.scan(
            body, (cache, tok, pos), None, length=k + 1)
        return cache, props[:k]

    return propose_impl


def make_draft_prefill(dec, on_trace=None):
    """Compiled draft-cache rebuild for one slot: run the ledger's
    tokens (all but the last — that one is the next step's feed)
    through the draft model from position 0 and install the fresh
    envelope into the pool-shaped draft cache at ``slot``.  The whole
    slot envelope is replaced, so a slot inherited dirty from a
    previous request is clean by construction; right-pad rows sit
    beyond every causal horizon until overwritten (the engine's
    standing prefill argument).

    Returns ``draft_prefill(variables, cache, tokens, slot) ->
    cache`` with ``tokens`` a ``[1, t_pad]`` int32 chunk.
    """

    def prefill_impl(variables, cache, tokens, slot):
        if on_trace is not None:
            on_trace(tokens.shape[1])
        params = {"params": variables["params"]}
        # fresh [1, ...] cache (mutable init), merged over the slot;
        # logits are sliced to one row by decode mode and discarded
        _, st = dec.apply(params, tokens, mutable=["cache"])

        def merge(pool_leaf, new_leaf):
            if jnp.ndim(new_leaf) == 0:  # scalar pos: host-owned
                return pool_leaf
            return jax.lax.dynamic_update_slice(
                pool_leaf, new_leaf,
                (slot,) + (0,) * (new_leaf.ndim - 1))

        return jax.tree_util.tree_map(merge, cache, st["cache"])

    return prefill_impl


def accept_length(proposed: np.ndarray, greedy: np.ndarray) -> int:
    """The greedy acceptance rule: the longest prefix of ``proposed``
    that the target model would itself have generated.  ``greedy[j]``
    is the target's argmax AFTER seeing proposal ``j`` tokens deep
    (``greedy[0]`` follows the committed context alone), so proposal
    ``j`` (0-based) is accepted iff every earlier proposal was and
    ``proposed[j] == greedy[j]``.  Bonus-token logic lives in the
    engine: position ``n`` of ``greedy`` is always committable.
    """
    n = 0
    for j in range(len(proposed)):
        if int(proposed[j]) != int(greedy[j]):
            break
        n += 1
    return n
