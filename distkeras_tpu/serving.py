"""Continuous-batching decode engine — slot-based LM serving over a
persistent KV cache.

``models.generate`` and ``StreamingGenerator`` are run-to-completion
servers: a micro-batch enters the compiled scan together and leaves
together, so an ``eos``-finished row keeps burning full T=1 steps until
its whole batch drains, and every step pays for the STATIC cache
envelope regardless of the live prefix (the §18 cost law).  Under mixed
prompt/output-length traffic most of the measured decode bandwidth is
spent on drained rows and oversized envelopes.

``DecodeEngine`` is the iteration-level scheduler that fixes both — the
Orca (Yu et al., OSDI '22) / vLLM (Kwon et al., SOSP '23) architecture
adapted to XLA's static-shape world:

* a persistent ``[slots, envelope, KVH, D]`` KV-cache POOL lives on
  device across requests, one pool per ``max_len`` BUCKET (e.g.
  512/1024/2048 envelopes), so a short request never pays a long
  request's static cache; every program is handed the pool (donated)
  and works on it in place: the declared order is the one the step
  computes in, so none re-lays it out (``pool_report``);
* one compiled STEP program per bucket advances every live slot by one
  token (``slot_pos`` per-row cache positions; per-slot eos /
  remaining-token state rides along), ``steps_per_sync`` steps per
  host round-trip;
* one compiled PREFILL program per (bucket, padded prompt length)
  writes an admitted request's prompt into a free slot via
  ``dynamic_update_slice`` — prompts are right-padded to
  ``prefill_align`` so arbitrary lengths hit a bounded set of
  compiled shapes, and the padded rows' K/V are masked by the per-slot
  causal horizon and overwritten by the first generated tokens;
* finished rows are evicted and replaced BETWEEN steps, so steady-state
  serving keeps every slot live and compiles nothing new — ragged
  arrivals reuse the same bounded program set (asserted by
  ``compile_counts`` and the tier-1 compile guard).

Greedy results are bit-identical to ``models.generate`` per request and
independent of admission order (each slot's attention reads only its
own cache rows).  Sampling draws from the engine's step/prefill key
stream, so it is reproducible for a fixed seed and arrival order but
NOT admission-order invariant.

Graceful degradation (the fault-tolerance layer, docs/API.md "Fault
tolerance"): ``queue_bound`` turns the admission queue into Orca-style
load shedding (``submit`` raises ``ShedError`` + counts
``serving_shed_total{reason}`` at the bound); per-request ``deadline``s
expire queued AND live requests into ``error`` results instead of
holding capacity; a poisoned request (failing prefill) errors out
alone (``error`` result key, ``serving_request_errors_total``) without
killing ``step()`` for its slot neighbors; ``drain()`` finishes the
backlog and ``close()`` cancels what remains (every in-flight id comes
back, ``error="engine_closed"``) and releases the device pools.

Prefill reuse + scheduling (ISSUE 8, the two standard fixes for the
remaining hot-path waste):

* ``prefix_cache_bytes`` turns on a SHARED-PREFIX KV CACHE — a
  host-side longest-prefix trie over token ids at ``prefill_align``
  granularity (SGLang's RadixAttention idea, Zheng et al. 2024) whose
  nodes hold ref-counted DEVICE segments (``[1, align, KVH, D]`` per
  cache leaf, envelope-free so one store serves every bucket).  On
  admit, the longest cached prefix is copied device-to-device into
  the slot (``dynamic_update_slice``, zero model FLOPs) and only the
  uncached tail is prefilled; finished requests donate their aligned
  prompt blocks back, LRU-evicted beyond the byte budget with live
  refs pinned.  ``swap_variables`` INVALIDATES the store — cached KV
  under new weights is silently wrong.
* ``prefill_chunk`` turns on CHUNKED PREFILL (Sarathi-Serve, Agrawal
  et al. 2024): prompts prefill as a sequence of chunk-sized compiled
  programs appended into the slot cache, at most one chunk per pool
  per ``step()``, so a max-length prompt costs its live neighbors one
  chunk quantum per token instead of freezing them for the whole
  prefill.  Deadlines are re-checked between chunks.

Both levers preserve greedy parity bit-for-bit (prefix rows are
position-causal, the chunk path runs the exact dense cache read) and
keep the compiled program set bounded; with both off, the legacy
one-shot prefill path is byte-identical to before.

Disaggregated prefill/decode (ISSUE 19): because prefix-store segments
and KV pages are the same ``[1, align, KVH, D]`` blocks, a finished
prefill's cache is a SHIPPABLE currency.  ``export_prefix`` pulls a
prompt's cached blocks out of the store as host arrays,
``import_prefix`` installs a shipped block set into another engine's
store (admission then takes the ordinary prefix-hit path, so the
decode-side tokens are byte-identical to a monolithic engine by
construction), and ``match_blocks`` is the cluster-tier lookup —
check local blocks before asking the prefill pool's store.
``pack_kv_blocks`` / ``unpack_kv_blocks`` are the wire codec (scope
``"kv"``, gather-sent page memoryviews behind a length-prefixed
msgpack meta); ``gateway.PrefillDecodeRouter`` drives the pipeline.

Observability (``distkeras_tpu.telemetry``; no-op until
``telemetry.enable()``): per-bucket ``serving_queue_depth`` /
``serving_slot_occupancy`` gauges, ``serving_ttft_seconds`` /
``serving_latency_seconds`` / ``serving_inter_token_seconds``
histograms (the latter feeds the watchdog's ``inter_token_p99``
signal), token/request/finish counters,
trace-time ``compiles_total{kind,bucket[,padded]}`` (the public face
of ``compile_counts``), and ``evict`` instants on the serving thread's
timeline track.  ``step()`` is one span tree, in the ring while
telemetry is enabled and in the profiler's trace (``dkt:<name>``)
while a ``jax.profiler`` session runs: ``engine_step`` > ``admit`` >
``prefill`` > ``prefill_dispatch`` / ``first_token_sync``;
``engine_step`` > ``decode_step`` > ``decode_dispatch`` /
``decode_fetch``; ``engine_step`` > ``emit``, ``sweep``.  The
prefix/chunk layer adds ``serving_prefix_{hits,misses,evictions,
invalidations}_total``, ``serving_prefill_tokens_saved_total``, the
``serving_prefix_hit_rate`` gauge (an SLO watchdog signal),
``prefix_copy``/``prefill_chunk`` spans, and a ``prefix_invalidate``
flight-recorder event on every store invalidation.  Request timing
stamps (``t_submit``, ``t_admit``, one ``t_tokens`` entry per token,
``t_finish``; always on) all read ``telemetry.now()`` — see
``_finish``.
"""

from __future__ import annotations

import collections
import struct
import threading
from typing import Iterable, Iterator, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import flight_recorder, layouts, paging, telemetry
from distkeras_tpu import speculative as _speculative
from distkeras_tpu.analysis import racecheck
from distkeras_tpu.models.generate import (_decode_model, _select,
                                           decode_step,
                                           stacked_expert_load)
from distkeras_tpu.parallel import transport

_UNSET = object()


class ShedError(RuntimeError):
    """``submit`` refused a request — admission-control load shedding
    (Orca-style: reject at the door under overload instead of letting
    the queue grow without bound).  ``reason`` is the machine-readable
    cause (currently ``"queue_full"``); every shed also increments the
    ``serving_shed_total{reason,bucket}`` counter.  The request never
    entered the engine: resubmit after draining, or drop it."""

    def __init__(self, reason: str, detail: str):
        super().__init__(detail)
        self.reason = reason


def _ceil_to(n: int, align: int) -> int:
    return -(-n // align) * align


# ---------------------------------------------------------------------
# KV page-block wire codec (ISSUE 19, wire scope "kv")
# ---------------------------------------------------------------------
#
# One exported block set travels as ONE transport frame:
#   b"K" + meta_len(8B BE) + pack_obj(meta) + block0 leaves + block1 ...
# where meta carries the prompt, the block count, the exporter's
# weights version, and one shape/dtype template per cache leaf
# (``paging.leaf_templates`` — every block of an export shares them).
# The raw leaf bytes carry NO per-part framing: the receiver slices
# the body by the templates' byte sizes, so the send side can gather-
# send page memoryviews with zero copies (``transport.send_msg_gather``).

_KV_META_HDR = struct.Struct(">Q")


def _np_dtype(name: str) -> np.dtype:
    """``np.dtype`` by name, falling back to the ml_dtypes extension
    types (bfloat16 et al.) that numpy only knows once registered."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def pack_kv_blocks(export: Mapping) -> list:
    """Wire parts for one ``export_prefix`` result, ready for
    ``transport.send_msg_gather`` (or ``b"".join`` for tests).  The
    leaf arrays ride as memoryviews — no ``tobytes`` copies."""
    blocks = export.get("blocks") or []
    meta = {"prompt": np.ascontiguousarray(export["prompt"],
                                           dtype=np.int32),
            "n_blocks": int(len(blocks)),
            "weights_ver": int(export.get("weights_ver", 0)),
            "leaves": (paging.leaf_templates(blocks[0])
                       if blocks else [])}
    mb = transport.pack_obj(meta)
    parts: list = [b"K", _KV_META_HDR.pack(len(mb)), mb]
    for segs in blocks:
        for s in segs:
            # uint8 view: extension dtypes (bfloat16 et al.) have no
            # buffer-protocol format, but their bytes ride fine
            parts.append(np.ascontiguousarray(
                np.asarray(s)).view(np.uint8).data)
    return parts


def unpack_kv_blocks(body) -> dict:
    """Inverse of ``pack_kv_blocks`` over a received frame body
    (bytes or the ``recv_msg_into`` memoryview): returns the export
    dict with host-array blocks.  Rejects a malformed frame loudly —
    a desynced stream must not install garbage KV."""
    body = memoryview(body)
    if body.nbytes < 1 + _KV_META_HDR.size or bytes(body[:1]) != b"K":
        raise ValueError("not a kv page_blocks frame")
    (mlen,) = _KV_META_HDR.unpack(bytes(body[1:1 + _KV_META_HDR.size]))
    off = 1 + _KV_META_HDR.size
    if off + mlen > body.nbytes:
        raise ValueError("kv frame meta overruns the body")
    meta = transport.unpack_obj(body[off:off + mlen])
    off += mlen
    n_blocks = int(meta["n_blocks"])
    tmpls = [(_np_dtype(t["dtype"]),
              tuple(int(d) for d in t["shape"])) for t in meta["leaves"]]
    blocks = []
    for _ in range(n_blocks):
        segs = []
        for dt, shape in tmpls:
            nb = dt.itemsize * int(np.prod(shape, dtype=np.int64))
            if off + nb > body.nbytes:
                raise ValueError("kv frame leaf overruns the body")
            segs.append(np.frombuffer(body[off:off + nb],
                                      dtype=dt).reshape(shape))
            off += nb
        blocks.append(segs)
    if off != body.nbytes:
        raise ValueError(
            f"kv frame length mismatch: parsed {off} of "
            f"{body.nbytes} bytes")
    return {"prompt": np.asarray(meta["prompt"], np.int32),
            "n_blocks": n_blocks,
            "weights_ver": int(meta["weights_ver"]),
            "blocks": blocks}


class _Request:
    __slots__ = ("rid", "prompt", "max_new", "eos_id", "tokens", "meta",
                 "submit_order", "t_submit", "t_admit", "t_tokens",
                 "t_last_tok", "traces_seen",
                 "deadline", "prefix_path", "weights_ver", "tenant",
                 "priority", "pages", "swap", "spec_on")

    def __init__(self, rid, prompt, max_new, eos_id, meta, submit_order,
                 deadline=None, tenant=None, priority=1):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.tokens: list[int] = []
        self.meta = meta
        self.submit_order = submit_order
        self.t_submit = telemetry.now()
        self.t_admit = None            # when _admit took it off its queue
        self.t_tokens: list[float] = []  # host stamp of each token
        self.t_last_tok = None         # inter-token gap anchor
        self.traces_seen = -1          # engine trace total at anchor
        # absolute telemetry.now() expiry (None: no deadline)
        self.deadline = (None if deadline is None
                         else self.t_submit + deadline)
        self.prefix_path: tuple = ()   # pinned store nodes (admit)
        self.weights_ver = -1          # engine weights at prefill time
        self.tenant = tenant           # QoS: quota accounting key
        self.priority = priority       # QoS: 0 (lowest) .. 2 (highest)
        self.pages: list[int] = []     # paged mode: held page ids
        self.swap = None               # parked: host KV / restore plan
        self.spec_on = None            # per-request speculative
        #                                override (None: engine config)

    @property
    def t_first(self) -> Optional[float]:
        return self.t_tokens[0] if self.t_tokens else None

    def times(self) -> dict:
        """The stamps a result carries, beside its ``t_finish``."""
        return {"t_submit": self.t_submit, "t_admit": self.t_admit,
                "t_first": self.t_first, "t_tokens": self.t_tokens}

    def ledger(self, env: Optional[int] = None) -> np.ndarray:
        """The slot's ONE retained-token ledger: prompt + every
        generated token, most-recent-``env`` truncated when an
        envelope is given.  Both consumers — the recompute-preemption
        readmission arm and the n-gram drafter — read exactly this
        (the pre-speculation engine kept two copies of the
        truncation logic)."""
        ext = np.concatenate(
            [self.prompt, np.asarray(self.tokens, np.int32)])
        return ext if env is None else ext[-env:]


class _PrefixNode:
    """One ``prefill_align``-sized block of a cached prefix: the K/V
    rows for its token block as device arrays (one ``[1, align, KVH,
    D|1]`` segment per 4-D cache leaf, in flatten order — envelope-
    free, so one store serves every bucket)."""

    __slots__ = ("key", "parent", "children", "segments", "nbytes",
                 "refs", "last_use")

    def __init__(self, key, parent, segments):
        self.key = key
        self.parent = parent
        self.children: dict = {}
        self.segments = segments
        self.nbytes = sum(int(s.nbytes) for s in segments)
        self.refs = 0
        self.last_use = 0


class _PrefixStore:
    """Host-side longest-prefix index over aligned token-id blocks
    (the RadixAttention idea at ``prefill_align`` granularity): a trie
    whose node at depth ``d`` holds block ``d``'s K/V segments.
    ``match`` walks a prompt's blocks to the longest cached path;
    donation inserts a finished request's blocks (dedup'd);
    ``evict_to_budget`` drops LRU childless unreferenced nodes until
    total bytes fit the budget (live refs are pinned).  Mutated only
    on the engine's stepping thread, except ``clear`` which the
    engine serializes under its admission lock."""

    def __init__(self, align: int, budget: int):
        self.align = align
        self.budget = budget
        self.root = _PrefixNode(None, None, [])
        self.nbytes = 0
        self.n_nodes = 0
        self._clock = 0
        self.hits = self.misses = 0
        self.evictions = self.invalidations = 0
        self.tokens_saved = 0

    def _touch(self, node: _PrefixNode) -> None:
        self._clock += 1
        node.last_use = self._clock

    def match(self, prompt, max_blocks: int) -> list[_PrefixNode]:
        """Longest cached path over ``prompt``'s aligned blocks (at
        most ``max_blocks`` — the caller caps it so at least one true
        token remains to prefill the first-token logits)."""
        node, path, a = self.root, [], self.align
        for b in range(max_blocks):
            child = node.children.get(
                prompt[b * a:(b + 1) * a].tobytes())
            if child is None:
                break
            path.append(child)
            node = child
        for n in path:
            self._touch(n)
        return path

    def insert(self, parent: _PrefixNode, key: bytes,
               segments) -> _PrefixNode:
        node = _PrefixNode(key, parent, segments)
        parent.children[key] = node
        self.nbytes += node.nbytes
        self.n_nodes += 1
        self._touch(node)
        return node

    def evict_to_budget(self) -> int:
        """LRU eviction to the byte budget: only childless nodes with
        zero refs are candidates (an interior node is implicitly
        pinned by its descendants; a refed node by its live
        requests), so eviction cascades leaf-first."""
        evicted = 0
        while self.nbytes > self.budget:
            victim = None

            def walk(node, victim=None):
                for child in node.children.values():
                    if not child.children and child.refs <= 0:
                        if (victim is None
                                or child.last_use < victim.last_use):
                            victim = child
                    else:
                        victim = walk(child, victim)
                return victim

            victim = walk(self.root)
            if victim is None:
                break  # everything left is pinned
            del victim.parent.children[victim.key]
            self.nbytes -= victim.nbytes
            self.n_nodes -= 1
            self.evictions += 1
            evicted += 1
        return evicted

    def clear(self) -> tuple:
        """Drop every cached segment (weight swap / close); returns
        ``(nodes, bytes)`` released.  Live requests keep their slot
        COPIES — only future admissions are affected."""
        n, b = self.n_nodes, self.nbytes
        self.root = _PrefixNode(None, None, [])
        self.n_nodes = 0
        self.nbytes = 0
        self.invalidations += 1
        return n, b


class _Pool:
    """One cache envelope: device pool + per-slot host bookkeeping."""

    __slots__ = ("env", "n_slots", "dec", "cache", "state", "reqs",
                 "step_fn", "prefill_fn", "queue", "chunk_fn",
                 "copy_fn", "extract_fn", "prefilling", "cache_tmpl",
                 "table", "table_np", "spec")

    def __init__(self, env, n_slots, dec):
        self.env = env
        self.n_slots = n_slots
        self.dec = dec
        self.reqs: list[Optional[_Request]] = [None] * n_slots
        self.queue: collections.deque[_Request] = collections.deque()
        # slot -> pending chunk-prefill plan (insertion order = the
        # order step() advances them, one chunk per pool per call)
        self.prefilling: dict = {}

    def live(self) -> bool:
        return any(r is not None for r in self.reqs)

    def decodable(self) -> bool:
        """At least one occupied slot is PAST its prefill — a decode
        step would produce a real token (mid-prefill slots ride along
        as done rows; a pool of only those skips the dispatch)."""
        return any(r is not None and s not in self.prefilling
                   for s, r in enumerate(self.reqs))


class DecodeEngine:
    """Slot-based continuous-batching server for any model that
    implements the decode contract (``models.generate.DECODE_CONTRACT``).

    Args:
      model: such a model, its ``ModelSpec``, or a config dict
        (same contract as ``generate``; GQA / int8-cache / attention
        spellings compose — the prefill runs the model's resolved
        kernel, steps run the cached dense row).
      variables: ``{"params": ...}`` from init/training.
      slots: concurrent requests per bucket (the step program's batch).
      buckets: cache envelopes — ``None`` (one pool at ``max_len``), a
        sequence of envelope lengths (each gets ``slots`` slots), or a
        ``{envelope: slots}`` mapping.  A request is routed to the
        smallest envelope that fits ``padded_prompt + max_new_tokens``;
        per the §18 cost law its steps then pay only that envelope's
        static cache read.
      max_new_tokens: default per-request cap (``submit`` overrides).
      eos_id: default stop token (``submit`` overrides; None = none).
      prefill_align: prompts are right-padded to this multiple before
        prefill, bounding the compiled prefill shapes per bucket to
        ``envelope / prefill_align``.  Pad rows never pollute results:
        the true-last-token logits seed generation (``last_index``) and
        pad K/V sit beyond every live causal horizon until overwritten.
      steps_per_sync: decode steps per compiled dispatch.  1 = admit /
        evict at every token (maximal slot reuse); larger values
        amortize host round-trips at an admission granularity of that
        many tokens (the right lever when dispatch latency is large
        next to a step).
      temperature/top_k/top_p/seed: sampling (0 = greedy, the
        admission-order-invariant mode).
      pad_id: prompt padding + post-eos filler token.
      donate: donate cache/state buffers to the compiled programs so
        the pool is updated in place (default: on for non-CPU
        backends; CPU XLA cannot always honor it and warns).
      queue_bound: bounded admission queue — per-bucket cap on WAITING
        requests.  At the bound, ``submit`` sheds: it raises
        ``ShedError(reason="queue_full")`` and counts
        ``serving_shed_total`` instead of queueing without bound
        (``None``: unbounded, the pre-fault-tolerance behavior).
      deadline: default per-request wall-clock budget in seconds (from
        submit; ``submit(deadline=...)`` overrides per request).  A
        request past its deadline — still queued, mid-prefill, or
        mid-decode — is finished with an ``error`` result instead of
        holding a slot or queue position (``None``: no deadline).
      prefix_cache_bytes: byte budget for the shared-prefix KV store
        (``None``: off).  Admitted prompts reuse the longest cached
        aligned prefix via a device-to-device copy (zero model
        FLOPs); finished requests donate their aligned prompt blocks
        back; LRU eviction beyond the budget skips segments pinned by
        live requests.  ``swap_variables`` invalidates the store.
      prefill_chunk: chunked-prefill quantum in tokens (``None``: off;
        must be a multiple of ``prefill_align``).  Prompts prefill as
        a sequence of at-most-this-long compiled chunk programs, at
        most one chunk per bucket per ``step()`` interleaved with
        decode, bounding live slots' inter-token latency by the chunk
        quantum instead of the longest neighbor prompt.  Deadlines
        are re-checked between chunks.
      kv_pages: number of usable device KV pages (``None``: the legacy
        envelope pools, byte-identical to before).  When set, every
        bucket's slots draw KV memory from ONE shared block-paged pool
        (``distkeras_tpu.paging``): a slot costs its actual token
        count rounded up to a page instead of a whole envelope, so the
        ``cache_envelope x slots`` memory cliff disappears and the
        sustainable concurrency at a fixed byte budget is set by the
        traffic, not the worst case.  Compiled programs gather a
        slot's pages into the envelope layout, run the UNCHANGED
        legacy compute, and scatter back — greedy results stay
        byte-identical to the envelope path.  Every bucket envelope
        must be a multiple of ``page_size``.
      page_size: tokens per KV page (default: ``prefill_align``; must
        equal it while ``prefix_cache_bytes`` is set, so prefix-store
        segments and pages are the same shape and prefix sharing +
        paging are one mechanism).
      preemption: pool-exhaustion policy in paged mode — ``"swap"``
        (default) parks the lowest-priority live request with its
        pages swapped to host memory and restores it page-exact when
        pages free up; ``"recompute"`` parks without saving KV and
        re-prefills prompt + generated tokens at readmission (cheaper
        in host memory, re-pays the prefill FLOPs); ``"none"``
        disables preemption (an exhausted pool sheds the growing
        request with ``error="kv_pages_exhausted"``).
      recompute_below: with ``preemption="swap"``, victims whose
        context (prompt + generated) is at most this many tokens are
        recompute-parked instead of swapped — below the threshold the
        re-prefill is cheaper than the host round-trip (0: always
        swap).
      tenant_quota: per-tenant page cap enforced at admission (int:
        every tenant; mapping: listed tenants, others unbounded;
        ``None``: off).  A quota-blocked request waits in the queue
        while others admit past it — quotas cannot be fixed by
        preemption.
      speculative: speculative-decoding config (``None``: off) — a
        mapping with ``proposer`` (``"ngram"``: model-free
        prompt-lookup over the slot's token ledger; ``"draft"``: a
        smaller same-vocab model with its own per-pool envelope KV),
        ``k`` (proposal window, default 4), ``ngram`` (match length,
        default 2), and for the draft proposer ``draft_model`` +
        ``draft_variables``.  Each step, every eligible slot's
        proposer guesses up to ``k`` tokens and ONE dense verify
        pass scores all ``k + 1`` positions (the chunk-prefill
        machinery with ``logits_all``); the longest prefix the
        target model itself would have produced is committed plus
        one bonus token, the rest rolled back by rewinding the slot
        position (envelope) or freeing tail page-table entries
        (paged) — greedy output is byte-identical to the
        non-speculative engine by construction.  Requires
        ``temperature=0.0`` and ``steps_per_sync=1``; composes with
        chunked prefill, the prefix store, preemption (draft KV is
        recompute-class, never swapped), and ``swap_variables``
        (drafts are invalidated with the weights version).
        ``submit(speculative=False)`` opts a request out.
    """

    def __init__(self, model, variables: Mapping, *, slots: int = 8,
                 buckets=None, max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 prefill_align: int = 128, steps_per_sync: int = 1,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, seed: int = 0,
                 donate: Optional[bool] = None,
                 queue_bound: Optional[int] = None,
                 deadline: Optional[float] = None,
                 prefix_cache_bytes: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 page_size: Optional[int] = None,
                 preemption: str = "swap",
                 recompute_below: int = 0,
                 tenant_quota=None,
                 speculative=None):
        base = _decode_model(model)
        self.max_len = base.max_len
        self.vocab_size = base.vocab_size
        if slots < 1:
            raise ValueError(f"slots must be >= 1; got {slots}")
        if prefill_align < 1:
            raise ValueError(
                f"prefill_align must be >= 1; got {prefill_align}")
        if steps_per_sync < 1:
            raise ValueError(
                f"steps_per_sync must be >= 1; got {steps_per_sync}")
        if max_new_tokens is not None and max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1; got {max_new_tokens}")
        for name, tok in (("eos_id", eos_id), ("pad_id", pad_id)):
            if tok is not None and not 0 <= tok < base.vocab_size:
                raise ValueError(
                    f"{name}={tok} outside vocab [0, {base.vocab_size})")
        if top_k is not None and not 1 <= top_k <= base.vocab_size:
            raise ValueError(
                f"top_k={top_k} out of range [1, {base.vocab_size}]")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p={top_p} out of range (0, 1]")
        if queue_bound is not None and queue_bound < 1:
            raise ValueError(
                f"queue_bound must be >= 1 (or None); got {queue_bound}")
        if deadline is not None and deadline <= 0:
            raise ValueError(
                f"deadline must be positive seconds (or None); got "
                f"{deadline}")
        if prefix_cache_bytes is not None and prefix_cache_bytes < 1:
            raise ValueError(
                f"prefix_cache_bytes must be >= 1 (or None); got "
                f"{prefix_cache_bytes}")
        if prefill_chunk is not None and (
                prefill_chunk < prefill_align
                or prefill_chunk % prefill_align):
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be a positive "
                f"multiple of prefill_align={prefill_align} — chunk "
                "boundaries must land on the padded-shape grid")
        if kv_pages is not None and kv_pages < 1:
            raise ValueError(
                f"kv_pages must be >= 1 (or None); got {kv_pages}")
        if page_size is None:
            page_size = prefill_align
        if page_size < 1:
            raise ValueError(
                f"page_size must be >= 1; got {page_size}")
        if (kv_pages is not None and prefix_cache_bytes is not None
                and page_size != prefill_align):
            raise ValueError(
                f"page_size={page_size} must equal prefill_align="
                f"{prefill_align} while prefix_cache_bytes is set — "
                "prefix-store segments and KV pages must be the same "
                "shape for zero-copy interchange")
        if preemption not in ("swap", "recompute", "none"):
            raise ValueError(
                f"preemption must be 'swap', 'recompute', or 'none'; "
                f"got {preemption!r}")
        if recompute_below < 0:
            raise ValueError(
                f"recompute_below must be >= 0 tokens; got "
                f"{recompute_below}")
        if tenant_quota is not None and not isinstance(
                tenant_quota, Mapping) and int(tenant_quota) < 1:
            raise ValueError(
                f"tenant_quota must be >= 1 pages (or a mapping, or "
                f"None); got {tenant_quota}")
        spec = _speculative.normalize(speculative,
                                      vocab_size=self.vocab_size,
                                      max_len=self.max_len)
        if spec is not None:
            if float(temperature) != 0.0:
                raise ValueError(
                    "speculative decoding requires temperature=0.0 — "
                    "the acceptance rule is the greedy one (byte-"
                    f"identical output); got {temperature}")
            if steps_per_sync != 1:
                raise ValueError(
                    "speculative decoding requires steps_per_sync=1 — "
                    "a verify already commits up to k+1 tokens per "
                    f"host sync; got {steps_per_sync}")
        if buckets is None:
            buckets = {self.max_len: slots}
        elif isinstance(buckets, Mapping):
            buckets = dict(buckets)
        else:
            buckets = {int(env): slots for env in buckets}
        if len(buckets) == 0:
            raise ValueError("buckets must name at least one envelope")
        for env, n in buckets.items():
            if not 0 < env <= self.max_len:
                raise ValueError(
                    f"bucket envelope {env} outside (0, max_len="
                    f"{self.max_len}]")
            if n < 1:
                raise ValueError(
                    f"bucket {env} needs >= 1 slots; got {n}")
            if kv_pages is not None and env % page_size:
                raise ValueError(
                    f"bucket envelope {env} is not a multiple of "
                    f"page_size={page_size} — the paged gather/"
                    "scatter needs a whole number of pages per "
                    "envelope")
        self.variables = dict(variables)  # guarded-by: _lock
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.pad_id = int(pad_id)
        self.prefill_align = int(prefill_align)
        self.steps_per_sync = int(steps_per_sync)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.queue_bound = queue_bound
        self.deadline = deadline
        self.prefix_cache_bytes = prefix_cache_bytes
        self.prefill_chunk = (None if prefill_chunk is None
                              else int(prefill_chunk))
        # either lever routes admission through the segmented path;
        # with both off the legacy one-shot prefill is untouched
        self._segmented = (prefix_cache_bytes is not None
                           or prefill_chunk is not None)
        self._prefix = (_PrefixStore(self.prefill_align,
                                     int(prefix_cache_bytes))
                        if prefix_cache_bytes is not None else None)
        self.kv_pages = kv_pages
        self.page_size = int(page_size)
        self.preemption = preemption
        self.recompute_below = int(recompute_below)
        self._paged = kv_pages is not None
        self._alloc = (paging.PageAllocator(kv_pages, self.page_size,
                                            tenant_quota)
                       if self._paged else None)
        self._pages = None       # shared device page pool (paged mode)
        self._parked = []        # preempted, awaiting readmission
        self._page_copy_fn = None
        self._page_extract_fn = None
        self._weights_ver = 0  # guarded-by: _lock
        self._spec = spec
        self._spec_proposed = 0  # host mirrors of the spec counters
        self._spec_accepted = 0
        if spec is not None and spec["draft_model"] is not None:
            # device_put once: the draft weights ride every propose/
            # prefill dispatch and must not re-transfer per call
            spec["draft_variables"] = jax.tree_util.tree_map(
                jnp.asarray, spec["draft_variables"])
        self._key = jax.random.key(seed)
        self._n_rng = 0
        self._n_submitted = 0
        self._inflight: set = set()  # rids queued or in a slot
        # Admission lock: ``submit()`` is safe from any thread — it
        # serializes the queue/rid/dedup mutations against the
        # stepping thread's admission sweep (which pops under the same
        # lock but prefills OUTSIDE it, so submitters never wait on a
        # compiled program).  ``step()`` itself must still run on one
        # thread at a time — the gateway's ``EngineReplica`` gives
        # every engine a single driver thread by construction.
        self._lock = racecheck.rlock("serving.engine")
        self._closed = False  # guarded-by: _lock
        self._traces: collections.Counter = collections.Counter()
        self._expert_load = None  # [expert layers, E], once seen
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)
        self._pools = []
        for env in sorted(buckets):
            dec = base if env == self.max_len else base.clone(
                cache_envelope=env)
            pool = _Pool(env, buckets[env], dec)
            self._init_pool(pool)
            self._pools.append(pool)

    # ---- compiled programs -------------------------------------------

    def _init_pool(self, pool: _Pool) -> None:
        s = pool.n_slots
        shapes = jax.eval_shape(
            lambda v: pool.dec.apply(v, jnp.zeros((s, 1), jnp.int32),
                                     mutable=["cache"]),
            {"params": self.variables["params"]})[1]["cache"]
        pool.cache_tmpl = shapes
        if self._paged:
            # no per-bucket envelope pool: slots read/write the shared
            # page pool through their table rows (all entries start at
            # the garbage page)
            pool.cache = None
            pool.table_np = np.zeros(
                (s, pool.env // self.page_size), np.int32)
            pool.table = jnp.asarray(pool.table_np)
            if self._pages is None:  # KVH/page/D are bucket-invariant
                self._pages = paging.build_pool(
                    shapes, self.kv_pages, self.page_size)
        else:
            pool.cache = jax.tree_util.tree_map(
                lambda sh: jnp.zeros(sh.shape, sh.dtype), shapes)
            pool.table = pool.table_np = None
            telemetry.instant("pool_layout", bucket=pool.env,
                              layout=layouts.describe(pool.cache))
        pool.state = {
            "tok": jnp.full((s,), self.pad_id, jnp.int32),
            "pos": jnp.zeros((s,), jnp.int32),
            "n_left": jnp.zeros((s,), jnp.int32),
            "eos": jnp.full((s,), -1, jnp.int32),
            "done": jnp.ones((s,), bool),
        }
        pool.step_fn = self._make_step(pool)
        pool.prefill_fn = self._make_prefill(pool)
        pool.chunk_fn = (self._make_chunk_prefill(pool)
                         if self._segmented else None)
        if self._spec is not None:
            k = self._spec["k"]
            pool.spec = {"verify_fns": {
                w: self._make_verify(pool, w) for w in (1, k + 1)}}
            if self._spec["draft_model"] is not None:
                pool.spec.update(self._init_draft(pool))
        else:
            pool.spec = None
        if self._paged:
            # paged prefix install/donation go page-direct (bucket-
            # independent shapes: ONE compiled pair for all pools)
            pool.copy_fn = pool.extract_fn = None
            if (self._prefix is not None
                    and self._page_copy_fn is None):
                self._page_copy_fn = self._make_page_copy()
                self._page_extract_fn = self._make_page_extract()
        else:
            pool.copy_fn = (self._make_prefix_copy(pool)
                            if self._prefix is not None else None)
            pool.extract_fn = (self._make_prefix_extract(pool)
                               if self._prefix is not None else None)

    def _make_step(self, pool: _Pool):
        dec, env = pool.dec, pool.env
        temp, top_k, top_p = self.temperature, self.top_k, self.top_p
        pad_id, n_sub = self.pad_id, self.steps_per_sync

        def step_core(variables, cache, state, rng):
            params = {"params": variables["params"]}

            def body(carry, sub):
                cache, st = carry
                fin = st["done"]
                # done slots re-write their last row (dead data, kept
                # in range so live rows never see the NaN poison)
                step_pos = jnp.minimum(st["pos"], env - 1)
                cache, nxt, load = decode_step(
                    dec, params, cache, st["tok"], slot_pos=step_pos,
                    temperature=temp, top_k=top_k, top_p=top_p,
                    rng=sub)
                eos_hit = (st["eos"] >= 0) & (nxt == st["eos"])
                nxt = jnp.where(fin, pad_id, nxt)
                n_left = jnp.where(fin, st["n_left"],
                                   st["n_left"] - 1)
                st = {"tok": nxt,
                      "pos": jnp.where(fin, st["pos"], st["pos"] + 1),
                      "n_left": n_left,
                      "eos": st["eos"],
                      "done": fin | eos_hit | (n_left <= 0)}
                return (cache, st), (nxt, fin, load)

            (cache, state), (toks, was_done, load) = jax.lax.scan(
                body, (cache, state), jax.random.split(rng, n_sub))
            # toks[k, s] is real iff the slot was live ENTERING sub-
            # step k (was_done[k, s] False); the host replays exactly
            # this predicate.  load: the rows routed to each expert,
            # [expert layers, E] over the sub-steps and over every row
            # the program computed, done slots' too (None for a model
            # without routed experts)
            if load is not None:
                load = load.sum(axis=0)
            return cache, state, toks, was_done, load

        if not self._paged:
            def step_impl(variables, cache, state, rng):
                # Python side effects: run at TRACE time only, so
                # these count compilations — the compile-guard test's
                # probe.  The registry counter sees only compiles that
                # happen while telemetry is enabled (enable before
                # construction).
                self._traces["step", env] += 1
                telemetry.metrics().counter(
                    "compiles_total", kind="step", bucket=env).inc()
                return step_core(variables, cache, state, rng)

            # one name a pool, so that a profile tells the pools'
            # decode programs apart (jit_step_impl_512, ...).  The name
            # is also part of JAX's compile-cache key, which named
            # scopes are not: a persistent cache filled before the
            # scopes existed cannot hand back a program without them.
            step_impl.__name__ = f"step_impl_{env}"
            donate = (1, 2) if self._donate else ()
            return jax.jit(step_impl, donate_argnums=donate)

        tmpl = pool.cache_tmpl

        def paged_step_impl(variables, pages, table, state, rng):
            self._traces["paged_step", env] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="paged_step", bucket=env).inc()
            cache = paging.gather_cache(tmpl, pages, table)
            cache, state, toks, was_done, load = step_core(
                variables, cache, state, rng)
            return (paging.scatter_cache(pages, cache, table), state,
                    toks, was_done, load)

        paged_step_impl.__name__ = f"paged_step_impl_{env}"
        donate = (1, 3) if self._donate else ()
        return jax.jit(paged_step_impl, donate_argnums=donate)

    def _make_prefill(self, pool: _Pool):
        dec, env = pool.dec, pool.env
        temp, top_k, top_p = self.temperature, self.top_k, self.top_p

        def prefill_core(variables, cache, state, prompt, slot,
                         last_idx, n_left0, eos_id, rng):
            params = {"params": variables["params"]}
            logits, st = dec.apply(params, prompt,
                                   mutable=["cache", "expert_load"],
                                   last_index=last_idx)
            tok0 = _select(logits[:, -1].astype(jnp.float32), temp,
                           top_k, top_p, rng)[0]

            def merge(pool_leaf, new_leaf):
                if jnp.ndim(new_leaf) == 0:  # scalar cache/pos index:
                    return pool_leaf         # slot state owns positions
                return jax.lax.dynamic_update_slice(
                    pool_leaf, new_leaf,
                    (slot,) + (0,) * (new_leaf.ndim - 1))

            # the WHOLE envelope is replaced, so a dirty evicted slot
            # is clean by construction on readmission
            with jax.named_scope("prefill_install"):
                cache = jax.tree_util.tree_map(merge, cache,
                                               st["cache"])
            done0 = (n_left0 <= 0) | ((eos_id >= 0) & (tok0 == eos_id))
            state = {
                "tok": state["tok"].at[slot].set(tok0),
                "pos": state["pos"].at[slot].set(last_idx + 1),
                "n_left": state["n_left"].at[slot].set(n_left0),
                "eos": state["eos"].at[slot].set(eos_id),
                "done": state["done"].at[slot].set(done0),
            }
            # padded rows are routed and computed too, and counted
            return cache, state, tok0, stacked_expert_load(st)

        if not self._paged:
            def prefill_impl(variables, cache, state, prompt, slot,
                             last_idx, n_left0, eos_id, rng):
                # trace-time counter: one compile per (bucket, padded
                # prompt length) — the bounded prefill program set
                self._traces["prefill", env, prompt.shape[1]] += 1
                telemetry.metrics().counter(
                    "compiles_total", kind="prefill", bucket=env,
                    padded=prompt.shape[1]).inc()
                return prefill_core(variables, cache, state, prompt,
                                    slot, last_idx, n_left0, eos_id,
                                    rng)

            donate = (1, 2) if self._donate else ()
            return jax.jit(prefill_impl, donate_argnums=donate)

        tmpl = pool.cache_tmpl

        def paged_prefill_impl(variables, pages, table, state, prompt,
                               slot, last_idx, n_left0, eos_id, rng):
            self._traces["paged_prefill", env, prompt.shape[1]] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="paged_prefill", bucket=env,
                padded=prompt.shape[1]).inc()
            cache = paging.gather_cache(tmpl, pages, table)
            cache, state, tok0, load = prefill_core(
                variables, cache, state, prompt, slot, last_idx,
                n_left0, eos_id, rng)
            return (paging.scatter_cache(pages, cache, table), state,
                    tok0, load)

        donate = (1, 3) if self._donate else ()
        return jax.jit(paged_prefill_impl, donate_argnums=donate)

    def _make_chunk_prefill(self, pool: _Pool):
        """One compiled program per (bucket, chunk length) appending a
        mid-prompt chunk into a slot's cache rows ``[start, start+T)``:
        the slot's envelope is sliced out of the pool, the scalar
        cache/pos indices are pointed at ``start``, and a DENSE-
        attention clone runs the chunk (the blocked prefill kernels
        are exact only from an empty cache; the dense cache read is
        exact at ANY offset — rows at/after ``start`` are causally
        masked until this very call overwrites them).  Slot state is
        installed by the FINAL chunk only; until then the slot stays
        ``done`` with its dead-write row parked at ``env - 1``, which
        interleaved decode steps may rewrite harmlessly (a slot reads
        that row only after overwriting it itself)."""
        env = pool.env
        dense = pool.dec.dense_prefill_clone()
        temp, top_k, top_p = self.temperature, self.top_k, self.top_p
        pad_id = self.pad_id

        def chunk_core(variables, cache, state, chunk, slot, start,
                       last_rel, is_final, n_left0, eos_id, rng):
            params = {"params": variables["params"]}

            def pick(leaf):
                if jnp.ndim(leaf) == 0:  # cache/pos index: the offset
                    return jnp.asarray(start, leaf.dtype)
                return jax.lax.dynamic_slice(
                    leaf, (slot,) + (0,) * (leaf.ndim - 1),
                    (1,) + leaf.shape[1:])

            sub = jax.tree_util.tree_map(pick, cache)
            logits, st = dense.apply({**params, "cache": sub}, chunk,
                                     mutable=["cache"],
                                     last_index=last_rel)
            tok0 = _select(logits[:, -1].astype(jnp.float32), temp,
                           top_k, top_p, rng)[0]

            def merge(pool_leaf, new_leaf):
                if jnp.ndim(new_leaf) == 0:
                    return pool_leaf
                return jax.lax.dynamic_update_slice(
                    pool_leaf, new_leaf,
                    (slot,) + (0,) * (new_leaf.ndim - 1))

            # rows outside [start, start+T) of the sub-envelope are
            # the pool's own rows read back unchanged, so the whole-
            # envelope merge equals a chunk-rows-only write
            cache = jax.tree_util.tree_map(merge, cache, st["cache"])
            done0 = (n_left0 <= 0) | ((eos_id >= 0) & (tok0 == eos_id))
            state = {
                "tok": state["tok"].at[slot].set(
                    jnp.where(is_final, tok0, pad_id)),
                "pos": state["pos"].at[slot].set(
                    jnp.where(is_final, start + last_rel + 1,
                              env - 1)),
                "n_left": state["n_left"].at[slot].set(
                    jnp.where(is_final, n_left0, 0)),
                "eos": state["eos"].at[slot].set(
                    jnp.where(is_final, eos_id, -1)),
                "done": state["done"].at[slot].set(
                    jnp.where(is_final, done0, True)),
            }
            return cache, state, tok0

        if not self._paged:
            def chunk_impl(variables, cache, state, chunk, slot,
                           start, last_rel, is_final, n_left0, eos_id,
                           rng):
                t_c = chunk.shape[1]
                self._traces["chunk_prefill", env, t_c] += 1
                telemetry.metrics().counter(
                    "compiles_total", kind="chunk_prefill", bucket=env,
                    padded=t_c).inc()
                return chunk_core(variables, cache, state, chunk,
                                  slot, start, last_rel, is_final,
                                  n_left0, eos_id, rng)

            donate = (1, 2) if self._donate else ()
            return jax.jit(chunk_impl, donate_argnums=donate)

        tmpl = pool.cache_tmpl

        def paged_chunk_impl(variables, pages, table, state, chunk,
                             slot, start, last_rel, is_final, n_left0,
                             eos_id, rng):
            t_c = chunk.shape[1]
            self._traces["paged_chunk_prefill", env, t_c] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="paged_chunk_prefill",
                bucket=env, padded=t_c).inc()
            cache = paging.gather_cache(tmpl, pages, table)
            cache, state, tok0 = chunk_core(
                variables, cache, state, chunk, slot, start, last_rel,
                is_final, n_left0, eos_id, rng)
            return (paging.scatter_cache(pages, cache, table), state,
                    tok0)

        donate = (1, 3) if self._donate else ()
        return jax.jit(paged_chunk_impl, donate_argnums=donate)

    def _make_verify(self, pool: _Pool, width: int):
        """The speculative VERIFY program: one dense-attention pass
        over a ``[1, width]`` chunk — ``[last committed token,
        proposal_1 .. proposal_{width-1}]`` — sliced into the slot's
        envelope at the scalar cache offset (exactly the chunk-
        prefill machinery), but with ``logits_all`` so EVERY
        position's greedy argmax comes back: ``greedy[j]`` is what
        the target model itself generates after proposal ``j`` tokens
        of the window, which is simultaneously the acceptance oracle
        for proposal ``j+1`` and the bonus token when acceptance ends
        at ``j``.  K/V rows for rejected proposals are left in place
        and rolled back by rewinding the slot position — the standing
        write-before-read argument makes the stale rows dead.  Two
        widths exist per bucket (``k + 1`` and the single-token
        fallback), so the compiled program set stays bounded."""
        env = pool.env
        dense = pool.dec.dense_prefill_clone()

        def verify_core(variables, cache, chunk, slot, start):
            params = {"params": variables["params"]}

            def pick(leaf):
                if jnp.ndim(leaf) == 0:  # cache/pos index: the offset
                    return jnp.asarray(start, leaf.dtype)
                return jax.lax.dynamic_slice(
                    leaf, (slot,) + (0,) * (leaf.ndim - 1),
                    (1,) + leaf.shape[1:])

            sub = jax.tree_util.tree_map(pick, cache)
            logits, st = dense.apply({**params, "cache": sub}, chunk,
                                     mutable=["cache"],
                                     logits_all=True)
            greedy = jnp.argmax(logits[0].astype(jnp.float32),
                                axis=-1).astype(jnp.int32)

            def merge(pool_leaf, new_leaf):
                if jnp.ndim(new_leaf) == 0:
                    return pool_leaf
                return jax.lax.dynamic_update_slice(
                    pool_leaf, new_leaf,
                    (slot,) + (0,) * (new_leaf.ndim - 1))

            cache = jax.tree_util.tree_map(merge, cache, st["cache"])
            return cache, greedy

        if not self._paged:
            def verify_impl(variables, cache, chunk, slot, start):
                self._traces["verify", env, width] += 1
                telemetry.metrics().counter(
                    "compiles_total", kind="verify", bucket=env,
                    padded=width).inc()
                return verify_core(variables, cache, chunk, slot,
                                   start)

            donate = (1,) if self._donate else ()
            return jax.jit(verify_impl, donate_argnums=donate)

        tmpl = pool.cache_tmpl

        def paged_verify_impl(variables, pages, table, chunk, slot,
                              start):
            self._traces["paged_verify", env, width] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="paged_verify", bucket=env,
                padded=width).inc()
            cache = paging.gather_cache(tmpl, pages, table)
            cache, greedy = verify_core(variables, cache, chunk,
                                        slot, start)
            return paging.scatter_cache(pages, cache, table), greedy

        donate = (1,) if self._donate else ()
        return jax.jit(paged_verify_impl, donate_argnums=donate)

    def _init_draft(self, pool: _Pool) -> dict:
        """Per-pool draft-proposer state: the draft model cloned at
        the bucket envelope, its own ``[slots, ...]`` ENVELOPE cache
        (never paged — draft KV is recompute-class state, rebuilt
        from the token ledger whenever invalidated, so the paged
        pool's swap machinery has nothing to preserve), host mirrors
        of each slot's draft feed token / position (``dpos == -1``
        means invalid: rebuild before proposing), and the compiled
        propose/prefill programs under the engine's compile guard."""
        s = pool.n_slots
        base = self._spec["draft_model"]
        ddec = (base if pool.env == base.max_len
                else base.clone(cache_envelope=pool.env))
        dshapes = jax.eval_shape(
            lambda v: ddec.apply(v, jnp.zeros((s, 1), jnp.int32),
                                 mutable=["cache"]),
            {"params": self._spec["draft_variables"]["params"]}
        )[1]["cache"]
        dcache = jax.tree_util.tree_map(
            lambda sh: jnp.zeros(sh.shape, sh.dtype), dshapes)
        env, k = pool.env, self._spec["k"]

        def note_step():
            self._traces["draft_step", env] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="draft_step", bucket=env).inc()

        def note_prefill(t_pad):
            self._traces["draft_prefill", env, t_pad] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="draft_prefill", bucket=env,
                padded=t_pad).inc()

        donate = (1,) if self._donate else ()
        return {
            "dec": ddec, "cache": dcache,
            "dtok": np.full((s,), self.pad_id, np.int32),
            "dpos": np.full((s,), -1, np.int32),
            "propose_fn": jax.jit(
                _speculative.make_draft_propose(
                    ddec, env, k, self.pad_id, on_trace=note_step),
                donate_argnums=donate),
            "prefill_fn": jax.jit(
                _speculative.make_draft_prefill(
                    ddec, on_trace=note_prefill),
                donate_argnums=donate),
        }

    def _make_page_copy(self):
        """Prefix-store install in paged mode: write one cached
        ``align``-row segment straight into an allocated page — the
        page IS the slot's block, no envelope in between.  Shapes are
        bucket-invariant, so this is ONE compiled program for the
        whole engine."""
        def page_copy_impl(pages, segments, pid):
            self._traces["page_copy", self.page_size] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="page_copy",
                bucket=self.page_size).inc()
            return [p.at[pid].set(s[0])
                    for p, s in zip(pages, segments)]

        donate = (0,) if self._donate else ()
        return jax.jit(page_copy_impl, donate_argnums=donate)

    def _make_page_extract(self):
        """Prefix donation in paged mode: slice one page out as a
        ``[1, page, KVH, D]`` store segment (fresh buffers — the pool
        keeps its own).  One compiled program for the engine."""
        def page_extract_impl(pages, pid):
            self._traces["page_extract", self.page_size] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="page_extract",
                bucket=self.page_size).inc()
            return [p[pid][None] for p in pages]

        return jax.jit(page_extract_impl)

    def _make_prefix_copy(self, pool: _Pool):
        """Device-to-device install of one cached ``align``-row block
        into a slot (zero model FLOPs — the prefill work the prefix
        cache eliminates).  One trace per bucket."""
        env = pool.env

        def copy_impl(cache, segments, slot, start):
            self._traces["prefix_copy", env] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="prefix_copy",
                bucket=env).inc()
            leaves, treedef = jax.tree_util.tree_flatten(cache)
            segs = iter(segments)
            out = []
            for leaf in leaves:
                if jnp.ndim(leaf) == 0:  # slot state owns positions
                    out.append(leaf)
                    continue
                out.append(jax.lax.dynamic_update_slice(
                    leaf, next(segs),
                    (slot, start) + (0,) * (leaf.ndim - 2)))
            return jax.tree_util.tree_unflatten(treedef, out)

        donate = (0,) if self._donate else ()
        return jax.jit(copy_impl, donate_argnums=donate)

    def _make_prefix_extract(self, pool: _Pool):
        """Slice one ``align``-row block of a slot's cache out for
        donation to the store — NO donation here: the pool keeps its
        buffers, the store gets fresh ones.  One trace per bucket."""
        env, align = pool.env, self.prefill_align

        def extract_impl(cache, slot, start):
            self._traces["prefix_extract", env] += 1
            telemetry.metrics().counter(
                "compiles_total", kind="prefix_extract",
                bucket=env).inc()
            out = []
            for leaf in jax.tree_util.tree_leaves(cache):
                if jnp.ndim(leaf) == 0:
                    continue
                out.append(jax.lax.dynamic_slice(
                    leaf, (slot, start) + (0,) * (leaf.ndim - 2),
                    (1, align) + leaf.shape[2:]))
            return out

        return jax.jit(extract_impl)

    # ---- admission ----------------------------------------------------

    def _route(self, t_p: int, max_new: int) -> _Pool:
        for pool in self._pools:  # ascending envelopes
            t_pad = min(pool.env, _ceil_to(t_p, self.prefill_align))
            if t_p <= t_pad <= pool.env and t_p + max_new <= pool.env:
                return pool
        raise ValueError(
            f"prompt length {t_p} + max_new_tokens {max_new} fits no "
            f"bucket (envelopes "
            f"{[p.env for p in self._pools]}, max_len={self.max_len})")

    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               eos_id=_UNSET, request_id=None, deadline=_UNSET,
               meta: Optional[Mapping] = None, tenant=None,
               priority: int = 1, speculative=None):
        """Queue one request; returns its id (auto-assigned if None).

        ``max_new_tokens``/``eos_id``/``deadline`` default to the
        engine's; the request fails HERE if it fits no bucket, never
        inside a later compiled flush.  A ``request_id`` equal to one
        still in flight is rejected (results would cross-deliver);
        auto-assigned ids skip over in-flight explicit ids.  With
        ``queue_bound`` set, a full admission queue sheds the request
        (``ShedError``) instead of accepting it.

        ``tenant``/``priority`` are the paged-mode QoS keys (accepted
        but inert on the envelope path): admission picks the highest
        priority class (2 > 1 > 0, FIFO within a class), per-tenant
        page quotas are enforced at admission, and on pool exhaustion
        a higher-priority request preempts the lowest-priority live
        one instead of waiting behind it.

        ``speculative`` is the per-request override of the engine's
        speculative-decoding config: ``None`` follows the engine,
        ``False`` opts this request out (it decodes via the
        single-token verify — still byte-identical), ``True`` is an
        explicit opt-in and REQUIRES the engine to be configured
        with ``speculative=`` (rejected here otherwise — a silent
        no-op would hide a misconfigured client).
        """
        if self._closed:
            raise RuntimeError("engine is closed; submit after close()")
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or len(prompt) < 1:
            raise ValueError(
                f"prompt must be a 1-D token-id array; got shape "
                f"{prompt.shape}")
        max_new = (self.max_new_tokens if max_new_tokens is None
                   else max_new_tokens)
        if max_new is None or max_new < 1:
            raise ValueError(
                "max_new_tokens must be >= 1 (set per request or as "
                f"the engine default); got {max_new}")
        eos = self.eos_id if eos_id is _UNSET else eos_id
        if eos is not None and not 0 <= eos < self.vocab_size:
            raise ValueError(
                f"eos_id={eos} outside vocab [0, {self.vocab_size})")
        dl = self.deadline if deadline is _UNSET else deadline
        if dl is not None and dl <= 0:
            raise ValueError(
                f"deadline must be positive seconds (or None); got "
                f"{dl}")
        if not isinstance(priority, int) or not 0 <= priority <= 2:
            raise ValueError(
                f"priority must be an int in 0..2; got {priority!r}")
        if speculative and self._spec is None:
            raise ValueError(
                "submit(speculative=True) needs an engine built with "
                "speculative=...; this engine has speculation off")
        pool = self._route(len(prompt), max_new)
        if self._paged:
            # worst-case page footprint must fit the pool AND the
            # tenant's whole quota, else the request could park
            # forever — reject at the door like an unroutable prompt
            t_p = len(prompt)
            t_pad = min(pool.env, _ceil_to(t_p, self.prefill_align))
            need = max(paging.pages_for(t_pad, self.page_size),
                       paging.pages_for(min(pool.env, t_p + max_new),
                                        self.page_size))
            if need > self.kv_pages:
                raise ValueError(
                    f"request needs {need} KV pages at its max length "
                    f"but the pool has kv_pages={self.kv_pages}")
            quota = self._alloc.quota_for(tenant)
            if quota is not None and need > quota:
                raise ValueError(
                    f"request needs {need} KV pages at its max length "
                    f"but tenant {tenant!r} has a tenant_quota of "
                    f"{quota}")
        m = telemetry.metrics()
        with self._lock:
            if self._closed:
                raise RuntimeError(
                    "engine is closed; submit after close()")
            if (self.queue_bound is not None
                    and len(pool.queue) >= self.queue_bound):
                m.counter("serving_shed_total", reason="queue_full",
                          bucket=pool.env).inc()
                # lint: allow(blocking-call-under-lock): the shed
                # decision and its evidence must be atomic vs a racing
                # drain re-opening admission
                flight_recorder.record("shed", reason="queue_full",
                                       bucket=pool.env)
                raise ShedError(
                    "queue_full",
                    f"bucket {pool.env} admission queue at its bound "
                    f"({self.queue_bound} waiting); request shed — "
                    "resubmit after draining")
            if request_id is None:
                rid = self._n_submitted
                while rid in self._inflight:  # skip in-flight ids
                    rid += 1
            else:
                rid = request_id
                if rid in self._inflight:
                    raise ValueError(
                        f"request_id {rid!r} is already in flight; "
                        "duplicate ids would cross-deliver results")
            req = _Request(rid, prompt, int(max_new), eos,
                           dict(meta or {}), self._n_submitted,
                           deadline=dl, tenant=tenant,
                           priority=priority)
            if speculative is not None:
                req.spec_on = bool(speculative)
            self._n_submitted += 1
            self._inflight.add(rid)
            pool.queue.append(req)
            m.counter("serving_requests_total", bucket=pool.env).inc()
            m.gauge("serving_queue_depth",
                    bucket=pool.env).set(len(pool.queue))
            return req.rid

    def _next_rng(self):
        self._n_rng += 1
        return jax.random.fold_in(self._key, self._n_rng)

    def reset_rng(self) -> None:
        """Rewind the sampling key stream so a replayed workload draws
        the same tokens (only meaningful when the engine is idle; the
        compiled programs and cache pools are untouched)."""
        if self.has_work():
            raise RuntimeError(
                "reset_rng with requests in flight would replay keys "
                "mid-stream; drain the engine first")
        self._n_rng = 0

    def swap_variables(self, variables: Mapping) -> None:
        """Hot weight swap: install a new parameter pytree WITHOUT
        recompiling — the compiled step/prefill programs take the
        weights as an argument, so a same-structure tree reuses every
        cached program (``compile_counts`` is unchanged by a swap; the
        tier-1 swap test pins this).

        The new tree must match the current one exactly in treedef,
        leaf shapes, and dtypes — a mismatch would silently retrace
        (new compiles mid-serving, the §23 bound broken), so it is
        rejected HERE.  The swap takes effect at the next step
        boundary: ``step()``/``_admit`` snapshot ``self.variables``
        once per call, so in-flight requests finish their current
        quantum on the old weights and every later token uses the new
        ones.  Live-slot KV caches are NOT invalidated — a
        mid-request swap serves a hybrid prefix (standard
        rolling-serve semantics); drain the engine first (the
        gateway's rolling update does) when that matters.  The
        PREFIX STORE however IS invalidated: cached prefix K/V was
        computed under the old weights, and reusing it after a swap
        would be silently wrong for every future hit."""
        if self._closed:
            raise RuntimeError("engine is closed; swap after close()")
        new = dict(variables)
        old_leaves, old_def = jax.tree_util.tree_flatten(self.variables)
        new_leaves, new_def = jax.tree_util.tree_flatten(new)
        if old_def != new_def:
            raise ValueError(
                f"swap_variables structure mismatch: engine has "
                f"{old_def}, got {new_def}")
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            o_sh, n_sh = jnp.shape(o), jnp.shape(n)
            o_dt = np.dtype(getattr(o, "dtype", np.asarray(o).dtype))
            n_dt = np.dtype(getattr(n, "dtype", np.asarray(n).dtype))
            if o_sh != n_sh or o_dt != n_dt:
                raise ValueError(
                    f"swap_variables leaf {i} mismatch: engine has "
                    f"{o_sh}/{o_dt}, got {n_sh}/{n_dt} — a swap must "
                    "not retrace the compiled programs")
        # device_put up front (PS centers arrive as read-only host
        # numpy): the step loop then reuses device buffers instead of
        # re-transferring the tree every dispatch
        new = jax.tree_util.tree_map(jnp.asarray, new)
        inval = None
        with self._lock:
            self.variables = new
            # in-flight requests whose KV was (partly) computed under
            # the old weights must not donate it back post-swap
            self._weights_ver += 1
            if self._prefix is not None:
                inval = self._prefix.clear()
            # in-flight DRAFTS are invalidated with the weights
            # version too: every slot's draft cache is rebuilt from
            # its token ledger before the next propose, so no
            # proposal spans the swap boundary
            for pool in self._pools:
                for slot in range(pool.n_slots):
                    self._draft_invalidate(pool, slot)
        telemetry.metrics().counter("serving_weight_swaps_total").inc()
        telemetry.instant("weight_swap")
        flight_recorder.record("weight_swap",
                               leaves=len(new_leaves))
        if inval is not None:
            n_nodes, n_bytes = inval
            telemetry.metrics().counter(
                "serving_prefix_invalidations_total").inc()
            telemetry.instant("prefix_invalidate", nodes=n_nodes,
                              bytes=n_bytes)
            flight_recorder.record("prefix_invalidate",
                                   nodes=n_nodes, bytes=n_bytes,
                                   reason="weight_swap")

    def _note_gauges(self, pool: _Pool) -> None:
        """Per-bucket queue-depth / slot-occupancy gauges — the levels
        an operator correlates with a TTFT spike (no-op while
        telemetry is disabled)."""
        m = telemetry.metrics()
        m.gauge("serving_queue_depth",
                bucket=pool.env).set(len(pool.queue))
        m.gauge("serving_slot_occupancy", bucket=pool.env).set(
            sum(r is not None for r in pool.reqs))
        if self._paged:
            m.gauge("serving_free_pages").set(self._alloc.n_free)

    def _shed_expired_queued(self, pool: _Pool) -> list[dict]:
        """Sweep the admission queue for requests already past their
        deadline — they leave with an ``error`` result instead of
        consuming a prefill + slot they can no longer use."""
        with self._lock:
            if not any(r.deadline is not None for r in pool.queue):
                return []
            now = telemetry.now()
            expired, alive = [], collections.deque()
            for req in pool.queue:
                (expired if req.deadline is not None
                 and now > req.deadline else alive).append(req)
            pool.queue = alive
        m = telemetry.metrics()
        out = []
        for req in expired:
            m.counter("serving_shed_total", reason="deadline",
                      bucket=pool.env).inc()
            out.append(self._finish_error(req, "deadline_exceeded",
                                          pool.env))
        return out

    # ---- paged-mode QoS: pages, preemption, readmission ---------------

    def _pages_needed(self, t_p: int, pool: _Pool) -> int:
        """Initial page footprint of a prompt: its padded prefill
        length (pad rows land in real pages too — they are dead by
        the write-before-read argument, but keeping them covered
        means the whole prefill scatter is page-backed)."""
        t_pad = min(pool.env, _ceil_to(t_p, self.prefill_align))
        return paging.pages_for(t_pad, self.page_size)

    def _alloc_pages(self, n: int, tenant) -> Optional[list]:
        pids = self._alloc.alloc(n, tenant)
        if pids:
            telemetry.metrics().counter(
                "serving_pages_allocated_total").inc(len(pids))
        return pids

    def _release_pages(self, req: _Request, pool: _Pool = None,
                       slot: Optional[int] = None) -> None:
        """Return a request's pages to the allocator and (when it held
        a slot) point the table row back at the garbage page.  Also
        drops any parked host KV.  Idempotent — every terminal path
        funnels through here."""
        if self._paged and req.pages:
            self._alloc.free(req.pages, req.tenant)
            telemetry.metrics().counter(
                "serving_pages_freed_total").inc(len(req.pages))
            req.pages = []
        req.swap = None
        if pool is not None and slot is not None and self._paged:
            pool.table_np[slot] = 0
            pool.table = jnp.asarray(pool.table_np)

    def _set_table_row(self, pool: _Pool, slot: int,
                       pages: list) -> None:
        pool.table_np[slot] = 0
        pool.table_np[slot, :len(pages)] = pages
        pool.table = jnp.asarray(pool.table_np)

    def _pick_queued(self, pool: _Pool) -> Optional[_Request]:
        """QoS admission order: highest priority class first, FIFO
        within a class; quota-blocked requests are skipped (left
        queued) so they never starve the pool for others."""
        with self._lock:
            best = None
            for req in pool.queue:
                if not self._alloc.fits_quota(
                        self._pages_needed(len(req.prompt), pool),
                        req.tenant):
                    continue
                key = (-req.priority, req.submit_order)
                if best is None or key < best[0]:
                    best = (key, req)
            if best is None:
                return None
            pool.queue.remove(best[1])
            return best[1]

    def _pick_victim(self, below: int, exclude=None):
        """Lowest-priority live decodable request strictly below
        priority ``below`` (latest-submitted first within a class) —
        the preemption victim.  Mid-prefill slots are not preempted
        (their restore plan would be partial)."""
        best = None
        for pool in self._pools:
            for slot, req in enumerate(pool.reqs):
                if (req is None or slot in pool.prefilling
                        or req is exclude or req.priority >= below):
                    continue
                key = (req.priority, -req.submit_order)
                if best is None or key < best[0]:
                    best = (key, pool, slot)
        return None if best is None else (best[1], best[2])

    def _preempt(self, pool: _Pool, slot: int, reason: str) -> None:
        """Evict a live request WITHOUT finishing it: swap its pages
        to host memory (or plan a recompute below the threshold /
        under ``preemption="recompute"``), free the pages, and park
        it for readmission.  Restore is page-exact for swap mode, so
        greedy tokens are unchanged through a preempt cycle."""
        req = pool.reqs[slot]
        ctx = len(req.prompt) + len(req.tokens)
        mode = ("recompute" if self.preemption == "recompute"
                or ctx <= self.recompute_below else "swap")
        m = telemetry.metrics()
        if mode == "swap":
            with telemetry.span("page_swap", direction="out",
                                request_id=req.rid,
                                pages=len(req.pages)):
                idx = jnp.asarray(np.asarray(req.pages, np.int32))
                host = jax.device_get(
                    [leaf[idx] for leaf in self._pages])
                st = jax.device_get(
                    {k: v[slot] for k, v in pool.state.items()})
            req.swap = {"mode": "swap", "pool": pool, "pages": host,
                        "state": st, "ver": req.weights_ver}
            m.counter("serving_pages_swapped_total").inc(
                len(req.pages))
        else:
            req.swap = {"mode": "recompute", "pool": pool}
        pool.reqs[slot] = None
        # draft KV is recompute-class: never part of the swap plan
        self._draft_invalidate(pool, slot)
        # parked requests re-match the store at readmission; holding
        # pins while parked would block eviction for no reader
        self._prefix_unpin(req)
        swap_plan = req.swap  # _release_pages clears it
        self._release_pages(req, pool, slot)
        req.swap = swap_plan
        self._parked.append(req)
        m.counter("serving_preemptions_total", reason=reason).inc()
        telemetry.instant("preempt", bucket=pool.env, slot=slot,
                          request_id=req.rid, mode=mode)
        flight_recorder.record("preempt", request_id=req.rid,
                               bucket=pool.env, reason=reason,
                               mode=mode)

    def _reserve_pages(self, req: _Request, n: int) -> bool:
        """Allocate ``n`` pages for an arriving/readmitted request,
        preempting strictly-lower-priority live requests while the
        pool is short (quota shortfalls never preempt — freeing other
        tenants' pages cannot help)."""
        if not self._alloc.fits_quota(n, req.tenant):
            return False
        pids = self._alloc_pages(n, req.tenant)
        while pids is None and self.preemption != "none":
            victim = self._pick_victim(below=req.priority)
            if victim is None:
                return False
            self._preempt(*victim, reason="admission")
            pids = self._alloc_pages(n, req.tenant)
        if pids is None:
            return False
        req.pages = pids
        return True

    def _sweep_parked(self) -> list[dict]:
        """Deadline check for PARKED requests: a preempted request
        waiting for readmission expires exactly like a queued one
        (the pre-paging engine only checked queued and live)."""
        out = []
        if not self._parked:
            return out
        now = telemetry.now()
        m = telemetry.metrics()
        for req in list(self._parked):
            if req.deadline is not None and now > req.deadline:
                self._parked.remove(req)
                env = req.swap["pool"].env
                self._release_pages(req)
                m.counter("serving_shed_total", reason="deadline",
                          bucket=env).inc()
                out.append(self._finish_error(
                    req, "deadline_exceeded", env))
        return out

    def _readmit_parked(self, variables) -> list[dict]:
        """Readmission sweep: parked requests re-enter (highest
        priority first, FIFO within a class) when their pool has a
        free slot and the allocator can cover them.  Swap-mode
        restores are page-exact; a weight swap since preemption
        invalidates the saved KV exactly like the prefix store, so
        those requests recompute from prompt + generated tokens
        under the new weights instead."""
        out = []
        if not self._parked:
            return out
        m = telemetry.metrics()
        for req in sorted(self._parked,
                          key=lambda r: (-r.priority, r.submit_order)):
            pool = req.swap["pool"]
            slot = next(
                (s for s in range(pool.n_slots)
                 if pool.reqs[s] is None and s not in pool.prefilling),
                None)
            if slot is None:
                continue
            # the satellite deadline fix: re-check AT readmission too
            if (req.deadline is not None
                    and telemetry.now() > req.deadline):
                self._parked.remove(req)
                self._release_pages(req)
                m.counter("serving_shed_total", reason="deadline",
                          bucket=pool.env).inc()
                out.append(self._finish_error(
                    req, "deadline_exceeded", pool.env))
                continue
            mode = req.swap["mode"]
            if (mode == "swap"
                    and req.swap["ver"] != self._weights_ver):
                mode = "recompute"  # stale KV: invalidated like the
                #                     prefix store on weight swap
            if mode == "swap":
                n = len(req.swap["pages"][0])
            else:
                ext_len = len(req.prompt) + len(req.tokens)
                n = self._pages_needed(ext_len, pool)
            if not self._reserve_pages(req, n):
                continue  # stays parked; retried next sweep
            self._parked.remove(req)
            m.counter("serving_readmissions_total").inc()
            flight_recorder.record("readmit", request_id=req.rid,
                                   bucket=pool.env, mode=mode,
                                   pages=n)
            if mode == "swap":
                swap, req.swap = req.swap, None
                self._set_table_row(pool, slot, req.pages)
                with telemetry.span("page_swap", direction="in",
                                    request_id=req.rid, pages=n):
                    idx = jnp.asarray(
                        np.asarray(req.pages, np.int32))
                    self._pages = [
                        leaf.at[idx].set(jnp.asarray(h))
                        for leaf, h in zip(self._pages,
                                           swap["pages"])]
                    pool.state = {
                        k: v.at[slot].set(swap["state"][k])
                        for k, v in pool.state.items()}
                pool.reqs[slot] = req
                # the TARGET restore is page-exact; the draft cache
                # for this slot is whatever its last tenant left
                self._draft_invalidate(pool, slot)
            else:
                req.swap = None
                req.weights_ver = self._weights_ver
                # a request preempted past its envelope was rolling
                # over row env-1; recompute keeps the most recent
                # env tokens of the ledger (the rolled state is
                # unrecoverable by construction — swap mode
                # preserves it exactly)
                out.extend(self._prefill_whole(
                    pool, slot, req, variables,
                    prompt_override=req.ledger(pool.env)))
            self._note_gauges(pool)
        return out

    def _grow_pages(self, pool: _Pool) -> list[dict]:
        """Before a decode quantum, extend every live slot's table to
        cover the rows it will write (``pos + steps_per_sync``, capped
        at the envelope) — an uncovered write would scatter real K/V
        onto the garbage page and lose it.  Exhaustion preempts a
        strictly-lower-priority victim; if none exists the grower
        parks ITSELF (swap/recompute) — or, with preemption off, is
        shed with ``error="kv_pages_exhausted"``."""
        out = []
        page = self.page_size
        m = telemetry.metrics()
        for slot in range(pool.n_slots):
            req = pool.reqs[slot]
            if req is None or slot in pool.prefilling:
                continue
            # host mirror of the device pos: prompt + generated - 1
            # (the first generated token came from prefill and is
            # written at pos t_p by the next decode write); live
            # writes this quantum stop at the remaining budget, so
            # growth never demands more pages than submit() validated
            # against kv_pages/quota (dead re-writes past the budget
            # scatter to the garbage page — dead data, never read)
            pos = len(req.prompt) + max(0, len(req.tokens) - 1)
            live = min(self.steps_per_sync,
                       req.max_new - len(req.tokens))
            need = paging.pages_for(min(pool.env, pos + live), page)
            changed = False
            while len(req.pages) < need:
                blocked_quota = not self._alloc.fits_quota(
                    1, req.tenant)
                pids = (None if blocked_quota
                        else self._alloc_pages(1, req.tenant))
                if pids is not None:
                    req.pages.extend(pids)
                    changed = True
                    continue
                if not blocked_quota and self.preemption != "none":
                    victim = self._pick_victim(below=req.priority,
                                               exclude=req)
                    if victim is not None:
                        self._preempt(*victim, reason="growth")
                        continue
                if self.preemption == "none":
                    pool.reqs[slot] = None
                    self._release_pages(req, pool, slot)
                    m.counter("serving_shed_total",
                              reason="kv_pages", bucket=pool.env).inc()
                    out.append(self._finish_error(
                        req, "kv_pages_exhausted", pool.env))
                else:
                    # no lower-priority victim (or quota-blocked):
                    # park SELF until pages free up
                    self._preempt(pool, slot,
                                  reason=("quota" if blocked_quota
                                          else "growth"))
                changed = False
                break
            if changed:
                self._set_table_row(pool, slot, req.pages)
        return out

    def free_pages(self) -> Optional[int]:
        """Free device KV pages right now (``None``: envelope mode).
        Safe to read from any thread — the gateway's ``least_loaded``
        tie-break samples it."""
        return self._alloc.n_free if self._paged else None

    def paging_stats(self) -> dict:
        """Host-side paging/QoS counters (operator introspection; the
        same numbers feed the metrics registry)."""
        if not self._paged:
            return {"enabled": False}
        return {"enabled": True, "parked": len(self._parked),
                "preemption": self.preemption,
                **self._alloc.stats()}

    # ---- admission sweep ----------------------------------------------

    def _admit(self) -> list[dict]:
        finished = []
        # weights are snapshotted ONCE per admission sweep, so a
        # concurrent swap_variables takes effect at the next step
        # boundary, never mid-sweep
        variables = self.variables
        if self._paged:
            finished.extend(self._sweep_parked())
            finished.extend(self._readmit_parked(variables))
        for pool in self._pools:
            finished.extend(self._shed_expired_queued(pool))
            for slot in range(pool.n_slots):
                if pool.reqs[slot] is not None:
                    continue
                if self._paged:
                    req = self._pick_queued(pool)
                    if req is None:
                        break
                    if not self._reserve_pages(
                            req, self._pages_needed(len(req.prompt),
                                                    pool)):
                        with self._lock:  # wait at the head, in order
                            pool.queue.appendleft(req)
                        break
                else:
                    with self._lock:  # pop vs racing submit() appends
                        if not pool.queue:
                            break
                        req = pool.queue.popleft()
                req.t_admit = telemetry.now()
                admit = (self._admit_segmented if self._segmented
                         else self._prefill_whole)
                finished.extend(admit(pool, slot, req, variables))
            self._note_gauges(pool)
        return finished

    def _prefill_whole(self, pool: _Pool, slot: int, req: _Request,
                       variables, prompt_override=None) -> list[dict]:
        """The legacy one-shot prefill: one compiled program writes
        the whole padded prompt into the slot and installs its state
        (byte-identical behavior to the pre-prefix engine — the
        compile guard pins it).  ``prompt_override`` is the recompute
        readmission path: the "prompt" is the original prompt plus
        every token generated before preemption, and the budget
        accounting continues from where the request left off."""
        m = telemetry.metrics()
        self._draft_invalidate(pool, slot)  # new slot tenant
        prompt = (req.prompt if prompt_override is None
                  else prompt_override)
        t_p = len(prompt)
        t_pad = min(pool.env, _ceil_to(t_p, self.prefill_align))
        padded = np.full((1, t_pad), self.pad_id, np.int32)
        padded[0, :t_p] = prompt
        # generation budget left AFTER this prefill's sampled token
        n_left0 = req.max_new - len(req.tokens) - 1
        try:
            with telemetry.span("prefill", bucket=pool.env,
                                slot=slot, padded=t_pad,
                                prompt_tokens=t_p,
                                request_id=req.rid) as sp:
                with telemetry.span("prefill_dispatch"):
                    if self._paged:
                        self._set_table_row(pool, slot, req.pages)
                        (self._pages, pool.state, tok0,
                         load) = pool.prefill_fn(
                            variables, self._pages, pool.table,
                            pool.state, jnp.asarray(padded), slot,
                            t_p - 1, n_left0,
                            -1 if req.eos_id is None else req.eos_id,
                            self._next_rng())
                    else:
                        (pool.cache, pool.state, tok0,
                         load) = pool.prefill_fn(
                            variables, pool.cache, pool.state,
                            jnp.asarray(padded), slot, t_p - 1,
                            n_left0,
                            -1 if req.eos_id is None else req.eos_id,
                            self._next_rng())
                with telemetry.span("first_token_sync"):
                    tok0, load = jax.device_get((tok0, load))
                    tok0 = int(tok0)
                self._note_expert_load(sp, load)
                req.tokens.append(tok0)
                req.t_tokens.append(telemetry.now())
        except Exception as e:
            # Per-request error isolation: a poisoned request is
            # finished with an ``error`` result — its slot stays free
            # and its neighbors keep decoding — instead of the
            # exception killing step() for every slot.  (With buffer
            # donation on, a failure DURING execution can still
            # poison the pool; trace-/dispatch-time failures, the
            # common case, are fully isolated.)
            self._release_pages(req, pool, slot)
            return [self._finish_error(
                req, f"prefill_failed: {e!r}", pool.env)]
        req.t_last_tok = req.t_tokens[-1]
        req.traces_seen = sum(self._traces.values())
        m.counter("serving_tokens_total", bucket=pool.env).inc()
        pool.reqs[slot] = req
        if (len(req.tokens) >= req.max_new
                or req.tokens[-1] == req.eos_id):
            return [self._finish(pool, slot)]
        return []

    def _admit_segmented(self, pool: _Pool, slot: int, req: _Request,
                         variables) -> list[dict]:
        """Prefix-cache + chunked admission: install the longest
        cached prefix by device copy, then plan the uncached tail as
        chunk programs (advanced by ``step()``, one per pool per
        call).  A fully uncached prompt with chunking off falls back
        to the legacy one-shot program — same compiled shapes, same
        admission latency."""
        m = telemetry.metrics()
        self._draft_invalidate(pool, slot)  # new slot tenant
        t_p = len(req.prompt)
        t_pad = min(pool.env, _ceil_to(t_p, self.prefill_align))
        align = self.prefill_align
        start = 0
        if self._prefix is not None:
            store = self._prefix
            path = store.match(req.prompt, (t_p - 1) // align)
            if path:
                start = len(path) * align
                try:
                    with telemetry.span("prefix_copy",
                                        bucket=pool.env, slot=slot,
                                        rows=start,
                                        request_id=req.rid):
                        for b, node in enumerate(path):
                            if self._paged:
                                # page == prefix block: install the
                                # segment into block b's own page
                                self._pages = self._page_copy_fn(
                                    self._pages, node.segments,
                                    req.pages[b])
                            else:
                                pool.cache = pool.copy_fn(
                                    pool.cache, node.segments, slot,
                                    b * align)
                except Exception as e:
                    self._release_pages(req, pool, slot)
                    return [self._finish_error(
                        req, f"prefill_failed: {e!r}", pool.env)]
                for node in path:   # pin: LRU must not evict under us
                    node.refs += 1
                req.prefix_path = tuple(path)
                store.hits += 1
                store.tokens_saved += start
                m.counter("serving_prefix_hits_total",
                          bucket=pool.env).inc()
                m.counter("serving_prefill_tokens_saved_total",
                          bucket=pool.env).inc(start)
            else:
                store.misses += 1
                m.counter("serving_prefix_misses_total",
                          bucket=pool.env).inc()
            m.gauge("serving_prefix_hit_rate").set(
                store.hits / (store.hits + store.misses))
        req.weights_ver = self._weights_ver
        if start == 0 and self.prefill_chunk is None:
            return self._prefill_whole(pool, slot, req, variables)
        padded = np.full((1, t_pad), self.pad_id, np.int32)
        padded[0, :t_p] = req.prompt
        quantum = self.prefill_chunk or (t_pad - start)
        chunks = []
        for c0 in range(start, t_pad, quantum):
            c1 = min(c0 + quantum, t_pad)
            final = c1 == t_pad
            # the true last token always lands in the final chunk
            # (t_p - 1 >= t_pad - align >= its start); non-final
            # chunks take any in-range row — their logits are unused
            last_rel = (t_p - 1 - c0) if final else (c1 - c0 - 1)
            chunks.append((c0, padded[:, c0:c1], last_rel, final))
        pool.reqs[slot] = req
        if self._paged:  # chunk writes must be page-backed from chunk 0
            self._set_table_row(pool, slot, req.pages)
        pool.prefilling[slot] = {"req": req, "chunks": chunks,
                                 "next": 0}
        if self.prefill_chunk is None:
            # prefix-only mode: the single tail program runs NOW, so
            # admission latency matches the legacy path
            return self._advance_prefill(pool, slot, variables)
        return []

    def _advance_prefill(self, pool: _Pool, slot: int,
                         variables) -> list[dict]:
        """Run ONE pending prefill chunk for ``slot``.  The request's
        deadline is re-checked first — between chunks, not only in
        ``_shed_expired_queued`` — so a chunked long prompt cannot
        ride out its own deadline mid-prefill."""
        plan = pool.prefilling[slot]
        req = plan["req"]
        m = telemetry.metrics()
        if req.deadline is not None and telemetry.now() > req.deadline:
            pool.reqs[slot] = None
            del pool.prefilling[slot]
            self._release_pages(req, pool, slot)
            m.counter("serving_shed_total", reason="deadline",
                      bucket=pool.env).inc()
            telemetry.instant("evict", bucket=pool.env, slot=slot,
                              request_id=req.rid)
            return [self._finish_error(req, "deadline_exceeded",
                                       pool.env)]
        c0, chunk, last_rel, final = plan["chunks"][plan["next"]]
        try:
            with telemetry.span("prefill_chunk", bucket=pool.env,
                                slot=slot, start=c0,
                                size=chunk.shape[1], final=final,
                                request_id=req.rid):
                if self._paged:
                    self._pages, pool.state, tok0 = pool.chunk_fn(
                        variables, self._pages, pool.table,
                        pool.state, jnp.asarray(chunk), slot, c0,
                        last_rel, final, req.max_new - 1,
                        -1 if req.eos_id is None else req.eos_id,
                        self._next_rng())
                else:
                    pool.cache, pool.state, tok0 = pool.chunk_fn(
                        variables, pool.cache, pool.state,
                        jnp.asarray(chunk), slot, c0, last_rel, final,
                        req.max_new - 1,
                        -1 if req.eos_id is None else req.eos_id,
                        self._next_rng())
                if final:
                    req.tokens.append(int(tok0))
                    req.t_tokens.append(telemetry.now())
        except Exception as e:
            # same per-request isolation contract as _prefill_whole
            pool.reqs[slot] = None
            del pool.prefilling[slot]
            self._release_pages(req, pool, slot)
            return [self._finish_error(
                req, f"prefill_failed: {e!r}", pool.env)]
        plan["next"] += 1
        if not final:
            return []
        del pool.prefilling[slot]
        req.t_last_tok = req.t_tokens[-1]
        req.traces_seen = sum(self._traces.values())
        m.counter("serving_tokens_total", bucket=pool.env).inc()
        if req.max_new == 1 or req.tokens[-1] == req.eos_id:
            return [self._finish(pool, slot)]
        return []

    def _prefix_unpin(self, req: _Request) -> None:
        """Release the request's live refs on its matched prefix path
        (idempotent: the path is cleared after the first call)."""
        for node in req.prefix_path:
            node.refs -= 1
        req.prefix_path = ()

    def _donate_prefix(self, pool: _Pool, slot: int,
                       req: _Request) -> None:
        """Donate the finished request's prompt K/V back to the store:
        extract each whole ``prefill_align`` block not already cached
        as envelope-free device segments, then evict down to the LRU
        byte budget.  Best-effort — a failure here must never fail the
        request it rides on."""
        store = self._prefix
        align = self.prefill_align
        n = min(len(req.prompt) // align, pool.env // align)
        if self._paged:
            # page_size == prefill_align (enforced in __init__), so
            # block b of the prompt lives exactly in req.pages[b] —
            # donation is a page slice, no envelope extraction
            n = min(n, len(req.pages))
        inserted = False
        try:
            node = store.root
            for b in range(n):
                key = req.prompt[b * align:(b + 1) * align].tobytes()
                child = node.children.get(key)
                if child is None:
                    if self._paged:
                        segs = self._page_extract_fn(
                            self._pages, req.pages[b])
                    else:
                        segs = pool.extract_fn(pool.cache, slot,
                                               b * align)
                    child = store.insert(node, key, segs)
                    inserted = True
                else:
                    store._touch(child)
                node = child
        except Exception:
            return
        if inserted:
            evicted = store.evict_to_budget()
            if evicted:
                telemetry.metrics().counter(
                    "serving_prefix_evictions_total").inc(evicted)

    def prefix_stats(self) -> dict:
        """Host-side prefix-store counters (operator introspection;
        the same numbers feed the metrics registry)."""
        if self._prefix is None:
            return {"enabled": False}
        s = self._prefix
        return {"enabled": True, "hits": s.hits, "misses": s.misses,
                "evictions": s.evictions,
                "invalidations": s.invalidations,
                "tokens_saved": s.tokens_saved, "nodes": s.n_nodes,
                "bytes": s.nbytes, "budget_bytes": s.budget}

    # ---- disaggregated prefill/decode interchange ---------------------
    #
    # The store mutators below follow the store's ownership discipline:
    # call them from the stepping thread only (the gateway replica
    # serializes them through its command mailbox, which IS the
    # stepping thread).

    def match_blocks(self, prompt) -> int:
        """How many leading whole ``prefill_align`` blocks of
        ``prompt`` the local prefix store already holds — the cluster-
        tier probe a decode-side router runs before asking the prefill
        pool's store (and before recomputing)."""
        if self._prefix is None:
            return 0
        prompt = np.ascontiguousarray(prompt, np.int32)
        return len(self._prefix.match(
            prompt, len(prompt) // self.prefill_align))

    def export_prefix(self, prompt) -> Optional[dict]:
        """Pull ``prompt``'s cached prefix blocks out of the store as
        HOST arrays — the prefill side of the disaggregated handoff.
        Returns ``{"prompt", "n_blocks", "weights_ver", "blocks"}``
        (``blocks[b]`` = block ``b``'s segment leaves, outermost
        first in cache-flatten order) or ``None`` when nothing is
        cached.  Pairs with ``pack_kv_blocks`` for the wire."""
        if self._prefix is None:
            return None
        prompt = np.ascontiguousarray(prompt, np.int32)
        path = self._prefix.match(
            prompt, len(prompt) // self.prefill_align)
        if not path:
            return None
        blocks = [[np.asarray(jax.device_get(s)) for s in n.segments]
                  for n in path]
        return {"prompt": prompt, "n_blocks": len(blocks),
                "weights_ver": self._weights_ver, "blocks": blocks}

    def import_prefix(self, prompt, blocks,
                      weights_ver: Optional[int] = None) -> int:
        """Install a shipped block set into the local prefix store —
        the decode side of the handoff.  Admission then takes the
        ordinary prefix-hit path (device copy + tail prefill), which
        existing parity tests pin byte-identical to a monolithic
        engine, so imported KV changes WHERE prefill ran, never what
        tokens come out.  Returns the number of blocks newly
        installed (already-cached blocks are touched, not
        duplicated).  A ``weights_ver`` that does not match the local
        engine's is a STALE export — rejected whole (return 0): KV
        under different weights is silently wrong."""
        if self._prefix is None or not blocks:
            return 0
        if weights_ver is not None and weights_ver != self._weights_ver:
            return 0
        store = self._prefix
        prompt = np.ascontiguousarray(prompt, np.int32)
        align = self.prefill_align
        installed = 0
        node = store.root
        for b, segs in enumerate(blocks):
            key = prompt[b * align:(b + 1) * align].tobytes()
            if len(key) < align * 4:
                break  # ragged tail: never index a partial block
            child = node.children.get(key)
            if child is None:
                child = store.insert(
                    node, key, [jnp.asarray(s) for s in segs])
                installed += 1
            else:
                store._touch(child)
            node = child
        if installed:
            evicted = store.evict_to_budget()
            if evicted:
                telemetry.metrics().counter(
                    "serving_prefix_evictions_total").inc(evicted)
        return installed

    def _finish(self, pool: _Pool, slot: int) -> dict:
        """Evict the finished request and assemble its result dict.

        Timing fields (all from ``telemetry.now()``, the repo's single
        monotonic clock — differences are meaningful, absolute values
        are not):

        * ``t_submit`` — when ``submit()`` queued the request;
        * ``t_admit``  — when ``_admit`` took it off its queue, so the
          queue wait is ``t_admit - t_submit``;
        * ``t_tokens`` — one stamp per generated token, taken where the
          token reached the host: after the first-token sync of the
          prefill, and once per pool per step right after the decode
          fetch (tokens of one fetch share a stamp), so
          ``len(t_tokens) == len(tokens)``;
        * ``t_first``  — ``t_tokens[0]``, i.e. queue-to-first-token is
          ``ttft = t_first - t_submit``;
        * ``t_finish`` — when the finished request was evicted;
          completion latency is ``latency = t_finish - t_submit``.

        The derived ``ttft``/``latency`` keys ride along precomputed.
        Engine-owned keys (including the timing fields above) win over
        same-named meta keys — ordered delivery depends on
        ``request_id`` surviving."""
        req = pool.reqs[slot]
        pool.reqs[slot] = None
        self._inflight.discard(req.rid)
        # unpin FIRST so this request's own path is evictable (but
        # freshly touched) when its donation pushes over budget
        self._prefix_unpin(req)
        if (self._prefix is not None
                and req.weights_ver == self._weights_ver):
            # rows [0, t_p) still hold the prompt's K/V — decode only
            # appended at pos >= t_p — so the slot is donated before
            # the result is assembled.  A weights_ver mismatch means
            # a swap landed mid-request: its KV is hybrid, never
            # donated.
            self._donate_prefix(pool, slot, req)
        # pages go back to the free list AFTER donation — the extract
        # above reads them; freeing never touches device page contents
        # (page data is only overwritten when a new owner writes it)
        self._release_pages(req, pool, slot)
        t_finish = telemetry.now()
        ttft = req.t_first - req.t_submit
        latency = t_finish - req.t_submit
        m = telemetry.metrics()
        m.counter("serving_finished_total", bucket=pool.env).inc()
        m.histogram("serving_ttft_seconds").observe(ttft)
        m.histogram("serving_latency_seconds").observe(latency)
        telemetry.instant("evict", bucket=pool.env, slot=slot,
                          request_id=req.rid)
        return {**req.meta,
                "request_id": req.rid, "prompt": req.prompt,
                "tokens": np.asarray(req.tokens, np.int32),
                **req.times(),
                "t_finish": t_finish, "ttft": ttft,
                "latency": latency}

    def _finish_error(self, req: _Request, error: str,
                      env: int) -> dict:
        """Terminal ERROR result: same shape as ``_finish``'s dict plus
        an ``error`` key (never present on success); ``tokens`` holds
        whatever was generated before the failure, ``ttft`` is None for
        a request that never produced a token.  The request has already
        left its queue/slot."""
        self._inflight.discard(req.rid)
        self._prefix_unpin(req)
        self._release_pages(req)  # safety net: idempotent, no table
        t_finish = telemetry.now()
        m = telemetry.metrics()
        m.counter("serving_request_errors_total", bucket=env).inc()
        telemetry.instant("request_error", bucket=env,
                          request_id=req.rid, error=error)
        # one durable event per terminal error result — covers
        # deadline expiries, poisoned prefills, and engine_closed
        # cancellations through the single exit point they share
        flight_recorder.record("request_error", request_id=req.rid,
                               bucket=env, error=error)
        ttft = (None if req.t_first is None
                else req.t_first - req.t_submit)
        return {**req.meta,
                "request_id": req.rid, "prompt": req.prompt,
                "tokens": np.asarray(req.tokens, np.int32),
                "error": error,
                **req.times(),
                "t_finish": t_finish, "ttft": ttft,
                "latency": t_finish - req.t_submit}

    def _note_inter_token(self, req: _Request, n: int) -> None:
        """Observe the decode-side inter-token gap for ``n`` freshly
        committed tokens: elapsed time since the request's previous
        token, spread evenly over the batch (speculative commits land
        several tokens from one program).  Feeds
        ``serving_inter_token_seconds`` — the histogram behind the
        ``inter_token_p99`` SLO signal and the disaggregation A/B's
        flood-flatness gate."""
        if n <= 0:
            return
        t_now = telemetry.now()
        # a gap that spans a program trace is a compile stall (cold
        # engine, new shape), not decode cadence — recording it would
        # flip a freshly built engine's SLO verdict critical and make
        # rolling_update's health gate roll back a healthy swap
        traces = sum(self._traces.values())
        if req.t_last_tok is not None and traces == req.traces_seen:
            gap = (t_now - req.t_last_tok) / n
            h = telemetry.metrics().histogram(
                "serving_inter_token_seconds")
            for _ in range(n):
                h.observe(gap)
        req.t_last_tok = t_now
        req.traces_seen = traces

    # ---- speculative decode -------------------------------------------

    def _commit_tokens(self, req: _Request,
                       cand: list) -> tuple[int, bool]:
        """Append candidate tokens under the PER-TOKEN stop scan: the
        ``max_new`` clamp and the ``eos_id`` check apply to EVERY
        committed token — generation stops mid-window and the tail of
        an accepted run is discarded, exactly the rule the one-token
        step loop applies per step.  Returns ``(committed,
        finished)``."""
        c = 0
        fin = False
        t_tok = telemetry.now()
        for t in cand:
            req.tokens.append(int(t))
            req.t_tokens.append(t_tok)
            c += 1
            if (len(req.tokens) >= req.max_new
                    or req.tokens[-1] == req.eos_id):
                fin = True
                break
        self._note_inter_token(req, c)
        return c, fin

    def _spec_grow(self, pool: _Pool, slot: int, req: _Request,
                   start: int, width: int) -> bool:
        """Cover rows ``[0, start + width)`` before a WIDE verify.
        The widening allocation is opportunistic — no preemption: a
        shortage (pool or tenant quota) falls back to the single-
        token verify, whose one write row standard ``_grow_pages``
        growth already covered, so speculation degrades to baseline
        throughput instead of evicting a neighbor."""
        need = paging.pages_for(min(pool.env, start + width),
                                self.page_size)
        extra = need - len(req.pages)
        if extra <= 0:
            return True
        if not self._alloc.fits_quota(extra, req.tenant):
            return False
        pids = self._alloc_pages(extra, req.tenant)
        if pids is None:
            return False
        req.pages.extend(pids)
        self._set_table_row(pool, slot, req.pages)
        return True

    def _spec_rewind(self, pool: _Pool, slot: int, req: _Request,
                     pos_next: int) -> int:
        """Roll rejected speculation back in the PAGE TABLE: pages
        past the committed frontier (``pos_next`` is the next write
        row, so ``pos_next + 1`` rows stay covered) return to the
        allocator and their table entries to the garbage page.  The
        padded-prompt floor is kept — prefix donation slices prompt
        pages at finish — and freed pages may be re-earned by a later
        ``_spec_grow``, always within the worst case ``submit()``
        validated."""
        t_pad = min(pool.env,
                    _ceil_to(len(req.prompt), self.prefill_align))
        keep = max(
            paging.pages_for(min(pool.env, pos_next + 1),
                             self.page_size),
            paging.pages_for(t_pad, self.page_size))
        if len(req.pages) <= keep:
            return 0
        drop = req.pages[keep:]
        del req.pages[keep:]
        self._alloc.free(drop, req.tenant)
        telemetry.metrics().counter(
            "serving_pages_freed_total").inc(len(drop))
        self._set_table_row(pool, slot, req.pages)
        return len(drop)

    def _draft_invalidate(self, pool: _Pool, slot: int) -> None:
        """Mark one slot's draft cache stale (rebuild-from-ledger at
        the next propose).  Draft KV is always recompute-class: slot
        turnover, preemption, swap-mode restore, and weight swaps all
        land here instead of any host round-trip."""
        if pool.spec is not None and "dpos" in pool.spec:
            pool.spec["dpos"][slot] = -1

    def _draft_propose(self, pool: _Pool, variables,
                       elig: dict) -> dict:
        """Draft-model proposals for every eligible slot: first
        rebuild any invalidated slot's draft cache from its token
        ledger (one bounded-shape prefill — the recompute-class
        contract), then ONE batched compiled program runs ``k + 1``
        cached greedy draft steps for all slots at once.  Returns
        ``{slot: k proposals}`` for the slots that were drafted."""
        d = pool.spec
        dvars = self._spec["draft_variables"]
        k = self._spec["k"]
        for s, ok in sorted(elig.items()):
            if not ok or d["dpos"][s] >= 0:
                continue
            req = pool.reqs[s]
            ledger = req.ledger(pool.env)
            if len(ledger) >= 2:
                t_pad = min(pool.env,
                            _ceil_to(len(ledger) - 1,
                                     self.prefill_align))
                padded = np.full((1, t_pad), self.pad_id, np.int32)
                padded[0, :len(ledger) - 1] = ledger[:-1]
                with telemetry.span("draft_prefill", bucket=pool.env,
                                    slot=s, padded=t_pad,
                                    request_id=req.rid):
                    d["cache"] = d["prefill_fn"](
                        dvars, d["cache"], jnp.asarray(padded), s)
            d["dpos"][s] = len(ledger) - 1
            d["dtok"][s] = ledger[-1]
        live = np.array([bool(elig.get(s)) and d["dpos"][s] >= 0
                         for s in range(pool.n_slots)])
        if not live.any():
            return {}
        with telemetry.span("draft_step", bucket=pool.env, k=k):
            d["cache"], props = d["propose_fn"](
                dvars, d["cache"], jnp.asarray(d["dtok"]),
                jnp.asarray(d["dpos"]), jnp.asarray(live))
            props = np.asarray(props)
        return {s: props[:, s] for s in range(pool.n_slots)
                if live[s]}

    def _spec_decode(self, pool: _Pool, variables) -> list[dict]:
        """One speculative decode quantum for a pool — the spec-mode
        replacement for the batched step dispatch.  Per live slot:
        propose up to ``k`` tokens (n-gram ledger lookup or the
        batched draft program), verify the whole window in one dense
        pass, commit the longest accepted prefix plus the bonus token
        under the per-token stop scan, and roll the rejected tail
        back (position rewind; paged mode also returns tail pages).
        A slot with no proposal (or out of budget/pages, or opted
        out) runs the single-token verify — byte-identical to the
        baseline step for that slot."""
        spec = self._spec
        k = spec["k"]
        m = telemetry.metrics()
        finished: list[dict] = []
        slots = [s for s, r in enumerate(pool.reqs)
                 if r is not None and s not in pool.prefilling]
        if not slots:
            return finished
        # WIDE-verify eligibility: the whole k+1 window must fit the
        # remaining budget — which, with the routing invariant
        # t_p + max_new <= env, also bounds every row the verify and
        # draft programs write to env - 2 (no envelope overflow, no
        # page demand past what submit() validated)
        elig = {s: (pool.reqs[s].spec_on is not False
                    and pool.reqs[s].max_new
                    - len(pool.reqs[s].tokens) > k)
                for s in slots}
        props: dict = {}
        if spec["draft_model"] is not None and any(elig.values()):
            props = self._draft_propose(pool, variables, elig)
        n_tok = 0
        for s in slots:
            req = pool.reqs[s]
            ledger = req.ledger(pool.env)
            start = len(ledger) - 1
            p = np.empty((0,), np.int32)
            if elig[s]:
                if spec["draft_model"] is None:
                    p = _speculative.ngram_propose(ledger, k,
                                                   spec["ngram"])
                else:
                    p = props.get(s, p)
            width = k + 1 if len(p) else 1
            if (width > 1 and self._paged
                    and not self._spec_grow(pool, s, req, start,
                                            width)):
                p = p[:0]  # page-short: degrade to the 1-wide verify
                width = 1
            chunk = np.full((1, width), self.pad_id, np.int32)
            chunk[0, 0] = ledger[-1]
            chunk[0, 1:1 + len(p)] = p
            try:
                with telemetry.span("verify", bucket=pool.env,
                                    slot=s, width=width,
                                    request_id=req.rid):
                    vf = pool.spec["verify_fns"][width]
                    if self._paged:
                        self._pages, greedy = vf(
                            variables, self._pages, pool.table,
                            jnp.asarray(chunk), s, start)
                    else:
                        pool.cache, greedy = vf(
                            variables, pool.cache,
                            jnp.asarray(chunk), s, start)
                    greedy = np.asarray(greedy)
            except Exception as e:
                # same per-request isolation contract as prefill
                pool.reqs[s] = None
                self._release_pages(req, pool, s)
                finished.append(self._finish_error(
                    req, f"verify_failed: {e!r}", pool.env))
                continue
            n = _speculative.accept_length(p, greedy)
            c, fin = self._commit_tokens(
                req, [int(x) for x in p[:n]] + [int(greedy[n])])
            n_tok += c
            if len(p):
                self._spec_proposed += len(p)
                self._spec_accepted += n
                m.counter("serving_spec_proposed_total",
                          bucket=pool.env).inc(len(p))
                m.counter("serving_spec_accepted_total",
                          bucket=pool.env).inc(n)
                m.histogram("serving_spec_accept_len").observe(n)
                m.gauge("serving_spec_accept_rate").set(
                    self._spec_accepted
                    / max(self._spec_proposed, 1))
                rejected = len(p) - n
                if rejected:
                    freed = (0 if fin or not self._paged
                             else self._spec_rewind(pool, s, req,
                                                    start + c))
                    flight_recorder.record(
                        "spec_rollback", request_id=req.rid,
                        bucket=pool.env, rejected=rejected,
                        pages_freed=freed)
            if spec["draft_model"] is not None and not fin:
                # commit keeps the draft exactly one token behind the
                # ledger (the k+1-step propose wrote every accepted
                # row's draft K/V), so only the host mirrors move
                pool.spec["dpos"][s] = start + c
                pool.spec["dtok"][s] = req.tokens[-1]
            if fin:
                finished.append(self._finish(pool, s))
        if n_tok:
            m.counter("serving_tokens_total",
                      bucket=pool.env).inc(n_tok)
        return finished

    def spec_stats(self) -> dict:
        """Host-side speculative-decoding counters (operator
        introspection; the same numbers feed the metrics registry
        and the ``spec_accept_rate`` SLO signal)."""
        if self._spec is None:
            return {"enabled": False}
        p, a = self._spec_proposed, self._spec_accepted
        return {"enabled": True,
                "proposer": self._spec["proposer"],
                "k": self._spec["k"], "proposed": p, "accepted": a,
                "accept_rate": (a / p) if p else None}

    # ---- serving loop -------------------------------------------------

    def has_work(self) -> bool:
        return (any(p.live() or p.queue for p in self._pools)
                or bool(self._parked))

    def load(self) -> dict:
        """Occupancy snapshot for load hooks (the traffic simulator's
        per-replica observable): queued admissions, live slots, and
        preempted-parked requests."""
        with self._lock:
            return {"queued": sum(len(p.queue) for p in self._pools),
                    "live": sum(1 for p in self._pools
                                for r in p.reqs if r is not None),
                    "parked": len(self._parked)}

    def step(self) -> list[dict]:
        """Admit waiting requests into free slots, advance every live
        bucket by ``steps_per_sync`` tokens, evict newly finished
        requests and return their results (as-completed order).
        Deadline-expired requests (queued or live) come back as
        ``error`` results; a poisoned request errors out alone without
        stalling its neighbors' slots."""
        if self._closed:
            raise RuntimeError("engine is closed; step after close()")
        # the root span's args are the occupancy ON ENTRY: a profiler
        # annotation takes its args when it opens
        with telemetry.span("engine_step", **self.load()):
            return self._step()

    def _step(self) -> list[dict]:
        with telemetry.span("admit"):
            finished = self._admit()
        # one weights snapshot per step: a concurrent swap_variables
        # lands atomically at the next step boundary (see _admit)
        variables = self.variables
        for pool in self._pools:
            # chunked-prefill interleave: at most ONE chunk per pool
            # per step, so a live slot's inter-token gap is bounded by
            # one chunk program (+ one decode quantum), never the full
            # prompt length
            if pool.prefilling:
                slot = next(iter(pool.prefilling))
                finished.extend(
                    self._advance_prefill(pool, slot, variables))
            if self._paged:
                # coverage invariant: before dispatch every live slot's
                # pages must cover its position plus this quantum's
                # writes — grow (preempting/parking as needed) NOW
                finished.extend(self._grow_pages(pool))
            if not pool.decodable():
                continue
            if self._spec is not None:
                # speculative mode replaces the batched one-token
                # dispatch with per-slot propose + verify (commits up
                # to k+1 tokens per slot per step); the deadline
                # sweep below is shared, so expiry mid-verify still
                # frees the slot this same step
                finished.extend(self._spec_decode(pool, variables))
            else:
                # the span covers dispatch AND the host sync
                # (np.asarray), so its duration is the true
                # step-quantum latency
                with telemetry.span(
                        "decode_step", bucket=pool.env,
                        steps=self.steps_per_sync,
                        live=sum(r is not None for r in pool.reqs)
                        ) as sp:
                    with telemetry.span("decode_dispatch"):
                        if self._paged:
                            (self._pages, pool.state, toks,
                             was_done, load) = pool.step_fn(
                                variables, self._pages, pool.table,
                                pool.state, self._next_rng())
                        else:
                            (pool.cache, pool.state, toks,
                             was_done, load) = pool.step_fn(
                                variables, pool.cache, pool.state,
                                self._next_rng())
                    with telemetry.span("decode_fetch"):
                        # one transfer: the tokens and, where the
                        # model routes over experts, their load
                        toks, was_done, load = jax.device_get(
                            (toks, was_done, load))
                    self._note_expert_load(sp, load)
                # one stamp for every token of this fetch: where they
                # reached the host
                t_tok = telemetry.now()
                with telemetry.span("emit"):
                    finished.extend(
                        self._emit(pool, toks, was_done, t_tok))
            with telemetry.span("sweep"):
                finished.extend(self._sweep_deadlines(pool))
                self._note_gauges(pool)
        with telemetry.span("admit"):
            finished.extend(self._admit())
        return finished

    def _emit(self, pool: _Pool, toks, was_done,
              t_tok: float) -> list[dict]:
        """Hand the fetched tokens of one decode quantum to their
        requests, each stamped ``t_tok``, and finish those that
        ended."""
        finished = []
        n_tok = 0
        for slot, req in enumerate(pool.reqs):
            if req is None:
                continue
            got = 0
            fin = False
            for k in range(toks.shape[0]):
                if was_done[k, slot]:
                    break
                req.tokens.append(int(toks[k, slot]))
                req.t_tokens.append(t_tok)
                got += 1
                if (len(req.tokens) >= req.max_new
                        or req.tokens[-1] == req.eos_id):
                    fin = True
                    break
            if got:
                self._note_inter_token(req, got)
                n_tok += got
            if fin:
                finished.append(self._finish(pool, slot))
        if n_tok:
            telemetry.metrics().counter(
                "serving_tokens_total", bucket=pool.env).inc(n_tok)
        return finished

    def _sweep_deadlines(self, pool: _Pool) -> list[dict]:
        """Live requests past their deadline free the slot NOW —
        graceful degradation under a stuck/slow decode rather than
        holding capacity for an answer nobody will take."""
        finished = []
        now = telemetry.now()
        for slot, req in enumerate(pool.reqs):
            if (req is not None and req.deadline is not None
                    and now > req.deadline):
                pool.reqs[slot] = None
                pool.prefilling.pop(slot, None)
                self._release_pages(req, pool, slot)
                telemetry.metrics().counter(
                    "serving_shed_total", reason="deadline",
                    bucket=pool.env).inc()
                telemetry.instant("evict", bucket=pool.env,
                                  slot=slot, request_id=req.rid)
                finished.append(self._finish_error(
                    req, "deadline_exceeded", pool.env))
        return finished

    # ---- graceful shutdown --------------------------------------------

    def drain(self) -> list[dict]:
        """Serve everything in flight to completion and return ALL
        results (as-completed order) — queued requests included.  The
        graceful half of shutdown: ``drain()`` then ``close()``."""
        out = []
        while self.has_work():
            out.extend(self.step())
        return out

    def close(self) -> list[dict]:
        """Shut the engine down: requests still queued or mid-decode
        are CANCELLED (returned as ``error="engine_closed"`` results —
        every in-flight id is accounted for, nothing vanishes), the
        device cache pools are released, and further ``submit``/
        ``step`` calls raise.  Call ``drain()`` first for a graceful
        shutdown that finishes the backlog instead."""
        with self._lock:
            if self._closed:
                return []
            out = []
            for pool in self._pools:
                while pool.queue:
                    out.append(self._finish_error(
                        pool.queue.popleft(), "engine_closed",
                        pool.env))
                for slot, req in enumerate(pool.reqs):
                    if req is not None:
                        pool.reqs[slot] = None
                        out.append(self._finish_error(
                            req, "engine_closed", pool.env))
                pool.prefilling.clear()
                pool.cache = pool.state = None  # release the pool
                pool.spec = None  # draft cache + verify programs too
                if self._paged:
                    pool.table = pool.table_np = None
                self._note_gauges(pool)
            for req in self._parked:  # preempted requests too
                env = req.swap["pool"].env if req.swap else 0
                out.append(self._finish_error(req, "engine_closed",
                                              env))
            self._parked.clear()
            self._pages = None  # release the page pool
            if self._prefix is not None:
                self._prefix.clear()  # release device segments
            self._closed = True
        flight_recorder.record("engine_closed", cancelled=len(out))
        flight_recorder.flush()
        return out

    def health(self) -> dict:
        """SLO verdict over the active metrics registry — the same
        evaluation ``/healthz`` serves (``ok``/``degraded``/
        ``critical`` with per-signal breaches)."""
        return telemetry.metrics().health()

    def __enter__(self) -> "DecodeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _submit_item(self, item):
        """``run``'s item contract: a prompt array, or a mapping with
        ``"prompt"`` (+ optional ``"max_new_tokens"``/``"eos_id"``;
        other keys ride into the result as meta)."""
        if isinstance(item, Mapping):
            meta = {k: v for k, v in item.items()
                    if k not in ("prompt", "max_new_tokens",
                                 "eos_id", "tenant", "priority",
                                 "speculative")}
            return self.submit(
                item["prompt"],
                max_new_tokens=item.get("max_new_tokens"),
                eos_id=item.get("eos_id", _UNSET),
                tenant=item.get("tenant"),
                priority=item.get("priority", 1),
                speculative=item.get("speculative"), meta=meta)
        return self.submit(item)

    def run(self, requests: Iterable, *, ordered: bool = True
            ) -> Iterator[dict]:
        """Serve an iterable of requests to completion.

        Each item is a prompt array or a mapping with ``"prompt"``
        (+ optional ``"max_new_tokens"``/``"eos_id"``; other keys are
        carried into the result).  ``ordered=True`` yields results in
        submission order; ``False`` yields as completed (lower
        latency for early finishers).

        With ``queue_bound`` set, a mid-iterable ``ShedError`` is
        handled as BACKPRESSURE, not failure: submission pauses while
        the engine steps (freeing queue space), then resumes — so
        already-completed results are delivered, never discarded, and
        deadline/poison casualties come back as ``error`` rows —
        matching ``StreamingGenerator``'s backpressure contract.  The
        whole iterable is always accounted for: one result per item.
        """
        order: list = []
        buffered: dict = {}
        next_emit = 0
        stalled = None  # item shed at the door, awaiting capacity
        it = iter(requests)
        exhausted = False
        while True:
            # feed until a shed: ShedError here is backpressure — the
            # stalled item waits while step() drains the queue
            while not exhausted or stalled is not None:
                if stalled is None:
                    try:
                        stalled = next(it)
                    except StopIteration:
                        exhausted = True
                        break
                try:
                    order.append(self._submit_item(stalled))
                    stalled = None
                except ShedError:
                    break
            if not self.has_work():
                if exhausted and stalled is None:
                    break
                # queue_bound >= 1 guarantees an idle engine admits:
                # a shed here means another consumer drained our work
                raise RuntimeError(
                    "run(): request shed while the engine is idle — "
                    "the engine is being stepped/drained concurrently")
            for res in self.step():
                if not ordered:
                    yield res
                    continue
                buffered[res["request_id"]] = res
                while (next_emit < len(order)
                       and order[next_emit] in buffered):
                    yield buffered.pop(order[next_emit])
                    next_emit += 1
        if ordered:
            while next_emit < len(order):
                yield buffered.pop(order[next_emit])
                next_emit += 1

    def _note_expert_load(self, span, load) -> None:
        """Add what one program routed to the histogram, and put its
        summary on the program's span (``experts_touched``: distinct
        experts that got a row, summed over the expert layers;
        ``expert_tokens_max``: the busiest expert's rows)."""
        if load is None:
            return
        if self._expert_load is None:
            self._expert_load = np.zeros(load.shape, np.int64)
        self._expert_load += load
        span.set_metadata(experts_touched=int(np.count_nonzero(load)),
                          expert_tokens_max=int(load.max()))

    def expert_load(self) -> Optional[np.ndarray]:
        """``[expert layers, experts]``: the rows that the one-shot
        prefills and the decode steps have routed to each expert since
        the engine was built (every row a program computed: a prompt's
        padding and a finished slot's dead row are routed like any
        other).  ``None`` for a model without routed experts, and until
        the first program has run.  Chunked prefills and speculative
        verifies are not counted."""
        return None if self._expert_load is None \
            else self._expert_load.copy()

    @property
    def compile_counts(self) -> dict:
        """{(kind, bucket[, padded_len]): trace count} — each compiled
        program traces exactly once, so steady-state serving holds
        these constant across ragged arrivals (the §23 bounded-
        program-set claim; pinned by the tier-1 compile guard)."""
        return dict(self._traces)

    def pool_report(self) -> list[dict]:
        """One record an envelope pool, for an operator or a smoke
        check: its envelope, the layout its K/V leaves live in, and for
        its step program and each one-shot prefill program traced so
        far how many operations of the compiled HLO copy a whole leaf
        of the pool: ``relayouts`` (a layout change or a second
        instance) and ``moves`` (into faster memory and back;
        ``layouts.whole_leaf_copies``).  Where the programs are handed
        the pool (``donated``) a ``relayouts`` above 0 means that the
        cache is not declared in the order that program works in.
        Every program is compiled again for its text (from the
        persistent cache where that holds it): seconds each at a real
        model's size, so not for a serving loop.  The paged arm has no
        envelope pools and reports none."""
        report = []
        for pool in self._pools:
            if pool.cache is None:
                continue
            lowered = {}
            if ("step", pool.env) in self._traces:
                lowered["step"] = pool.step_fn.lower(
                    self.variables, pool.cache, pool.state, self._key)
            for _, _, t_pad in sorted(
                    k for k in self._traces
                    if k[:2] == ("prefill", pool.env)):
                lowered[f"prefill_{t_pad}"] = pool.prefill_fn.lower(
                    self.variables, pool.cache, pool.state,
                    jax.ShapeDtypeStruct((1, t_pad), jnp.int32),
                    0, 0, 0, -1, self._key)
            programs = {}
            for name, low in lowered.items():
                text = low.compile().as_text()
                programs[name] = {
                    "relayouts": layouts.whole_leaf_copies(
                        text, pool.cache_tmpl),
                    "moves": layouts.whole_leaf_copies(
                        text, pool.cache_tmpl, moves=True)}
            report.append({"bucket": pool.env, "donated": self._donate,
                           "layout": layouts.describe(pool.cache),
                           "programs": programs})
        return report
