"""Block-paged KV memory for the decode engine (vLLM's PagedAttention
idea under XLA's static-shape constraint) + the QoS bookkeeping the
paged pool makes possible.

``DecodeEngine``'s envelope pools pay the §18 cost law twice: every
slot reserves ``cache_envelope`` rows of K/V up front, so concurrency
is provisioned for the worst case (``cache_envelope x slots`` bytes)
while most requests use a fraction of it.  PagedAttention (Kwon et
al., SOSP '23) breaks the reservation: KV lives in fixed-size PAGES
(here ``page_size`` tokens, one device pool per cache leaf), each
request holds a PAGE TABLE, and a slot's cost is its actual token
count rounded up to a page.

XLA cannot index a cache through a dynamic page table inside the
attention kernel without a custom pager, so the lowering here keeps
the *compute* byte-identical to the envelope path instead of
rewriting it: each compiled program GATHERS a bucket's slot pages into
the exact envelope layout (``[slots, env, KVH, D]``), runs the
UNCHANGED legacy step/prefill body, and SCATTERS the envelope back
into the pages.  Greedy parity with the envelope pool is therefore
structural, not numerical — the attention sees the same unmasked rows
bit-for-bit (masked rows differ — stale page garbage vs zeros — but
both contribute exactly ``exp(-1e30 - max) == 0.0`` after the f32
softmax, see ``models.transformer``).

Page id 0 is RESERVED as a garbage/scratch page: unallocated page-
table entries point at it, so the envelope-wide scatter is always
well-formed (writes land on page 0 and are never read back for live
rows) and the gather never faults.  ``PageAllocator`` hands out ids
``1..n_pages`` from a host-side free list with per-tenant quotas —
the admission-time substrate for the engine's QoS scheduler
(priority classes, preemption, readmission).

The pool layout per 4-D cache leaf is ``[n_pages + 1, page_size,
KVH, D]`` — envelope-free, exactly ``_PrefixStore``'s segment
shape batched over pages — so with ``page_size == prefill_align``
prefix sharing and paging are one mechanism: a prefix-cache hit is a
device copy into a page, donation is a page slice out.
"""

from __future__ import annotations

from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: the reserved garbage/scratch page id (never allocated; the page-
#: table filler for unallocated entries)
GARBAGE_PAGE = 0


def pages_for(tokens: int, page_size: int) -> int:
    """Pages covering ``tokens`` rows (ceil division)."""
    return -(-int(tokens) // int(page_size))


def build_pool(cache_shapes, n_pages: int, page_size: int) -> list:
    """Zeroed device page pool: one ``[n_pages + 1, page, KVH, D]``
    leaf per 4-D cache leaf of ``cache_shapes`` (an ``eval_shape``
    cache template), in flatten order — scalar cache/pos-index leaves
    are skipped, exactly like ``_PrefixStore`` segments.  Row 0 is the
    garbage page.  Zero-init keeps every pool value finite from the
    start: the masked-row exactness argument needs finite garbage,
    never NaN."""
    out = []
    for leaf in jax.tree_util.tree_leaves(cache_shapes):
        if len(leaf.shape) == 0:
            continue
        out.append(jnp.zeros(
            (n_pages + 1, page_size) + tuple(leaf.shape[2:]),
            leaf.dtype))
    return out


def pool_nbytes(pages: list) -> int:
    return sum(int(p.nbytes) for p in pages)


def leaf_templates(segments) -> list[dict]:
    """Self-describing ``{"shape", "dtype"}`` descriptors for one KV
    block's segment leaves — the wire meta the disaggregated handoff
    ships ahead of the raw page bytes, so the receiver can slice a
    gather-sent frame back into typed arrays without any per-leaf
    framing (``serving.pack_kv_blocks`` / ``unpack_kv_blocks``).
    Every block of one export shares these templates: blocks are
    ``[1, page, KVH, D]`` slices of the same pool leaves."""
    return [{"shape": [int(d) for d in np.asarray(s).shape],
             "dtype": str(np.asarray(s).dtype)} for s in segments]


def gather_cache(cache_shapes, pages: list, table):
    """Materialize the envelope-layout cache pytree from the page pool
    (traced; runs inside the compiled program).  ``table`` is the
    ``[slots, env // page]`` int32 page table; scalar template leaves
    come back as zeros (slot state owns positions — the legacy
    programs never read them)."""
    leaves, treedef = jax.tree_util.tree_flatten(cache_shapes)
    segs = iter(pages)
    out = []
    for tmpl in leaves:
        if len(tmpl.shape) == 0:
            out.append(jnp.zeros((), tmpl.dtype))
            continue
        p = next(segs)                     # [P+1, page, KVH, D]
        x = p[table]                       # [S, MB, page, KVH, D]
        out.append(x.reshape(table.shape[0],
                             table.shape[1] * p.shape[1],
                             *p.shape[2:]))
    return jax.tree_util.tree_unflatten(treedef, out)


def scatter_cache(pages: list, cache, table) -> list:
    """Write the envelope-layout cache back into the page pool
    (traced).  Every unallocated table entry is ``GARBAGE_PAGE``, so
    the scatter's duplicate indices all land on page 0 — which slot's
    garbage wins is unspecified and irrelevant (page 0 is never read
    for a live row, and cache values are always finite)."""
    flat = table.reshape(-1)
    segs = iter(pages)
    out = []
    for leaf in jax.tree_util.tree_leaves(cache):
        if jnp.ndim(leaf) == 0:
            continue
        p = next(segs)
        # [S, env, KVH, D]: a slot's pages are consecutive rows
        out.append(p.at[flat].set(
            leaf.reshape((flat.shape[0],) + p.shape[1:])))
    return out


class PageAllocator:
    """Host-side free-list allocator over page ids ``1..n_pages`` with
    per-tenant quotas.

    Mutated only on the engine's stepping thread (the same ownership
    discipline as ``_PrefixStore``); ``n_free`` is a plain int read
    and safe to sample from other threads (the gateway's
    ``free_pages`` load signal).

    ``tenant_quota`` caps the pages any one tenant may hold at once:
    an int applies to every tenant, a mapping caps listed tenants and
    leaves the rest unbounded, ``None`` disables quotas.  Quota is
    enforced at allocation time — the admission scheduler skips a
    quota-blocked request instead of letting it starve the pool.
    """

    def __init__(self, n_pages: int, page_size: int,
                 tenant_quota=None):
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        # pop() order: 1, 2, ... — deterministic page ids for a
        # deterministic workload (the seeded preemption drill relies
        # on reproducible allocation)
        self._free = list(range(self.n_pages, 0, -1))
        self.tenant_quota = tenant_quota
        self.used: dict = {}          # tenant -> pages held
        self.allocated_total = 0
        self.freed_total = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    def quota_for(self, tenant) -> Optional[int]:
        if self.tenant_quota is None:
            return None
        if isinstance(self.tenant_quota, Mapping):
            q = self.tenant_quota.get(tenant)
            return None if q is None else int(q)
        return int(self.tenant_quota)

    def fits_quota(self, n: int, tenant) -> bool:
        q = self.quota_for(tenant)
        return q is None or self.used.get(tenant, 0) + n <= q

    def alloc(self, n: int, tenant=None) -> Optional[list]:
        """``n`` page ids, or None if capacity or the tenant's quota
        is short (the caller distinguishes via ``fits_quota`` —
        preemption can fix capacity, never quota)."""
        if n > len(self._free) or not self.fits_quota(n, tenant):
            return None
        pids = [self._free.pop() for _ in range(n)]
        self.used[tenant] = self.used.get(tenant, 0) + n
        self.allocated_total += n
        return pids

    def free(self, pids: list, tenant=None) -> None:
        self._free.extend(reversed(pids))
        left = self.used.get(tenant, 0) - len(pids)
        if left > 0:
            self.used[tenant] = left
        else:
            self.used.pop(tenant, None)
        self.freed_total += len(pids)

    def stats(self) -> dict:
        return {"n_pages": self.n_pages, "page_size": self.page_size,
                "free": self.n_free,
                "allocated_total": self.allocated_total,
                "freed_total": self.freed_total,
                "tenants": dict(self.used)}
