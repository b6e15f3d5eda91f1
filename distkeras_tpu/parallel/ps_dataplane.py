"""On-chip compiled PS data plane — the ``fidelity="mesh"`` tier.

The emulated rounds (``ps_emulator``) are one XLA program per round,
but their data plane still *looks* like a parameter server: the center
is replicated, every round materializes a ``[W, params]`` pulled stack
(``_broadcast_like``), and the closed-form commit is a ``tensordot``
against a replicated center.  This module lowers the same round to the
layout the SNIPPETS exemplars (pjit + donated buffers + partition
rules) and the original port brief ("gradient push/pull lowered to ICI
all-reduce / async reduce-scatter") actually describe:

* the center lives *sharded*: packed per-dtype into 1-D buffers and
  split row-wise ``[W, block]`` over the ``workers`` mesh axis — each
  device owns exactly one shard (a ZeRO-style layout for the PS);
* one ``shard_map`` program runs the whole round: the round-start pull
  is an ``all_gather`` of the center shards fused into the program (no
  W-way host-visible replication), each device runs its worker's
  window locally, and the scaled deltas are folded into the center by
  a single ``psum_scatter`` (reduce-scatter) — each device updates its
  own shard and never sees the others';
* PS state and worker states are donated (``donate_argnums``), so the
  round updates HBM in place instead of double-buffering ``[W,
  params]`` trees;
* worker params are not carried between rounds at all: for the
  delta family the round-barrier pull makes them a pure function of
  the center, so ``MeshWorkerState`` is ``TrainState`` minus
  ``params``.

Communication compression (ISSUE 16 tentpole) — two independent knobs,
both lowered INSIDE the compiled round, mirroring the host wire codecs
(``parallel.compression``) which remain the parity oracle:

* ``comm_codec="int8"`` replaces the f32 center ``all_gather`` with an
  int8 one: each device quantizes its own shard with PER-LEAF symmetric
  scales computed on-device (partial per-leaf ``segment_max`` over the
  local block, ``pmax`` across shards — the exact global ``max|x|``,
  then ``scale = amax/127``, ``clip(round(x/scale))`` — the same law as
  ``compression.Int8Codec``, float32 scale math instead of the host
  codec's float64).  Dequantization is FUSED into the per-leaf unpack
  (each leaf is sliced from the int8 buffer and multiplied by its
  scalar scale), so no f32 intermediate of the full packed center ever
  materializes — the program's only full-center transfer is 1 byte per
  element plus one [n_leaves] scale vector.  The center shards
  themselves stay exact f32; only the broadcast is lossy, and the
  commit folds each worker's delta (computed against the center it
  actually saw) into the exact shards.
* ``comm_dtype="bfloat16"`` narrows the delta reduce-scatter: the
  scaled f32 payload is cast to bf16 (the ``Bf16Codec`` law:
  round-to-nearest-even) before ``psum_scatter`` and the reduction is
  widened back into the f32 shard.  Unlike the host codec the
  reduction itself runs in bf16 (the wire IS the reduction here), so
  end-to-end tolerance is documented looser than the cast law.

Both knobs apply to the float32 groups only; other dtypes ride
uncompressed.  ``comm_bytes_per_round`` / ``comm_bytes_saved_per_round``
expose the static per-round wire accounting (remote fraction of each
collective, all devices), and every dispatched round increments
``ps_round_comm_bytes_saved_total`` by the saving.

Async host dispatch (tentpole 3): per-round metrics (loss / grad_norm /
staleness, each ``[W]``) no longer return as a per-round dict — they
accumulate into a device-resident ring of ``metrics_every`` rounds
(``init_ring()``), written at a traced slot index so the slot never
retraces.  ``MeshRoundDriver`` owns the dispatch loop: it enqueues
round k+1 before fetching round k's metrics, fetches a completed ring
only after at least one newer round is in flight
(``ps_metrics_fetches_total`` counts the device reads), and its
``sync=True`` mode is the eager-fetch oracle the async path is tested
byte-identical against.

Semantics are the ``fast`` tier's closed form, exactly: the center
trajectory for DOWNPOUR/ADAG/DynSGD matches ``ps_emulator._fast_round``
under the same seeded ``commit_permutation`` (DynSGD's per-commit
``1/(position+1)`` scale is applied per device before the reduce).
The pipelined variant matches ``make_pipelined_round_fn``'s contract:
window *k* overlaps the commit of round *k-1*'s pending payloads at
staleness ``position + W``, and ``flush`` drains the final pending at
its true depth (offset 0).  The elastic family commits absolute
params against a serialized center — structurally not a reduction —
and stays on the faithful/host tiers.

Compile-guard telemetry: each distinct (round shape x comm config)
traces exactly one program, counted by
``ps_round_compiles_total{fidelity="mesh"}`` (``"mesh_pipelined"`` for
the pipelined variant) — the same trace-time counter contract as the
emulated tiers.
"""

from __future__ import annotations

import collections
import math
import re
import time
from typing import Any, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import PartitionSpec as P

from distkeras_tpu import mesh as mesh_lib
from distkeras_tpu import telemetry, utils
from distkeras_tpu.parallel.update_rules import (
    DynSGDRule,
    PSState,
    UpdateRule,
)
from distkeras_tpu.workers import TrainState, make_window_runner

Pytree = Any

#: valid ``comm_dtype`` values (the delta reduce-scatter element type)
COMM_DTYPES = ("float32", "bfloat16")
#: valid ``comm_codec`` values (the center re-broadcast codec)
COMM_CODECS = (None, "int8")


# ---------------------------------------------------------------------------
# On-chip codec law — jnp mirror of ``compression.Int8Codec`` /
# ``Bf16Codec`` (the host parity oracles).
# ---------------------------------------------------------------------------


def quantize_int8(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization, the ``Int8Codec`` law on-device:
    ``scale = max|x|/127`` (1.0 when all-zero), ``q = clip(round(
    x/scale), -127, 127)``.  Scale math is float32 (the host codec
    computes it in float64 — parity to rtol ~1e-6, documented in
    ``tests/test_ps_dataplane.py``)."""
    x = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(x)) if x.size else jnp.float32(0.0)
    scale = jnp.where(amax > 0, amax / jnp.float32(127.0),
                      jnp.float32(1.0))
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale) -> jnp.ndarray:
    """Inverse of ``quantize_int8`` (== ``Int8Codec.decode_leaf``)."""
    return q.astype(jnp.float32) * jnp.float32(scale)


# ---------------------------------------------------------------------------
# Regex partition rules -> PartitionSpec pytree (SNIPPETS [2] shape).
# ---------------------------------------------------------------------------

#: default rules for the stacked ``[W, ...]`` worker state: every
#: non-scalar leaf shards its leading (worker) axis over the mesh's
#: ``workers`` axis.  Override per-dataplane to co-shard large moments
#: differently (future model-parallel tiers).
DEFAULT_WORKER_RULES = ((r".*", P(mesh_lib.WORKER_AXIS)),)


def _path_str(path) -> str:
    """KeyPath -> ``a/b/0/c`` string the rule regexes match against."""
    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(k.name)
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.FlattenedIndexKey):
            parts.append(str(k.key))
        else:  # pragma: no cover - future key kinds
            parts.append(str(k))
    return "/".join(parts)


def match_partition_rules(rules, tree: Pytree) -> Pytree:
    """``((regex, PartitionSpec), ...)`` -> PartitionSpec pytree.

    First rule whose pattern ``re.search``-matches the leaf's
    '/'-joined key path wins.  Scalar (size <= 1) leaves always get
    ``P()`` — there is nothing to shard and replicating them keeps
    every rule set valid for optimizer step counters.  A leaf no rule
    matches raises, naming the path — silent replication is how layout
    bugs hide.
    """
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def assign(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        if math.prod(shape) <= 1:
            return P()
        name = _path_str(path)
        for pat, spec in compiled:
            if pat.search(name):
                return spec
        raise ValueError(
            f"no partition rule matches leaf {name!r} "
            f"(shape {shape}); add a rule (regex, PartitionSpec) "
            f"covering it")

    return jax.tree_util.tree_map_with_path(assign, tree)


# ---------------------------------------------------------------------------
# Packed center layout: per-dtype 1-D buffers, padded to W, sharded
# row-wise [W, block] over the workers axis.
# ---------------------------------------------------------------------------


class _Group(NamedTuple):
    indices: tuple[int, ...]   # leaf indices (flatten order)
    offsets: dict[int, int]    # leaf index -> offset into the buffer
    total: int                 # payload elements (before padding)
    padded: int                # total rounded up to a multiple of W


class _FlatSpec:
    """Host-side description of the center's packed layout.

    Pure shape metadata: ``pack``/``pack_flat``/``unpack`` are
    static-shape jittable tree <-> buffer transforms.
    """

    def __init__(self, template: Pytree, num_shards: int):
        leaves, treedef = jax.tree_util.tree_flatten(template)
        if not leaves:
            raise ValueError("empty parameter tree")
        self.treedef = treedef
        self.shapes = [tuple(x.shape) for x in leaves]
        self.dtypes = [jnp.dtype(x.dtype) for x in leaves]
        self.sizes = [int(math.prod(s)) for s in self.shapes]
        self.num_shards = int(num_shards)
        by_dtype: dict[str, list[int]] = {}
        for i, dt in enumerate(self.dtypes):
            by_dtype.setdefault(dt.name, []).append(i)
        self.groups: dict[str, _Group] = {}
        for name, idxs in sorted(by_dtype.items()):
            offsets, off = {}, 0
            for i in idxs:
                offsets[i] = off
                off += self.sizes[i]
            padded = -(-max(off, 1) // num_shards) * num_shards
            self.groups[name] = _Group(tuple(idxs), offsets, off, padded)

    def pack_flat(self, tree: Pytree) -> dict[str, jnp.ndarray]:
        """Tree -> ``{dtype: [padded]}`` full-length 1-D buffers."""
        leaves = self.treedef.flatten_up_to(tree)
        out = {}
        for name, g in self.groups.items():
            flat = jnp.concatenate(
                [jnp.ravel(leaves[i]) for i in g.indices])
            if g.padded > g.total:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((g.padded - g.total,), flat.dtype)])
            out[name] = flat
        return out

    def pack(self, tree: Pytree) -> dict[str, jnp.ndarray]:
        """Tree -> ``{dtype: [W, block]}`` row-sharded center blocks."""
        return {
            name: flat.reshape(self.num_shards, -1)
            for name, flat in self.pack_flat(tree).items()}

    def unpack(self, flats: Mapping[str, jnp.ndarray]) -> Pytree:
        """``{dtype: [padded]}`` -> tree (inverse of ``pack_flat``)."""
        leaves: list = [None] * len(self.shapes)
        for name, g in self.groups.items():
            flat = flats[name]
            for i in g.indices:
                off = g.offsets[i]
                leaves[i] = flat[off:off + self.sizes[i]].reshape(
                    self.shapes[i])
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def seg_ids(self, name: str) -> np.ndarray:
        """Static ``[padded]`` map position -> group-local leaf
        ordinal; padding tail gets the extra id ``n_leaves`` so it
        never pollutes a leaf's quantization scale."""
        g = self.groups[name]
        ids = np.full((g.padded,), len(g.indices), np.int32)
        for j, i in enumerate(g.indices):
            off = g.offsets[i]
            ids[off:off + self.sizes[i]] = j
        return ids


# ---------------------------------------------------------------------------
# States.
# ---------------------------------------------------------------------------


class MeshPSState(struct.PyTreeNode):
    """Sharded-center PS state.

    ``blocks`` maps dtype name -> ``[W, block]`` packed center rows
    (row *w* lives on worker *w*'s device); ``clock`` is the replicated
    commit clock (same meaning as ``PSState.clock``).
    """

    blocks: Mapping[str, jnp.ndarray]
    clock: jnp.ndarray


class MeshWorkerState(struct.PyTreeNode):
    """``TrainState`` minus ``params``, stacked ``[W, ...]``.

    Between mesh rounds the delta family's worker params are a pure
    function of the center (round-barrier pull), so carrying them
    would re-create exactly the ``[W, params]`` replication this tier
    deletes.
    """

    step: jnp.ndarray
    opt_state: Pytree
    model_state: Mapping[str, Pytree]
    rng: jax.Array


# ---------------------------------------------------------------------------
# The dataplane.
# ---------------------------------------------------------------------------


class MeshDataplane:
    """One compiled SPMD program per PS round (see module docstring).

    Per-round metrics accumulate in a device-resident ring (see
    ``init_ring``/``MeshRoundDriver``), so the signatures are:

    * plain:     ``round(ps, ws, batch, perm, ring, slot)
      -> (ps, ws, ring)``
    * pipelined: ``round(ps, ws, batch, perm, pending, pending_perm,
      pending_valid, ring, slot) -> (ps, ws, pending, perm, valid,
      ring)`` and ``flush(ps, pending, pending_perm) -> ps``

    ``slot`` is a traced replicated int32 scalar (``slot_index(i)``),
    so cycling the ring never retraces.  ``ps``/``ws`` are donated;
    the ring is NOT (old handles stay fetchable for the late metrics
    read).  Convert a host-layout ``(PSState, TrainState)`` pair with
    ``to_device`` once before the first round, and read results back
    via ``center`` / ``export_ps_state``.
    """

    def __init__(self, rule: UpdateRule, step_fn, mesh,
                 center_template: Pytree, *, pipelined: bool = False,
                 partition_rules=DEFAULT_WORKER_RULES,
                 comm_dtype: str = "float32", comm_codec=None,
                 metrics_every: int = 1):
        if rule.payload_kind != "delta":
            raise ValueError(
                "fidelity='mesh' compiles the delta-family commit "
                "(DOWNPOUR/ADAG/DynSGD) into a reduce-scatter; the "
                "elastic family commits absolute params against a "
                "serialized center — use fidelity='faithful' or "
                "'host'")
        if mesh_lib.WORKER_AXIS not in mesh.axis_names:
            raise ValueError(
                f"mesh has no {mesh_lib.WORKER_AXIS!r} axis: "
                f"{mesh.axis_names}")
        extra = [a for a in mesh.axis_names
                 if a != mesh_lib.WORKER_AXIS and mesh.shape[a] > 1]
        if extra:
            raise ValueError(
                "fidelity='mesh' is data-parallel only (one worker "
                f"per device); mesh has extra axes {extra}")
        if comm_dtype not in COMM_DTYPES:
            raise ValueError(
                f"unknown comm_dtype {comm_dtype!r}; valid: "
                f"{list(COMM_DTYPES)}")
        if comm_codec not in COMM_CODECS:
            raise ValueError(
                f"unknown comm_codec {comm_codec!r}; valid: "
                f"{list(COMM_CODECS)}")
        if int(metrics_every) < 1:
            raise ValueError(
                f"metrics_every must be >= 1, got {metrics_every}")
        self.rule = rule
        self.mesh = mesh
        self.num_workers = int(mesh.shape[mesh_lib.WORKER_AXIS])
        self.pipelined = bool(pipelined)
        self.partition_rules = tuple(partition_rules)
        self.comm_dtype = str(comm_dtype)
        self.comm_codec = comm_codec
        self.metrics_every = int(metrics_every)
        self._window_run = make_window_runner(step_fn)
        self.spec = _FlatSpec(center_template, self.num_workers)
        # compression applies to the float32 groups only — other
        # dtypes (int counters, bool masks) ride uncompressed
        self._quant_groups = frozenset(
            n for n in self.spec.groups
            if comm_codec == "int8" and jnp.dtype(n) == jnp.float32)
        self._bf16_groups = frozenset(
            n for n in self.spec.groups
            if comm_dtype == "bfloat16" and jnp.dtype(n) == jnp.float32)
        self._account_comm_bytes()
        self._rep = mesh_lib.replicated_sharding(mesh)
        self._row = mesh_lib.batch_sharding(mesh)
        self._block_shardings = {n: self._row for n in self.spec.groups}
        self._pack_jit = jax.jit(self.spec.pack,
                                 out_shardings=self._block_shardings)
        self._center_jit = jax.jit(
            lambda mps: self.spec.unpack(
                {n: b.reshape(-1) for n, b in mps.blocks.items()}),
            out_shardings=self._rep)
        self._slot_cache: dict[int, jax.Array] = {}
        self._ws_specs = None  # resolved on first to_device
        # XLA cost ledger: batch-shape key -> (Compiled, record)
        self._programs: dict[tuple, tuple] = {}
        self._cost_records: list[dict] = []
        self._last_record: dict | None = None

    def _account_comm_bytes(self) -> None:
        """Static per-round wire accounting.  Convention: the REMOTE
        fraction each collective moves per device ((W-1)/W of the
        padded buffer), summed over all W devices; the int8 arm adds
        its per-leaf scale ``pmax`` side channel.  ``saved`` is vs the
        all-f32 configuration of the same shapes."""
        W = self.num_workers
        gather = scatter = saved = 0
        for n, g in self.spec.groups.items():
            item = jnp.dtype(n).itemsize
            remote = (g.padded - g.padded // W) * W
            if n in self._quant_groups:
                side = (len(g.indices) + 1) * 4 * W
                gather += remote * 1 + side
                saved += remote * (item - 1) - side
            else:
                gather += remote * item
            if n in self._bf16_groups:
                scatter += remote * 2
                saved += remote * (item - 2)
            else:
                scatter += remote * item
        self.comm_bytes_per_round = {"gather": int(gather),
                                     "scatter": int(scatter)}
        self.comm_bytes_saved_per_round = max(int(saved), 0)

    # -- state conversion ------------------------------------------------

    def to_device(self, ps_state: PSState, worker_states: TrainState
                  ) -> tuple[MeshPSState, MeshWorkerState]:
        """Host/emulated layout -> this tier's sharded layout.

        Must be called once before ``round`` (it also resolves the
        worker partition specs from the concrete state shapes and
        finalizes the compiled programs).
        """
        mws = MeshWorkerState(
            step=worker_states.step, opt_state=worker_states.opt_state,
            model_state=worker_states.model_state,
            rng=worker_states.rng)
        if self._ws_specs is None:
            self._build_programs(mws)
        mws = jax.device_put(mws, self._ws_shardings)
        blocks = self._pack_jit(ps_state.center)
        clock = jax.device_put(jnp.asarray(ps_state.clock), self._rep)
        return MeshPSState(blocks=blocks, clock=clock), mws

    def center(self, mps: MeshPSState) -> Pytree:
        """Replicated center pytree (for eval/export); one compiled
        gather+unpack program, shared by every call."""
        return self._center_jit(mps)

    def export_ps_state(self, mps: MeshPSState) -> PSState:
        """Sharded layout -> the emulated tiers' ``PSState``."""
        return PSState(center=self.center(mps), clock=mps.clock)

    def init_pending(self) -> dict[str, jnp.ndarray]:
        """Zero pending payloads ``{dtype: [W, padded]}`` (inert for
        the delta family until the first round marks them valid)."""
        out = {}
        for name, g in self.spec.groups.items():
            dt = jnp.dtype(name)
            out[name] = jax.device_put(
                jnp.zeros((self.num_workers, g.padded), dt), self._row)
        return out

    def init_ring(self) -> dict[str, jnp.ndarray]:
        """Zero device-resident metrics ring: ``metrics_every`` rounds
        of per-worker ``[W]`` rows per metric.  NOT donated by
        ``round``, so a saved handle from round k stays fetchable
        while round k+1 runs — the async driver's late read."""
        N, W = self.metrics_every, self.num_workers
        ring = {"loss": jnp.zeros((N, W), jnp.float32),
                "grad_norm": jnp.zeros((N, W), jnp.float32),
                "staleness": jnp.zeros((N, W), jnp.int32)}
        return jax.device_put(ring, self._rep)

    def slot_index(self, i: int) -> jax.Array:
        """Replicated traced int32 scalar for ring slot ``i`` (cached:
        one device array per slot, so cycling never re-transfers)."""
        i = int(i) % self.metrics_every
        if i not in self._slot_cache:
            self._slot_cache[i] = jax.device_put(
                jnp.asarray(i, jnp.int32), self._rep)
        return self._slot_cache[i]

    # -- program construction --------------------------------------------

    def _build_programs(self, template: MeshWorkerState) -> None:
        specs = match_partition_rules(self.partition_rules, template)
        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        paths = jax.tree_util.tree_flatten_with_path(template)[0]
        spec_leaves = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
        for (path, leaf), sp in zip(paths, spec_leaves):
            if math.prod(tuple(leaf.shape)) <= 1:
                continue
            if not len(sp) or sp[0] != mesh_lib.WORKER_AXIS:
                raise ValueError(
                    "mesh-tier worker leaves are stacked [W, ...] and "
                    "must shard the leading axis over "
                    f"{mesh_lib.WORKER_AXIS!r}; rule resolved "
                    f"{_path_str(path)!r} to {sp}")
        self._ws_specs = specs
        self._ws_shardings = mesh_lib.shardings_for(self.mesh, specs)

        spec = self.spec
        rule = self.rule
        W = self.num_workers
        WA = mesh_lib.WORKER_AXIS
        dyn = isinstance(rule, DynSGDRule)
        window_run = self._window_run
        row_blocks = {n: P(WA) for n in spec.groups}
        quant = self._quant_groups
        bf16 = self._bf16_groups

        # static per-position leaf ids for the quantized groups, packed
        # [W, block] like the center so each device reads its own row
        self._seg_blocks = {
            n: jax.device_put(
                jnp.asarray(spec.seg_ids(n).reshape(W, -1)), self._row)
            for n in sorted(quant)}
        seg_specs = {n: P(WA) for n in self._seg_blocks}

        def _local(tree):
            return jax.tree_util.tree_map(lambda x: x[0], tree)

        def _stacked(tree):
            return jax.tree_util.tree_map(lambda x: x[None], tree)

        def pull_center(blocks, segs):
            # Fused round-start pull: ONE all-gather per dtype group —
            # the program's only full-center transfer.  Quantized
            # groups gather int8 (per-leaf scales replicated by the
            # pmax, never gathered) and dequantize FUSED into the
            # per-leaf unpack below, so no full-width f32 packed
            # buffer of the center ever materializes.
            flats, scales = {}, {}
            for n, b in blocks.items():
                local = b[0]
                if n in quant:
                    g = spec.groups[n]
                    nseg = len(g.indices)
                    seg = segs[n][0]
                    part = jax.ops.segment_max(
                        jnp.abs(local), seg, num_segments=nseg + 1,
                        indices_are_sorted=True)
                    amax = jax.lax.pmax(part, WA)[:nseg]
                    # the Int8Codec law (quantize_int8), per leaf
                    scale = jnp.where(amax > 0,
                                      amax / jnp.float32(127.0),
                                      jnp.float32(1.0))
                    spos = jnp.concatenate(
                        [scale, jnp.ones((1,), jnp.float32)])[seg]
                    q = jnp.clip(jnp.round(local / spos),
                                 -127.0, 127.0).astype(jnp.int8)
                    flats[n] = jax.lax.all_gather(q, WA, tiled=True)
                    scales[n] = scale
                else:
                    flats[n] = jax.lax.all_gather(b[0], WA, tiled=True)
            leaves: list = [None] * len(spec.shapes)
            for n, g in spec.groups.items():
                flat, sc = flats[n], scales.get(n)
                for j, i in enumerate(g.indices):
                    off = g.offsets[i]
                    piece = flat[off:off + spec.sizes[i]]
                    if sc is not None:
                        piece = piece.astype(jnp.float32) * sc[j]
                    leaves[i] = piece.reshape(spec.shapes[i])
            return jax.tree_util.tree_unflatten(spec.treedef, leaves)

        def window_and_delta(blocks, segs, ws, batch):
            center = pull_center(blocks, segs)
            state = TrainState(
                step=ws.step[0], params=center,
                opt_state=_local(ws.opt_state),
                model_state=_local(ws.model_state), rng=ws.rng[0])
            local_batch = _local(batch)
            window = jax.tree_util.tree_leaves(
                local_batch)[0].shape[0]
            new_state, step_metrics = window_run(state, local_batch)
            # delta vs the center this worker actually SAW (the
            # dequantized pull under comm_codec) — commits fold into
            # the exact shards, so the server never drifts lossily
            delta = rule.normalize_delta(
                utils.tree_sub(new_state.params, center), window)
            new_ws = MeshWorkerState(
                step=new_state.step[None],
                opt_state=_stacked(new_state.opt_state),
                model_state=_stacked(new_state.model_state),
                rng=new_state.rng[None])
            return spec.pack_flat(delta), new_ws, step_metrics

        def commit(blocks, flat, scale):
            # Per-device scaled payload -> reduce-scatter -> each
            # device folds the reduction into its own center shard.
            # bf16 groups ride the wire (and reduce) narrowed — the
            # Bf16Codec cast law; the shard itself stays f32.
            out = {}
            for n, b in blocks.items():
                payload = flat[n] * scale.astype(flat[n].dtype)
                if n in bf16:
                    red = jax.lax.psum_scatter(
                        payload.astype(jnp.bfloat16), WA,
                        tiled=True).astype(b.dtype)
                else:
                    red = jax.lax.psum_scatter(payload, WA, tiled=True)
                out[n] = b + red[None]
            return out

        def round_body(blocks, segs, clock, ws, batch, inv):
            flat, new_ws, sm = window_and_delta(blocks, segs, ws, batch)
            pos = inv[jax.lax.axis_index(WA)]
            scale = (1.0 / (pos.astype(jnp.float32) + 1.0) if dyn
                     else jnp.float32(1.0))
            new_blocks = commit(blocks, flat, scale)
            metrics = {
                "loss": sm["loss"].mean()[None],
                "grad_norm": sm["grad_norm"].mean()[None],
                "staleness": pos.astype(jnp.int32)[None],
            }
            return new_blocks, clock + W, new_ws, metrics

        round_smap = jax.shard_map(
            round_body, mesh=self.mesh,
            in_specs=(row_blocks, seg_specs, P(), specs, P(WA), P()),
            out_specs=(row_blocks, P(), specs, P(WA)))

        rep = self._rep

        def write_ring(ring, slot, metrics):
            # Pin the updated ring to the replicated sharding of
            # ``init_ring`` — GSPMD would otherwise propagate the
            # metric rows' worker sharding into the output, giving
            # round k+1 a different input signature than round k and
            # breaking the one-executable-per-shape AOT ledger.
            return {k: jax.lax.with_sharding_constraint(
                        ring[k].at[slot].set(
                            metrics[k].astype(ring[k].dtype)), rep)
                    for k in ring}

        def plain_round(mps, mws, batch, perm, ring, slot):
            # Python side effect at TRACE time only — the public
            # one-compile-per-(round-shape x comm-config) guard (same
            # contract as the emulated tiers' counter).
            telemetry.metrics().counter(
                "ps_round_compiles_total", fidelity="mesh").inc()
            inv = jnp.argsort(perm)
            blocks, clock, ws, metrics = round_smap(
                mps.blocks, self._seg_blocks, mps.clock, mws, batch,
                inv)
            return (MeshPSState(blocks=blocks, clock=clock), ws,
                    write_ring(ring, slot, metrics))

        def pipe_body(blocks, segs, clock, ws, batch, inv, pending,
                      pinv, pvalid):
            # window k (on the pre-commit center) and the commit of
            # round k-1's pending are independent subgraphs — XLA
            # overlaps them, same contract as make_pipelined_round_fn.
            flat, new_ws, sm = window_and_delta(blocks, segs, ws, batch)
            pos = inv[jax.lax.axis_index(WA)]
            ppos = pinv[jax.lax.axis_index(WA)]
            pscale = (1.0 / (ppos.astype(jnp.float32) + W + 1.0)
                      if dyn else jnp.float32(1.0))
            pscale = pscale * pvalid.astype(jnp.float32)
            new_blocks = commit(
                blocks, {n: p[0] for n, p in pending.items()}, pscale)
            new_clock = clock + W * pvalid.astype(clock.dtype)
            metrics = {
                "loss": sm["loss"].mean()[None],
                "grad_norm": sm["grad_norm"].mean()[None],
                # true commit depth: one full round behind + position
                "staleness": (pos + W).astype(jnp.int32)[None],
            }
            new_pending = {n: f[None] for n, f in flat.items()}
            return (new_blocks, new_clock, new_ws, metrics,
                    new_pending, jnp.asarray(True))

        pipe_smap = jax.shard_map(
            pipe_body, mesh=self.mesh,
            in_specs=(row_blocks, seg_specs, P(), specs, P(WA), P(),
                      {n: P(WA) for n in spec.groups}, P(), P()),
            out_specs=(row_blocks, P(), specs, P(WA),
                       {n: P(WA) for n in spec.groups}, P()))

        def pipe_round(mps, mws, batch, perm, pending, pending_perm,
                       pending_valid, ring, slot):
            telemetry.metrics().counter(
                "ps_round_compiles_total",
                fidelity="mesh_pipelined").inc()
            inv = jnp.argsort(perm)
            pinv = jnp.argsort(pending_perm)
            (blocks, clock, ws, metrics, new_pending,
             valid) = pipe_smap(mps.blocks, self._seg_blocks,
                                mps.clock, mws, batch, inv, pending,
                                pinv, pending_valid)
            return (MeshPSState(blocks=blocks, clock=clock), ws,
                    new_pending, perm, valid,
                    write_ring(ring, slot, metrics))

        def flush_body(blocks, clock, pending, pinv):
            # drain at TRUE depth: no window ran ahead -> offset 0
            ppos = pinv[jax.lax.axis_index(WA)]
            scale = (1.0 / (ppos.astype(jnp.float32) + 1.0) if dyn
                     else jnp.float32(1.0))
            new_blocks = commit(
                blocks, {n: p[0] for n, p in pending.items()}, scale)
            return new_blocks, clock + W

        flush_smap = jax.shard_map(
            flush_body, mesh=self.mesh,
            in_specs=(row_blocks, P(),
                      {n: P(WA) for n in spec.groups}, P()),
            out_specs=(row_blocks, P()))

        def flush_fn(mps, pending, pending_perm):
            pinv = jnp.argsort(pending_perm)
            blocks, clock = flush_smap(mps.blocks, mps.clock, pending,
                                       pinv)
            return MeshPSState(blocks=blocks, clock=clock)

        if self.pipelined:
            round_jit = jax.jit(pipe_round, donate_argnums=(0, 1, 4))
            self.flush = jax.jit(flush_fn, donate_argnums=(0, 1))
            fid = "mesh_pipelined"
        else:
            round_jit = jax.jit(plain_round, donate_argnums=(0, 1))
            fid = "mesh"
        self._round_jit = round_jit
        self._round_fid = fid
        saved = self.comm_bytes_saved_per_round
        programs = self._programs

        def dispatch_round(*args):
            # host-side wire accounting per dispatched round (static
            # bytes, from the packed shapes) — ~200ns when telemetry
            # is disabled, invisible next to the device round
            if saved:
                telemetry.metrics().counter(
                    "ps_round_comm_bytes_saved_total",
                    fidelity=fid).inc(saved)
            # AOT execution path: one explicit lower+compile per batch
            # shape (args[2]; every other operand's shape is fixed per
            # dataplane), so the cost ledger holds the Compiled handle
            # for EVERY program that ever runs — same one-trace-per-
            # shape contract the compile guard asserts, plus
            # cost/memory analysis and compile time on the record.
            key = tuple((tuple(x.shape), str(x.dtype))
                        for x in jax.tree_util.tree_leaves(args[2]))
            entry = programs.get(key)
            if entry is None:
                entry = self._compile_round(key, args)
            self._last_record = entry[1]
            return entry[0](*args)

        self.round = dispatch_round

    def _compile_round(self, key, args):
        """Ledger miss: AOT-compile the round for this batch shape and
        record its XLA cost model (tentpole 1, ISSUE 17)."""
        from distkeras_tpu import attrib as attrib_lib

        fid = self._round_fid
        t0 = time.perf_counter()
        compiled = self._round_jit.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        cost = attrib_lib.extract_cost(compiled)
        rec = {
            "program": fid,
            "comm_dtype": self.comm_dtype,
            "comm_codec": self.comm_codec,
            "workers": self.num_workers,
            "batch_shapes": key,
            "flops": cost["flops"],
            "bytes_accessed": cost["bytes_accessed"],
            "peak_temp_bytes": cost["peak_temp_bytes"],
            "argument_bytes": cost["argument_bytes"],
            "output_bytes": cost["output_bytes"],
            "collective_bytes": dict(self.comm_bytes_per_round),
            "comm_bytes_saved": self.comm_bytes_saved_per_round,
            "compile_s": compile_s,
        }
        m = telemetry.metrics()
        m.counter("ps_round_compile_seconds_total",
                  fidelity=fid).inc(compile_s)
        if cost["flops"] is not None:
            m.gauge("ps_round_program_flops", fidelity=fid).set(
                cost["flops"])
        if cost["bytes_accessed"] is not None:
            m.gauge("ps_round_program_bytes_accessed",
                    fidelity=fid).set(cost["bytes_accessed"])
        self._cost_records.append(rec)
        entry = (compiled, rec)
        self._programs[key] = entry
        return entry

    def compiled_rounds(self) -> list:
        """The AOT ``Compiled`` handle of every round program that ever
        ran, in ledger order — for reading the lowered program itself
        (``as_text()``: the collectives the header claims are there)."""
        return [compiled for compiled, _ in self._programs.values()]

    def last_program_record(self) -> dict | None:
        """Ledger record of the most recently dispatched program (the
        driver's sampled MFU pair reads per-device flops off it)."""
        return self._last_record

    def cost_report(self) -> list[dict]:
        """The XLA cost ledger: one record per compiled round program
        (per batch shape; a dataplane instance is already per comm
        config), with the roofline prediction appended against the
        local device's peak numbers.

        Record schema: ``program`` (fidelity), ``comm_dtype`` /
        ``comm_codec`` / ``workers`` / ``batch_shapes`` (config),
        ``flops`` / ``bytes_accessed`` / ``peak_temp_bytes`` (XLA cost
        + memory analysis, per device; ``None`` when the backend hides
        them), ``collective_bytes`` / ``comm_bytes_saved`` (static wire
        accounting), ``compile_s``, and ``roofline`` (``t_compute_s`` /
        ``t_comm_s`` / ``t_roofline_s`` / ``bound`` /
        ``arithmetic_intensity`` per :func:`attrib.roofline`) with the
        ``peak_flops`` / ``peak_bytes_per_sec`` / ``peak_known`` terms
        it was computed against.
        """
        from distkeras_tpu import attrib as attrib_lib
        from distkeras_tpu import profiling

        dev = jax.devices()[0]
        peak, peak_known = profiling.peak_flops(dev)
        bw, bw_known = profiling.peak_bandwidth(dev)
        out = []
        for rec in self._cost_records:
            r = dict(rec)
            per_dev_comm = (sum(rec["collective_bytes"].values())
                            / max(rec["workers"], 1))
            r["roofline"] = attrib_lib.roofline(
                rec["flops"] or 0.0, per_dev_comm, peak, bw)
            r["peak_flops"] = peak
            r["peak_bytes_per_sec"] = bw
            r["peak_known"] = bool(peak_known and bw_known)
            out.append(r)
        return out


# ---------------------------------------------------------------------------
# Async host dispatch.
# ---------------------------------------------------------------------------


class MeshRoundDriver:
    """Host loop for the mesh round: dispatch k+1 before fetching k.

    Owns the dataplane state (``mps``/``mws``, plus the pipelined
    variant's pending commit) and the metrics ring.  ``dispatch``
    enqueues one round and NEVER blocks on device results; a completed
    ring (every ``metrics_every`` rounds) is fetched only after at
    least one newer round has been dispatched, so host control never
    serializes the device.  ``metrics_every=1`` with async fetch
    reproduces the trainer's historical one-round-late drain exactly.

    ``sync=True`` fetches eagerly after every dispatch — the test
    oracle the async path is asserted byte-identical against.

    ``poll()`` returns per-round metric dicts (host numpy, ``[W]`` per
    metric) that became available since the last call, in round order;
    ``drain()`` additionally blocks on everything outstanding
    (including a partially filled ring) and resets the ring cursor.
    Each device read of a ring increments
    ``ps_metrics_fetches_total``.

    ``attrib_every=N`` arms the sampled step-time decomposition (ISSUE
    17 tentpole 2): every Nth dispatched round is split into host_gap /
    dispatch / device_compute / ring_fetch segments
    (``ps_round_attrib_seconds_total{segment}``) and pairs the
    observed MFU against the ledger's roofline prediction
    (``mfu_observed`` / ``mfu_roofline`` gauges; the latest sample is
    also kept on ``last_attrib`` so bench records work with telemetry
    off).  A sampled round serializes host on device — it is a
    measurement, not the fast path — while non-sampled rounds pay only
    the ``_attrib_tick`` guard plus one clock stamp, and
    ``attrib_every=0`` (default) pays a single int test
    (``attrib.attrib_overhead`` bounds both).  Sampling only ever adds
    reads (an extra block + ring fetch), so the trained state is
    byte-identical to an attrib-off run.
    """

    def __init__(self, dp: MeshDataplane, mps: MeshPSState,
                 mws: MeshWorkerState, *, sync: bool = False,
                 attrib_every: int = 0):
        self.dp = dp
        self.mps = mps
        self.mws = mws
        self.sync = bool(sync)
        self.attrib_every = int(attrib_every)
        if self.attrib_every < 0:
            raise ValueError("attrib_every must be >= 0 (0 disables "
                             "round attribution sampling)")
        self.ring = dp.init_ring()
        self._slot = 0          # next ring slot to write
        self._emitted = 0       # current-ring slots already emitted
        self._round_index = 0   # total rounds dispatched (attrib clock)
        self._last_end = None   # host-gap anchor: prior dispatch end
        self._peaks = None      # cached (peak_flops, known) per driver
        self.last_attrib: dict | None = None
        self._queued: collections.deque = collections.deque()
        self._ready: list[dict] = []
        if dp.pipelined:
            self.pending = dp.init_pending()
            self.pending_perm = jax.device_put(
                jnp.arange(dp.num_workers, dtype=jnp.int32), dp._rep)
            self._false = jax.device_put(jnp.asarray(False), dp._rep)
            self.pending_valid = self._false
            self.pend_live = False

    def _attrib_tick(self) -> bool:
        """Fast-path sampling guard: is the round about to be
        dispatched a sampled one?  ``attrib_every=0`` exits on one int
        test; armed it adds one modulo — the whole disabled-path cost
        ``attrib.attrib_overhead`` bounds (plus the end-of-dispatch
        clock stamp when armed)."""
        ae = self.attrib_every
        if not ae:
            return False
        return self._round_index % ae == 0

    def dispatch(self, batch, perm) -> None:
        """Enqueue one round; fetch only rings completed BEFORE this
        dispatch (async) or everything so far (sync)."""
        sampled = self._attrib_tick()
        self._round_index += 1
        if sampled:
            t0 = time.perf_counter()
        ready = list(self._queued)
        self._queued.clear()
        slot = self.dp.slot_index(self._slot)
        if self.dp.pipelined:
            (self.mps, self.mws, self.pending, self.pending_perm,
             self.pending_valid, self.ring) = self.dp.round(
                self.mps, self.mws, batch, perm, self.pending,
                self.pending_perm, self.pending_valid, self.ring, slot)
            self.pend_live = True
        else:
            self.mps, self.mws, self.ring = self.dp.round(
                self.mps, self.mws, batch, perm, self.ring, slot)
        self._slot += 1
        if sampled:
            self._sample(t0)
        elif self.attrib_every:
            self._last_end = time.perf_counter()
        if self.sync:
            # eager oracle: read the just-written slot every round
            self._emit(self.ring, self._emitted, self._slot)
            self._emitted = self._slot
            if self._slot == self.dp.metrics_every:
                self._slot = self._emitted = 0
        else:
            if self._slot == self.dp.metrics_every:
                self._queued.append((self.ring, self._slot))
                self._slot = 0
            for ring, count in ready:
                self._emit(ring, 0, count)

    def _sample(self, t0: float) -> None:
        """Sampled-round decomposition: split the just-dispatched round
        into segments, emit counters/gauges, stash ``last_attrib``.

        Segments: ``host_gap`` (end of previous dispatch -> this
        dispatch start: host-side work between rounds), ``dispatch``
        (enqueue: program-cache hit + runtime dispatch), and — read off
        the SAME in-flight round by serializing on it — ``device_compute``
        (enqueue return -> outputs ready) and ``ring_fetch`` (device ->
        host transfer of the metrics ring).  The extra block/fetch only
        READS; the trained state is untouched.
        """
        t1 = time.perf_counter()
        jax.block_until_ready((self.mps.blocks, self.ring))
        t2 = time.perf_counter()
        jax.device_get(self.ring)
        t3 = time.perf_counter()
        seg = {
            "host_gap": (t0 - self._last_end
                         if self._last_end is not None else 0.0),
            "dispatch": t1 - t0,
            "device_compute": t2 - t1,
            "ring_fetch": t3 - t2,
        }
        m = telemetry.metrics()
        for name, secs in seg.items():
            m.counter("ps_round_attrib_seconds_total",
                      segment=name).inc(secs)
        attrib = dict(seg)
        rec = self.dp.last_program_record()
        if rec is not None and rec.get("flops"):
            from distkeras_tpu import attrib as attrib_lib
            from distkeras_tpu import profiling

            if self._peaks is None:
                dev = jax.devices()[0]
                self._peaks = (profiling.peak_flops(dev),
                               profiling.peak_bandwidth(dev))
            (peak, peak_known), (bw, bw_known) = self._peaks
            per_dev_comm = (sum(rec["collective_bytes"].values())
                            / max(rec["workers"], 1))
            roof = attrib_lib.roofline(rec["flops"], per_dev_comm,
                                       peak, bw)
            # observed round time = enqueue + device execution: on an
            # async backend dispatch is ~0 so this IS device time; on
            # the synchronous CPU backend the round runs inside the
            # enqueue call and device_compute alone would be ~0
            obs = attrib_lib.mfu(
                rec["flops"],
                seg["dispatch"] + seg["device_compute"], peak)
            pred = attrib_lib.mfu(rec["flops"], roof["t_roofline_s"],
                                  peak)
            if obs is not None and pred is not None:
                m.gauge("mfu_observed").set(obs)
                m.gauge("mfu_roofline").set(pred)
                attrib["mfu_observed"] = obs
                attrib["mfu_roofline"] = pred
                attrib["peak_known"] = bool(peak_known and bw_known)
                attrib["roofline"] = roof
        self.last_attrib = attrib
        self._last_end = time.perf_counter()

    def _emit(self, ring, start: int, stop: int) -> None:
        telemetry.metrics().counter("ps_metrics_fetches_total").inc()
        host = jax.device_get(ring)
        for r in range(start, stop):
            self._ready.append({k: v[r] for k, v in host.items()})

    def poll(self) -> list[dict]:
        """Metric dicts that became available since the last call."""
        out, self._ready = self._ready, []
        return out

    def drain(self) -> list[dict]:
        """Block on every outstanding metric (full + partial rings),
        reset the ring cursor, and return them in round order."""
        while self._queued:
            ring, count = self._queued.popleft()
            self._emit(ring, 0, count)
        if self._slot > self._emitted:
            self._emit(self.ring, self._emitted, self._slot)
        self._slot = self._emitted = 0
        return self.poll()

    def flush_pipeline(self) -> None:
        """Pipelined variant: fold the carried pending commit into the
        center (epoch end / end of training) and re-arm a fresh inert
        pending (the flushed buffers were donated)."""
        if not self.dp.pipelined or not self.pend_live:
            return
        self.mps = self.dp.flush(self.mps, self.pending,
                                 self.pending_perm)
        self.pending = self.dp.init_pending()
        self.pending_valid = self._false
        self.pend_live = False
