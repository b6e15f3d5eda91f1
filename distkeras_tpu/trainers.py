"""Trainer hierarchy — the framework's front door.

Mirrors the reference's ``distkeras/trainers.py`` surface (SURVEY.md §2.1):
``SingleTrainer``, ``EnsembleTrainer``/``AveragingTrainer``, and the async
parameter-server family ``DOWNPOUR`` / ``ADAG`` / ``AEASGD`` / ``EAMSGD`` /
``DynSGD`` — plus the TPU-native ``SyncTrainer`` (synchronous data
parallelism over ICI, the convergence control arm the reference lacked,
SURVEY.md §2.3).

Semantics map (reference -> rebuild):

* Spark DataFrame             -> ``distkeras_tpu.data.Dataset``
* ``num_workers`` partitions  -> slices of the device mesh's worker axis
  (``distkeras_tpu.mesh``), emulated per-device via ``vmap`` when the
  worker count exceeds the device count (Spark ``local[N]`` analogue)
* TCP pull/commit to the driver PS -> emulated commit rounds compiled
  on-mesh (``parallel.ps_emulator``) with deterministic staleness
* ``communication_window``    -> window of jitted local steps per round
* trained Keras model         -> flax variables dict (+ ``ModelSpec``)

Every trainer records ``training_time`` (as the reference's ``Trainer``
does) and a richer ``history`` (per-round losses, staleness telemetry —
SURVEY.md §5 "honest observability").
"""

from __future__ import annotations

import functools
import os
import pathlib
import queue
import threading
import time
from typing import Any, ClassVar, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu import mesh as mesh_lib
from distkeras_tpu import telemetry
from distkeras_tpu.analysis import racecheck
from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models.core import ModelSpec
from distkeras_tpu.parallel import ps_dataplane, tensor_parallel
from distkeras_tpu.parallel.ps_emulator import make_round_fn
from distkeras_tpu.parallel.tiers import resolve_tier, tiers_with
from distkeras_tpu.parallel.update_rules import (
    AdagRule,
    DownpourRule,
    DynSGDRule,
    ElasticRule,
    UpdateRule,
)
from distkeras_tpu.workers import (
    TrainState,
    make_train_step,
    make_window_runner,
    resolve_optimizer,
)

Pytree = Any


def _resolve_spec(model) -> ModelSpec:
    if isinstance(model, ModelSpec):
        return model
    if isinstance(model, Mapping):
        return ModelSpec.from_config(model)
    raise TypeError(
        "model must be a ModelSpec or a model config dict "
        "(distkeras_tpu.models.model_config); got "
        f"{type(model).__name__}")


def _stack_batches(shard: Dataset, batch_size: int,
                   columns: Sequence[str]) -> dict[str, np.ndarray] | None:
    """Rows -> stacked batch arrays ``[num_batches, B, ...]``."""
    n = shard.num_batches(batch_size)
    if n == 0:
        return None
    out = {}
    for c in columns:
        col = shard[c][:n * batch_size]
        out[c] = col.reshape((n, batch_size) + col.shape[1:])
    return out


def _prefetch_depth() -> int:
    """Segments to load ahead of the consumer (0 disables).  Env-gated
    so the IO/compute-overlap A/B (PERF.md) and the bit-identity test
    can toggle it; prefetch never changes results, only timing."""
    return int(os.environ.get("DKT_SEGMENT_PREFETCH", "1"))


def _prefetch_iter(it, depth: int | None = None):
    """Iterate ``it`` on a daemon thread, keeping up to ``depth`` items
    built ahead of the consumer — overlaps segment IO (read / parse /
    shuffle) with the compute consuming the previous segment.  Order-
    preserving; iterator exceptions re-raise at the consumer's ``next``.
    """
    if depth is None:
        depth = _prefetch_depth()
    if depth <= 0:
        yield from it
        return
    done = object()
    q: queue.Queue = queue.Queue()
    # build tickets: the feeder may hold depth items beyond the one the
    # consumer is processing; released as the consumer moves on
    slots = threading.Semaphore(depth + 1)
    # set when the consumer abandons the generator mid-epoch (train
    # error, KeyboardInterrupt): the feeder must exit rather than block
    # in slots.acquire() forever pinning loaded segments
    cancelled = threading.Event()

    def feed():
        try:
            while True:
                slots.acquire()
                if cancelled.is_set():
                    return
                try:
                    item = next(it)
                except StopIteration:
                    q.put(done)
                    return
                q.put((item,))
        except BaseException as exc:  # surfaced on the consumer side
            q.put(exc)
            q.put(done)

    threading.Thread(target=feed, daemon=True,
                     name="dkt-segment-prefetch").start()
    try:
        while True:
            got = q.get()
            if got is done:
                return
            if isinstance(got, BaseException):
                raise got
            yield got[0]
            slots.release()
    finally:
        cancelled.set()
        slots.release()  # wake a feeder blocked on the ticket


def _epoch_segments(dataset, seed: int, stall: list | None = None):
    """One epoch as in-memory ``Dataset`` segments.

    In-memory datasets yield exactly one segment — the whole set,
    shuffled — so existing behavior is bit-identical.  A
    ``ShardedDataset`` (``data/sharded.py``) streams its shard files in
    seed-permuted order with rows shuffled per shard, so host peak
    memory is one segment being trained plus the prefetched next
    (Spark's partition streaming was the reference's equivalent,
    SURVEY.md §1 L0).

    ``stall`` (a one-element list) accumulates the seconds the CONSUMER
    spent blocked waiting for segments — the IO stall the prefetch
    thread exists to hide.  Unlike epoch wall-time it is exact, not
    noise-bound: with prefetch off it converges to the full load cost,
    with prefetch on to whatever the overlap could not hide."""
    from distkeras_tpu.data.sharded import ShardedDataset

    if isinstance(dataset, ShardedDataset):
        it = _prefetch_iter(dataset.epoch_segments(seed))
    else:
        it = iter([dataset.shuffle(seed=seed)])
    if stall is None:
        return it

    def timed():
        while True:
            t0 = telemetry.now()
            try:
                item = next(it)
            except StopIteration:
                return
            stall[0] += telemetry.now() - t0
            yield item
    return timed()


class _SegmentPrefetch:
    """One-deep background segment load for plan-driven loops (the
    emulated-PS arm, which must decide skips from metadata *before*
    touching the file).  ``queue(key, load)`` starts ``load()`` on a
    daemon thread; ``get(key, load)`` joins and returns it — or falls
    back to a synchronous ``load()`` on a key mismatch, so a wrong
    lookahead prediction costs only the overlap, never correctness.
    Load errors re-raise in ``get`` on the consumer thread."""

    def __init__(self):
        self._key = None
        self._thread: threading.Thread | None = None
        self._box: dict | None = None

    def queue(self, key, load):
        box: dict = {}

        def run():
            try:
                box["value"] = load()
            except BaseException as exc:
                box["error"] = exc

        t = threading.Thread(target=run, daemon=True,
                             name="dkt-segment-prefetch")
        t.start()
        self._key, self._thread, self._box = key, t, box

    def get(self, key, load):
        if self._thread is not None and self._key == key:
            self._thread.join()
            box = self._box
            self._key = self._thread = self._box = None
            if "error" in box:
                raise box["error"]
            return box["value"]
        return load()


def _epoch_segment_loaders(dataset, seed: int):
    """``_epoch_segments`` with the data deferred: yields ``(rows,
    load)`` so a resuming PS trainer can skip whole already-consumed
    shard files from header metadata alone."""
    from distkeras_tpu.data.sharded import ShardedDataset

    if isinstance(dataset, ShardedDataset):
        return dataset.epoch_segment_loaders(seed)
    return iter([(len(dataset),
                  lambda: dataset.shuffle(seed=seed))])


class Trainer:
    """Base trainer: owns the model spec, loss, worker optimizer, batch
    size and epoch count (the reference ``Trainer``'s fields), plus the
    trained result and timing."""

    def __init__(self, model, loss: str = "categorical_crossentropy",
                 worker_optimizer="sgd", learning_rate=None,
                 features_col: str = "features", label_col: str = "label",
                 batch_size: int = 32, num_epoch: int = 1, seed: int = 0,
                 checkpoint_dir: str | None = None,
                 profile_dir: str | None = None):
        """``learning_rate``: float, optax schedule, or a JSON-friendly
        ``{"schedule": name, **kwargs}`` dict (see
        ``workers.resolve_schedule``).  ``profile_dir`` wraps the whole
        ``train()`` in a ``jax.profiler`` trace written there (view
        with TensorBoard / xprof)."""
        self.spec = _resolve_spec(model)
        n_heads = len(self.spec.kwargs.get("outputs", ()))
        if n_heads > 1:
            # multi-output models train with one loss + label column
            # PER HEAD — validate here, not deep inside a jit trace
            if not (isinstance(loss, (list, tuple))
                    and isinstance(label_col, (list, tuple))
                    and len(loss) == n_heads
                    and len(label_col) == n_heads):
                raise ValueError(
                    f"this model has {n_heads} output heads: pass "
                    f"loss= and label_col= as sequences of {n_heads} "
                    f"entries (one loss and one label column per "
                    f"head); got loss={loss!r}, "
                    f"label_col={label_col!r}")
        elif isinstance(loss, (list, tuple)) \
                or isinstance(label_col, (list, tuple)):
            # single-head model: unwrap the length-1 sequence spelling
            # (mirrors the multi-head API), reject anything longer
            if not (isinstance(loss, (list, tuple))
                    and isinstance(label_col, (list, tuple))
                    and len(loss) == 1 and len(label_col) == 1):
                raise ValueError(
                    f"this model has one output head; loss= and "
                    f"label_col= sequences must both have exactly one "
                    f"entry (got loss={loss!r}, "
                    f"label_col={label_col!r})")
            loss, label_col = loss[0], label_col[0]
        self.model = self.spec.build()
        self.loss = loss
        self.worker_optimizer = worker_optimizer
        self.learning_rate = learning_rate
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)
        self.seed = int(seed)
        self.checkpoint_dir = checkpoint_dir
        self.profile_dir = profile_dir
        self.training_time: float = 0.0
        # ``history`` is a read VIEW over this trainer's own metrics
        # registry (ISSUE 2: one bookkeeping system, not a second
        # hand-rolled dict): ``_record`` appends to thread-safe
        # registry series, the dict-like read surface — history[k],
        # .get, ``in`` — is unchanged.  The per-trainer registry is
        # always on (history must exist with global telemetry
        # disabled) and exportable like any other registry
        # (``trainer.metrics.write_jsonl(...)``).
        self.metrics = telemetry.MetricsRegistry()
        self.history = telemetry.HistoryView(self.metrics)
        self.trained_variables: dict | None = None

    # -- shared plumbing ---------------------------------------------------

    def _tx(self):
        return resolve_optimizer(self.worker_optimizer, self.learning_rate)

    def _init_variables(self, initial_variables=None) -> dict:
        if initial_variables is not None:
            return dict(initial_variables)
        sample = jnp.asarray(self.spec.example_input(self.batch_size))
        return self.model.init(jax.random.key(self.seed), sample)

    def _columns(self) -> list[str]:
        labels = (list(self.label_col)
                  if isinstance(self.label_col, (list, tuple))
                  else [self.label_col])
        return [self.features_col, *labels]

    def _record(self, **kwargs):
        for k, v in kwargs.items():
            self.metrics.series(k).append(v)

    def train(self, dataset: Dataset, initial_variables=None,
              resume_from: str | None = None,
              eval_dataset: Dataset | None = None) -> dict:
        """Train on ``dataset``.  ``resume_from`` continues from a
        checkpoint written by a previous run with ``checkpoint_dir``
        set (same trainer configuration + dataset ⇒ bitwise-identical
        continuation; see distkeras_tpu.checkpoint).  ``eval_dataset``
        records ``history['eval_accuracy']`` at every epoch boundary
        (the reference notebooks' accuracy-vs-trainer comparison,
        done in-framework)."""
        from distkeras_tpu.profiling import profiler_trace

        if eval_dataset is not None and isinstance(
                self.label_col, (list, tuple)):
            raise NotImplementedError(
                "per-epoch eval_dataset= supports single-head models "
                "(one prediction column against one label column); "
                "evaluate a multi-output model per head after "
                "training via ModelPredictor + ops.metrics")
        self._eval_dataset = eval_dataset
        start = time.time()
        try:
            with profiler_trace(self.profile_dir), \
                    telemetry.span("train",
                                   trainer=type(self).__name__):
                return self._train(dataset, initial_variables,
                                   resume_from)
        finally:
            self.training_time = time.time() - start

    def _eval_epoch(self, variables) -> None:
        """Epoch-boundary hook: accuracy on ``eval_dataset`` if set.
        The predictor (and its jitted forward) is built once and reused
        across epochs — only ``.variables`` is swapped."""
        if getattr(self, "_eval_dataset", None) is None:
            return
        from distkeras_tpu.evaluators import metrics_from_logits
        from distkeras_tpu.predictors import ModelPredictor

        host_vars = jax.tree_util.tree_map(mesh_lib.fetch, variables)
        predictor = getattr(self, "_eval_predictor", None)
        if predictor is None:
            predictor = ModelPredictor(
                self.model, host_vars, features_col=self.features_col,
                output="logits", batch_size=max(self.batch_size, 256))
            self._eval_predictor = predictor
        predictor.variables = host_vars
        scored = predictor.predict(self._eval_dataset)
        m = metrics_from_logits(scored["prediction"],
                                self._eval_dataset[self.label_col])
        self._record(eval_accuracy=m["accuracy"])

    def _train(self, dataset, initial_variables, resume_from=None):
        raise NotImplementedError

    # -- checkpoint plumbing ----------------------------------------------

    def _maybe_save(self, state, cursor: dict):
        # The full history rides in every checkpoint so a resumed run
        # reproduces the uninterrupted history exactly.  Cost grows with
        # rounds trained (O(rounds) per save); for very long runs with
        # frequent mid-epoch saves, an append-only side log would be
        # cheaper — revisit if save latency ever shows up in profiles.
        if self.checkpoint_dir is not None:
            from distkeras_tpu import checkpoint as ckpt

            # materialize the registry view: the cursor is JSON-encoded
            cursor = {**cursor, "history": dict(self.history)}
            if getattr(self, "_sharded_ckpt", False):
                # multi-host sharded state: every process writes only
                # its own shards (orbax)
                ckpt.save_sharded(self.checkpoint_dir, state, cursor)
                # one layout per dir (see the mirror-image cleanup in
                # the msgpack branch)
                if jax.process_index() == 0:
                    (pathlib.Path(self.checkpoint_dir) /
                     ckpt.LATEST).unlink(missing_ok=True)
            else:
                ckpt.save_checkpoint(self.checkpoint_dir, state,
                                     cursor)
                # one layout per dir: a stale sharded checkpoint left
                # from an earlier multi-host run would otherwise shadow
                # this (newer) msgpack save at the next resume
                if ckpt.has_sharded(self.checkpoint_dir) and \
                        jax.process_index() == 0:
                    import shutil

                    shutil.rmtree(
                        pathlib.Path(self.checkpoint_dir) /
                        ckpt.SHARDED, ignore_errors=True)

    def _restore_history(self, cursor: dict) -> dict:
        """Pop the checkpointed history into the registry-backed view
        (the view object stays; its backing series are reset)."""
        self.history.replace({
            k: list(v) for k, v in cursor.pop("history", {}).items()})
        return cursor

    def _maybe_resume(self, resume_from, state_template):
        """Returns (state, cursor) — (template, {}) when not resuming."""
        if resume_from is None:
            return state_template, {}
        from distkeras_tpu import checkpoint as ckpt

        state, cursor = ckpt.load_checkpoint(resume_from, state_template)
        return state, self._restore_history(cursor)


class SingleTrainer(Trainer):
    """Sequential baseline: one worker, whole dataset (reference
    ``SingleTrainer``: coalesce to one partition, SURVEY.md §3.1).  The
    epoch is scanned on-device in chunks, not stepped from Python."""

    SCAN_CHUNK = 64  # batches per device call (host loop granularity)

    def _train(self, dataset, initial_variables, resume_from=None):
        tx = self._tx()
        variables = self._init_variables(initial_variables)
        state = TrainState.create(variables, tx,
                                  jax.random.key(self.seed + 1))
        state, cursor = self._maybe_resume(resume_from, state)
        start_epoch = int(cursor.get("epoch", 0))
        step = make_train_step(self.model, self.loss, tx,
                               self.features_col, self.label_col)
        run_chunk = jax.jit(make_window_runner(step))

        for epoch in range(start_epoch, self.num_epoch):
            t_epoch = telemetry.now()
            losses = []
            stall = [0.0]
            segments = _epoch_segments(dataset, self.seed + epoch,
                                       stall)
            while True:
                with telemetry.span("segment_wait", epoch=epoch):
                    segment = next(segments, None)
                if segment is None:
                    break
                with telemetry.span("stack_and_put", epoch=epoch):
                    stacked = _stack_batches(segment, self.batch_size,
                                             self._columns())
                if stacked is None:
                    # a shard file smaller than one batch: dropped like
                    # any other tail remainder (never silently for the
                    # whole epoch — see the check below)
                    continue
                n = len(next(iter(stacked.values())))
                for lo in range(0, n, self.SCAN_CHUNK):
                    steps = min(self.SCAN_CHUNK, n - lo)
                    with telemetry.span("stack_and_put", epoch=epoch,
                                        steps=steps):
                        chunk = {
                            k: jnp.asarray(v[lo:lo + self.SCAN_CHUNK])
                            for k, v in stacked.items()}
                    with telemetry.span("chunk_dispatch", epoch=epoch,
                                        steps=steps):
                        state, metrics = run_chunk(state, chunk)
                    with telemetry.span("loss_fetch", epoch=epoch,
                                        steps=steps):
                        losses.append(np.asarray(metrics["loss"]))
            if not losses:
                raise ValueError("dataset smaller than one batch")
            with telemetry.span("epoch_end", epoch=epoch,
                                steps=sum(len(x) for x in losses)):
                epoch_loss = float(np.concatenate(losses).mean())
                self._record(epoch_loss=epoch_loss,
                             segment_stall_s=round(stall[0], 4))
                self._eval_epoch(state.variables())
                self._maybe_save(state, {"epoch": epoch + 1})
            telemetry.complete("epoch", t_epoch, epoch=epoch,
                               trainer=type(self).__name__)
        self.trained_variables = state.variables()
        return self.trained_variables


class SyncTrainer(Trainer):
    """Synchronous data parallelism over the mesh — one jitted step with
    the global batch sharded across the worker axis; XLA inserts the ICI
    all-reduce on the gradients (SURVEY.md §2.3 "sync DP via pjit is the
    natural TPU baseline").  Not in the reference; it is the convergence
    control arm for the async family."""

    SCAN_CHUNK = 32

    def __init__(self, model, num_workers: int | None = None,
                 model_parallel: int = 1, tp_rules=None,
                 pipeline_stages: int = 1,
                 pipeline_microbatches: int | None = None, **kwargs):
        """``model_parallel`` > 1 adds a tensor-parallel dimension: the
        mesh becomes ``(workers, model)`` and parameters are sharded
        over the ``model`` axis per ``parallel.tensor_parallel`` rules
        (Megatron-style for ``transformer_lm``/``mlp``; pass
        ``tp_rules`` for custom models).  Pure GSPMD — same numerics as
        ``model_parallel=1``, XLA inserts the collectives.

        ``pipeline_stages`` > 1 instead runs dp x pp over a
        ``(workers, stage)`` mesh: the model must be a
        ``transformer_lm`` whose ``num_layers`` divides into the stage
        count — its layer stack (``scan_blocks`` form) is sharded one
        slice per stage and driven through the GPipe microbatch
        schedule (``parallel.pipeline``).  ``pipeline_microbatches``
        defaults to 2 x stages (bubble fraction (S-1)/(M+S-1)).
        Mutually exclusive with ``model_parallel``."""
        super().__init__(model, **kwargs)
        self.num_workers = num_workers
        self.model_parallel = int(model_parallel)
        if self.model_parallel < 1:
            raise ValueError(
                f"model_parallel must be >= 1, got {model_parallel}")
        self.tp_rules = tp_rules
        self.pipeline_stages = int(pipeline_stages)
        if self.pipeline_stages < 1:
            raise ValueError(
                f"pipeline_stages must be >= 1, got {pipeline_stages}")
        if self.pipeline_stages > 1 and self.model_parallel > 1:
            raise ValueError(
                "pipeline_stages and model_parallel are mutually "
                "exclusive (pp x tp composition is not implemented)")
        self.pipeline_microbatches = (
            None if pipeline_microbatches is None
            else int(pipeline_microbatches))

    def _train(self, dataset, initial_variables, resume_from=None):
        if self.pipeline_stages > 1:
            return self._train_pipeline(dataset, initial_variables,
                                        resume_from)
        return self._train_dp(dataset, initial_variables, resume_from)

    def _train_pipeline(self, dataset, initial_variables, resume_from):
        """dp x pp: see ``parallel.pipeline.make_pp_train_step``."""
        from distkeras_tpu.models.core import ModelSpec
        from distkeras_tpu.parallel import pipeline as pp
        from distkeras_tpu.ops.losses import resolve_loss

        if jax.process_count() > 1:
            raise NotImplementedError(
                "pipeline_stages > 1 is single-process for now (the "
                "stage axis must not cross hosts anyway; use more "
                "workers per host)")
        stages = self.pipeline_stages
        if self.spec.family != "transformer_lm":
            raise ValueError(
                f"pipeline_stages > 1 supports the transformer_lm "
                f"family (homogeneous blocks), got "
                f"{self.spec.family!r}")
        kwargs = dict(self.spec.kwargs)
        if kwargs.get("num_experts"):
            raise ValueError(
                "pipeline_stages > 1 supports the dense-FFN "
                "transformer (MoE blocks are not homogeneous across "
                "the stack's expert dispatch)")
        n_layers = kwargs.get("num_layers", 4)
        if n_layers % stages:
            raise ValueError(
                f"num_layers={n_layers} does not divide into "
                f"{stages} stages")
        kwargs["scan_blocks"] = True
        spec = ModelSpec(family="transformer_lm", kwargs=kwargs,
                         input_shape=self.spec.input_shape,
                         input_dtype=self.spec.input_dtype)
        model = spec.build()

        devices = jax.devices()
        num_workers = self.num_workers or max(
            1, len(devices) // stages)
        if num_workers * stages > len(devices):
            raise ValueError(
                f"pipeline_stages={stages} with {num_workers} workers "
                f"needs {num_workers * stages} devices, have "
                f"{len(devices)}")
        mesh = Mesh(
            np.asarray(devices[:num_workers * stages]).reshape(
                num_workers, stages),
            (mesh_lib.WORKER_AXIS, pp.STAGE_AXIS))
        microbatches = self.pipeline_microbatches or 2 * stages
        if self.batch_size % microbatches:
            raise ValueError(
                f"per-worker batch {self.batch_size} not divisible "
                f"into {microbatches} microbatches")

        tx = self._tx()
        if initial_variables is not None:
            variables = dict(initial_variables)
        else:
            sample = jnp.asarray(spec.example_input(self.batch_size))
            variables = model.init(jax.random.key(self.seed), sample)
        state = TrainState.create(variables, tx,
                                  jax.random.key(self.seed + 1))
        state, cursor = self._maybe_resume(resume_from, state)
        specs = pp.lm_state_specs(state)
        state_shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        state = jax.device_put(state, state_shardings)
        step = pp.make_pp_train_step(
            model, resolve_loss(self.loss), tx, mesh,
            num_microbatches=microbatches,
            workers_axis=mesh_lib.WORKER_AXIS,
            features_col=self.features_col, label_col=self.label_col)
        run_chunk = jax.jit(make_window_runner(step))

        global_batch = self.batch_size * num_workers
        batch_sharded = NamedSharding(
            mesh, P(None, mesh_lib.WORKER_AXIS))
        start_epoch = int(cursor.get("epoch", 0))
        self.num_workers = num_workers
        for epoch in range(start_epoch, self.num_epoch):
            t_epoch = telemetry.now()
            pending = []
            stall = [0.0]
            for segment in _epoch_segments(dataset, self.seed + epoch,
                                           stall):
                stacked = _stack_batches(segment, global_batch,
                                         self._columns())
                if stacked is None:
                    continue
                n = len(next(iter(stacked.values())))
                for lo in range(0, n, self.SCAN_CHUNK):
                    local = {k: v[lo:lo + self.SCAN_CHUNK]
                             for k, v in stacked.items()}
                    chunk = jax.device_put(local, batch_sharded)
                    state, metrics = run_chunk(state, chunk)
                    pending.append(metrics["loss"])
            if not pending:
                raise ValueError(
                    f"dataset smaller than one global batch "
                    f"({global_batch})")
            losses = [mesh_lib.fetch(x) for x in pending]
            self._record(
                epoch_loss=float(np.concatenate(losses).mean()),
                segment_stall_s=round(stall[0], 4))
            self._eval_epoch(state.variables())
            self._maybe_save(state, {"epoch": epoch + 1})
            telemetry.complete("epoch", t_epoch, epoch=epoch,
                               trainer=type(self).__name__)
        self.trained_variables = state.variables()
        return self.trained_variables

    def _train_dp(self, dataset, initial_variables, resume_from=None):
        devices = jax.devices()
        mp = self.model_parallel
        num_workers = self.num_workers or max(1, len(devices) // mp)
        use_mesh = len(devices) >= num_workers * mp > 1
        if mp > 1 and not use_mesh:
            raise ValueError(
                f"model_parallel={mp} with {num_workers} workers needs "
                f"{num_workers * mp} devices, have {len(devices)}")
        # Multi-host TP state is not fully addressable: switch
        # _maybe_save to the per-shard orbax layout (checkpoint.py
        # save_sharded) instead of the single-file msgpack fetch.
        self._sharded_ckpt = mp > 1 and jax.process_count() > 1
        global_batch = self.batch_size * num_workers
        # Multi-host: every process runs this same program; each holds
        # only its rows of the (identically generated) global dataset and
        # contributes them to the globally-sharded batch.
        pc = jax.process_count()
        if pc > 1:
            if not use_mesh or global_batch % pc:
                raise ValueError(
                    f"multi-host SyncTrainer needs a mesh and a global "
                    f"batch divisible by process count ({pc})")
        local_batch = global_batch // pc

        tx = self._tx()
        variables = self._init_variables(initial_variables)
        state = TrainState.create(variables, tx,
                                  jax.random.key(self.seed + 1))
        from distkeras_tpu import checkpoint as ckpt_mod

        resume_sharded = (resume_from is not None
                          and ckpt_mod.has_sharded(resume_from))
        cursor: dict = {}
        if not resume_sharded:
            state, cursor = self._maybe_resume(resume_from, state)
        step = make_train_step(self.model, self.loss, tx,
                               self.features_col, self.label_col)
        run_chunk = make_window_runner(step)

        if use_mesh:
            m = mesh_lib.create_mesh(num_workers, model_parallel=mp,
                                     devices=devices)
            rep = NamedSharding(m, P())
            # [chunk, B_global, ...]: global batch axis sharded across
            # workers — both the jit contract and the host-side chunk
            # assembly below use this one sharding.
            batch_sharded = NamedSharding(
                m, P(None, mesh_lib.WORKER_AXIS))
            if mp > 1:
                rules = (self.tp_rules if self.tp_rules is not None
                         else tensor_parallel.rules_for(self.spec.family))
                state_sharding = tensor_parallel.tree_shardings(
                    m, state, rules)
            else:
                state_sharding = rep
            state = mesh_lib.global_batch_from_local(state_sharding,
                                                     state)
            if resume_sharded:
                # sharded (orbax) checkpoints restore INTO the mesh
                # shardings — each process reads only its own shards
                state, cursor = ckpt_mod.load_sharded(resume_from,
                                                      state)
                cursor = self._restore_history(cursor)
            run_chunk = jax.jit(
                run_chunk,
                in_shardings=(state_sharding, batch_sharded),
                out_shardings=(state_sharding, rep))
        elif resume_sharded:
            raise ValueError(
                f"{resume_from!r} holds a sharded checkpoint but this "
                f"run has no mesh to restore it onto")
        else:
            run_chunk = jax.jit(run_chunk)

        start_epoch = int(cursor.get("epoch", 0))
        self.num_workers = num_workers
        for epoch in range(start_epoch, self.num_epoch):
            t_epoch = telemetry.now()
            pending = []
            stall = [0.0]
            for segment in _epoch_segments(dataset, self.seed + epoch,
                                           stall):
                shard = mesh_lib.process_shard(segment)
                stacked = _stack_batches(shard, local_batch,
                                         self._columns())
                if stacked is None:
                    # shard file smaller than one global batch: tail
                    # remainder; the epoch-level emptiness check below
                    # keeps it from passing silently
                    continue
                n = len(next(iter(stacked.values())))
                for lo in range(0, n, self.SCAN_CHUNK):
                    local = {k: v[lo:lo + self.SCAN_CHUNK]
                             for k, v in stacked.items()}
                    if use_mesh:
                        chunk = mesh_lib.global_batch_from_local(
                            batch_sharded, local)
                    else:
                        chunk = {k: jnp.asarray(v)
                                 for k, v in local.items()}
                    state, metrics = run_chunk(state, chunk)
                    # keep the device handle; fetching here would block
                    # next chunk's host assembly behind device compute
                    pending.append(metrics["loss"])
            if not pending:
                raise ValueError(
                    f"dataset smaller than one global batch "
                    f"({global_batch})")
            losses = [mesh_lib.fetch(x) for x in pending]
            self._record(
                epoch_loss=float(np.concatenate(losses).mean()),
                segment_stall_s=round(stall[0], 4))
            self._eval_epoch(state.variables())
            self._maybe_save(state, {"epoch": epoch + 1})
            telemetry.complete("epoch", t_epoch, epoch=epoch,
                               trainer=type(self).__name__)
        self.trained_variables = state.variables()
        return self.trained_variables


class DistributedTrainer(Trainer):
    """Base for the async PS family (reference ``DistributedTrainer`` /
    ``AsynchronousDistributedTrainer``): ``num_workers`` +
    ``communication_window``, worker placement on the mesh, emulated
    commit rounds."""

    #: effective per-round lr (configured lr x family amplification)
    #: above which the staleness families measurably degrade on the
    #: PARITY.md calibration task (MNIST MLP, sgd workers): the
    #: collapsing configs sit at 0.2-0.8, every law-scaled PARITY row
    #: at <= 0.1.  A heuristic guardrail, not a convergence proof.
    _LR_LAW_EFFECTIVE_MAX = 0.1

    def __init__(self, model, num_workers: int = 2,
                 communication_window: int = 5,
                 fidelity: str = "faithful",
                 transport: str = "inprocess",
                 checkpoint_every_rounds: int | None = None,
                 max_worker_failures: int = 0,
                 worker_retries: int = 0,
                 worker_timeout: float | None = None,
                 fault_injector=None, compression=None,
                 model_parallel: int = 1, tp_rules=None,
                 lr_law: str = "warn",
                 commit_overlap: bool = False,
                 ps_address: tuple[str, int] | None = None,
                 ps_replicas: list | None = None,
                 ps_shards: int = 1,
                 ps_elastic: bool = False,
                 ps_groups: list | None = None,
                 ps_snapshot_path: str | None = None,
                 ps_snapshot_every: int = 0,
                 comm_dtype: str = "float32",
                 comm_codec=None,
                 metrics_every: int = 1,
                 attrib_every: int = 0, **kwargs):
        """Elastic recovery (``fidelity='host'`` — the arm with real
        concurrency, hence real failures; the emulated arms recover via
        checkpoint/resume instead): a failing worker round is retried
        up to ``worker_retries`` times — the worker re-pulls the center
        and re-runs the window, which is exactly-once-per-commit by
        construction (the failed window's delta never reached the
        server; durable state lives only in the PS).  This is the
        correct form of the retry the reference inherited from Spark,
        which replayed a partition *against the live PS* (SURVEY.md §5
        "semantic hazard").  A worker that exhausts its retries dies;
        training continues if at most ``max_worker_failures`` workers
        have died (default 0: fail fast, the round-1 behavior).
        ``fault_injector(worker, epoch, round)`` is the chaos hook —
        called before every round; raise from it to inject a failure
        (SURVEY.md §5 "fault injection").  ``worker_timeout`` (seconds)
        arms a watchdog that records workers silent on the PS heartbeat
        beyond the timeout into ``history['detected_idle_workers']`` —
        the detection signal; the retry/elastic machinery is the
        action.  ``compression`` (``'int8'`` / ``'bfloat16'`` /
        ``'topk[:frac]'`` / a ``parallel.compression`` codec, host arm
        only) compresses each delta-family commit on the wire with
        client-side error feedback; wire/raw byte totals land in
        ``history['commit_wire_bytes']`` / ``['commit_raw_bytes']``
        (process-local under multi-host).  ``model_parallel=k`` runs
        every emulated worker tensor-parallel over a ``(workers,
        model)`` mesh — worker states shard ``P(workers, *tp_spec)``
        (``tp_rules`` defaulting to the family's Megatron-style rules),
        the PS center shards by the TP specs alone, and GSPMD derives
        both the TP collectives inside each worker and the commit
        reduction across workers; for PS-family models too big for one
        chip (beyond the reference, which was DP-only).

        Fault tolerance (host arm; docs/API.md "Fault tolerance"):
        network-level failures — connects, pulls, commits — are
        retried INSIDE ``parallel.host_ps.ResilientPSClient`` with
        exponential backoff + jitter and at-most-once commit seqs (a
        commit whose ack was lost is deduped server-side, never
        applied twice); compute-level failures (``fault_injector``, a
        poisoned window) re-pull and re-run the window here.  Both
        budgets are ``worker_retries`` and both record
        ``history['worker_round_retries']``.
        ``ps_snapshot_path`` + ``ps_snapshot_every=N`` (socket/
        in-process host arm) write a warm-restart PS snapshot every N
        commits — ``PSServer.restart_from`` brings a killed server
        back and reconnecting workers resume without double-applying
        (``history['ps_snapshots']`` counts the writes).
        ``ps_address=(host, port)`` attaches to an EXTERNALLY managed
        ``PSServer`` instead of creating one: the PS outlives this
        driver (the reference's driver-death=job-death hole,
        SURVEY.md §5), and an operator can kill/warm-restart it
        mid-run; requires ``transport='socket'`` (the server's rule
        must match this trainer's; staleness history stays
        server-side).

        ``ps_replicas=[(host, port), ...]`` attaches to a REPLICATED
        external PS (``parallel.replicated_ps``): the ORDERED worker
        address list of the replica group (the same order every
        replica holds — it is also the promotion tie-break).  Each
        worker's client walks the list with probe-before-declare-dead
        (``ResilientPSClient.for_replicas``), so a primary kill
        mid-training fails over to the promoted standby with the
        retried commit deduped by the replicated commit log — no
        operator action, byte-identical final center.
        ``history['ps_failovers']`` counts client-observed failovers;
        ``history['ps_epoch']`` records the serving replica's fencing
        epoch at the end of the run (``-1`` when that replica died
        after the final pull).  Mutually exclusive with
        ``ps_address`` (a one-element list is the unreplicated
        equivalent); same contract otherwise — socket transport, the
        group outlives the driver, snapshotting configured on the
        replicas.

        ``ps_shards=K`` (host arm, delta family) runs the PS sharded
        (``parallel.sharded_ps``): the parameter tree's leaves are
        partitioned into K byte-balanced shards, each with its own
        lock/clock/dedupe, so commits from different workers proceed
        per shard instead of convoying on one mutex; over
        ``transport='socket'`` the exchange additionally rides the
        zero-copy scatter-gather wire with version-delta pulls
        (``history['pull_shards_skipped'/'pull_bytes_saved']``).
        With an external ``ps_address`` the server must have been
        created with the same K.  Both rule families shard: the delta
        family's additive updates and the elastic family's per-leaf
        lerp are each exact per shard (the elastic local tree rides
        the wire as a second frame per shard).

        ``ps_elastic=True`` (host arm, socket) attaches to an
        ``parallel.elastic_ps.ElasticPSGroup`` member instead of a
        classic ``PSServer``: ``ps_address`` seeds the versioned
        shard-map bootstrap, and the group may split/merge/migrate
        shards (or be driven by ``telemetry.Autoscaler``) WHILE this
        trainer runs — workers re-route on fence/stale rejections via
        ``ResilientPSClient`` with zero training downtime.  Shard
        topology is owned server-side, so ``ps_shards`` stays 1 here;
        compression does not compose (the elastic wire ships raw
        leaf bytes so resharding stays byte-exact).

        ``ps_groups=[(leader_addr, [worker_ids...]), ...]`` (host arm,
        socket, delta family) runs the two-level hierarchical topology
        (``parallel.hier_ps``): each listed group's workers commit to
        a ``GroupLeader`` that folds their deltas over an
        ``aggregate_window`` (the group size) and forwards ONE
        pre-reduced upstream commit per window, cutting root fan-in
        from O(workers) to O(groups).  ``leader_addr`` is the
        ``(host, port)`` the leader binds, or ``None`` for a
        loopback-ephemeral bind; workers not listed in any group stay
        direct-to-root.  A dead leader degrades its workers to
        direct-to-root mode via a two-hop failover route (the
        ``leader_down`` / ``leader_rejoin`` flight kinds and the
        ``leader_failover_rate`` SLO); history grows
        ``ps_upstream_commits`` / ``ps_fanin_reduction`` /
        ``ps_leader_failovers``.  Composes with ``ps_shards`` (the
        root runs sharded; upstream windows ship the full tree),
        ``compression`` (the worker->leader hop), chaos and
        snapshots; the trainer must own the root server
        (mutually exclusive with ``ps_address`` / ``ps_replicas`` /
        ``ps_elastic`` and multi-host).

        ``commit_overlap=True`` on the host
        arm double-buffers each worker's loop: the commit/pull
        exchange for window *n* runs on a background thread while the
        device computes window *n+1* (the worker trains one exchange
        behind — +1 round of staleness, same trade as the emulated
        pipelined round).

        ``comm_dtype='bfloat16'`` / ``comm_codec='int8'`` (mesh tier
        only) lower communication compression INSIDE the compiled
        round: bf16 deltas through the reduce-scatter, an int8
        per-leaf-quantized center re-broadcast replacing the f32
        all-gather (``parallel.ps_dataplane``; the host arm's
        ``compression=`` codecs are the parity oracle).
        ``metrics_every=N`` (mesh tier) accumulates per-round metrics
        in a device-resident ring fetched every N rounds, and the
        driver loop dispatches round k+1 before blocking on round k —
        history contents are identical to the per-round fetch.
        ``attrib_every=N`` (mesh tier) samples every Nth round into the
        step-time decomposition (dispatch / device-compute / ring-fetch
        / host-gap segments, ``ps_round_attrib_seconds_total``) and the
        ``mfu_observed``/``mfu_roofline`` gauge pair from the XLA cost
        ledger; 0 (default) disables sampling and trained state is
        byte-identical either way."""
        super().__init__(model, **kwargs)
        self.num_workers = int(num_workers)
        self.communication_window = int(communication_window)
        # one registry validates every fidelity and names its
        # capabilities — feature gates below read flags, not strings
        self.tier = resolve_tier(fidelity)
        self.fidelity = fidelity
        #: the ``MeshRoundDriver`` of the last ``fidelity="mesh"`` train()
        self.mesh_driver = None
        self.transport = transport
        self.checkpoint_every_rounds = checkpoint_every_rounds
        self.max_worker_failures = int(max_worker_failures)
        self.worker_retries = int(worker_retries)
        self.fault_injector = fault_injector
        self.worker_timeout = (None if worker_timeout is None
                               else float(worker_timeout))
        self.model_parallel = int(model_parallel)
        self.tp_rules = tp_rules
        if self.model_parallel < 1:
            raise ValueError(
                f"model_parallel must be >= 1, got {model_parallel}")
        if self.model_parallel > 1 and not self.tier.model_parallel:
            raise ValueError(
                f"model_parallel > 1 is unsupported on the "
                f"{fidelity!r} tier (host workers are per-thread "
                f"device programs and the mesh tier maps one worker "
                f"per device — both DP-only); tensor-parallel tiers: "
                f"{tiers_with('model_parallel')}")
        self.compression = compression
        if compression is not None:
            from distkeras_tpu.parallel.compression import resolve_codec

            resolve_codec(compression)  # fail fast on a bad spec
        if self.worker_timeout is not None and self.worker_timeout <= 0:
            raise ValueError(
                f"worker_timeout must be positive, got {worker_timeout}")
        self.ps_address = (None if ps_address is None
                           else (str(ps_address[0]),
                                 int(ps_address[1])))
        self.ps_replicas = (None if ps_replicas is None
                            else [(str(h), int(p))
                                  for h, p in ps_replicas])
        if self.ps_replicas is not None and not self.ps_replicas:
            raise ValueError(
                "ps_replicas needs at least one (host, port) address")
        if ps_address is not None and ps_replicas is not None:
            raise ValueError(
                "ps_address and ps_replicas are mutually exclusive — "
                "a one-element ps_replicas list is the unreplicated "
                "equivalent")
        self.ps_shards = int(ps_shards)
        if self.ps_shards < 1:
            raise ValueError(
                f"ps_shards must be >= 1, got {ps_shards}")
        self.ps_elastic = bool(ps_elastic)
        if self.ps_elastic:
            if self.ps_address is None:
                raise ValueError(
                    "ps_elastic attaches to an externally managed "
                    "ElasticPSGroup member; pass ps_address=(host, "
                    "port) of any group server (it seeds the shard-"
                    "map bootstrap)")
            if self.ps_shards > 1:
                raise ValueError(
                    "ps_elastic owns its shard topology server-side "
                    "(the versioned shard map); leave ps_shards=1")
            if compression is not None:
                raise ValueError(
                    "compression does not compose with ps_elastic "
                    "(the elastic wire ships raw leaf bytes so "
                    "resharding stays byte-exact)")
        self.ps_groups = None
        if ps_groups is not None:
            groups, seen_ids = [], set()
            for entry in ps_groups:
                leader_addr, members = entry
                members = [int(m) for m in members]
                if not members:
                    raise ValueError(
                        "every ps_groups entry needs at least one "
                        "worker id")
                for m in members:
                    if not 0 <= m < self.num_workers:
                        raise ValueError(
                            f"ps_groups worker id {m} out of range "
                            f"[0, {self.num_workers})")
                    if m in seen_ids:
                        raise ValueError(
                            f"worker {m} appears in two ps_groups "
                            f"entries")
                    seen_ids.add(m)
                addr = (None if leader_addr is None
                        else (str(leader_addr[0]), int(leader_addr[1])))
                groups.append((addr, members))
            if not groups:
                raise ValueError(
                    "ps_groups needs at least one (leader_addr, "
                    "[worker_ids...]) entry")
            self.ps_groups = groups
            if transport != "socket":
                raise ValueError(
                    "ps_groups runs group leaders as TCP servers "
                    "fronting their workers; it requires "
                    f"transport='socket', got {transport!r}")
            if (ps_address is not None or ps_replicas is not None
                    or self.ps_elastic):
                raise ValueError(
                    "ps_groups needs the trainer-owned root server "
                    "(its HierPSServer speaks the upstream op); it "
                    "is mutually exclusive with ps_address / "
                    "ps_replicas / ps_elastic")
        self.ps_snapshot_path = ps_snapshot_path
        self.ps_snapshot_every = int(ps_snapshot_every)
        # on-chip comm knobs (mesh tier): lowered INSIDE the compiled
        # round, unlike the host arm's `compression=` wire codecs
        self.comm_dtype = str(comm_dtype)
        self.comm_codec = comm_codec
        self.metrics_every = int(metrics_every)
        if self.metrics_every < 1:
            raise ValueError(
                f"metrics_every must be >= 1, got {metrics_every}")
        if ((self.comm_dtype != "float32"
             or self.comm_codec is not None
             or self.metrics_every != 1)
                and not self.tier.comm_compression):
            raise ValueError(
                "comm_dtype / comm_codec / metrics_every lower "
                "communication compression and the metrics ring "
                "INSIDE the compiled round; they apply only to tiers "
                "with an on-chip data plane, got "
                f"fidelity={fidelity!r}; on-chip tiers: "
                f"{tiers_with('comm_compression')} (the host arm "
                "compresses the wire via compression= instead)")
        self.attrib_every = int(attrib_every)
        if self.attrib_every < 0:
            raise ValueError(
                f"attrib_every must be >= 0 (0 disables round "
                f"attribution sampling), got {attrib_every}")
        if self.attrib_every and not self.tier.round_attrib:
            raise ValueError(
                "attrib_every samples the compiled round's step-time "
                "decomposition off the mesh driver's AOT cost ledger; "
                f"it applies only to tiers with round attribution, got "
                f"fidelity={fidelity!r}; attribution tiers: "
                f"{tiers_with('round_attrib')}")
        if not self.tier.concurrent and (self.max_worker_failures
                                         or self.worker_retries
                                         or self.worker_timeout is not None
                                         or fault_injector is not None
                                         or compression is not None
                                         or ps_address is not None
                                         or ps_replicas is not None
                                         or self.ps_shards > 1
                                         or self.ps_elastic
                                         or ps_groups is not None
                                         or ps_snapshot_path is not None
                                         or self.ps_snapshot_every):
            raise ValueError(
                "max_worker_failures / worker_retries / worker_timeout "
                "/ fault_injector / compression / ps_address / "
                "ps_replicas / ps_shards / ps_groups / ps_snapshot_* "
                "apply only to "
                "fidelity='host' (the compiled tiers are "
                "deterministic; recover via checkpoint/resume), got "
                f"fidelity={fidelity!r}; concurrent tiers: "
                f"{tiers_with('concurrent')}")
        if ps_address is not None and transport != "socket":
            raise ValueError(
                "ps_address attaches to an external PSServer over TCP; "
                f"it requires transport='socket', got {transport!r}")
        if ps_replicas is not None and transport != "socket":
            raise ValueError(
                "ps_replicas attaches to an external replica group "
                "over TCP; it requires transport='socket', got "
                f"{transport!r}")
        if self.ps_snapshot_every and ps_snapshot_path is None:
            raise ValueError(
                "ps_snapshot_every needs ps_snapshot_path to write to")
        if ps_address is not None and (ps_snapshot_path is not None
                                       or self.ps_snapshot_every):
            raise ValueError(
                "with an external ps_address, configure snapshotting "
                "on the externally created HostParameterServer, not "
                "on the trainer (the driver does not own the server)")
        if ps_replicas is not None and (ps_snapshot_path is not None
                                        or self.ps_snapshot_every):
            raise ValueError(
                "with ps_replicas, configure snapshotting on the "
                "PSReplica nodes, not on the trainer (the driver does "
                "not own the replica group)")
        self.commit_overlap = bool(commit_overlap)
        if self.commit_overlap and not self.tier.commit_overlap:
            raise ValueError(
                "commit_overlap pipelines the commit against the next "
                "window; it needs a tier with a separate commit phase "
                "(faithful's pipelined round scan, mesh's overlapped "
                "reduce-scatter, host's double-buffered worker loop) "
                "— the fast arm's closed form has none, got "
                f"fidelity={fidelity!r}; overlap-capable tiers: "
                f"{tiers_with('commit_overlap')}")
        if self.commit_overlap and (checkpoint_every_rounds
                                    or kwargs.get("checkpoint_dir")):
            raise ValueError(
                "commit_overlap runs one commit round behind — "
                "mid-training checkpoints would snapshot a center "
                "missing the pending round; train without "
                "checkpointing or without commit_overlap")
        if lr_law not in ("warn", "scale", "off"):
            raise ValueError(
                f"lr_law={lr_law!r} must be 'warn' (default: warn "
                "when the configured lr violates the measured "
                "per-family stability law), 'scale' (divide lr by "
                "the family's amplification factor), or 'off'")
        self.lr_law = lr_law
        self._apply_lr_law()

    def _lr_law(self):
        """``(amplification, scale_divisor, law)`` for this family, or
        ``None``.

        The staleness families amplify the configured lr per PS round
        (PARITY.md "per-family learning-rate scaling laws", measured
        on the calibration task): DOWNPOUR commits raw window-summed
        deltas from every worker (x workers*window), ADAG normalizes
        the window but still sums worker commits (x workers), DynSGD's
        1/(staleness+1) divides the commit depth but not the window
        sum (x window), EAMSGD's Nesterov workers amplify ~1/(1-m).
        ``amplification`` drives the warning threshold;
        ``scale_divisor`` is the MEASURED correction ``lr_law='scale'``
        applies — equal for most families, but EAMSGD's measured law
        row is lr/2, not lr(1-m) (momentum amplification is transient,
        not a steady-state divisor).  The elastic exchange itself is
        lr-neutral (AEASGD: the rho x lr sweep is flat), so the AEASGD
        base declares no law."""
        return None

    def _apply_lr_law(self) -> None:
        """The library-side guardrail for the measured footguns the
        round-3/4 parity campaign documented only in prose (PARITY.md:
        DOWNPOUR at window 4 collapses to 0.26 accuracy unless the lr
        follows the family law).  ``lr_law='warn'`` (default) warns
        when lr x amplification exceeds the measured stability scale;
        ``'scale'`` applies the measured law (divides lr), matching
        what examples/compare_trainers.py hand-codes; ``'off'``
        silences informed users."""
        law = self._lr_law()
        if law is None or self.lr_law == "off":
            return
        factor, divisor, suggestion = law
        try:
            lr = float(self.learning_rate)
        except (TypeError, ValueError):
            return  # schedules: the law is about constant-lr configs
        if self.lr_law == "scale":
            self.learning_rate = lr / divisor
            return
        effective = lr * factor
        if effective > self._LR_LAW_EFFECTIVE_MAX:
            import warnings

            warnings.warn(
                f"{type(self).__name__}: learning_rate={lr:g} is "
                f"amplified ~{factor:g}x per PS round by this "
                f"family's update law (effective {effective:g} > "
                f"{self._LR_LAW_EFFECTIVE_MAX} — the measured "
                "stability scale; PARITY.md 'per-family learning-"
                f"rate scaling laws').  Consider {suggestion}, pass "
                "lr_law='scale' to apply it automatically, or "
                "lr_law='off' if this lr is deliberate.",
                UserWarning, stacklevel=3)

    def allocate_rule(self) -> UpdateRule:
        raise NotImplementedError

    def _train(self, dataset, initial_variables, resume_from=None):
        tier = self.tier
        if not tier.checkpoint and (resume_from or self.checkpoint_dir):
            if tier.name == "host":
                raise NotImplementedError(
                    "fidelity='host' is the nondeterministic faithful "
                    "arm; checkpoint/resume of racing threads is not "
                    "supported — use the emulated fidelities")
            raise NotImplementedError(
                f"fidelity={tier.name!r} does not checkpoint its "
                f"sharded-center layout; checkpointing tiers: "
                f"{tiers_with('checkpoint')}")
        if tier.data_plane == "host-wire":
            return self._train_host(dataset, initial_variables)
        mesh_tier = tier.data_plane == "mesh"
        rule = self.allocate_rule()
        tx = self._tx()
        variables = self._init_variables(initial_variables)
        center = variables["params"]
        model_state = {k: v for k, v in variables.items()
                       if k != "params"}
        num_workers = self.num_workers
        window = self.communication_window

        pc, pid = jax.process_count(), jax.process_index()
        if pc > 1 and num_workers % pc:
            raise ValueError(
                f"multi-host needs num_workers ({num_workers}) "
                f"divisible by process count ({pc})")
        local_workers = range(pid * (num_workers // pc),
                              (pid + 1) * (num_workers // pc))

        # Per-worker states: identical start, distinct rng streams.
        # Multi-host, each process materializes only its own workers'
        # states (the key split stays global so streams are identical to
        # a single-process run).
        def make_worker(rng):
            return TrainState.create(
                {"params": center, **model_state}, tx, rng)

        worker_keys = jax.random.split(
            jax.random.key(self.seed + 1), num_workers)
        mp = self.model_parallel
        if pc > 1 and mp == 1:
            worker_keys = worker_keys[local_workers.start:
                                      local_workers.stop]
        if mp > 1:
            tp_rules_resolved = (
                self.tp_rules if self.tp_rules is not None
                else tensor_parallel.rules_for(self.spec.family))
            m_tp = mesh_lib.create_mesh(num_workers, model_parallel=mp)
            # Worker states are BORN sharded: without out_shardings the
            # [W, ...] stack (params + optimizer moments) would
            # materialize on one device before placement — an OOM for
            # exactly the models TP exists for.  (The single center
            # copy from model.init still lands on one device first —
            # the same init limitation SyncTrainer's TP path has.)
            ws_struct = jax.eval_shape(jax.vmap(make_worker),
                                       worker_keys)
            ws_sharding = tensor_parallel.stacked_tree_shardings(
                m_tp, ws_struct, tp_rules_resolved)
            worker_states = jax.jit(
                jax.vmap(make_worker),
                out_shardings=ws_sharding)(worker_keys)
        else:
            worker_states = jax.vmap(make_worker)(worker_keys)

        step = make_train_step(self.model, self.loss, tx,
                               self.features_col, self.label_col)
        overlap = self.commit_overlap
        if overlap:
            if resume_from is not None:
                raise ValueError(
                    "commit_overlap cannot resume from a checkpoint "
                    "(the pipelined round carries an uncheckpointed "
                    "pending commit)")
            if self.model_parallel > 1:
                raise ValueError(
                    "commit_overlap supports data-parallel workers "
                    "only (model_parallel=1)")
            if not mesh_tier:
                from distkeras_tpu.parallel.ps_emulator import (
                    flush_pending, make_pipelined_round_fn)

                round_fn = make_pipelined_round_fn(rule, step)
                flush_fn = functools.partial(flush_pending, rule,
                                             num_workers=num_workers)
        elif not mesh_tier:
            round_fn = make_round_fn(rule, step, self.fidelity)
        ps_state = rule.init_state(center)
        perm_key = jax.random.key(self.seed + 2)

        # Multi-host: worker states are sharded across processes, so
        # checkpoints use the per-shard orbax layout (each process
        # writes/reads only its own rows); single-process runs keep the
        # single-file msgpack path.  Sharded restore happens below,
        # after mesh placement, INTO the mesh shardings.
        from distkeras_tpu import checkpoint as ckpt_mod

        self._sharded_ckpt = pc > 1
        resume_sharded = (resume_from is not None
                          and ckpt_mod.has_sharded(resume_from))
        if pc > 1 and resume_from is not None and not resume_sharded:
            raise ValueError(
                f"multi-host resume needs a sharded checkpoint, but "
                f"{resume_from!r} holds none — single-file msgpack "
                f"checkpoints restore only in single-process runs")
        cursor: dict = {}
        if not resume_sharded:
            ckpt_state, cursor = self._maybe_resume(
                resume_from, {"ps": ps_state, "workers": worker_states,
                              "perm_key": perm_key})
            ps_state, worker_states, perm_key = (
                ckpt_state["ps"], ckpt_state["workers"],
                ckpt_state["perm_key"])

        if mp > 1:
            # tensor-parallel workers: the (workers, model) mesh built
            # at init time (no vmap fallback — TP is a layout over real
            # devices)
            placement = mesh_lib.WorkerPlacement(
                mesh=m_tp, mesh_workers=num_workers, vmap_workers=1)
        else:
            placement = mesh_lib.place_workers(num_workers)
        if pc > 1 and (placement.mesh is None
                       or placement.mesh_workers != num_workers):
            raise ValueError(
                "multi-host needs one mesh slot per worker "
                f"({num_workers} workers over "
                f"{len(jax.devices())} global devices)")
        if mesh_tier:
            if pc > 1:
                raise NotImplementedError(
                    "fidelity='mesh' is single-process for now (the "
                    "sharded-center programs assume one controller) — "
                    "use fidelity='faithful'/'fast' for multi-host")
            if placement.mesh is None or placement.vmap_workers != 1:
                raise ValueError(
                    f"fidelity='mesh' maps one worker per device over "
                    f"the {mesh_lib.WORKER_AXIS!r} mesh axis; "
                    f"num_workers={num_workers} does not fit "
                    f"{len(jax.devices())} devices — use "
                    f"fidelity='fast' for vmap-folded workers")
        if placement.mesh is not None:
            m = placement.mesh
            rep = NamedSharding(m, P())
            row = NamedSharding(m, P(mesh_lib.WORKER_AXIS))
            if mesh_tier:
                # On-chip compiled data plane: the whole round is one
                # SPMD shard_map program with the center sharded over
                # the worker axis; states move into its packed layout
                # here and stay on device (donated) between rounds.
                dp = ps_dataplane.MeshDataplane(
                    rule, step, m, center, pipelined=overlap,
                    comm_dtype=self.comm_dtype,
                    comm_codec=self.comm_codec,
                    metrics_every=self.metrics_every)
                ps_state, worker_states = dp.to_device(
                    ps_state, worker_states)
            elif mp > 1:
                # PS center sharded by the TP specs (worker states were
                # born sharded above; a msgpack resume replaced them
                # with host arrays, which round_jit's in_shardings
                # place)
                ps_sharding = tensor_parallel.tree_shardings(
                    m, ps_state, tp_rules_resolved)
                ps_state = mesh_lib.global_batch_from_local(
                    ps_sharding, ps_state)
            else:
                ps_sharding, ws_sharding = rep, row
                # Each process contributes its own workers' states (and
                # the full replica of the PS state) to the global
                # arrays.
                worker_states = mesh_lib.global_batch_from_local(
                    ws_sharding, worker_states)
                ps_state = mesh_lib.global_batch_from_local(
                    ps_sharding, ps_state)
            if resume_sharded:
                # the sharded layout carries the device state; the
                # (host-local, process-identical) permutation key rides
                # in the cursor as raw key data
                restored, cursor = ckpt_mod.load_sharded(
                    resume_from,
                    {"ps": ps_state, "workers": worker_states})
                ps_state, worker_states = (restored["ps"],
                                           restored["workers"])
                cursor = self._restore_history(cursor)
                perm_key = jax.random.wrap_key_data(jnp.asarray(
                    np.asarray(cursor.pop("perm_key_data"),
                               np.uint32)))
            if mesh_tier:
                # async host dispatch: the driver owns the dataplane
                # state (and the pipelined pending), enqueues round
                # k+1 before fetching round k's metrics, and drains
                # the device-resident ring every metrics_every rounds
                driver = ps_dataplane.MeshRoundDriver(
                    dp, ps_state, worker_states,
                    attrib_every=self.attrib_every)
                # kept after train(): the sharded center/worker state
                # (``.mps``/``.mws``) and the dataplane's cost ledger
                # and compiled rounds (``.dp``) are what a caller
                # inspects to see where the work actually ran
                self.mesh_driver = driver
            elif overlap:
                round_jit = jax.jit(
                    round_fn,
                    in_shardings=(ps_sharding, ws_sharding, row, rep,
                                  row, rep, rep),
                    out_shardings=(ps_sharding, ws_sharding, rep, row,
                                   rep, rep))
                flush_jit = jax.jit(
                    flush_fn,
                    in_shardings=(ps_sharding, row, rep),
                    out_shardings=ps_sharding)
            else:
                round_jit = jax.jit(
                    round_fn,
                    in_shardings=(ps_sharding, ws_sharding, row, rep),
                    out_shardings=(ps_sharding, ws_sharding, rep))
            # worker-0 row of the model state (batch stats etc.),
            # sliced on device; jitted ONCE so epoch-boundary eval and
            # the end-of-train extraction share the compiled program
            slice_row0 = jax.jit(
                lambda t: jax.tree_util.tree_map(lambda x: x[0], t),
                out_shardings=rep)
        else:
            if resume_sharded:
                raise ValueError(
                    f"{resume_from!r} holds a sharded checkpoint but "
                    f"this run has no mesh to restore it onto")
            round_jit = jax.jit(round_fn)
            if overlap:
                flush_jit = jax.jit(flush_fn)
            slice_row0 = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda x: x[0], t)

        start_epoch = int(cursor.get("epoch", 0))
        start_round = int(cursor.get("round", 0))
        rows_per_worker_batch = self.batch_size
        cols = self._columns()

        if overlap and not mesh_tier:
            # the pipelined round's carried pending commit: a zero
            # delta (inert for the delta family) until the first round
            # marks it valid; pend_live mirrors validity host-side so
            # the epoch-end flush doesn't fetch a device bool
            # (the mesh tier's pending lives inside MeshRoundDriver)
            pend_payloads = jax.tree_util.tree_map(
                jnp.zeros_like, worker_states.params)
            if placement.mesh is not None:
                pend_perm = mesh_lib.global_batch_from_local(
                    rep, np.arange(num_workers, dtype=np.int32))
                pend_valid = mesh_lib.global_batch_from_local(
                    rep, np.asarray(False))
                _false = pend_valid
            else:
                pend_perm = jnp.arange(num_workers, dtype=jnp.int32)
                pend_valid = jnp.asarray(False)
                _false = pend_valid
            pend_live = False

        def save_point(point: dict):
            # reads the loop's current ps/worker/key state at call time
            if self._sharded_ckpt:
                self._maybe_save(
                    {"ps": ps_state, "workers": worker_states},
                    {**point, "perm_key_data": np.asarray(
                        jax.random.key_data(perm_key)).tolist()})
            else:
                self._maybe_save(
                    {"ps": ps_state, "workers": worker_states,
                     "perm_key": perm_key}, point)

        for epoch in range(start_epoch, self.num_epoch):
            t_epoch = telemetry.now()
            resuming_mid_epoch = epoch == start_epoch and start_round > 0
            if resuming_mid_epoch:
                # this epoch's pre-kill rounds live in the restored
                # history: seed epoch_losses with them (so epoch_loss
                # matches the uninterrupted run) and don't re-record
                # dropped_tail_batches for the same epoch
                epoch_losses = list(
                    self.history.get("round_loss", [])[-start_round:])
            else:
                epoch_losses = []
            first_round = start_round if epoch == start_epoch else 0

            # Metrics are fetched one round LATE: round r's device
            # metrics are pulled to host while round r+1 is already
            # queued, so the host-side batch assembly for the next round
            # overlaps device compute instead of blocking on a sync
            # every round (round-1 Weak #9; values and record order are
            # identical to the eager fetch).
            pending = None  # (device metrics of the previous round)

            def drain(metrics_dev):
                round_loss = float(
                    np.mean(mesh_lib.fetch(metrics_dev["loss"])))
                epoch_losses.append(round_loss)
                self._record(
                    round_loss=round_loss,
                    staleness=mesh_lib.fetch(
                        metrics_dev["staleness"]).tolist())

            def sync_metrics():
                # record everything outstanding, in round order: the
                # mesh driver's ring (full + partial cycles) or the
                # emulated tiers' one-round-late pending fetch
                nonlocal pending
                if mesh_tier:
                    for fetched in driver.drain():
                        drain(fetched)
                elif pending is not None:
                    drain(pending)
                    pending = None

            # Rounds are numbered globally across segments (one segment
            # for in-memory datasets — identical behavior; one per
            # shard file for ShardedDataset) so the checkpoint cursor's
            # "round" stays meaningful out-of-core.
            round_base = 0
            # a mid-epoch save due exactly at a segment boundary is
            # deferred until the next segment proves the epoch goes on
            # (the epoch-end save supersedes it otherwise) — keeps the
            # in-memory path save-for-save identical while still
            # honoring checkpoint_every_rounds across segments
            due_save = None
            def predicted_rounds(rows: int) -> int:
                # mirrors repartition + _stack_batches + // window
                # exactly, from row counts alone
                if rows < num_workers:
                    return 0
                return ((rows // num_workers)
                        // rows_per_worker_batch) // window

            plan = list(_epoch_segment_loaders(
                dataset, self.seed + 17 * epoch))
            prefetch = _SegmentPrefetch()
            seg_stall = 0.0

            def next_loadable(j: int, rb: int) -> int | None:
                # metadata-only replay of this loop's own skip rules,
                # to find which segment after j will actually load —
                # a wrong answer only costs the overlap (get() falls
                # back to a synchronous load on key mismatch)
                rb += predicted_rounds(plan[j][0])
                for k in range(j + 1, len(plan)):
                    hint = predicted_rounds(plan[k][0])
                    if rb + hint <= first_round and hint > 0:
                        rb += hint
                        continue
                    if plan[k][0] < num_workers:
                        continue
                    return k
                return None

            for seg_j, (seg_rows, load_segment) in enumerate(plan):
                sr_hint = predicted_rounds(seg_rows)
                if round_base + sr_hint <= first_round and sr_hint > 0:
                    # resume fast-path: every round of this segment
                    # predates the resume point — skip the file read
                    # entirely (records suppressed below anyway)
                    round_base += sr_hint
                    continue
                # records are suppressed for segments already processed
                # before a mid-epoch kill (their records live in the
                # restored history): a segment was entered pre-kill iff
                # its first round predates the resume round
                record_this_segment = round_base >= first_round
                if seg_rows < num_workers:
                    # too few rows to give every worker one: the whole
                    # segment is dropped — never silently, and without
                    # reading the file (row count is header metadata)
                    if record_this_segment:
                        self._record(skipped_segment_rows=seg_rows)
                    continue
                t_get = telemetry.now()
                segment = prefetch.get(seg_j, load_segment)
                seg_stall += telemetry.now() - t_get
                if _prefetch_depth() > 0:
                    nxt = next_loadable(seg_j, round_base)
                    if nxt is not None:
                        prefetch.queue(nxt, plan[nxt][1])
                shards = segment.repartition(num_workers)
                # Multi-host: stack only this process's workers' shards
                # (segment order is seed-deterministic, so every process
                # sees the same global rows and takes a disjoint slice).
                per_worker = [
                    _stack_batches(shards[i], rows_per_worker_batch,
                                   cols)
                    for i in local_workers]
                if any(p is None for p in per_worker):
                    if record_this_segment:
                        self._record(skipped_segment_rows=seg_rows)
                    continue  # segment smaller than one batch/worker
                n_batches = min(len(next(iter(p.values())))
                                for p in per_worker)
                seg_rounds = n_batches // window
                if record_this_segment:
                    # Tail batches that don't fill a whole window are
                    # dropped (the reference's per-partition loop had
                    # the same remainder behavior); record the count so
                    # it is never silent.
                    self._record(
                        dropped_tail_batches=(n_batches
                                              - seg_rounds * window))
                if due_save is not None and seg_rounds > 0:
                    sync_metrics()
                    save_point({"epoch": epoch, "round": due_save})
                    due_save = None
                for r_local in range(seg_rounds):
                    r = round_base + r_local
                    if r < first_round:
                        continue  # resume: rounds already in the ckpt
                    t_round = telemetry.now()
                    perm_key, sub = jax.random.split(perm_key)
                    perm = jax.random.permutation(sub, num_workers)
                    # [W, window, B, ...] device batch for this round;
                    # note the whole segment is already stacked per
                    # worker on the host (per_worker above) — host peak
                    # is one segment, the device sees one round at a
                    # time.
                    batch = {
                        k: np.stack(
                            [p[k][r_local * window:
                                  (r_local + 1) * window]
                             for p in per_worker])
                        for k in cols}
                    if placement.mesh is not None:
                        batch = mesh_lib.global_batch_from_local(row,
                                                                 batch)
                        perm = mesh_lib.global_batch_from_local(
                            rep, np.asarray(perm))
                    else:
                        batch = {k: jnp.asarray(v)
                                 for k, v in batch.items()}
                    if mesh_tier:
                        # dispatch round k+1 before blocking on k:
                        # poll() only surfaces rings fetched AFTER a
                        # newer round was already in flight
                        driver.dispatch(batch, perm)
                        for fetched in driver.poll():
                            drain(fetched)
                    elif overlap:
                        (ps_state, worker_states, metrics,
                         pend_payloads, pend_perm, pend_valid) = \
                            round_jit(ps_state, worker_states, batch,
                                      perm, pend_payloads, pend_perm,
                                      pend_valid)
                        pend_live = True
                    else:
                        ps_state, worker_states, metrics = round_jit(
                            ps_state, worker_states, batch, perm)
                    if not mesh_tier:
                        if pending is not None:
                            drain(pending)
                        pending = metrics
                    # host-side round span (dispatch + previous-round
                    # drain; device time lives in profiler traces)
                    telemetry.complete("ps_round", t_round,
                                       epoch=epoch, round=r)
                    every = self.checkpoint_every_rounds
                    if every and (r + 1) % every == 0:
                        if r_local + 1 < seg_rounds:
                            sync_metrics()
                            save_point({"epoch": epoch,
                                        "round": r + 1})
                        else:
                            # due exactly at the segment boundary:
                            # defer — flushed when the next segment
                            # proves the epoch continues, superseded
                            # by the epoch-end save otherwise
                            due_save = r + 1
                round_base += seg_rounds
            if round_base == 0:
                raise ValueError(
                    f"not enough batches per worker for one "
                    f"communication window ({window}) in any segment")
            sync_metrics()
            if mesh_tier:
                if overlap:
                    # the pipeline always runs one commit behind: fold
                    # the final pending round in so epoch-boundary eval
                    # (and the returned model) see every commit
                    driver.flush_pipeline()
                ps_state, worker_states = driver.mps, driver.mws
            elif overlap and pend_live:
                # same flush for the emulated pipelined tiers
                ps_state = flush_jit(ps_state, pend_payloads,
                                     pend_perm)
                pend_valid = _false
                pend_live = False
            self._record(epoch_loss=float(np.mean(epoch_losses)),
                         segment_stall_s=round(seg_stall, 4))
            if getattr(self, "_eval_dataset", None) is not None:
                self._eval_epoch({
                    "params": (dp.center(ps_state) if mesh_tier
                               else ps_state.center),
                    **slice_row0(worker_states.model_state)})
            save_point({"epoch": epoch + 1, "round": 0})
            telemetry.complete("epoch", t_epoch, epoch=epoch,
                               trainer=type(self).__name__)

        # Keep worker 0's model state (batch stats etc.): slice on device
        # (replicated output) so only one row ever crosses to host.
        final_model_state = jax.tree_util.tree_map(
            mesh_lib.fetch, slice_row0(worker_states.model_state))
        # Mesh tier: unpack the sharded-center layout back into the
        # public PSState shape callers (and save()) expect.
        ps_export = (dp.export_ps_state(ps_state) if mesh_tier
                     else ps_state)
        self.trained_variables = {"params": ps_export.center,
                                  **final_model_state}
        self.parameter_server_state = jax.device_get(ps_export)
        return self.trained_variables


    def _train_host(self, dataset, initial_variables):
        """Design 5a (SURVEY.md §7): free-running worker threads against
        a concurrent host-side parameter server.  Real races, emergent
        staleness — the faithful arm the on-mesh emulator's deterministic
        staleness is validated against.  See ``parallel.host_ps``.

        Multi-host (``transport='socket'`` required): process 0 hosts
        the PS, every process runs its slice of the worker ids, and the
        reference's star topology spans hosts over real TCP — the DCN
        arm.  The PS address travels by collective broadcast; the final
        center, staleness log, and epoch telemetry are broadcast/
        reduced so every process returns identical results."""
        from distkeras_tpu.parallel.compression import (raw_nbytes,
                                                        resolve_codec)
        from distkeras_tpu.parallel.host_ps import (
            HostParameterServer, PSClient, PSRetryExhausted, PSServer,
            ResilientPSClient, fetch_epoch)
        from distkeras_tpu.utils import (tree_add, tree_sub,
                                         tree_zeros_like)

        rule = self.allocate_rule()
        codec = resolve_codec(self.compression)
        if codec is not None and rule.payload_kind != "delta":
            raise ValueError(
                "compression applies only to the delta-family rules "
                "(DOWNPOUR/ADAG/DynSGD): their additive payloads are "
                "error-feedback-correctable; the elastic family "
                "commits absolute parameters")
        if self.commit_overlap and rule.payload_kind != "delta":
            raise ValueError(
                "commit_overlap on the host arm supports the delta "
                "family only (the elastic exchange folds the pulled "
                "center back into the worker's CURRENT locals — "
                "nothing to overlap)")
        tx = self._tx()
        variables = self._init_variables(initial_variables)
        center = variables["params"]
        model_state = {k: v for k, v in variables.items()
                       if k != "params"}
        num_workers = self.num_workers
        window = self.communication_window

        if self.transport not in ("inprocess", "socket"):
            raise ValueError(
                f"unknown transport {self.transport!r}; "
                "expected 'inprocess' or 'socket'")
        pc = jax.process_count()
        rank = jax.process_index()
        multi = pc > 1
        if multi:
            from jax.experimental import multihost_utils
            if self.transport != "socket":
                raise ValueError(
                    "multi-host fidelity='host' needs "
                    "transport='socket' (the PS lives on process 0)")
            if num_workers % pc:
                raise ValueError(
                    f"multi-host needs num_workers ({num_workers}) "
                    f"divisible by the process count ({pc})")
            if self.ps_address is not None:
                raise ValueError(
                    "external ps_address does not compose with "
                    "multi-host runs (process 0 hosts the PS there)")
            if self.ps_replicas is not None:
                raise ValueError(
                    "ps_replicas does not compose with multi-host "
                    "runs (process 0 hosts the PS there)")
            if self.ps_groups is not None:
                raise ValueError(
                    "ps_groups does not compose with multi-host runs "
                    "(group leaders run as threads of the single "
                    "driver process)")

        shard_plan = None
        if self.ps_shards > 1:
            from distkeras_tpu.parallel.sharded_ps import plan_shards

            # the one plan every endpoint derives: byte-balanced leaf
            # partition, a pure function of (template, K)
            shard_plan = plan_shards(
                jax.tree_util.tree_map(np.asarray, center),
                self.ps_shards)

        ps = None
        server = None
        if (self.ps_address is None and self.ps_replicas is None
                and (not multi or rank == 0)):
            if self.ps_shards > 1:
                from distkeras_tpu.parallel.sharded_ps import (
                    ShardedParameterServer)

                ps = ShardedParameterServer(
                    rule, center, self.ps_shards,
                    snapshot_path=self.ps_snapshot_path,
                    snapshot_every=self.ps_snapshot_every)
            else:
                ps = HostParameterServer(
                    rule, center, snapshot_path=self.ps_snapshot_path,
                    snapshot_every=self.ps_snapshot_every)
            if self.transport == "socket":
                server_cls = PSServer
                if self.ps_groups is not None:
                    # root must understand the leaders' upstream op
                    from distkeras_tpu.parallel.hier_ps import (
                        HierPSServer)

                    server_cls = HierPSServer
                server = server_cls(
                    ps, center,
                    host="0.0.0.0" if multi else "127.0.0.1").start()
        if multi:
            # ship process 0's "host:port" to everyone (fixed-width
            # byte buffer: broadcast needs one shape on all processes)
            wire = np.zeros(64, np.uint8)
            if rank == 0:
                import os as _os

                from distkeras_tpu.parallel import transport as _tp

                ps_host = (_os.environ.get("DKT_PS_HOST")
                           or _tp.determine_host_address())
                if ps_host.startswith("127."):
                    # correct for single-machine multi-process (the
                    # local[N] analogue); a real pod must override
                    print("[distkeras_tpu] PS address resolved to "
                          f"loopback ({ps_host}) — fine for processes "
                          "on one machine; set DKT_PS_HOST to a "
                          "routable address for true multi-host",
                          flush=True)
                addr = f"{ps_host}:{server.address[1]}".encode()
                wire[:len(addr)] = np.frombuffer(addr, np.uint8)
            wire = np.asarray(
                multihost_utils.broadcast_one_to_all(wire))
            host_s, _, port_s = bytes(
                wire).rstrip(b"\0").decode().rpartition(":")
            ps_address = (host_s, int(port_s))
        elif self.ps_address is not None:
            ps_address = self.ps_address  # externally managed PSServer
        else:
            ps_address = server.address if server is not None else None

        # Hierarchical aggregation (parallel.hier_ps): one in-process
        # GroupLeader per ps_groups entry fronts its workers and folds
        # their windows into single upstream commits against the root.
        leaders: list = []
        group_of: dict[int, int] = {}
        if self.ps_groups is not None:
            from distkeras_tpu.parallel.hier_ps import (
                GroupLeader, resilient_hier_client)

            if rule.payload_kind != "delta":
                raise ValueError(
                    "ps_groups supports the delta-family rules only "
                    "(DOWNPOUR/ADAG/DynSGD): leaders fold additive "
                    "payloads; the elastic exchange has no "
                    "closed-form combination")
            for gi, (addr, members) in enumerate(self.ps_groups):
                leader = GroupLeader(
                    rule, center, ps_address, group_id=gi,
                    aggregate_window=len(members),
                    host=addr[0] if addr is not None else "127.0.0.1",
                    port=addr[1] if addr is not None else 0)
                leader.start()
                leaders.append(leader)
                for m in members:
                    group_of[m] = gi

        step = make_train_step(self.model, self.loss, tx,
                               self.features_col, self.label_col)
        run_window = jax.jit(make_window_runner(step))
        worker_keys = jax.random.split(
            jax.random.key(self.seed + 1), num_workers)
        cols = self._columns()
        # Thread-shared accumulators are telemetry primitives (ISSUE 2:
        # the hand-rolled history_lock is gone) — Series/Counter carry
        # their own locks, so worker threads append race-free and the
        # post-join code snapshots once.
        round_records = telemetry.Series()  # (worker, epoch, loss)
        retry_records = telemetry.Series()  # (worker, epoch, round)
        failures = telemetry.Series()       # (worker, exception)
        wire_total = telemetry.Counter()    # codec-arm commit bytes
        raw_total = telemetry.Counter()
        skip_total = telemetry.Counter()    # version-delta pull savings
        saved_total = telemetry.Counter()   # (sharded socket arm)
        failover_total = telemetry.Counter()  # ps_replicas client arm
        leader_failover_total = telemetry.Counter()  # ps_groups arm

        # Threads free-run through epochs, so the per-epoch shuffle +
        # repartition is memoized under a lock: the first worker to
        # reach epoch e builds the shards once (not one full-dataset
        # copy per thread); entries are dropped after the last worker
        # fetches them.
        # RLock: segment_shard -> epoch_plan nests the acquisition
        shard_lock = racecheck.rlock("trainers.shard")
        # keyed (epoch, segment slot): one segment for in-memory
        # datasets (the whole shuffled set), one per shard file for
        # ShardedDataset — the host arm streams out-of-core data the
        # same way the emulated arms do, with peak memory bounded by
        # the segments concurrently in flight across threads
        # entry: (shards | None | BaseException, fetched, event, ready)
        shard_cache: dict[tuple[int, int], tuple] = {}
        plan_cache: dict[int, list] = {}
        per_proc = num_workers // pc
        local_workers = (range(rank * per_proc, (rank + 1) * per_proc)
                         if multi else range(num_workers))
        # workers this process will never run (multi-host slices) count
        # as "never fetching" for the shard-cache sweep, or every
        # epoch's repartition would stay pinned in memory
        dead_workers: set[int] = (set(range(num_workers))
                                  - set(local_workers))
        dropped_per_epoch = [0] * self.num_epoch
        skipped_rows_per_epoch = [0] * self.num_epoch
        accum_lock = racecheck.lock("trainers.accum")  # the two index+= arrays above

        def _sweep_shard_cache():
            # caller holds shard_lock: drop READY entries every live
            # worker has fetched (dead workers never will — without
            # this, each dead worker would pin one segment per slot)
            for e in [e for e, (_, fetched, _, ready)
                      in shard_cache.items()
                      if ready and fetched | dead_workers
                      >= set(range(num_workers))]:
                del shard_cache[e]

        def epoch_plan(epoch: int) -> list:
            # (rows, load) pairs, deterministic in the epoch seed —
            # every worker walks the same segment order
            with shard_lock:
                if epoch not in plan_cache:
                    plan_cache[epoch] = list(_epoch_segment_loaders(
                        dataset, self.seed + 17 * epoch))
                return plan_cache[epoch]

        def build_segment(key: tuple[int, int],
                          event: threading.Event):
            """Load/shuffle/repartition segment ``key`` and publish it.
            Build failures poison the entry before the event fires:
            waiting workers re-raise instead of blocking forever on an
            event nobody will set."""
            epoch, slot = key
            shards: object = None
            try:
                rows, load = epoch_plan(epoch)[slot]
                shards = (load().repartition(num_workers)
                          if rows >= num_workers else None)
            except BaseException as exc:
                shards = exc
                raise
            finally:
                with shard_lock:
                    shard_cache[key] = (shards, set(), event, True)
                event.set()

        def prefetch_segment(epoch: int, slot: int):
            """Background one-ahead build: claim the entry if nobody
            has, then build it through the same publish/poison path a
            requesting worker would use."""
            key = (epoch, slot)
            with shard_lock:
                if key in shard_cache:
                    return
                event = threading.Event()
                shard_cache[key] = (None, set(), event, False)
            try:
                build_segment(key, event)
            except BaseException:
                pass  # poisoned entry re-raises in every requester

        def segment_shard(epoch: int, slot: int, w: int):
            """Worker ``w``'s slice of segment ``slot``; None when the
            segment cannot give every worker a row.  The segment is
            built (loaded / shuffled / repartitioned) OUTSIDE the lock
            by the first requester — other workers wait on its event,
            and requesters of cached or different segments never block
            behind the IO.  A successful build kicks a one-ahead
            background build of the next slot so segment IO overlaps
            the epoch's compute."""
            key = (epoch, slot)
            while True:
                build = False
                with shard_lock:
                    entry = shard_cache.get(key)
                    if entry is None:
                        event = threading.Event()
                        shard_cache[key] = (None, set(), event, False)
                        build = True
                    else:
                        shards, fetched, event, ready = entry
                        if ready:
                            fetched.add(w)
                            _sweep_shard_cache()
                            if isinstance(shards, BaseException):
                                raise RuntimeError(
                                    f"segment (epoch {epoch}, slot "
                                    f"{slot}) failed to build in "
                                    "another worker") from shards
                            return (None if shards is None
                                    else shards[w])
                if build:
                    build_segment(key, event)
                    nxt = slot + 1
                    if (_prefetch_depth() > 0
                            and nxt < len(epoch_plan(epoch))):
                        threading.Thread(
                            target=prefetch_segment, args=(epoch, nxt),
                            daemon=True,
                            name="dkt-segment-prefetch").start()
                else:
                    event.wait()

        def note_death(w: int):
            with shard_lock:
                dead_workers.add(w)
                _sweep_shard_cache()

        def worker_loop(w: int):
            # (epoch, round) the retry callback stamps; -1 = startup.
            # Network-level failures (connect/pull/commit) are retried
            # INSIDE ResilientPSClient — backoff + jitter + at-most-once
            # commit seqs; this loop keeps only the COMPUTE-level
            # budget (fault_injector, a poisoned window).
            round_ctx = [-1, -1]

            def on_retry(attempt, exc):
                retry_records.append((w, round_ctx[0], round_ctx[1]))
                telemetry.instant("worker_retry", worker=w,
                                  epoch=round_ctx[0],
                                  round=round_ctx[1])

            retry_kw = dict(retries=self.worker_retries,
                            seed=self.seed + 101 * w,
                            on_retry=on_retry)
            socket_arm = (ps_address is not None
                          or self.ps_replicas is not None)
            sharded_socket = socket_arm and (self.ps_shards > 1
                                             or self.ps_elastic)
            # per-worker, so client instances (rebuilt per reconnect)
            # accumulate race-free; folded into the shared counters
            # in the finally below
            shard_stats = ({"pull_shards_skipped": 0,
                            "pull_bytes_saved": 0}
                           if sharded_socket else None)
            gi = group_of.get(w)
            if gi is not None:
                # grouped worker: leader first, root on leader death
                client = resilient_hier_client(
                    leaders[gi].address, ps_address, worker_id=w,
                    template=center, codec=codec, **retry_kw)
            elif self.ps_elastic:
                client = ResilientPSClient.for_elastic(
                    [ps_address], worker_id=w, template=center,
                    stats=shard_stats, **retry_kw)
            elif self.ps_replicas is not None:
                client = ResilientPSClient.for_replicas(
                    self.ps_replicas, worker_id=w, template=center,
                    codec=codec, shards=self.ps_shards,
                    shard_stats=shard_stats, **retry_kw)
            elif socket_arm:
                client = ResilientPSClient.for_address(
                    *ps_address, worker_id=w, template=center,
                    codec=codec, shards=self.ps_shards,
                    shard_stats=shard_stats, **retry_kw)
            else:
                client = ResilientPSClient.for_server(ps, w,
                                                      **retry_kw)
            overlap = self.commit_overlap
            exchange = None
            pending: list = [None]
            if overlap:
                from concurrent.futures import ThreadPoolExecutor

                # one-deep double buffer: the exchange for window n
                # runs here while the device computes window n+1 (the
                # worker trains one exchange behind — +1 staleness)
                exchange = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"dkt-ps-exchange-{w}")

            def drain_exchange():
                """Join the in-flight exchange (if any) and adopt its
                pulled center; every synchronous client op must be
                preceded by this (one connection, one op at a time).
                Raises what the exchange raised (PSRetryExhausted
                included)."""
                fut, pending[0] = pending[0], None
                return fut.result() if fut is not None else None

            wire_bytes = raw_bytes = 0
            try:
                state = TrainState.create(
                    {"params": center, **model_state}, tx,
                    worker_keys[w])
                residual = (tree_zeros_like(center)
                            if codec is not None else None)
                # startup contact rides the same budget as any later
                # op (the client builds its connection lazily inside
                # the retry loop)
                pulled = client.pull()
                for epoch in range(self.num_epoch):
                    epoch_rounds = 0  # global round id across segments
                    for slot in range(len(epoch_plan(epoch))):
                        shard = segment_shard(epoch, slot, w)
                        stacked = (None if shard is None else
                                   _stack_batches(shard,
                                                  self.batch_size,
                                                  cols))
                        if stacked is None:
                            # segment too small for this worker's
                            # batch: its rows never train — recorded,
                            # never silent (this worker's nominal
                            # slice; summed over workers ~= the
                            # segment)
                            rows = epoch_plan(epoch)[slot][0]
                            with accum_lock:
                                skipped_rows_per_epoch[epoch] += (
                                    len(shard) if shard is not None
                                    else rows // num_workers)
                            continue
                        n_batches = len(next(iter(stacked.values())))
                        seg_rounds = n_batches // window
                        with accum_lock:
                            dropped_per_epoch[epoch] += (
                                n_batches - seg_rounds * window)
                        for r_local in range(seg_rounds):
                            r = epoch_rounds
                            epoch_rounds += 1
                            t_round = telemetry.now()
                            batches = {
                                k: jnp.asarray(
                                    v[r_local * window:
                                      (r_local + 1) * window])
                                for k, v in stacked.items()}
                            round_ctx[0], round_ctx[1] = epoch, r
                            attempts = 0  # compute-level retry budget
                            base_state = state  # pre-round snapshot: a
                            # retried window must not see optimizer
                            # moments / rng / step already advanced by the
                            # aborted attempt
                            while True:
                                try:
                                    if self.fault_injector is not None:
                                        self.fault_injector(w, epoch, r)
                                    start_params = (
                                        jax.tree_util.tree_map(
                                            jnp.asarray, pulled))
                                    state = base_state.replace(
                                        params=start_params)
                                    state, metrics = run_window(
                                        state, batches)
                                    if rule.payload_kind == "params":
                                        payload = local = state.params
                                    else:
                                        payload = rule.normalize_delta(
                                            tree_sub(state.params,
                                                     start_params),
                                            window)
                                        local = None
                                    if codec is not None:
                                        # Error feedback: fold the
                                        # residual under-transmitted so
                                        # far into this window's delta.
                                        # The client retries internally
                                        # with these IDENTICAL bytes
                                        # under ONE commit seq, so a
                                        # lost-ack retry dedupes
                                        # server-side and the residual
                                        # always matches what the
                                        # server absorbed.
                                        total = tree_add(payload,
                                                         residual)
                                        if sharded_socket:
                                            encoded, applied = (
                                                codec.round_trip_shards(
                                                    total, shard_plan))
                                            enc_len = sum(
                                                len(d) for d in encoded)
                                        else:
                                            encoded, applied = (
                                                codec.round_trip(total))
                                            enc_len = len(encoded)
                                        commit_args = (
                                            encoded if socket_arm
                                            else applied, None)
                                        residual = tree_sub(total,
                                                            applied)
                                        wire_bytes += enc_len
                                        raw_bytes += raw_nbytes(
                                            payload)
                                    else:
                                        commit_args = (
                                            payload,
                                            local
                                            if rule.pull_uses_local
                                            else None)
                                    if overlap:
                                        # adopt exchange n-1's center
                                        # (it ran under window n's
                                        # compute), hand exchange n to
                                        # the background thread
                                        got = drain_exchange()
                                        if got is not None:
                                            pulled = got
                                        pending[0] = exchange.submit(
                                            client.commit,
                                            *commit_args)
                                    else:
                                        pulled = client.commit(
                                            *commit_args)
                                    break
                                except PSRetryExhausted:
                                    # the network budget died inside
                                    # the client; recomputing the
                                    # window cannot revive the link
                                    raise
                                except Exception:
                                    # Compute-level failure (chaos
                                    # hook, poisoned window): re-pull
                                    # and re-run on this loop's own
                                    # budget.  At-most-once holds: an
                                    # uncommitted window's delta never
                                    # reached the PS.  (Exception, not
                                    # BaseException: KeyboardInterrupt
                                    # / MemoryError should not be
                                    # retried.)
                                    attempts += 1
                                    if attempts > self.worker_retries:
                                        raise
                                    retry_records.append((w, epoch, r))
                                    telemetry.instant("worker_retry",
                                                      worker=w,
                                                      epoch=epoch,
                                                      round=r)
                                    if overlap:
                                        # serialize with the in-flight
                                        # exchange before re-pulling
                                        # (its PSRetryExhausted, if
                                        # any, kills the worker here)
                                        drain_exchange()
                                    pulled = client.pull()
                            round_records.append(
                                (w, epoch,
                                 float(np.mean(
                                     np.asarray(metrics["loss"])))))
                            # one span per worker round on this
                            # worker thread's track — the acceptance
                            # timeline next to ps_commit spans
                            telemetry.complete("worker_round",
                                               t_round, worker=w,
                                               epoch=epoch, round=r)
                    if epoch_rounds == 0:
                        raise ValueError(
                            f"worker {w}: not enough batches per "
                            f"worker for one communication window "
                            f"({window}) in any segment")
                if overlap:
                    # the last window's exchange is still in flight;
                    # its center must land before the clean finish
                    drain_exchange()
                client.done()
                client.close()
            except BaseException as e:  # handled by the join below
                note_death(w)
                failures.append((w, e))
            finally:
                if exchange is not None:
                    exchange.shutdown(wait=False)
                # telemetry flush runs even for workers that die
                # mid-run — their applied commits' traffic was real
                if codec is not None:
                    wire_total.inc(wire_bytes)
                    raw_total.inc(raw_bytes)
                    m = telemetry.metrics()
                    m.counter("commit_wire_bytes_total").inc(wire_bytes)
                    m.counter("commit_raw_bytes_total").inc(raw_bytes)
                if shard_stats is not None:
                    skip_total.inc(shard_stats["pull_shards_skipped"])
                    saved_total.inc(shard_stats["pull_bytes_saved"])
                if self.ps_replicas is not None:
                    # the cycler survives reconnects, so its count is
                    # this worker's whole-run failover total
                    failover_total.inc(client.replicas.failovers)
                if group_of.get(w) is not None:
                    leader_failover_total.inc(
                        client.replicas.failovers)

        threads = [threading.Thread(target=worker_loop, args=(w,))
                   for w in local_workers]
        for t in threads:
            t.start()
        # Active failure detection (SURVEY.md §5): while workers run, a
        # watchdog samples the PS contact heartbeat and records any
        # worker silent beyond worker_timeout — the monitoring signal an
        # operator would page on; the join + elastic machinery below is
        # the corresponding action.
        detected: list[list[int]] = []
        watcher = None
        stop_watch = threading.Event()
        if self.worker_timeout is not None and ps is not None:
            for w in range(num_workers):
                # monitor from t=0: a worker hanging before its first
                # PS contact must be flagged, not invisible; grouped
                # workers heartbeat at their leader, not the root
                gi = group_of.get(w)
                (leaders[gi] if gi is not None else ps).register(w)

            def watchdog():
                while not stop_watch.wait(self.worker_timeout / 4):
                    seen = set(ps.idle_workers(self.worker_timeout))
                    for lead in leaders:
                        seen.update(
                            lead.idle_workers(self.worker_timeout))
                    # leader ids live in their own space above the
                    # worker range; only workers are paged on
                    idle = sorted(i for i in seen if i < num_workers)
                    if idle and (not detected or detected[-1] != idle):
                        detected.append(idle)
                        # timeline marker on the watchdog's own track
                        telemetry.instant("idle_workers", workers=idle)

            watcher = threading.Thread(target=watchdog, daemon=True)
            watcher.start()
        try:
            for t in threads:
                t.join()
            if multi:
                # the PS (and its watchdog — remote workers may still
                # be running and must stay monitored) must outlive
                # every process's workers
                multihost_utils.sync_global_devices(
                    "dkt-host-ps-drained")
        finally:
            # always reap the watchdog — a KeyboardInterrupt in join()
            # must not leak a thread polling the PS forever
            stop_watch.set()
            if watcher is not None:
                watcher.join()
        if detected:
            self._record(detected_idle_workers=detected)
        for lead in leaders:
            # drain flushes any partial window upstream so the root
            # center (the deliverable) holds every acked commit
            lead.drain()
            lead.stop()
        if server is not None:
            server.stop()
        # threads are joined: snapshot the shared accumulators once
        failures = failures.values()
        retry_records = retry_records.values()
        round_records = round_records.values()
        total_failures = len(failures)
        if multi:
            total_failures = int(multihost_utils.process_allgather(
                np.asarray([len(failures)])).sum())
        if total_failures and (total_failures > self.max_worker_failures
                               or total_failures == num_workers):
            if failures:
                raise failures[0][1]
            raise RuntimeError(
                f"{total_failures} worker(s) failed on other "
                f"processes (> max_worker_failures="
                f"{self.max_worker_failures})")
        if failures:
            # Elastic continuation: the dead workers' committed rounds
            # stay in the center (durable by construction); survivors
            # carried the rest of the budget.
            self._record(worker_failures=[(w, repr(e))
                                          for w, e in failures])
        if retry_records:
            self._record(worker_round_retries=retry_records)
        if ps is not None and ps.num_snapshots:
            self._record(ps_snapshots=ps.num_snapshots)
        if leaders:
            total_folded = sum(l.num_commits for l in leaders)
            ups = sum(l.num_upstream for l in leaders)
            self._record(
                ps_upstream_commits=ups,
                ps_fanin_reduction=total_folded / max(ups, 1),
                ps_leader_failovers=int(leader_failover_total.value))
        if codec is not None:
            self._record(commit_wire_bytes=int(wire_total.value),
                         commit_raw_bytes=int(raw_total.value))
        if ((self.ps_shards > 1 or self.ps_elastic)
                and self.transport == "socket"):
            # version-delta pull savings (process-local): shards the
            # server did NOT ship because this process's workers were
            # already current on them
            self._record(
                pull_shards_skipped=int(skip_total.value),
                pull_bytes_saved=int(saved_total.value))
        # end-of-run SLO verdict over whatever the run metered (with
        # telemetry disabled every signal is absent → "ok")
        self._record(slo_health=telemetry.metrics().health()["state"])

        # round_loss is per-process telemetry (this process's workers);
        # epoch_loss / dropped tails are reduced globally so every
        # process reports identical curves.
        for _, _, loss in round_records:
            self._record(round_loss=loss)
        sums = np.zeros((self.num_epoch, 4))
        for _, e, loss in round_records:
            sums[e] += (loss, 1.0, 0.0, 0.0)
        sums[:, 2] = dropped_per_epoch
        sums[:, 3] = skipped_rows_per_epoch
        if multi:
            sums = np.asarray(
                multihost_utils.process_allgather(sums)).sum(axis=0)
        for epoch in range(self.num_epoch):
            self._record(
                epoch_loss=float(sums[epoch, 0]
                                 / max(sums[epoch, 1], 1.0)),
                dropped_tail_batches=int(sums[epoch, 2]))
            if sums[epoch, 3]:
                self._record(
                    skipped_segment_rows=int(sums[epoch, 3]))

        if multi:
            # staleness log + final center live on process 0; broadcast
            # (two-phase: length first — shapes must match everywhere)
            n_stal = int(np.asarray(multihost_utils.broadcast_one_to_all(
                np.asarray([len(ps.staleness_log) if ps is not None
                            else 0])))[0])
            stal = np.zeros(n_stal, np.int64)
            if rank == 0:
                stal[:] = ps.staleness_log
            stal = np.asarray(
                multihost_utils.broadcast_one_to_all(stal))
            self._record(staleness=[int(s) for s in stal])
            final_center = multihost_utils.broadcast_one_to_all(
                jax.tree_util.tree_map(
                    np.asarray, ps.center if ps is not None else center),
                is_source=rank == 0)
        elif ps is not None:
            self._record(staleness=list(ps.staleness_log))
            final_center = ps.center
        elif self.ps_replicas is not None:
            # replicated external PS: the final center is pulled
            # through the SAME multi-address failover path the workers
            # used — the group may have promoted mid-run, so a pinned
            # address could point at a fenced ex-primary
            fin = ResilientPSClient.for_replicas(
                self.ps_replicas, worker_id=num_workers,
                template=center, retries=self.worker_retries,
                seed=self.seed, use_seq=False)
            try:
                final_center = fin.pull()
                fin.done()
                try:
                    served_epoch = fetch_epoch(
                        *fin.replicas.current())
                except OSError:
                    # the serving replica died between the final pull
                    # and this probe; the pull (the deliverable)
                    # already succeeded — record the sentinel, not a
                    # failed run
                    served_epoch = -1
                self._record(
                    ps_failovers=int(failover_total.value),
                    ps_epoch=served_epoch)
            finally:
                fin.close()
        elif self.ps_elastic:
            # elastic external PS: the group may have split / merged /
            # migrated mid-run, so the final pull walks the versioned
            # shard map exactly the way the workers did
            fin = ResilientPSClient.for_elastic(
                [self.ps_address], worker_id=num_workers,
                template=center, retries=self.worker_retries,
                seed=self.seed)
            try:
                final_center = fin.pull()
                fin.done()
            finally:
                fin.close()
        else:
            # external ps_address: the final center is pulled over the
            # wire; staleness history stays server-side (the PS
            # outlives this driver — the ps_address contract)
            fin = PSClient(*self.ps_address, worker_id=num_workers,
                           template=center)
            try:
                final_center = fin.pull()
                fin.done()
            finally:
                fin.close()
        self.parameter_server_state = ps  # None off process 0 and
        # for external ps_address (the server owns its state there)
        self.trained_variables = {
            "params": jax.tree_util.tree_map(jnp.asarray, final_center),
            **model_state}
        # Free-running threads have no global epoch boundary; evaluate
        # the final center once.
        self._eval_epoch(self.trained_variables)
        return self.trained_variables


class DOWNPOUR(DistributedTrainer):
    """Dean et al. async SGD (reference ``DOWNPOUR``)."""

    def allocate_rule(self):
        return DownpourRule()

    def _lr_law(self):
        f = self.num_workers * self.communication_window
        return (f, f, "learning_rate / (num_workers * "
                "communication_window)")


class ADAG(DistributedTrainer):
    """Asynchronous Distributed Adaptive Gradients — window-normalized
    deltas (reference's flagship, ``ADAG``)."""

    def allocate_rule(self):
        return AdagRule()

    def _lr_law(self):
        return (self.num_workers, self.num_workers,
                "learning_rate / num_workers")


class DynSGD(DistributedTrainer):
    """Staleness-scaled commits (reference ``DynSGD``)."""

    def allocate_rule(self):
        return DynSGDRule()

    def _lr_law(self):
        return (self.communication_window, self.communication_window,
                "learning_rate / communication_window")


class AEASGD(DistributedTrainer):
    """Asynchronous Elastic Averaging SGD (Zhang et al.; reference
    ``AEASGD``).  ``alpha = learning_rate * rho`` as in the paper's
    stability condition."""

    def __init__(self, model, rho: float = 5.0, **kwargs):
        kwargs.setdefault("learning_rate", 0.01)
        super().__init__(model, **kwargs)
        self.rho = float(rho)

    @property
    def alpha(self) -> float:
        try:
            lr = float(self.learning_rate)
        except (TypeError, ValueError):
            raise ValueError(
                "the elastic family derives alpha = learning_rate * "
                "rho (the paper's stability condition), which needs a "
                "scalar learning_rate — schedules are not supported "
                f"here, got {self.learning_rate!r}") from None
        return lr * self.rho

    def allocate_rule(self):
        return ElasticRule(alpha=self.alpha)


class EAMSGD(AEASGD):
    """AEASGD with Nesterov momentum in the worker loop (reference
    ``EAMSGD`` — same server law, momentum on the worker)."""

    def __init__(self, model, momentum: float = 0.9, **kwargs):
        kwargs.setdefault("worker_optimizer", "nesterov")
        # before super(): _apply_lr_law runs in the base __init__ and
        # EAMSGD's law reads the momentum
        self.momentum = momentum
        super().__init__(model, **kwargs)

    def _lr_law(self):
        if self.worker_optimizer != "nesterov" or self.momentum >= 1:
            return super()._lr_law()
        # Nesterov workers amplify the effective step ~1/(1-m)
        # transiently (10x at the default m=0.9) — that drives the
        # warning threshold — but the MEASURED correction is lr/2
        # (PARITY.md's "momentum law" row restores 0.99): momentum
        # amplification is transient, so dividing by the full 1/(1-m)
        # would under-train 5x below the measured parity lr.
        return (1.0 / (1.0 - self.momentum), 2.0,
                "learning_rate / 2 (the measured momentum-law row "
                "at the default momentum=0.9)")

    def _tx(self):
        if self.worker_optimizer == "nesterov":
            return resolve_optimizer("nesterov",
                                     self.learning_rate,
                                     m=self.momentum)
        return super()._tx()


class _MemberParallelTrainer(Trainer):
    """Shared engine for Ensemble/Averaging: every member trains
    *simultaneously* inside one vmapped, jitted program, members sharded
    across the mesh's worker axis (round-1 ran them as sequential
    Python loops — zero mesh utilization for an embarrassingly parallel
    job, VERDICT.md Weak #7)."""

    SCAN_CHUNK = 32

    #: False -> every member shares one init (the averaging setting);
    #: True -> per-member init seeds (independent ensemble members).
    distinct_inits: ClassVar[bool] = True

    def __init__(self, model, num_models: int = 2, **kwargs):
        super().__init__(model, **kwargs)
        self.num_models = int(num_models)

    def _member_states(self, initial_variables) -> "TrainState":
        tx = self._tx()
        n = self.num_models
        sample = jnp.asarray(self.spec.example_input(self.batch_size))
        if initial_variables is not None:
            variables = dict(initial_variables)
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(
                    jnp.asarray(x), (n,) + jnp.shape(x)), variables)
        elif self.distinct_inits:
            init_keys = jnp.stack(
                [jax.random.key(self.seed + i) for i in range(n)])
            stacked = jax.vmap(
                lambda k: self.model.init(k, sample))(init_keys)
        else:
            variables = self.model.init(jax.random.key(self.seed),
                                        sample)
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n,) + x.shape),
                variables)
        member_rngs = jax.vmap(
            lambda i: jax.random.fold_in(
                jax.random.key(self.seed + 1), i))(jnp.arange(n))
        return jax.vmap(lambda v, r: TrainState.create(v, tx, r))(
            stacked, member_rngs)

    def _train_members(self, dataset, initial_variables):
        """Returns final member states (leaves stacked ``[M, ...]``)."""
        n = self.num_models
        tx = self._tx()
        states = self._member_states(initial_variables)
        step = make_train_step(self.model, self.loss, tx,
                               self.features_col, self.label_col)
        vrun = jax.vmap(make_window_runner(step))

        placement = mesh_lib.place_workers(n)
        self._member_placement = placement
        if placement.mesh is not None:
            m = placement.mesh
            # member axis sharded across the mesh for states and batches
            row = NamedSharding(m, P(mesh_lib.WORKER_AXIS))
            self._member_sharding = row
            states = mesh_lib.global_batch_from_local(row, states)
            vrun = jax.jit(vrun, in_shardings=(row, row),
                           out_shardings=(row, row))
        else:
            self._member_sharding = None
            vrun = jax.jit(vrun)

        cols = self._columns()
        # Partition ONCE (after one global shuffle so contiguous/sorted
        # datasets don't give members order-biased shards): member i
        # sees only its own 1/n of the data for the whole run — the
        # disjointness ensembling's variance reduction rests on.  Only
        # the within-shard batch order reshuffles per epoch.
        member_shards = dataset.shuffle(seed=self.seed).repartition(n)
        for epoch in range(self.num_epoch):
            t_epoch = telemetry.now()
            per_member = [
                _stack_batches(
                    s.shuffle(seed=self.seed + 13 * epoch + i),
                    self.batch_size, cols)
                for i, s in enumerate(member_shards)]
            if any(p is None for p in per_member):
                raise ValueError(
                    "a member shard is smaller than one batch")
            n_batches = min(len(next(iter(p.values())))
                            for p in per_member)
            losses = []
            for lo in range(0, n_batches, self.SCAN_CHUNK):
                # [M, chunk, B, ...]
                chunk = {
                    k: np.stack([p[k][lo:lo + self.SCAN_CHUNK]
                                 for p in per_member])
                    for k in cols}
                if placement.mesh is not None:
                    chunk = mesh_lib.global_batch_from_local(row, chunk)
                else:
                    chunk = {k: jnp.asarray(v)
                             for k, v in chunk.items()}
                states, metrics = vrun(states, chunk)
                losses.append(mesh_lib.fetch(metrics["loss"]))
            # per-member mean loss this epoch, [M]
            per_member_loss = np.concatenate(losses, axis=1).mean(
                axis=1)
            self._record(
                epoch_loss=float(per_member_loss.mean()),
                member_loss=[float(x) for x in per_member_loss])
            telemetry.complete("epoch", t_epoch, epoch=epoch,
                               trainer=type(self).__name__)
        return states

    def _guard_no_checkpoint(self, resume_from):
        if resume_from is not None or self.checkpoint_dir is not None:
            raise ValueError(
                f"{type(self).__name__} does not support checkpointing;"
                " checkpoint the member SingleTrainers instead")


class EnsembleTrainer(_MemberParallelTrainer):
    """Train ``num_models`` independent replicas (different init seeds,
    disjoint data shards) concurrently across the mesh; returns the list
    of member variable dicts (reference ``EnsembleTrainer``, SURVEY.md
    §2.3 [LOW])."""

    distinct_inits: ClassVar[bool] = True

    def _train(self, dataset, initial_variables, resume_from=None):
        self._guard_no_checkpoint(resume_from)
        states = self._train_members(dataset, initial_variables)
        # variables() first: drops the typed-rng leaf, which cannot
        # pass through numpy
        host = jax.tree_util.tree_map(mesh_lib.fetch,
                                      states.variables())
        results = [jax.tree_util.tree_map(lambda x: x[i], host)
                   for i in range(self.num_models)]
        self.trained_variables = results[0]
        self.ensemble_variables = results
        return results


class AveragingTrainer(_MemberParallelTrainer):
    """Train workers concurrently on disjoint shards from one shared
    init, then average their parameters — one-shot model averaging
    (reference ``AveragingTrainer``, SURVEY.md §2.3 [LOW])."""

    distinct_inits: ClassVar[bool] = False

    def __init__(self, model, num_workers: int = 2, **kwargs):
        super().__init__(model, num_models=num_workers, **kwargs)

    @property
    def num_workers(self) -> int:
        return self.num_models

    def _train(self, dataset, initial_variables, resume_from=None):
        self._guard_no_checkpoint(resume_from)
        states = self._train_members(dataset, initial_variables)

        # Mean over the member axis + member 0's model state, both on
        # device (one ICI reduce / slice when members are mesh-sharded)
        # so only the final values cross to host.
        def finalize(s):
            return (jax.tree_util.tree_map(lambda x: x.mean(axis=0),
                                           s.params),
                    jax.tree_util.tree_map(lambda x: x[0],
                                           s.model_state))

        row = self._member_sharding
        fin = (jax.jit(finalize, out_shardings=NamedSharding(
                   self._member_placement.mesh, P()))
               if row is not None else jax.jit(finalize))
        avg_params, member0_state = fin(states)
        self.trained_variables = {
            "params": jax.tree_util.tree_map(mesh_lib.fetch,
                                             avg_params),
            **jax.tree_util.tree_map(mesh_lib.fetch, member0_state)}
        return self.trained_variables
