"""Training cells on one chip: ``SingleTrainer.train()`` with its input
path running, one call for the whole run.

The feed is a ``ShardedDataset`` whose segments are made from the seed as
the trainer asks for them, ``segment_batches`` batches to a segment, so
that every call of the trainer's compiled chunk program scans that many
steps: the one program, state and feed that the window drives also take
the steps that are compared.  The call has three epochs:

* epoch 0 is one segment, the first chunk.  At its end the trainer calls
  its checkpoint hook with the state: the benchmark's subclass overrides
  that one hook and reads, on the device, the norms that the comparison
  with the reference needs (nothing is written);
* epoch 1 is ``warm_segments`` segments, still set-up;
* epoch 2 is the window.  It opens when the trainer asks the feed for the
  epoch's segments, which it does once the epoch before has returned its
  last loss to the host, and it closes when ``train()`` returns.  The
  feed stops making segments once ``--seconds`` have passed; the trainer
  ends the ones it already holds, so the window is a little longer than
  that, and every step of the epoch lies inside it.

Only the dataset's protocol (``epoch_segments``), ``train()``'s return
and ``history`` set the clock and the count; that the checkpoint hook is
called with the state after every epoch is asserted at run time.
"""

import gc

import numpy as np

from perfbench import common, trafficgen
from perfbench.common import now

CHECK_EPOCH, WARM_EPOCH, WINDOW_EPOCH = 0, 1, 2


def build_feed(traffic, seed, vocab, seconds, tracer, meter):
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.data.sharded import ShardedDataset

    per_segment = traffic["segment_batches"]

    class Feed(ShardedDataset):
        """Segments from the seed; see the module's docstring."""

        def __init__(self):
            self.asked = []            # host stamp of each epoch's request
            self.made = []             # segments made, by epoch
            self.made_at = []          # host stamps of the window's segments
            self.check_batches = None  # the first chunk's batches
            self.compiles_open = None
            self.step = 0

        def _segment(self, epoch):
            batches = [trafficgen.lm_batch(traffic, seed, self.step + i,
                                           vocab) for i in range(per_segment)]
            self.step += per_segment
            self.made[epoch] += 1
            if epoch == CHECK_EPOCH:
                self.check_batches = batches
            tokens = np.concatenate(batches)
            return Dataset({"features": tokens[:, :-1],
                            "label": tokens[:, 1:]})

        def epoch_segments(self, seed_=0):
            epoch, t = len(self.asked), now()
            self.asked.append(t)
            self.made.append(0)
            if epoch == WINDOW_EPOCH:
                self.compiles_open = meter.compiles
                if tracer is not None:
                    tracer.start()
            return self._epoch(epoch, t)

        def _epoch(self, epoch, t_asked):
            if epoch == CHECK_EPOCH:
                yield self._segment(epoch)
            elif epoch == WARM_EPOCH:
                for _ in range(traffic["warm_segments"]):
                    yield self._segment(epoch)
            else:
                while not self.made_at or now() - t_asked < seconds:
                    self.made_at.append(now())
                    yield self._segment(epoch)

    return Feed()


def program_norms(tree, name_of, minus=None) -> dict:
    """``{benchmark leaf name: l2 norm}`` of a tree in the program's names
    (of ``tree - minus`` where that is given), on the device in one call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        diff = a if b is None else jax.tree_util.tree_map(jnp.subtract, a, b)
        return jax.tree_util.tree_map(
            lambda x: jnp.linalg.norm(x.astype(jnp.float32).ravel()), diff)

    flat = jax.tree_util.tree_flatten_with_path(norms(tree, minus))[0]
    return {name_of(tuple(str(k.key) for k in path)): float(n)
            for path, n in flat}


def first_moment(opt_state):
    """Adam's first moment inside an optax state, whatever wraps it."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for s in opt_state:
            mu = first_moment(s)
            if mu is not None:
                return mu
    return None


def worst_leaf_gap(got: dict, want: dict, leave_out=()) -> tuple:
    """The worst leaf's gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    median = float(np.median(list(want.values())))
    worst, where = 0.0, None
    for leaf, ref in want.items():
        if leaf in leave_out:
            continue
        gap = abs(got[leaf] - ref) / max(ref, median)
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def compare(program: dict, ref: dict, checks) -> None:
    """Each number of the training comparison beside its limit: the
    first chunk's mean loss, and by the worst leaf the norm of Adam's
    first moment (the gradients as the optimizer got them) and of the
    parameters' change, both after the chunk's last step."""
    checks.at_most("loss_chunk_gap", abs(program["loss"] - ref["loss"]))
    gap, leaf = worst_leaf_gap(program["moment_norms"], ref["moment_norms"])
    checks.at_most("moment_norm_worst_leaf_gap", gap, leaf=leaf)
    # a leaf whose gradient is nought to rounding in the reference moves
    # under Adam by round-off alone: left out of the change by a rule on
    # the reference's gradient, under a thousandth of the median leaf's
    median = float(np.median(list(ref["grad_norms"].values())))
    out = [k for k, v in ref["grad_norms"].items() if v < 1e-3 * median]
    gap, leaf = worst_leaf_gap(program["change_norms"], ref["change_norms"],
                               leave_out=out)
    checks.at_most("change_norm_worst_leaf_gap", gap, leaf=leaf,
                   leaves_left_out=len(out))


def run(ctx) -> dict:
    import jax
    from distkeras_tpu import trainers

    cfg, traffic = ctx.config, ctx.traffic
    adapter, reference, counts, weights = ctx.arch
    meter = common.CompileMeter().install()
    stages = {}

    t = now()
    variables = adapter.program_variables(
        weights.make(cfg, ctx.seed, cfg["weights_as_run"]))
    jax.block_until_ready(variables)
    stages["weights_s"] = now() - t

    tracer = common.Tracer(ctx.trace_dir) if ctx.trace else None
    seconds = min(ctx.seconds, traffic["trace_s"]) if ctx.trace \
        else ctx.seconds
    feed = build_feed(traffic, ctx.seed, cfg["vocab_size"], seconds, tracer,
                      meter)
    probe = {}
    p0 = variables["params"]

    base = getattr(trainers, traffic["trainer"])

    class Probed(base):
        """The trainer with its checkpoint hook reading norms instead of
        writing files; ``train()`` and ``_train()`` are the program's."""

        def _maybe_save(self, state, cursor):
            if cursor["epoch"] == CHECK_EPOCH + 1 and not probe:
                probe["moment_norms"] = program_norms(
                    first_moment(state.opt_state),
                    adapter.benchmark_leaf_name)
                probe["change_norms"] = program_norms(
                    state.params, adapter.benchmark_leaf_name, minus=p0)
                probe["t"] = now()

    model = adapter.program_model(cfg, traffic["seq_len"],
                                  **traffic.get("model_overrides", {}))
    trainer = Probed(model, batch_size=traffic["batch_size"],
                     num_epoch=WINDOW_EPOCH + 1, **traffic["trainer_args"])
    t = now()
    trainer.train(feed, initial_variables=variables)
    t_close = now()
    if tracer is not None:
        tracer.stop()
    compiles_close = meter.compiles
    epoch_losses = list(trainer.history["epoch_loss"])
    # what the clock and the count rest on, said out loud: one request of
    # the feed and one loss for each epoch, and the state handed to the
    # checkpoint hook between the first epoch's end and the second's start
    if (len(feed.asked) != WINDOW_EPOCH + 1
            or len(epoch_losses) != WINDOW_EPOCH + 1 or not probe
            or not feed.asked[CHECK_EPOCH] < probe["t"]
            < feed.asked[WARM_EPOCH]):
        raise RuntimeError(
            "the trainer no longer asks its dataset once an epoch, records "
            "one epoch_loss an epoch and hands the state to _maybe_save at "
            f"every epoch's end: {len(feed.asked)} requests, "
            f"{len(epoch_losses)} losses, probe {sorted(probe)}")
    stages["train_call_s"] = t_close - t
    stages["first_chunk_s"] = probe["t"] - t
    stages["compiles_in_setup"] = feed.compiles_open

    t_open = feed.asked[WINDOW_EPOCH]
    steps = feed.made[WINDOW_EPOCH] * traffic["segment_batches"]
    window_s = t_close - t_open
    setup_s = t_open - ctx.t_process_start
    compiles_in_window = compiles_close - feed.compiles_open
    peak = common.memory_peak_bytes(ctx.chips)

    batches = feed.check_batches
    # a look for stalls only: the feed is asked for a segment each time
    # the trainer takes one, so a chunk that hangs shows as a long gap
    made = feed.made_at
    segment_gap_max_s = max((b - a for a, b in zip(made, made[1:])),
                            default=0.0)
    flops_step = traffic["batch_size"] * counts.train_flops_per_sequence(
        cfg, traffic["seq_len"])
    mfu = 100.0 * steps * flops_step / window_s / (
        ctx.chips * ctx.peaks["flops_bf16"])
    del trainer, variables, p0, feed
    gc.collect()

    t = now()
    ref = reference.train_readings(cfg, ctx.seed, batches,
                                   traffic["trainer_args"]["learning_rate"])
    stages["reference_s"] = now() - t
    checks = common.Checks(ctx.limits)
    compare({"loss": float(epoch_losses[CHECK_EPOCH]),
             "moment_norms": probe["moment_norms"],
             "change_norms": probe["change_norms"]}, ref, checks)
    checks.at_most("compiles_in_window", compiles_in_window)

    return {
        "checks": checks, "attempted": steps, "failed": 0,
        "end_to_end": {"train_mfu": mfu, "setup_s": setup_s,
                       "steps_in_window": steps, "window_s": window_s,
                       "step_ms": 1e3 * window_s / max(steps, 1),
                       "segment_gap_max_s": segment_gap_max_s},
        "memory_peak_bytes": peak, "stages": stages,
        "layers": {"steps": steps, "window_s": window_s, "tracer": tracer,
                   "flops_step": flops_step, "peak_bytes": peak,
                   "batch": traffic["batch_size"],
                   "seq_len": traffic["seq_len"],
                   "reference": ref, "batches": batches},
    }
