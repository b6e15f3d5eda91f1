"""Does the system still start on the chip?  One process, the public
entry points (``Trainer.train()``, ``DecodeEngine.run()``), models at
full width, a few steps each, random weights from a seed.

    python chip_smoke.py

Phases, each printing one JSON line (the device as JAX reports it —
platform, kind, count — compile seconds, steady seconds, what it
checked):

* ``kernels``       — the Pallas flash forward/backward at the LM's
  attention shape against an f32 reference; what ``attrib.extract_cost``
  and ``native.available()`` report; ``block_until_ready`` vs
  ``profiling.host_sync`` around one step chain.
* ``train/resnet50`` — ResNet-50 (bottleneck 3,4,6,3, GroupNorm, bf16,
  space-to-depth stem) at 224 px / 1000 classes through ``SingleTrainer``
  and an ``ADAG(fidelity="fast")`` parameter-server arm.
* ``train/lm``      — ``transformer_lm`` L12 d768 H12 vocab 32768 at
  T=2048 b8 through ``SingleTrainer`` with ``attn="auto"``: the lowered
  step must hold the Mosaic kernels (forward AND backward), and one
  step's loss must match ``attn="dense"`` on the same batch.
* ``serve/lm``      — ``DecodeEngine`` over the trained variables,
  envelope pools and the paged pool: greedy tokens equal
  ``models.generate()`` per request, byte for byte.
* ``serve/pools``   — a two-layer engine with the benchmark
  configuration's 16 heads of 128: its compiled step and prefill
  programs, handed their pool, hold no copy of a whole pool leaf, and
  its step programs read the cache through the decode kernel (their
  ``decode_step`` spans carry ``attended_rows > 0``).
* ``four chips/…``  — only where ``len(jax.devices()) >= 4``:
  ``DOWNPOUR(fidelity="mesh")`` one worker per chip, then the LM
  through ``SyncTrainer(num_workers=4)`` (blockwise attention: Mosaic
  calls cannot be auto-partitioned), each with its spread asserted.

There is no CPU arm and no switch: without a TPU whose ``device_kind``
has a row in ``profiling.PEAK_FLOPS`` it exits non-zero before compiling
anything.  Any failed check raises, so the run ends non-zero and the
last line is not the result.  The phase functions take their sizes as
arguments so that ``tests/test_chip_smoke.py`` can drive the same code
at toy width on the CPU mesh; a CPU run proves structure, never speed.

Last line of stdout on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import collections
import contextlib
import functools
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import attrib, native, profiling, telemetry
from distkeras_tpu.data import datasets
from distkeras_tpu.models import ModelSpec, generate, model_config
from distkeras_tpu.models.transformer import dense_causal_attention
from distkeras_tpu.ops.attention import (decode_attention,
                                         decode_attention_applies,
                                         flash_attention)
from distkeras_tpu.serving import DecodeEngine
from distkeras_tpu.trainers import (ADAG, DOWNPOUR, SingleTrainer,
                                    SyncTrainer)
from distkeras_tpu.workers import (TrainState, make_train_step,
                                   resolve_optimizer)

LM_LOSS = "sparse_categorical_crossentropy"

# Full-width sizes of the chip run.  ResNet-50 is the paper's flagship
# at its published shape; the LM and its serve mix are the shapes PERF.md
# has on-chip history for.
RESNET = dict(image=224, classes=1000, width=64, stage_sizes=(3, 4, 6, 3),
              rows=1536)
RESNET_TRAIN = dict(batch=128, ps_workers=4, ps_batch=32, ps_window=2)
LM = dict(layers=12, d_model=768, heads=12, vocab=32768, seq=2048)
LM_TRAIN = dict(batch=8, steps=4, parity_layers=2)
KERNELS = dict(batch=8, seq=2048, heads=12, head_dim=64, ref_batch=2,
               chain=20)
# the slot-mode step's read of the cache through the decode kernel, at
# the two shapes the benchmark's cells have: one query head a cached
# head, and one latent leaf under all heads whose first `v_width` columns
# are its values; ragged lengths, a finished slot's 0 among them
DECODE_KERNEL = dict(
    per_head=dict(slots=8, cache_len=1024, kvh=16, group=1, width=128),
    shared_leaf=dict(slots=8, cache_len=2048, kvh=1, group=32, width=640,
                     v_width=512),
    lengths=(0, 1, 255, 256, 257, 700, 1024, 1000))
# (prompt length, budget): three padded lengths, one per bucket, so the
# compile count stays at one prefill + one step program per bucket
SERVE = dict(buckets=(512, 1024, 2048), align=128, slots=4, kv_pages=64,
             requests=((128, 16), (512, 32), (1024, 64)) * 3)
# 16 heads of 128 as in the benchmark's configuration (what the decode
# kernel's rule takes in bfloat16); one prompt a bucket
POOLS = dict(layers=2, d_model=2048, heads=16, vocab=4096, seq=1024,
             buckets=(512, 1024), align=128, slots=4,
             requests=((100, 4), (600, 4)))
# four chips: batches are per chip
MESH_PS = dict(workers=4, batch=64, window=2)
SYNC_LM = dict(workers=4, batch=4, steps=3)


# ---- measurement plumbing ----------------------------------------------

class CompileMeter:
    """Seconds spent in (and count of) backend compiles, and persistent
    cache hits, from ``jax.monitoring`` — a cache hit's retrieval is
    inside the same duration event, so warm and cold runs read off the
    same counter."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def install(self) -> "CompileMeter":
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter, device: dict):
    """Print one JSON line for the phase — only if it did not raise."""
    rec: dict = {"phase": name, "device": device}
    s0, c0, h0 = meter.seconds, meter.compiles, meter.cache_hits
    t0 = time.perf_counter()
    yield rec
    rec["wall_s"] = round(time.perf_counter() - t0, 2)
    rec["compile_s"] = round(meter.seconds - s0, 2)
    rec["compiles"] = meter.compiles - c0
    rec["cache_hits"] = meter.cache_hits - h0
    print(json.dumps(rec), flush=True)


@contextlib.contextmanager
def fresh_telemetry():
    """A new registry + tracer for one measured section: the trainers'
    ``epoch`` spans and the engine's latency histograms are the repo's
    own clocks for steady-state time."""
    tel = telemetry.enable()
    try:
        yield tel
    finally:
        telemetry.disable()


def steady_seconds(tel, per_epoch: int) -> float:
    """Seconds per step (or round) of the LAST epoch — the first one
    holds the compile."""
    epochs = [e["dur"] * 1e-6 for e in tel.tracer.events()
              if e["name"] == "epoch"]
    if len(epochs) < 2:
        raise AssertionError(
            f"need >= 2 epoch spans to separate compile from steady "
            f"state, got {len(epochs)}")
    return epochs[-1] / per_epoch


def check_losses(name: str, losses) -> list[float]:
    losses = [float(x) for x in losses]
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: loss did not decrease {losses}")
    return [round(x, 4) for x in losses]


def device_sets(tree) -> set[int]:
    """Sizes of ``sharding.device_set`` over a pytree's leaves."""
    return {len(x.sharding.device_set)
            for x in jax.tree_util.tree_leaves(tree)}


# ---- phases -------------------------------------------------------------

def kernel_facts(*, batch, seq, heads, head_dim, ref_batch, chain,
                 interpret=False) -> dict:
    """Flash forward + backward at the LM's attention shape: compiled
    once ahead of time (the cost ledger's view of it is printed, a
    silent all-``None`` ledger is seen here), checked against an f32
    ``highest``-precision dense reference on ``ref_batch`` rows, then
    timed as a chain two ways."""
    keys = jax.random.split(jax.random.key(0), 4)
    q, k, v, do = (jax.random.normal(kk, (batch, seq, heads, head_dim),
                                     jnp.bfloat16) for kk in keys)

    def fwd_bwd(attn):
        def f(q, k, v, do):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out, *vjp(do))
        return jax.jit(f)

    lowered = fwd_bwd(functools.partial(
        flash_attention, interpret=interpret)).lower(q, k, v, do)
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    if not interpret and mosaic_calls < 3:
        raise AssertionError(
            f"flash fwd+bwd lowered to {mosaic_calls} Mosaic calls; "
            "want the forward, dQ and dK/dV kernels")
    flash = lowered.compile()
    got = flash(q, k, v, do)

    scale = head_dim ** -0.5
    with jax.default_matmul_precision("highest"):
        want = fwd_bwd(functools.partial(
            dense_causal_attention, scale=scale))(
            *(x[:ref_batch].astype(jnp.float32) for x in (q, k, v, do)))
    # The kernels keep logits and softmax statistics in f32 and round
    # the probabilities (and dS) to bf16 for the MXU exactly as the
    # dense path does: 2**-9 per product, averaged over up to `seq`
    # terms, plus one bf16 rounding of the result.  2e-2 of the
    # reference's range passes that and fails a wrong mask or a
    # skipped block, which are O(1).
    errs = {}
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        g = np.asarray(g[:ref_batch].astype(jnp.float32))
        w = np.asarray(w)
        errs[name] = float(np.abs(g - w).max() / np.abs(w).max())
        if not errs[name] <= 2e-2:
            raise AssertionError(
                f"flash {name} off the f32 reference: {errs}")

    def chain_seconds(sync) -> float:
        sync(flash(q, k, v, do))
        t0 = time.perf_counter()
        for _ in range(chain):
            out = flash(q, k, v, do)
        sync(out)
        return (time.perf_counter() - t0) / chain

    return {
        "mosaic_calls": mosaic_calls,
        "max_err_vs_f32": {n: round(e, 5) for n, e in errs.items()},
        "extract_cost": attrib.extract_cost(flash),
        "native_available": native.available(),
        "chain_block_until_ready_s": round(
            chain_seconds(jax.block_until_ready), 5),
        "chain_host_sync_s": round(
            chain_seconds(profiling.host_sync), 5),
    }


def resnet_job(*, image, classes, width, stage_sizes, rows):
    """``(model config, dataset)`` — GroupNorm bottleneck ResNet with the
    space-to-depth stem as ``bench.py`` builds it, on synthetic images."""
    cfg = model_config("resnet", (image, image, 3), num_classes=classes,
                       stage_sizes=stage_sizes, bottleneck=True,
                       width=width, stem="space_to_depth")
    return cfg, datasets.imagenet_synth(rows, image_size=image,
                                        num_classes=classes)


def train_resnet(*, batch, ps_workers, ps_batch, ps_window, rows,
                 **model) -> dict:
    cfg, data = resnet_job(rows=rows, **model)
    with fresh_telemetry() as tel:
        single = SingleTrainer(cfg, worker_optimizer="sgd",
                               learning_rate=0.02, batch_size=batch,
                               num_epoch=2)
        single.train(data)
        step_s = steady_seconds(tel, rows // batch)
    with fresh_telemetry() as tel:
        ps = ADAG(cfg, num_workers=ps_workers,
                  communication_window=ps_window, fidelity="fast",
                  batch_size=ps_batch, num_epoch=2,
                  worker_optimizer="sgd", learning_rate=0.04,
                  lr_law="scale")
        ps.train(data)
        per_round = ps_workers * ps_batch * ps_window
        round_s = steady_seconds(tel, rows // per_round)
    return {
        "single_epoch_loss": check_losses(
            "SingleTrainer", single.history["epoch_loss"]),
        "steady_step_s": round(step_s, 4),
        "single_images_per_s": round(batch / step_s, 1),
        "ps_epoch_loss": check_losses("ADAG", ps.history["epoch_loss"]),
        "steady_round_s": round(round_s, 4),
        "ps_images_per_s": round(per_round / round_s, 1),
    }


def lm_config(*, layers, d_model, heads, vocab, seq, **attn) -> dict:
    return model_config("transformer_lm", (seq,), input_dtype="int32",
                        vocab_size=vocab, num_layers=layers,
                        d_model=d_model, num_heads=heads, max_len=seq,
                        dtype="bfloat16", **attn)


def mosaic_calls_in_step(trainer, batch: int, seq: int) -> int:
    """Count the Mosaic custom calls in the lowered program of the step
    ``trainer`` trains with (same model, loss and optimizer through the
    same builder) — what ``attn="auto"`` resolved to in THIS process,
    read from the program and not from the config."""
    tx = resolve_optimizer(trainer.worker_optimizer,
                           trainer.learning_rate)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    state = jax.eval_shape(
        lambda: TrainState.create(
            trainer.model.init(jax.random.key(0),
                               jnp.zeros(tokens.shape, tokens.dtype)),
            tx, jax.random.key(1)))
    step = make_train_step(trainer.model, trainer.loss, tx)
    lowered = jax.jit(step).lower(
        state, {"features": tokens, "label": tokens})
    return lowered.as_text().count("tpu_custom_call")


def train_lm(*, layers, d_model, heads, vocab, seq, batch, steps,
             parity_layers, interpret=False) -> tuple[dict, dict, dict]:
    """Returns ``(facts, model config, trained variables)``."""
    width = dict(d_model=d_model, heads=heads, vocab=vocab, seq=seq)
    # the chip run leaves the choice to "auto" and then proves what it
    # chose; the CPU test names the kernels and runs them interpreted
    attn = ({"attn_fn": functools.partial(flash_attention,
                                          interpret=True)}
            if interpret else {"attn": "auto"})

    def trainer(cfg, epochs):
        return SingleTrainer(cfg, loss=LM_LOSS, worker_optimizer="adam",
                             learning_rate=1e-3, batch_size=batch,
                             num_epoch=epochs)

    cfg = lm_config(layers=layers, **width, **attn)
    main = trainer(cfg, 2)
    mosaic_calls = mosaic_calls_in_step(main, batch, seq)
    if not interpret and mosaic_calls < 3 * layers:
        raise AssertionError(
            f"attn='auto' at T={seq} lowered to {mosaic_calls} Mosaic "
            f"calls; want forward + dQ + dK/dV for each of {layers} "
            "layers — the platform probe fell back to blockwise")
    data = datasets.lm_synth(batch * steps, seq_len=seq,
                             vocab_size=vocab)
    with fresh_telemetry() as tel:
        variables = main.train(data)
        step_s = steady_seconds(tel, steps)

    # One step on one batch, flash against dense, both through the
    # trainer: the epoch loss of a one-step epoch IS that step's loss.
    one_batch = datasets.lm_synth(batch, seq_len=seq, vocab_size=vocab)
    parity = {}
    for name, kw in (("flash", attn), ("dense", {"attn": "dense"})):
        t = trainer(lm_config(layers=parity_layers, **width, **kw), 1)
        t.train(one_batch)
        parity[name] = float(t.history["epoch_loss"][0])
    # bf16 activations: 2**-8 relative per element on a loss of
    # ~ln(vocab), averaged over batch*seq tokens — 1e-2 absolute is
    # generous for rounding and far below what a wrong mask moves
    if not abs(parity["flash"] - parity["dense"]) <= 1e-2:
        raise AssertionError(f"flash vs dense step loss: {parity}")

    facts = {
        "mosaic_calls": mosaic_calls,
        "epoch_loss": check_losses("LM SingleTrainer",
                                   main.history["epoch_loss"]),
        "steady_step_s": round(step_s, 4),
        "tokens_per_s": round(batch * seq / step_s, 1),
        "step_loss_flash": round(parity["flash"], 5),
        "step_loss_dense": round(parity["dense"], 5),
    }
    return facts, cfg, variables


def decode_kernel_facts(*, lengths, interpret=False, **bodies) -> dict:
    """``ops.attention.decode_attention`` against the XLA read it stands
    in for (two contractions over the whole envelope under a position
    mask, here in float32 at ``highest`` precision on the same bfloat16
    operands).  Not timed: at these sizes a call is shorter than its
    dispatch (the benchmark's cells time it, under ``mla_decode``)."""
    facts = {}
    lengths = jnp.asarray(lengths, jnp.int32)
    for name, b in bodies.items():
        keys = jax.random.split(jax.random.key(1), 3)
        shape = (b["slots"], b["cache_len"], b["kvh"], b["width"])
        q = jax.random.normal(
            keys[0], (b["slots"], b["kvh"], b["group"], b["width"]),
            jnp.bfloat16)
        k = jax.random.normal(keys[1], shape, jnp.bfloat16)
        dv = b.get("v_width", b["width"])
        v = None if "v_width" in b else \
            jax.random.normal(keys[2], shape, jnp.bfloat16)
        scale = b["width"] ** -0.5

        def xla_read(q, k, v):
            mask = jnp.arange(b["cache_len"])[None, None, None] \
                < lengths[:, None, None, None]
            logits = jnp.where(
                mask, jnp.einsum("bhgd,blhd->bhgl", q, k) * scale, -1e30)
            probs = jax.nn.softmax(logits.astype(jnp.float32),
                                   axis=-1).astype(q.dtype)
            return jnp.einsum("bhgl,blhd->bhgd", probs,
                              k if v is None else v)[..., :dv]

        kernel = jax.jit(lambda q, k, v: decode_attention(
            q, k, v, lengths, scale=scale, v_width=dv,
            interpret=interpret))
        mosaic_calls = kernel.lower(q, k, v).as_text().count(
            "tpu_custom_call")
        if mosaic_calls != (not interpret):
            raise AssertionError(
                f"decode kernel ({name}): {mosaic_calls} Mosaic calls")
        got = np.asarray(kernel(q, k, v).astype(jnp.float32))
        f32 = [None if x is None else x.astype(jnp.float32)
               for x in (q, k, v)]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(xla_read)(*f32))
        live = np.asarray(lengths) > 0
        err = float(np.abs(got - want)[live].max())
        facts[name] = {"max_abs_diff_vs_f32": round(err, 5)}
        if not err < 2e-2 or got[~live].any():
            raise AssertionError(
                f"decode kernel ({name}) off the XLA read: {facts}, rows "
                f"of length 0 all zero: {not got[~live].any()}")
    return facts


def pools_in_place(*, layers, d_model, heads, vocab, seq, buckets, align,
                   slots, requests) -> dict:
    """A small engine's compiled step and prefill programs hold no copy
    of a whole pool leaf (``DecodeEngine.pool_report``): the cache is
    declared in the order the decode step computes in, so a program
    that is handed a pool (donated: every engine on a chip) works on it
    in place.  That holds in every step or in none, so this count,
    zero, is the whole of the mechanism's counter; the pool's layout is
    printed with it.  Heads of 128: at this script's LM's 64 the TPU
    lays a ``[.., KVH, 64]`` leaf out another way by default and the
    step re-lays it out, as it did before PR 27 (48 whole-leaf copies
    in its 12-layer step program either way; PERF.md section 7).

    Beside the copies, whether the step programs read the cache through
    ``ops.attention.decode_attention``: a pool's ``decode_step`` spans
    carry ``attended_rows > 0`` where ``decode_attention_applies`` takes
    the pool's leaves (on the chip, 16 heads of 128 in bfloat16) and 0
    where it does not (any CPU run, 8 heads)."""
    cfg = lm_config(layers=layers, d_model=d_model, heads=heads,
                    vocab=vocab, seq=seq)
    model = ModelSpec.from_config(cfg).build()
    variables = jax.jit(model.init)(jax.random.key(0),
                                    jnp.zeros((1, align), jnp.int32))
    engine = DecodeEngine(cfg, variables, slots=slots,
                          buckets=list(buckets), prefill_align=align)
    rng = np.random.default_rng(0)
    with fresh_telemetry() as tel:
        for res in engine.run(
                [{"prompt": rng.integers(0, vocab, (t,)).astype(np.int32),
                  "max_new_tokens": n} for t, n in requests]):
            if "error" in res:
                raise AssertionError(f"pools: {res['error']}")
        steps = [e["args"] for e in tel.tracer.events()
                 if e["name"] == "decode_step"]
    report = engine.pool_report()
    engine.close()
    sds = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    for pool in report:
        for rows in ("attended_rows", "envelope_rows"):
            pool[rows] = sum(a[rows] for a in steps
                             if a["bucket"] == pool["bucket"])
        leaf = sds((slots, pool["bucket"], heads, d_model // heads))
        kernel = decode_attention_applies(
            sds((slots, heads, 1, d_model // heads)), leaf, leaf,
            jax.ShapeDtypeStruct((slots,), jnp.int32))
        if (pool["attended_rows"] > 0) != kernel:
            raise AssertionError(
                f"pool {pool['bucket']}: the decode kernel's rule says "
                f"{kernel} and its steps attended "
                f"{pool['attended_rows']} of {pool['envelope_rows']} rows")
        held = {name: n["relayouts"]
                for name, n in pool["programs"].items() if n["relayouts"]}
        if pool["donated"] and (held or not pool["programs"]):
            raise AssertionError(
                f"pool {pool['bucket']} ({pool['layout']}) is copied "
                f"whole inside its own programs: {held}")
    return {"pools": report}


def serve_lm(cfg: dict, variables: dict, *, buckets, align, slots,
             kv_pages, requests) -> dict:
    vocab = cfg["kwargs"]["vocab_size"]
    rng = np.random.default_rng(0)
    reqs = [{"prompt": rng.integers(0, vocab, (t,)).astype(np.int32),
             "max_new_tokens": n, "i": i}
            for i, (t, n) in enumerate(requests)]

    # one trace per distinct (prompt length, budget)
    reference = jax.jit(
        lambda v, p, n_new: generate(cfg, v, p, max_new_tokens=n_new),
        static_argnums=2)
    want = [np.asarray(reference(variables, r["prompt"][None, :],
                                 r["max_new_tokens"]))[0, len(r["prompt"]):]
            for r in reqs]

    facts: dict = {"requests": len(reqs)}
    for arm, kw in (("envelope", {}), ("paged", {"kv_pages": kv_pages})):
        engine = DecodeEngine(cfg, variables, slots=slots,
                              buckets=list(buckets), prefill_align=align,
                              **kw)
        # pass 1 compiles every program; pass 2 is the steady one
        for _ in range(2):
            with fresh_telemetry() as tel:
                got = list(engine.run(reqs))
                metrics = tel.metrics
            for res in got:
                if "error" in res:
                    raise AssertionError(
                        f"{arm}: request {res['i']} -> {res['error']}")
                if not np.array_equal(res["tokens"], want[res["i"]]):
                    raise AssertionError(
                        f"{arm}: request {res['i']} (prompt "
                        f"{len(res['prompt'])}) differs from generate(): "
                        f"{res['tokens'].tolist()} != "
                        f"{want[res['i']].tolist()}")
        if kw and engine.free_pages() != kv_pages:
            raise AssertionError(
                f"paged: {engine.free_pages()} of {kv_pages} pages "
                "free after a drained run")
        gaps = metrics.histogram("serving_inter_token_seconds").snapshot()
        facts[arm] = {
            "programs": len(engine.compile_counts),
            "inter_token_mean_s": round(gaps["sum"] / gaps["count"], 5),
            "ttft_max_s": round(max(r["ttft"] for r in got), 4),
        }
        engine.close()
    return facts


def chips_in_use(workers: int) -> list:
    """``bytes_in_use`` of the first ``workers`` devices; on a TPU every
    one must hold something (CPU devices report no statistics)."""
    devices = jax.devices()[:workers]
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices]
    if devices[0].platform == "tpu" and not all(in_use):
        raise AssertionError(f"a chip holds nothing: {in_use}")
    return in_use


def mesh_ps_on_chips(*, workers, batch, window, rows, **model) -> dict:
    """``DOWNPOUR(fidelity="mesh")``, one worker per chip."""
    cfg, data = resnet_job(rows=rows, **model)
    with fresh_telemetry() as tel:
        ps = DOWNPOUR(cfg, fidelity="mesh", num_workers=workers,
                      communication_window=window, batch_size=batch,
                      num_epoch=2, worker_optimizer="sgd",
                      learning_rate=0.08, lr_law="scale")
        ps.train(data)
        per_round = workers * batch * window
        round_s = steady_seconds(tel, rows // per_round)
        compiles = tel.metrics.sum_counter("ps_round_compiles_total",
                                           fidelity="mesh")
    driver = ps.mesh_driver
    # The round asks for an all_gather (pull) and a psum_scatter
    # (commit); which opcodes carry them is the compiler's choice — the
    # v5e 2x2 renders both as full-size all-reduces — so the check is
    # that the compiled program communicates at least twice, and the
    # histogram is printed for the reader.
    collectives = collections.Counter(re.findall(
        r"\b(all-gather|reduce-scatter|all-reduce|all-to-all|"
        r"collective-permute)(?:-start)?\(",
        driver.dp.compiled_rounds()[0].as_text()))
    spread = {
        "center_device_sets": sorted(device_sets(driver.mps)),
        "worker_device_sets": sorted(device_sets(driver.mws)),
        "round_collectives": dict(collectives),
        "ps_round_compiles_total": compiles,
    }
    if (spread["center_device_sets"] != [workers]
            or spread["worker_device_sets"] != [workers]
            or sum(collectives.values()) < 2 or compiles != 1):
        raise AssertionError(f"mesh round is not spread: {spread}")
    ledger = driver.dp.cost_report()[0]
    return {
        **spread,
        "epoch_loss": check_losses("DOWNPOUR mesh",
                                   ps.history["epoch_loss"]),
        "steady_round_s": round(round_s, 4),
        "images_per_s_per_chip": round(per_round / round_s / workers, 1),
        "round_ledger": {k: ledger[k] for k in
                         ("flops", "bytes_accessed", "peak_temp_bytes",
                          "collective_bytes", "compile_s")},
        "bytes_in_use": chips_in_use(workers),
    }


def sync_lm_on_chips(*, workers, batch, steps, seq, vocab,
                     **model) -> dict:
    """The LM through ``SyncTrainer``, data parallel over the chips.

    ``attn`` is pinned: "auto" picks the Mosaic kernels at the chip
    run's length and JAX refuses them under a multi-device GSPMD jit
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map." — seen on the four-chip host, ROADMAP
    S2); blockwise is what composes with the partitioned step today."""
    cfg = lm_config(seq=seq, vocab=vocab, attn="blockwise", **model)
    sync = SyncTrainer(cfg, num_workers=workers, loss=LM_LOSS,
                       worker_optimizer="adam", learning_rate=1e-3,
                       batch_size=batch, num_epoch=2)
    data = datasets.lm_synth(workers * batch * steps, seq_len=seq,
                             vocab_size=vocab)
    with fresh_telemetry() as tel:
        variables = sync.train(data)
        step_s = steady_seconds(tel, steps)
    if device_sets(variables) != {workers}:
        raise AssertionError(
            f"SyncTrainer state on {device_sets(variables)} devices")
    return {
        "attn": "blockwise",
        "epoch_loss": check_losses("LM SyncTrainer",
                                   sync.history["epoch_loss"]),
        "steady_step_s": round(step_s, 4),
        "tokens_per_s_per_chip": round(batch * seq / step_s, 1),
        "bytes_in_use": chips_in_use(workers),
    }


def main() -> None:
    cache_dir = profiling.enable_compile_cache()
    profiling.require_tpu()
    device = profiling.device_record()
    meter = CompileMeter().install()
    print(json.dumps({"phase": "start", "device": device,
                      "compile_cache": cache_dir}), flush=True)

    with phase("kernels", meter, device) as rec:
        rec.update(kernel_facts(**KERNELS))
    with phase("kernels/decode", meter, device) as rec:
        rec.update(decode_kernel_facts(**DECODE_KERNEL))
    with phase("train/resnet50", meter, device) as rec:
        rec.update(train_resnet(**RESNET, **RESNET_TRAIN))
    with phase("train/lm", meter, device) as rec:
        facts, lm_cfg, lm_variables = train_lm(**LM, **LM_TRAIN)
        rec.update(facts)
    with phase("serve/lm", meter, device) as rec:
        rec.update(serve_lm(lm_cfg, lm_variables, **SERVE))
    with phase("serve/pools", meter, device) as rec:
        rec.update(pools_in_place(**POOLS))
    if device["count"] >= 4:
        with phase("four chips/ps-mesh", meter, device) as rec:
            rec.update(mesh_ps_on_chips(**RESNET, **MESH_PS))
        with phase("four chips/lm-sync", meter, device) as rec:
            rec.update(sync_lm_on_chips(**LM, **SYNC_LM))
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
