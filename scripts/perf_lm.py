"""TransformerLM training throughput on one chip (PERF.md §13).

The ResNet-50 number is the BASELINE.md flagship; this records the
transformer side — tokens/sec and analytic MFU for a GPT-2-small-shaped
``TransformerLM`` — so the long-context family has a measured baseline
too.  MFU uses the standard 6 * params * tokens training-FLOPs
estimate (PaLM appendix convention; attention FLOPs reported
separately), against the chip's bf16 peak.

Usage:  PYTHONPATH=/root/repo python scripts/perf_lm.py
        [--layers 12 --d-model 768 --seq-len 1024 --batch 8]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.profiling import (enable_compile_cache, host_sync,
                                     peak_flops)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--attn",
                    choices=["auto", "dense", "blockwise", "flash"],
                    default="dense",
                    help="'blockwise': device-local flash-style "
                         "attention (online-softmax q-chunks, no "
                         "[T,T] materialization) — the long-T lever "
                         "PERF.md §13 measures.  'flash': the same "
                         "algorithm as hand-written Pallas kernels "
                         "(ops.attention, PERF.md §17)")
    ap.add_argument("--q-chunk", type=int, default=128,
                    help="q block length for --attn blockwise; for "
                         "--attn flash the kernel's measured default "
                         "blocks (512/1024) are used")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize each block in the backward "
                         "(jax.checkpoint): ~1 extra forward of FLOPs "
                         "for O(layers) less activation memory")
    ap.add_argument("--experts", type=int, default=0,
                    help=">0 swaps every block's FFN for a top-1 "
                         "Switch MoE with this many experts (dense "
                         "einsum form; runs replicated on one chip).  "
                         "MFU is computed on ACTIVE params (one "
                         "expert per token), the number that tracks "
                         "useful work")
    args = ap.parse_args()

    from distkeras_tpu.models import ModelSpec, model_config
    from distkeras_tpu.workers import (TrainState, make_train_step,
                                       resolve_optimizer)

    spec = model_config(
        "transformer_lm", (args.seq_len,), input_dtype="int32",
        vocab_size=args.vocab, num_layers=args.layers,
        d_model=args.d_model, num_heads=args.heads,
        max_len=args.seq_len, dtype="bfloat16",
        num_experts=args.experts,
        remat_blocks=args.remat,
        attn=args.attn if args.attn in ("auto", "dense") else "auto",
        blockwise_attn=args.attn == "blockwise",
        flash_attn=args.attn == "flash",
        attn_q_chunk=(args.q_chunk if args.attn == "blockwise"
                      else None))
    model = ModelSpec.from_config(spec).build()
    tx = resolve_optimizer("adam", 3e-4)
    tokens = jnp.zeros((args.batch, args.seq_len), jnp.int32)
    variables = model.init(jax.random.key(0), tokens[:2])
    n_params = sum(x.size for x in
                   jax.tree_util.tree_leaves(variables["params"]))
    state = TrainState.create(variables, tx, jax.random.key(1))
    step = jax.jit(make_train_step(
        model, "sparse_categorical_crossentropy", tx),
        donate_argnums=0)
    batch = {"features": tokens, "label": tokens}

    for _ in range(3):
        state, metrics = step(state, batch)
    host_sync(metrics)
    t0 = time.perf_counter()
    for _ in range(args.reps):
        state, metrics = step(state, batch)
    val = host_sync(metrics)
    dt = (time.perf_counter() - t0) / args.reps

    toks = args.batch * args.seq_len
    # 6ND (fwd 2ND + bwd 4ND) + attention term 12*L*d*T^2 (fwd+bwd).
    # MoE: count ACTIVE params — top-1 routing touches one expert's
    # FFN per token, so (E-1) experts' FFN weights are excluded.
    n_active = n_params
    if args.experts > 1:
        per_expert_ffn = 2 * args.d_model * (args.d_model
                                             * 4) + args.d_model * 5
        n_active -= (args.experts - 1) * args.layers * per_expert_ffn
    flops_param = 6.0 * n_active * toks
    flops_attn = (12.0 * args.layers * args.d_model
                  * args.seq_len * args.seq_len * args.batch)
    peak, known = peak_flops(jax.devices()[0])
    print(json.dumps({
        "model": f"lm L{args.layers} d{args.d_model} T{args.seq_len}",
        "attn": args.attn,
        "experts": args.experts,
        "params_active_m": round(n_active / 1e6, 1),
        "params_m": round(n_params / 1e6, 1),
        "step_ms": round(dt * 1e3, 2),
        "tokens_per_sec": round(toks / dt, 1),
        "mfu_6nd": (round(flops_param / dt / peak, 4)
                    if known else None),
        "mfu_with_attn": (round((flops_param + flops_attn) / dt / peak,
                                4) if known else None),
        "loss_finite": bool(np.isfinite(val)),
    }))


if __name__ == "__main__":
    main()
