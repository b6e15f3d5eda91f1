"""Serving throughput for the LM family: KV-cache autoregressive
decode (PERF.md §18).

Measures the two numbers that characterize the serving path on one
chip for a GPT-2-small-shaped ``TransformerLM``:

- **prefill**: one forward over the prompt that fills every layer's
  KV cache (compute-bound, ~the training forward);
- **decode**: per-token latency of the T=1 cached step inside
  ``lax.scan`` (bandwidth-bound: every weight is read per token), and
  the resulting tokens/s at the given batch.

Usage:  PYTHONPATH=/root/repo python scripts/perf_decode.py
        [--layers 12 --d-model 768 --prompt 512 --new 128 --batch 8]
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
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--max-len", type=int, default=1024)
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--new-lo", type=int, default=32)
    ap.add_argument("--new-hi", type=int, default=160)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="GQA: number of K/V heads (divides --heads); "
                         "shrinks the per-token KV-cache read by the "
                         "group factor (PERF.md §18 addendum)")
    ap.add_argument("--kv-dtype", default=None,
                    choices=["int8"],
                    help="int8: quantized KV cache (halves the bf16 "
                         "cache's per-token HBM traffic)")
    ap.add_argument("--attn", default="auto",
                    choices=["auto", "dense", "blockwise", "flash"],
                    help="prefill attention spelling (decode keeps "
                         "it for 128-aligned prompt chunks)")
    ap.add_argument("--prompt-lo", type=int, default=None,
                    help="with --prompt-hi: measure PREFILL marginal "
                         "cost by differencing two prompt lengths at "
                         "fixed new tokens (the §18 flash-prefill "
                         "row); skips the decode measurement")
    ap.add_argument("--prompt-hi", type=int, default=None)
    args = ap.parse_args()

    from distkeras_tpu.models import ModelSpec, generate, model_config

    spec = model_config(
        "transformer_lm", (args.max_len,), input_dtype="int32",
        vocab_size=args.vocab, num_layers=args.layers,
        d_model=args.d_model, num_heads=args.heads,
        max_len=args.max_len, dtype=args.dtype, attn=args.attn,
        num_kv_heads=args.kv_heads, kv_cache_dtype=args.kv_dtype)
    model = ModelSpec.from_config(spec).build()
    tokens = jnp.zeros((args.batch, args.max_len), jnp.int32)
    variables = model.init(jax.random.key(0), tokens[:, :8])
    n_params = sum(x.size for x in
                   jax.tree_util.tree_leaves(variables["params"]))
    prompt = jax.random.randint(jax.random.key(1),
                                (args.batch, args.prompt), 0,
                                args.vocab)

    if args.prompt_lo is not None or args.prompt_hi is not None:
        if not (args.prompt_lo and args.prompt_hi):
            raise SystemExit("--prompt-lo and --prompt-hi go together")
        # prefill marginal cost: t(prompt_hi) - t(prompt_lo) at fixed
        # new tokens — the dispatch overhead and the decode tail
        # cancel, leaving the prefill cost of the extra tokens.  With
        # --attn flash/auto the 128-aligned prompt runs the Pallas
        # kernels; --attn dense is the round-4 O(T·max_len) cache read.
        def timed_prompt(t_len):
            p = jax.random.randint(jax.random.key(1),
                                   (args.batch, t_len), 0, args.vocab)
            f = jax.jit(lambda v, p: generate(model, v, p,
                                              max_new_tokens=8))
            host_sync(f(variables, p))
            t0 = time.perf_counter()
            for _ in range(args.reps):
                host_sync(f(variables, p))
            return (time.perf_counter() - t0) / args.reps

        t_lo = timed_prompt(args.prompt_lo)
        t_hi = timed_prompt(args.prompt_hi)
        extra = args.prompt_hi - args.prompt_lo
        print(json.dumps({
            "metric": "lm_prefill_marginal",
            "attn": args.attn,
            "model": f"lm L{args.layers} d{args.d_model} b{args.batch}",
            "prompt_lo": args.prompt_lo, "prompt_hi": args.prompt_hi,
            "prefill_ms_for_extra": round((t_hi - t_lo) * 1e3, 2),
            "prefill_us_per_token": round(
                (t_hi - t_lo) / extra / args.batch * 1e6, 2),
            "t_lo_ms": round(t_lo * 1e3, 2),
            "t_hi_ms": round(t_hi * 1e3, 2),
        }))
        return

    # Per-token decode cost by DIFFERENCING two generation lengths:
    # t(new_hi) - t(new_lo) cancels the prompt prefill AND the
    # per-dispatch overhead, so only the differenced per-token cost is
    # reported (no prefill/total latency).
    def timed(n_new):
        f = jax.jit(lambda v, p: generate(model, v, p,
                                          max_new_tokens=n_new))
        host_sync(f(variables, prompt))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            host_sync(f(variables, prompt))
        return (time.perf_counter() - t0) / args.reps

    t_lo = timed(args.new_lo)
    t_hi = timed(args.new_hi)
    per_tok = (t_hi - t_lo) / (args.new_hi - args.new_lo)
    # decode is bandwidth-bound: each token reads every parameter once
    # (f32 param storage; compute casts to the model dtype)
    hbm_gbs = n_params * 4 / per_tok / 1e9
    peak, known = peak_flops(jax.devices()[0])
    print(json.dumps({
        "model": f"lm L{args.layers} d{args.d_model} "
                 f"prompt{args.prompt} new{args.new_lo}->"
                 f"{args.new_hi} b{args.batch}",
        "params_m": round(n_params / 1e6, 1),
        "per_token_ms": round(per_tok * 1e3, 3),
        "decode_tokens_per_sec": round(args.batch / per_tok, 1),
        "weight_read_gb_per_sec": round(hbm_gbs, 1),
        "mfu_decode": (round(2.0 * n_params * args.batch / per_tok
                             / peak, 4) if known else None),
        "t_lo_ms": round(t_lo * 1e3, 2),
        "t_hi_ms": round(t_hi * 1e3, 2),
    }))


if __name__ == "__main__":
    main()
