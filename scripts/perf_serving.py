"""Serving A/B under mixed-length traffic: continuous batching vs
run-to-completion bucketed streaming (PERF.md §23).

Workload: ``--requests`` LM requests with prompt lengths drawn from the
``prefill_align`` grid in [--prompt-lo, --prompt-hi] and output budgets
drawn uniformly in [--new-lo, --new-hi]; ``--rate`` paces arrivals as a
Poisson process (default: full backlog at t=0, the saturated-server
throughput measurement).  Three arms over the SAME workload + params:

- ``baseline``  — ``StreamingGenerator`` (run-to-completion per-length
  buckets): every row decodes the GLOBAL --new-hi budget and finished
  rows drain with their batch;
- ``single``    — ``DecodeEngine`` with ONE max_len envelope: isolates
  the slot-refill win (finished rows evicted/replaced between steps,
  per-request budgets honored);
- ``bucketed``  — ``DecodeEngine`` with --buckets envelopes: adds the
  static-cache-law win (short requests pay a short envelope's step).

Reported per arm: aggregate goodput tokens/s (sum of REQUESTED output
tokens / wall), raw generated tokens/s, p50/p95 queue-to-first-token
and per-token completion latency.  All shapes are warmed up before the
timed run so compile time (the one-time cost; bounded per §23) never
pollutes the steady-state numbers.  Greedy; the smoke mode asserts the
continuous arms' tokens equal the baseline's per request.

Usage:  PYTHONPATH=/root/repo python scripts/perf_serving.py
        [--smoke] [--arms baseline,single,bucketed] [--rate 8]
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

import numpy as np


def build_workload(args):
    rng = np.random.default_rng(args.seed)
    grid = np.arange(args.prompt_lo, args.prompt_hi + 1,
                     args.prefill_align)
    grid = grid[grid + args.new_hi <= args.max_len]
    if len(grid) == 0:
        raise SystemExit("no prompt length fits max_len with --new-hi")
    lengths = rng.choice(grid, size=args.requests)
    budgets = rng.integers(args.new_lo, args.new_hi + 1,
                           size=args.requests)
    if args.rate:
        arrivals = np.cumsum(rng.exponential(1.0 / args.rate,
                                             size=args.requests))
    else:
        arrivals = np.zeros(args.requests)
    return [{"prompt": rng.integers(0, args.vocab,
                                    (int(t),)).astype(np.int32),
             "n_new": int(n), "arrival": float(a)}
            for t, n, a in zip(lengths, budgets, arrivals)]


def _percentiles(xs):
    return (round(float(np.percentile(xs, 50)), 4),
            round(float(np.percentile(xs, 95)), 4))


def run_baseline(spec, variables, work, args):
    """Run-to-completion bucketed streaming.  Completion times are the
    GENEROUS per-bucket-flush accounting (when the compiled flush
    returns), not in-order yield time."""
    from distkeras_tpu.streaming import StreamingGenerator

    sg = StreamingGenerator(spec, variables,
                            max_new_tokens=args.new_hi,
                            batch_size=args.baseline_batch)
    # warmup: compile every prompt-length bucket once (excluded)
    lengths = sorted({len(w["prompt"]) for w in work})
    warm = [{"prompt": next(w["prompt"] for w in work
                            if len(w["prompt"]) == t)}
            for t in lengths]
    list(sg(iter(warm)))

    t_flush: dict[int, float] = {}
    orig = sg._run_bucket

    def timed_bucket(items, n_flush):
        out = orig(items, n_flush)
        now = time.perf_counter() - t0
        for i, _ in items:
            t_flush[i] = now
        return out

    sg._run_bucket = timed_bucket
    t_consume: dict[int, float] = {}

    def paced_rows():
        for i, w in enumerate(work):
            wait = w["arrival"] - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            t_consume[i] = time.perf_counter() - t0
            yield {"prompt": w["prompt"], "i": i}

    t0 = time.perf_counter()
    n_done = sum(1 for _ in sg(paced_rows()))
    wall = time.perf_counter() - t0
    assert n_done == len(work)
    lat_first, lat_tok = [], []
    for i, w in enumerate(work):
        # run-to-completion: the first token is only observable when
        # the whole flush returns
        done = t_flush[i] - w["arrival"]
        lat_first.append(done)
        # per-token latency divides by tokens actually committed: the
        # baseline generator runs every request to the uniform new_hi
        lat_tok.append(done / args.new_hi)
    return {"wall_s": wall, "lat_first": lat_first, "lat_tok": lat_tok,
            "raw_tokens": len(work) * args.new_hi}


def run_continuous(spec, variables, work, args, buckets):
    from distkeras_tpu.serving import DecodeEngine

    eng = DecodeEngine(spec, variables, slots=args.slots,
                       buckets=buckets,
                       prefill_align=args.prefill_align,
                       steps_per_sync=args.steps_per_sync)
    # warmup: compile every (bucket, padded length) prefill the
    # workload can touch + every bucket's step program (excluded from
    # the timed run).  A length that fits several envelopes is routed
    # to each in turn by choosing a budget that overflows the smaller
    # ones.
    lengths = sorted({len(w["prompt"]) for w in work})
    warm, prev = [], 0
    for pool in eng._pools:
        for t in lengths:
            n = max(2, prev - t + 1)  # >=2: the step program runs too
            if t + n <= pool.env and eng._route(t, n).env == pool.env:
                warm.append({"prompt": np.zeros((t,), np.int32),
                             "max_new_tokens": n})
        prev = pool.env
    list(eng.run(warm))

    results = []
    t0 = time.perf_counter()
    i = 0
    while i < len(work) or eng.has_work():
        now = time.perf_counter() - t0
        while i < len(work) and work[i]["arrival"] <= now:
            eng.submit(work[i]["prompt"],
                       max_new_tokens=work[i]["n_new"],
                       request_id=i)
            i += 1
        if not eng.has_work():
            if i < len(work):
                time.sleep(max(0.0, work[i]["arrival"] - now))
            continue
        results.extend(eng.step())
    wall = time.perf_counter() - t0
    assert len(results) == len(work)
    lat_first, lat_tok, toks = [], [], {}
    for r in results:
        w = work[r["request_id"]]
        lat_first.append((r["t_first"] - t0) - w["arrival"])
        lat_tok.append(((r["t_finish"] - t0) - w["arrival"])
                       / max(len(r["tokens"]), 1))
        toks[r["request_id"]] = r["tokens"]
    return {"wall_s": wall, "lat_first": lat_first, "lat_tok": lat_tok,
            "raw_tokens": sum(w["n_new"] for w in work),
            "tokens": toks, "compiles": dict(eng.compile_counts)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU shapes + token-parity assertions "
                         "(the tier-1 registration)")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--kv-dtype", default="int8", choices=["int8", "none"])
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--prompt-lo", type=int, default=128)
    ap.add_argument("--prompt-hi", type=int, default=1024)
    ap.add_argument("--new-lo", type=int, default=16)
    ap.add_argument("--new-hi", type=int, default=256)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = full "
                         "backlog at t=0 (saturated throughput)")
    ap.add_argument("--slots", type=int, default=16,
                    help="continuous slots per bucket")
    ap.add_argument("--baseline-batch", type=int, default=16)
    ap.add_argument("--buckets", default="512,1024,2048",
                    help="envelope lengths for the bucketed arm")
    ap.add_argument("--prefill-align", type=int, default=128)
    ap.add_argument("--steps-per-sync", type=int, default=16,
                    help="decode steps per dispatch (raise through "
                         "high-RTT links; admission granularity)")
    ap.add_argument("--arms", default="baseline,single,bucketed")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable telemetry and dump a Perfetto-"
                         "loadable Chrome trace of the run here")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable telemetry and dump the metrics "
                         "registry as JSONL here")
    args = ap.parse_args()

    if args.smoke:
        # tiny CPU shapes; exercises Poisson pacing + all three arms
        args.layers, args.d_model, args.heads = 1, 32, 2
        args.kv_heads, args.kv_dtype, args.vocab = 1, "none", 61
        args.max_len, args.prompt_lo, args.prompt_hi = 32, 4, 12
        args.new_lo, args.new_hi, args.requests = 2, 6, 12
        args.slots, args.baseline_batch = 3, 3
        args.buckets, args.prefill_align = "16,32", 4
        args.steps_per_sync, args.rate = 2, 200.0
    else:
        from distkeras_tpu.profiling import enable_compile_cache

        enable_compile_cache()

    from distkeras_tpu.models import model_config, ModelSpec
    import jax
    import jax.numpy as jnp

    # telemetry consumer: enabled BEFORE engine construction so the
    # trace-time compile counters see every program.  Smoke always
    # enables it — tier-1 then exercises the instrumented serving
    # paths end to end.
    tel = None
    if args.trace or args.metrics or args.smoke:
        from distkeras_tpu import telemetry

        tel = telemetry.enable()

    spec = model_config(
        "transformer_lm", (args.max_len,), input_dtype="int32",
        vocab_size=args.vocab, num_layers=args.layers,
        d_model=args.d_model, num_heads=args.heads,
        max_len=args.max_len, dtype=args.dtype,
        num_kv_heads=args.kv_heads or None,
        kv_cache_dtype=None if args.kv_dtype == "none" else args.kv_dtype)
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((2, 8), jnp.int32))

    work = build_workload(args)
    goodput_tokens = sum(w["n_new"] for w in work)
    buckets = [int(b) for b in args.buckets.split(",")]
    arms = args.arms.split(",")
    out = {"metric": "lm_serving_mixed_traffic",
           "model": f"lm L{args.layers} d{args.d_model} "
                    f"kvh{args.kv_heads} {args.kv_dtype}",
           "requests": args.requests,
           "prompt": [args.prompt_lo, args.prompt_hi],
           "new": [args.new_lo, args.new_hi],
           "rate": args.rate, "slots": args.slots,
           "steps_per_sync": args.steps_per_sync,
           "goodput_tokens": int(goodput_tokens), "arms": {}}
    runs = {}
    for arm in arms:
        if arm == "baseline":
            runs[arm] = run_baseline(spec, variables, work, args)
        elif arm == "single":
            runs[arm] = run_continuous(spec, variables, work, args,
                                       [args.max_len])
        elif arm == "bucketed":
            runs[arm] = run_continuous(spec, variables, work, args,
                                       buckets)
        else:
            raise SystemExit(f"unknown arm {arm!r}")
        r = runs[arm]
        p50f, p95f = _percentiles(r["lat_first"])
        p50t, p95t = _percentiles(r["lat_tok"])
        out["arms"][arm] = {
            "wall_s": round(r["wall_s"], 3),
            "goodput_tok_s": round(goodput_tokens / r["wall_s"], 1),
            "raw_tok_s": round(r["raw_tokens"] / r["wall_s"], 1),
            "queue_to_first_p50_s": p50f,
            "queue_to_first_p95_s": p95f,
            "per_token_p50_s": p50t, "per_token_p95_s": p95t,
        }
        if "compiles" in r:
            out["arms"][arm]["n_programs"] = len(r["compiles"])

    if "baseline" in runs:
        base = out["arms"]["baseline"]["goodput_tok_s"]
        for arm in ("single", "bucketed"):
            if arm in runs:
                out["arms"][arm]["speedup_vs_baseline"] = round(
                    out["arms"][arm]["goodput_tok_s"] / base, 3)

    if tel is not None:
        # registry-side view of the same run: TTFT percentiles from
        # the histogram (bucket resolution), total generated tokens,
        # the bounded compiled-program set
        ttft = tel.metrics.histogram("serving_ttft_seconds")
        snap = tel.metrics.snapshot()
        out["telemetry"] = {
            "ttft_p50_s": ttft.percentile(0.5),
            "ttft_p95_s": ttft.percentile(0.95),
            "requests_finished": ttft.count,
            "tokens_total": tel.metrics.sum_counter(
                "serving_tokens_total"),
            "compiled_programs": sum(
                1 for k in snap["counters"]
                if k.startswith("compiles_total")),
        }
        if args.metrics:
            tel.metrics.write_jsonl(args.metrics)
        if args.trace:
            tel.tracer.write_chrome_trace(args.trace)

    if args.smoke:
        # greedy parity: each continuous arm's tokens are the
        # baseline generation truncated to the request's budget
        from distkeras_tpu.models import generate

        for i, w in enumerate(work):
            want = np.asarray(generate(
                model, variables, w["prompt"][None, :],
                max_new_tokens=w["n_new"]))[0, len(w["prompt"]):]
            for arm in ("single", "bucketed"):
                if arm in runs:
                    got = runs[arm]["tokens"][i]
                    assert np.array_equal(got, want), (arm, i, got,
                                                       want)
        # the registry saw the run: finished requests + live gauges
        assert out["telemetry"]["requests_finished"] > 0
        assert out["telemetry"]["tokens_total"] > 0
        assert any(k.startswith("serving_slot_occupancy")
                   for k in tel.metrics.snapshot()["gauges"])
        out["smoke_parity"] = "ok"
    print(json.dumps(out))


if __name__ == "__main__":
    main()
