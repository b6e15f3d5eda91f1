"""Observability report — summarize a telemetry run (metrics snapshot
+ trace-event timeline) into one text report.

Two modes:

* ``--metrics m.jsonl --trace t.json`` — summarize artifacts an
  earlier run wrote (``MetricsRegistry.write_jsonl`` /
  ``Tracer.write_chrome_trace``).  Either flag alone works.
* ``--smoke`` — self-contained end-to-end proof at tiny CPU shapes
  (the tier-1 registration, via test_examples.py's scripts-coverage
  check): enables telemetry, runs (1) a mixed-length ``DecodeEngine``
  workload and (2) an async host-PS training run over the REAL socket
  transport, writes both artifacts to ``--out-dir`` (a temp dir by
  default), asserts the report shows PS commit spans, per-worker round
  spans on distinct thread tracks, queue/occupancy gauges, a TTFT
  histogram and per-bucket compile counters — then prints the report.

The report sections: counters (sorted by value), gauges, histograms
(count / mean / p50 / p95 at bucket resolution), series (count + last),
and trace tracks (per-thread span rollup: which spans, how many, how
much wall time).
"""

from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import tempfile

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


# ---- summarizers -------------------------------------------------------

def _hist_percentile(buckets: dict, count: int, hi, q: float):
    need = q * count
    for edge, cum in buckets.items():
        if cum >= need:
            return float(edge)
    return hi


def summarize_metrics(path: str) -> list[str]:
    recs = [json.loads(line) for line in open(path) if line.strip()]
    by_kind: dict[str, list] = collections.defaultdict(list)
    for r in recs:
        by_kind[r["kind"]].append(r)
    lines = [f"== metrics ({len(recs)} series from {path}) =="]
    for r in sorted(by_kind.get("counter", ()),
                    key=lambda r: -r["value"]):
        lines.append(f"  counter    {r['key']:<58} {r['value']:g}")
    for r in sorted(by_kind.get("gauge", ()), key=lambda r: r["key"]):
        lines.append(f"  gauge      {r['key']:<58} {r['value']:g}")
    for r in sorted(by_kind.get("histogram", ()),
                    key=lambda r: r["key"]):
        n = r["count"]
        mean = r["sum"] / n if n else float("nan")
        p50 = _hist_percentile(r["buckets"], n, r["max"], 0.5)
        p95 = _hist_percentile(r["buckets"], n, r["max"], 0.95)
        lines.append(
            f"  histogram  {r['key']:<38} n={n} mean={mean:.4g} "
            f"p50<={p50:.4g} p95<={p95:.4g}")
    for r in sorted(by_kind.get("series", ()), key=lambda r: r["key"]):
        vals = r["values"]
        last = vals[-1] if vals else None
        lines.append(f"  series     {r['key']:<38} n={len(vals)} "
                     f"last={last!r}")
    return lines


def summarize_trace(path: str) -> list[str]:
    trace = json.load(open(path))
    events = trace["traceEvents"]
    names = {e["tid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    spans = [e for e in events if e.get("ph") == "X"]
    instants = [e for e in events if e.get("ph") == "i"]
    lines = [f"== trace ({len(spans)} spans, {len(instants)} instant "
             f"events, {len(names)} thread tracks from {path}) =="]
    by_tid: dict[int, list] = collections.defaultdict(list)
    for e in spans:
        by_tid[e["tid"]].append(e)
    for tid in sorted(by_tid):
        evs = by_tid[tid]
        per_name: dict[str, list] = collections.defaultdict(list)
        for e in evs:
            per_name[e["name"]].append(e["dur"])
        track = names.get(tid, str(tid))
        lines.append(f"  track {track} (tid {tid}):")
        for name, durs in sorted(per_name.items(),
                                 key=lambda kv: -sum(kv[1])):
            lines.append(
                f"    {name:<24} n={len(durs):<5} "
                f"total={sum(durs) / 1e6:.3f}s "
                f"mean={sum(durs) / len(durs) / 1e3:.2f}ms")
    per_instant = collections.Counter(e["name"] for e in instants)
    for name, n in per_instant.most_common():
        lines.append(f"  instant {name:<22} n={n}")
    return lines


def build_report(metrics_path: str | None,
                 trace_path: str | None) -> str:
    lines: list[str] = ["distkeras_tpu observability report"]
    if metrics_path:
        lines += summarize_metrics(metrics_path)
    if trace_path:
        lines += summarize_trace(trace_path)
    return "\n".join(lines)


# ---- the smoke run -----------------------------------------------------

def smoke_run(out_dir: str) -> tuple[str, str]:
    """Tiny engine + host-PS(socket) runs with telemetry on; returns
    (metrics_path, trace_path)."""
    import numpy as np

    from distkeras_tpu import telemetry
    from distkeras_tpu.data import datasets
    from distkeras_tpu.models import ModelSpec, model_config
    from distkeras_tpu.trainers import DOWNPOUR

    tel = telemetry.enable()

    # (1) mixed-length continuous-batching serving
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.serving import DecodeEngine

    spec = model_config("transformer_lm", (32,), input_dtype="int32",
                        vocab_size=61, num_layers=1, d_model=32,
                        num_heads=2, max_len=32, dtype="float32")
    model = ModelSpec.from_config(spec).build()
    variables = model.init(jax.random.key(0),
                           jnp.zeros((2, 32), jnp.int32))
    eng = DecodeEngine(model, variables, slots=3, buckets=[16, 32],
                       prefill_align=4, max_new_tokens=6)
    rng = np.random.default_rng(0)
    reqs = [{"prompt": rng.integers(0, 61, (t,)).astype(np.int32),
             "max_new_tokens": int(n)}
            for t, n in zip([5, 9, 3, 14, 7, 4], [6, 3, 5, 4, 2, 6])]
    list(eng.run(reqs))

    # (1b) one disaggregated prefill->decode handoff, so the report
    # surfaces the handoff counters (serving_kv_pages_shipped_total,
    # serving_handoff_requeue_total — the latter pre-touched at 0)
    from distkeras_tpu.gateway import EngineReplica, PrefillDecodeRouter

    def _pd_engine():
        return DecodeEngine(model, variables, slots=2, prefill_align=4,
                            max_new_tokens=6,
                            prefix_cache_bytes=1 << 22)

    with PrefillDecodeRouter(
            [EngineReplica(_pd_engine(), name="obs-p0")],
            [EngineReplica(_pd_engine(), name="obs-d0")],
            block_size=4) as router:
        rid = router.submit(rng.integers(0, 61, (12,)).astype(np.int32),
                            max_new_tokens=3)
        res = router.result(rid, timeout=120)
        assert res.get("error") is None, res

    # (2) async host-PS training over the real socket transport
    mlp = model_config("mlp", (8,), num_classes=4, hidden=(16,))
    data = datasets.synthetic_classification(512, (8,), 4, seed=0)
    t = DOWNPOUR(mlp, fidelity="host", transport="socket",
                 num_workers=2, communication_window=2, batch_size=16,
                 num_epoch=1, learning_rate=0.01,
                 worker_optimizer="adam")
    t.train(data)

    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    metrics_path = tel.metrics.write_jsonl(out / "metrics.jsonl")
    trace_path = tel.tracer.write_chrome_trace(out / "trace.json")
    telemetry.disable()
    return metrics_path, trace_path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--metrics", default=None,
                    help="metrics JSONL (MetricsRegistry.write_jsonl)")
    ap.add_argument("--trace", default=None,
                    help="Chrome trace JSON "
                         "(Tracer.write_chrome_trace)")
    ap.add_argument("--smoke", action="store_true",
                    help="run tiny engine + host-PS workloads and "
                         "report on their artifacts (tier-1 mode)")
    ap.add_argument("--out-dir", default=None,
                    help="--smoke artifact directory (temp default)")
    ap.add_argument("--out", default=None,
                    help="also write the report to this file")
    args = ap.parse_args()

    if args.smoke:
        out_dir = args.out_dir or tempfile.mkdtemp(prefix="dkt_obs_")
        args.metrics, args.trace = smoke_run(out_dir)
    elif not (args.metrics or args.trace):
        ap.error("pass --metrics and/or --trace, or --smoke")

    report = build_report(args.metrics, args.trace)

    if args.smoke:
        # the end-to-end exporter contract tier-1 pins: serving
        # metrics, per-bucket compile counters, PS commit spans and
        # per-worker round spans all visible in one report
        for needle in ("serving_ttft_seconds", "serving_queue_depth",
                       "serving_slot_occupancy", "compiles_total",
                       "ps_commits_total", "ps_commit",
                       "worker_round", "ps_wire_bytes_total",
                       "serving_inter_token_seconds",
                       "serving_kv_pages_shipped_total",
                       "serving_handoff_requeue_total"):
            assert needle in report, f"report lacks {needle}:\n{report}"
        trace = json.load(open(args.trace))
        commit_tids = {e["tid"] for e in trace["traceEvents"]
                       if e.get("ph") == "X"
                       and e["name"] == "ps_commit"}
        round_tids = {e["tid"] for e in trace["traceEvents"]
                      if e.get("ph") == "X"
                      and e["name"] == "worker_round"}
        # socket arm: commits land on PS handler threads — tracks
        # DISTINCT from the worker threads' round spans
        assert commit_tids and round_tids
        assert commit_tids.isdisjoint(round_tids), (commit_tids,
                                                    round_tids)
        report += "\nsmoke: ok"

    print(report)
    if args.out:
        pathlib.Path(args.out).write_text(report + "\n")


if __name__ == "__main__":
    main()
