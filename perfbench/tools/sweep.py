"""Find the knee of an open-loop serving cell, once: the highest rate the
engine sustains without a growing queue.

    python3 perfbench/tools/sweep.py <cell> <seconds> <seed> <rate> [<rate> ...]

A process for each rate; in it the cell's traffic is offered at that rate for
``seconds`` after its pre-roll, and the table in the README is printed:
queue at the close, occupancy, tails, tokens per second.  The cell's rate
is then fixed at about four fifths of the knee, in its traffic file.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import common, run as bench  # noqa: E402


def main() -> int:
    cell_name, seconds, seed = sys.argv[1], float(sys.argv[2]), int(sys.argv[3])
    rates = [float(r) for r in sys.argv[4:]]
    if len(rates) > 1:      # a process for each rate: each has the chip alone
        return max(subprocess.call([sys.executable, __file__, *sys.argv[1:4],
                                    str(r)]) for r in rates)
    bench.place_compile_cache()
    manifest = bench.load_manifest()
    cell = bench.find(manifest["workloads"], cell_name, "workload")
    peaks = bench.require_chips(cell["chips"])
    for rate in rates:
        args = bench.types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
        ctx = bench.make_context(manifest, cell, args, peaks)
        ctx.traffic = {**ctx.traffic, "rate_per_s": rate}
        ctx.t_process_start = common.now()
        kind = bench.importlib.import_module(
            f"perfbench.kinds.{ctx.traffic['kind']}")
        out = kind.run(ctx)
        L, e = out["layers"], out["end_to_end"]
        served = L["served"]
        queued_at_close = sum(
            1 for r in served.requests
            if r.index in served.submitted
            and served.submitted[r.index] <= L["t_close"]
            and (not served.deliveries(r.index)
                 or served.deliveries(r.index)[0] > L["t_close"]))
        print(json.dumps({
            "rate_per_s": rate, "requests": out["attempted"],
            "failed": out["failed"], "queued_at_close": queued_at_close,
            "occupancy_mean": round(e["occupancy_mean"], 2),
            "ttft_p50_ms": round(e["ttft_p50_ms"], 1),
            "ttft_p90_ms": round(e["ttft_p90_ms"], 1),
            "tpot_p50_ms": round(e["tpot_p50_ms"], 1),
            "tpot_p95_ms": round(e["tpot_p95_ms"], 1),
            "tokens_per_s": round(e["serve_tokens_per_s"], 1),
            "step_ms": round(1e3 * e["window_s"] / max(e["steps_in_window"], 1), 1),
            "correct": out["checks"].correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
