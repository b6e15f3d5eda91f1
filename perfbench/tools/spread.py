"""Medians and spreads of sets of runs kept by ``runs.py``.

    python3 perfbench/tools/spread.py <tag> [<tag> ...]

A spread is the distance between the first and the third quartile, as
``statistics.quantiles(values, n=4)`` gives them, as a share of the
median: the contract's measure for a bound.
"""

import glob
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(tag: str) -> dict:
    """``{workload: [result line, ...]}`` of the runs kept under ``tag``."""
    out: dict = {}
    for path in sorted(glob.glob(os.path.join(
            REPO, "chiprun_out", f"{tag}_*_t0.out"))):
        with open(path) as f:
            lines = f.read().strip().splitlines()
        if not lines:
            continue
        try:
            line = json.loads(lines[-1])
        except ValueError:
            continue
        name = os.path.basename(path)[len(tag) + 1:].rsplit("_", 2)[0]
        out.setdefault(name, []).append(line)
    return out


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    for tag in sys.argv[1:]:
        for workload, lines in load(tag).items():
            row = {"tag": tag, "workload": workload, "runs": len(lines),
                   "correct": sum(l["correct"] for l in lines)}
            names = list(lines[0]["metrics"]) + [
                k for k in ("tokens_in_window", "occupancy_mean",
                            "steps_in_window", "ttft_p50_ms", "tpot_p50_ms",
                            "serve_tokens_per_s", "lateness_p95_ms")
                if k in lines[0].get("window", {})]
            for k in names:
                vals = [l["metrics"][k]["value"] if k in l["metrics"]
                        else l["window"][k] for l in lines]
                row[k] = {"median": round(statistics.median(vals), 4),
                          "spread": round(spread(vals), 5)
                          if len(vals) >= 2 else None,
                          "values": [round(v, 3) for v in vals]}
            print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
