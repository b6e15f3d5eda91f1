"""Run cells one after another, each in a process of its own, and keep
what they print: for the builder's trials and the sets of six.

    python3 perfbench/tools/runs.py <tag> <workload>:<seed>:<seconds>:<trace> ...

The parent never touches JAX, so each child has the chip to itself.
Outputs go to ``chiprun_out/<tag>_<workload>_<seed>_t<trace>.{out,err}``;
a one-line summary of each run is printed as it ends.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    tag, specs = sys.argv[1], sys.argv[2:]
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    worst = 0
    for spec in specs:
        workload, seed, seconds, trace = spec.split(":")
        base = os.path.join(out_dir, f"{tag}_{workload}_{seed}_t{trace}")
        t0 = time.time()
        with open(base + ".out", "w") as out, open(base + ".err", "w") as err:
            rc = subprocess.call(
                [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
                 "--workload", workload, "--seed", seed, "--seconds", seconds,
                 "--trace", trace], stdout=out, stderr=err, cwd=REPO)
        worst = max(worst, rc)
        wall = time.time() - t0
        last = ""
        with open(base + ".out") as f:
            lines = f.read().strip().splitlines()
            last = lines[-1] if lines else ""
        try:
            line = json.loads(last)
            brief = {"correct": line["correct"],
                     "metrics": {k: round(v["value"], 4)
                                 for k, v in line["metrics"].items()},
                     "window": {k: (round(v, 3) if isinstance(v, float) else v)
                                for k, v in line.get("window", {}).items()},
                     "peak_gb": round(
                         line["device"]["memory_peak_bytes"] / 1e9, 3),
                     "stages": {k: (round(v, 2) if isinstance(v, float) else v)
                                for k, v in line.get("stages", {}).items()},
                     "checks": {c["name"]: c["value"]
                                for c in line.get("checks", [])}}
            for k in ("busy_s", "window_s"):
                if k in line["device"]:
                    brief[k] = round(line["device"][k], 4)
            if "breakdown" in line:
                brief["breakdown"] = line["breakdown"]
        except Exception:
            with open(base + ".err") as f:
                brief = {"no_result": f.read()[-1500:]}
        print(json.dumps({"run": spec, "rc": rc, "wall_s": round(wall, 1),
                          **brief}), flush=True)
    return worst


if __name__ == "__main__":
    sys.exit(main())
