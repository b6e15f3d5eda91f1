"""Readings for the limits of "How correct is decided": the program's, the
control's and the planted faults', a process for each seed.

    python3 perfbench/tools/readings.py <cell> <seconds> <seed> [<seed> ...]

For each seed the cell is run as the benchmark runs it (a short window at
the cell's own load) and its numbers are printed; then the control, which
is the plain reference put in the program's place and computed with every
matmul operand rounded to float8_e4m3fn, the precision below bfloat16;
and, for a training cell, the reference with half of each batch left out.
Each goes through the run's own comparison and limits and prints whether
it came out ``correct``: the program's lines have to say true, the
others false.  Run on the chip; the benchmark's own runs never run this.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import common, run as bench  # noqa: E402


def main() -> int:
    cell_name, seconds, seeds = sys.argv[1], float(sys.argv[2]), sys.argv[3:]
    if len(seeds) > 1:      # a process for each seed: each has the chip alone
        return max(subprocess.call([sys.executable, __file__, *sys.argv[1:3],
                                    s]) for s in seeds)
    bench.place_compile_cache()
    manifest = bench.load_manifest()
    cell = bench.find(manifest["workloads"], cell_name, "workload")
    peaks = bench.require_chips(cell["chips"])
    for seed in seeds:
        args = bench.types.SimpleNamespace(seed=int(seed), seconds=seconds,
                                           trace=0)
        ctx = bench.make_context(manifest, cell, args, peaks)
        ctx.t_process_start = common.now()
        kind = bench.importlib.import_module(
            f"perfbench.kinds.{ctx.traffic['kind']}")
        out = kind.run(ctx)

        def show(reading, checks, **extra):
            print(json.dumps({"reading": reading, "seed": int(seed),
                              "cell": cell_name, "correct": checks.correct,
                              **extra, "checks": checks.rows}), flush=True)

        show("program", out["checks"],
             window={k: v for k, v in out["end_to_end"].items()})
        _, reference, _, _ = ctx.arch
        L = out["layers"]
        # the control and the faults go through the run's own comparison
        # and limits, and have to come out as not correct
        if ctx.traffic["kind"] == "serve":
            checks = common.Checks(ctx.limits)
            kind.check_served(checks, reference, ctx.config, ctx.seed,
                              L["picked"], L["served"], precision="fp8")
            show("control_fp8", checks)
        else:
            lr = ctx.traffic["trainer_args"]["learning_rate"]
            for reading, kw in (("control_fp8", {"precision": "fp8"}),
                                ("fault_half_batch",
                                 {"fault": "half_batch"})):
                got = reference.train_readings(ctx.config, ctx.seed,
                                               L["batches"], lr, **kw)
                checks = common.Checks(ctx.limits)
                kind.compare(got, L["reference"], checks)
                show(reading, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
