"""Readings of a serving cell's ``served_logit_worst_gap`` for any of its
reference's spellings of ``precision``, a process for each seed:

    python3 perfbench/tools/served_readings.py <cell> <seconds> \\
        <precision>[,<precision> ...] <seed> [<seed> ...]

As ``readings.py`` (which reads the float8 control alone): the cell is
run as the benchmark runs it and its numbers are printed; then, for each
``precision``, the plain reference stands in the program's place at that
spelling (a lowered precision such as ``fp8``, or a fault that the
cell's reference knows how to plant, such as ``f32+no_routed`` of
``reference/mla_moe_hc.py``) and goes through the run's own comparison
and limits.  The program's line has to say ``correct`` true, a control's
or a fault's false.  The float32 pass that judges them all is computed
once.  Run on the chip; the benchmark's own runs never run this.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench import common, run as bench  # noqa: E402
from perfbench.kinds import serve  # noqa: E402


def readings(ctx, out, spellings):
    """``(spelling, Checks)`` for each of ``spellings``, read on the sample
    of the run ``out`` of ``serve.run(ctx)``."""
    _, reference, _, _ = ctx.arch
    L = out["layers"]
    whole, judge = reference.served_logits, {}

    def served_logits(cfg, seed, dtype, seqs, rows, precision="f32"):
        if precision != "f32":
            return whole(cfg, seed, dtype, seqs, rows, precision)
        if not judge:
            judge["f32"] = whole(cfg, seed, dtype, seqs, rows, "f32")
        return judge["f32"]

    reference.served_logits = served_logits
    try:
        for precision in spellings:
            checks = common.Checks(ctx.limits)
            serve.check_served(checks, reference, ctx.config, ctx.seed,
                               L["picked"], L["served"], precision=precision)
            yield precision, checks
    finally:
        reference.served_logits = whole


def main() -> int:
    cell_name, seconds, spellings, *seeds = sys.argv[1:]
    if len(seeds) > 1:      # a process for each seed: each has the chip alone
        return max(subprocess.call([sys.executable, __file__, *sys.argv[1:4],
                                    s]) for s in seeds)
    seed = int(seeds[0])
    bench.place_compile_cache()
    manifest = bench.load_manifest()
    cell = bench.find(manifest["workloads"], cell_name, "workload")
    peaks = bench.require_chips(cell["chips"])
    args = bench.types.SimpleNamespace(seed=seed, seconds=float(seconds),
                                       trace=0)
    ctx = bench.make_context(manifest, cell, args, peaks)
    ctx.t_process_start = common.now()
    out = serve.run(ctx)

    def show(reading, checks, **extra):
        print(json.dumps({"reading": reading, "seed": seed,
                          "cell": cell_name, "correct": checks.correct,
                          **extra, "checks": checks.rows}), flush=True)

    show("program", out["checks"], window=dict(out["end_to_end"]),
         stages=out["stages"])
    for precision, checks in readings(ctx, out, spellings.split(",")):
        show(precision, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
