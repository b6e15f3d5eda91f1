"""Protocol model-checker CI gate (ISSUE 11).

Exhaustively explores the replicated-PS election/fencing/replication
protocol (``analysis.protomodel`` over ``analysis.modelcheck``) and
exits 2 on any invariant violation — or on a mutation-harness miss,
because a checker that can't catch known-unsafe mutants proves
nothing:

    python scripts/check_protocol.py             # all scenarios, full
    python scripts/check_protocol.py --scenario rewind
    python scripts/check_protocol.py --mutate    # every mutant must
                                                 # yield a replayable
                                                 # counterexample
    python scripts/check_protocol.py --smoke     # tier-1: small clean
                                                 # sweep + 2 mutants
    python scripts/check_protocol.py --replay "<schedule tokens>" \
        --scenario rewind --with-mutant skip-rewind

``modelcheck_states_explored_total`` / ``modelcheck_violations_total
{invariant=...}`` are emitted through the telemetry registry;
``--metrics-out`` writes the snapshot as JSON.
"""

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from distkeras_tpu import telemetry  # noqa: E402
from distkeras_tpu.analysis import modelcheck, protomodel  # noqa: E402

#: --smoke trims every scenario's bounds to keep tier-1 fast; the
#: rewind scenario still reaches its seeded divergence window.
SMOKE_BOUNDS = {"max_depth": 10, "max_states": 3_000}
SMOKE_MUTANTS = ("no-quorum", "no-dedupe-repl")


def run_clean(names, bounds_override=None) -> int:
    """Explore scenarios expecting ZERO violations; returns rc."""
    rc = 0
    for name in names:
        model, bounds = protomodel.build(name)
        if bounds_override:
            bounds = {**bounds, **bounds_override}
        t0 = time.perf_counter()
        rep = modelcheck.Explorer(model, **bounds).run()
        dt = time.perf_counter() - t0
        status = "ok" if rep.violation is None else "VIOLATION"
        print(f"scenario {name}: {status} — {rep.states} states, "
              f"{rep.executions} executions, {rep.truncated} at "
              f"bound, depth<={bounds['max_depth']}, {dt:.2f}s")
        if rep.violation is not None:
            print(f"  {rep.violation}")
            rc = 2
    return rc


def run_mutants(muts, bounds_override=None) -> int:
    """Every known-unsafe mutant must produce a minimized,
    schedule-replayable counterexample breaking the EXPECTED
    invariant; anything less is a checker failure."""
    rc = 0
    for mut in muts:
        desc, scen, want = protomodel.MUTANTS[mut]
        model, bounds = protomodel.build(scen, mutants=[mut])
        if bounds_override:
            bounds = {**bounds, **bounds_override}
        explorer = modelcheck.Explorer(model, **bounds)
        t0 = time.perf_counter()
        rep = explorer.run()
        dt = time.perf_counter() - t0
        v = rep.violation
        if v is None:
            print(f"mutant {mut} ({scen}): MISSED — no "
                  f"counterexample in {rep.states} states ({dt:.2f}s)")
            rc = 2
            continue
        # the explorer replay-verifies during minimization; verify
        # once more from the printed string — the artifact a human
        # would paste into --replay
        rv = explorer.replay(v.schedule)
        replayed = (rv is not None and rv.invariant == v.invariant
                    and rv.schedule == v.schedule)
        ok = v.invariant == want and replayed
        print(f"mutant {mut} ({scen}): "
              f"{'caught' if ok else 'WRONG'} — {v.invariant} at "
              f"depth {v.depth} (want {want}, replay "
              f"{'ok' if replayed else 'FAILED'}), {rep.states} "
              f"states, {dt:.2f}s")
        print(f"  guard flipped: {desc}")
        print(f"  schedule: {v.schedule}")
        if not ok:
            rc = 2
    return rc


def run_replay(scenario: str, mutants, schedule: str) -> int:
    model, _ = protomodel.build(scenario, mutants=mutants)
    v = modelcheck.Explorer(model).replay(schedule)
    if v is None:
        print("replay: schedule runs clean (no violation)")
        return 0
    print(f"replay: {v}")
    return 2


def emit_metrics(out_path) -> None:
    if out_path:
        pathlib.Path(out_path).write_text(
            json.dumps(telemetry.metrics().snapshot(), indent=2,
                       sort_keys=True, default=str))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default=None,
                    choices=sorted(protomodel.SCENARIOS),
                    help="explore one scenario (default: all)")
    ap.add_argument("--mutate", action="store_true",
                    help="mutation harness: every known-unsafe "
                         "mutant must be caught")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 subset: trimmed clean sweep + "
                         f"mutants {', '.join(SMOKE_MUTANTS)}")
    ap.add_argument("--replay", default=None, metavar="SCHEDULE",
                    help="re-execute a schedule string against "
                         "--scenario (+ --with-mutant)")
    ap.add_argument("--with-mutant", action="append", default=[],
                    choices=sorted(protomodel.MUTANTS),
                    help="apply a mutant during --replay")
    ap.add_argument("--max-depth", type=int, default=None)
    ap.add_argument("--max-states", type=int, default=None)
    ap.add_argument("--metrics-out", default=None,
                    help="write the telemetry registry snapshot here")
    args = ap.parse_args(argv)

    telemetry.enable()  # the explorer's counters need a live registry
    override = {}
    if args.max_depth is not None:
        override["max_depth"] = args.max_depth
    if args.max_states is not None:
        override["max_states"] = args.max_states

    if args.replay:
        if not args.scenario:
            ap.error("--replay needs --scenario")
        rc = run_replay(args.scenario, args.with_mutant, args.replay)
    elif args.smoke:
        rc = run_clean(sorted(protomodel.SCENARIOS),
                       {**SMOKE_BOUNDS, **override})
        rc = max(rc, run_mutants(SMOKE_MUTANTS, override))
        if rc == 0:
            print("check_protocol: smoke OK (clean sweep at smoke "
                  "bounds; every smoke mutant caught + replayed)")
    elif args.mutate:
        rc = run_mutants(sorted(protomodel.MUTANTS), override)
        if rc == 0:
            print(f"check_protocol: all {len(protomodel.MUTANTS)} "
                  "mutants caught with replayable counterexamples")
    else:
        names = [args.scenario] if args.scenario else sorted(
            protomodel.SCENARIOS)
        rc = run_clean(names, override)
        if rc == 0:
            print(f"check_protocol: {len(names)} scenario(s) "
                  "explored to their bounds, zero violations")

    emit_metrics(args.metrics_out)
    return rc


if __name__ == "__main__":
    sys.exit(main())
