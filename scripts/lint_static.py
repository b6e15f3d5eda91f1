"""Concurrency & protocol static-analysis driver (ISSUE 9).

Runs all three analysis passes over the package and exits non-zero on
unsuppressed findings:

    python scripts/lint_static.py            # full lint, exit 2 on dirt
    python scripts/lint_static.py --smoke    # lint + seeded self-check
    python scripts/lint_static.py --metrics-out lint.json

Suppression is in-source (``# lint: allow(<rule>)`` on or above the
flagged line) or via the committed baseline ``scripts/lint_baseline.txt``
(``Finding.baseline_key`` lines — rule|path|message, line-number-free).
Suppressions that no longer match any finding are themselves reported
(rule ``dead-suppression``; report-only unless ``--strict-baseline``).

Finding counts are emitted as ``lint_findings_total{rule=...}`` through
the telemetry registry; ``--metrics-out`` writes the registry snapshot
as JSON.
"""

import argparse
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from distkeras_tpu import telemetry  # noqa: E402
from distkeras_tpu.analysis import (  # noqa: E402
    dead_suppressions,
    filter_suppressed,
    load_baseline,
    lockcheck,
    package_files,
    read_sources,
    surfaces,
)

BASELINE = REPO / "scripts" / "lint_baseline.txt"


def run_lint(baseline_path: pathlib.Path = BASELINE):
    """All passes -> (unsuppressed findings, counts-by-rule, stats).
    ``stats["dead"]`` carries the dead-suppression findings, reported
    separately so the caller decides whether they gate."""
    paths = package_files(REPO)
    sources = read_sources(REPO, paths)
    findings = lockcheck.analyze_paths(REPO, paths)
    findings += surfaces.check_all(REPO, paths)
    kept, n_allowed = filter_suppressed(findings, sources)
    baseline = load_baseline(baseline_path)
    final = [f for f in kept if f.baseline_key() not in baseline]
    n_baselined = len(kept) - len(final)
    dead = dead_suppressions(findings, sources, baseline)
    counts: dict[str, int] = {}
    for f in final + dead:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    stats = {"files": len(paths), "raw": len(findings),
             "allowed": n_allowed, "baselined": n_baselined,
             "dead": dead}
    return final, counts, stats


def emit_metrics(counts, out_path=None):
    reg = telemetry.MetricsRegistry()
    total = reg.counter("lint_findings_total")
    total.inc(0)
    for rule, n in sorted(counts.items()):
        reg.counter("lint_findings_total", rule=rule).inc(n)
        total.inc(n)
    if out_path:
        pathlib.Path(out_path).write_text(
            json.dumps(reg.snapshot(), indent=2, sort_keys=True,
                       default=str))
    return reg


def self_check() -> list[str]:
    """Seeded-violation fixtures: every rule must fire on a source
    snippet that violates it — a broken analyzer fails loudly here
    rather than passing silently forever."""
    failures = []

    def expect(rules, got, label):
        got_rules = {f.rule for f in got}
        missing = set(rules) - got_rules
        if missing:
            failures.append(f"{label}: expected {sorted(missing)}, "
                            f"got {sorted(got_rules)}")

    expect([lockcheck.RULE_BLOCKING], lockcheck.analyze_source(
        "import threading, time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def bad(self):\n"
        "        with self._lock:\n"
        "            time.sleep(1)\n"), "blocking-under-lock")
    expect([lockcheck.RULE_ORDER], lockcheck.analyze_source(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def one(self):\n"
        "        with self._a:\n"
        "            with self._b: pass\n"
        "    def two(self):\n"
        "        with self._b:\n"
        "            with self._a: pass\n"), "lock-order-inversion")
    expect([lockcheck.RULE_GUARDED], lockcheck.analyze_source(
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0  # guarded-by: _lock\n"
        "    def bad(self):\n"
        "        self._n = 1\n"), "guarded-write")
    s = surfaces.extract_source(
        'm.counter("bogus_metric_zzz").inc()', "fixture.py")
    expect([surfaces.RULE_METRIC],
           surfaces.check_docs(s, docs="(empty)"), "undoc-metric")
    from distkeras_tpu.analysis import Finding, RULE_DEAD
    fixture_src = ("x = 1  # lint: allow(bogus-rule)\n"
                   "y = 2\n")
    dead = dead_suppressions(
        [Finding("other-rule", "fixture.py", 2, "m")],
        {"fixture.py": fixture_src.splitlines()},
        {"stale-rule|gone.py|old message"})
    expect([RULE_DEAD, RULE_DEAD], dead, "dead-suppression")
    if len(dead) != 2:
        failures.append(f"dead-suppression: expected a dead allow "
                        f"AND a dead baseline entry, got {dead}")
    s = surfaces.extract_source(
        'transport.send_msg(sock, b"Z")', "fixture.py",
        wire_scope="ps")
    expect([surfaces.RULE_OPCODE], surfaces.check_opcodes(s),
           "unregistered-opcode")
    s = surfaces.extract_source(
        'TIERS = {"bogus_tier": None}', "fixture.py")
    expect([surfaces.RULE_TIER],
           surfaces.check_docs(s, docs="(empty)"), "undoc-tier")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="full lint + seeded-violation self-check")
    ap.add_argument("--baseline", default=str(BASELINE))
    ap.add_argument("--strict-baseline", action="store_true",
                    help="exit 2 on dead suppressions (baseline "
                         "entries / allow comments matching nothing)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the telemetry registry snapshot here")
    args = ap.parse_args(argv)

    findings, counts, stats = run_lint(pathlib.Path(args.baseline))
    emit_metrics(counts, args.metrics_out)

    for f in findings:
        print(f)
    dead = stats["dead"]
    for f in dead:
        print(f"{f}{'' if args.strict_baseline else '  (report-only)'}")
    print(f"lint_static: {stats['files']} files, "
          f"{len(findings)} unsuppressed finding(s) "
          f"({stats['allowed']} allowed in-source, "
          f"{stats['baselined']} baselined, "
          f"{len(dead)} dead suppression(s))")

    if args.smoke:
        failures = self_check()
        if failures:
            for msg in failures:
                print(f"SELF-CHECK FAILED: {msg}")
            return 1
        print("lint_static: self-check OK (all rules fire on seeded "
              "violations)")

    if findings:
        return 2
    return 2 if (args.strict_baseline and dead) else 0


if __name__ == "__main__":
    sys.exit(main())
