"""Protocol model checker (ISSUE 11): the explorer's scheduling
semantics on toy models (Choose forking, Recv FIFO blocking, timer and
crash budgets, footprint POR, state dedup, preemption bounding, replay
byte-for-byte, counterexample minimization), then the real protocol
models: every scenario explores clean at smoke bounds and every seeded
unsafe mutant yields a minimized, replayable counterexample breaking
exactly the invariant the mutant table predicts."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from distkeras_tpu.analysis import protomodel
from distkeras_tpu.analysis.modelcheck import (Choose, Explorer, Model,
                                               Recv, Step, Timer, check)

REPO = Path(__file__).resolve().parent.parent

# the scenario bounds used by ``scripts/check_protocol.py --smoke`` —
# tier-1-sized; the full bounds run via the script's default mode
SMOKE = {"max_depth": 10, "max_states": 3_000}


class W:
    """Tiny dict-backed world with the fingerprint the explorer needs."""

    def __init__(self, **kw):
        self.d = dict(kw)

    def fingerprint(self):
        return tuple(sorted(self.d.items()))


# ---- explorer semantics on toy models --------------------------------

def test_choose_forks_every_option():
    """Each Choose option becomes its own branch; the chosen value is
    sent back into the generator."""
    def actor(ctx):
        got = yield Choose("pick", ["a", "b", "c"])
        ctx.world.d.setdefault("seen", set()).add(got)
        ctx.world.d["last"] = got
        yield Step("after")

    picks = set()

    def spy(w):
        if "last" in w.d:
            picks.add(w.d["last"])
        return None

    m = Model(lambda: W()).actor("p", actor).invariant("spy", spy)
    rep = check(m, max_depth=4)
    assert rep.violation is None
    assert picks == {"a", "b", "c"}


def test_recv_blocks_until_send_and_is_fifo():
    """Recv disables the actor while the channel is empty; messages
    arrive in send order."""
    def producer(ctx):
        yield Step("p1")
        ctx.send("ch", 1)
        yield Step("p2")
        ctx.send("ch", 2)

    def consumer(ctx):
        a = yield Recv("ch")
        b = yield Recv("ch")
        ctx.world.d["got"] = (a, b)

    orders = set()

    def spy(w):
        if "got" in w.d:
            orders.add(w.d["got"])
        return None

    m = (Model(lambda: W()).actor("prod", producer)
         .actor("cons", consumer).invariant("spy", spy))
    rep = check(m, max_depth=8)
    assert rep.violation is None
    assert orders == {(1, 2)}  # FIFO: never (2, 1)


def test_timer_budget_bounds_firings():
    """A Timer fires at most ``timer_budget`` times per execution."""
    def ticker(ctx):
        while True:
            yield Timer("tick")
            ctx.world.d["fires"] = ctx.world.d.get("fires", 0) + 1

    seen = set()

    def spy(w):
        seen.add(w.d.get("fires", 0))
        return None

    m = Model(lambda: W()).actor("t", ticker).invariant("spy", spy)
    m.timer_budget = 2
    rep = check(m, max_depth=10)
    assert rep.violation is None
    assert seen == {0, 1, 2}  # never a third firing


def test_crash_budget_and_hook():
    """crash:<name> transitions appear only while budget remains; the
    on_crash hook gets the ctx and mutates the world."""
    def actor(ctx):
        while True:
            yield Step("work")

    def on_crash(ctx):
        ctx.world.d["crashed"] = True

    crash_worlds = set()

    def spy(w):
        crash_worlds.add(w.d.get("crashed", False))
        return None

    m = (Model(lambda: W()).actor("a", actor).invariant("spy", spy)
         .allow_crash("a", on_crash, budget=1))
    rep = check(m, max_depth=5)
    assert rep.violation is None
    assert crash_worlds == {False, True}


def test_footprint_por_prunes_disjoint_actors():
    """Two actors with disjoint static footprints commute — POR must
    explore far fewer executions than the full interleaving product,
    without losing the invariant check."""
    def writer(key):
        def fn(ctx):
            for _ in range(3):
                yield Step(f"w:{key}", footprint=[key])
                ctx.world.d[key] = ctx.world.d.get(key, 0) + 1
        return fn

    def build(with_footprints):
        def mk(key):
            def fn(ctx):
                for _ in range(3):
                    yield Step(
                        f"w:{key}",
                        footprint=[key] if with_footprints else None)
                    ctx.world.d[key] = ctx.world.d.get(key, 0) + 1
            return fn
        return (Model(lambda: W()).actor("x", mk("x"))
                .actor("y", mk("y"))
                .invariant("bounded",
                           lambda w: None if w.d.get("x", 0) <= 3
                           else "x overflow"))

    por = check(build(True), max_depth=10)
    full = check(build(False), max_depth=10)
    assert por.violation is None and full.violation is None
    assert por.executions < full.executions


def test_state_dedup_collapses_diamonds():
    """Confluent interleavings reconverge; dedup prunes the rejoin."""
    def inc(key):
        def fn(ctx):
            yield Step(f"i:{key}")
            ctx.world.d[key] = 1
        return fn

    m = (Model(lambda: W()).actor("a", inc("a")).actor("b", inc("b")))
    rep = check(m, max_depth=6)
    assert rep.violation is None
    assert rep.pruned_dedup >= 1  # a=1,b=1 reached via both orders


def test_preemption_bound_limits_switches():
    """max_preemptions=0 forbids switching away from a still-enabled
    actor — strictly fewer executions than the unbounded run."""
    def spin(name):
        def fn(ctx):
            for k in range(3):
                yield Step(f"s{k}")
                # record the interleaving so states stay distinct
                ctx.world.d["trace"] = (
                    ctx.world.d.get("trace", "") + name)
        return fn

    def build():
        return (Model(lambda: W()).actor("a", spin("a"))
                .actor("b", spin("b")))

    bounded = check(build(), max_depth=8, max_preemptions=0)
    free = check(build(), max_depth=8)
    assert bounded.executions < free.executions


def test_violation_minimized_and_replays():
    """A seeded violation comes back as the SHORTEST schedule and
    replays byte-for-byte through Explorer.replay."""
    def actor(ctx):
        yield Step("a")
        yield Step("b")
        ctx.world.d["bad"] = True
        yield Step("c")

    def filler(ctx):
        for _ in range(4):
            yield Step("noise")

    m = (Model(lambda: W()).actor("m", actor).actor("f", filler)
         .invariant("no-bad",
                    lambda w: "bad set" if w.d.get("bad") else None))
    ex = Explorer(m, max_depth=10)
    rep = ex.run()
    v = rep.violation
    assert v is not None and v.invariant == "no-bad"
    # minimal: exactly the two steps of "m" that set the flag
    assert v.schedule.split() == ["m/a", "m/b"]
    rv = ex.replay(v.schedule)
    assert rv is not None
    assert rv.schedule == v.schedule
    assert rv.invariant == "no-bad"


def test_replay_rejects_disabled_token():
    def actor(ctx):
        yield Step("only")

    ex = Explorer(Model(lambda: W()).actor("a", actor))
    with pytest.raises(KeyError, match="not enabled"):
        ex.replay("a/only a/only")


def test_max_states_truncates():
    def spin(ctx):
        while True:
            bit = yield Choose("c", [0, 1])
            # distinct world per choice history: the tree can't dedup
            ctx.world.d["path"] = ctx.world.d.get("path", "") + str(bit)

    rep = check(Model(lambda: W()).actor("a", spin),
                max_depth=30, max_states=20)
    assert rep.truncated >= 1
    assert rep.states <= 21


# ---- protocol scenarios ----------------------------------------------

@pytest.mark.parametrize("scenario", sorted(protomodel.SCENARIOS))
def test_scenario_explores_clean(scenario):
    """Every protocol scenario is violation-free at smoke bounds (the
    full bounds run in ``scripts/check_protocol.py``'s default mode)."""
    model, _bounds = protomodel.build(scenario)
    rep = check(model, **SMOKE)
    assert rep.violation is None, str(rep.violation)
    assert rep.states > 10  # actually explored, not vacuously empty


@pytest.mark.parametrize("mutant", sorted(protomodel.MUTANTS))
def test_mutant_yields_replayable_counterexample(mutant):
    """Flipping one protocol guard must surface a counterexample
    breaking exactly the invariant the MUTANTS table predicts, and the
    minimized schedule must replay byte-for-byte on a fresh explorer
    over the same mutated model."""
    _desc, scenario, expected_inv = protomodel.MUTANTS[mutant]
    model, bounds = protomodel.build(scenario, mutants=(mutant,))
    ex = Explorer(model, **bounds)
    rep = ex.run()
    v = rep.violation
    assert v is not None, f"mutant {mutant} not caught"
    assert v.invariant == expected_inv, (
        f"mutant {mutant} broke {v.invariant}, expected {expected_inv}")
    fresh_model, _ = protomodel.build(scenario, mutants=(mutant,))
    rv = Explorer(fresh_model).replay(v.schedule)
    assert rv is not None, f"{mutant}: schedule did not replay"
    assert rv.invariant == expected_inv
    assert rv.schedule == v.schedule


def test_unmutated_rewind_tolerates_stale_primary():
    """The durability invariant is scoped by ack epoch: the stale,
    still-partitioned old primary missing a commit acked under a HIGHER
    epoch is the tolerated fenced-on-contact transient, not a
    violation (the invariant only binds primaries at >= the acking
    epoch)."""
    model, _ = protomodel.build("rewind")
    rep = check(model, max_depth=8, max_states=2_000)
    assert rep.violation is None, str(rep.violation)


def test_elect_is_the_production_function():
    """The model imports ``elect`` from the runtime module rather than
    re-implementing it — checking the model checks the real tiebreak."""
    from distkeras_tpu.parallel import replicated_ps
    assert protomodel.elect is replicated_ps.elect
    assert protomodel.mint_epoch is replicated_ps.mint_epoch


def test_metrics_snapshot_holds_states_explored(tmp_path):
    """``--metrics-out`` writes a registry snapshot whose
    ``modelcheck_states_explored_total`` counts the exploration."""
    snap = tmp_path / "mc.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_protocol.py"),
         "--scenario", "split", "--max-depth", "8",
         "--metrics-out", str(snap)],
        capture_output=True, text=True, timeout=120, cwd=str(REPO))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    counters = json.loads(snap.read_text())["counters"]
    assert counters["modelcheck_states_explored_total"] > 0


def test_check_protocol_replay_cli():
    """The printed counterexample replays from the CLI: --replay with
    the schedule string reproduces the same invariant and exits 2."""
    mutant = "no-dedupe-repl"
    _desc, scenario, expected_inv = protomodel.MUTANTS[mutant]
    model, bounds = protomodel.build(scenario, mutants=(mutant,))
    v = Explorer(model, **bounds).run().violation
    assert v is not None
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_protocol.py"),
         "--replay", v.schedule, "--scenario", scenario,
         "--with-mutant", mutant],
        capture_output=True, text=True, timeout=120,
        cwd=str(REPO))
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert expected_inv in proc.stdout
