"""Deterministic cooperative scheduler + exhaustive interleaving
explorer for protocol models (ISSUE 11 tentpole).

This is the CHESS/DPOR shape applied to our own stack: protocol
participants are GENERATOR-based actors that yield at labeled decision
points; the explorer enumerates every schedule up to a bound, asserts
safety invariants in every reached state, and reports any violation as
a minimized schedule trace that replays byte-for-byte.

Actor API
---------
An actor is a generator function ``def actor(ctx): ...`` registered on
a :class:`Model`.  It runs ATOMICALLY between yields; every yield is a
labeled decision point the scheduler owns:

* ``yield Step("label")``         — plain scheduling point (the actor
  is re-enabled immediately; the step's world mutations happened
  before the yield).
* ``x = yield Choose("label", options)`` — internal nondeterminism;
  the explorer forks one branch per option and sends the chosen value
  back into the generator.
* ``msg = yield Recv("chan")``    — blocks until the named channel is
  nonempty, then receives its head (channels are FIFO per key; the
  nondeterminism between channels comes from WHICH actor the
  scheduler runs, so per-pair FIFO order is preserved like TCP).
* ``yield Timer("label")``        — fires only when the scheduler
  chooses this actor AND the model's timer budget allows it; models
  timeouts (election timers) without wall clocks.

Within an atomic step the actor mutates the shared ``world`` object
and calls ``ctx.send(chan, msg)`` freely.  Discipline: ALL protocol
state lives in ``world`` (fingerprinted for state-hash dedup);
generator locals only drive control flow.

Crashes are explorer-level transitions on actors declared
``crashable``: the explorer may, at any scheduling point while the
crash budget lasts, kill the actor and invoke the model's
``on_crash`` hook to mutate the world.

Exploration
-----------
Generators cannot be cloned, so the explorer is REPLAY-based: to
explore a sibling branch it rebuilds the initial world from the model
factory and re-executes the schedule prefix — O(depth) per branch,
the standard stateless-model-checking trade (Godefroot's VeriSoft).
DFS is bounded by ``max_depth`` and a CHESS-style preemption budget
(``max_preemptions``: unforced actor switches).  Visited states are
deduplicated by ``(world.fingerprint(), per-actor program position)``.
Partial-order reduction: transitions may declare static footprints
(sets of world-resource keys); at each state, transitions whose
footprints are disjoint from every other enabled transition's are
explored as a singleton (persistent set of one), and a sleep-set pass
prunes re-exploration of commutative siblings.

Violations come back as :class:`Violation` with a schedule string —
space-joined transition tokens — that :meth:`Explorer.replay`
re-executes deterministically; ``minimize`` then BFSes for the
shortest violating schedule.

Telemetry: ``modelcheck_states_explored_total`` and
``modelcheck_violations_total{invariant=...}`` counters on the global
registry (``scripts/check_protocol.py --metrics-out`` snapshots
them).
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Optional

from distkeras_tpu import telemetry

# ---------------------------------------------------------------------
# decision-point ops (yielded by actors)


def _token_label(label) -> str:
    """Labels become schedule-string tokens, so they must survive a
    whitespace split-and-rejoin byte-for-byte."""
    return re.sub(r"\s+", "", str(label))


class Op:
    """Base decision point; subclasses carry the scheduling payload."""

    label: str
    #: static footprint: world-resource keys this step may touch, or
    #: None for "dependent with everything" (the safe default)
    footprint: Optional[frozenset] = None


class Step(Op):
    """Plain labeled scheduling point."""

    def __init__(self, label: str, footprint: Optional[Iterable] = None):
        self.label = _token_label(label)
        self.footprint = (frozenset(footprint)
                          if footprint is not None else None)

    def __repr__(self):
        return f"Step({self.label!r})"


class Choose(Op):
    """Internal nondeterminism: the explorer forks one branch per
    option and sends the chosen option back into the generator."""

    def __init__(self, label: str, options: Iterable):
        self.label = _token_label(label)
        self.options = list(options)
        if not self.options:
            raise ValueError(f"Choose({label!r}) with no options")

    def __repr__(self):
        return f"Choose({self.label!r}, {self.options!r})"


class Recv(Op):
    """Receive the head of a FIFO channel; blocks (actor disabled)
    while the channel is empty."""

    def __init__(self, chan, footprint: Optional[Iterable] = None):
        self.chan = chan
        self.label = _token_label(f"recv:{chan!r}")
        self.footprint = (frozenset(footprint)
                          if footprint is not None else None)

    def __repr__(self):
        return f"Recv({self.chan!r})"


class Timer(Op):
    """A timeout that fires only when the scheduler picks it and the
    model's timer budget allows; never fires otherwise (models 'the
    timer MAY fire now' without wall clocks)."""

    def __init__(self, label: str):
        self.label = _token_label(label)

    def __repr__(self):
        return f"Timer({self.label!r})"


# ---------------------------------------------------------------------
# runtime context handed to actors


class Context:
    """Actor-facing handle on the world: shared state + channels."""

    def __init__(self, world):
        self.world = world
        self._channels: dict[Any, list] = {}

    def send(self, chan, msg) -> None:
        """Append ``msg`` to channel ``chan`` (FIFO per channel)."""
        self._channels.setdefault(chan, []).append(msg)

    def pending(self, chan) -> int:
        return len(self._channels.get(chan, ()))

    def drain(self, chan) -> list:
        """Drop every queued message on ``chan`` (link down / crash)."""
        msgs = self._channels.pop(chan, [])
        return msgs

    def _chan_fingerprint(self):
        return tuple(sorted(
            (repr(k), tuple(repr(m) for m in v))
            for k, v in self._channels.items() if v))


# ---------------------------------------------------------------------
# model + violation containers


@dataclass
class Invariant:
    name: str
    check: Callable[[Any], Optional[str]]  # world -> error or None


@dataclass
class Violation(Exception):
    invariant: str
    detail: str
    schedule: str
    depth: int

    def __str__(self):
        return (f"invariant {self.invariant!r} violated at depth "
                f"{self.depth}: {self.detail}\n  schedule: "
                f"{self.schedule}")


class Model:
    """A checkable protocol instance: a world factory, actors, and
    invariants.  ``make_world()`` must be deterministic — replay
    correctness depends on it."""

    def __init__(self, make_world: Callable[[], Any]):
        self.make_world = make_world
        self.actors: list[tuple[str, Callable]] = []
        self.invariants: list[Invariant] = []
        self.crashable: dict[str, Callable] = {}
        self.timer_budget: int = 0
        self.crash_budget: int = 0

    def actor(self, name: str, fn: Callable) -> "Model":
        self.actors.append((str(name), fn))
        return self

    def invariant(self, name: str, check: Callable) -> "Model":
        self.invariants.append(Invariant(str(name), check))
        return self

    def allow_crash(self, name: str, on_crash: Callable,
                    budget: int = 1) -> "Model":
        """Declare actor ``name`` crashable; ``on_crash(ctx)`` runs
        when the explorer kills it (the ctx lets it mutate the world
        AND drain the dead actor's channels).  ``budget`` is shared
        across all crashable actors per execution."""
        self.crashable[str(name)] = on_crash
        self.crash_budget = max(self.crash_budget, int(budget))
        return self


# ---------------------------------------------------------------------
# a single deterministic execution


@dataclass
class _ActorState:
    name: str
    gen: Any
    op: Optional[Op]  # current pending decision point; None = done
    crashed: bool = False


class _Execution:
    """One run of the model: actors started, stepped by transition
    token.  The explorer drives it; ``replay`` re-drives it."""

    def __init__(self, model: Model):
        self.model = model
        self.world = model.make_world()
        self.ctx = Context(self.world)
        self.timer_budget = int(model.timer_budget)
        self.crash_budget = int(model.crash_budget)
        self.actors: dict[str, _ActorState] = {}
        for name, fn in model.actors:
            gen = fn(self.ctx)
            st = _ActorState(name, gen, None)
            self.actors[name] = st
            self._advance(st, None, first=True)

    # -- stepping ------------------------------------------------------

    def _advance(self, st: _ActorState, send_value,
                 first: bool = False) -> None:
        """Run the actor's next atomic step, parking it at its next
        decision point (or marking it done)."""
        try:
            op = (next(st.gen) if first
                  else st.gen.send(send_value))
        except StopIteration:
            st.op = None
            return
        if not isinstance(op, Op):
            raise TypeError(f"actor {st.name!r} yielded {op!r}; "
                            "expected a modelcheck.Op")
        st.op = op

    def enabled(self) -> list[str]:
        """Sorted transition tokens enabled in the current state.

        Token grammar (stable — schedules are strings of these):
          ``<actor>/<label>``            run a Step/Timer/Recv
          ``<actor>/<label>=<i>``        resolve a Choose with option i
          ``crash:<actor>``              kill a crashable actor
        """
        toks = []
        for name, st in sorted(self.actors.items()):
            if st.crashed or st.op is None:
                continue
            op = st.op
            if isinstance(op, Choose):
                for i in range(len(op.options)):
                    toks.append(f"{name}/{op.label}={i}")
            elif isinstance(op, Recv):
                if self.ctx.pending(op.chan):
                    toks.append(f"{name}/{op.label}")
            elif isinstance(op, Timer):
                if self.timer_budget > 0:
                    toks.append(f"{name}/{op.label}")
            else:
                toks.append(f"{name}/{op.label}")
            if (st.name in self.model.crashable
                    and self.crash_budget > 0):
                toks.append(f"crash:{name}")
        return sorted(set(toks))

    def footprint_of(self, token: str) -> Optional[frozenset]:
        """Static footprint of an enabled transition, or None for
        'dependent with everything'."""
        if token.startswith("crash:"):
            return None
        name = token.split("/", 1)[0]
        st = self.actors.get(name)
        if st is None or st.op is None:
            return None
        if isinstance(st.op, (Choose, Timer)):
            return None
        return st.op.footprint

    def step(self, token: str) -> None:
        """Execute one transition token (must be in ``enabled()``)."""
        if token.startswith("crash:"):
            name = token[len("crash:"):]
            st = self.actors[name]
            if st.crashed or name not in self.model.crashable:
                raise KeyError(f"cannot crash {name!r}")
            if self.crash_budget <= 0:
                raise KeyError("crash budget exhausted")
            self.crash_budget -= 1
            st.crashed = True
            st.op = None
            st.gen.close()
            self.model.crashable[name](self.ctx)
            return
        name, rest = token.split("/", 1)
        st = self.actors[name]
        op = st.op
        if op is None or st.crashed:
            raise KeyError(f"{token!r} not enabled (actor parked)")
        if isinstance(op, Choose):
            label, _, idx = rest.rpartition("=")
            if label != op.label:
                raise KeyError(f"{token!r}: actor is at {op.label!r}")
            self._advance(st, op.options[int(idx)])
        elif isinstance(op, Recv):
            if rest != op.label or not self.ctx.pending(op.chan):
                raise KeyError(f"{token!r} not enabled")
            msg = self.ctx._channels[op.chan].pop(0)
            if not self.ctx._channels[op.chan]:
                del self.ctx._channels[op.chan]
            self._advance(st, msg)
        elif isinstance(op, Timer):
            if rest != op.label or self.timer_budget <= 0:
                raise KeyError(f"{token!r} not enabled")
            self.timer_budget -= 1
            self._advance(st, None)
        else:
            if rest != op.label:
                raise KeyError(f"{token!r}: actor is at {op.label!r}")
            self._advance(st, None)

    # -- state identity ------------------------------------------------

    def fingerprint(self) -> str:
        """Hash of (world, channels, per-actor position, budgets) —
        the state-dedup key."""
        parts = [repr(self.world.fingerprint()),
                 repr(self.ctx._chan_fingerprint()),
                 f"t={self.timer_budget}", f"c={self.crash_budget}"]
        for name, st in sorted(self.actors.items()):
            parts.append(f"{name}:{'X' if st.crashed else ''}"
                         f"{st.op!r}")
        return hashlib.sha1(
            "\x00".join(parts).encode()).hexdigest()

    def check_invariants(self) -> Optional[tuple[str, str]]:
        for inv in self.model.invariants:
            err = inv.check(self.world)
            if err:
                return inv.name, str(err)
        return None


# ---------------------------------------------------------------------
# explorer


@dataclass
class Report:
    states: int
    executions: int
    truncated: int
    violation: Optional[Violation] = None
    pruned_sleep: int = 0
    pruned_dedup: int = 0


class Explorer:
    """Bounded DFS over interleavings with state dedup + POR."""

    def __init__(self, model: Model, *, max_depth: int = 24,
                 max_preemptions: Optional[int] = None,
                 max_states: int = 2_000_000):
        self.model = model
        self.max_depth = int(max_depth)
        self.max_preemptions = (None if max_preemptions is None
                                else int(max_preemptions))
        self.max_states = int(max_states)

    # -- replay --------------------------------------------------------

    def _exec_prefix(self, prefix: list[str]) -> _Execution:
        ex = _Execution(self.model)
        for tok in prefix:
            ex.step(tok)
        return ex

    def replay(self, schedule: str) -> Optional[Violation]:
        """Re-execute a schedule string deterministically, checking
        invariants after every transition; returns the Violation it
        reproduces (or None if the schedule runs clean — i.e. the
        counterexample does NOT replay)."""
        toks = schedule.split()
        ex = _Execution(self.model)
        bad = ex.check_invariants()
        for i, tok in enumerate(toks):
            if tok not in ex.enabled():
                raise KeyError(
                    f"replay: {tok!r} not enabled at step {i} "
                    f"(enabled: {ex.enabled()})")
            ex.step(tok)
            bad = ex.check_invariants()
            if bad:
                return Violation(bad[0], bad[1],
                                 " ".join(toks[:i + 1]), i + 1)
        return None

    # -- exploration ---------------------------------------------------

    def run(self) -> Report:
        """Bounded DFS.  Returns a Report; ``report.violation`` is the
        MINIMIZED, replay-verified counterexample if one exists."""
        reg = telemetry.metrics()
        states = reg.counter("modelcheck_states_explored_total")
        rep = Report(states=0, executions=0, truncated=0)
        visited: set[str] = set()

        def actor_of(tok: str) -> str:
            if tok.startswith("crash:"):
                return tok[len("crash:"):]
            return tok.split("/", 1)[0]

        # stack entries: (prefix, sleep-set, last-actor, preemptions)
        stack: list[tuple[list[str], frozenset, Optional[str], int]]
        stack = [([], frozenset(), None, 0)]
        found: Optional[Violation] = None
        while stack and found is None:
            prefix, sleep, last, preempt = stack.pop()
            ex = self._exec_prefix(prefix)
            rep.executions += 1
            fp = ex.fingerprint()
            # the preemption count is part of state identity when the
            # budget is bounded: a state first reached expensively must
            # not shadow a cheaper path with budget left to spend
            key = (fp, sleep,
                   preempt if self.max_preemptions is not None else 0)
            if key in visited:
                rep.pruned_dedup += 1
                continue
            visited.add(key)
            rep.states += 1
            states.inc()
            if rep.states > self.max_states:
                rep.truncated += 1
                break
            bad = ex.check_invariants()
            if bad:
                found = Violation(bad[0], bad[1],
                                  " ".join(prefix), len(prefix))
                break
            if len(prefix) >= self.max_depth:
                rep.truncated += 1
                continue
            enabled = ex.enabled()
            if not enabled:
                continue
            # persistent-singleton POR: a transition whose static
            # footprint is disjoint from every OTHER enabled
            # transition's commutes with all of them — exploring it
            # alone covers the state space from here.
            fps = {t: ex.footprint_of(t) for t in enabled}
            chosen = None
            for t in enabled:
                f = fps[t]
                if f is None:
                    continue
                if all(o == t or (fps[o] is not None
                                  and not (f & fps[o]))
                       for o in enabled):
                    chosen = t
                    break
            branch = [chosen] if chosen is not None else enabled
            # sleep sets: skip transitions slept at this state;
            # wake dependents as siblings are taken.
            branch = [t for t in branch if t not in sleep]
            if not branch:
                rep.pruned_sleep += 1
                continue
            taken: list[str] = []
            new_frames = []
            for t in branch:
                if (self.max_preemptions is not None
                        and last is not None
                        and actor_of(t) != last
                        and any(actor_of(e) == last
                                for e in enabled)):
                    if preempt >= self.max_preemptions:
                        rep.truncated += 1
                        continue
                    npre = preempt + 1
                else:
                    npre = preempt
                # sleep set for this child: siblings already taken
                # whose footprints are independent of t stay asleep
                ft = fps[t]
                child_sleep = set()
                for s in sleep | set(taken):
                    fs = fps.get(s, None)
                    if (ft is not None and fs is not None
                            and not (ft & fs)):
                        child_sleep.add(s)
                new_frames.append((prefix + [t],
                                   frozenset(child_sleep),
                                   actor_of(t), npre))
                taken.append(t)
            # DFS order: push reversed so branch[0] explores first
            stack.extend(reversed(new_frames))

        if found is not None:
            found = self.minimize(found)
            reg.counter("modelcheck_violations_total",
                        invariant=found.invariant).inc()
            rep.violation = found
        return rep

    # -- minimization --------------------------------------------------

    def minimize(self, v: Violation) -> Violation:
        """BFS for the SHORTEST violating schedule no longer than the
        found one, then verify it replays byte-for-byte."""
        limit = len(v.schedule.split())
        seen: set[str] = set()
        frontier: list[list[str]] = [[]]
        best = v
        for depth in range(limit + 1):
            nxt: list[list[str]] = []
            for prefix in frontier:
                ex = self._exec_prefix(prefix)
                fp = ex.fingerprint()
                if fp in seen:
                    continue
                seen.add(fp)
                bad = ex.check_invariants()
                if bad:
                    best = Violation(bad[0], bad[1],
                                     " ".join(prefix), len(prefix))
                    # byte-for-byte replay check before trusting it
                    rv = self.replay(best.schedule)
                    if (rv is None
                            or rv.invariant != best.invariant
                            or rv.schedule != best.schedule):
                        raise AssertionError(
                            "minimized schedule failed to replay: "
                            f"{best.schedule!r}")
                    return best
                if depth < limit and len(seen) < self.max_states:
                    for t in ex.enabled():
                        nxt.append(prefix + [t])
            frontier = nxt
            if not frontier:
                break
        return best


def check(model: Model, **kw) -> Report:
    """One-shot convenience: explore ``model`` and return the Report."""
    return Explorer(model, **kw).run()
