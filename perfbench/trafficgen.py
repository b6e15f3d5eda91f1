"""The one general traffic generator: data files in, requests or batches out.

A traffic mix is a file ``perfbench/traffic/<name>.json`` of parameters.
Every seed of a serving mix offers the same work in another order:

* lengths are the N-point quantile grid of each distribution (its inverse
  CDF at (i + 0.5) / N), clipped and rounded, so the multiset of prompt
  lengths and of output budgets is the same for every seed;
* prompts are paired with budgets by a permutation fixed in the lengths
  file (``pairing_seed``), so the multiset of requests, and with it the
  load on each of the engine's buckets, is the same for every seed;
* arrivals of an open loop are cumulative sums of the quantile grid of the
  exponential distribution, scaled to fill the span: the gaps of a Poisson
  process, the same multiset for every seed;
* ``--seed`` permutes which request arrives when, permutes the gaps, and
  makes the token ids.

The pre-roll and the window are drawn apart by the same rule, each with
its own N, so the window's requests are the same for every seed.  A
backlog, of which a run serves the part it reaches, is drawn in rounds for
the same end (``backlog_rounds``), and ``backlog_holds`` says how fast a
program may be before the backlog runs dry inside a run.
"""

import dataclasses
import heapq
import json
import math
import os
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def load(name: str, repo: str) -> dict:
    """A traffic file, with the lengths file it names read into it."""
    folder = os.path.join(repo, "perfbench", "traffic")
    with open(os.path.join(folder, f"{name}.json")) as f:
        traffic = json.load(f)
    if "lengths" in traffic:
        with open(os.path.join(folder, f"{traffic['lengths']}.json")) as f:
            traffic["lengths_spec"] = json.load(f)
    return traffic


def quantile_grid(dist: dict, n: int) -> np.ndarray:
    """The n-point quantile grid of a clipped lognormal, as whole numbers."""
    if dist["law"] != "lognormal":
        raise ValueError(f"unknown law {dist['law']!r}")
    mu, sigma = math.log(dist["median"]), dist["sigma"]
    grid = [math.exp(mu + sigma * _NORMAL.inv_cdf((i + 0.5) / n))
            for i in range(n)]
    return np.clip(np.rint(grid), dist["min"], dist["max"]).astype(np.int64)


def request_sizes(lengths: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """``(prompt lengths, budgets)`` of n requests, in the file's fixed
    pairing: the same n pairs whatever ``--seed``."""
    prompts = quantile_grid(lengths["prompt"], n)
    budgets = quantile_grid(lengths["budget"], n)
    pairing = np.random.default_rng(lengths["pairing_seed"]).permutation(n)
    budgets = budgets[pairing]
    if (prompts + budgets).max() > lengths["max_total"]:
        raise ValueError("a prompt and its budget pass max_total")
    return prompts, budgets


def exponential_gaps(n: int, span: float) -> np.ndarray:
    """The n-point quantile grid of the exponential law, scaled so that
    the gaps sum to ``span``."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return gaps * (span / gaps.sum())


@dataclasses.dataclass
class Request:
    index: int
    phase: str            # "preroll" or "window"
    due: float            # seconds after the offered span starts
    prompt: np.ndarray
    budget: int


def _phase(phase, n, t0, span, lengths, arrival, vocab, rng, first_index):
    prompts, budgets = request_sizes(lengths, n)
    order = rng.permutation(n)
    if arrival == "poisson":
        # n arrivals: half a mean gap before the first and after the last,
        # and between them the n - 1 gaps of the grid in the seed's order
        inner = exponential_gaps(n - 1, span * (n - 1) / n)
        inner = inner[rng.permutation(n - 1)]
        due = t0 + span / (2 * n) + np.concatenate([[0.0], np.cumsum(inner)])
    elif arrival == "backlog":
        due = np.full(n, t0)
    else:
        raise ValueError(f"unknown arrival {arrival!r}")
    out = []
    for slot, i in enumerate(order):
        out.append(Request(
            index=first_index + slot, phase=phase, due=float(due[slot]),
            prompt=rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
            budget=int(budgets[i])))
    return out


def serving_requests(traffic: dict, seed: int, seconds: float,
                     vocab: int) -> list[Request]:
    """The requests of one run, in due order.  ``seconds`` is the window;
    the counts scale with it, so that a short trial run keeps the rate."""
    lengths = traffic["lengths_spec"]
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    arrival = traffic["arrival"]
    pre = traffic["preroll_s"]
    if arrival == "backlog":
        size, rounds = backlog_rounds(traffic)
        return [r for k in range(rounds)
                for r in _phase("window", size, 0.0, 0.0, lengths, arrival,
                                vocab, rng, k * size)]
    rate = traffic["rate_per_s"]
    n_pre, n_win = round(rate * pre), round(rate * seconds)
    reqs = _phase("preroll", n_pre, 0.0, pre, lengths, arrival, vocab, rng, 0)
    reqs += _phase("window", n_win, pre, seconds, lengths, arrival, vocab,
                   rng, n_pre)
    return reqs


def offered(traffic: dict, n: int) -> dict:
    """The distribution actually offered by n requests: quantiles of both
    lengths and their totals (recorded in the traffic file's ``why``)."""
    prompts, budgets = request_sizes(traffic["lengths_spec"], n)
    q = lambda a: [int(np.quantile(a, p, method="inverted_cdf"))  # noqa: E731
                   for p in (0.1, 0.5, 0.9, 0.99)]
    return {"n": n, "prompt_q10_50_90_99": q(prompts),
            "budget_q10_50_90_99": q(budgets),
            "prompt_tokens": int(prompts.sum()),
            "budget_tokens": int(budgets.sum())}


def backlog_rounds(traffic: dict) -> tuple[int, int]:
    """``(requests a round, rounds)`` of a backlog.  Its ``requests`` come
    in rounds of ``round`` (one round where the file gives none): every
    round is the same ``round``-point grid in the file's pairing, and the
    seed orders each round by itself.  However far down its queue a program
    gets, it has then served whole rounds, the same requests for every
    seed, and a part of one more; drawn as one grid of ``requests``, what
    it reached would be another sample of them for every seed."""
    n = traffic["requests"]
    size = traffic.get("round", n)
    if n % size:
        raise ValueError(f"{n} requests are no whole number of rounds of "
                         f"{size}")
    return size, n // size


def backlog_offered(traffic: dict) -> dict:
    """``offered`` for a backlog: the quantiles of one round, which are
    those of all rounds, and the totals of all of them."""
    size, rounds = backlog_rounds(traffic)
    one = offered(traffic, size)
    return {**one, "n": size * rounds,
            "prompt_tokens": one["prompt_tokens"] * rounds,
            "budget_tokens": one["budget_tokens"] * rounds}


def pool_class(envelopes, prompt_len: int, budget: int) -> int:
    """The pool a request waits for: the smallest envelope (of the
    ascending ``envelopes``) that holds the prompt and its budget."""
    return next(e for e in envelopes if prompt_len + budget <= e)


def first_idle_step(budgets, slots: int) -> int:
    """``budgets`` list-scheduled in their order onto ``slots`` slots, one
    token a slot a step, each slot taking the queue's next request when
    it comes free: the step at which the queue is empty and the first
    slot finishes, so that a slot falls idle."""
    free = [0] * slots      # a heap: the step at which each slot comes free
    for b in budgets:
        heapq.heapreplace(free, free[0] + int(b))
    return free[0]


BACKLOG_ORDERS = 64


def backlog_holds(traffic: dict, seconds: float) -> dict:
    """How fast a program may be before a backlog stops being one.

    A backlog cell measures tokens delivered with every slot busy, so its
    ``requests`` must outlast the pre-roll and the window whatever the
    program's speed.  From the traffic file alone: every pool class
    (``pool_class``) drains a queue of its own in the seed's order, round
    by round (``backlog_rounds``), and its first slot falls idle at
    ``first_idle_step`` of the class's budgets.  All pools step together,
    so by then the program has delivered ``step x all slots`` tokens in
    ``preroll_s + seconds``: the least of that over the pools is the rate
    the backlog holds.

    The order is the seed's, so this is the least over ``BACKLOG_ORDERS``
    orders of one fixed generator: a lower estimate over orders, not a
    bound over all of them.  A request is reckoned one step a budget
    token; the program gives a request's first two tokens in one step, a
    step fewer and a token more in it: the same tokens by the same time.

    Returns ``{"tokens_per_s", "first_idle_step": {envelope: step}}``, the
    steps being each pool's in its worst order."""
    pools = {int(e): int(n) for e, n in traffic["engine"]["buckets"].items()}
    envelopes = sorted(pools)
    size, rounds = backlog_rounds(traffic)
    prompts, budgets = request_sizes(traffic["lengths_spec"], size)
    cls = np.array([pool_class(envelopes, p, b)
                    for p, b in zip(prompts, budgets)])
    waited_for = np.unique(cls).tolist()    # a pool no request waits for
    worst = {}                              # has no backlog to run dry
    for k in range(BACKLOG_ORDERS):
        rng = np.random.default_rng([0xBAC10C, k])
        order = np.concatenate([rng.permutation(size) for _ in range(rounds)])
        queued, pool_of = budgets[order], cls[order]
        for e in waited_for:
            step = first_idle_step(queued[pool_of == e], pools[e])
            worst[e] = min(worst.get(e, step), step)
    span = traffic["preroll_s"] + seconds
    return {"tokens_per_s": int(min(worst.values()) * sum(pools.values())
                                / span),
            "first_idle_step": worst}


def lm_batch(traffic: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """Batch ``step`` of a packed language-model job: ``[B, T + 1]`` token
    ids, every row different, the same for the same seed and step."""
    rng = np.random.default_rng([int(seed), 0x7A41, int(step)])
    return rng.integers(0, vocab, (traffic["batch_size"],
                                   traffic["seq_len"] + 1), dtype=np.int32)
