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
its own N, so the window's requests are the same for every seed.
"""

import dataclasses
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
        n = traffic["requests"]
        return _phase("window", n, 0.0, 0.0, lengths, arrival, vocab, rng, 0)
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


def lm_batch(traffic: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """Batch ``step`` of a packed language-model job: ``[B, T + 1]`` token
    ids, every row different, the same for the same seed and step."""
    rng = np.random.default_rng([int(seed), 0x7A41, int(step)])
    return rng.integers(0, vocab, (traffic["batch_size"],
                                   traffic["seq_len"] + 1), dtype=np.int32)
