"""The generator offers every seed the same work in another order."""

import collections
import json
import os

import numpy as np
import pytest

from perfbench import trafficgen

from toybench import REPO

SEEDS = (0, 7, 2**31 + 12345)


def _requests(name, seed, seconds=50.0):
    traffic = trafficgen.load(name, REPO)
    return traffic, trafficgen.serving_requests(traffic, seed, seconds, 50257)


@pytest.mark.parametrize("name", ["chat-poisson", "chat-backlog"])
def test_same_multiset_of_requests_for_every_seed(name):
    sizes = []
    for seed in SEEDS:
        _, reqs = _requests(name, seed)
        sizes.append(collections.Counter(
            (r.phase, len(r.prompt), r.budget) for r in reqs))
    assert sizes[0] == sizes[1] == sizes[2]


@pytest.mark.parametrize("name", ["chat-poisson", "chat-backlog"])
def test_same_token_totals_for_every_seed(name):
    totals = set()
    for seed in SEEDS:
        _, reqs = _requests(name, seed)
        win = [r for r in reqs if r.phase == "window"]
        totals.add((len(win), sum(len(r.prompt) for r in win),
                    sum(r.budget for r in win)))
    assert len(totals) == 1


@pytest.mark.parametrize("name", ["chat-poisson", "chat-backlog"])
def test_seeds_differ_in_order_and_ids(name):
    _, a = _requests(name, 1)
    _, b = _requests(name, 2)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not np.array_equal(a[0].prompt[:4], b[0].prompt[:4]) or \
        not np.array_equal(a[1].prompt[:4], b[1].prompt[:4])


def test_same_seed_same_requests():
    _, a = _requests("chat-poisson", 2**31 + 5)
    _, b = _requests("chat-poisson", 2**31 + 5)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               and x.budget == y.budget for x, y in zip(a, b))


def test_arrival_gaps_are_one_multiset_in_another_order():
    gaps = []
    for seed in SEEDS:
        traffic, reqs = _requests("chat-poisson", seed)
        win = np.array([r.due for r in reqs if r.phase == "window"])
        assert np.all(np.diff(win) > 0)
        assert traffic["preroll_s"] < win[0] and win[-1] < \
            traffic["preroll_s"] + 50.0
        gaps.append(np.sort(np.round(np.diff(win), 9)))
    assert np.allclose(gaps[0], gaps[1]) and np.allclose(gaps[0], gaps[2])
    assert len(gaps[0]) == round(traffic["rate_per_s"] * 50.0) - 1


def test_backlog_is_all_due_at_the_start():
    traffic, reqs = _requests("chat-backlog", 3)
    assert len(reqs) == traffic["requests"]
    assert {r.due for r in reqs} == {0.0}


def test_lengths_keep_to_their_clips_and_the_context():
    traffic, reqs = _requests("chat-poisson", 4)
    spec = traffic["lengths_spec"]
    for r in reqs:
        assert spec["prompt"]["min"] <= len(r.prompt) <= spec["prompt"]["max"]
        assert spec["budget"]["min"] <= r.budget <= spec["budget"]["max"]
        assert len(r.prompt) + r.budget <= spec["max_total"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 50257


def test_quantile_grid_median_and_exponential_gaps():
    grid = trafficgen.quantile_grid(
        {"law": "lognormal", "median": 256, "sigma": 1.0, "min": 1,
         "max": 10**6}, 201)
    assert grid[100] == 256 and np.all(np.diff(grid) >= 0)
    gaps = trafficgen.exponential_gaps(200, 50.0)
    assert gaps.sum() == pytest.approx(50.0)
    # an exponential law: the standard deviation is near the mean
    assert 0.85 < gaps.std() / gaps.mean() < 1.05


def test_lm_batches_rows_differ_and_repeat_with_the_seed():
    traffic = trafficgen.load("lm-packed-t2048", REPO)
    a = trafficgen.lm_batch(traffic, 2**31 + 9, 0, 50257)
    b = trafficgen.lm_batch(traffic, 2**31 + 9, 0, 50257)
    c = trafficgen.lm_batch(traffic, 2**31 + 9, 1, 50257)
    assert a.shape == (traffic["batch_size"], traffic["seq_len"] + 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len({row.tobytes() for row in a}) == len(a)


def test_first_idle_step_by_hand():
    # two slots, budgets 3, 1, 2, 2 in that order: slot A takes the 3 and
    # is busy to step 3; slot B takes the 1 (to step 1) and then the first
    # 2 (to step 3); at step 3 both come free, one takes the last 2, the
    # queue is empty and the other falls idle
    assert trafficgen.first_idle_step([3, 1, 2, 2], 2) == 3
    # more slots than requests: idle from the start
    assert trafficgen.first_idle_step([5, 5], 3) == 0
    # one slot: idle when everything is served
    assert trafficgen.first_idle_step([4, 7, 2], 1) == 13


# a dozen requests in two pools.  A sigma of 50 throws every prompt onto a
# clip, six of 8 tokens and six of 40, and a sigma of 0 gives every request
# the budget 10, so that no order changes the answer: six requests of 8 + 10
# wait for the two slots of the 32-token pool and six of 40 + 10 for the four
# slots of the 64-token pool
HAND_MADE = {
    "requests": 12, "preroll_s": 1,
    "engine": {"buckets": {"32": 2, "64": 4, "128": 1}},
    "lengths_spec": {
        "prompt": {"law": "lognormal", "median": 16, "sigma": 50.0,
                   "min": 8, "max": 40},
        "budget": {"law": "lognormal", "median": 10, "sigma": 0.0,
                   "min": 1, "max": 99},
        "max_total": 64, "pairing_seed": 3}}


def test_backlog_holds_on_a_hand_made_traffic():
    # pool 32: its two slots serve 10-token requests in three rounds and
    # the third round empties the queue: the first slot idles at step 30.
    # pool 64: four of its six requests start at once, at step 10 two more
    # take two of the four slots that come free, the queue is empty and the
    # other two idle: step 10.  Pool 128 gets no request and sets nothing.
    # All pools step together, 7 slots, so a program that reaches step 10
    # just as the 1 s of pre-roll and a 4 s window end has delivered
    # 10 x 7 tokens in 5 s
    assert trafficgen.backlog_holds(HAND_MADE, 4) == {
        "tokens_per_s": 14, "first_idle_step": {32: 30, 64: 10}}
    # in two rounds of six, three requests a pool a round: the same queues
    assert trafficgen.backlog_holds({**HAND_MADE, "round": 6}, 4) == {
        "tokens_per_s": 14, "first_idle_step": {32: 30, 64: 10}}
    # a longer window is harder to hold
    assert trafficgen.backlog_holds(HAND_MADE, 9)["tokens_per_s"] == 7


def test_backlog_holds_takes_the_worst_of_its_orders():
    # budgets that differ: the least over the fixed orders is no more than
    # any one order gives, and no less than the least over all orders of
    # the pool's six requests
    import itertools

    spec = {**HAND_MADE["lengths_spec"],
            "budget": {"law": "lognormal", "median": 10, "sigma": 1.0,
                       "min": 2, "max": 24}}
    traffic = {**HAND_MADE, "lengths_spec": spec}
    got = trafficgen.backlog_holds(traffic, 4)["first_idle_step"]
    prompts, budgets = trafficgen.request_sizes(spec, 12)
    for envelope, slots in ((32, 2), (64, 4)):
        mine = [int(b) for p, b in zip(prompts, budgets)
                if trafficgen.pool_class([32, 64, 128], p, b) == envelope]
        steps = [trafficgen.first_idle_step(order, slots)
                 for order in itertools.permutations(mine)]
        assert min(steps) <= got[envelope] <= trafficgen.first_idle_step(
            mine, slots)
        assert got[envelope] < max(steps)


BACKLOGS = sorted(
    name for name, ext in map(os.path.splitext, os.listdir(
        os.path.join(REPO, "perfbench", "traffic")))
    if ext == ".json"
    and trafficgen.load(name, REPO).get("arrival") == "backlog")


def test_the_backlog_cells_are_found():
    assert {"chat-backlog", "reason-backlog"} <= set(BACKLOGS)


@pytest.mark.parametrize("name", BACKLOGS)
def test_recorded_offer_is_what_the_generator_offers(name):
    traffic, reqs = _requests(name, 2**31 + 17)
    recorded = {k: v for k, v in traffic["offered"].items() if k != "note"}
    assert recorded == trafficgen.backlog_offered(traffic)
    assert recorded["n"] == traffic["requests"] == len(reqs)
    assert recorded["prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    assert recorded["budget_tokens"] == sum(r.budget for r in reqs)


@pytest.mark.parametrize("name", BACKLOGS)
def test_every_round_of_a_backlog_is_one_multiset_in_another_order(name):
    """Whatever part of its queue a program reaches is whole rounds, the
    same requests for every seed, and a part of one more."""
    traffic = trafficgen.load(name, REPO)
    size, rounds = trafficgen.backlog_rounds(traffic)
    assert size * rounds == traffic["requests"] and rounds >= 2
    seen, orders = set(), set()
    for seed in SEEDS:
        _, reqs = _requests(name, seed)
        assert [r.index for r in reqs] == list(range(len(reqs)))
        for k in range(rounds):
            part = reqs[k * size:(k + 1) * size]
            seen.add(tuple(sorted((len(r.prompt), r.budget) for r in part)))
            orders.add(tuple((len(r.prompt), r.budget) for r in part))
    assert len(seen) == 1 and len(orders) == len(SEEDS) * rounds


def test_a_backlog_without_rounds_is_one_round_and_rounds_must_fit():
    assert trafficgen.backlog_rounds({"requests": 600}) == (600, 1)
    assert trafficgen.backlog_rounds({"requests": 600, "round": 200}) == \
        (200, 3)
    with pytest.raises(ValueError, match="whole number of rounds"):
        trafficgen.backlog_rounds({"requests": 600, "round": 250})


@pytest.mark.parametrize("name", BACKLOGS)
def test_recorded_rate_the_backlog_holds_is_the_rule_s(name):
    traffic = trafficgen.load(name, REPO)
    recorded = traffic["backlog"]
    got = trafficgen.backlog_holds(traffic, recorded["window_s"])
    assert recorded["holds_tokens_per_s"] == got["tokens_per_s"]
    assert recorded["first_idle_step"] == {
        str(e): step for e, step in got["first_idle_step"].items()}


@pytest.mark.parametrize("name", BACKLOGS)
def test_backlog_is_sized_for_the_fastest_program_at_the_run_length(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        run_seconds = json.load(f)["run_seconds"]
    recorded = trafficgen.load(name, REPO)["backlog"]
    assert recorded["window_s"] == run_seconds
    assert recorded["holds_tokens_per_s"] >= \
        recorded["sized_for_tokens_per_s"] > 0


@pytest.mark.parametrize("name,keys", [("chat-backlog", 5),
                                       ("reason-backlog", 8)])
def test_the_backlog_cells_warm_the_programs_they_did(name, keys):
    from perfbench.kinds import serve

    traffic, reqs = _requests(name, 2**31 + 31)
    eng = traffic["engine"]
    got = serve.warm_keys(reqs, sorted(int(b) for b in eng["buckets"]),
                          eng["prefill_align"])
    assert len(got) == keys
