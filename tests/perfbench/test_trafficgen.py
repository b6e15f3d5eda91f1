"""The generator offers every seed the same work in another order."""

import collections

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
