"""The traffic generator: the same schedule for every seed, tokens from
the seed."""
import numpy as np
import pytest

from chipbench import loadgen, spec

CHAT = spec.read_json(f"{spec.PKG}/traffic/chat.json")


def _schedule(plan):
    return [(p.at, p.prompt.size, p.max_new_tokens, p.greedy) for p in plan]


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2 ** 31 + 9)])
def test_seeds_share_sizes_and_gaps(seeds):
    a, b = (loadgen.generate(CHAT, s, 40.0, 1000) for s in seeds)
    assert len(a) == len(b) == round(CHAT["arrivals"]["rate_rps"] * 40)
    assert _schedule(a) == _schedule(b)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, b))
    assert [p.seed for p in a] != [p.seed for p in b]


def test_same_seed_same_requests():
    a, b = (loadgen.generate(CHAT, 5, 10.0, 1000) for _ in range(2))
    for x, y in zip(a, b):
        assert x.at == y.at and x.seed == y.seed
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_open_loop_fills_the_window_within_limits():
    plan = loadgen.generate(CHAT, 3, 30.0, 500)
    at = [p.at for p in plan]
    assert at[0] == 0.0 and at == sorted(at) and at[-1] < 30.0
    for p in plan:
        assert CHAT["prompt"]["min"] <= p.prompt.size <= CHAT["prompt"]["max"]
        assert CHAT["output"]["min"] <= p.max_new_tokens \
            <= CHAT["output"]["max"]
        assert 0 <= p.prompt.min() and p.prompt.max() < 500
    greedy = [p.greedy for p in plan]
    every = CHAT["sampling"]["greedy_every"]
    assert greedy == [i % every == 0 for i in range(len(plan))]


def test_gamma_gaps_are_bursty():
    mix = dict(CHAT, arrivals={"process": "gamma", "rate_rps": 20.0,
                               "cv": 3.0})
    plan = loadgen.generate(mix, 1, 100.0, 100)
    gaps = np.diff([p.at for p in plan])
    assert gaps.std() / gaps.mean() > 2.0


def test_rate_override_and_closed_loop():
    assert len(loadgen.generate(CHAT, 1, 10.0, 100, rate_rps=5.0)) == 50
    mix = dict(CHAT, loop="closed", clients=3, pool=20)
    plan = loadgen.generate(mix, 1, 10.0, 100)
    assert len(plan) == 20 and all(p.at == 0.0 for p in plan)
