"""The traffic generator: deterministic per seed, lengths inside their
clips, the same work for every seed."""

import numpy as np
import pytest

from bench import traffic
from conftest import ROOT

MIXES = ["gen-offline", "chat-bursty"]


def _gen(name, seed, seconds=40.0):
    return traffic.generate(traffic.load_mix(name, ROOT / "bench"),
                            seed=seed, seconds=seconds, vocab=32064, batch=4)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    a, b = _gen(name, 2**31 + 5), _gen(name, 2**31 + 5)
    assert [(r.prompt, r.max_new_tokens, r.due) for r in a.requests] == \
        [(r.prompt, r.max_new_tokens, r.due) for r in b.requests]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_clips(name):
    tr = _gen(name, 7)
    mix = tr.mix
    p = np.array([len(r.prompt) for r in tr.requests])
    o = np.array([r.max_new_tokens + 1 for r in tr.requests])
    assert p.min() >= mix["prompt"]["min"] and p.max() <= mix["prompt"]["max"]
    assert o.max() <= mix["output"]["max"]
    if not mix.get("first_wave_cut"):
        assert o.min() >= mix["output"]["min"]
    for r in tr.requests:
        assert all(0 <= t < 32064 for t in r.prompt)


def _schedule(tr):
    return [(len(r.prompt), r.max_new_tokens, r.due) for r in tr.requests]


@pytest.mark.parametrize("name", ["chat-bursty"])
def test_every_seed_offers_the_same_work(name):
    a, b = _gen(name, 1), _gen(name, 2**31 + 11)
    assert sorted(len(r.prompt) for r in a.requests) == \
        sorted(len(r.prompt) for r in b.requests)
    assert sorted(r.max_new_tokens for r in a.requests) == \
        sorted(r.max_new_tokens for r in b.requests)
    # the mix fixes its schedule: the same requests come at the same times
    # for every seed, and only the token ids differ
    assert "schedule_seed" in a.mix
    assert _schedule(a) == _schedule(b)
    assert [r.prompt for r in a.requests] != [r.prompt for r in b.requests]


def test_seed_shuffles_the_order_without_a_schedule_seed():
    mix = dict(traffic.load_mix("chat-bursty", ROOT / "bench"))
    del mix["schedule_seed"]

    def gen(seed):
        return traffic.generate(mix, seed=seed, seconds=40.0, vocab=32064,
                                batch=4)

    a, b = gen(1), gen(2)
    assert sorted(len(r.prompt) for r in a.requests) == \
        sorted(len(r.prompt) for r in b.requests)
    # the due times show all gaps but the last: the two seeds share all of
    # them but at most one, in another order
    ga = np.round(np.diff([r.due for r in a.requests]), 9)
    gb = np.round(np.diff([r.due for r in b.requests]), 9)
    assert list(ga) != list(gb)
    assert len(set(ga) & set(gb)) >= len(ga) - 1


def test_schedule_seed_draws_the_order_and_not_the_ids():
    """With ``schedule_seed`` the schedule is the one that seed would shuffle
    without the key; the ids are drawn from the run's seed."""
    mix = traffic.load_mix("chat-bursty", ROOT / "bench")
    free = {k: v for k, v in mix.items() if k != "schedule_seed"}
    fixed = traffic.generate(mix, seed=2**31 + 3, seconds=40.0, vocab=32064,
                             batch=4)
    shuffled = traffic.generate(free, seed=mix["schedule_seed"],
                                seconds=40.0, vocab=32064, batch=4)
    assert _schedule(fixed) == _schedule(shuffled)
    again = traffic.generate(mix, seed=2**31 + 3, seconds=40.0, vocab=32064,
                             batch=4)
    assert [r.prompt for r in fixed.requests] == \
        [r.prompt for r in again.requests]


@pytest.mark.parametrize("name", ["chat-bursty"])
def test_open_loop_due_inside_window(name):
    tr = _gen(name, 3, seconds=40.0)
    due = [r.due for r in tr.requests]
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 40.0
    assert len(due) == round(tr.mix["rate_rps"] * 40.0)


def test_gamma_burstiness_and_lognormal_median():
    mix = {"arrival": "gamma", "gamma_shape": 0.25, "rate_rps": 2.0,
           "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.7,
                      "min": 1, "max": 100000},
           "output": {"dist": "uniform", "min": 1, "max": 1}}
    tr = traffic.generate(mix, seed=0, seconds=5000.0, vocab=8, batch=1)
    gaps = np.diff([r.due for r in tr.requests])
    assert gaps.std() / gaps.mean() == pytest.approx(2.0, rel=0.1)
    assert np.median([len(r.prompt) for r in tr.requests]) == \
        pytest.approx(256, abs=2)


def test_first_wave_is_cut():
    tr = _gen("gen-offline", 9)
    full = traffic.quantile_lengths(tr.mix["output"], tr.mix["pool"])
    first = [r.max_new_tokens + 1 for r in tr.requests[:4]]
    assert first[0] < 0.3 * 768 and max(first) >= 640 * 0.99
    assert sorted(r.max_new_tokens + 1 for r in tr.requests[4:]) != []
    assert full.min() >= 640


def test_percentile_nearest_rank():
    vals = sorted(range(1, 101))
    assert traffic.percentile(vals, 0.9) == 90
    assert traffic.percentile(vals, 0.0) == 1
    assert traffic.percentile(vals, 1.0) == 100
