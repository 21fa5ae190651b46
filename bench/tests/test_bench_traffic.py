"""The traffic generator: seeded, deterministic, in range, out of phase."""
import json
import os

import _paths  # noqa: F401
import pytest
from harness.traffic import Sessions, deck

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(_paths.BENCH,
                                                        "traffic")))


def _mix(name):
    with open(os.path.join(_paths.BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _draw(mix, seed, n=40):
    s = Sessions(mix, seed, vocab=1000)
    firsts = [s.first(i) for i in range(mix["sessions"])]
    rest = [s.next(i % mix["sessions"]) for i in range(n)]
    return firsts, rest


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    """A seed fixes the token ids; every seed serves the same lengths in
    the same order."""
    mix = _mix(name)
    seed = 2**31 + 12345
    assert _draw(mix, seed) == _draw(mix, seed)
    assert _draw(mix, seed) != _draw(mix, seed + 1)
    lengths = lambda d: [(len(p), a) for p, a in d[0] + d[1]]
    assert lengths(_draw(mix, seed)) == lengths(_draw(mix, 3))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_fit_the_cache(name):
    mix = _mix(name)
    firsts, rest = _draw(mix, 7)
    for prompt, answer in rest:
        assert mix["prompt"]["min"] <= len(prompt) <= mix["prompt"]["max"]
        assert mix["answer"]["min"] <= answer <= mix["answer"]["max"]
        assert all(1 <= t < 1000 for t in prompt)
        assert len(prompt) + answer <= mix["max_seq"] - 1
    for prompt, answer in firsts:
        assert 1 <= len(prompt) <= mix["prompt"]["max"]
        assert 1 <= answer <= mix["answer"]["max"]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_serves_the_same_deck(name):
    mix = _mix(name)
    k = mix["deck"]
    for seed in (1, 2**31 + 3):
        s = Sessions(mix, seed, vocab=1000)
        got = sorted(s.next(0)[1] for _ in range(k))
        assert got == sorted(deck(mix["answer"], k))


@pytest.mark.parametrize("name", MIXES)
def test_first_requests_start_out_of_phase(name):
    """Residual first requests: the sessions' remaining work differs, so
    they do not all finish (or all prefill) together."""
    mix = _mix(name)
    firsts, _ = _draw(mix, 11)
    chunk = mix["prefill_chunk"]
    left = [-(-len(p) // chunk) + a for p, a in firsts]
    assert len(set(left)) == len(left)
    full = -(-mix["prompt"]["max"] // chunk) + mix["answer"]["max"]
    assert max(left) < full


def test_deck_follows_the_truncated_lognormal():
    """The deck's lengths are the distribution's quantiles: sorted, inside
    the truncation, split evenly about the median, with the long tail on
    the right."""
    dist = {"median": 100, "sigma": 0.8, "min": 10, "max": 1000}
    d = deck(dist, 16)
    assert d == sorted(d) and 10 <= d[0] and d[-1] <= 1000
    assert d[7] < 100 < d[8]
    assert d[-1] - 100 > 100 - d[0]
    assert sum(d) / len(d) > 100
