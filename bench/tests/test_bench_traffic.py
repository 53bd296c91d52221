"""The traffic generator: deterministic for a seed, inside its bounds,
and the same work for every seed."""

import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import traffic  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


def _mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.generate(mix, 2**31 + 11, 96, 50304, rate_per_s=7.0)
    b = traffic.generate(mix, 2**31 + 11, 96, 50304, rate_per_s=7.0)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == [
        (r.due, r.max_new, r.prompt.tolist()) for r in b]
    c = traffic.generate(mix, 5, 96, 50304, rate_per_s=7.0)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_ids_within_bounds(name):
    mix = _mix(name)
    vocab = 1000
    reqs = traffic.generate(mix, 3, 4 * mix["block"], vocab, rate_per_s=9.0)
    cuts = mix.get("cuts", {})
    for r in reqs:
        for kind, n in (("prompt", len(r.prompt)), ("output", r.max_new)):
            assert mix[kind]["min"] <= n
            if f"{kind}_max" in cuts:
                assert n <= cuts[f"{kind}_max"]["tokens"]
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < vocab
        # every request fits the mix's lane geometry
        pages = -(-(len(r.prompt) + r.max_new) // 16)
        assert pages <= mix["max_lane_pages"] and r.max_new <= mix["max_out"]
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_work(name):
    """Whole blocks hold the same lengths, and end at the same time."""
    mix = _mix(name)
    n = mix["block"]
    runs = [traffic.generate(mix, s, 3 * n, 50304, rate_per_s=6.0)
            for s in (1, 2, 2**31 + 99)]
    for reqs in runs[1:]:
        assert sorted(len(r.prompt) for r in reqs) == sorted(
            len(r.prompt) for r in runs[0])
        assert sorted(r.max_new for r in reqs) == sorted(
            r.max_new for r in runs[0])
        assert reqs[-1].due == pytest.approx(runs[0][-1].due)
    assert [r.max_new for r in runs[0]] != [r.max_new for r in runs[1]]


def test_poisson_rate_is_exact_per_block():
    mix = _mix("chat-poisson")
    reqs = traffic.generate(mix, 8, mix["block"], 100, rate_per_s=4.0)
    assert reqs[-1].due == pytest.approx(mix["block"] / 4.0)
    gaps = np.diff([0.0] + [r.due for r in reqs])
    assert gaps.min() > 0


@pytest.mark.parametrize("name", MIXES)
def test_every_cut_names_its_cause_and_caps_the_tail(name):
    """A cut caps the source's distribution only where it says so: the
    longest lengths of a block sit at the cap, the others below it."""
    mix = _mix(name)
    grid = traffic.length_set(mix)
    for key, cut in mix.get("cuts", {}).items():
        kind = key[:-len("_max")]
        assert key == f"{kind}_max" and kind in grid
        assert cut["cause"].strip()
        assert grid[kind].max() == cut["tokens"]
        uncut = traffic.length_set(dict(mix, cuts={}))[kind]
        assert uncut.max() > cut["tokens"]
        assert (grid[kind] == np.minimum(uncut, cut["tokens"])).all()


def test_requests_for_covers_the_window():
    mix = _mix("chat-poisson")
    reqs = traffic.requests_for(mix, 1, 10.0, 100, 8.0)
    assert reqs[-1].due > 10.0
    assert len(reqs) % mix["block"] == 0
