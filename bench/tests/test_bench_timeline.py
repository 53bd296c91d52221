"""End-to-end metric arithmetic on a synthetic timeline."""

import math
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import timeline  # noqa: E402
from traffic import Request  # noqa: E402


def _req(rid, due, t_first=None, steps_first=-1, t_last=None, steps_last=-1):
    r = Request(rid=rid, due=due, prompt=np.zeros(4, np.int32), max_new=8)
    if t_first is not None:
        r.t_first, r.steps_first = t_first, steps_first
    if t_last is not None:
        r.t_last_w, r.steps_last_w = t_last, steps_last
    return r


def test_percentile_nearest_rank():
    xs = list(range(1, 11))
    assert timeline.percentile(xs, 0.5) == 5
    assert timeline.percentile(xs, 0.9) == 9
    assert timeline.percentile(xs, 1.0) == 10
    assert timeline.percentile([3.0], 0.9) == 3.0
    assert math.isnan(timeline.percentile([], 0.5))


def test_ttft_counts_from_the_due_time():
    """A stall delays every request due during it: the first token of a
    request due at 1.0 that arrives at 2.0 is one second late, however
    late it was submitted."""
    reqs = [_req(0, 0.1, t_first=0.3, steps_first=8),
            _req(1, 1.0, t_first=2.0, steps_first=16),
            _req(2, 1.1, t_first=2.0, steps_first=16)]
    assert timeline.ttfts(reqs, 10.0) == pytest.approx([0.2, 1.0, 0.9])


def test_missing_first_token_is_infinite_and_late_dues_are_left_out():
    reqs = [_req(0, 0.5, t_first=0.7, steps_first=8), _req(1, 0.9),
            _req(2, 5.0, t_first=5.2, steps_first=16)]
    t = timeline.ttfts(reqs, 4.0)
    assert t[0] == pytest.approx(0.2) and t[1] == math.inf and len(t) == 2
    m = timeline.end_to_end(reqs, [], 4.0)
    assert m["ttft_p90_ms"] == math.inf


def test_tpot_is_time_per_step_between_first_and_last_chunk():
    reqs = [
        # first token at the chunk ending after step 8 (t=0.2), last
        # inside the window at the chunk ending after step 40 (t=1.0)
        _req(0, 0.0, t_first=0.2, steps_first=8, t_last=1.0, steps_last=40),
        # all its window tokens came in one chunk: no gap to measure
        _req(1, 0.0, t_first=0.5, steps_first=24, t_last=0.5, steps_last=24),
    ]
    assert timeline.tpots(reqs) == pytest.approx([0.8 / 32])


def test_tokens_per_s_counts_chunks_ending_inside_the_window():
    chunks = [{"t_end": 0.5, "tokens": 100}, {"t_end": 1.9, "tokens": 60},
              {"t_end": 2.1, "tokens": 1000}]
    assert timeline.tokens_per_s(chunks, 2.0) == pytest.approx(80.0)


def test_pool_occupancy_averages_the_chunk_ends_inside_the_window():
    import spec

    read = spec.load_reader("pool_occupancy")

    class Win:
        seconds = 2.0
        chunks = [{"t_end": 0.5, "pages": 100}, {"t_end": 1.9, "pages": 300},
                  {"t_end": 2.4, "pages": 512}]

    class Run:
        win, cfg = Win(), {"num_pages": 400}

    assert read(Run()) == pytest.approx(50.0)
    Run.win.chunks = []
    assert read(Run()) is None
