"""End-to-end metrics from what the serving loop recorded.

Tokens reach the host only at the end of a fused chunk of decode
steps, so every time here is the end of a chunk, on the host's clock,
in seconds after the window opened:

  ttft   a request's due time (open loop) to the end of the chunk that
         decoded its first token. A request due inside the window that
         never gets one counts as missing: +inf, above every limit.
  tpot   a request's mean time per decode step between the end of the
         chunk of its first token and the end of the last chunk inside
         the window that decoded one of its tokens. The request decodes
         one token at every step in between, so this is its mean gap
         between tokens. Requests whose tokens inside the window all
         came in one chunk have no gap to measure and are left out.
  tokens_per_s   tokens decoded by chunks that ended inside the window,
         divided by the window.

Percentiles are nearest-rank: the smallest value with at least q of
the sample at or below it.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

from traffic import Request


def percentile(values: Sequence[float], q: float) -> float:
    if not values:
        return float("nan")
    xs = sorted(values)
    return xs[max(math.ceil(q * len(xs)) - 1, 0)]


def ttfts(reqs: List[Request], seconds: float) -> List[float]:
    """TTFT of every request due inside the window (+inf if missing)."""
    return [
        (r.t_first - r.due) if r.steps_first >= 0 else math.inf
        for r in reqs if r.due < seconds
    ]


def tpots(reqs: List[Request]) -> List[float]:
    return [
        (r.t_last_w - r.t_first) / (r.steps_last_w - r.steps_first)
        for r in reqs
        if r.steps_first >= 0 and r.steps_last_w > r.steps_first
    ]


def tokens_per_s(chunks: List[dict], seconds: float) -> float:
    return sum(c["tokens"] for c in chunks if c["t_end"] <= seconds) / seconds


def end_to_end(reqs: List[Request], chunks: List[dict],
               seconds: float) -> Dict[str, float]:
    """Every end-to-end metric the timeline can give, in ms and
    tokens/s; the cell keeps those it reports."""
    t = ttfts(reqs, seconds)
    return {
        "tokens_per_s": tokens_per_s(chunks, seconds),
        "tpot_p90_ms": 1e3 * percentile(tpots(reqs), 0.9),
        "ttft_p50_ms": 1e3 * percentile(t, 0.5),
        "ttft_p90_ms": 1e3 * percentile(t, 0.9),
    }
