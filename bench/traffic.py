"""One generator for every traffic mix: requests from a parameter file
and a seed.

A mix file (`bench/traffic/<name>.json`) gives:

  source       where the distribution comes from
  prompt       {"median", "sigma", "min"}: lognormal length of the
               prompt in tokens and the sigma of its log
  output       the same for the number of tokens to generate
  cuts         {"prompt_max" / "output_max": {"tokens", "cause"}}: the
               caps that the system under test forces on the source's
               distribution, each with its cause; a length without a
               cut is not capped
  block        requests per stratified block (below)
  max_lane_pages, max_out   the engine's lane geometry for this mix

Lengths and gaps are drawn by stratification, not freely: each block
of `block` requests takes the lengths at the quantiles (i + 0.5) /
block of their distributions, and gaps at the same quantiles of the
exponential distribution scaled to a mean of exactly 1 / rate. The
seed permutes each block and draws the token ids. So every seed offers
the same sizes and the same arrivals, in another order, and a whole
block always ends at the same time: runs with different seeds differ
by the order of the work, not by its amount.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    """One request as offered, and what the benchmark saw of it."""

    rid: int
    due: float          # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int
    # filled in by the serving loop
    submitted: float = float("nan")
    t_first: float = float("nan")   # end of the chunk of its first token
    steps_first: int = -1           # engine steps done at that chunk end
    n_first: int = 0                # tokens seen at that chunk end
    n_seen: int = 0                 # tokens seen so far at chunk ends
    t_last_w: float = float("nan")  # last chunk inside the window with a token
    steps_last_w: int = -1
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def _lognormal_grid(p: dict, n: int, cut: dict | None) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(np.log(p["median"]) + p["sigma"] * z)
    hi = cut["tokens"] if cut else None
    return np.clip(np.rint(x), p["min"], hi).astype(np.int64)


def _exp_grid(n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g / g.mean()


def length_set(mix: dict) -> dict:
    """The sorted prompt and output lengths of one block: every run of
    the mix draws from exactly these."""
    n = int(mix["block"])
    cuts = mix.get("cuts", {})
    return {k: np.sort(_lognormal_grid(mix[k], n, cuts.get(f"{k}_max")))
            for k in ("prompt", "output")}


def generate(
    mix: dict, seed: int, n_requests: int, vocab: int, rate_per_s: float,
) -> List[Request]:
    """`n_requests` requests of the mix, in due order: Poisson arrivals,
    open loop on the wall clock, at a mean gap of 1 / `rate_per_s`."""
    rng = np.random.default_rng(seed)
    n = int(mix["block"])
    grid = length_set(mix)
    gaps = _exp_grid(n)
    out: List[Request] = []
    t = 0.0
    while len(out) < n_requests:
        prompts = rng.permutation(grid["prompt"])
        outputs = rng.permutation(grid["output"])
        order = rng.permutation(n)
        for i in range(n):
            if len(out) == n_requests:
                break
            t += gaps[order[i]] / rate_per_s
            plen = int(prompts[i])
            out.append(Request(
                rid=len(out),
                due=t,
                prompt=rng.integers(0, vocab, size=plen, dtype=np.int32),
                max_new=int(outputs[i]),
            ))
    return out


def requests_for(mix: dict, seed: int, seconds: float, vocab: int,
                 rate_per_s: float) -> List[Request]:
    """Every request due inside a window of `seconds`, and one block
    beyond."""
    n = int(mix["block"])
    count = int(np.ceil(rate_per_s * seconds / n) + 1) * n
    return generate(mix, seed, count, vocab, rate_per_s)
