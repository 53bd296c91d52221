"""Find a chat cell's knee: the highest offered rate at which the queue
of requests waiting for their first token does not grow over a window.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> RATE [RATE ...]

One process builds the cell's engine once and offers its traffic at
each rate in turn, for `--seconds` each. For every rate it prints the
end-to-end metrics and the backlog (requests due and still without a
first token) at each quarter of the window. The benchmark itself never
searches: the rate found here, times 0.8, is written into the cell's
file (`bench/cells/<cell>.json`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
import spec  # noqa: E402
import timeline  # noqa: E402
import traffic  # noqa: E402


def backlog(reqs, t: float) -> int:
    return sum(1 for r in reqs if r.due <= t and not (r.t_first <= t))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("rates", type=float, nargs="+")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    run.init_jax(cell.chips, True, run.CACHE_DIR)
    import serve_loop
    from repro.launch.serve import CHUNK

    eng, arch, _, _ = run.build(cell, args.seed)
    for i, rate in enumerate(args.rates):
        reqs = traffic.requests_for(cell.traffic, args.seed + i, args.seconds,
                                    arch.vocab_size, rate)
        win = serve_loop.serve(eng, reqs, args.seconds, CHUNK)
        m = timeline.end_to_end(win.reqs, win.chunks, args.seconds)
        quarters = [backlog(win.reqs, args.seconds * q / 4) for q in (1, 2, 3, 4)]
        print(json.dumps({"rate_per_s": rate, **m, "backlog_at_quarters": quarters,
                          "grace_s": win.grace_s}), flush=True)


if __name__ == "__main__":
    main()
