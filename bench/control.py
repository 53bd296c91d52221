"""Readings that set a cell's check limit: the program's logit gap and
the control's, on several seeds, in one process, each judged by the
benchmark's own comparison.

    python3 bench/control.py --workload <cell> --seconds <s> SEED [SEED ...]

For each seed it builds the engine, serves the cell's own traffic for
a short window at the cell's load (as `run.py` does), reads the exact
counts of that window, and runs the configuration's plain reference
(`spec.reference`) over the same sample of served requests that a
benchmark run compares, with the control beside it: for
`bench/reference.py`, the reference with its weights rounded to float8
e4m3. Both go through `run.compare` with the cell's limits, the
program's gap in one and the control's in its place in the other, and
it prints, per seed, both gaps and both verdicts: the program's
`correct` has to read true and the control's false. The
lower reading of the limit is the largest program gap over a dozen
seeds or more, the upper one the smallest control gap (PERF.md gives
both and the limit). The benchmark's own runs do not run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402


def readings(cell, seed: int, seconds: float) -> dict:
    """One seed: serve, sample as a benchmark run does, read both gaps."""
    import serve_loop
    from repro.launch.serve import CHUNK

    eng, arch, _, _ = run.build(cell, seed)
    reqs = traffic.requests_for(cell.traffic, seed, seconds, arch.vocab_size,
                                cell.cell["rate_per_s"])
    win = serve_loop.serve(eng, reqs, seconds, CHUNK)
    counts = run.window_counts(eng, win, cell.config["num_pages"], seconds)
    del eng
    gc.collect()
    check = cell.cell["check"]
    gaps = run.reference_gaps(win, cell.config, seed, check, control=True)
    program = run.compare(gaps["gap"], gaps["tokens"], check, counts)
    ctl = run.compare(gaps["control_gap"], gaps["tokens"], check, counts)
    return {"seed": seed, "correct": run.is_correct(program),
            "control_correct": run.is_correct(ctl), "counts": counts, **gaps}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    run.init_jax(cell.chips, True, run.CACHE_DIR)
    rows = []
    for seed in args.seeds:
        rows.append(readings(cell, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "program_gap_max": max(r["gap"] for r in rows),
        "control_gap_min": min(r["control_gap"] for r in rows),
        "program_correct_all": all(r["correct"] for r in rows),
        "control_correct_any": any(r["control_correct"] for r in rows),
        "seeds": args.seeds}), flush=True)


if __name__ == "__main__":
    main()
