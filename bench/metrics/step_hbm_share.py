"""HBM bytes the traced decode steps need (counts.step_work: all
weights once per step, the live K and V read, the new K and V
written) over `engine_run`'s device time at the chip's peak HBM
bandwidth. Moves tpot_p90_ms."""

import counts


def read(run):
    t = run.program_s("engine_run")
    if not t:
        return None
    byts = sum(counts.step_work(run.cfg, a)[1] for a in run.attended_per_step())
    return 100.0 * byts / (t * run.peaks["hbm_bytes_per_s"])
