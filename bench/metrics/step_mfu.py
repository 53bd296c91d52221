"""Model FLOPs of the traced decode steps (counts.step_work: 2 per
weight per token plus attention over the live context) over
`engine_run`'s device time at the chip's peak bf16 FLOP/s. Moves
tpot_p90_ms."""

import counts


def read(run):
    t = run.program_s("engine_run")
    if not t:
        return None
    flops = sum(counts.step_work(run.cfg, a)[0] for a in run.attended_per_step())
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
