"""Model FLOPs of the traced decode steps of a routed model
(routed_counts.step_work: 2 per dense weight per token, 2 per expert
weight per routed (token, expert) pair from the engine's counters,
attention by layer kind over the live contexts) over `engine_run`'s
device time at the chip's peak bf16 FLOP/s. Moves tpot_p90_ms."""

import routed_counts


def read(run):
    t = run.program_s("engine_run")
    work = routed_counts.traced_work(run)
    if not t or work is None:
        return None
    return 100.0 * work[0] / (t * run.peaks["bf16_flops_per_s"])
