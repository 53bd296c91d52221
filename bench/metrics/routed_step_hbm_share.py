"""HBM bytes the traced decode steps of a routed model need
(routed_counts.step_work: the dense weights and the LM head once a
step, each held expert with a routed token once, the K and V each
token attends to by layer kind, the new K and V written) over
`engine_run`'s device time at the chip's peak HBM bandwidth. Moves
tpot_p90_ms.

The bytes needed, not the bytes moved: the decode reads every held
expert's weights at up to `models.moe.DENSE_MAX_TOKENS` tokens, more
than the routed ones this counts, so a path that reads the routed
experts alone raises the share."""

import routed_counts


def read(run):
    t = run.program_s("engine_run")
    work = routed_counts.traced_work(run)
    if not t or work is None:
        return None
    return 100.0 * work[1] / (t * run.peaks["hbm_bytes_per_s"])
