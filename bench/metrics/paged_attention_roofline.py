"""Share of its roofline the paged-attention kernel reaches: the least
time the chip could take for the work the live contexts need (each
kernel call bound by the larger of its FLOPs at peak FLOP/s and its
bytes at peak HBM bandwidth, counted by counts.attn_kernel_work) over
the kernel's device time in the trace. Moves tpot_p90_ms."""

import counts


def read(run):
    t = run.kernel_s
    if not t or not run.steps:
        return None
    pf, bw = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    least = 0.0
    for attended in run.attended_per_step():
        work = [counts.attn_kernel_work(run.cfg, a) for a in attended]
        least += max(sum(f for f, _ in work) / pf, sum(b for _, b in work) / bw)
    return 100.0 * least / t
