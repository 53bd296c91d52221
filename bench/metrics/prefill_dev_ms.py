"""Device time of the admission programs (admit_pages, serve_prefill,
prefill_insert) per admitted request, from the trace. Moves
ttft_p50_ms."""

PROGRAMS = ("admit_pages", "serve_prefill", "prefill_insert")


def read(run):
    n = run.admitted
    if not n:
        return None
    return 1e3 * sum(run.program_s(p) for p in PROGRAMS) / n
