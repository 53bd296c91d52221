"""Device time of the allocator inside the engine step (the
`nbbs_alloc` and `retire_free` scopes) per decode step, from the
trace. Moves tpot_p90_ms."""


def read(run):
    if not run.steps:
        return None
    return 1e6 * (run.scope_s("nbbs_alloc") + run.scope_s("retire_free")) / run.steps
