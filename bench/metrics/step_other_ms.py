"""Device time of `engine_run` outside its four named scopes (the
pool's relayout copies and the scan's glue) per decode step, from the
trace. Moves tpot_p90_ms."""

from devtrace import SCOPES


def read(run):
    if not run.steps:
        return None
    other = run.program_s("engine_run") - sum(run.scope_s(s) for s in SCOPES)
    return 1e3 * other / run.steps
