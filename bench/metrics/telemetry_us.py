"""Device time of the engine step's metric plane (the `telemetry`
scope) per decode step, from the trace. Moves tpot_p90_ms."""


def read(run):
    if not run.steps:
        return None
    return 1e6 * run.scope_s("telemetry") / run.steps
