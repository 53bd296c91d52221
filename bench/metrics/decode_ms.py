"""Device time of the model's decode (the `paged_decode` scope: every
layer, the paged-attention kernel, the LM head and argmax) per decode
step, from the trace. Moves tpot_p90_ms."""


def read(run):
    if not run.steps:
        return None
    return 1e3 * run.scope_s("paged_decode") / run.steps
