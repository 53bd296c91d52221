"""Held experts the routing needs per layer of a decode step: the
engine's `expert_reads` counter (held experts with at least one routed
token, summed over the layers) between the first and the last
`experts` journal record of the traced span, over the steps between
them and the layers. None for a program without the counter.

It describes the routing, not what the step reads: up to
`models.moe.DENSE_MAX_TOKENS` tokens the decode runs every held
expert's weights whatever the routing. It is the floor of the expert
weight sets a step must read, the experts' part of
routed_step_hbm_share's yardstick, and it moves tpot_p90_ms only once
a decode path reads the routed experts' weights alone."""

import routed_counts


def read(run):
    rates = routed_counts.expert_rates(run.engine_spans)
    if rates is None:
        return None
    return rates[0] / run.cfg["num_hidden_layers"]
