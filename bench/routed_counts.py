"""The work a routed decode step needs, from the engine's expert
counters: the yardstick for the whole-step shares of a configuration
whose MLPs are routed experts (`mlp_layer_types` all "sparse").

Which experts a step needs depends on the routing, so the counts come
from the program (the bytes counted are those needed: the decode reads
every held expert up to `models.moe.DENSE_MAX_TOKENS` tokens): the engine's `experts` journal records (one at every
drain: the running `expert_reads`, held experts with a routed token
summed over the layers, and `expert_tokens`, the (token, held expert)
pairs routed, of the steps before `step`). Everything else is
counted from the configuration file and the lanes' live contexts, as
`counts.py` counts a dense step, attention by layer kind through
`counts.attended_positions`.

Sizes: `moe_intermediate_size` is the width of one expert; the router
has `source_values.num_experts` outputs (every expert of the published
layer), of which the file's `num_experts` are held here.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from counts import BYTES, attended_positions


def router_width(cfg: dict) -> int:
    return int(cfg.get("source_values", {}).get("num_experts",
                                                cfg["num_experts"]))


def dense_params(cfg: dict) -> int:
    """Weights every decode token touches: q, k, v, o and the router of
    each layer, and the LM head."""
    d, H, Hkv, D = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    layer = 2 * d * H * D + 2 * d * Hkv * D + d * router_width(cfg)
    return cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * d


def expert_params(cfg: dict) -> int:
    """Weights of one expert: its three SwiGLU matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def step_work(cfg: dict, attended: Iterable[int], expert_reads: float,
              expert_tokens: float) -> Tuple[float, float]:
    """Model FLOPs and HBM bytes of one decode step for the lanes whose
    contexts hold `attended` positions, with `expert_reads` expert
    weight sets needed and `expert_tokens` (token, expert) pairs routed
    over the layers: 2 FLOPs per weight per token (per pair for an
    expert) plus attention; the dense weights and those experts read
    once, the K and V each token attends to by layer kind read, the new
    K and V written."""
    L, H, Hkv, D = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    b = BYTES[cfg["torch_dtype"]]
    P, X = dense_params(cfg), expert_params(cfg)
    flops = 2.0 * X * expert_tokens
    byts = float(P * b) + expert_reads * X * b
    for a in attended:
        pos = attended_positions(cfg, a)
        flops += 2.0 * P + 4.0 * pos * H * D
        byts += 2.0 * pos * Hkv * D * b + 2.0 * Hkv * D * b * L
    return flops, byts


def expert_rates(records: List[dict]) -> Optional[Tuple[float, float]]:
    """Expert reads and pairs per decode step between the first and the
    last `experts` record of a journal slice; None with fewer than two
    records a step apart (a program without the counters has none)."""
    recs = [r for r in records if r.get("phase") == "experts"]
    if len(recs) < 2 or recs[-1]["step"] <= recs[0]["step"]:
        return None
    steps = recs[-1]["step"] - recs[0]["step"]
    return ((recs[-1]["expert_reads"] - recs[0]["expert_reads"]) / steps,
            (recs[-1]["expert_tokens"] - recs[0]["expert_tokens"]) / steps)


def traced_work(run) -> Optional[Tuple[float, float]]:
    """FLOPs and bytes of the traced decode steps (`run`, a
    `devtrace.RunView`): each step's live contexts, and the expert
    counts at the traced span's mean rate per step."""
    rates = expert_rates(run.engine_spans)
    if rates is None:
        return None
    flops = byts = 0.0
    for attended in run.attended_per_step():
        f, b = step_work(run.cfg, attended, *rates)
        flops, byts = flops + f, byts + b
    return flops, byts
