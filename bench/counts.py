"""The work a decode step needs, counted from the configuration and the
lanes' live contexts: the yardstick for roofline and peak shares.

Only the work the algorithm needs counts: the live context of each
lane, never the table slots, head padding or head masks a kernel may
spend on top. A kernel that wastes less then reads as a larger share
of its roofline, and no share can pass 100% unless the time leaves out
part of the work.

Sizes come from a configuration file (`bench/configs/<name>.json`);
bytes are those of the served dtype (2 for bfloat16). Where the file
has Hugging Face's `layer_types` and `sliding_window`, a
`sliding_attention` layer attends over at most the window's newest
positions; without them every layer attends over the whole context.
"""

from __future__ import annotations

from typing import Iterable, Tuple

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"],
            BYTES[cfg["torch_dtype"]])


def attended_positions(cfg: dict, attended: int) -> int:
    """Positions one token attends over, summed over the layers: the
    whole context at a full layer, at most `sliding_window` of it at a
    sliding one."""
    L = cfg["num_hidden_layers"]
    kinds = cfg.get("layer_types")
    if kinds is None:
        return attended * L
    if len(kinds) != L or not set(kinds) <= {"full_attention",
                                             "sliding_attention"}:
        raise ValueError(f"layer_types must give one of full_attention, "
                         f"sliding_attention for each of {L} layers")
    window = min(attended, cfg["sliding_window"])
    n_sliding = kinds.count("sliding_attention")
    return attended * (L - n_sliding) + window * n_sliding


def layer_params(cfg: dict) -> int:
    """Weights of one block: q, k, v, o and the three SwiGLU matrices."""
    L, d, H, Hkv, D, ff, V, _ = _dims(cfg)
    return 2 * d * H * D + 2 * d * Hkv * D + 3 * d * ff


def decode_params(cfg: dict) -> int:
    """Weights a decode token touches: every block and the LM head
    (not the embedding table, of which it reads one row)."""
    L, d, *_ = _dims(cfg)
    return L * layer_params(cfg) + cfg["vocab_size"] * d


def attn_kernel_work(cfg: dict, attended: int) -> Tuple[float, float]:
    """FLOPs and bytes of paged decode attention for one token whose
    context holds `attended` positions, summed over the layers: q.k and
    p.v (4 * positions * Hq * D FLOPs), the K and V it attends to read
    once, q read and the output written."""
    L, d, H, Hkv, D, ff, V, b = _dims(cfg)
    pos = attended_positions(cfg, attended)
    flops = 4.0 * pos * H * D
    byts = 2.0 * pos * Hkv * D * b + 2.0 * H * D * b * L
    return flops, byts


def step_work(cfg: dict, attended: Iterable[int]) -> Tuple[float, float]:
    """Model FLOPs and HBM bytes one decode step needs for the lanes
    whose contexts hold `attended` positions: 2 FLOPs per weight per
    token plus attention; all weights read once, the K and V each token
    attends to read, the new K and V written."""
    L, d, H, Hkv, D, ff, V, b = _dims(cfg)
    P = decode_params(cfg)
    flops = 0.0
    byts = float(P * b)
    for a in attended:
        pos = attended_positions(cfg, a)
        flops += 2.0 * P + 4.0 * pos * H * D
        byts += 2.0 * pos * Hkv * D * b + 2.0 * Hkv * D * b * L
    return flops, byts
