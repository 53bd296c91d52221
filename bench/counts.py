"""The work a decode step needs, counted from the configuration and the
lanes' live contexts: the yardstick for roofline and peak shares.

Only the work the algorithm needs counts: the live context of each
lane, never the table slots, head padding or head masks a kernel may
spend on top. A kernel that wastes less then reads as a larger share
of its roofline, and no share can pass 100% unless the time leaves out
part of the work.

Sizes come from a configuration file (`bench/configs/<name>.json`);
bytes are those of the served dtype (2 for bfloat16).
"""

from __future__ import annotations

from typing import Iterable, Tuple

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"],
            BYTES[cfg["torch_dtype"]])


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
    """FLOPs and bytes of paged decode attention for one token that
    attends over `attended` positions, summed over the layers:
    q.k and p.v (4 * ctx * Hq * D FLOPs), the live K and V read once,
    q read and the output written."""
    L, d, H, Hkv, D, ff, V, b = _dims(cfg)
    flops = 4.0 * attended * H * D * L
    byts = (2.0 * attended * Hkv * D * b + 2.0 * H * D * b) * L
    return flops, byts


def step_work(cfg: dict, attended: Iterable[int]) -> Tuple[float, float]:
    """Model FLOPs and HBM bytes one decode step needs for the lanes
    whose tokens attend over `attended` positions: 2 FLOPs per weight
    per token plus attention; all weights read once, the live K and V
    read, the new K and V written."""
    L, d, H, Hkv, D, ff, V, b = _dims(cfg)
    P = decode_params(cfg)
    flops = 0.0
    byts = float(P * b)
    for a in attended:
        flops += 2.0 * P + 4.0 * a * H * D * L
        byts += 2.0 * a * Hkv * D * b * L + 2.0 * Hkv * D * b * L
    return flops, byts
