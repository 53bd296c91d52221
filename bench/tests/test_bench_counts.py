"""The work counts (`bench/counts.py`): a configuration without
`layer_types` counts every layer over the whole context, to the bit as
it always did; one with sliding layers counts at most the window on
those layers alone."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import counts  # noqa: E402

CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs")))
CONTEXTS = [1, 15, 16, 17, 334, 1020, 1024, 1025, 2048, 2560, 4096, 8192,
            131072]


def _cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _full_attn_work(cfg, attended):
    """Every layer over the whole context: the counts before layer kinds."""
    L, d, H, Hkv, D, ff, V, b = counts._dims(cfg)
    flops = 4.0 * attended * H * D * L
    byts = (2.0 * attended * Hkv * D * b + 2.0 * H * D * b) * L
    return flops, byts


def _full_step_work(cfg, attended):
    L, d, H, Hkv, D, ff, V, b = counts._dims(cfg)
    P = counts.decode_params(cfg)
    flops = 0.0
    byts = float(P * b)
    for a in attended:
        flops += 2.0 * P + 4.0 * a * H * D * L
        byts += 2.0 * a * Hkv * D * b * L + 2.0 * Hkv * D * b * L
    return flops, byts


@pytest.mark.parametrize("name", CONFIGS)
def test_without_layer_types_every_layer_counts_the_whole_context(name):
    cfg = _cfg(name)
    assert "layer_types" not in cfg
    for a in CONTEXTS:
        assert counts.attn_kernel_work(cfg, a) == _full_attn_work(cfg, a)
    for lanes in ([], [1020], CONTEXTS, [2560] * 16, list(range(300, 2400, 131))):
        assert counts.step_work(cfg, lanes) == _full_step_work(cfg, lanes)


def _windowed():
    """Four layers, three sliding over 1024 positions to one full."""
    return dict(_cfg(CONFIGS[0]), num_hidden_layers=4, sliding_window=1024,
                layer_types=["sliding_attention"] * 3 + ["full_attention"])


@pytest.mark.parametrize("attended", CONTEXTS)
def test_sliding_layers_count_at_most_the_window(attended):
    cfg = _windowed()
    L, d, H, Hkv, D, ff, V, b = counts._dims(cfg)
    spans = [min(attended, 1024)] * 3 + [attended]
    assert counts.attended_positions(cfg, attended) == sum(spans)
    flops, byts = counts.attn_kernel_work(cfg, attended)
    assert flops == sum(4.0 * s * H * D for s in spans)
    assert byts == sum(2.0 * s * Hkv * D * b + 2.0 * H * D * b for s in spans)
    sflops, sbytes = counts.step_work(cfg, [attended])
    P = counts.decode_params(cfg)
    assert sflops == 2.0 * P + flops
    assert sbytes == P * b + sum(2.0 * s * Hkv * D * b for s in spans) \
        + 2.0 * Hkv * D * b * L


def test_a_windowed_model_at_8k_counts_its_full_and_sliding_layers_apart():
    """28 layers, one full to three sliding over 1,024 positions: a
    token at 8,192 positions attends over 7 * 8192 + 21 * 1024."""
    cfg = dict(_cfg(CONFIGS[0]), num_hidden_layers=28, sliding_window=1024,
               layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 7)
    assert counts.attended_positions(cfg, 8192) == 78848
    assert counts.attended_positions(dict(cfg, layer_types=None), 8192) == (
        28 * 8192)


@pytest.mark.parametrize("kinds", [["full_attention"] * 3,
                                   ["full_attention"] * 3 + ["chunked"]])
def test_layer_types_must_name_each_layer_by_a_known_kind(kinds):
    cfg = dict(_windowed(), layer_types=kinds)
    with pytest.raises(ValueError, match="layer_types"):
        counts.attended_positions(cfg, 100)
