"""Mellum2-12B-A2.5B in the benchmark: the served engine against the
configuration's own reference (`references/mellum2-12b.py`) at a small
size, the configuration file tied to the program's registry entry, and
the readers of the cell's per-layer metrics on a synthetic run."""

import dataclasses
import importlib.util
import json
import os
import sys
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402
import spec  # noqa: E402

SEED = 2**31 + 29


def _file():
    with open(os.path.join(BENCH, "configs", "mellum2-12b.json")) as f:
        return json.load(f)


def _tiny_file():
    """The configuration file at the program's reduced sizes, two whole
    periods of layers deep: window 8, 3 of 8 experts held, top-4."""
    cfg = _file()
    cfg.update(num_hidden_layers=8, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=256,
               moe_intermediate_size=32, num_experts=3,
               num_experts_per_tok=4, sliding_window=8,
               layer_types=cfg["layer_types"][:8],
               mlp_layer_types=cfg["mlp_layer_types"][:8],
               source_values={"num_experts": 8})
    return cfg


def _tiny_arch():
    from repro.configs import get_config

    return dataclasses.replace(get_config("mellum2-12b").reduced(), n_layers=8)


def _reference():
    path = os.path.join(BENCH, "references", "mellum2-12b.py")
    own = importlib.util.spec_from_file_location("mellum_reference", path)
    mod = importlib.util.module_from_spec(own)
    own.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", params=["reference", "interpret"])
def served(request):
    """Prompts of 3-70 tokens decoded for 12-21 more through
    `JitServeEngine` on 4-token pages, so that contexts cross the
    8-token window at every offset within a page, with the jnp paged
    attention and with the Pallas kernel in interpret mode; the
    70-token prompt's prefill (128 tokens) computes its experts by
    grouped matmul, the rest every held expert for every token. Weights
    from the seed in bf16 (as the served engine stores them), computed
    in float32."""
    from repro.models import init_params
    from repro.serve.engine import Request
    from repro.serve.jit_engine import JitServeEngine

    arch = _tiny_arch()
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
        init_params(arch, jax.random.PRNGKey(SEED)))
    eng = JitServeEngine(arch, params, num_pages=128, page_tokens=4,
                         max_batch=4, max_lane_pages=32, max_out=24,
                         dtype=jnp.float32, impl=request.param)
    rng = np.random.default_rng(3)
    prompts = {i: rng.integers(0, 256, n).astype(np.int32)
               for i, n in enumerate((3, 9, 27, 70))}
    for i, p in prompts.items():
        eng.submit(Request(i, p, 12 + 3 * i))
    eng.run_to_completion(chunk=4)
    return [{"prompt": prompts[i],
             "served": np.asarray(eng.completed[i].out_tokens, np.int32)}
            for i in sorted(prompts)]


def test_engine_serves_the_reference_s_tokens(served):
    """Every token the engine served, prefill and then paged decode
    across the window, is the reference's first choice up to 1e-4 of
    logit: float32 on both sides, the engine summing in another order
    (online softmax over pages, experts grouped) moves a logit by about
    1e-6. Each mechanism is seen by the comparison: the same tokens
    read against a reference without the window, without YaRN, or with
    another share of experts lie far outside that."""
    ref = _reference()
    cfg = _tiny_file()
    assert max(len(s["prompt"]) + len(s["served"]) for s in served) > 40
    got = ref.logit_gaps(cfg, SEED, served, (4, 128))
    assert got["tokens"] == sum(len(s["served"]) for s in served) == 66
    assert got["gap"] <= 1e-4, got
    rope = dict(cfg["rope_parameters"],
                full_attention=cfg["rope_parameters"]["sliding_attention"])
    for other in (dict(cfg, layer_types=["full_attention"] * 8),
                  dict(cfg, rope_parameters=rope),
                  dict(cfg, experts_held_from=1)):
        assert ref.logit_gaps(other, SEED, served, (4, 128))["gap"] > 1e-2


def test_the_control_reads_further_from_the_reference(served):
    """The control (every weight in float8 e4m3) strays from the
    reference by far more than the program does."""
    got = _reference().logit_gaps(_tiny_file(), SEED, served, (4, 128),
                                  control=True)
    assert got["gap"] <= 1e-4 < 1e-2 < got["control_gap"]
    assert got["control_argmax_share"] < got["argmax_share"] == 1.0


def test_the_file_ties_to_the_registry_entry():
    """`run.arch_config` builds, from the file, the registry's model at
    the file's sizes: the window and the rotary kind of each layer from
    `layer_types` and `rope_parameters`, the published router over
    `source_values.num_experts` with the file's `num_experts` held from
    `experts_held_from`, and the expert width from
    `moe_intermediate_size`; `d_ff` stays the dense width."""
    from repro.models.transformer import rope_arrays, window_array

    f = _file()
    arch = run.arch_config(f)
    L = f["num_hidden_layers"]
    assert arch.n_layers == L == len(f["layer_types"]) == 28
    windows = [f["sliding_window"] if k == "sliding_attention" else 0
               for k in f["layer_types"]]
    assert np.asarray(window_array(arch)).tolist() == windows
    rope = f["rope_parameters"]
    yarn = rope["full_attention"]
    assert yarn["rope_type"] == "yarn"
    assert rope["sliding_attention"] == {"rope_type": "default",
                                         "rope_theta": yarn["rope_theta"]}
    assert arch.rope_theta == f["rope_theta"] == yarn["rope_theta"]
    y = arch.yarn
    assert (y.factor, y.original_max_position_embeddings, y.beta_fast,
            y.beta_slow, y.attention_factor) == (
        yarn["factor"], yarn["original_max_position_embeddings"],
        yarn["beta_fast"], yarn["beta_slow"], yarn["attention_factor"])
    kinds = [arch.rope_pattern[i % len(arch.rope_pattern)] for i in range(L)]
    assert kinds == ["yarn" if k == "full_attention" else "plain"
                     for k in f["layer_types"]]
    _, scale = rope_arrays(arch)
    assert (np.asarray(scale) > 1).tolist() == [
        k == "full_attention" for k in f["layer_types"]]
    assert set(f["mlp_layer_types"]) == {"sparse"} and f["norm_topk_prob"]
    assert arch.n_experts == f["source_values"]["num_experts"] == 64
    assert arch.held_experts == f["num_experts"] == 16
    assert arch.expert_start == f["experts_held_from"] == 0
    assert arch.top_k == f["num_experts_per_tok"] == 8
    assert arch.expert_d_ff == f["moe_intermediate_size"] == 896
    assert arch.d_ff == f["intermediate_size"] == 7168
    assert f["reduced"] == ["num_experts"]
    assert not arch.tie_embeddings and arch.vocab_size == 98304
    assert arch.param_count() == 3_826_188_288


def _experts_record(step, reads, tokens):
    return {"phase": "experts", "t0": 0.0, "t1": 0.0, "step0": step,
            "step1": step, "step": step, "expert_reads": reads,
            "expert_tokens": tokens}


def _synthetic_run(records):
    """Four traced steps of two lanes, 0.2 s of engine_run, 0.05 s of
    kernel, and the engine's expert records 16 steps apart."""
    steps = [[1000 + i, 2048 + i] for i in range(4)]
    times = {"engine_run": 0.2}
    return NS(cfg=_file(), peaks=spec.peaks("TPU v5 lite"), steps=4,
              kernel_s=0.05, engine_spans=records,
              program_s=lambda name: times.get(name, 0.0),
              attended_per_step=lambda: steps)


def _by_hand(steps, reads_per_step, tokens_per_step):
    """FLOPs and bytes of the synthetic steps from the published shape:
    per layer q, k, v, o (2 * 2304 * 4096 + 2 * 2304 * 512) and the
    router (2304 * 64), the LM head (98304 * 2304), an expert of
    3 * 2304 * 896, and attention by kind (7 full, 21 over <= 1024)."""
    dense = 28 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64) + 98304 * 2304
    expert = 3 * 2304 * 896
    flops = byts = 0.0
    for lanes in steps:
        flops += 2.0 * expert * tokens_per_step
        byts += 2.0 * dense + 2.0 * expert * reads_per_step
        for a in lanes:
            pos = 7 * a + 21 * min(a, 1024)
            flops += 2.0 * dense + 4.0 * pos * 32 * 128
            byts += 2.0 * pos * 4 * 128 * 2 + 2.0 * 4 * 128 * 2 * 28
    return flops, byts


def test_the_routed_readers_on_a_synthetic_run():
    recs = [_experts_record(100, 5_000, 20_000),
            {"phase": "engine.sync", "t0": 0.0, "t1": 0.0},
            _experts_record(108, 8_584, 32_800),
            _experts_record(116, 12_168, 45_600)]
    r = _synthetic_run(recs)
    reads, tokens = (12_168 - 5_000) / 16, (45_600 - 20_000) / 16
    assert spec.load_reader("expert_reads")(r) == pytest.approx(
        reads / 28, rel=1e-12)
    flops, byts = _by_hand(r.attended_per_step(), reads, tokens)
    assert spec.load_reader("routed_step_mfu")(r) == pytest.approx(
        100 * flops / (0.2 * 197e12), rel=1e-12)
    assert spec.load_reader("routed_step_hbm_share")(r) == pytest.approx(
        100 * byts / (0.2 * 819e9), rel=1e-12)
    import counts

    least = sum(max(sum(counts.attn_kernel_work(r.cfg, a)[0] for a in s)
                    / 197e12,
                    sum(counts.attn_kernel_work(r.cfg, a)[1] for a in s)
                    / 819e9) for s in r.attended_per_step())
    assert spec.load_reader("paged_attention_window_roofline")(r) == \
        pytest.approx(100 * least / 0.05, rel=1e-12)
    # a window layer counts at most 1024 positions of a 2048-token lane
    assert counts.attended_positions(r.cfg, 2048) == 7 * 2048 + 21 * 1024


@pytest.mark.parametrize("records", [
    [], [_experts_record(100, 5, 9)],
    [_experts_record(100, 5, 9), _experts_record(100, 5, 9)]])
def test_the_routed_readers_read_nothing_without_counts(records):
    """A program without the counters (the parent of the change that
    adds them) journals no `experts` records: the readers return None,
    and raise nothing."""
    r = _synthetic_run(records)
    for name in ("expert_reads", "routed_step_mfu", "routed_step_hbm_share"):
        assert spec.load_reader(name)(r) is None
