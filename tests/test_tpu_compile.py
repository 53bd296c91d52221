"""Compile rehearsals for a TPU v5e, without a chip.

The installed TPU compiler compiles for a described v5e topology, so
these tests catch what interpret mode cannot: a block shape Mosaic
refuses, a kernel that is not on the served path, a program that does
not fit.  Nothing runs; the topology is described inside a fixture (so
that only the worker given this file loads the TPU library), and the
persistent compile cache is off around the compiles (an entry written
for a described chip cannot be read back without one).
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import paged_attention as pa
from repro.kernels.paged_attention import paged_attention
from repro.models import init_params
from repro.serve.jit_engine import (
    EngineConfig, engine_run, engine_step, init_engine_state,
)

KERNEL_CALL = "tpu_custom_call"


@pytest.fixture(scope="module")
def one_chip():
    pytest.importorskip("libtpu", reason="no TPU compiler installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(x, sharding, float_dtype=None):
    dt = x.dtype
    if float_dtype is not None and jnp.issubdtype(dt, jnp.floating):
        dt = float_dtype
    return jax.ShapeDtypeStruct(x.shape, dt, sharding=sharding)


def _compile_paged_attention(sharding, H, Hkv, D, dtype, P, page=16, B=8,
                             max_pages=32, L=2, window=False):
    """The kernel on stacked pools of L layers, the layer (and with
    `window` the layer's window) a traced argument as the decode step's
    layer scan passes it."""
    args = [
        jax.ShapeDtypeStruct((B, H, D), dtype, sharding=sharding),
        jax.ShapeDtypeStruct((L, P, page, Hkv, D), dtype, sharding=sharding),
        jax.ShapeDtypeStruct((L, P, page, Hkv, D), dtype, sharding=sharding),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((B, max_pages), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sharding),
    ]
    if window:
        args.append(jax.ShapeDtypeStruct((1,), jnp.int32, sharding=sharding))
    return jax.jit(paged_attention).lower(*args).compile()


def test_paged_attention_compiles_at_stablelm_widths(one_chip):
    cfg = get_config("stablelm-3b")
    compiled = _compile_paged_attention(
        one_chip, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16,
        P=512,
    )
    assert KERNEL_CALL in compiled.as_text()


@pytest.mark.parametrize("arch", [
    "phi3-medium-14b", "minitron-4b", "gemma2-27b", "musicgen-large",
])
def test_paged_attention_compiles_at_config_heads(one_chip, arch):
    """Every KV-head count the configs use, at their published head
    geometry: the [page, Hkv, D] -> [page*Hkv, D] flatten must compile
    when Hkv is not a multiple of the sublane tile (phi3-medium: 10)."""
    cfg = get_config(arch)
    compiled = _compile_paged_attention(
        one_chip, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16,
        P=64,
    )
    assert KERNEL_CALL in compiled.as_text()


@pytest.mark.parametrize("H,Hkv,D,dtype", [
    (4, 2, 16, jnp.float32), (4, 1, 32, jnp.float32),
    (8, 2, 32, jnp.bfloat16),
])
def test_paged_attention_compiles_at_small_gqa(one_chip, H, Hkv, D, dtype):
    """The small GQA shapes of the interpret-mode sweep (Hkv 1 and 2)."""
    compiled = _compile_paged_attention(one_chip, H, Hkv, D, dtype, P=64)
    assert KERNEL_CALL in compiled.as_text()


def test_paged_attention_compiles_with_a_window_at_mellum_heads(one_chip):
    """Mellum2-12B's heads (32 q over 4 kv heads of dim 128) with the
    layer's window as the fourth prefetched scalar."""
    cfg = get_config("mellum2-12b")
    compiled = _compile_paged_attention(
        one_chip, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16,
        P=2048, B=32, max_pages=160, window=True,
    )
    assert KERNEL_CALL in compiled.as_text()


@pytest.mark.parametrize("arch,lanes,num_pages", [
    ("minitron-4b", 16, 1024),
    ("mellum2-12b", 32, 2048),  # with its window
])
def test_live_page_walk_compiles_at_the_cells_shapes(one_chip, arch, lanes,
                                                     num_pages):
    """The live-page walk at a benchmark cell's real shapes: its lanes,
    its pool over all its layers, 160-slot tables. Mosaic accepts the
    double-buffered VMEM blocks, the DMA semaphores and the page DMAs
    out of the stacked HBM pools."""
    cfg = get_config(arch)
    assert pa.walks_live_pages(cfg.n_kv_heads, cfg.head_dim, 2)
    compiled = _compile_paged_attention(
        one_chip, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16,
        P=num_pages, B=lanes, max_pages=160, L=cfg.n_layers,
        window=bool(cfg.window_pattern),
    )
    assert KERNEL_CALL in compiled.as_text()


def _served_engine(sharding, arch="stablelm-3b", num_pages=512, n_layers=2,
                   max_batch=8):
    """The served engine's configuration at an arch's widths (stablelm-3b
    by default), depth cut to 2 layers, with the shapes of its bf16
    weights and state."""
    cfg = dataclasses.replace(get_config(arch), n_layers=n_layers)
    ecfg = EngineConfig(
        arch=cfg, num_pages=num_pages, page_tokens=16, max_batch=max_batch,
        max_lane_pages=32, max_out=32, dtype="bfloat16", impl="pallas",
    )
    params = jax.tree.map(
        lambda x: _spec(x, sharding, jnp.bfloat16),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))),
    )
    state = jax.tree.map(
        lambda x: _spec(x, sharding),
        jax.eval_shape(lambda: init_engine_state(ecfg)),
    )
    return ecfg, params, state


def test_engine_step_compiles_at_full_width(one_chip):
    """The served step at stablelm-3b widths, depth cut to 2 layers,
    bf16 weights: compiles, and runs the Pallas kernel."""
    ecfg, params, state = _served_engine(one_chip)
    compiled = engine_step.lower(ecfg, params, state).compile()
    assert KERNEL_CALL in compiled.as_text()
    mem = compiled.memory_analysis()
    # the donated KV pool is updated in place, not copied out
    pool = jax.tree.map(lambda x: x.size * x.dtype.itemsize, state)
    assert mem.alias_size_in_bytes >= pool.kv_k + pool.kv_v


def test_engine_run_names_the_kernel(one_chip):
    """The fused chunk the server dispatches holds the kernel as a
    custom call named `paged_attention`: the profiler trace names its
    device op after that instruction, and the benchmark's reduction
    finds the kernel by that name."""
    ecfg, params, state = _served_engine(one_chip)
    text = engine_run.lower(ecfg, params, state, 2).compile().as_text()
    named = re.findall(
        r"^\s*%(paged_attention(?:\.\d+)?) = .*custom-call\(.*"
        r'custom_call_target="tpu_custom_call"', text, re.MULTILINE)
    assert named


@pytest.mark.parametrize("arch,num_pages", [
    ("stablelm-3b", 512),  # 32 heads of dim 80 (padded to the lane tile)
    ("minitron-4b", 1024),  # 24 q heads over 8 kv heads of dim 128
])
def test_engine_run_reads_the_stacked_pool(one_chip, arch, num_pages):
    """The kernel addresses its layer in the stacked pools itself: no
    instruction of the fused chunk outputs one layer of a pool (a
    `pool[layer]` operand would be copied whole, every page, at every
    layer of every step), and the kernel's k/v operands are the
    `[L, P, page, Hkv, D]` pools."""
    ecfg, params, state = _served_engine(one_chip, arch, num_pages)
    cfg = ecfg.arch
    text = engine_run.lower(ecfg, params, state, 2).compile().as_text()
    layer = f"{num_pages},{ecfg.page_tokens},{cfg.n_kv_heads},{cfg.head_dim}"
    sliced = re.findall(rf"^\s*(%\S+) = bf16\[{layer}\]", text, re.MULTILINE)
    assert not sliced
    calls = re.findall(
        r"^\s*%paged_attention(?:\.\d+)? = .*custom-call\(.*"
        r"operand_layout_constraints=\{(.*?)\}\}", text, re.MULTILINE)
    assert calls
    stacked = f"bf16[{cfg.n_layers},{layer}]"
    for operands in calls:
        k, v = re.findall(r"\w+\[[\d,]*\]", operands)[-2:]
        assert k == v == stacked


def test_engine_run_routes_experts_at_mellum_widths(one_chip):
    """One whole period of Mellum2-12B's layers (three window layers and
    a full one) at its widths, the held share of 16 of 64 experts, 32
    lanes: the fused chunk compiles with the kernel, its 32 tokens run
    every held expert by plain dots that read the layer's weights in
    place (a copy of one layer's held experts, 66 MB a matrix, would
    show in the temporaries), and the 2,048-token prefill groups its
    routed pairs by expert (`ragged-dot`) with no buffer sized by a
    per-expert capacity (E x T x top_k rows)."""
    from repro.serve.paged_decode import serve_prefill

    ecfg, params, state = _served_engine(
        one_chip, "mellum2-12b", num_pages=2048, n_layers=4, max_batch=32)
    compiled = engine_run.lower(ecfg, params, state, 2).compile()
    text = compiled.as_text()
    assert KERNEL_CALL in text and "ragged-dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    toks = {"tokens": jax.ShapeDtypeStruct((1, 2048), jnp.int32,
                                           sharding=one_chip)}
    text = serve_prefill.lower(ecfg.arch, params, toks, max_len=2048,
                               dtype=jnp.bfloat16).compile().as_text()
    assert "ragged-dot" in text
    assert not re.search(r"bf16\[(16|64),(8192|16385|16384),2304\]", text)
