"""Pallas TPU paged decode attention — the NBBS consumer (serving hot spot).

One new token per sequence attends over a KV cache stored as
buddy-allocated pages in a global pool.  The page indirection uses the
TPU scalar-prefetch pattern (`PrefetchScalarGridSpec`): the block table
is prefetched into SMEM and the k/v BlockSpec index maps read it to
steer each grid step's DMA at the right pool page — the TPU-native
equivalent of vLLM's gather, with two NBBS-specific advantages
(docs/design.md §2): buddy blocks are power-of-two *contiguous* page runs,
so (a) larger pages are addressable with the same table and (b) the
pool fragments without external holes (the paper's coalescing at work).

Grid: (batch, pages); pages innermost with fp32 online-softmax scratch,
invalid pages (table id < 0, or beyond the sequence's context length)
skipped with @pl.when.  Each grid step covers *all* heads of one page,
so every block keeps its last two dimensions whole — q/o blocks are
(1, Hq, D) and k/v blocks (1, 1, page, Hkv, D) — which is what Mosaic
requires when D (80 for stablelm-3b) is not a multiple of 128.

The kernel takes the model's stacked pools `[L, P, page, Hkv, D]` and
the layer to read as a prefetched scalar; the k/v index maps steer each
DMA at (layer, page).  A sliced one-layer operand would make XLA copy
that layer's whole pool, every page, before each call.  A caller that
holds one layer passes `pool[None]` and layer 0.

A model with sliding-window layers passes the layer's window as a
fourth prefetched scalar (0 = global).  A window layer attends to the
newest `window` positions of the context, the token itself included
(Hugging Face's `sliding_window`).  Pages that lie wholly before the
window are skipped with @pl.when, and their k/v index maps repeat the
first page inside the window, so the pipeline issues no DMA for them;
the page that straddles the window's start is masked.  At window 0 the
DMAs and the results are those of a global layer.  A model without
windows passes none (`window=None`) and gets the kernel without the
window's scalar work, which runs at every grid step (every table slot
of every lane): at window 0 it makes a call 21% slower at minitron-4b's
heads (16 lanes) and 9% at stablelm-3b's (8 lanes) on one v5e, over
160-slot tables at contexts of 1,100-1,500 tokens.

The page's K/V are flattened to [page*Hkv, D] (row r = slot r // Hkv,
kv head r % Hkv) so both products are plain 2-D matmuls over all
heads at once; a static mask keeps each query head on its own kv head
(GQA group) and inside the context.  That multiplies the attention
FLOPs by Hkv (one page of stablelm-3b is 160 KiB of K+V against
~5 MFLOP, plus a [Hq, page*Hkv] f32 exp and mask); whether the step is
bound by the page DMA or by that compute is unmeasured.  Mosaic
compiles the flatten for a v5e at Hkv 1, 2, 8, 10, 16 and 32
(tests/test_tpu_compile.py); whether an Hkv that is not a multiple of
the f32 sublane tile (8) costs a relayout is unmeasured.  Softmax state
lives in 2-D VMEM scratch ([Hq, 1] running max and denominator,
[Hq, D] accumulator).

Checked three ways: interpret mode against
`ref.paged_attention_reference` over shape/dtype/page-size sweeps
(tests/test_kernels.py); compiled for a TPU v5e at the head geometries
of the configs (tests/test_tpu_compile.py); and run on a v5e at
stablelm-3b widths by `chip_smoke.py`, which compares it with the
reference on the chip.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _paged_decode_kernel(
    # static
    scale: float,
    softcap: Optional[float],
    page: int,
    group: int,
    windowed: bool,
    # prefetched scalars: layer, (window,) tables, lens
    *refs,
):
    if windowed:
        layer_ref, win_ref, tables_ref, lens_ref, *refs = refs
    else:
        layer_ref, tables_ref, lens_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = lens_ref[b]
    page_id = tables_ref[b, j]
    live = (page_id >= 0) & (j * page < ctx)
    if windowed:
        lo = _window_start(win_ref[0], ctx)
        live = live & ((j + 1) * page > lo)

    @pl.when(live)
    def _compute():
        _, _, _, hkv, d = k_ref.shape
        q = q_ref[0].astype(jnp.float32)  # [Hq, D]
        k = k_ref[0, 0].astype(jnp.float32).reshape(page * hkv, d)
        v = v_ref[0, 0].astype(jnp.float32).reshape(page * hkv, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [Hq, page*Hkv]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        own = col % hkv == row // group
        pos = j * page + col // hkv
        keep = own & (pos < ctx)
        if windowed:
            keep = keep & (pos >= lo)
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_scr[...]  # [Hq, 1]
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        p = jnp.where(m_cur == NEG_INF, 0.0, p)
        alpha = jnp.where(m_cur == NEG_INF, 1.0, alpha)
        m_scr[...] = m_cur
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == n_pages - 1)
    def _finalize():
        l = l_scr[...]
        norm = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / norm).astype(o_ref.dtype)


def _window_start(window, ctx):
    """First position a token attends to: ctx - window for a window
    layer (window > 0), else 0."""
    return jnp.where(window > 0, jnp.maximum(ctx - window, 0), 0)


@functools.partial(
    jax.jit, static_argnames=("softcap", "scale", "interpret")
)
def paged_attention(
    q: Array,
    k_pages: Array,
    v_pages: Array,
    layer: Array,
    block_tables: Array,
    context_lens: Array,
    window: Optional[Array] = None,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> Array:
    """q: [B,Hq,D]; k/v_pages: [L,P,page,Hkv,D]; layer: int32 scalar or
    [1], the layer of the pools to attend over; tables: [B,max_pages];
    window: None (a model without windows), or an int32 scalar or [1],
    the layer's sliding window (0 = global)."""
    B, Hq, D = q.shape
    _, P, page, Hkv, _ = k_pages.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    windowed = window is not None
    kernel = functools.partial(
        _paged_decode_kernel, scale, softcap, page, group, windowed
    )

    def q_map(b, j, *scalars):
        return (b, 0, 0)

    def kv_map(b, j, *scalars):
        if windowed:
            layer, win, tables, lens = scalars
            # a page before the window repeats the window's first page
            j = jnp.maximum(j, _window_start(win[0], lens[b]) // page)
        else:
            layer, tables, lens = scalars
        return (layer[0], jnp.maximum(tables[b, j], 0), 0, 0, 0)

    scalars = [jnp.reshape(layer, (1,)).astype(jnp.int32)]
    if windowed:
        scalars.append(jnp.reshape(window, (1,)).astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars) + 2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, Hq, D), q_map),
            pl.BlockSpec((1, 1, page, Hkv, D), kv_map),
            pl.BlockSpec((1, 1, page, Hkv, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        # the custom call's name in the compiled program and the
        # profiler trace, where the benchmark finds the kernel by it
        name="paged_attention",
    )(
        *scalars,
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        q,
        k_pages,
        v_pages,
    )
