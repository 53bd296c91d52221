"""Pallas TPU paged decode attention — the NBBS consumer (serving hot spot).

One new token per sequence attends over a KV cache stored as
buddy-allocated pages in a global pool.  The kernel takes the model's
stacked pools `[L, P, page, Hkv, D]` and the layer to read as a
prefetched scalar, with the block table and the context lengths: it
addresses its layer in the stacked pools itself, since a sliced
one-layer operand would make XLA copy that layer's whole pool, every
page, before each call.  A caller that holds one layer passes
`pool[None]` and layer 0.  Buddy blocks are power-of-two *contiguous*
page runs (docs/design.md §2), so the pool fragments without external
holes (the paper's coalescing at work).

Two walks over a lane's pages, picked by the page's shape alone
(`walks_live_pages`):

* The live-page walk, where the page's last two dims [Hkv, D] are whole
  tiles of the pool's layout: D a multiple of 128 and, in a bf16 pool,
  Hkv 2, 4 or a multiple of 8 (minitron-4b, Mellum2-12B).  Grid (B,):
  one grid step a lane, and inside it a loop over the lane's live
  pages only, N pages a block.  The pools stay in HBM
  (`memory_space=ANY`) and the kernel DMAs each page
  (`pool.at[layer, page_id]`) into one of two VMEM block buffers,
  starting the next block's DMAs before it computes the current one;
  a lane's last block starts the first block of the next lane that
  has a context, so a lane's first DMA latency hides too.  The loop
  runs from the window's first page (0 in a global layer) to
  ceil(ctx / page), so a window layer skips the pages before its
  window at the cost of a few scalar ops a lane; slots of the last
  block past the context, and slots with no page (table id < 0), get
  no DMA and are masked.  A lane at ctx 0 runs no block and writes
  zeros.  N (`pages_per_block`) is the most pages whose two K+V
  buffers fit `BLOCK_VMEM_BYTES`, a power of two from 4 to 16: 8 at
  minitron-4b's pages (64 KiB of K+V), 16 at Mellum2-12B's (32 KiB).
* The slot grid, for every other page (stablelm-3b: D 80, where Mosaic
  refuses a DMA slice of the pool: "Slice shape along dimension 4 must
  be aligned to tiling (128), but is 80").  Grid (B, max_pages): one
  table slot a grid step, the block table prefetched into SMEM and the
  k/v BlockSpec index maps steering each step's page DMA
  (`PrefetchScalarGridSpec`), dead slots (table id < 0, or past the
  context) skipped with @pl.when.  Each block keeps its last two
  dimensions whole — q/o (1, Hq, D), k/v (1, 1, page, Hkv, D) — which
  is what Mosaic requires when D is not a multiple of 128.  A model
  with windows passes the layer's window as a fourth prefetched scalar
  (0 = global): pages wholly before the window are skipped, and their
  index maps repeat the window's first page, so the pipeline issues no
  DMA for them.  A model without windows passes none (`window=None`)
  and gets the grid without the window's scalar work, which runs at
  every grid step: at window 0 it makes a call 21% slower at
  minitron-4b's heads and 9% at stablelm-3b's on one v5e.

A window layer attends to the newest `window` positions of the
context, the token itself included (Hugging Face's `sliding_window`);
the page that straddles the window's start is masked.

Both walks compute the same math: a page's (or block's) K/V are
flattened to [pages*page*Hkv, D] (row r = slot r // Hkv, kv head
r % Hkv) so both products are plain 2-D matmuls over all heads at
once, and a mask keeps each query head on its own kv head (GQA group),
inside the context and inside the window.  That multiplies the
attention FLOPs by Hkv.  f32 online softmax, state in 2-D VMEM scratch
([Hq, 1] running max and denominator, [Hq, D] accumulator).
`slots_walked` gives the table slots a walk visits, which the decode
step counts (`attn_slots_walked`).

Checked three ways: interpret mode against
`ref.paged_attention_reference` over shape/dtype/page-size sweeps and
the live-page walk's edge cases (tests/test_kernels.py); compiled for
a TPU v5e at the head geometries of the configs and the benchmark
cells' real shapes (tests/test_tpu_compile.py); and run on a v5e by
`chip_smoke.py`, which compares both walks with the reference on the
chip.
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


# The live-page walk moves N pages a block, N by the rule below: the
# double-buffered K+V blocks take at most this much VMEM, and N is a
# power of two in [MIN_BLOCK_PAGES, MAX_BLOCK_PAGES].
BLOCK_VMEM_BYTES = 1 << 20
MIN_BLOCK_PAGES, MAX_BLOCK_PAGES = 4, 16


def walks_live_pages(n_kv_heads: int, head_dim: int, itemsize: int) -> bool:
    """Whether the kernel takes the live-page walk: Mosaic DMAs a page
    [page, Hkv, D] out of the stacked HBM pool only where its last two
    dims are whole tiles of the pool's layout, that is D a multiple of
    128 lanes and, in a 2-byte pool, Hkv 2, 4 or a multiple of 8 (a
    4-byte pool takes any Hkv)."""
    if head_dim % 128:
        return False
    if itemsize == 4:
        return True
    return itemsize == 2 and (n_kv_heads in (2, 4) or n_kv_heads % 8 == 0)


def pages_per_block(page: int, n_kv_heads: int, head_dim: int,
                    itemsize: int) -> int:
    """N, the pages of one block of the live-page walk: the most whose
    two K+V buffers fit `BLOCK_VMEM_BYTES`, as a power of two."""
    kv_page = 2 * page * n_kv_heads * head_dim * itemsize
    n = max(BLOCK_VMEM_BYTES // (2 * kv_page), 1)
    n = 1 << (n.bit_length() - 1)
    return max(MIN_BLOCK_PAGES, min(MAX_BLOCK_PAGES, n))


def slots_walked(context_lens: Array, window, *, page: int, max_pages: int,
                 n_kv_heads: int, head_dim: int, itemsize: int) -> Array:
    """Table slots the kernel visits for each lane: under the live-page
    walk ceil((hi - lo) / N) * N, the blocks over the lane's live pages
    (hi = ceil(ctx / page), lo = the window's first page, 0 for a
    global layer, nothing at ctx 0); under the slot grid every slot of
    the table.  `window` broadcasts against `context_lens` (0 = all)."""
    ctx = jnp.asarray(context_lens, jnp.int32)
    window = jnp.asarray(window, jnp.int32)
    if not walks_live_pages(n_kv_heads, head_dim, itemsize):
        shape = jnp.broadcast_shapes(ctx.shape, window.shape)
        return jnp.full(shape, max_pages, jnp.int32)
    n = pages_per_block(page, n_kv_heads, head_dim, itemsize)
    first, hi = _live_pages(ctx, window, page, max_pages)
    return pl.cdiv(hi - first, n) * n


def _live_pages(ctx, window, page, max_pages):
    """First page the lane attends to, and one past its last: the
    window's first page (0 for a global layer) and ceil(ctx / page),
    both 0 at ctx 0."""
    hi = jnp.minimum(pl.cdiv(ctx, page), max_pages)
    first = jnp.minimum(_window_start(window, ctx) // page, hi)
    return first, hi


def _live_walk_kernel(
    # static
    scale: float,
    softcap: Optional[float],
    page: int,
    group: int,
    n_blk: int,
    max_pages: int,
    windowed: bool,
    # prefetched scalars: layer, (window,) tables (flat), lens
    *refs,
):
    if windowed:
        layer_ref, win_ref, tables_ref, lens_ref, *refs = refs
    else:
        layer_ref, tables_ref, lens_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, walk, m_scr, l_scr,
     acc_scr) = refs
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    layer = layer_ref[0]

    def bounds(lane):
        ctx = lens_ref[lane]
        win = win_ref[0] if windowed else 0
        first, hi = _live_pages(ctx, win, page, max_pages)
        return first, hi, pl.cdiv(hi - first, n_blk)

    def copies(lane, first, hi, blk, slot):
        """(live, k copy, v copy) for each page slot of block `blk`: a
        slot past the context, or with no page, has no DMA."""
        out = []
        for i in range(n_blk):
            j = first + blk * n_blk + i
            pid = tables_ref[lane * max_pages + jnp.minimum(j, max_pages - 1)]
            src = (layer, jnp.maximum(pid, 0))
            out.append((
                (j < hi) & (pid >= 0),
                pltpu.make_async_copy(k_hbm.at[src], kbuf.at[slot, i],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[src], vbuf.at[slot, i],
                                      sems.at[1, slot]),
            ))
        return out

    def start(lane, first, hi, blk, slot):
        for live, ck, cv in copies(lane, first, hi, blk, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(lane, first, hi, blk, slot):
        for live, ck, cv in copies(lane, first, hi, blk, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(b == 0)
    def _first_lane():
        # walk[0]: the buffer slot of the next block to compute;
        # walk[1]: the lane whose first block is in flight (-1: none).
        # Slots past a lane's context get no DMA and keep what an
        # earlier block left, or these zeros: p is 0 there, and 0 * v
        # must not meet a NaN of uninitialised memory
        walk[0] = 0
        walk[1] = -1
        vbuf[...] = jnp.zeros_like(vbuf)

    ctx = lens_ref[b]
    first, hi, n_blocks = bounds(b)
    lo = _window_start(win_ref[0], ctx) if windowed else None

    @pl.when((n_blocks > 0) & (walk[1] != b))
    def _own_first_block():
        start(b, first, hi, 0, walk[0])

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(blk, carry):
        slot = walk[0]
        nxt = 1 - slot

        @pl.when(blk + 1 < n_blocks)
        def _next_block():
            start(b, first, hi, blk + 1, nxt)

        @pl.when(blk + 1 == n_blocks)
        def _next_lane():
            # the first block of the next lane with a context, so that
            # its DMA latency hides behind this lane's last block
            nb = jax.lax.while_loop(
                lambda c: (c < n_lanes)
                & (lens_ref[jnp.minimum(c, n_lanes - 1)] == 0),
                lambda c: c + 1, b + 1)

            @pl.when(nb < n_lanes)
            def _():
                nf, nh, _ = bounds(nb)
                start(nb, nf, nh, 0, nxt)
                walk[1] = nb

        wait(b, first, hi, blk, slot)
        _, _, hkv, d = kbuf.shape[1:]
        width = n_blk * page * hkv
        q = q_ref[0].astype(jnp.float32)  # [Hq, D]
        k = kbuf[slot].astype(jnp.float32).reshape(width, d)
        v = vbuf[slot].astype(jnp.float32).reshape(width, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [Hq, N*page*Hkv]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        j0 = first + blk * n_blk
        pos = j0 * page + col // hkv
        keep = (col % hkv == row // group) & (pos < ctx)
        if windowed:
            keep = keep & (pos >= lo)
        # a slot inside the context whose table id is below 0 holds no
        # page: one [1, width] row of the slots that do
        in_blk = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // (
            page * hkv)
        held = jnp.ones((1, width), jnp.bool_)
        for i in range(n_blk):
            pid = tables_ref[
                b * max_pages + jnp.minimum(j0 + i, max_pages - 1)]
            held = held & ((in_blk != i) | (pid >= 0))
        s = jnp.where(keep & held, s, NEG_INF)

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
        walk[0] = nxt
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = l_scr[...]
    norm = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_scr[...] / norm).astype(o_ref.dtype)


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
    the layer's sliding window (0 = global).  Pages whose [Hkv, D]
    fill whole tiles take the live-page walk, any others the slot grid
    (`walks_live_pages`)."""
    _, Hq, D = q.shape
    Hkv = k_pages.shape[3]
    assert Hq % Hkv == 0
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    scalars = [jnp.reshape(layer, (1,)).astype(jnp.int32)]
    if window is not None:
        scalars.append(jnp.reshape(window, (1,)).astype(jnp.int32))
    walks = walks_live_pages(Hkv, D, k_pages.dtype.itemsize)
    walk = _live_walk if walks else _slot_grid
    return walk(
        q, k_pages, v_pages, scalars, block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32), windowed=window is not None,
        softcap=softcap, scale=scale, interpret=interpret,
    )


def _live_walk(q, k_pages, v_pages, scalars, block_tables, context_lens, *,
               windowed, softcap, scale, interpret):
    """One grid step a lane; k/v stay in HBM and the kernel DMAs the
    lane's live pages itself, N to a block, double-buffered."""
    B, Hq, D = q.shape
    _, P, page, Hkv, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    n_blk = pages_per_block(page, Hkv, D, k_pages.dtype.itemsize)
    kernel = functools.partial(
        _live_walk_kernel, scale, softcap, page, Hq // Hkv, n_blk,
        max_pages, windowed,
    )

    def q_map(b, *scalars):
        return (b, 0, 0)

    buf = (2, n_blk, page, Hkv, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars) + 2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, D), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), q_map),
        scratch_shapes=[
            pltpu.VMEM(buf, k_pages.dtype),
            pltpu.VMEM(buf, v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # lanes run in order: a lane's last block starts the next
        # lane's DMAs, and the walk's state carries across grid steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the custom call's name in the compiled program and the
        # profiler trace, where the benchmark finds the kernel by it
        name="paged_attention",
    )(*scalars, block_tables.reshape(-1), context_lens, q, k_pages, v_pages)


def _slot_grid(q, k_pages, v_pages, scalars, block_tables, context_lens, *,
               windowed, softcap, scale, interpret):
    """Grid (B, max_pages): one table slot a grid step, the page's DMA
    steered by the BlockSpec index maps."""
    B, Hq, D = q.shape
    _, P, page, Hkv, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    kernel = functools.partial(
        _paged_decode_kernel, scale, softcap, page, Hq // Hkv, windowed
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
        name="paged_attention",
    )(*scalars, block_tables, context_lens, q, k_pages, v_pages)
