"""Pallas TPU kernel: wavefront NBBS allocation with the tree in VMEM.

The paper's hot path is the alloc/free critical section: on x86 each
climb step is an atomic RMW that takes a cache line exclusive (§III-D).
On TPU the equivalent cost model is HBM round-trips per tree-word
update.  This kernel removes them entirely: the whole tree state
lives in VMEM for the duration of a wavefront (a 2^19-node tree is
2 MiB of int32 unpacked; with `TreeConfig(layout=BUNCH_PACKED)` the
VMEM-resident state is the §III-D packed bunch words — ~1/7 the word
count, uint32 — and the merged climb touches ~B x fewer words, see
`core/layout.py`), and every arbitration round is a handful of
full-tree VPU passes:

  round =  top-down ancestor-OCC propagation        (d vector steps)
         + per-level rank/prefix-sum assignment      (d cumsums)
         + min-id conflict propagation up + down     (2d vector steps)
         + merged occupancy climb                    (d vector steps)

i.e. O(depth) (8,128)-lane vector ops per round regardless of how many
requests commit — the vector-width limit of the paper's "one CAS per
level per thread" cost model.  The round body is `alloc_round` /
`free_round` shared verbatim with `core/concurrent.py`, so the kernels
are layout-agnostic too: block shapes come from `cfg.n_state_words` /
`cfg.state_dtype`, and under `BunchPacked` the winner/freed commit
passes write bunch-leaf range masks into packed words instead of
per-node masks.

The mixed entry point (`wavefront_step_pallas`) prepends the merged
release pass (`free_round`): a whole burst of frees costs one O(depth)
sweep — no retry rounds, since meeting-point conflicts are resolved by
the bottom-up sub-tree-occupancy OR — before the allocation rounds run,
all while the tree stays VMEM-resident.

The pooled entry point (`pool_wavefront_step_pallas`) extends this to
the sharded pool of `core/pool.py`: the grid iterates over shards, each
program pulls exactly one shard's tree into VMEM (BlockSpec row slice of
the stacked [S, n_state_words] array) and runs the full mixed step for the
lanes routed to that shard (shard-membership masks computed in-kernel
from `pl.program_id`).  Overflow probing happens *between* kernel
launches (the `ops.nbbs_pool_wavefront_step` driver re-routes failed
lanes to the next shard in the pool's fixed probe order), so each
launch keeps the single-shard VMEM residency property; the in-graph
lockstep router of `core/pool.py` is the oracle whenever no overflow
occurs, and the attempt-granular linearization here is one of the pool's
legal linearizations otherwise.

Grid: a single program; rounds run as a bounded fori_loop inside the
kernel (conflict losers retry exactly like failed CAS).  BlockSpecs map
the full tree / request vectors into VMEM — the deliberate tiling
decision here is *no tiling*: climbs need random access to all levels,
which is precisely why the tree must be VMEM-resident (HBM-blocked
variants would pay a round-trip per level, reproducing the x86 cache
line ping-pong the paper fights).

Mosaic does not lower these kernels (docs/design.md §6): the round body
commits winners with a scatter, and compiling any entry point here for
a TPU v5e fails with Mosaic's `NotImplementedError: Unimplemented
primitive in Pallas TPU lowering ...: scatter`, in both tree layouts.
They run only in interpret mode, where the tests check them against
the XLA round bodies (`core/concurrent.py`, shared verbatim via
`alloc_round`).  The XLA round bodies are what the serving engine runs
and what `ops.nbbs_*` resolve `impl="auto"` to on every backend; an
explicit `impl="pallas"` raises Mosaic's error.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core import fastpath
from repro.core.concurrent import TreeConfig, alloc_round, free_round
from repro.core.pool import PoolConfig
from repro.obs.schema import (
    POOL_STEP_SLOTS,
    WAVEFRONT_ALLOC_SLOTS,
    WAVEFRONT_STEP_SLOTS,
    pack_slots,
)

Array = jax.Array


def _wavefront_kernel(
    cfg: TreeConfig,
    max_rounds: int,
    tree_ref,
    levels_ref,
    active_ref,
    tree_out_ref,
    nodes_ref,
    stats_ref,
):
    tree = tree_ref[...]
    levels = levels_ref[...]
    pending = active_ref[...] != 0
    K = levels.shape[0]
    nodes = jnp.zeros((K,), dtype=jnp.int32)

    def body(_, carry):
        tree, nodes, pending, rounds, merged, logical = carry
        live = pending.any()

        def run(args):
            tree, nodes, pending, rounds, merged, logical = args
            tree, nodes, pending, m, l, _ = alloc_round(
                cfg, tree, levels, pending, nodes
            )
            return tree, nodes, pending, rounds + 1, merged + m, logical + l

        return lax.cond(
            live, run, lambda a: a, (tree, nodes, pending, rounds, merged, logical)
        )

    tree, nodes, pending, rounds, merged, logical = lax.fori_loop(
        0,
        max_rounds,
        body,
        (tree, nodes, pending, jnp.int32(0), jnp.int32(0), jnp.int32(0)),
    )
    tree_out_ref[...] = tree
    nodes_ref[...] = nodes
    # slot order is the schema's, not this file's (tests/test_obs.py)
    stats_ref[...] = pack_slots(WAVEFRONT_ALLOC_SLOTS, {
        "rounds": rounds,
        "merged_writes": merged,
        "logical_rmws": logical,
    })


def _wavefront_step_kernel(
    cfg: TreeConfig,
    max_rounds: int,
    tree_ref,
    free_nodes_ref,
    free_active_ref,
    levels_ref,
    active_ref,
    tree_out_ref,
    nodes_ref,
    stats_ref,
):
    """Mixed round: the merged release pass (one O(depth) sweep — frees
    never need retry rounds), then the allocation wavefront, all with the
    tree VMEM-resident for the whole step."""
    tree = tree_ref[...]
    tree, free_merged, free_logical, freed = free_round(
        cfg, tree, free_nodes_ref[...], free_active_ref[...] != 0
    )
    n_freed = freed.sum(dtype=jnp.int32)

    levels = levels_ref[...]
    pending = active_ref[...] != 0
    K = levels.shape[0]
    nodes = jnp.zeros((K,), dtype=jnp.int32)

    def body(_, carry):
        tree, nodes, pending, rounds, merged, logical = carry
        live = pending.any()

        def run(args):
            tree, nodes, pending, rounds, merged, logical = args
            tree, nodes, pending, m, l, _ = alloc_round(
                cfg, tree, levels, pending, nodes
            )
            return tree, nodes, pending, rounds + 1, merged + m, logical + l

        return lax.cond(
            live, run, lambda a: a, (tree, nodes, pending, rounds, merged, logical)
        )

    tree, nodes, pending, rounds, merged, logical = lax.fori_loop(
        0,
        max_rounds,
        body,
        (tree, nodes, pending, jnp.int32(0), jnp.int32(0), jnp.int32(0)),
    )
    tree_out_ref[...] = tree
    nodes_ref[...] = nodes
    stats_ref[...] = pack_slots(WAVEFRONT_STEP_SLOTS, {
        "rounds": rounds,
        "merged_writes": merged,
        "logical_rmws": logical,
        "free_merged_writes": free_merged,
        "free_logical_rmws": free_logical,
        "freed": n_freed,
    })


@functools.partial(
    jax.jit, static_argnames=("cfg", "max_rounds", "interpret")
)
def wavefront_step_pallas(
    cfg: TreeConfig,
    tree: Array,
    free_nodes: Array,
    free_active: Array,
    levels: Array,
    max_rounds: int = 64,
    *,
    active: Array | None = None,
    interpret: bool = True,
) -> Tuple[Array, Array, Array, Array]:
    """Mixed alloc+free Pallas entry point.

    Returns (tree, nodes, ok, stats[6]) with stats = [alloc_rounds,
    alloc_merged, alloc_logical, free_merged, free_logical, freed].
    """
    if active is None:
        active = jnp.ones(levels.shape, dtype=jnp.int32)
    else:
        active = active.astype(jnp.int32)
    K = levels.shape[0]
    F = free_nodes.shape[0]
    kernel = functools.partial(_wavefront_step_kernel, cfg, max_rounds)
    tree_out, nodes, stats = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((cfg.n_state_words,), cfg.state_dtype),
            jax.ShapeDtypeStruct((K,), jnp.int32),
            jax.ShapeDtypeStruct((len(WAVEFRONT_STEP_SLOTS),), jnp.int32),
        ],
        in_specs=[
            pl.BlockSpec((cfg.n_state_words,), lambda: (0,)),  # tree state in VMEM
            pl.BlockSpec((F,), lambda: (0,)),
            pl.BlockSpec((F,), lambda: (0,)),
            pl.BlockSpec((K,), lambda: (0,)),
            pl.BlockSpec((K,), lambda: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((cfg.n_state_words,), lambda: (0,)),
            pl.BlockSpec((K,), lambda: (0,)),
            pl.BlockSpec((len(WAVEFRONT_STEP_SLOTS),), lambda: (0,)),
        ],
        grid=(),
        interpret=interpret,
    )(
        tree,
        free_nodes.astype(jnp.int32),
        free_active.astype(jnp.int32),
        levels.astype(jnp.int32),
        active,
    )
    return tree_out, nodes, nodes > 0, stats


def _pool_step_kernel(
    pcfg: PoolConfig,
    max_rounds: int,
    trees_ref,
    free_nodes_ref,
    free_shard_ref,
    free_active_ref,
    levels_ref,
    alloc_shard_ref,
    active_ref,
    trees_out_ref,
    nodes_ref,
    stats_ref,
):
    """One shard's mixed step (grid axis 0 = shard).  The program sees
    only its own tree (VMEM row slice) plus the full lane vectors, and
    masks lanes by shard membership — the Pallas analogue of the
    vmapped per-shard round in `core/pool.py`.

    With a fastpath configured the shard's slab bitmap words ride in
    the same VMEM row (appended after the tree state): frees route by
    node range before the merged tree release, and every alloc
    iteration probes the slab (single-RMW claim) before the buddy
    round, exactly like the reference pool."""
    s = pl.program_id(0)
    cfg = pcfg.tree
    fp = pcfg.fastpath
    TW = cfg.n_state_words
    row = trees_ref[0]
    tree, slab = row[:TW], row[TW:]
    fmask_all = (free_active_ref[...] != 0) & (free_shard_ref[...] == s)
    free_nodes = free_nodes_ref[...]
    if fp is not None:
        slab_leaf = fastpath.in_slab_leaf(cfg, fp, free_nodes)
        junk = fastpath.in_carved_junk(cfg, fp, free_nodes)
        slab, sl_freed, sl_merged, sl_logical = fastpath.slab_release(
            cfg, fp, slab, free_nodes, fmask_all & slab_leaf
        )
        fmask = fmask_all & ~slab_leaf & ~junk
    else:
        sl_freed = jnp.zeros_like(fmask_all)
        sl_merged = sl_logical = jnp.int32(0)
        fmask = fmask_all
    tree, free_merged, free_logical, freed = free_round(
        cfg, tree, free_nodes, fmask
    )
    n_freed = freed.sum(dtype=jnp.int32) + sl_freed.sum(dtype=jnp.int32)
    free_merged = free_merged + sl_merged
    free_logical = free_logical + sl_logical

    levels = levels_ref[...]
    pending = (active_ref[...] != 0) & (alloc_shard_ref[...] == s)
    K = levels.shape[0]
    nodes = jnp.zeros((K,), dtype=jnp.int32)

    def body(_, carry):
        tree, slab, nodes, pending, rounds, merged, logical, hits = carry
        live = pending.any()

        def run(args):
            tree, slab, nodes, pending, rounds, merged, logical, hits = args
            if fp is not None:
                want = pending & (levels == fastpath.fp_level(cfg, fp))
                slab, n_fp, got, m_fp, h = fastpath.slab_claim(
                    cfg, fp, slab, want
                )
                nodes = jnp.where(got, n_fp, nodes)
                pending = pending & ~got
                merged, logical = merged + m_fp, logical + h
                hits = hits + h
            tree, nodes, pending, m, l, _ = alloc_round(
                cfg, tree, levels, pending, nodes
            )
            return (
                tree, slab, nodes, pending,
                rounds + 1, merged + m, logical + l, hits,
            )

        return lax.cond(
            live, run, lambda a: a,
            (tree, slab, nodes, pending, rounds, merged, logical, hits),
        )

    tree, slab, nodes, pending, rounds, merged, logical, hits = lax.fori_loop(
        0,
        max_rounds,
        body,
        (
            tree, slab, nodes, pending,
            jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
        ),
    )
    trees_out_ref[0] = (
        jnp.concatenate([tree, slab]) if fp is not None else tree
    )
    nodes_ref[0] = nodes
    # the magazine slots are structurally zero here: magazines are
    # per-lane state shared across shards, so the claim/stash phases
    # run in the `ops.nbbs_pool_wavefront_step` driver around the
    # launches (that driver fills these slots in its aggregate row)
    stats_ref[0] = pack_slots(POOL_STEP_SLOTS, {
        "rounds": rounds,
        "merged_writes": merged,
        "logical_rmws": logical,
        "free_merged_writes": free_merged,
        "free_logical_rmws": free_logical,
        "freed": n_freed,
        "fastpath_hits": hits,
        "magazine_hits": jnp.int32(0),
        "magazine_spills": jnp.int32(0),
        "magazine_refills": jnp.int32(0),
    })


@functools.partial(
    jax.jit, static_argnames=("pcfg", "max_rounds", "interpret")
)
def pool_wavefront_step_pallas(
    pcfg: PoolConfig,
    trees: Array,
    free_nodes: Array,
    free_shard: Array,
    free_active: Array,
    levels: Array,
    alloc_shard: Array,
    max_rounds: int = 64,
    *,
    active: Array | None = None,
    interpret: bool = True,
) -> Tuple[Array, Array, Array, Array]:
    """Pooled mixed alloc+free Pallas entry point (grid over shards).

    Each lane allocates on `alloc_shard[k]` and each free lands on
    `free_shard[f]`; overflow re-routing across launches is the caller's
    job (`ops.nbbs_pool_wavefront_step`).  Returns (trees, nodes, ok,
    stats[S, len(POOL_STEP_SLOTS)]) with per-shard stats rows in
    POOL_STEP_SLOTS order — [alloc_rounds, alloc_merged, alloc_logical,
    free_merged, free_logical, freed, fastpath_hits, magazine_hits,
    magazine_spills, magazine_refills]; fastpath_hits is 0 without a
    configured fastpath and the magazine slots are always 0 (filled by
    the driver, see `_pool_step_kernel`).
    """
    if active is None:
        active = jnp.ones(levels.shape, dtype=jnp.int32)
    else:
        active = active.astype(jnp.int32)
    S = pcfg.n_shards
    K = levels.shape[0]
    F = free_nodes.shape[0]
    kernel = functools.partial(_pool_step_kernel, pcfg, max_rounds)
    trees_out, nodes_s, stats = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((S, pcfg.n_state_words), pcfg.tree.state_dtype),
            jax.ShapeDtypeStruct((S, K), jnp.int32),
            jax.ShapeDtypeStruct((S, len(POOL_STEP_SLOTS)), jnp.int32),
        ],
        in_specs=[
            pl.BlockSpec((1, pcfg.n_state_words), lambda s: (s, 0)),  # own shard tree
            pl.BlockSpec((F,), lambda s: (0,)),
            pl.BlockSpec((F,), lambda s: (0,)),
            pl.BlockSpec((F,), lambda s: (0,)),
            pl.BlockSpec((K,), lambda s: (0,)),
            pl.BlockSpec((K,), lambda s: (0,)),
            pl.BlockSpec((K,), lambda s: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((1, pcfg.n_state_words), lambda s: (s, 0)),
            pl.BlockSpec((1, K), lambda s: (s, 0)),
            pl.BlockSpec((1, len(POOL_STEP_SLOTS)), lambda s: (s, 0)),
        ],
        grid=(S,),
        interpret=interpret,
    )(
        trees,
        free_nodes.astype(jnp.int32),
        free_shard.astype(jnp.int32),
        free_active.astype(jnp.int32),
        levels.astype(jnp.int32),
        alloc_shard.astype(jnp.int32),
        active,
    )
    # a lane is routed to exactly one shard, so at most one row is non-zero
    nodes = nodes_s.max(axis=0)
    return trees_out, nodes, nodes > 0, stats


@functools.partial(
    jax.jit, static_argnames=("cfg", "max_rounds", "interpret")
)
def wavefront_alloc_pallas(
    cfg: TreeConfig,
    tree: Array,
    levels: Array,
    max_rounds: int = 64,
    *,
    active: Array | None = None,
    interpret: bool = True,
) -> Tuple[Array, Array, Array, Array]:
    """Pallas entry point. Returns (tree, nodes, ok, stats[3]).

    `interpret=True` (the default) runs the kernel body in Python, the
    only way these kernels run (see the module docstring: Mosaic
    refuses the scatter in the round body).
    """
    if active is None:
        active = jnp.ones(levels.shape, dtype=jnp.int32)
    else:
        active = active.astype(jnp.int32)
    K = levels.shape[0]
    kernel = functools.partial(_wavefront_kernel, cfg, max_rounds)
    tree_out, nodes, stats = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((cfg.n_state_words,), cfg.state_dtype),
            jax.ShapeDtypeStruct((K,), jnp.int32),
            jax.ShapeDtypeStruct((len(WAVEFRONT_ALLOC_SLOTS),), jnp.int32),
        ],
        in_specs=[
            pl.BlockSpec((cfg.n_state_words,), lambda: (0,)),  # tree state in VMEM
            pl.BlockSpec((K,), lambda: (0,)),
            pl.BlockSpec((K,), lambda: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((cfg.n_state_words,), lambda: (0,)),
            pl.BlockSpec((K,), lambda: (0,)),
            pl.BlockSpec((len(WAVEFRONT_ALLOC_SLOTS),), lambda: (0,)),
        ],
        grid=(),
        interpret=interpret,
    )(tree, levels.astype(jnp.int32), active)
    return tree_out, nodes, nodes > 0, stats
