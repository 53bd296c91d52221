"""Public kernel ops: backend dispatch + differentiability.

Selection policy (`impl`):
  "auto"      — attention: Pallas/Mosaic on TPU backends, pure-jnp
                reference otherwise (XLA CPU/GPU cannot lower Mosaic
                kernels; identical math, verified allclose by the kernel
                test sweeps).  NBBS ops: the XLA round bodies on every
                backend — Mosaic has no scatter lowering, so the NBBS
                kernels do not compile for a TPU (see `nbbs_alloc.py`).
  "pallas"    — compiled Pallas (TPU runtime); a kernel Mosaic refuses
                raises Mosaic's own error.
  "interpret" — Pallas interpret mode (CPU validation; slow).
  "reference" — pure-jnp / XLA oracle.

`flash_attention` is differentiable: forward may use the fused kernel,
backward recomputes through the reference (identical math -> exact
gradients w.r.t. the reference function).

The NBBS dispatchers are tree-layout-agnostic: the `cfg`/`pcfg` they
take carries its `TreeLayout` (docs/design.md §3), and every impl path
— reference, interpret, pallas — runs the same layout-parameterized
round bodies, so packed and unpacked configs dispatch identically.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import fastpath
from repro.core import magazine as magmod
from repro.core.concurrent import TreeConfig, wavefront_step
from repro.core.pool import (
    PoolConfig,
    _gid_parts,
    _mag_spill_all,
    _mag_stash_phase,
    home_shard,
    pool_wavefront_alloc,
    pool_wavefront_step,
    pool_wavefront_step_mag,
)
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.nbbs_alloc import (
    pool_wavefront_step_pallas,
    wavefront_alloc_pallas,
    wavefront_step_pallas,
)
from repro.kernels.paged_attention import paged_attention as paged_attention_pallas
from repro.obs.schema import (
    POOL_STEP_SLOTS,
    WAVEFRONT_ALLOC_SLOTS,
    WAVEFRONT_STEP_SLOTS,
    unpack_slots,
)

Array = jax.Array


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _resolve(impl: str) -> str:
    return default_impl() if impl == "auto" else impl


def _resolve_nbbs(impl: str) -> str:
    # the NBBS round bodies commit winners with a scatter, which Mosaic
    # cannot lower: "auto" names the XLA path the engine runs anyway
    return "reference" if impl == "auto" else impl


# ---------------------------------------------------------------------------
# Flash attention (differentiable)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash_attention(q, k, v, causal, window, softcap, scale, impl):
    if impl == "reference":
        return kref.mha_reference(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        )
    return flash_attention_fwd(
        q,
        k,
        v,
        causal=causal,
        window=window,
        softcap=softcap,
        scale=scale,
        interpret=(impl == "interpret"),
    )


def _flash_fwd(q, k, v, causal, window, softcap, scale, impl):
    out = _flash_attention(q, k, v, causal, window, softcap, scale, impl)
    return out, (q, k, v)


def _flash_bwd(causal, window, softcap, scale, impl, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: kref.mha_reference(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        ),
        q,
        k,
        v,
    )
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    impl: str = "auto",
) -> Array:
    """Differentiable attention. q:[B,Hq,S,D], k/v:[B,Hkv,Sk,D]."""
    return _flash_attention(
        q, k, v, causal, window, softcap, scale, _resolve(impl)
    )


# ---------------------------------------------------------------------------
# Paged decode attention (inference only — no vjp needed)
# ---------------------------------------------------------------------------


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
    impl: str = "auto",
) -> Array:
    """Decode attention over layer `layer` of the stacked pools
    k/v_pages: [L,P,page,Hkv,D], over the newest `window` positions of
    each context (0 or None = all of it; None compiles the kernel
    without the window's scalar work)."""
    impl = _resolve(impl)
    if impl == "reference":
        return kref.paged_attention_reference(
            q,
            k_pages[layer],
            v_pages[layer],
            block_tables,
            context_lens,
            0 if window is None else window,
            softcap=softcap,
            scale=scale,
        )
    return paged_attention_pallas(
        q,
        k_pages,
        v_pages,
        layer,
        block_tables,
        context_lens,
        window,
        softcap=softcap,
        scale=scale,
        interpret=(impl == "interpret"),
    )


# ---------------------------------------------------------------------------
# NBBS wavefront allocation
# ---------------------------------------------------------------------------


def nbbs_wavefront_alloc(
    cfg: TreeConfig,
    tree: Array,
    levels: Array,
    *,
    active: Array | None = None,
    max_rounds: int = 64,
    impl: str = "auto",
):
    """Returns (tree, nodes, ok, stats-dict)."""
    impl = _resolve_nbbs(impl)
    if impl == "reference":
        if active is None:
            active = jnp.ones(levels.shape, dtype=bool)
        return kref.nbbs_wavefront_reference(
            cfg, tree, levels, active, max_rounds
        )
    tree, nodes, ok, stats = wavefront_alloc_pallas(
        cfg,
        tree,
        levels,
        max_rounds,
        active=active,
        interpret=(impl == "interpret"),
    )
    # name the positional kernel row through the shared schema order
    return tree, nodes, ok, unpack_slots(WAVEFRONT_ALLOC_SLOTS, stats)


def nbbs_wavefront_step(
    cfg: TreeConfig,
    tree: Array,
    free_nodes: Array,
    free_active: Array,
    levels: Array,
    *,
    active: Array | None = None,
    max_rounds: int = 64,
    impl: str = "auto",
):
    """Mixed release+allocation round (frees via the merged vectorized
    pass, then the alloc wavefront).  Returns (tree, nodes, ok, stats)."""
    impl = _resolve_nbbs(impl)
    if active is None:
        active = jnp.ones(levels.shape, dtype=bool)
    if impl == "reference":
        return wavefront_step(
            cfg, tree, free_nodes, free_active, levels, active, max_rounds
        )
    tree, nodes, ok, stats = wavefront_step_pallas(
        cfg,
        tree,
        free_nodes,
        free_active,
        levels,
        max_rounds,
        active=active,
        interpret=(impl == "interpret"),
    )
    out = unpack_slots(WAVEFRONT_STEP_SLOTS, stats)
    out["free_writes"] = out["free_merged_writes"]  # legacy alias
    return tree, nodes, ok, out


def nbbs_pool_wavefront_step(
    pcfg: PoolConfig,
    trees: Array,
    free_nodes: Array,
    free_shard: Array,
    free_active: Array,
    levels: Array,
    *,
    lane_ids: Array | None = None,
    active: Array | None = None,
    max_rounds: int = 64,
    impl: str = "auto",
    mags=None,
    free_mag_lane: Array | None = None,
    alloc_mag_lane: Array | None = None,
):
    """Pooled mixed release+allocation step across S sharded trees.

    "reference" runs the in-graph lockstep router (`pool_wavefront_step`
    — lanes re-route between pool rounds).  The Pallas paths launch the
    grid-over-shards kernel once per probe attempt: every launch keeps
    one shard's tree VMEM-resident per program, and lanes whose shard is
    exhausted are re-routed to the next shard in the pool's fixed probe
    order before the next launch (an attempt-granular linearization of
    the same routing; identical to the reference whenever no lane
    overflows).  Returns (trees, nodes, shard, ok, stats).

    With `mags` (a `core.magazine.MagazineState`; requires
    `pcfg.magazines`), the magazine layer fuses around the kernel
    launches: the stash pre-pass recycles freed leaf handles of
    `free_mag_lane` lanes before the first launch, the claim phase
    serves `alloc_mag_lane` lanes before any launch runs, and on
    exhaustion one merged spill-back plus a reference-path retry keeps
    failure semantics magazines-off-equivalent.  Magazines are per-lane
    state shared across shards, so these phases live here in the driver
    — the per-shard kernel rows keep their magazine slots zero — and
    the driver fills the aggregate 'magazine_*' slots.  Returns
    (trees, mags, nodes, shard, ok, stats) in this mode.
    """
    impl = _resolve_nbbs(impl)
    K = levels.shape[0]
    if active is None:
        active = jnp.ones(levels.shape, dtype=bool)
    if lane_ids is None:
        lane_ids = jnp.arange(K, dtype=jnp.int32)
    if mags is not None and pcfg.magazines is None:
        raise ValueError("mags given but pcfg has no MagazineConfig")
    if impl == "reference":
        if mags is None:
            return pool_wavefront_step(
                pcfg, trees, free_nodes, free_shard, free_active, levels,
                active, max_rounds, lane_ids,
            )
        return pool_wavefront_step_mag(
            pcfg, trees, mags, free_nodes, free_shard, free_active,
            levels, active, max_rounds, lane_ids, free_mag_lane,
            alloc_mag_lane,
        )
    S = pcfg.n_shards
    home = home_shard(pcfg, lane_ids)
    shard = home
    pending = active
    nodes = jnp.zeros(K, dtype=jnp.int32)
    out_shard = shard
    fa = free_active
    mag_got = jnp.zeros(K, bool)
    f_spills = jnp.int32(0)
    n_stashed = jnp.int32(0)
    if mags is not None:
        # stash pre-pass: recycle freed leaf handles lane-locally; the
        # drop-through mask `fa` feeds the first launch's merged release
        if free_mag_lane is None:
            free_mag_lane = jnp.full(free_nodes.shape[0], -1, jnp.int32)
        mags, fa, stashed, f_spills = _mag_stash_phase(
            pcfg, trees, mags, free_nodes, free_shard, fa, free_mag_lane
        )
        n_stashed = stashed.sum(dtype=jnp.int32)
        # claim phase: leaf-octave lanes pop their magazines and skip
        # the launches entirely; misses stay pending
        if alloc_mag_lane is None:
            alloc_mag_lane = jnp.full(K, -1, jnp.int32)
        want = pending & (levels == pcfg.tree.depth)
        mags, gids, mag_got, _ = magmod.mag_claim(
            pcfg.magazines, mags, want, alloc_mag_lane
        )
        g_sh, g_nd = _gid_parts(pcfg, gids)
        nodes = jnp.where(mag_got, g_nd, nodes)
        out_shard = jnp.where(mag_got, g_sh, out_shard)
        pending = pending & ~mag_got
    # aggregation slots come from the same schema tuple the kernel
    # packs its per-shard stat rows with — neither side can drift
    agg = {name: jnp.int32(0) for name in POOL_STEP_SLOTS}
    for _ in range(S):
        trees, n_a, ok_a, st = pool_wavefront_step_pallas(
            pcfg,
            trees,
            free_nodes,
            free_shard,
            fa,
            levels,
            shard,
            max_rounds,
            active=pending,
            interpret=(impl == "interpret"),
        )
        won = pending & ok_a
        nodes = jnp.where(won, n_a, nodes)
        out_shard = jnp.where(won, shard, out_shard)
        pending = pending & ~ok_a
        shard = jnp.where(pending, (shard + 1) % S, shard)
        named = unpack_slots(POOL_STEP_SLOTS, st)  # [S] column per slot
        for name in POOL_STEP_SLOTS:
            # shards run concurrently within a launch: rounds is the
            # max row; every other slot sums across shards
            red = named[name].max() if name == "rounds" else named[name].sum()
            agg[name] = agg[name] + red
        fa = jnp.zeros_like(free_active)  # frees apply on the first launch
        # early exit is an eager-mode optimization only: under jit
        # `pending` is a tracer and the loop simply runs all S launches
        if jax.core.is_concrete(pending) and not bool(pending.any()):
            break
    if mags is not None:
        # exhaustion spill-back + retry: one merged release of every
        # stashed page, then failed lanes rerun on the reference
        # wavefront (the rare slow path; launches stay magazine-free)
        failed = active & ~(nodes > 0)
        do_spill = failed.any() & (magmod.mag_total(mags) > 0)

        def spill(args):
            return _mag_spill_all(pcfg, *args)

        def no_spill(args):
            trees, mags = args
            z = jnp.int32(0)
            return trees, mags, z, z, z

        trees, mags, sp_m, sp_l, n_spill = jax.lax.cond(
            do_spill, spill, no_spill, (trees, mags)
        )
        retry = failed & do_spill
        trees, n2, s2, ok2, rstats = pool_wavefront_alloc(
            pcfg, trees, levels, retry, max_rounds, lane_ids
        )
        won2 = retry & ok2
        nodes = jnp.where(won2, n2, nodes)
        out_shard = jnp.where(won2, s2, out_shard)
        agg["rounds"] = agg["rounds"] + rstats["rounds"]
        agg["merged_writes"] = (
            agg["merged_writes"] + rstats["merged_writes"] + sp_m
        )
        agg["logical_rmws"] = (
            agg["logical_rmws"] + rstats["logical_rmws"] + sp_l
        )
        agg["fastpath_hits"] = (
            agg["fastpath_hits"] + rstats["fastpath_hits"]
        )
        agg["freed"] = agg["freed"] + n_stashed
        agg["magazine_hits"] = mag_got.sum(dtype=jnp.int32)
        agg["magazine_spills"] = f_spills + n_spill
    ok = nodes > 0
    agg["free_writes"] = agg["free_merged_writes"]  # legacy alias
    # a magazine pop serves a lane off the popped page's recorded
    # shard — recycling, not an overflow probe
    agg["overflows"] = (
        (ok & ~mag_got & (out_shard != home)).sum(dtype=jnp.int32)
    )
    if pcfg.fastpath is None:
        fast_total = jnp.int32(0)
    else:
        fast = levels == fastpath.fp_level(pcfg.tree, pcfg.fastpath)
        fast_total = (active & fast).sum(dtype=jnp.int32)
        if fastpath.fp_level(pcfg.tree, pcfg.fastpath) == pcfg.tree.depth:
            # magazine-served lanes never reached the slab
            fast_total = fast_total - mag_got.sum(dtype=jnp.int32)
    agg["fastpath_spills"] = fast_total - agg["fastpath_hits"]
    if mags is not None:
        return trees, mags, nodes, out_shard, ok, agg
    return trees, nodes, out_shard, ok, agg
