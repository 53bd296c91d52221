"""Jit-resident continuous-batching engine — one compiled decode step.

The host-orchestrated `serve.engine.ServeEngine` proves the buddy
system *admits* realistic serving traffic, but every decode token pays
a host round-trip: tables are rebuilt in numpy, logits sync back for
argmax, and the allocator of record is the host-side `NBBSRef`.  This
module is the ROADMAP's "millions-of-users" refactor: the whole per-
iteration loop — paged decode attention, in-graph page allocation for
lanes crossing a page boundary, greedy sampling, retirement detection,
and the burst free of retired sequences — is one `jax.jit`-compiled
`engine_step` over a device-resident `EngineState`, with **zero host
synchronization inside the step** (verified by the trace-count /
transfer-guard test in tests/test_serving.py).  The Python
`JitServeEngine` is reduced to a thin request-queue shim that drains
arrivals into the compiled step at chunk boundaries.

Design (docs/design.md §8):

  * the engine runs `max_batch` fixed *lanes*; a lane is either empty
    (`seq_id == -1`) or carries one sequence.  All shapes are static,
    so N decode steps re-use one executable;
  * KV pages are allocated *one leaf unit at a time*: admission claims
    the prompt's pages through the same in-graph wavefront
    (`nb_pool_alloc_pages`, all-or-nothing with in-graph rollback), and
    decode steps claim one page for every lane whose next token starts
    a page (`ctx == n_pages * page_tokens`).  Leaf-only allocation
    means the engine pytree needs no index[]: a page handle is the
    (shard, unit_offset) pair stored directly in the lane's page
    table, and the global page id is `shard * pages_per_shard + off`;
  * retirement (out-budget reached, EOS, or an in-step allocation
    overflow) frees **all** of a lane's pages as one merged
    `pool_free_round` burst inside the same compiled step;
  * the prompt's last token is decoded by the *engine*, not prefill:
    prefill (bucketed to power-of-two lengths so compiles are bounded)
    only populates the KV pages of positions `0..S-2`, and the lane
    enters with `ctx = S-1, last_tok = prompt[-1]`.  The first engine
    step then computes position S-1 through the paged path — identical
    attention set, and no per-prompt-length recompiles;
  * `engine_step` returns a schema-checked metrics dict (obs/schema.py
    `ENGINE_METRICS`: pages allocated/freed, overflow lanes, probe
    overflows, free pages + largest allocatable run from the in-graph
    occupancy scan, RMW counters, rounds/probe-distance histograms)
    that the shim accumulates lazily through `obs.metrics.merge` —
    reading them is the *caller's* sync, never the step's.  With
    `ring_capacity > 0` the state also carries an in-graph event ring
    (obs/ring.py) recording one event per step; `snapshot()` drains
    metrics + ring + host-phase spans into the export format
    `obs/trace_export.py` renders as a Perfetto trace.

Failure semantics mirror the PR 1/3 hardening exactly (regression
tests in tests/test_serving.py): requests that can never fit the lane
geometry are rejected at admission instead of head-of-line blocking,
and junk page handles in a lane table are dropped by the free round's
validity mask instead of aliasing live pages.

The differential oracle is `serve.oracle.HostOracleEngine` — the same
scheduling policy run from Python against per-shard `NBBSRef` trees —
which must produce identical page assignments, retirement order, and
final pool occupancy on a replayed trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import time
from collections import Counter, deque
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.concurrent import BUNCH_PACKED, TreeConfig, UNPACKED
from repro.core.fastpath import FastPathConfig
from repro.core.magazine import MagazineConfig, MagazineState, mag_total
from repro.core.nbbs_jax import (
    nb_pool_alloc_pages,
    nb_pool_alloc_pages_mag,
    nb_pool_free_pages,
    nb_pool_free_pages_mag,
)
from repro.core.pool import (
    PoolConfig,
    home_shard,
    pool_free_units,
    pool_init_magazines,
    pool_largest_run,
    pool_mag_free_per_shard,
)
from repro.obs import metrics as om
from repro.obs import ring as oring
from repro.obs.schema import ENGINE_METRICS
from repro.obs.trace_export import SNAPSHOT_VERSION
from repro.serve.engine import Request
from repro.serve.paged_decode import paged_decode_step, serve_prefill

Array = jax.Array
Metrics = om.Metrics

# Incremented inside the traced step body: tracing happens only at
# compile time, so tests can assert "N steps, one trace" (the
# no-recompilation guarantee) by watching this counter.
TRACE_COUNTS: Counter = Counter()


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static geometry of the jitted engine (hashable -> one compile
    per geometry, shared across engine instances)."""

    arch: ArchConfig
    num_pages: int
    page_tokens: int
    max_batch: int
    max_lane_pages: int
    max_out: int
    n_shards: int = 1
    layout: str = "unpacked"
    eos: Optional[int] = None
    impl: str = "auto"
    dtype: str = "float32"
    max_rounds: int = 64
    # fixed-size fast path (core/fastpath.py): a per-shard bitmap slab
    # of single pages carved out of the buddy tree, probed in-graph
    # before the buddy climb on every decode-boundary alloc
    fastpath: bool = False
    fastpath_slab_level: int = 2
    # per-lane magazine capacity (core/magazine.py): every engine lane
    # keeps a LIFO of its own retired pages and recycles them with zero
    # shared-state RMWs; 0 disables magazines entirely (no state, no
    # graph ops)
    magazines: int = 0
    magazine_refill: int = 0
    # in-graph event ring capacity (obs/ring.py); 0 disables the ring
    # (pushes become no-op scatters, so telemetry-off pays nothing)
    ring_capacity: int = 0

    def __post_init__(self):
        if self.num_pages & (self.num_pages - 1):
            raise ValueError("num_pages must be a power of two")
        if self.ring_capacity < 0:
            raise ValueError("ring_capacity must be >= 0")
        if self.n_shards < 1 or (self.n_shards & (self.n_shards - 1)):
            raise ValueError("n_shards must be a power of two >= 1")
        if self.num_pages % self.n_shards:
            raise ValueError("num_pages must divide evenly across shards")
        if self.layout not in ("unpacked", "bunch-packed"):
            raise ValueError(f"unknown tree layout {self.layout!r}")
        if self.magazines < 0 or self.magazine_refill < 0:
            raise ValueError("magazines/magazine_refill must be >= 0")
        if self.fastpath or self.magazines:
            self.pool_config()  # fail fast on bad slab/magazine geometry

    @property
    def pages_per_shard(self) -> int:
        return self.num_pages // self.n_shards

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def pool_config(self) -> PoolConfig:
        depth = (self.pages_per_shard - 1).bit_length()
        layout = BUNCH_PACKED if self.layout == "bunch-packed" else UNPACKED
        fp = (
            FastPathConfig(level=None, slab_level=self.fastpath_slab_level)
            if self.fastpath
            else None
        )
        mcfg = (
            MagazineConfig(
                mag_cap=self.magazines, refill_batch=self.magazine_refill
            )
            if self.magazines
            else None
        )
        return PoolConfig(
            TreeConfig(depth=depth, max_level=0, layout=layout),
            self.n_shards,
            fastpath=fp,
            magazines=mcfg,
        )

    def lane_capacity_tokens(self) -> int:
        return self.max_lane_pages * self.page_tokens


class EngineState(NamedTuple):
    """Device-resident engine state, threaded through `engine_step`."""

    trees: Array       # [S, n_state_words] pool tree state (layout dtype)
    kv_k: Array        # [L, P, page, Hkv, D] global KV page pool
    kv_v: Array
    page_shard: Array  # int32[B, MP]  page handle shard, -1 = no page
    page_off: Array    # int32[B, MP]  page handle unit offset
    seq_id: Array      # int32[B]      -1 = empty lane
    ctx: Array         # int32[B]      tokens currently in the KV cache
    n_pages: Array     # int32[B]      pages mapped in the lane table
    last_tok: Array    # int32[B]      next decode input token
    out_toks: Array    # int32[B, MO]  generated tokens
    n_out: Array       # int32[B]      generated-so-far
    max_new: Array     # int32[B]      per-lane output budget
    active: Array      # bool[B]       decoding this step?
    overflowed: Array  # bool[B]       retired by in-step alloc failure
    done_step: Array   # int32[B]      step index of retirement, -1 live
    step_no: Array     # int32 scalar  global step counter
    ring: oring.EventRing  # in-graph event ring (cap 0 = disabled)
    mag_pages: Array   # int32[B, mag_cap] per-lane magazine (gid, -1=empty)
    mag_depth: Array   # int32[B]          magazine fill depth


def _engine_mags(ecfg: EngineConfig, state: EngineState) -> MagazineState:
    return MagazineState(pages=state.mag_pages, depth=state.mag_depth)


def _zero_metrics(ecfg: EngineConfig) -> Metrics:
    """Fresh all-zero engine metrics (the schema's `ENGINE_METRICS` set;
    per-shard gauges sized to the pool geometry)."""
    return om.zeros(
        ENGINE_METRICS, vector_lens={"free_pages_shard": ecfg.n_shards}
    )


def init_engine_state(ecfg: EngineConfig) -> EngineState:
    arch = ecfg.arch
    B, MP, MO = ecfg.max_batch, ecfg.max_lane_pages, ecfg.max_out
    pcfg = ecfg.pool_config()
    kv_shape = (
        arch.n_layers, ecfg.num_pages, ecfg.page_tokens,
        arch.n_kv_heads, arch.head_dim,
    )
    return EngineState(
        trees=pcfg.empty_trees(),
        kv_k=jnp.zeros(kv_shape, ecfg.jdtype),
        kv_v=jnp.zeros(kv_shape, ecfg.jdtype),
        page_shard=jnp.full((B, MP), -1, jnp.int32),
        page_off=jnp.full((B, MP), -1, jnp.int32),
        seq_id=jnp.full((B,), -1, jnp.int32),
        ctx=jnp.zeros((B,), jnp.int32),
        n_pages=jnp.zeros((B,), jnp.int32),
        last_tok=jnp.zeros((B,), jnp.int32),
        out_toks=jnp.zeros((B, MO), jnp.int32),
        n_out=jnp.zeros((B,), jnp.int32),
        max_new=jnp.zeros((B,), jnp.int32),
        active=jnp.zeros((B,), bool),
        overflowed=jnp.zeros((B,), bool),
        done_step=jnp.full((B,), -1, jnp.int32),
        step_no=jnp.int32(0),
        ring=oring.make_ring(ecfg.ring_capacity),
        **_init_mag_fields(ecfg),
    )


def _init_mag_fields(ecfg: EngineConfig) -> dict:
    """Fresh magazine arrays: one lane per engine lane when magazines
    are on; zero-width placeholders (no memory, no graph ops) when off."""
    B = ecfg.max_batch
    if ecfg.magazines:
        mags = pool_init_magazines(ecfg.pool_config(), B)
        return {"mag_pages": mags.pages, "mag_depth": mags.depth}
    return {
        "mag_pages": jnp.zeros((B, 0), jnp.int32),
        "mag_depth": jnp.zeros((B,), jnp.int32),
    }


def global_tables(ecfg: EngineConfig, page_shard: Array, page_off: Array) -> Array:
    """Device-table view: global page ids, -1 padded — what the paged-
    attention kernel consumes (shard base folded in, mirroring the host
    `PagedKVManager.block_table` numbering)."""
    return jnp.where(
        page_shard >= 0,
        page_shard * ecfg.pages_per_shard + page_off,
        -1,
    )


# ---------------------------------------------------------------------------
# The compiled step
# ---------------------------------------------------------------------------


def _engine_step_impl(
    ecfg: EngineConfig, params: dict, state: EngineState
) -> Tuple[EngineState, Metrics]:
    TRACE_COUNTS[ecfg] += 1  # python side effect: fires at trace only
    pcfg = ecfg.pool_config()
    B, MP, MO = ecfg.max_batch, ecfg.max_lane_pages, ecfg.max_out
    pt = ecfg.page_tokens
    bidx = jnp.arange(B)

    # -- 1. in-graph page allocation for lanes crossing a page boundary
    with jax.named_scope("nbbs_alloc"):
        need = state.active & (state.ctx == state.n_pages * pt)
        need = need & (state.n_pages < MP)  # lane table full = overflow
        if ecfg.magazines:
            # magazine-first claim: a lane that stashed a page at a
            # previous retirement pops it back with zero shared-state
            # RMWs; misses fall through into the same round's
            # fastpath-then-tree wavefront.  Every engine lane owns its
            # own magazine, so the claim rank is identically zero — no
            # group-rank sort in the compiled step
            trees, mags, a_shard, a_off, ok, astats = nb_pool_alloc_pages_mag(
                pcfg, state.trees, _engine_mags(ecfg, state), need,
                state.seq_id, ecfg.max_rounds, mag_lane=bidx,
                mag_rank=jnp.zeros(B, jnp.int32),
            )
        else:
            mags = _engine_mags(ecfg, state)
            trees, a_shard, a_off, ok, astats = nb_pool_alloc_pages(
                pcfg, state.trees, need, state.seq_id, ecfg.max_rounds
            )
        pos = jnp.clip(state.n_pages, 0, MP - 1)
        page_shard = state.page_shard.at[bidx, pos].set(
            jnp.where(ok, a_shard, state.page_shard[bidx, pos])
        )
        page_off = state.page_off.at[bidx, pos].set(
            jnp.where(ok, a_off, state.page_off[bidx, pos])
        )
        n_pages = state.n_pages + ok.astype(jnp.int32)
        overflow_now = (
            state.active & (state.ctx == state.n_pages * pt)
        ) & ~ok

    # -- 2. one paged decode for every writable lane ------------------
    with jax.named_scope("paged_decode"):
        writable = state.active & ~overflow_now
        tables = global_tables(ecfg, page_shard, page_off)
        pool = {"k": state.kv_k, "v": state.kv_v}
        logits, pool, experts = paged_decode_step(
            ecfg.arch, params, pool, tables, state.ctx, state.last_tok,
            page_tokens=pt, impl=ecfg.impl, dtype=ecfg.jdtype,
            active=writable,
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        wrote = writable
        ctx = state.ctx + wrote.astype(jnp.int32)
        out_pos = jnp.clip(state.n_out, 0, MO - 1)
        out_toks = state.out_toks.at[bidx, out_pos].set(
            jnp.where(wrote, nxt, state.out_toks[bidx, out_pos])
        )
        n_out = state.n_out + wrote.astype(jnp.int32)
        last_tok = jnp.where(wrote, nxt, state.last_tok)

    # -- 3+4. retirement + burst free of every retired lane's pages ---
    with jax.named_scope("retire_free"):
        finished = wrote & (n_out >= state.max_new)
        if ecfg.eos is not None:
            finished = finished | (wrote & (nxt == ecfg.eos))
        retire = finished | overflow_now

        f_active = (retire[:, None] & (page_shard >= 0)).reshape(-1)
        if ecfg.magazines:
            # retired lanes stash their pages into their own magazine
            # first (up to mag_cap); the overflow falls through into
            # the same merged free burst.  Block tables fill prefix-
            # wise with distinct pages the lane allocated, so the
            # stash rank is the column index and the handles are
            # known-owned — both stash-phase fast paths apply
            # (no B*MP-wide sort, no [S, B*MP] occupancy re-derivation)
            f_lane = jnp.broadcast_to(bidx[:, None], (B, MP)).reshape(-1)
            f_rank = jnp.broadcast_to(
                jnp.arange(MP, dtype=jnp.int32)[None, :], (B, MP)
            ).reshape(-1)
            trees, mags, freed, fstats = nb_pool_free_pages_mag(
                pcfg, trees, mags,
                page_shard.reshape(-1), page_off.reshape(-1), f_active,
                mag_lane=f_lane, mag_rank=f_rank, assume_owned=True,
            )
        else:
            trees, freed, fstats = nb_pool_free_pages(
                pcfg, trees,
                page_shard.reshape(-1), page_off.reshape(-1), f_active,
            )
        page_shard = jnp.where(retire[:, None], -1, page_shard)
        page_off = jnp.where(retire[:, None], -1, page_off)
        n_pages = jnp.where(retire, 0, n_pages)
        active = state.active & ~retire
        overflowed = state.overflowed | overflow_now
        done_step = jnp.where(
            retire & (state.done_step < 0), state.step_no, state.done_step
        )

    # -- 5. telemetry: named metrics + one ring event per live step ---
    with jax.named_scope("telemetry"):
        fp_shard = pool_free_units(pcfg, trees)  # int32[S], one scan
        if ecfg.magazines:
            # stashed pages are allocated in the tree's eyes but
            # instantly claimable: capacity gauges must count them
            fp_shard = fp_shard + pool_mag_free_per_shard(pcfg, mags)
        free_total = fp_shard.sum(dtype=jnp.int32)
        won = ok.sum(dtype=jnp.int32)
        freed_n = freed.sum(dtype=jnp.int32)
        ring = oring.push(
            state.ring,
            oring.event(
                oring.EV_STEP,
                step=state.step_no,
                lanes_won=won,
                lanes_overflowed=overflow_now.sum(dtype=jnp.int32),
                lanes_spilled=astats["fastpath_spills"],
                frees_merged=freed_n,
                rounds=astats["rounds"],
                free_pages=free_total,
            ),
            mask=state.active.any(),
        )

        m = _zero_metrics(ecfg)
        m["alloc_pages"] = won
        m["freed_pages"] = freed_n
        m["overflow_lanes"] = overflow_now.sum(dtype=jnp.int32)
        m["probe_overflows"] = astats["overflows"]
        m["retired"] = retire.sum(dtype=jnp.int32)
        m["active_lanes"] = active.sum(dtype=jnp.int32)
        m["alloc_rounds"] = astats["rounds"]
        m["merged_writes"] = astats["merged_writes"]
        m["logical_rmws"] = astats["logical_rmws"]
        m["free_merged_writes"] = fstats["free_merged_writes"]
        m["free_logical_rmws"] = fstats["free_logical_rmws"]
        m["free_pages"] = free_total
        m["free_pages_shard"] = fp_shard
        run = pool_largest_run(pcfg, trees)
        if ecfg.magazines:
            # a non-empty magazine can always serve a 1-run
            run = jnp.where(mag_total(mags) > 0, jnp.maximum(run, 1), run)
            m["magazine_hits"] = astats["magazine_hits"]
            m["magazine_spills"] = (
                astats["magazine_spills"] + fstats["magazine_spills"]
            )
            m["magazine_refills"] = astats["magazine_refills"]
        m["largest_run"] = run
        m["fastpath_hits"] = astats["fastpath_hits"]
        m["fastpath_spills"] = astats["fastpath_spills"]
        m["expert_reads"] = experts["expert_reads"]
        m["expert_tokens"] = experts["expert_tokens"]
        # ring counters as per-step deltas (merge sums them back up)
        m["ring_events"] = ring.count - state.ring.count
        m["ring_dropped"] = oring.dropped(ring) - oring.dropped(state.ring)
        # rounds-to-completion of this step's page-boundary wavefront
        m = om.observe(m, "alloc_rounds_hist", astats["rounds"])
        # probe distance of each won allocation (0 = home shard)
        home = home_shard(pcfg, state.seq_id)
        dist = (a_shard - home) % pcfg.n_shards
        m = om.observe_many(m, "probe_distance_hist", dist, ok)

    new_state = EngineState(
        trees=trees, kv_k=pool["k"], kv_v=pool["v"],
        page_shard=page_shard, page_off=page_off,
        seq_id=state.seq_id, ctx=ctx, n_pages=n_pages,
        last_tok=last_tok, out_toks=out_toks, n_out=n_out,
        max_new=state.max_new, active=active, overflowed=overflowed,
        done_step=done_step, step_no=state.step_no + 1,
        ring=ring, mag_pages=mags.pages, mag_depth=mags.depth,
    )
    return new_state, m


# the EngineState argument is donated everywhere below: the KV pool is
# by far the largest buffer in the state, and donation lets XLA update
# it in place instead of copying pool-sized buffers every dispatch
@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def engine_step(
    ecfg: EngineConfig, params: dict, state: EngineState
) -> Tuple[EngineState, Metrics]:
    """One fully-fused decode iteration (alloc + decode + free)."""
    return _engine_step_impl(ecfg, params, state)


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=(2,))
def engine_run(
    ecfg: EngineConfig, params: dict, state: EngineState, num_steps: int
) -> Tuple[EngineState, Metrics]:
    """`num_steps` fused decode iterations under one `lax.scan` — a
    whole chunk of tokens per dispatch, still zero host syncs.  Returns
    (state, metrics with a leading [num_steps] axis)."""
    def body(st, _):
        return _engine_step_impl(ecfg, params, st)

    return jax.lax.scan(body, state, None, length=num_steps)


# ---------------------------------------------------------------------------
# Admission-boundary helpers (host calls these *between* decode bursts)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0,))
def admit_pages(
    ecfg: EngineConfig,
    trees: Array,
    mag_pages: Array,
    mag_depth: Array,
    seq_id: Array,
    need: Array,
) -> Tuple[Array, ...]:
    """All-or-nothing in-graph claim of `need` prompt pages for one
    sequence: every page is a leaf-unit wavefront lane homed by the
    sequence id; on partial failure the successes are rolled back by
    the same merged free pass, so a failed admission leaves the pool
    bit-identical.  Returns (trees, mag_pages, mag_depth, shards[MP],
    offs[MP], admitted, probe_overflows, fastpath_hits,
    fastpath_spills, magazine_spills) — the fastpath counters include
    rolled-back claims, matching the oracle's accounting.

    Admission is *magazine-oblivious* on the claim side (a prompt's
    pages are not any lane's recycled working set), but the exhaustion
    spill-back still applies: when every probe fails and magazines
    hold pages, the whole stash spills back in one burst and the
    failed pages retry — so a full-looking pool whose capacity is
    parked in magazines still admits.  A spill mutates trees and
    magazines even when the admission ultimately fails; callers must
    persist both unconditionally."""
    pcfg = ecfg.pool_config()
    MP = ecfg.max_lane_pages
    lanes = jnp.arange(MP)
    active = lanes < need
    lane_ids = jnp.full((MP,), seq_id, jnp.int32)
    mag_spills = jnp.int32(0)
    if ecfg.magazines:
        mags = MagazineState(pages=mag_pages, depth=mag_depth)
        trees1, mags, shard, off, ok, stats = nb_pool_alloc_pages_mag(
            pcfg, trees, mags, active, lane_ids, ecfg.max_rounds
        )
        mag_pages, mag_depth = mags.pages, mags.depth
        mag_spills = stats["magazine_spills"]
    else:
        trees1, shard, off, ok, stats = nb_pool_alloc_pages(
            pcfg, trees, active, lane_ids, ecfg.max_rounds
        )
    admitted = ok.sum(dtype=jnp.int32) == need
    trees_rb, _, _ = nb_pool_free_pages(
        pcfg, trees1, shard, off, ok & jnp.logical_not(admitted)
    )
    trees_out = jnp.where(admitted, trees1, trees_rb)
    keep = admitted & ok
    return (
        trees_out,
        mag_pages,
        mag_depth,
        jnp.where(keep, shard, -1),
        jnp.where(keep, off, -1),
        admitted,
        stats["overflows"],
        stats["fastpath_hits"],
        stats["fastpath_spills"],
        mag_spills,
    )


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def prefill_insert(
    ecfg: EngineConfig,
    state: EngineState,
    lane: Array,        # int32 scalar: empty lane index
    seq_id: Array,      # int32 scalar
    shards: Array,      # int32[MP] from admit_pages
    offs: Array,        # int32[MP]
    n_pages: Array,     # int32 scalar: pages actually claimed
    kv_len: Array,      # int32 scalar: prompt tokens to copy (= S-1)
    cache_k: Array,     # [L, Spad, Hkv, D] prefill KV (bucketed)
    cache_v: Array,
    last_tok: Array,    # int32 scalar: prompt's final token
    max_new: Array,     # int32 scalar
) -> EngineState:
    """Insert an admitted sequence into an empty lane: scatter the
    prefill KV of positions 0..kv_len-1 into its pages and set the lane
    registers so the next `engine_step` decodes position kv_len (the
    prompt's last token) through the paged path."""
    pt, P, MP = ecfg.page_tokens, ecfg.num_pages, ecfg.max_lane_pages
    gpage = jnp.where(shards >= 0, shards * ecfg.pages_per_shard + offs, P)
    Spad = cache_k.shape[1]
    t = jnp.arange(Spad)
    mask = t < kv_len
    pidx = gpage[jnp.clip(t // pt, 0, MP - 1)]
    pidx = jnp.where(mask, pidx, P)  # OOB page -> dropped write
    slot = t % pt
    kv_k = state.kv_k.at[:, pidx, slot].set(cache_k, mode="drop")
    kv_v = state.kv_v.at[:, pidx, slot].set(cache_v, mode="drop")
    return state._replace(
        kv_k=kv_k, kv_v=kv_v,
        page_shard=state.page_shard.at[lane].set(shards),
        page_off=state.page_off.at[lane].set(offs),
        seq_id=state.seq_id.at[lane].set(seq_id),
        ctx=state.ctx.at[lane].set(kv_len),
        n_pages=state.n_pages.at[lane].set(n_pages),
        last_tok=state.last_tok.at[lane].set(last_tok),
        n_out=state.n_out.at[lane].set(0),
        max_new=state.max_new.at[lane].set(max_new),
        active=state.active.at[lane].set(True),
        overflowed=state.overflowed.at[lane].set(False),
        done_step=state.done_step.at[lane].set(-1),
    )


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def clear_lanes(
    ecfg: EngineConfig, state: EngineState, mask: Array
) -> EngineState:
    """Reset drained lanes to empty (their pages were already freed by
    the retirement burst inside `engine_step`)."""
    return state._replace(
        seq_id=jnp.where(mask, -1, state.seq_id),
        ctx=jnp.where(mask, 0, state.ctx),
        n_out=jnp.where(mask, 0, state.n_out),
        overflowed=jnp.where(mask, False, state.overflowed),
        done_step=jnp.where(mask, -1, state.done_step),
    )


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


# jitted so chunked accumulation stays transfer-free; the kind-aware
# semantics (counters/histograms sum, gauges keep the latest value)
# live in obs/metrics.py, keyed off the schema — no hand-listed fields
_reduce_traj = jax.jit(om.reduce_trajectory)
_acc_stats = jax.jit(om.merge)


# ---------------------------------------------------------------------------
# The thin host shim
# ---------------------------------------------------------------------------

# records the span journal holds (about 14 MB of them): some 5,000 pass
# in a benchmark run's traced span and grace together, at about 10 a
# 230-ms chunk
SPAN_JOURNAL_CAP = 1 << 15


class SpanJournal:
    """The newest `cap` records of the host-phase journal, indexed as
    the list of every record ever appended: `len()` counts them all, and
    an index or a slice (a `parent`, or a caller's `len()` taken
    earlier) reads the records still held -- older ones are dropped,
    `dropped` counts them, and an index into them raises IndexError.
    Iterating yields the records held, oldest first."""

    def __init__(self, cap: int = SPAN_JOURNAL_CAP) -> None:
        self._recs: deque = deque(maxlen=cap)
        self.dropped = 0

    def append(self, rec: Dict) -> None:
        if len(self._recs) == self._recs.maxlen:
            self.dropped += 1
        self._recs.append(rec)

    def __len__(self) -> int:
        return self.dropped + len(self._recs)

    def __iter__(self):
        return iter(self._recs)

    def __getitem__(self, i):
        if isinstance(i, slice):
            start, stop, step = i.indices(len(self))
            if step != 1:
                raise ValueError("SpanJournal slices take no step")
            start = max(start, self.dropped)
            return list(itertools.islice(
                self._recs, start - self.dropped,
                max(stop, start) - self.dropped))
        j = (i + len(self) if i < 0 else i) - self.dropped
        if not 0 <= j < len(self._recs):
            raise IndexError(f"span {i} is not held (dropped {self.dropped},"
                             f" held {len(self._recs)})")
        return self._recs[j]


class JitServeEngine:
    """Request-queue shim around the compiled step.

    The public surface mirrors `ServeEngine` (submit / step /
    run_to_completion / stats / completed) so callers migrate by
    swapping the class; the difference is *where the loop lives*: all
    per-token work happens on device inside `engine_step`, and the host
    only touches the state at drain/admission boundaries (`decode_steps`
    runs whole chunks with no host sync at all)."""

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        *,
        num_pages: int = 256,
        page_tokens: int = 16,
        max_batch: int = 8,
        max_lane_pages: Optional[int] = None,
        max_out: int = 64,
        eos_token: Optional[int] = None,
        dtype=jnp.float32,
        impl: str = "auto",
        n_shards: int = 1,
        layout: Optional[str] = None,
        max_rounds: int = 64,
        fastpath: bool = False,
        fastpath_slab_level: int = 2,
        magazines: int = 0,
        magazine_refill: int = 0,
        ring_capacity: int = 0,
    ) -> None:
        assert cfg.family in ("dense", "moe", "vlm", "audio"), (
            "paged engine covers attention families (docs/design.md §5)"
        )
        if max_lane_pages is None:
            max_lane_pages = min(num_pages, 128)
        self.ecfg = EngineConfig(
            arch=cfg,
            num_pages=num_pages,
            page_tokens=page_tokens,
            max_batch=max_batch,
            max_lane_pages=max_lane_pages,
            max_out=max_out,
            n_shards=n_shards,
            layout=layout or "unpacked",
            eos=eos_token,
            impl=impl,
            dtype=jnp.dtype(dtype).name,
            max_rounds=max_rounds,
            fastpath=fastpath,
            fastpath_slab_level=fastpath_slab_level,
            magazines=magazines,
            magazine_refill=magazine_refill,
            ring_capacity=ring_capacity,
        )
        self.cfg = cfg
        self.params = params
        self.page_tokens = page_tokens
        self.max_batch = max_batch
        self.state = init_engine_state(self.ecfg)
        self.waiting: List[Request] = []
        self.running: Dict[int, Request] = {}   # seq_id -> request
        self._lane_of: Dict[int, int] = {}
        self.completed: Dict[int, Request] = {}
        self.done_steps: Dict[int, int] = {}    # seq_id -> retire step
        self.retired_order: List[int] = []      # drain-observed order
        self.stats = {
            "admitted": 0, "queued_full": 0, "rejected": 0,
            "steps": 0, "overflow_retired": 0,
            # admission-path slab counters (decode-path ones live in
            # the device-side metric accumulator; `stat_totals` folds
            # both through one schema-aware merge)
            "admit_fastpath_hits": 0, "admit_fastpath_spills": 0,
            # admission-path magazine spill-backs (the decode-path
            # magazine counters live in the device accumulator)
            "admit_magazine_spills": 0,
        }
        self.acc = _zero_metrics(self.ecfg)  # device-side totals
        # host-phase span journal, in seconds since engine construction:
        # the `engine.*` records of `_span`, and the `admit` / `decode`
        # / `drain` records that the trace exporter and the benchmark's
        # `admit_host_ms` read (admissions that admitted, decode chunks,
        # drains that drained); for an expert model, an `experts`
        # record at every drain with the running `expert_reads` and
        # `expert_tokens` of the steps before `step`; the newest
        # SPAN_JOURNAL_CAP are held
        self.spans = SpanJournal()
        self._open: List[int] = []  # indices of the open `_span` records
        self._t_origin = time.perf_counter()

    def _now(self) -> float:
        return time.perf_counter() - self._t_origin

    def _record_span(self, phase: str, t0: float, step0: int, **extra):
        self.spans.append({
            "phase": phase, "t0": t0, "t1": self._now(),
            "step0": step0, "step1": self.stats["steps"], **extra,
        })

    @contextlib.contextmanager
    def _span(self, name: str, **args):
        """One host phase, written to both outputs: a profiler
        annotation `engine.<name>`, which a JAX trace shows beside the
        device ops, and a journal record with `phase` `engine.<name>`,
        `t0`/`t1` (enclosing the annotation), `step0`/`step1`, `parent`
        (the index in `spans` of the enclosing record, or -1) and
        `args` (`rid` for a span of one request)."""
        rec = {"phase": f"engine.{name}", "t0": self._now(), "t1": None,
               "step0": self.stats["steps"], "step1": None,
               "parent": self._open[-1] if self._open else -1, **args}
        self._open.append(len(self.spans))
        self.spans.append(rec)
        try:
            with jax.profiler.TraceAnnotation(rec["phase"]):
                yield rec
        finally:
            self._open.pop()
            rec["t1"], rec["step1"] = self._now(), self.stats["steps"]

    # -- admission ----------------------------------------------------
    def _pages_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 0) // self.page_tokens)

    def _oversized(self, req: Request) -> bool:
        """A request that can never fit the lane geometry (mirrors the
        PagedKVManager ValueError semantics: reject, don't block)."""
        total = len(req.prompt) + req.max_new_tokens
        return (
            self._pages_for(total) > self.ecfg.max_lane_pages
            or self._pages_for(total) > self.ecfg.num_pages
            or req.max_new_tokens > self.ecfg.max_out
        )

    def submit(self, req: Request) -> None:
        self.waiting.append(req)

    def _free_lanes(self) -> List[int]:
        with self._span("sync"):
            seq = np.asarray(self.state.seq_id)
        return [int(i) for i in np.nonzero(seq < 0)[0]]

    def _admit(self) -> None:
        with self._span("admit") as span:
            admitted0 = self.stats["admitted"]
            free = self._free_lanes()
            while self.waiting and free:
                req = self.waiting[0]
                if self._oversized(req):
                    self.waiting.pop(0)
                    req.done = True
                    self.completed[req.req_id] = req
                    self.stats["rejected"] += 1
                    continue
                need = self._pages_for(len(req.prompt) - 1)
                with self._span("alloc", rid=req.req_id):
                    (
                        trees, mag_pages, mag_depth, shards, offs, admitted,
                        _, fp_h, fp_s, mag_sp,
                    ) = admit_pages(
                        self.ecfg, self.state.trees,
                        self.state.mag_pages, self.state.mag_depth,
                        jnp.int32(req.req_id), jnp.int32(need),
                    )
                    # persist trees+magazines even on failure: an
                    # exhaustion spill-back moves pages from magazines
                    # into the tree whether or not the admission fits
                    self.state = self.state._replace(
                        trees=trees, mag_pages=mag_pages, mag_depth=mag_depth
                    )
                    with self._span("sync"):
                        fits = bool(admitted)
                        if self.ecfg.fastpath:
                            self.stats["admit_fastpath_hits"] += int(fp_h)
                            self.stats["admit_fastpath_spills"] += int(fp_s)
                        if self.ecfg.magazines:
                            self.stats["admit_magazine_spills"] += int(mag_sp)
                if not fits:
                    self.stats["queued_full"] += 1
                    break  # pool full: natural admission control
                self.waiting.pop(0)
                self._insert(free.pop(0), req, shards, offs, need)
                self.stats["admitted"] += 1
            n_adm = self.stats["admitted"] - admitted0
            if n_adm:
                self._record_span("admit", span["t0"], span["step0"],
                                  admitted=n_adm)

    def _insert(self, lane: int, req: Request, shards, offs, n_pages) -> None:
        S = len(req.prompt)
        arch, ecfg = self.cfg, self.ecfg
        Spad = _next_pow2(S)
        with self._span("prefill", rid=req.req_id):
            if S > 1:
                toks = np.zeros((1, Spad), np.int32)
                toks[0, :S] = req.prompt
                _, cache = serve_prefill(
                    arch, self.params, {"tokens": jnp.asarray(toks)},
                    max_len=Spad, dtype=ecfg.jdtype,
                )
                cache_k, cache_v = cache["k"][:, 0], cache["v"][:, 0]
            else:
                kv_shape = (
                    arch.n_layers, Spad, arch.n_kv_heads, arch.head_dim
                )
                cache_k = jnp.zeros(kv_shape, ecfg.jdtype)
                cache_v = jnp.zeros(kv_shape, ecfg.jdtype)
        with self._span("insert", rid=req.req_id):
            self.state = prefill_insert(
                ecfg, self.state,
                jnp.int32(lane), jnp.int32(req.req_id), shards, offs,
                jnp.int32(n_pages), jnp.int32(S - 1), cache_k, cache_v,
                jnp.int32(req.prompt[S - 1]), jnp.int32(req.max_new_tokens),
            )
        self.running[req.req_id] = req
        self._lane_of[req.req_id] = lane

    # -- the device loop ----------------------------------------------
    def decode_steps(self, n: int, *, fused: bool = False) -> None:
        """Run n compiled decode iterations with no host sync.  With
        `fused=True` the whole chunk is one `lax.scan` dispatch."""
        with self._span("decode_steps") as span:
            if fused:
                self.state, traj = engine_run(
                    self.ecfg, self.params, self.state, n
                )
                self.acc = _acc_stats(self.acc, _reduce_traj(traj))
            else:
                for _ in range(n):
                    self.state, stat = engine_step(
                        self.ecfg, self.params, self.state
                    )
                    self.acc = _acc_stats(self.acc, stat)
            self.stats["steps"] += n
            self._record_span("decode", span["t0"], span["step0"], n=n,
                              fused=int(fused))

    def _drain(self) -> List[int]:
        """Collect retired lanes (one host sync), clear them, and
        return the drained seq ids in retirement-step order."""
        with self._span("drain") as span:
            # an expert model's running expert counts ride the same read
            experts = (
                (self.acc["expert_reads"], self.acc["expert_tokens"])
                if self.cfg.n_experts else ()
            )
            with self._span("sync"):
                (seq, act, n_out, out_toks, over, done), experts = (
                    jax.device_get(((
                        self.state.seq_id, self.state.active,
                        self.state.n_out, self.state.out_toks,
                        self.state.overflowed, self.state.done_step,
                    ), experts)))
            if experts:
                self._record_span(
                    "experts", span["t0"], span["step0"],
                    step=self.stats["steps"],
                    expert_reads=int(experts[0]),
                    expert_tokens=int(experts[1]),
                )
            lanes = np.nonzero((seq >= 0) & ~act)[0]
            # deterministic retirement order: by retire step, then lane id
            lanes = sorted(lanes, key=lambda i: (int(done[i]), int(i)))
            drained = []
            for lane in lanes:
                sid = int(seq[lane])
                req = self.running.pop(sid)
                self._lane_of.pop(sid)
                req.out_tokens = [
                    int(t) for t in out_toks[lane, : n_out[lane]]
                ]
                req.done = True
                self.completed[sid] = req
                self.done_steps[sid] = int(done[lane])
                self.retired_order.append(sid)
                if over[lane]:
                    self.stats["overflow_retired"] += 1
                drained.append(sid)
            if drained:
                mask = np.zeros((self.ecfg.max_batch,), bool)
                mask[list(lanes)] = True
                with self._span("clear"):
                    self.state = clear_lanes(
                        self.ecfg, self.state, jnp.asarray(mask)
                    )
                self._record_span("drain", span["t0"], span["step0"],
                                  drained=len(drained))
        return drained

    # -- ServeEngine-compatible surface --------------------------------
    def step(self) -> int:
        """Drain + admit + one compiled decode step.  Returns the
        number of running sequences (this *is* a host sync — use
        `decode_steps` for the no-sync hot loop)."""
        self._drain()
        self._admit()
        if not self.running:
            return 0
        self.decode_steps(1)
        with self._span("sync"):
            return int(np.asarray(self.state.active).sum())

    def run_to_completion(
        self, max_steps: int = 10_000, chunk: int = 1
    ) -> None:
        steps = 0
        while steps < max_steps:
            self._drain()
            self._admit()
            if not self.running and not self.waiting:
                return
            if not self.running:  # waiting but pool full of nothing??
                break
            n = min(chunk, max_steps - steps)
            self.decode_steps(n, fused=chunk > 1)
            steps += n

    # -- observability -------------------------------------------------
    def stat_totals(self) -> Dict[str, object]:
        """Sync and return all accumulated metrics: device accumulator
        and host scheduler counters folded through ONE schema-aware
        `obs.metrics.merge` (no hand-rolled `+=` per field).  The
        admission-path slab claims contribute to `fastpath_hits`/
        `fastpath_spills` as well as their `admit_*` breakouts, so the
        combined totals compare directly against `PageOracle`'s."""
        host = om.host_counters({
            "steps": self.stats["steps"],
            "admitted": self.stats["admitted"],
            "queued_full": self.stats["queued_full"],
            "rejected": self.stats["rejected"],
            "overflow_retired": self.stats["overflow_retired"],
            "admit_fastpath_hits": self.stats["admit_fastpath_hits"],
            "admit_fastpath_spills": self.stats["admit_fastpath_spills"],
            "fastpath_hits": self.stats["admit_fastpath_hits"],
            "fastpath_spills": self.stats["admit_fastpath_spills"],
            "admit_magazine_spills": self.stats["admit_magazine_spills"],
            "magazine_spills": self.stats["admit_magazine_spills"],
        })
        # pad both sides to the union key set (merge refuses drift);
        # device values ride the "new" side so gauges keep theirs
        acc = dict(self.acc)
        for k in host:
            acc.setdefault(k, jnp.int32(0))
        base = {k: host.get(k, jnp.zeros_like(v)) for k, v in acc.items()}
        with self._span("sync"):
            return om.to_host(om.merge(base, acc))

    def snapshot(self) -> Dict[str, object]:
        """Drain the whole telemetry plane into the exporter's snapshot
        format (obs/trace_export.py): schema-checked metric totals, the
        event ring's surviving window, and the host-phase span log.
        This is a deliberate host sync — call it at run boundaries."""
        ecfg = self.ecfg
        metrics = self.stat_totals()
        with self._span("sync"):
            events = oring.drain(self.state.ring)
        return {
            "obs_schema": SNAPSHOT_VERSION,
            "source": "jit_engine",
            "config": {
                "num_pages": ecfg.num_pages,
                "page_tokens": ecfg.page_tokens,
                "max_batch": ecfg.max_batch,
                "max_lane_pages": ecfg.max_lane_pages,
                "n_shards": ecfg.n_shards,
                "layout": ecfg.layout,
                "fastpath": ecfg.fastpath,
                "magazines": ecfg.magazines,
                "ring_capacity": ecfg.ring_capacity,
            },
            "metrics": metrics,
            "events": events,
            "spans": list(self.spans),
        }

    def device_free_pages(self) -> int:
        with self._span("sync"):
            free = int(pool_free_units(
                self.ecfg.pool_config(), self.state.trees).sum())
            if self.ecfg.magazines:  # stashed pages are instantly claimable
                free += int(self.state.mag_depth.sum())
        return free

    def device_block_table(self, seq_id: int) -> np.ndarray:
        """Global-page-id table of one running sequence (debug/test
        sync; mirrors `PagedKVManager.block_table` numbering)."""
        lane = self._lane_of[seq_id]
        with self._span("sync"):
            tables = global_tables(
                self.ecfg, self.state.page_shard, self.state.page_off
            )
            return np.asarray(tables[lane])
