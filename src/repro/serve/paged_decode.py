"""Paged decode step — the full-model consumer of the NBBS page pool.

For attention families (dense/moe/vlm/audio, and the hybrid's shared
attention sites), the decode-time KV cache lives in a global page pool
[L, P, page, Hkv, D] addressed through per-sequence block tables
produced by `memory.PagedKVManager` (buddy runs).  Each decode step:

  1. computes this token's K/V per layer,
  2. scatters them into the pool page/slot given by the block table
     (page = table[b, pos // page_tokens], slot = pos % page_tokens),
  3. attends over the pages via `kernels.ops.paged_attention`
     (Pallas on TPU, jnp reference elsewhere — same math), a window
     layer over the newest `window` positions only,
  4. runs the MLP, or for an expert model the routed experts this
     chip holds (`models.moe.apply_moe_routed`).

Per-sequence context lengths make this the continuous-batching step:
sequences at different positions decode together in one jitted call.

The step accepts an optional `active` lane mask so it can run at a
*static* batch width inside the jit-resident engine (docs/design.md
§8): inactive lanes contribute nothing — their K/V scatter is dropped
(the page index is redirected out of bounds and the scatter uses
``mode="drop"``) and their attention context is forced to zero, so the
kernel skips every page and emits zeros.  With `active=None` the
behavior is exactly the historical all-lanes-live step.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models.attention import apply_rope
from repro.models.layers import apply_swiglu, embed, logits as lm_logits, rms_norm
from repro.models.transformer import prefill, rope_arrays, serve_moe, window_array

Array = jax.Array


@functools.partial(
    jax.jit, static_argnums=(0,), static_argnames=("max_len", "dtype")
)
def serve_prefill(cfg: ArchConfig, params, batch, *, max_len, dtype):
    """Jitted prefill for the serving engines (one compile per prompt
    bucket — both engines pad prompts to a bounded set of lengths)."""
    return prefill(cfg, params, batch, max_len, dtype=dtype)


def init_pool(
    cfg: ArchConfig, num_pages: int, page_tokens: int, dtype=jnp.bfloat16
) -> dict:
    shape = (cfg.n_layers, num_pages, page_tokens, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@functools.partial(
    jax.jit,
    static_argnums=(0,),
    static_argnames=("page_tokens", "impl", "dtype"),
)
def paged_decode_step(
    cfg: ArchConfig,
    params: dict,
    pool: dict,
    block_tables: Array,  # [B, max_pages] int32, -1 padded
    context_lens: Array,  # [B] int32 — tokens already in cache
    tokens: Array,  # [B] int32 — the new token per sequence
    *,
    page_tokens: int,
    impl: str = "auto",
    dtype=jnp.bfloat16,
    active: Array | None = None,  # bool[B]; None = all lanes live
) -> Tuple[Array, dict, dict]:
    """Returns (logits [B, V], updated pool, expert counts).
    Dense-family archs only.  The counts are int32 sums over the
    layers: `expert_reads`, held experts with a routed token, and
    `expert_tokens`, (token, held expert) pairs routed; zero for a
    model without experts.  Inactive lanes compute no expert rows and
    count in neither."""
    assert cfg.family in ("dense", "moe", "vlm", "audio"), cfg.family
    B = tokens.shape[0]
    P = pool["k"].shape[1]
    if active is None:
        active = jnp.ones((B,), dtype=bool)
    x = embed(params["embed"], tokens[:, None], dtype, scale=cfg.embed_scale)
    positions = context_lens[:, None]  # this token's position per seq
    windows = window_array(cfg)

    # page/slot of the new token per sequence; lanes that are inactive
    # (or whose table has no page mapped at this position) are steered
    # to the out-of-bounds page P so the scatter drops their write
    # instead of aliasing page 0 / the last page
    page_raw = block_tables[
        jnp.arange(B), context_lens // page_tokens
    ]  # [B]
    page_idx = jnp.where(active & (page_raw >= 0), page_raw, P)
    slot = context_lens % page_tokens
    ctx_att = jnp.where(active, context_lens + 1, 0)

    def body(carry, xs):
        # the pools ride the carry and are updated in place: passed as
        # scan xs/ys, XLA keeps a second whole-pool buffer for the
        # stacked ys, which does not fit beside stablelm-3b on one v5e
        x, pk, pv = carry  # pk/pv: [L, P, page, Hkv, D]
        lp, window, layer, rope = xs
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q = (h @ lp["attn"]["wq"].astype(dtype)).reshape(
            B, 1, cfg.n_heads, cfg.head_dim
        )
        k = (h @ lp["attn"]["wk"].astype(dtype)).reshape(
            B, 1, cfg.n_kv_heads, cfg.head_dim
        )
        v = (h @ lp["attn"]["wv"].astype(dtype)).reshape(
            B, 1, cfg.n_kv_heads, cfg.head_dim
        )
        q = apply_rope(q, positions, cfg.rope_theta, rope)
        k = apply_rope(k, positions, cfg.rope_theta, rope)
        # scatter this token's K/V into its page (inactive lanes were
        # redirected to the OOB page above and are dropped here)
        pk = pk.at[layer, page_idx, slot].set(k[:, 0], mode="drop")
        pv = pv.at[layer, page_idx, slot].set(v[:, 0], mode="drop")
        # the kernel reads this layer's pages from the stacked pools;
        # a `pk[layer]` operand would be copied whole at every layer
        o = ops.paged_attention(
            q[:, 0],
            pk,
            pv,
            layer,
            block_tables,
            ctx_att,
            window if cfg.window_pattern else None,
            softcap=cfg.attn_softcap or None,
            impl=impl,
        )
        h = o.reshape(B, 1, -1) @ lp["attn"]["wo"].astype(dtype)
        if cfg.post_norm:
            h = rms_norm(h, lp["ln1_post"], cfg.norm_eps)
        x = x + h
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        counts = None
        if cfg.n_experts:
            h, counts = serve_moe(cfg, lp["moe"], h, active)
        else:
            h = apply_swiglu(lp["mlp"], h, dtype=dtype)
        if cfg.post_norm:
            h = rms_norm(h, lp["ln2_post"], cfg.norm_eps)
        return (x + h, pk, pv), counts

    layers = jnp.arange(cfg.n_layers)
    (x, ks, vs), counts = jax.lax.scan(
        body, (x, pool["k"], pool["v"]),
        (params["layers"], windows, layers, rope_arrays(cfg)),
    )
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    lg = lm_logits(h[:, 0], table, cfg.final_softcap or None)
    if counts is None:
        zero = jnp.zeros((), jnp.int32)
        counts = {"expert_reads": zero, "expert_tokens": zero}
    else:
        counts = {k: v.sum(dtype=jnp.int32) for k, v in counts.items()}
    return lg, {"k": ks, "v": vs}, counts
