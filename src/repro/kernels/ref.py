"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics-defining implementations: kernel tests sweep
shapes/dtypes and assert allclose against these functions; the model
code uses them on backends where Mosaic lowering is unavailable (this
CPU container's dry-run) — selected by `ops.py`.

The NBBS wavefront kernel's oracle is `repro.core.concurrent.
wavefront_alloc` (shared code, by construction identical); re-exported
here for uniformity.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.concurrent import wavefront_alloc as nbbs_wavefront_reference  # noqa: F401

Array = jax.Array

NEG_INF = -1e30


def mha_reference(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> Array:
    """Dense reference attention. q: [B,Hq,S,D]; k,v: [B,Hkv,Sk,D].

    GQA broadcast, causal/sliding-window masks and logit softcap match
    `flash_attention.flash_attention_fwd` exactly.
    """
    B, Hq, S, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32)
    ) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    rows = jnp.arange(S)[:, None]
    cols = jnp.arange(Sk)[None, :]
    mask = jnp.ones((S, Sk), dtype=bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    # Neutralize fully-masked rows (can only happen with degenerate
    # windows); softmax over all-NEG_INF rows would be uniform garbage.
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[None, None].any(-1, keepdims=True), p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_reference(
    q: Array,
    k_pages: Array,
    v_pages: Array,
    block_tables: Array,
    context_lens: Array,
    window: Array | int = 0,
    *,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> Array:
    """Decode attention through a page table (the NBBS consumer).

    q:            [B, Hq, D]        — one new token per sequence
    k/v_pages:    [P, page, Hkv, D] — global page pool (buddy blocks)
    block_tables: [B, max_pages]    — page ids per sequence, -1 padded
    context_lens: [B]               — valid kv length per sequence
    window:       []                — sliding window: the newest
                                      `window` positions (0 = all)
    returns       [B, Hq, D]
    """
    B, Hq, D = q.shape
    P, page, Hkv, _ = k_pages.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    max_pages = block_tables.shape[1]

    safe_tables = jnp.maximum(block_tables, 0)
    k = k_pages[safe_tables]  # [B, max_pages, page, Hkv, D]
    v = v_pages[safe_tables]
    k = k.reshape(B, max_pages * page, Hkv, D)
    v = v.reshape(B, max_pages * page, Hkv, D)
    kr = jnp.repeat(k, group, axis=2)  # [B, L, Hq, D]
    vr = jnp.repeat(v, group, axis=2)
    s = jnp.einsum(
        "bhd,blhd->bhl", q.astype(jnp.float32), kr.astype(jnp.float32)
    ) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(max_pages * page)[None, :]
    valid_page = (block_tables >= 0)[:, :, None]  # [B, max_pages, 1]
    valid = jnp.broadcast_to(valid_page, (B, max_pages, page)).reshape(
        B, max_pages * page
    )
    valid &= pos < context_lens[:, None]
    window = jnp.asarray(window, jnp.int32)
    lo = jnp.where(window > 0, context_lens - window, 0)
    valid &= pos >= lo[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhl,blhd->bhd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)
