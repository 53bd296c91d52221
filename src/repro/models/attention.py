"""Attention: GQA with RoPE; chunked (flash-style) jnp implementation.

Two compute paths:

  * `chunked_attention` — pure-jnp online-softmax over KV chunks via
    lax.scan.  This is the *memory-safe* path used under jit for
    training and long prefill (peak logits memory S x chunk instead of
    S x S) and the path that lowers in the CPU dry-run.  Supports a
    *traced* sliding-window size, which lets a scanned stack of layers
    carry a per-layer window array (gemma2 local/global alternation)
    through one scan body.
  * `repro.kernels.ops.flash_attention` — the fused Pallas kernel
    (static variant selection), picked when the backend can lower it
    and the window is static.

Decode attention over a full in-graph KV cache is plain dense attention
on [B, S] logits (one query token), with sharding constraints leaving
XLA's SPMD partitioner to produce the flash-decode partial-softmax
combine when the cache is sequence-sharded.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import apply_rope

Array = jax.Array
NEG_INF = -1e30


def init_attention(
    key: Array, d: int, n_heads: int, n_kv_heads: int, head_dim: int
) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d ** -0.5
    so = (n_heads * head_dim) ** -0.5
    return {
        "wq": jax.random.normal(kq, (d, n_heads * head_dim), jnp.float32) * s,
        "wk": jax.random.normal(kk, (d, n_kv_heads * head_dim), jnp.float32) * s,
        "wv": jax.random.normal(kv, (d, n_kv_heads * head_dim), jnp.float32) * s,
        "wo": jax.random.normal(ko, (n_heads * head_dim, d), jnp.float32) * so,
    }


def chunked_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: Optional[Array] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    chunk: int = 1024,
    q_offset: int = 0,
) -> Array:
    """Online-softmax attention over KV chunks.

    q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D]  (seq-major layout).
    `window` may be a traced scalar (<=0 means no window).
    Returns [B, Sq, Hq, D].
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    chunk = min(chunk, Sk)
    # Pad KV to a chunk multiple (masked out below).
    pad = (-Sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Sk + pad) // chunk
    kc = k.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)

    # GQA via grouped einsum — no materialized repeat, no f32 cast of
    # K/V (bf16 on the wire, f32 MXU accumulation): the HLO-roofline
    # analysis showed cast+repeat dominating decode memory traffic.
    qg = q.reshape(B, Sq, Hkv, group, D)
    rows = q_offset + jnp.arange(Sq)
    win = None if window is None else jnp.asarray(window, jnp.int32)

    def step(carry, xs):
        m, l, acc = carry  # [B,Hkv,G,Sq], ..., [B,Hkv,G,Sq,D]
        ci, kch, vch = xs  # kch/vch: [B, chunk, Hkv, D]
        cols = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, kch,
            preferred_element_type=jnp.float32,
        ) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = (cols < Sk)[None, :]
        if causal:
            mask = mask & (cols[None, :] <= rows[:, None])
        if win is not None:
            mask = mask & (
                (win <= 0) | (cols[None, :] > rows[:, None] - win)
            )
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        alpha = jnp.where(m_new == NEG_INF, 1.0, alpha)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where((m_new == NEG_INF)[..., None], 0.0, p)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vch.dtype), vch,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((B, Hkv, group, Sq), NEG_INF, jnp.float32),
        jnp.zeros((B, Hkv, group, Sq), jnp.float32),
        jnp.zeros((B, Hkv, group, Sq, D), jnp.float32),
    )
    (m, l, acc), _ = lax.scan(
        step, init, (jnp.arange(n_chunks), kc, vc)
    )
    norm = jnp.where(l == 0.0, 1.0, l)
    out = acc / norm[..., None]  # [B,Hkv,G,Sq,D]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


def decode_attention(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    context_len: Array,
    *,
    window: Optional[Array] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> Array:
    """One-token decode over a dense cache.

    q: [B, 1, Hq, D]; k_cache/v_cache: [B, S, Hkv, D]; context_len: [] or [B].
    """
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    # grouped einsum: bf16 cache on the wire, f32 accumulation — never
    # materialize an f32 or head-repeated copy of the cache
    qg = q.reshape(B, -1, Hkv, group, D)
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(S)[None, :]
    ctx = jnp.broadcast_to(jnp.asarray(context_len), (B,))[:, None]
    mask = pos < ctx
    if window is not None:
        win = jnp.asarray(window, jnp.int32)
        mask = mask & ((win <= 0) | (pos > ctx - 1 - win))
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, -1, Hq, D).astype(q.dtype)


def attention_block(
    p: dict,
    x: Array,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    causal: bool = True,
    window: Optional[Array] = None,
    softcap: Optional[float] = None,
    positions: Optional[Array] = None,
    chunk: int = 1024,
    rope: Optional[Tuple[Array, Array]] = None,
) -> Array:
    """Full GQA block (projections + RoPE + chunked attention).

    x: [B, S, d] -> [B, S, d].  `rope`: the layer's rotary table where
    the model mixes kinds (`layers.apply_rope`).
    """
    B, S, d = x.shape
    dtype = x.dtype
    q = (x @ p["wq"].astype(dtype)).reshape(B, S, n_heads, head_dim)
    k = (x @ p["wk"].astype(dtype)).reshape(B, S, n_kv_heads, head_dim)
    v = (x @ p["wv"].astype(dtype)).reshape(B, S, n_kv_heads, head_dim)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = apply_rope(q, positions, rope_theta, rope)
    k = apply_rope(k, positions, rope_theta, rope)
    out = chunked_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, chunk=chunk
    )
    return out.reshape(B, S, n_heads * head_dim) @ p["wo"].astype(dtype)


def attention_decode_block(
    p: dict,
    x: Array,
    k_cache: Array,
    v_cache: Array,
    pos: Array,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[Array] = None,
    softcap: Optional[float] = None,
) -> Tuple[Array, Array, Array]:
    """Decode step: x [B, 1, d], cache [B, S, Hkv, D], pos [] scalar.

    Returns (out [B,1,d], new_k_cache, new_v_cache)."""
    B, _, d = x.shape
    dtype = x.dtype
    q = (x @ p["wq"].astype(dtype)).reshape(B, 1, n_heads, head_dim)
    k = (x @ p["wk"].astype(dtype)).reshape(B, 1, n_kv_heads, head_dim)
    v = (x @ p["wv"].astype(dtype)).reshape(B, 1, n_kv_heads, head_dim)
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    k_cache = lax.dynamic_update_slice(k_cache, k, (0, pos, 0, 0))
    v_cache = lax.dynamic_update_slice(v_cache, v, (0, pos, 0, 0))
    out = decode_attention(
        q, k_cache, v_cache, pos + 1, window=window, softcap=softcap
    )
    out = out.reshape(B, 1, n_heads * head_dim) @ p["wo"].astype(dtype)
    return out, k_cache, v_cache


def attention_decode_stacked(
    p: dict,
    x: Array,
    k_all: Array,
    v_all: Array,
    layer: Array,
    pos: Array,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[Array] = None,
    softcap: Optional[float] = None,
    rope: Optional[Tuple[Array, Array]] = None,
) -> Tuple[Array, Array, Array]:
    """Decode step against a stacked cache [L, B, S, Hkv, D].

    The new token's K/V is written *directly* into the stacked carry
    (a [1,B,1,Hkv,D] dynamic-update-slice — the roofline HLO walk showed
    that slicing a layer out and writing the whole [B,S,Hkv,D] slice
    back makes XLA materialize full-cache copies per step); the
    attention read then slices the updated layer.
    """
    B, _, d = x.shape
    dtype = x.dtype
    q = (x @ p["wq"].astype(dtype)).reshape(B, 1, n_heads, head_dim)
    k = (x @ p["wk"].astype(dtype)).reshape(B, 1, n_kv_heads, head_dim)
    v = (x @ p["wv"].astype(dtype)).reshape(B, 1, n_kv_heads, head_dim)
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = apply_rope(q, positions, rope_theta, rope)
    k = apply_rope(k, positions, rope_theta, rope)
    zero = jnp.zeros((), jnp.int32)
    k_all = lax.dynamic_update_slice(
        k_all, k[None], (layer, zero, pos, zero, zero)
    )
    v_all = lax.dynamic_update_slice(
        v_all, v[None], (layer, zero, pos, zero, zero)
    )
    kc = lax.dynamic_index_in_dim(k_all, layer, 0, keepdims=False)
    vc = lax.dynamic_index_in_dim(v_all, layer, 0, keepdims=False)
    out = decode_attention(q, kc, vc, pos + 1, window=window, softcap=softcap)
    out = out.reshape(B, 1, n_heads * head_dim) @ p["wo"].astype(dtype)
    return out, k_all, v_all
