"""Scan-over-layers decoder backbone for all ten assigned architectures.

One backbone, four family bodies:

  dense/moe/vlm/audio — GQA attention + SwiGLU-or-MoE, with a per-layer
      sliding-window array threaded through one scan body (this is how
      gemma2's local/global alternation lives inside a single scanned
      body: the window size is a traced scalar in `chunked_attention`).
  hybrid (zamba2)     — groups of `attn_every` Mamba2 mixers followed by
      one *shared* attention block (shared parameters, per-group KV
      cache sites).
  ssm (rwkv6)         — RWKV6 time-mix/channel-mix blocks.

Layer parameters are stacked on a leading axis and scanned (keeps the
HLO one-layer-sized for the 512-device dry-run compiles); training wraps
the body in jax.checkpoint (full per-layer remat).

Entry points (all pure):
  init_params(cfg, key)
  forward(cfg, params, x, ...)               -> [B, S, d] hidden states
  train_loss(cfg, params, batch, ...)        -> scalar loss
  prefill(cfg, params, batch, max_len, ...)  -> (last-token logits, cache)
  decode_step(cfg, params, cache, tokens, .) -> (logits, cache)
  init_cache(cfg, batch, max_len)            -> cache pytree
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import (
    apply_rope,
    attention_block,
    attention_decode_block,
    attention_decode_stacked,
    chunked_attention,
    init_attention,
)
from repro.models.layers import (
    apply_swiglu,
    cross_entropy,
    embed,
    init_embedding,
    init_rms_norm,
    init_swiglu,
    logits as lm_logits,
    rms_norm,
    rope_freqs,
    yarn_freqs,
)
from repro.models.sharding import MeshAxes, act_spec, constrain

Array = jax.Array


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_dense_layer(cfg: ArchConfig, key: Array) -> dict:
    ka, km = jax.random.split(key)
    p = {
        "ln1": init_rms_norm(cfg.d_model),
        "ln2": init_rms_norm(cfg.d_model),
        "attn": init_attention(
            ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        ),
    }
    if cfg.post_norm:
        p["ln1_post"] = init_rms_norm(cfg.d_model)
        p["ln2_post"] = init_rms_norm(cfg.d_model)
    if cfg.n_experts:
        p["moe"] = moe_lib.init_moe(
            km, cfg.d_model, cfg.expert_d_ff, cfg.n_experts,
            cfg.expert_start, cfg.experts_held,
        )
    else:
        p["mlp"] = init_swiglu(km, cfg.d_model, cfg.d_ff)
    return p


def _init_mamba_layer(cfg: ArchConfig, key: Array) -> dict:
    return {
        "ln": init_rms_norm(cfg.d_model),
        "mamba": ssm_lib.init_mamba2(
            key, cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim
        ),
    }


def init_params(cfg: ArchConfig, key: Array) -> dict:
    kemb, klay, kattn, khead = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": init_embedding(kemb, cfg.vocab_size, cfg.d_model),
        "final_norm": init_rms_norm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_embedding(khead, cfg.vocab_size, cfg.d_model)
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        keys = jax.random.split(klay, cfg.n_layers)
        params["layers"] = jax.vmap(
            functools.partial(_init_dense_layer, cfg)
        )(keys)
    elif cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        keys = jax.random.split(klay, G * cfg.attn_every).reshape(
            G, cfg.attn_every, 2
        )
        params["groups"] = jax.vmap(
            jax.vmap(functools.partial(_init_mamba_layer, cfg))
        )(keys)
        params["shared_attn"] = {
            "ln": init_rms_norm(cfg.d_model),
            "attn": init_attention(
                kattn, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
            ),
        }
    elif cfg.family == "ssm":
        keys = jax.random.split(klay, cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: rwkv_lib.init_rwkv6(
                k, cfg.d_model, cfg.d_ff, cfg.rwkv_head_dim
            )
        )(keys)
    else:
        raise ValueError(cfg.family)
    return params


def window_array(cfg: ArchConfig) -> Array:
    """Per-layer sliding window sizes (0 = global), cycled pattern."""
    if not cfg.window_pattern:
        return jnp.zeros((cfg.n_layers,), jnp.int32)
    pat = jnp.asarray(cfg.window_pattern, jnp.int32)
    reps = -(-cfg.n_layers // len(cfg.window_pattern))
    return jnp.tile(pat, reps)[: cfg.n_layers]


def rope_arrays(cfg: ArchConfig) -> Optional[Tuple[Array, Array]]:
    """Per-layer rotary tables, scanned with the layers: inverse
    frequencies [L, D/2] and the cos/sin scale [L] of each layer's kind
    (`rope_pattern`, cycled).  None where the model has one kind, plain
    rotary at `rope_theta`, which `apply_rope` computes itself."""
    if not cfg.rope_pattern:
        return None
    plain = rope_freqs(cfg.head_dim, cfg.rope_theta)
    tables = {"plain": (plain, 1.0)}
    if cfg.yarn is not None:
        tables["yarn"] = yarn_freqs(cfg.head_dim, cfg.rope_theta, cfg.yarn)
    kinds = [cfg.rope_pattern[i % len(cfg.rope_pattern)]
             for i in range(cfg.n_layers)]
    inv = jnp.stack([tables[k][0] for k in kinds])
    scale = jnp.asarray([tables[k][1] for k in kinds], jnp.float32)
    return inv, scale


def serve_moe(
    cfg: ArchConfig, p: dict, h: Array, live: Optional[Array] = None
) -> Tuple[Array, dict]:
    """The expert MLP of the serving paths: routed over the held
    experts, drop-free (`moe.apply_moe_routed`)."""
    return moe_lib.apply_moe_routed(
        p, h, top_k=cfg.top_k, expert_start=cfg.expert_start,
        dtype=h.dtype, live=live,
    )


# ---------------------------------------------------------------------------
# Forward (training / prefill trunk)
# ---------------------------------------------------------------------------


def _attn_kwargs(cfg: ArchConfig) -> dict:
    return dict(
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta,
        softcap=cfg.attn_softcap or None,
    )


def _dense_body(cfg: ArchConfig, axes, carry, xs):
    x, aux = carry
    lp, window, rope = xs
    x = constrain(x, axes, act_spec(axes, "dp", None, None))
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    h = attention_block(
        lp["attn"], h, window=window, rope=rope, **_attn_kwargs(cfg)
    )
    if cfg.post_norm:
        h = rms_norm(h, lp["ln1_post"], cfg.norm_eps)
    x = x + h
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        h, a = moe_lib.apply_moe(
            lp["moe"],
            h,
            top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
            dtype=h.dtype,
            n_blocks=cfg.dispatch_blocks,
            axes=axes,
            dispatch=cfg.dispatch_mode,
            group_size=cfg.dispatch_group,
            expert_start=cfg.expert_start,
        )
        aux = aux + a
    else:
        h = apply_swiglu(lp["mlp"], h, dtype=h.dtype)
    if cfg.post_norm:
        h = rms_norm(h, lp["ln2_post"], cfg.norm_eps)
    return (x + h, aux)


def _hybrid_body(cfg: ArchConfig, axes, shared, carry, xs):
    x, aux = carry
    gp = xs  # leaves [attn_every, ...]
    x = constrain(x, axes, act_spec(axes, "dp", None, None))
    for i in range(cfg.attn_every):
        lp = jax.tree.map(lambda a: a[i], gp)
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        h = ssm_lib.apply_mamba2(
            lp["mamba"],
            h,
            d_inner=cfg.d_inner,
            d_state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim,
        )
        x = x + h
    h = rms_norm(x, shared["ln"], cfg.norm_eps)
    h = attention_block(shared["attn"], h, window=None, **_attn_kwargs(cfg))
    return (x + h, aux)


def _ssm_body(cfg: ArchConfig, axes, carry, xs):
    x, aux = carry
    lp = xs
    x = constrain(x, axes, act_spec(axes, "dp", None, None))
    x, _ = rwkv_lib.apply_rwkv6(lp, x, head_dim=cfg.rwkv_head_dim)
    return (x, aux)


def forward(
    cfg: ArchConfig,
    params: dict,
    x: Array,
    *,
    axes: Optional[MeshAxes] = None,
    remat: bool = False,
) -> Tuple[Array, Array]:
    """x: [B, S, d] embedded inputs -> (hidden [B, S, d], aux loss)."""
    aux0 = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        body = functools.partial(_dense_body, cfg, axes)
        xs = (params["layers"], window_array(cfg), rope_arrays(cfg))
    elif cfg.family == "hybrid":
        body = functools.partial(_hybrid_body, cfg, axes, params["shared_attn"])
        xs = params["groups"]
    elif cfg.family == "ssm":
        body = functools.partial(_ssm_body, cfg, axes)
        xs = params["layers"]
    else:
        raise ValueError(cfg.family)

    def scan_body(carry, xs_):
        return body(carry, xs_), None

    if remat:
        scan_body = jax.checkpoint(scan_body)
    (x, aux), _ = lax.scan(scan_body, (x, aux0), xs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def _embed_inputs(cfg: ArchConfig, params: dict, batch: dict, dtype) -> Array:
    if cfg.frontend != "none" and "embeds" in batch:
        # Modality frontend is a stub: precomputed frame/patch embeddings.
        return batch["embeds"].astype(dtype)
    return embed(params["embed"], batch["tokens"], dtype, scale=cfg.embed_scale)


def train_loss(
    cfg: ArchConfig,
    params: dict,
    batch: dict,
    *,
    axes: Optional[MeshAxes] = None,
    dtype=jnp.bfloat16,
    remat: bool = True,
) -> Array:
    x = _embed_inputs(cfg, params, batch, dtype)
    x = constrain(x, axes, act_spec(axes, "dp", None, None))
    h, aux = forward(cfg, params, x, axes=axes, remat=remat)
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    lg = lm_logits(h, table, cfg.final_softcap or None)
    lg = constrain(lg, axes, act_spec(axes, "dp", None, "tp"))
    return cross_entropy(lg, batch["labels"]) + 0.01 * aux


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------


def init_cache(
    cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> dict:
    """Cache pytree for decode. 'pos' is the current context length."""
    kv = lambda sites: jnp.zeros(
        (sites, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype
    )
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm", "audio"):
        cache["k"] = kv(cfg.n_layers)
        cache["v"] = kv(cfg.n_layers)
    elif cfg.family == "hybrid":
        G = cfg.n_layers // cfg.attn_every
        cache["k"] = kv(G)
        cache["v"] = kv(G)
        cache["mamba"] = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a, (G, cfg.attn_every) + a.shape
            ),
            ssm_lib.init_mamba2_state(
                batch, cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim, dtype=dtype
            ),
        )
    elif cfg.family == "ssm":
        cache["rwkv"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
            rwkv_lib.init_rwkv6_state(batch, cfg.d_model, cfg.rwkv_head_dim),
        )
    return cache


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_step(
    cfg: ArchConfig,
    params: dict,
    cache: dict,
    tokens: Array,
    *,
    axes: Optional[MeshAxes] = None,
    dtype=jnp.bfloat16,
) -> Tuple[Array, dict]:
    """One decode step. tokens: [B] int32 -> (logits [B, V], cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]
    x = embed(params["embed"], tokens[:, None], dtype, scale=cfg.embed_scale)
    x = constrain(x, axes, act_spec(axes, "dp", None, None))

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        windows = window_array(cfg)

        # The cache is threaded as a scan CARRY with a tiny in-place
        # dynamic-update-slice per layer — xs/ys threading (or slice +
        # full-slice write-back) makes XLA materialize full-cache copies
        # per step (verified via the HLO roofline walk).
        def body(carry, xs):
            x, k_all, v_all = carry
            lp, window, li, rope = xs
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            h, k_all, v_all = attention_decode_stacked(
                lp["attn"], h, k_all, v_all, li, pos,
                window=window, rope=rope, **_attn_kwargs(cfg),
            )
            if cfg.post_norm:
                h = rms_norm(h, lp["ln1_post"], cfg.norm_eps)
            x = x + h
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.n_experts and axes is None:
                h, _ = serve_moe(cfg, lp["moe"], h)
            elif cfg.n_experts:
                # Sharded decode keeps the capacity dispatch, whose
                # expert axis shards on 'model' with an all-to-all (the
                # routed path has no exchange between chips), at
                # drop-free capacity (= n_experts x the mean load):
                # capacity dropping is a training trade-off, not
                # acceptable at serving time.
                # Dispatch is always the scatter path at decode: with
                # T = batch tokens the one-hot matmuls of the einsum
                # mode cost more than the tiny scatter (measured:
                # docs/experiments.md §Perf generalization table).
                h, _ = moe_lib.apply_moe(
                    lp["moe"],
                    h,
                    top_k=cfg.top_k,
                    capacity_factor=(
                        cfg.serve_capacity_factor or float(cfg.n_experts)
                    ),
                    dtype=h.dtype,
                    n_blocks=cfg.dispatch_blocks,
                    axes=axes,
                    dispatch="scatter",
                    expert_start=cfg.expert_start,
                )
            else:
                h = apply_swiglu(lp["mlp"], h, dtype=h.dtype)
            if cfg.post_norm:
                h = rms_norm(h, lp["ln2_post"], cfg.norm_eps)
            return (x + h, k_all, v_all), None

        (x, k_new, v_new), _ = lax.scan(
            body,
            (x, cache["k"], cache["v"]),
            (params["layers"], windows, jnp.arange(cfg.n_layers),
             rope_arrays(cfg)),
        )
        cache = dict(cache, k=k_new, v=v_new)

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]
        G = cfg.n_layers // cfg.attn_every

        def body(carry, xs):
            x, k_all, v_all, m_all = carry
            gp, gi = xs
            mstate = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, gi, 0, keepdims=False),
                m_all,
            )
            new_m = []
            for i in range(cfg.attn_every):
                lp = jax.tree.map(lambda a: a[i], gp)
                st = jax.tree.map(lambda a: a[i], mstate)
                h = rms_norm(x, lp["ln"], cfg.norm_eps)
                h, st = ssm_lib.apply_mamba2_decode(
                    lp["mamba"],
                    h,
                    st,
                    d_inner=cfg.d_inner,
                    d_state=cfg.ssm_state,
                    head_dim=cfg.ssm_head_dim,
                )
                x = x + h
                new_m.append(st)
            mstate = jax.tree.map(lambda *a: jnp.stack(a), *new_m)
            h = rms_norm(x, shared["ln"], cfg.norm_eps)
            h, k_all, v_all = attention_decode_stacked(
                shared["attn"], h, k_all, v_all, gi, pos,
                window=None, **_attn_kwargs(cfg),
            )
            m_all = jax.tree.map(
                lambda a, s: lax.dynamic_update_index_in_dim(a, s, gi, 0),
                m_all,
                mstate,
            )
            return (x + h, k_all, v_all, m_all), None

        (x, k_new, v_new, m_new), _ = lax.scan(
            body,
            (x, cache["k"], cache["v"], cache["mamba"]),
            (params["groups"], jnp.arange(G)),
        )
        cache = dict(cache, k=k_new, v=v_new, mamba=m_new)

    elif cfg.family == "ssm":

        def body(carry, xs):
            x, st_all = carry
            lp, li = xs
            st = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
                st_all,
            )
            x, st = rwkv_lib.apply_rwkv6(
                lp, x, head_dim=cfg.rwkv_head_dim, state=st
            )
            st_all = jax.tree.map(
                lambda a, s: lax.dynamic_update_index_in_dim(a, s, li, 0),
                st_all,
                st,
            )
            return (x, st_all), None

        (x, r_new), _ = lax.scan(
            body,
            (x, cache["rwkv"]),
            (params["layers"], jnp.arange(cfg.n_layers)),
        )
        cache = dict(cache, rwkv=r_new)
    else:
        raise ValueError(cfg.family)

    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    lg = lm_logits(h[:, 0], table, cfg.final_softcap or None)
    lg = constrain(lg, axes, act_spec(axes, "dp", "tp"))
    cache = dict(cache, pos=pos + 1)
    return lg, cache


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(
    cfg: ArchConfig,
    params: dict,
    batch: dict,
    max_len: int,
    *,
    axes: Optional[MeshAxes] = None,
    dtype=jnp.bfloat16,
) -> Tuple[Array, dict]:
    """Process the prompt; returns (last-token logits [B, V], cache).

    The trunk is the same scanned forward; per-layer KV (or SSM/RWKV
    state) is collected as scan outputs.  KV caches are written into
    max_len-sized buffers (the serving engine's NBBS pages back the
    paged variant; this dense path is what the dry-run lowers).
    """
    if cfg.frontend != "none" and "embeds" in batch:
        x = batch["embeds"].astype(dtype)
        B, S = x.shape[:2]
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed(params["embed"], tokens, dtype, scale=cfg.embed_scale)
    x = constrain(x, axes, act_spec(axes, "dp", None, None))
    positions = jnp.arange(S)[None, :]
    cache = init_cache(cfg, B, max_len, dtype)

    def pad_kv(k):  # [B, S, Hkv, D] -> [B, max_len, Hkv, D]
        return jnp.pad(k, ((0, 0), (0, max_len - S), (0, 0), (0, 0)))

    if cfg.family in ("dense", "moe", "vlm", "audio"):
        windows = window_array(cfg)

        def body(x, xs):
            lp, window, rope = xs
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            # attention with KV capture for the cache
            Bx, Sx, d = h.shape
            dt = h.dtype
            q = (h @ lp["attn"]["wq"].astype(dt)).reshape(
                Bx, Sx, cfg.n_heads, cfg.head_dim
            )
            k = (h @ lp["attn"]["wk"].astype(dt)).reshape(
                Bx, Sx, cfg.n_kv_heads, cfg.head_dim
            )
            v = (h @ lp["attn"]["wv"].astype(dt)).reshape(
                Bx, Sx, cfg.n_kv_heads, cfg.head_dim
            )
            q = apply_rope(q, positions, cfg.rope_theta, rope)
            k = apply_rope(k, positions, cfg.rope_theta, rope)
            o = chunked_attention(
                q, k, v, causal=True, window=window,
                softcap=cfg.attn_softcap or None,
            )
            h = o.reshape(Bx, Sx, -1) @ lp["attn"]["wo"].astype(dt)
            if cfg.post_norm:
                h = rms_norm(h, lp["ln1_post"], cfg.norm_eps)
            x = x + h
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            if cfg.n_experts and axes is None:
                h, _ = serve_moe(cfg, lp["moe"], h)
            elif cfg.n_experts:
                # sharded prefill: drop-free capacity (see decode_step)
                h, _ = moe_lib.apply_moe(
                    lp["moe"], h, top_k=cfg.top_k,
                    capacity_factor=(
                        cfg.serve_capacity_factor or float(cfg.n_experts)
                    ),
                    dtype=h.dtype, n_blocks=cfg.dispatch_blocks, axes=axes,
                    dispatch=cfg.dispatch_mode,
                    group_size=cfg.dispatch_group,
                    expert_start=cfg.expert_start,
                )
            else:
                h = apply_swiglu(lp["mlp"], h, dtype=h.dtype)
            if cfg.post_norm:
                h = rms_norm(h, lp["ln2_post"], cfg.norm_eps)
            return x + h, (pad_kv(k), pad_kv(v))

        x, (ks, vs) = lax.scan(
            body, x, (params["layers"], windows, rope_arrays(cfg))
        )
        cache = dict(cache, k=ks, v=vs)

    elif cfg.family == "hybrid":
        shared = params["shared_attn"]

        def body(x, gp):
            # mamba sub-layers: chunked forward, exact final state captured
            new_m = []
            for i in range(cfg.attn_every):
                lp = jax.tree.map(lambda a: a[i], gp)
                h = rms_norm(x, lp["ln"], cfg.norm_eps)
                h, st = ssm_lib.apply_mamba2(
                    lp["mamba"], h, d_inner=cfg.d_inner,
                    d_state=cfg.ssm_state, head_dim=cfg.ssm_head_dim,
                    return_state=True,
                )
                x = x + h
                new_m.append(st)
            mstate = jax.tree.map(lambda *a: jnp.stack(a), *new_m)
            h = rms_norm(x, shared["ln"], cfg.norm_eps)
            Bx, Sx, d = h.shape
            dt = h.dtype
            q = (h @ shared["attn"]["wq"].astype(dt)).reshape(
                Bx, Sx, cfg.n_heads, cfg.head_dim
            )
            k = (h @ shared["attn"]["wk"].astype(dt)).reshape(
                Bx, Sx, cfg.n_kv_heads, cfg.head_dim
            )
            v = (h @ shared["attn"]["wv"].astype(dt)).reshape(
                Bx, Sx, cfg.n_kv_heads, cfg.head_dim
            )
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            o = chunked_attention(
                q, k, v, causal=True, softcap=cfg.attn_softcap or None
            )
            h = o.reshape(Bx, Sx, -1) @ shared["attn"]["wo"].astype(dt)
            return x + h, (pad_kv(k), pad_kv(v), mstate)

        x, (ks, vs, ms) = lax.scan(body, x, params["groups"])
        cache = dict(cache, k=ks, v=vs, mamba=ms)

    elif cfg.family == "ssm":

        def body(x, lp):
            x, st = rwkv_lib.apply_rwkv6(lp, x, head_dim=cfg.rwkv_head_dim)
            return x, st

        x, states = lax.scan(body, x, params["layers"])
        cache = dict(cache, rwkv=states)
    else:
        raise ValueError(cfg.family)

    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    lg = lm_logits(h[:, -1], table, cfg.final_softcap or None)
    cache = dict(cache, pos=jnp.asarray(S, jnp.int32))
    return lg, cache
