"""Shared model layers: norms, RoPE, MLPs, embeddings.

Functional style throughout: `init_*` returns a param pytree (dict of
jnp arrays); `apply` functions are pure.  Params are created in float32
(master weights); compute casts to the config dtype (bf16 on TPU).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + scale) parameterization keeps init at identity.
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_rms_norm(d: int) -> Array:
    return jnp.zeros((d,), jnp.float32)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def yarn_freqs(head_dim: int, theta: float, yarn) -> Tuple[Array, float]:
    """YaRN's inverse frequencies and cos/sin scale, as Hugging Face's
    `_compute_yarn_parameters` defines them (`truncate` on): each
    frequency blends extrapolation (theta's own) and interpolation
    (theta's over `factor`) by a linear ramp between the correction
    dims of `beta_fast` and `beta_slow` at the original context.
    `yarn` is a `configs.base.YaRN`."""
    n = head_dim // 2
    L0 = yarn.original_max_position_embeddings

    def correction_dim(rotations: float) -> float:
        return (head_dim * math.log(L0 / (rotations * 2 * math.pi))) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # as HF: no zero-width ramp
    ramp = jnp.clip(
        (jnp.arange(n, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    pos_freqs = theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    )
    extrapolation = 1.0 - ramp  # weight of theta's own frequency
    inv = (1.0 / (yarn.factor * pos_freqs)) * (1.0 - extrapolation) + (
        1.0 / pos_freqs
    ) * extrapolation
    scale = yarn.attention_factor
    if scale is None:
        scale = 0.1 * math.log(yarn.factor) + 1.0 if yarn.factor > 1 else 1.0
    return inv, float(scale)


def apply_rope(
    x: Array,
    positions: Array,
    theta: float = 10000.0,
    rope: Optional[Tuple[Array, Array]] = None,
) -> Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S].

    `rope`, where given, is (inverse frequencies [D/2], cos/sin scale)
    of the layer (a YaRN layer's, or a plain one's in a model that
    mixes kinds) and replaces theta's."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if rope is None else rope[0]  # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    angles = angles[..., None, :]  # broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if rope is not None:
        sin, cos = sin * rope[1], cos * rope[1]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_swiglu(key: Array, d: int, ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d ** -0.5
    s_out = ff ** -0.5
    return {
        "w_gate": jax.random.normal(k1, (d, ff), jnp.float32) * s_in,
        "w_in": jax.random.normal(k2, (d, ff), jnp.float32) * s_in,
        "w_out": jax.random.normal(k3, (ff, d), jnp.float32) * s_out,
    }


def apply_swiglu(p: dict, x: Array, dtype=jnp.bfloat16) -> Array:
    g = x @ p["w_gate"].astype(dtype)
    h = x @ p["w_in"].astype(dtype)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * h
    return act @ p["w_out"].astype(dtype)


def init_gelu_mlp(key: Array, d: int, ff: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w_in": jax.random.normal(k1, (d, ff), jnp.float32) * d ** -0.5,
        "w_out": jax.random.normal(k2, (ff, d), jnp.float32) * ff ** -0.5,
    }


def apply_gelu_mlp(p: dict, x: Array, dtype=jnp.bfloat16) -> Array:
    h = x @ p["w_in"].astype(dtype)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(dtype)
    return h @ p["w_out"].astype(dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def init_embedding(key: Array, vocab: int, d: int) -> Array:
    return jax.random.normal(key, (vocab, d), jnp.float32) * (d ** -0.5)


def embed(table: Array, tokens: Array, dtype=jnp.bfloat16, scale: bool = False):
    x = table.astype(dtype)[tokens]
    if scale:
        x = x * jnp.asarray(table.shape[1] ** 0.5, dtype)
    return x


def logits(
    x: Array,
    table: Array,
    softcap: Optional[float] = None,
) -> Array:
    """LM head (tied or untied table [V, d]); returns fp32 logits."""
    out = jnp.einsum("...d,vd->...v", x.astype(jnp.float32), table.astype(jnp.float32))
    if softcap:
        out = softcap * jnp.tanh(out / softcap)
    return out


def cross_entropy(
    lg: Array, labels: Array, z_loss: float = 1e-4
) -> Array:
    """Mean token cross-entropy with an optional z-loss regularizer."""
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    loss = (lse - ll).mean()
    if z_loss:
        loss = loss + z_loss * jnp.square(lse).mean()
    return loss
