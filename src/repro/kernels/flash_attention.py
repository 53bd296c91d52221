"""Pallas TPU flash attention (forward) — training/prefill hot spot.

Canonical TPU tiling: grid (batch, q_heads, q_blocks, kv_blocks) with the
kv dimension innermost (sequential revisiting of the output block), fp32
online-softmax state (running max / denominator / accumulator) in VMEM
scratch.  Block sizes default to 128x128 — MXU-aligned (128 multiples)
and (8,128) VPU-tile aligned.

Supported attention variants (exactly those required by the assigned
architectures):
  * GQA              — kv head = q head // group (llama/phi/gemma/zamba)
  * causal masking   — decoder LMs
  * sliding window   — gemma2 local layers
  * logit softcap    — gemma2 (softcap * tanh(logits / softcap))

Fully-masked kv blocks (beyond the causal diagonal or outside the
window) are skipped with @pl.when — the TPU analogue of flash
attention's block skipping on GPUs.

Backward: `ops.flash_attention` wraps this forward in a jax.custom_vjp
whose backward recomputes attention with the pure-jnp reference oracle
(`ref.mha_reference`) — identical math, so gradients are exact while
the forward enjoys the fused kernel.  (A fused Pallas backward is a
further optimization documented in docs/experiments.md §Perf.)
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


def _flash_fwd_kernel(
    # static
    scale: float,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    bq: int,
    bk: int,
    kv_len: int,
    # refs
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    row0 = iq * bq
    col0 = ik * bk

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level skip: beyond causal diagonal / outside sliding window.
    live = jnp.bool_(True)
    if causal:
        live &= col0 <= row0 + bq - 1
    if window is not None:
        live &= col0 + bk - 1 >= row0 - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < kv_len
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # [bq]
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        # Rows where everything so far is masked: keep state neutral.
        p = jnp.where((m_cur == NEG_INF)[:, None], 0.0, p)
        alpha = jnp.where(m_cur == NEG_INF, 1.0, alpha)
        l_cur = l_scr[...] * alpha + p.sum(axis=1)
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_cur
        l_scr[...] = l_cur
        acc_scr[...] = acc

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        norm = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / norm[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "softcap",
        "scale",
        "block_q",
        "block_k",
        "interpret",
    ),
)
def flash_attention_fwd(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> Array:
    """q: [B, Hq, S, D]; k, v: [B, Hkv, S, D]; returns [B, Hq, S, D]."""
    B, Hq, S, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, bq, Sk, bk)
    nq, nk = S // bq, Sk // bk
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_fwd_kernel, scale, causal, window, softcap, bq, bk, Sk
    )
    scratch = [
        pltpu.VMEM((bq,), jnp.float32),
        pltpu.VMEM((bq,), jnp.float32),
        pltpu.VMEM((bq, D), jnp.float32),
    ]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec(
                (1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
