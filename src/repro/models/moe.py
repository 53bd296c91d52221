"""Mixture-of-Experts layer with capacity-based dispatch (EP-shardable).

GShard/Switch-style: top-k routing, per-expert capacity buffers, one-hot
position assignment via cumulative sums, scatter dispatch / gather
combine.  The expert dimension of the buffers and weights is sharded on
the 'model' mesh axis (expert parallelism); the token->expert scatter
then lowers to an all-to-all under SPMD partitioning.

Dispatch locality (`n_blocks`): positions-in-expert computed with one
global cumsum over tokens serialize the token dimension — under SPMD
the compiler must all-gather the (T x E) running counts per layer,
which the dry-run roofline showed dominating the collective term
(~8.6 GB/layer at 32k prefill).  With `n_blocks` > 1 the cumsum runs
within token blocks aligned to the data shards (GShard's per-device
expert capacity): no cross-shard dependency, identical drop semantics
per block.  n_blocks=1 reproduces the global-capacity baseline.

Aux losses: load-balancing (Switch) + router z-loss.

Serving runs `apply_moe_routed` instead: drop-free by construction,
its work bound by the routed (token, expert) pairs rather than by
E x capacity.  Both paths take a held share of the experts (the ones a
chip keeps under expert parallelism): the router scores all of them,
and the layer adds the part of the result its own experts give.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.sharding import MeshAxes, act_spec, constrain

Array = jax.Array


def init_moe(
    key: Array,
    d: int,
    ff: int,
    n_experts: int,
    expert_start: int = 0,
    experts_held: int = 0,
) -> dict:
    """Router over all `n_experts`; weights of the held experts
    [expert_start, expert_start + experts_held) (0 = all).  Expert e's
    three matrices come from `fold_in(expert key, e)`, so any share is
    drawn alone and the shares of all chips make up the whole layer."""
    held = experts_held or n_experts
    kr, ke = jax.random.split(key)
    s_in = d ** -0.5
    s_out = ff ** -0.5

    def expert(e):
        kg, ki, ko = jax.random.split(jax.random.fold_in(ke, e), 3)
        return (
            jax.random.normal(kg, (d, ff), jnp.float32) * s_in,
            jax.random.normal(ki, (d, ff), jnp.float32) * s_in,
            jax.random.normal(ko, (ff, d), jnp.float32) * s_out,
        )

    w_gate, w_in, w_out = jax.vmap(expert)(
        jnp.arange(expert_start, expert_start + held)
    )
    return {
        "router": jax.random.normal(kr, (d, n_experts), jnp.float32) * s_in,
        "w_gate": w_gate,
        "w_in": w_in,
        "w_out": w_out,
    }


def route(p: dict, x: Array, top_k: int) -> Tuple[Array, Array]:
    """Softmax over every router output in float32, the top_k, their
    probabilities renormalised over those k.  x: [T, d] -> (gates
    [T, K] f32, experts [T, K] int32)."""
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.clip(
        gate_vals.sum(-1, keepdims=True), 1e-9, None
    )
    return gate_vals, expert_idx


# At most this many tokens, a call computes every held expert for every
# token and weights the results by the gates (0 where a token did not
# route to the expert): each expert's matmul is then bound by reading
# its weights (a v5e does ~240 bf16 FLOPs in the time it reads one
# byte), so the extra rows cost no time, and the plain dots read the
# layer's weights where they lie in the scanned parameters.  Above it,
# the routed pairs are grouped by expert (`lax.ragged_dot`), whose work
# follows the pairs.  On one v5e the grouped matmul at 32 decode tokens
# read its weights at ~190 GB/s, and XLA copied each layer's expert
# weights out of the stacked parameters to feed it.
DENSE_MAX_TOKENS = 64


def apply_moe_routed(
    p: dict,
    x: Array,
    *,
    top_k: int,
    expert_start: int = 0,
    dtype=jnp.bfloat16,
    live: Optional[Array] = None,
) -> Tuple[Array, dict]:
    """Drop-free expert MLP of the held experts, for serving.

    x: [B, S, d] -> (y [B, S, d], stats).  Routing is over every
    router output (`route`); the layer's result is the gate-weighted
    sum of the held experts' outputs for the (token, expert) pairs
    routed to them.  `live` (bool [B]) leaves the rows of idle batch
    lanes out of those pairs.  Up to DENSE_MAX_TOKENS tokens every held
    expert runs on every token (a zero gate drops the rest); above it
    only the routed pairs are computed: sorted by expert, then one
    grouped matmul (`lax.ragged_dot`) per SwiGLU matrix over the held
    experts' weights, pairs of experts held elsewhere sorting last,
    outside every group (ragged_dot gives them zero rows).  No pair is
    ever dropped, and no buffer is sized by a per-expert capacity.
    `stats`: int32 `expert_reads` (held experts with at least one
    routed pair) and `expert_tokens` (the routed pairs of held
    experts)."""
    B, S, d = x.shape
    T = B * S
    held = p["w_gate"].shape[0]
    xf = x.reshape(T, d)
    with jax.named_scope("moe_route"):
        gate_vals, expert_idx = route(p, xf, top_k)
        local = expert_idx.reshape(-1) - expert_start  # [T*K]
        mine = (local >= 0) & (local < held)
        if live is not None:
            mine = mine & jnp.repeat(live, S * top_k)
        group = jnp.where(mine, local, held)  # elsewhere -> past the groups
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    stats = {
        "expert_reads": (sizes > 0).sum(dtype=jnp.int32),
        "expert_tokens": mine.sum(dtype=jnp.int32),
    }
    if T <= DENSE_MAX_TOKENS:
        with jax.named_scope("moe_experts"):
            # gates [T, held]: a token's weight for each held expert
            w = jax.nn.one_hot(group, held, dtype=jnp.float32)
            w = (w * gate_vals.reshape(-1, 1)).reshape(T, top_k, held).sum(1)
            xe = jnp.broadcast_to(xf.astype(dtype), (held, T, d))
            g = jnp.einsum("etd,edf->etf", xe, p["w_gate"].astype(dtype))
            h = jnp.einsum("etd,edf->etf", xe, p["w_in"].astype(dtype))
            act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * h
            out = jnp.einsum("etf,efd->etd", act, p["w_out"].astype(dtype))
            y = jnp.einsum("etd,te->td", out.astype(jnp.float32), w)
        return y.reshape(B, S, d).astype(x.dtype), stats
    with jax.named_scope("moe_route"):
        order = jnp.argsort(group, stable=True)
        rows = xf.astype(dtype)[order // top_k]  # [T*K, d], by expert
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(rows, p["w_gate"].astype(dtype), sizes)
        h = jax.lax.ragged_dot(rows, p["w_in"].astype(dtype), sizes)
        act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * h
        out = jax.lax.ragged_dot(act, p["w_out"].astype(dtype), sizes)
        # back to (token, slot) order, weighted by the gates
        back = jnp.zeros_like(order).at[order].set(jnp.arange(order.size))
        w = jnp.where(mine, gate_vals.reshape(-1), 0.0)[:, None]
        y = (out[back].astype(jnp.float32) * w).reshape(T, top_k, d).sum(1)
    return y.reshape(B, S, d).astype(x.dtype), stats


def apply_moe(
    p: dict,
    x: Array,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    dtype=jnp.bfloat16,
    n_blocks: int = 1,
    axes: Optional[MeshAxes] = None,
    dispatch: str = "scatter",
    group_size: int = 2048,
    expert_start: int = 0,
) -> Tuple[Array, Array]:
    """x: [B, S, d] -> (y: [B, S, d], aux_loss: scalar).

    With a held share (fewer expert weights than router outputs) the
    capacity buffers still span every expert, and only the held
    experts' slots are computed: the others give zero.

    dispatch="einsum" selects the GShard-style one-hot-matmul dispatch:
    the roofline HLO walk showed XLA lowering the cross-shard dispatch
    *scatter* as full-buffer f32 all-reduces (~1.7 GB x 4 per MoE layer
    at train_4k scale); the einsum formulation replaces them with MXU
    matmuls whose collective footprint is just the [G,E,C,d] buffer
    reshard — trading ~2x small matmul flops for the dominant
    collective term (docs/experiments.md §Perf, llama4/phi3.5 cells).
    """
    if dispatch == "einsum":
        return _apply_moe_einsum(
            p, x, top_k=top_k, capacity_factor=capacity_factor,
            dtype=dtype, axes=axes, group_size=group_size,
            expert_start=expert_start,
        )
    B, S, d = x.shape
    E = p["router"].shape[1]
    T = B * S
    if T % n_blocks != 0:
        n_blocks = 1
    Tb = T // n_blocks
    xf = x.reshape(T, d)

    router_logits = xf.astype(jnp.float32) @ p["router"]  # [T, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # [T, K]
    gate_vals = gate_vals / jnp.clip(
        gate_vals.sum(-1, keepdims=True), 1e-9, None
    )

    # Load-balancing loss (Switch eq. 4) + router z-loss.
    me = probs.mean(axis=0)
    ce = jnp.zeros(E).at[expert_idx[:, 0]].add(1.0) / T
    aux = E * jnp.sum(me * ce)
    aux = aux + 1e-3 * jnp.square(jax.nn.logsumexp(router_logits, -1)).mean()

    cap_b = max(int(capacity_factor * Tb * top_k / E), 1)
    capacity = cap_b * n_blocks  # per-expert total slots
    # Position of each (token, slot) within its expert: computed per
    # token block (block-local cumsum, no cross-shard dependency), then
    # mapped to the expert's global slot range block*cap_b + pos.
    # n_blocks=1 is exactly the global formulation.
    e_blk = expert_idx.reshape(n_blocks, Tb, top_k).transpose(0, 2, 1)
    flat = e_blk.reshape(n_blocks, top_k * Tb)  # [NB, K*Tb]
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)  # [NB, K*Tb, E]
    pos_flat = (jnp.cumsum(onehot, axis=1) - 1) * onehot
    pos_b = pos_flat.sum(-1).reshape(n_blocks, top_k, Tb)  # [NB, K, Tb]
    keep_b = pos_b < cap_b
    blk = jnp.arange(n_blocks, dtype=jnp.int32)[:, None, None]
    slot_b = jnp.where(keep_b, pos_b + blk * cap_b, capacity)
    # back to the proven slot-major [K, T] scatter layout
    keep = keep_b.transpose(1, 0, 2).reshape(top_k, T)
    slot = slot_b.transpose(1, 0, 2).reshape(top_k, T)
    e_kt = e_blk.transpose(1, 0, 2).reshape(top_k, T)

    # Dispatch: scatter tokens into [E, capacity(+1 overflow), d].
    buf = jnp.zeros((E, capacity + 1, d), dtype=dtype)
    if axes is not None:
        buf = constrain(buf, axes, act_spec(axes, "tp", None, None))
    xe = jnp.broadcast_to(xf.astype(dtype), (top_k, T, d))
    buf = buf.at[e_kt, slot].set(xe)
    buf = buf[:, :capacity]  # drop overflow slot

    # Expert FFN (SwiGLU) — E dim shardable on 'model' (EP).
    held = p["w_gate"].shape[0]
    if held < E:
        buf = buf[expert_start: expert_start + held]
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dtype))
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_in"].astype(dtype))
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * h
    out_e = jnp.einsum("ecf,efd->ecd", act, p["w_out"].astype(dtype))
    out_e = jnp.pad(
        out_e, ((expert_start, E - held - expert_start), (0, 1), (0, 0))
    )

    # Combine: gather each (token, slot) result, weight by gate.
    gathered = out_e[e_kt, slot]  # [K, T, d]
    w = (gate_vals.transpose(1, 0) * keep)[..., None].astype(jnp.float32)
    y = (gathered.astype(jnp.float32) * w).sum(0)
    return y.reshape(B, S, d).astype(x.dtype), aux


def _apply_moe_einsum(
    p: dict,
    x: Array,
    *,
    top_k: int,
    capacity_factor: float,
    dtype,
    axes: Optional[MeshAxes],
    group_size: int,
    expert_start: int = 0,
) -> Tuple[Array, Array]:
    """GShard-style dispatch: one-hot (token -> expert,slot) tensors
    contracted with matmuls; no scatter/gather anywhere.

    Tokens are split into G groups of Sg (groups align with the data
    shards); capacity is per (group, expert).  group_size == T
    reproduces the global-capacity semantics of the scatter path
    exactly (same slot-major priority)."""
    B, S, d = x.shape
    E = p["router"].shape[1]
    T = B * S
    G = max(T // group_size, 1)
    while T % G:
        G -= 1
    Sg = T // G
    xg = x.reshape(G, Sg, d)

    router_logits = xg.astype(jnp.float32) @ p["router"]  # [G, Sg, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # [G, Sg, K]
    gate_vals = gate_vals / jnp.clip(
        gate_vals.sum(-1, keepdims=True), 1e-9, None
    )

    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros(E).at[expert_idx[..., 0].reshape(-1)].add(1.0) / T
    aux = E * jnp.sum(me * ce)
    aux = aux + 1e-3 * jnp.square(jax.nn.logsumexp(router_logits, -1)).mean()

    C = max(int(capacity_factor * Sg * top_k / E), 1)
    # slot-major positions within (group, expert)
    e_sm = expert_idx.transpose(0, 2, 1)  # [G, K, Sg]
    oh = jax.nn.one_hot(e_sm, E, dtype=jnp.int32)  # [G, K, Sg, E]
    ohf = oh.reshape(G, top_k * Sg, E)
    pos = ((jnp.cumsum(ohf, axis=1) - 1) * ohf).sum(-1)
    pos = pos.reshape(G, top_k, Sg)
    keep = pos < C
    # one_hot of an out-of-range index is all-zeros: dropped tokens
    # vanish from both dispatch and combine automatically
    pos_oh = jax.nn.one_hot(
        jnp.where(keep, pos, C), C, dtype=dtype
    )  # [G, K, Sg, C]

    disp = jnp.einsum(
        "gkse,gksc->gsec", oh.astype(dtype), pos_oh
    )  # [G, Sg, E, C]
    buf = jnp.einsum("gsec,gsd->gecd", disp, xg.astype(dtype))
    if axes is not None:
        buf = constrain(buf, axes, act_spec(axes, "dp", "tp", None, None))

    held = p["w_gate"].shape[0]
    if held < E:
        buf = buf[:, expert_start: expert_start + held]
    g = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(dtype))
    h = jnp.einsum("gecd,edf->gecf", buf, p["w_in"].astype(dtype))
    act = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * h
    out_e = jnp.einsum("gecf,efd->gecd", act, p["w_out"].astype(dtype))
    if held < E:
        out_e = jnp.pad(out_e, (
            (0, 0), (expert_start, E - held - expert_start), (0, 0), (0, 0)))

    gates_sm = gate_vals.transpose(0, 2, 1)  # [G, K, Sg] slot-major
    comb = jnp.einsum(
        "gkse,gksc,gks->gsec",
        oh.astype(jnp.float32),
        pos_oh.astype(jnp.float32),
        gates_sm * keep,
    ).astype(dtype)
    y = jnp.einsum("gsec,gecd->gsd", comb, out_e)
    return y.reshape(B, S, d).astype(x.dtype), aux
