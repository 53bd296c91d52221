"""The plain reference of Mellum2-12B-A2.5B (`bench/configs/mellum2-12b.json`),
and its control.

A decoder written from the configuration file alone, in float32
`jax.numpy` at the highest matmul precision, with no cache, paging,
kernel, sorting or batching trick. It imports nothing of the program
under test and takes nothing the program made. Per layer, by the
file's `layer_types`:

  attention  GQA (`num_attention_heads` over `num_key_value_heads`),
             causal; a `sliding_attention` layer sees only the newest
             `sliding_window` positions, the token itself included
             (position j is seen from i when i - window < j <= i)
  rotary     by layer kind from `rope_parameters`: plain at the kind's
             `rope_theta`, or YaRN (Hugging Face's
             `_compute_yarn_parameters`: inverse frequencies blended
             between theta's and theta's over `factor` by a linear ramp
             between the correction dims of `beta_fast` and `beta_slow`
             at `original_max_position_embeddings`, cos and sin scaled
             by `attention_factor`)
  experts    every MLP sparse: softmax over all `source_values.num_experts`
             router outputs, the `num_experts_per_tok` largest,
             renormalised over them (`norm_topk_prob`); the layer adds
             the SwiGLU outputs (width `moe_intermediate_size`) of the
             `num_experts` experts held here, from `experts_held_from`
             on, each weighted by its gate where the token routed to it
             and by 0 elsewhere. Each held expert is computed densely
             over every token

Weights are made from the run's seed, one layer at a time on the
device: every matrix drawn from a normal distribution with standard
deviation fan_in ** -0.5 and stored in bfloat16 (the served dtype),
then computed in float32; norm scales zero (applied as 1 + scale). The
keys follow the tree the served engine's weights are drawn from:

  key = PRNGKey(seed); embed, layers, (unused), lm_head = split(key, 4)
  layer i: split(split(layers, L)[i]) -> attention key, expert key
  attention key -> split 4: wq, wk, wv, wo
  expert key -> split 2: router, experts
  expert e (its number among all the router's) ->
      split(fold_in(experts, e), 3): w_gate, w_in, w_out

The comparison reads, at each position where the engine served a
token, how far that token's reference logit lies below the reference's
best logit there (`logit gap`, 0 when the served token is the
reference's argmax). Its control is the same model with every weight
matrix (the router's too) rounded to float8 e4m3 (absmax scaled per
output channel), the step below the served bfloat16: at each position
the control's own first choice is read against the float32 reference.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8 e4m3fn
KINDS = ("full_attention", "sliding_attention")


def _inv_freq(rope: dict, D: int):
    """Inverse frequencies [D/2] and cos/sin scale of one layer kind."""
    theta = float(rope["rope_theta"])
    pos_freqs = theta ** (np.arange(0, D, 2, dtype=np.float32) / np.float32(D))
    if rope.get("rope_type", "default") == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    L0 = float(rope["original_max_position_embeddings"])

    def corr(rot):
        return D * math.log(L0 / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(rope.get("beta_slow", 1))), D - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(D // 2, dtype=np.float32) - low) / (high - low),
                   0, 1).astype(np.float32)
    extra = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1.0 - extra) + (1.0 / pos_freqs) * extra
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv.astype(np.float32), float(scale)


class Dims:
    """The sizes the reference needs, read from a configuration file."""

    def __init__(self, cfg: dict):
        if cfg.get("hidden_act") != "silu" or cfg.get("attention_bias"):
            raise ValueError("the reference computes silu and no bias")
        if not cfg.get("norm_topk_prob"):
            raise ValueError("the reference renormalises the top-k")
        self.L = int(cfg["num_hidden_layers"])
        if set(cfg["mlp_layer_types"]) != {"sparse"}:
            raise ValueError("the reference computes sparse MLPs only")
        kinds = tuple(cfg["layer_types"])
        if len(kinds) != self.L or not set(kinds) <= set(KINDS):
            raise ValueError("layer_types must name a kind for every layer")
        self.kinds = kinds
        self.window = int(cfg["sliding_window"])
        self.d = int(cfg["hidden_size"])
        self.H = int(cfg["num_attention_heads"])
        self.Hkv = int(cfg["num_key_value_heads"])
        self.D = int(cfg["head_dim"])
        self.V = int(cfg["vocab_size"])
        self.eps = float(cfg["rms_norm_eps"])
        self.E = int(cfg.get("source_values", {}).get(
            "num_experts", cfg["num_experts"]))
        self.held = int(cfg["num_experts"])
        self.first = int(cfg.get("experts_held_from", 0))
        self.K = int(cfg["num_experts_per_tok"])
        self.ff = int(cfg["moe_intermediate_size"])
        self.rope = []
        for kind in sorted(set(kinds)):
            inv, scale = _inv_freq(cfg["rope_parameters"][kind], self.D)
            self.rope.append((kind, tuple(inv.tolist()), scale))
        self.rope = tuple(self.rope)

    def rope_of(self, kind: str):
        for k, inv, scale in self.rope:
            if k == kind:
                return jnp.asarray(inv, jnp.float32), scale
        raise KeyError(kind)

    def __hash__(self):
        return hash(tuple(sorted(vars(self).items())))

    def __eq__(self, other):
        return vars(self) == vars(other)


def _normal(key, shape, fan_in):
    w = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    return w.astype(jnp.bfloat16).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_weights(dm: Dims, key) -> Dict[str, jax.Array]:
    ka, km = jax.random.split(key)
    kq, kk, kv, ko = jax.random.split(ka, 4)
    kr, ke = jax.random.split(km)
    d, HD, KD, ff = dm.d, dm.H * dm.D, dm.Hkv * dm.D, dm.ff
    gate, inp, out = [], [], []
    for e in range(dm.first, dm.first + dm.held):
        k1, k2, k3 = jax.random.split(jax.random.fold_in(ke, e), 3)
        gate.append(_normal(k1, (d, ff), d))
        inp.append(_normal(k2, (d, ff), d))
        out.append(_normal(k3, (ff, d), ff))
    return {
        "wq": _normal(kq, (d, HD), d), "wk": _normal(kk, (d, KD), d),
        "wv": _normal(kv, (d, KD), d), "wo": _normal(ko, (HD, d), HD),
        "router": _normal(kr, (d, dm.E), d),
        "w_gate": jnp.stack(gate), "w_in": jnp.stack(inp),
        "w_out": jnp.stack(out),
    }


def _f8(w):
    """Round a weight matrix to float8 e4m3, absmax scaled per output
    channel (its last axis), and back to float32."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, inv, scale):
    ang = pos[:, None].astype(jnp.float32) * inv  # [S, D/2]
    cos = (jnp.cos(ang) * scale)[:, None]
    sin = (jnp.sin(ang) * scale)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _experts(dm: Dims, w, h):
    """The held experts' part of the sparse MLP for rows h [N, d]."""
    probs = jax.nn.softmax(h @ w["router"], axis=-1)  # [N, E]
    top, idx = jax.lax.top_k(probs, dm.K)
    top = top / top.sum(-1, keepdims=True)

    def one(y, e):
        gate = jnp.where(idx == dm.first + e, top, 0.0).sum(-1)  # [N]
        a = jax.nn.silu(h @ w["w_gate"][e]) * (h @ w["w_in"][e])
        return y + gate[:, None] * (a @ w["w_out"][e]), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(dm.held))
    return y


def _layer(dm: Dims, kind: str, w, x):
    """One block for a batch of sequences x [B, S, d]."""
    B, S, _ = x.shape
    pos = jnp.arange(S)
    inv, scale = dm.rope_of(kind)
    h = _rms(x, dm.eps)
    q = (h @ w["wq"]).reshape(B, S, dm.H, dm.D)
    k = (h @ w["wk"]).reshape(B, S, dm.Hkv, dm.D)
    v = (h @ w["wv"]).reshape(B, S, dm.Hkv, dm.D)
    q, k = _rope(q, pos, inv, scale), _rope(k, pos, inv, scale)
    group = dm.H // dm.Hkv
    k = jnp.repeat(k, group, axis=2)  # query head h reads kv head h // group
    v = jnp.repeat(v, group, axis=2)
    seen = pos[:, None] >= pos[None, :]
    if kind == "sliding_attention":
        seen &= pos[:, None] - pos[None, :] < dm.window

    def attend(qkv):  # one sequence at a time: [H, S, S] scores
        q1, k1, v1 = qkv
        s = jnp.einsum("qhd,khd->hqk", q1, k1) / math.sqrt(dm.D)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v1)

    o = jax.lax.map(attend, (q, k, v)).reshape(B, S, dm.H * dm.D)
    x = x + o @ w["wo"]
    h = _rms(x, dm.eps).reshape(B * S, dm.d)
    return x + _experts(dm, w, h).reshape(B, S, dm.d)


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def _layer_step(dm: Dims, kind: str, key, xs, control: bool):
    """Both streams through one layer, its weights made once."""
    w = _layer_weights(dm, key)
    with jax.default_matmul_precision("highest"):
        out = [_layer(dm, kind, w, xs[0])]
        if control:
            out.append(_layer(dm, kind, jax.tree.map(_f8, w), xs[1]))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _table(dm: Dims, which: int, key):
    kemb, _, _, khead = jax.random.split(key, 4)
    return _normal((kemb, khead)[which], (dm.V, dm.d), dm.d)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _read_rows(dm: Dims, head, rows, served, control: bool):
    """Logit gaps of a block of final hidden rows.

    rows: [1 or 2, N, d] (reference, control) before the final norm;
    served: [N] token ids. Returns the gap of each served token, and
    with `control` the gap of the control's first choice."""
    with jax.default_matmul_precision("highest"):
        ref = _rms(rows[0], dm.eps) @ head.T
        best = ref.max(axis=-1)
        gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if not control:
            return gap, gap
        ctl = _rms(rows[1], dm.eps) @ _f8(head.T)
        pick = jnp.argmax(ctl, axis=-1)
        return gap, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def logit_gaps(cfg: dict, seed: int, seqs: Sequence[dict], shape: tuple,
               control: bool = False, row_block: int = 256) -> dict:
    """Run the reference over served sequences and read the gaps.

    Each of `seqs` has `prompt` (token ids) and `served` (the tokens
    the engine generated, in order). They run as one batch of the
    fixed `shape` (sequences, tokens), padded at the end, so that every
    run reuses one compiled program; causal attention never reads the
    padding from an earlier position. Returns per-sequence and widest
    gaps of the served tokens, and with `control` those of the
    control's own first choices."""
    dm = Dims(cfg)
    key = jax.random.PRNGKey(seed)
    inputs = [np.concatenate([s["prompt"], s["served"][:-1]]).astype(np.int32)
              for s in seqs]
    K, S = shape
    if len(inputs) > K or max(len(t) for t in inputs) > S:
        raise ValueError(f"{len(inputs)} sequences do not fit shape {shape}")
    toks = np.zeros((K, S), np.int32)
    for i, t in enumerate(inputs):
        toks[i, : len(t)] = t
    emb = _table(dm, 0, key)
    x = emb[jnp.asarray(toks)]
    del emb
    xs = (x, x) if control else (x,)
    layer_keys = jax.random.split(jax.random.split(key, 4)[1], dm.L)
    for li in range(dm.L):
        xs = _layer_step(dm, dm.kinds[li], layer_keys[li], xs, control)
    # rows where a served token was predicted: prompt end onwards
    idx, served, owner = [], [], []
    for i, s in enumerate(seqs):
        n, p = len(s["served"]), len(s["prompt"])
        idx += [(i, p - 1 + j) for j in range(n)]
        served += list(s["served"])
        owner += [i] * n
    n_rows = len(idx)
    pad = -n_rows % row_block
    idx = np.asarray(idx + [idx[-1]] * pad)
    served = np.asarray(served + [served[-1]] * pad, np.int32)
    head = _table(dm, 1, key)
    stacked = jnp.stack([a[idx[:, 0], idx[:, 1]] for a in xs])
    del xs
    gaps, cgaps = [], []
    for b in range(0, len(idx), row_block):
        g, cg = _read_rows(dm, head, stacked[:, b: b + row_block],
                           jnp.asarray(served[b: b + row_block]), control)
        gaps.append(np.asarray(g))
        cgaps.append(np.asarray(cg))
    gaps = np.concatenate(gaps)[:n_rows]
    cgaps = np.concatenate(cgaps)[:n_rows]
    owner = np.asarray(owner)
    out = {
        "tokens": int(len(gaps)),
        "gap": float(gaps.max()),
        "gap_per_seq": [float(gaps[owner == i].max()) for i in range(len(seqs))],
        "argmax_share": float((gaps == 0).mean()),
    }
    if control:
        out["control_gap"] = float(cgaps.max())
        out["control_argmax_share"] = float((cgaps == 0).mean())
    return out
