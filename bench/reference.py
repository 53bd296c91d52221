"""The plain reference of a served configuration, and its control.

A decoder-only transformer written from the configuration file alone,
in float32 `jax.numpy` at the highest matmul precision, with no cache,
paging, kernel or batching trick: for each sequence, every position
attends causally to all earlier ones. It imports nothing of the
program under test and takes nothing the program made.

Weights are made from the run's seed, layer by layer on the device,
as the configuration states them: every matrix drawn from a normal
distribution with standard deviation fan_in ** -0.5 and stored in
bfloat16 (the served dtype); norm scales zero (applied as 1 + scale).
The keys follow one tree, the one the served engine's weights are
drawn from:

  key = PRNGKey(seed); embed, layers, (unused), lm_head = split(key, 4)
  layer i: split(split(layers, L)[i]) -> attention key, mlp key
  attention key -> split 4: wq, wk, wv, wo
  mlp key -> split 3: w_gate, w_in, w_out

The comparison reads, at each position where the engine served a
token, how far that token's reference logit lies below the reference's
best logit there (`logit gap`, 0 when the served token is the
reference's argmax). Its control is the same model with every weight
matrix rounded to float8 e4m3 (absmax scaled per output channel), the
step below the served bfloat16: at each position the control's own
first choice is read against the float32 reference in the same way.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8 e4m3fn


class Dims:
    """The sizes the reference needs, read from a configuration file."""

    def __init__(self, cfg: dict):
        if cfg.get("partial_rotary_factor", 1.0) != 1.0:
            raise ValueError("the reference rotates the whole head")
        if cfg.get("norm") != "rmsnorm" or cfg.get("mlp") != "swiglu":
            raise ValueError("the reference computes rmsnorm and swiglu")
        self.L = int(cfg["num_hidden_layers"])
        self.d = int(cfg["hidden_size"])
        self.H = int(cfg["num_attention_heads"])
        self.Hkv = int(cfg["num_key_value_heads"])
        self.D = int(cfg["head_dim"])
        self.ff = int(cfg["intermediate_size"])
        self.V = int(cfg["vocab_size"])
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])

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
    k1, k2, k3 = jax.random.split(km, 3)
    d, HD, KD = dm.d, dm.H * dm.D, dm.Hkv * dm.D
    return {
        "wq": _normal(kq, (d, HD), d), "wk": _normal(kk, (d, KD), d),
        "wv": _normal(kv, (d, KD), d), "wo": _normal(ko, (HD, d), HD),
        "w_gate": _normal(k1, (d, dm.ff), d), "w_in": _normal(k2, (d, dm.ff), d),
        "w_out": _normal(k3, (dm.ff, d), dm.ff),
    }


def _f8(w):
    """Round a weight matrix to float8 e4m3, absmax scaled per output
    channel (its last axis), and back to float32."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    D = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos[:, None].astype(jnp.float32) * freqs  # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(dm: Dims, w, x):
    """One block for a batch of sequences x [B, S, d], causal."""
    B, S, _ = x.shape
    pos = jnp.arange(S)
    h = _rms(x, dm.eps)
    q = (h @ w["wq"]).reshape(B, S, dm.H, dm.D)
    k = (h @ w["wk"]).reshape(B, S, dm.Hkv, dm.D)
    v = (h @ w["wv"]).reshape(B, S, dm.Hkv, dm.D)
    q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
    group = dm.H // dm.Hkv
    k = jnp.repeat(k, group, axis=2)  # query head h reads kv head h // group
    v = jnp.repeat(v, group, axis=2)
    causal = pos[:, None] >= pos[None, :]

    def attend(qkv):  # one sequence at a time: [H, S, S] scores
        q1, k1, v1 = qkv
        s = jnp.einsum("qhd,khd->hqk", q1, k1) / math.sqrt(dm.D)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v1)

    o = jax.lax.map(attend, (q, k, v)).reshape(B, S, dm.H * dm.D)
    x = x + o @ w["wo"]
    h = _rms(x, dm.eps)
    a = jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_in"])
    return x + a @ w["w_out"]


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_step(dm: Dims, key, xs, control: bool):
    """Both streams through one layer, its weights made once."""
    w = _layer_weights(dm, key)
    with jax.default_matmul_precision("highest"):
        out = [_layer(dm, w, xs[0])]
        if control:
            out.append(_layer(dm, jax.tree.map(_f8, w), xs[1]))
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
        xs = _layer_step(dm, layer_keys[li], xs, control)
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
