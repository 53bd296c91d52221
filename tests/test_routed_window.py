"""Routed experts over a held share, the paged kernel's window mask and
YaRN rotary: the mechanisms Mellum2-12B brings to the serving path, each
against an oracle of its own at a small size on the CPU."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import YaRN
from repro.kernels.paged_attention import paged_attention as paged_pallas
from repro.kernels.ref import paged_attention_reference
from repro.models import init_params
from repro.models.layers import rope_freqs, yarn_freqs
from repro.models.moe import (
    DENSE_MAX_TOKENS, apply_moe, apply_moe_routed, init_moe, route,
)
from repro.serve.engine import Request
from repro.serve.jit_engine import JitServeEngine

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# (b) the kernel's window mask
# ---------------------------------------------------------------------------

PAGE, MAXP, P, HQ, HKV, D = 4, 10, 48, 4, 2, 16


def _pool_case(ctx):
    """One layer of pool, three lanes of distinct pages with contexts
    `ctx` (positions attended, the new token's included) and an idle
    lane; tables cover each context exactly."""
    key = jax.random.fold_in(KEY, 7)
    kp = jax.random.normal(jax.random.fold_in(key, 0), (1, P, PAGE, HKV, D))
    vp = jax.random.normal(jax.random.fold_in(key, 1), (1, P, PAGE, HKV, D))
    q = jax.random.normal(jax.random.fold_in(key, 2), (len(ctx) + 1, HQ, D))
    rng = np.random.default_rng(5)
    bt = np.full((len(ctx) + 1, MAXP), -1, np.int32)
    pages = rng.permutation(P)
    for b, c in enumerate(ctx):
        n = -(-c // PAGE)
        bt[b, :n], pages = pages[:n], pages[n:]
    cl = np.asarray(list(ctx) + [0], np.int32)
    return q, kp, vp, bt, cl


def _window_oracle(q, kp, vp, bt, cl, window):
    """Attention of each live lane over the newest `window` positions of
    its context (all of it at window 0), gathered densely in numpy."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp[0], vp[0]))
    out = np.zeros(q.shape)
    group = HQ // HKV
    for b, c in enumerate(cl):
        if c == 0:
            continue
        k = kp[bt[b, : -(-c // PAGE)]].reshape(-1, HKV, D)[:c]
        v = vp[bt[b, : -(-c // PAGE)]].reshape(-1, HKV, D)[:c]
        lo = max(c - window, 0) if window else 0
        for h in range(HQ):
            s = k[lo:, h // group] @ q[b, h] / math.sqrt(D)
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[lo:, h // group]
    return out


@pytest.mark.parametrize("window", [0, 7, 8, 9, 11, 12, 13])
def test_kernel_window_mask_matches_a_dense_oracle(window):
    """Contexts at window - 1, window and window + 1, and at page
    multiples +- 1 (window 8 on 4-token pages: the window's start then
    falls inside a page, on its first slot and on its last), through
    the kernel in interpret mode and the jnp reference, against the
    dense oracle; window 0 is global. Tolerance 2e-5: float32 on both
    sides, the kernel's online softmax sums in another order."""
    ctx = sorted({max(c, 1) for c in (window - 1, window, window + 1,
                                      4 * PAGE - 1, 4 * PAGE + 1, 37)})
    q, kp, vp, bt, cl = _pool_case(ctx)
    want = _window_oracle(q, kp, vp, bt, cl, window)
    live = cl > 0
    kern = paged_pallas(q, kp, vp, 0, jnp.asarray(bt), jnp.asarray(cl),
                        window, interpret=True)
    ref = paged_attention_reference(q, kp[0], vp[0], jnp.asarray(bt),
                                    jnp.asarray(cl), window)
    for out in (kern, ref):
        np.testing.assert_allclose(np.asarray(out)[live], want[live],
                                   atol=2e-5, rtol=2e-5)
    assert not np.asarray(kern)[~live].any()


def test_kernel_never_reads_pages_before_the_window():
    """Pages that lie wholly before a lane's window are skipped: filled
    with NaN they leave the kernel's output unchanged (a page it
    computed would carry the NaN into p @ v)."""
    window, ctx = 8, [37, 30, 6]
    q, kp, vp, bt, cl = _pool_case(ctx)
    clean = paged_pallas(q, kp, vp, 0, jnp.asarray(bt), jnp.asarray(cl),
                         window, interpret=True)
    dead = [bt[b, j] for b, c in enumerate(ctx)
            for j in range((c - window) // PAGE) if c > window]
    assert dead
    kp = kp.at[0, np.asarray(dead)].set(jnp.nan)
    vp = vp.at[0, np.asarray(dead)].set(jnp.nan)
    poisoned = paged_pallas(q, kp, vp, 0, jnp.asarray(bt), jnp.asarray(cl),
                            window, interpret=True)
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(clean))


# ---------------------------------------------------------------------------
# (c) disjoint expert shares add up to the uncut layer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S", [1, 6, 30])
def test_expert_shares_add_up_to_the_uncut_layer(S):
    """Four chips' shares of 16 experts, each drawn alone: their weights
    are the uncut layer's, and their partial outputs sum to the uncut
    output, through the routed path (every held expert on every token
    at 3 and 18 tokens, the grouped matmul at 90) and the capacity path
    alike. Tolerance 1e-5: float32, the shares add in another order."""
    assert (3 * S <= DENSE_MAX_TOKENS) == (S < 30)
    d, ff, E, K = 16, 24, 16, 4
    whole = init_moe(KEY, d, ff, E)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (3, S, d))
    y_whole, _ = apply_moe_routed(whole, x, top_k=K, dtype=jnp.float32)
    y_cap, _ = apply_moe(whole, x, top_k=K, capacity_factor=float(E),
                         dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_cap), np.asarray(y_whole),
                               atol=1e-5)
    parts, cap_parts = [], []
    for start in range(0, E, 4):
        share = init_moe(KEY, d, ff, E, start, 4)
        for name in ("w_gate", "w_in", "w_out"):
            np.testing.assert_array_equal(
                np.asarray(share[name]),
                np.asarray(whole[name][start: start + 4]))
        np.testing.assert_array_equal(np.asarray(share["router"]),
                                      np.asarray(whole["router"]))
        parts.append(apply_moe_routed(share, x, top_k=K, expert_start=start,
                                      dtype=jnp.float32)[0])
        cap_parts.append(apply_moe(share, x, top_k=K, expert_start=start,
                                   capacity_factor=float(E),
                                   dtype=jnp.float32)[0])
    for got in (sum(parts), sum(cap_parts)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(y_whole),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# (d) YaRN against a hand-computed table
# ---------------------------------------------------------------------------


def test_yarn_frequencies_and_scale_at_mellum_widths():
    """Mellum's full layers (head_dim 128, theta 500000, factor 16 over
    8192 positions, beta_fast 32, beta_slow 1): the correction dims are
    floor(18.08) = 18 and ceil(34.98) = 35, so frequencies 0-18 keep
    theta's own, 35-63 are theta's over 16, and those between blend
    linearly; cos and sin scale by 0.1 ln 16 + 1. Checked against that
    table in float64 to float32's precision."""
    cfg = get_config("mellum2-12b")
    inv, scale = yarn_freqs(cfg.head_dim, cfg.rope_theta, cfg.yarn)
    i = np.arange(64)
    base = 500000.0 ** (-2.0 * i / 128)
    ramp = np.clip((i - 18) / (35 - 18), 0.0, 1.0)
    want = base * (1 - ramp) + base / 16 * ramp
    np.testing.assert_allclose(np.asarray(inv), want, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(inv)[[0, 18, 35, 63]],
                               [1.0, base[18], base[35] / 16, base[63] / 16],
                               rtol=2e-6)
    assert scale == 1.2772588722239782 == 0.1 * math.log(16) + 1
    plain = rope_freqs(cfg.head_dim, cfg.rope_theta)
    np.testing.assert_array_equal(np.asarray(inv)[:19], np.asarray(plain)[:19])
    # an unset attention factor is YaRN's default, 0.1 ln(factor) + 1
    _, dflt = yarn_freqs(128, 500000.0, YaRN(factor=16.0,
                         original_max_position_embeddings=8192))
    assert dflt == pytest.approx(1.2772588722239782, rel=1e-15)


def test_rope_kind_follows_the_layer():
    from repro.models.transformer import rope_arrays, window_array

    cfg = get_config("mellum2-12b")
    inv, scale = (np.asarray(a) for a in rope_arrays(cfg))
    full = np.asarray(window_array(cfg)) == 0
    assert full.sum() == 7 and (np.flatnonzero(full) % 4 == 3).all()
    yarn, _ = yarn_freqs(cfg.head_dim, cfg.rope_theta, cfg.yarn)
    plain = rope_freqs(cfg.head_dim, cfg.rope_theta)
    np.testing.assert_array_equal(inv[full], np.broadcast_to(yarn, (7, 64)))
    np.testing.assert_array_equal(inv[~full], np.broadcast_to(plain, (21, 64)))
    assert (scale[full] == np.float32(1.2772588722239782)).all()
    assert (scale[~full] == 1.0).all()
    assert rope_arrays(get_config("minitron-4b")) is None


# ---------------------------------------------------------------------------
# (e) the expert counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S", [1, 5, 20])
def test_expert_counts_match_a_host_recount(S):
    """`expert_reads` and `expert_tokens` of one routed call equal a
    recount on the host from the routing itself, idle lanes left out,
    on both sides of DENSE_MAX_TOKENS (6, 30 and 120 tokens); the two
    ways of computing the experts give one result."""
    d, ff, E, K, start, held = 16, 8, 8, 3, 2, 3
    p = init_moe(KEY, d, ff, E, start, held)
    x = jax.random.normal(jax.random.fold_in(KEY, 9), (6, S, d))
    live = jnp.asarray([True, False, True, True, False, True])
    _, stats = apply_moe_routed(p, x, top_k=K, expert_start=start,
                                dtype=jnp.float32, live=live)
    _, idx = route(p, x.reshape(-1, d), K)
    idx = np.asarray(idx).reshape(6, S, K)[np.asarray(live)]
    mine = (idx >= start) & (idx < start + held)
    assert int(stats["expert_tokens"]) == int(mine.sum())
    assert int(stats["expert_reads"]) == len(set(idx[mine].tolist()))
    y, _ = apply_moe_routed(p, x, top_k=K, expert_start=start,
                            dtype=jnp.float32, live=live)
    dense = jax.vmap(lambda xt: apply_moe_routed(
        p, xt[None], top_k=K, expert_start=start, dtype=jnp.float32)[0][0])
    np.testing.assert_allclose(
        np.asarray(y)[np.asarray(live)],
        np.asarray(dense(x))[np.asarray(live)], atol=1e-5)
    assert not np.asarray(y)[~np.asarray(live)].any()


def test_engine_accumulates_expert_counts_and_journals_them():
    """The engine's totals hold the counters of every decode step, and
    each drain journals their running values (`experts` records, one a
    drain, never decreasing); after the last drain the record equals the
    totals. A model without experts counts nothing and journals no such
    record."""
    cfg = get_config("mellum2-12b").reduced()
    params = init_params(cfg, KEY)
    eng = JitServeEngine(cfg, params, num_pages=64, page_tokens=4,
                         max_batch=3, max_lane_pages=16, max_out=16,
                         dtype=jnp.float32, impl="reference")
    rng = np.random.default_rng(1)
    for i in range(4):
        eng.submit(Request(i, rng.integers(0, 256, 6 + 5 * i).astype(np.int32),
                           8 + i))
    eng.run_to_completion(chunk=4)
    eng._drain()
    tot = eng.stat_totals()
    recs = [s for s in eng.spans if s["phase"] == "experts"]
    assert len(recs) >= 3
    for a, b in zip(recs, recs[1:]):
        assert a["step"] <= b["step"]
        assert a["expert_reads"] <= b["expert_reads"]
        assert a["expert_tokens"] <= b["expert_tokens"]
    assert recs[-1]["step"] == tot["steps"]
    assert recs[-1]["expert_reads"] == tot["expert_reads"] > 0
    assert recs[-1]["expert_tokens"] == tot["expert_tokens"] > 0
    # each token decoded routes top_k pairs, at most all of them held
    tokens = sum(len(r.out_tokens) for r in eng.completed.values())
    assert tot["expert_tokens"] <= tokens * cfg.top_k * cfg.n_layers
    assert tot["expert_reads"] <= (tot["steps"] * cfg.n_layers
                                   * cfg.held_experts)

    dense = get_config("stablelm-3b").reduced()
    eng = JitServeEngine(dense, init_params(dense, KEY), num_pages=16,
                         page_tokens=4, max_batch=2, max_lane_pages=8,
                         max_out=8, dtype=jnp.float32, impl="reference")
    eng.submit(Request(0, np.arange(5, dtype=np.int32), 4))
    eng.run_to_completion(chunk=4)
    tot = eng.stat_totals()
    assert tot["expert_reads"] == tot["expert_tokens"] == 0
    assert not [s for s in eng.spans if s["phase"] == "experts"]
