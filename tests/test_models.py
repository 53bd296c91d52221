"""Per-arch smoke tests (reduced configs) + mixer equivalences +
serving-path consistency.  One forward/train step on CPU per assigned
architecture, asserting output shapes and finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models import decode_step, init_params, prefill, train_loss
from repro.models.rwkv import apply_rwkv6, init_rwkv6, init_rwkv6_state
from repro.models.ssm import (
    apply_mamba2,
    apply_mamba2_decode,
    init_mamba2,
    init_mamba2_state,
)

KEY = jax.random.PRNGKey(0)
B, S = 2, 16


def make_batch(cfg, key, seq=S):
    batch = {"labels": jax.random.randint(key, (B, seq), 0, cfg.vocab_size)}
    if cfg.frontend != "none":
        batch["embeds"] = jax.random.normal(
            key, (B, seq, cfg.d_model), jnp.float32
        )
    else:
        batch["tokens"] = jax.random.randint(key, (B, seq), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_arch_smoke_train_step(name):
    cfg = get_config(name).reduced()
    params = init_params(cfg, KEY)
    batch = make_batch(cfg, KEY)
    loss, grads = jax.value_and_grad(
        lambda p: train_loss(cfg, p, batch, dtype=jnp.float32, remat=True)
    )(params)
    assert jnp.isfinite(loss), name
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    )
    assert jnp.isfinite(gnorm) and gnorm > 0, name


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_arch_smoke_serve_step(name):
    cfg = get_config(name).reduced()
    params = init_params(cfg, KEY)
    batch = make_batch(cfg, KEY)
    lg, cache = prefill(cfg, params, batch, max_len=S + 4, dtype=jnp.float32)
    assert lg.shape == (B, cfg.vocab_size)
    assert jnp.isfinite(lg).all(), name
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    lg2, cache2 = decode_step(cfg, params, cache, tok, dtype=jnp.float32)
    assert lg2.shape == (B, cfg.vocab_size)
    assert jnp.isfinite(lg2).all(), name
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize(
    "name",
    ["stablelm-3b", "gemma2-27b", "zamba2-1.2b", "rwkv6-7b",
     "phi3.5-moe-42b-a6.6b", "llama4-scout-17b-a16e", "mellum2-12b"],
)
def test_serve_consistency(name):
    """prefill(S+1) last logits == prefill(S) + decode(token S)."""
    cfg = get_config(name).reduced()
    params = init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    lg_full, _ = prefill(
        cfg, params, {"tokens": toks}, max_len=S + 4, dtype=jnp.float32
    )
    _, cache = prefill(
        cfg, params, {"tokens": toks[:, :S]}, max_len=S + 4, dtype=jnp.float32
    )
    lg_dec, _ = decode_step(cfg, params, cache, toks[:, S], dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(lg_full), np.asarray(lg_dec), atol=1e-4
    )


def test_gemma2_window_pattern():
    from repro.models.transformer import window_array

    cfg = get_config("gemma2-27b")
    w = np.asarray(window_array(cfg))
    assert len(w) == 46
    assert (w[::2] == 4096).all() and (w[1::2] == 0).all()


def test_mamba2_chunk_invariance_and_decode():
    d, d_inner, d_state, hd = 32, 64, 16, 16
    p = init_mamba2(KEY, d, d_inner, d_state, hd)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (B, 24, d), jnp.float32)
    kw = dict(d_inner=d_inner, d_state=d_state, head_dim=hd)
    y8 = apply_mamba2(p, x, chunk=8, **kw)
    y24 = apply_mamba2(p, x, chunk=24, **kw)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y24), atol=1e-4)
    st = init_mamba2_state(B, d_inner, d_state, hd, dtype=jnp.float32)
    ys = []
    for t in range(24):
        yt, st = apply_mamba2_decode(p, x[:, t : t + 1], st, **kw)
        ys.append(yt)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(ys, 1)), np.asarray(y8), atol=1e-4
    )


def test_mamba2_prefill_state_continuation():
    d, d_inner, d_state, hd = 32, 64, 16, 16
    p = init_mamba2(KEY, d, d_inner, d_state, hd)
    kw = dict(d_inner=d_inner, d_state=d_state, head_dim=hd)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (B, 20, d), jnp.float32)
    y_full = apply_mamba2(p, x, chunk=8, **kw)
    _, st = apply_mamba2(p, x[:, :12], chunk=8, return_state=True, **kw)
    ys = []
    for t in range(12, 20):
        yt, st = apply_mamba2_decode(p, x[:, t : t + 1], st, **kw)
        ys.append(yt)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(ys, 1)),
        np.asarray(y_full[:, 12:]),
        atol=1e-4,
    )


def test_rwkv6_streaming_equivalence():
    d, hd = 32, 16
    p = init_rwkv6(KEY, d, 4 * d, hd)
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (B, 24, d), jnp.float32)
    y1, _ = apply_rwkv6(p, x, head_dim=hd)
    ha, sta = apply_rwkv6(p, x[:, :12], head_dim=hd)
    hb, _ = apply_rwkv6(p, x[:, 12:], head_dim=hd, state=sta)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([ha, hb], 1)), np.asarray(y1), atol=1e-4
    )
    st = init_rwkv6_state(B, d, hd)
    ys = []
    for t in range(24):
        yt, st = apply_rwkv6(p, x[:, t : t + 1], head_dim=hd, state=st)
        ys.append(yt)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(ys, 1)), np.asarray(y1), atol=1e-4
    )


def test_moe_aux_loss_and_balance():
    from repro.models.moe import apply_moe, init_moe

    p = init_moe(KEY, 32, 64, 4)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 64, 32), jnp.float32)
    y, aux = apply_moe(p, x, top_k=2, dtype=jnp.float32)
    assert y.shape == x.shape
    assert jnp.isfinite(y).all() and jnp.isfinite(aux)
    assert float(aux) > 0


def test_param_counts_sane():
    # analytic counts should be within 2x of actual reduced-model counts
    for name in ARCH_NAMES:
        cfg = get_config(name).reduced()
        params = init_params(cfg, KEY)
        actual = sum(x.size for x in jax.tree.leaves(params))
        est = cfg.param_count()
        assert 0.4 < est / actual < 2.5, (name, est, actual)


def test_moe_dispatch_modes_equivalent():
    """GShard einsum dispatch == scatter dispatch (same capacity
    semantics) — the §Perf collective fix must not change the math."""
    from repro.models.moe import apply_moe, init_moe

    p = init_moe(KEY, 16, 32, 4)
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (2, 32, 16), jnp.float32)
    for k in (1, 2):
        y1, a1 = apply_moe(p, x, top_k=k, capacity_factor=1.25,
                           dtype=jnp.float32)
        y2, a2 = apply_moe(p, x, top_k=k, capacity_factor=1.25,
                           dtype=jnp.float32, dispatch="einsum",
                           group_size=64)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
        np.testing.assert_allclose(float(a1), float(a2), atol=1e-5)
    # block-local scatter == group-local einsum at matching geometry
    y3, _ = apply_moe(p, x, top_k=2, capacity_factor=2.0, dtype=jnp.float32,
                      n_blocks=4)
    y4, _ = apply_moe(p, x, top_k=2, capacity_factor=2.0, dtype=jnp.float32,
                      dispatch="einsum", group_size=16)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y4), atol=1e-5)


def test_moe_einsum_arch_end_to_end():
    """A MoE arch trains and serves with dispatch_mode='einsum'."""
    import dataclasses

    cfg = dataclasses.replace(
        get_config("phi3.5-moe-42b-a6.6b").reduced(),
        dispatch_mode="einsum", dispatch_group=16,
    )
    params = init_params(cfg, KEY)
    batch = make_batch(cfg, KEY)
    loss = train_loss(cfg, params, batch, dtype=jnp.float32, remat=False)
    assert jnp.isfinite(loss)
    lg, cache = prefill(cfg, params, batch, max_len=S + 2, dtype=jnp.float32)
    lg2, _ = decode_step(cfg, params, cache,
                         jnp.argmax(lg, -1).astype(jnp.int32),
                         dtype=jnp.float32)
    assert jnp.isfinite(lg2).all()
