"""Pallas kernel validation: interpret-mode execution vs pure-jnp
oracles across shape/dtype sweeps (per-kernel allclose)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.concurrent import (
    BUNCH_PACKED,
    TreeConfig,
    UNPACKED,
    wavefront_alloc,
    wavefront_step,
)

_LAYOUTS = {"unpacked": UNPACKED, "packed": BUNCH_PACKED}
from repro.core.pool import PoolConfig
from repro.kernels import ops as ops_mod
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.nbbs_alloc import wavefront_alloc_pallas, wavefront_step_pallas
from repro.kernels.ops import (
    flash_attention,
    nbbs_pool_wavefront_step,
    nbbs_wavefront_alloc,
    nbbs_wavefront_step,
    paged_attention,
)
from repro.kernels.paged_attention import paged_attention as paged_pallas
from repro.kernels.ref import mha_reference, paged_attention_reference

KEY = jax.random.PRNGKey(0)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("S,D,Hq,Hkv", [
        (128, 32, 4, 4),    # MHA
        (256, 64, 8, 2),    # GQA
        (192, 16, 2, 1),    # MQA, non-128 seq
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, S, D, Hq, Hkv, dtype):
        B = 2
        q = rand(jax.random.fold_in(KEY, 1), (B, Hq, S, D), dtype)
        k = rand(jax.random.fold_in(KEY, 2), (B, Hkv, S, D), dtype)
        v = rand(jax.random.fold_in(KEY, 3), (B, Hkv, S, D), dtype)
        out = flash_attention_fwd(q, k, v, block_q=64, block_k=64)
        ref = mha_reference(q, k, v)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=tol, rtol=tol,
        )

    @pytest.mark.parametrize("variant", [
        dict(causal=False),
        dict(causal=True, window=64),
        dict(causal=True, softcap=30.0),
        dict(causal=True, window=96, softcap=50.0),
    ])
    def test_variants(self, variant):
        B, Hq, Hkv, S, D = 1, 4, 2, 256, 32
        q = rand(jax.random.fold_in(KEY, 4), (B, Hq, S, D), jnp.float32)
        k = rand(jax.random.fold_in(KEY, 5), (B, Hkv, S, D), jnp.float32)
        v = rand(jax.random.fold_in(KEY, 6), (B, Hkv, S, D), jnp.float32)
        out = flash_attention_fwd(q, k, v, block_q=64, block_k=64, **variant)
        ref = mha_reference(q, k, v, **variant)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    @pytest.mark.parametrize("bq,bk", [(32, 32), (64, 128), (128, 64)])
    def test_block_size_sweep(self, bq, bk):
        B, Hq, Hkv, S, D = 1, 2, 2, 256, 32
        q = rand(jax.random.fold_in(KEY, 7), (B, Hq, S, D), jnp.float32)
        k = rand(jax.random.fold_in(KEY, 8), (B, Hkv, S, D), jnp.float32)
        v = rand(jax.random.fold_in(KEY, 9), (B, Hkv, S, D), jnp.float32)
        out = flash_attention_fwd(q, k, v, block_q=bq, block_k=bk)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
        )

    def test_gradients_match_reference(self):
        B, Hq, Hkv, S, D = 1, 4, 2, 128, 32
        q = rand(jax.random.fold_in(KEY, 10), (B, Hq, S, D), jnp.float32)
        k = rand(jax.random.fold_in(KEY, 11), (B, Hkv, S, D), jnp.float32)
        v = rand(jax.random.fold_in(KEY, 12), (B, Hkv, S, D), jnp.float32)
        g1 = jax.grad(
            lambda q, k, v: flash_attention(q, k, v, impl="interpret").sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g2 = jax.grad(
            lambda q, k, v: flash_attention(q, k, v, impl="reference").sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _paged_case(seed, L, P, page, maxp, B, Hq, Hkv, D, dtype):
    """A stacked pool [L, P, page, Hkv, D] with distinct data per layer,
    -1-padded tables of distinct pages and contexts inside them; the
    last lane is idle (context 0, no pages), as the engine passes an
    inactive lane."""
    key = jax.random.fold_in(KEY, seed)
    kp = rand(jax.random.fold_in(key, 0), (L, P, page, Hkv, D), dtype)
    vp = rand(jax.random.fold_in(key, 1), (L, P, page, Hkv, D), dtype)
    q = rand(jax.random.fold_in(key, 2), (B, Hq, D), dtype)
    rng = np.random.default_rng(seed)
    bt = np.full((B, maxp), -1, np.int32)
    cl = np.zeros((B,), np.int32)
    for b in range(B - 1):
        n = int(rng.integers(1, maxp + 1))
        bt[b, :n] = rng.choice(P, size=n, replace=False)
        cl[b] = int(rng.integers(1, n * page + 1))
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(cl)


def _check_paged(out, q, kp, vp, layer, bt, cl, tol, kernel=True):
    """`out` is the attention over layer `layer` of the stacked pools:
    it matches the reference on that layer's pool in every live lane.
    The kernel's is zero in an idle lane; the reference's softmax over
    an empty context is undefined there."""
    ref = paged_attention_reference(q, kp[layer], vp[layer], bt, cl)
    out = np.asarray(out, np.float32)
    live = np.asarray(cl) > 0
    np.testing.assert_allclose(
        out[live], np.asarray(ref, np.float32)[live], atol=tol, rtol=tol,
    )
    assert not (kernel and out[~live].any())


class TestPagedAttention:
    @pytest.mark.parametrize("page,maxp,Hq,Hkv,D", [
        (16, 8, 4, 2, 64),
        (8, 16, 8, 8, 32),
        (32, 4, 2, 1, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, page, maxp, Hq, Hkv, D, dtype):
        """At every layer of a stacked pool, the kernel matches the
        reference on that layer's pool: dead slots, an idle lane, GQA
        and MHA."""
        L, B, P = 3, 4, 64
        q, kp, vp, bt, cl = _paged_case(
            20, L, P, page, maxp, B, Hq, Hkv, D, dtype
        )
        tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
        for layer in range(L):
            out = paged_pallas(q, kp, vp, layer, bt, cl, interpret=True)
            _check_paged(out, q, kp, vp, layer, bt, cl, tol)

    def test_softcap(self):
        B, P, page, maxp, Hq, Hkv, D = 2, 16, 8, 4, 4, 2, 32
        kp = rand(jax.random.fold_in(KEY, 23), (P, page, Hkv, D), jnp.float32)
        vp = rand(jax.random.fold_in(KEY, 24), (P, page, Hkv, D), jnp.float32)
        q = rand(jax.random.fold_in(KEY, 25), (B, Hq, D), jnp.float32)
        bt = jnp.asarray([[0, 1, 2, 3], [4, 5, -1, -1]], jnp.int32)
        cl = jnp.asarray([30, 12], jnp.int32)
        out = paged_pallas(
            q, kp[None], vp[None], 0, bt, cl, softcap=20.0, interpret=True
        )
        ref = paged_attention_reference(q, kp, vp, bt, cl, softcap=20.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    @pytest.mark.parametrize("impl", ["interpret", "reference"])
    @pytest.mark.parametrize("Hq,Hkv", [(6, 2), (4, 4)])
    def test_layer_traced_in_scan(self, impl, Hq, Hkv):
        """The layer as `paged_decode_step` passes it: a traced index of
        a `lax.scan` over the layers, with the stacked pools as the
        scan's carry, through `ops.paged_attention`."""
        L, B, P, page, maxp, D = 4, 3, 32, 8, 6, 32
        q, kp, vp, bt, cl = _paged_case(
            26, L, P, page, maxp, B, Hq, Hkv, D, jnp.float32
        )

        @jax.jit
        def per_layer(kp, vp):
            def body(carry, layer):
                pk, pv = carry
                o = paged_attention(q, pk, pv, layer, bt, cl, impl=impl)
                return (pk, pv), o

            return jax.lax.scan(body, (kp, vp), jnp.arange(L))[1]

        outs = per_layer(kp, vp)
        for layer in range(L):
            _check_paged(outs[layer], q, kp, vp, layer, bt, cl, 2e-5,
                         kernel=impl != "reference")


class TestNBBSKernel:
    @pytest.mark.parametrize("depth,K,seed,layout", [
        (6, 16, 0, "unpacked"), (9, 64, 1, "unpacked"),
        (8, 33, 2, "packed"), (10, 128, 3, "unpacked"),
        (6, 16, 4, "packed"),
    ])
    def test_matches_jnp_wavefront(self, depth, K, seed, layout):
        cfg = TreeConfig(depth=depth, max_level=0, layout=_LAYOUTS[layout])
        rng = np.random.default_rng(seed)
        levels = jnp.asarray(
            rng.integers(2, depth + 1, size=K), jnp.int32
        )
        t0 = cfg.empty_tree()
        t1, n1, ok1, _ = wavefront_alloc(cfg, t0, levels, jnp.ones(K, bool))
        t2, n2, ok2, stats = wavefront_alloc_pallas(cfg, t0, levels)
        assert (np.asarray(t1) == np.asarray(t2)).all()
        assert (np.asarray(n1) == np.asarray(n2)).all()

    def test_on_fragmented_tree(self):
        cfg = TreeConfig(depth=8, max_level=0)
        tree = cfg.empty_tree()
        # fragment: allocate some, free alternating
        tree, nodes, ok, _ = wavefront_alloc(
            cfg, tree, jnp.full(32, 8, jnp.int32), jnp.ones(32, bool)
        )
        from repro.core.concurrent import free_batch
        tree, _ = free_batch(cfg, tree, nodes[::2], jnp.ones(16, bool))
        levels = jnp.asarray([4, 5, 8, 8, 6], jnp.int32)
        t1, n1, ok1, _ = wavefront_alloc(cfg, tree, levels, jnp.ones(5, bool))
        t2, n2, ok2, _ = wavefront_alloc_pallas(cfg, tree, levels)
        assert (np.asarray(t1) == np.asarray(t2)).all()
        assert (np.asarray(n1) == np.asarray(n2)).all()

    def test_ops_dispatch(self):
        cfg = TreeConfig(depth=6, max_level=0)
        levels = jnp.asarray([3, 4, 5], jnp.int32)
        t1, n1, ok1, s1 = nbbs_wavefront_alloc(
            cfg, cfg.empty_tree(), levels, impl="interpret"
        )
        t2, n2, ok2, s2 = nbbs_wavefront_alloc(
            cfg, cfg.empty_tree(), levels, impl="reference"
        )
        assert (np.asarray(t1) == np.asarray(t2)).all()
        assert int(s1["rounds"]) == int(s2["rounds"])

    def test_auto_dispatch_runs_xla_rounds(self, monkeypatch):
        """Mosaic cannot lower the NBBS kernels, so "auto" names the XLA
        round bodies on every backend, a TPU included (where the paged
        attention kernel is still the Pallas one)."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert ops_mod.default_impl() == "pallas"
        assert ops_mod._resolve_nbbs("auto") == "reference"
        cfg = TreeConfig(depth=6, max_level=0)
        levels = jnp.asarray([3, 4, 6, 6], jnp.int32)
        t1, n1, ok1, _ = nbbs_wavefront_alloc(cfg, cfg.empty_tree(), levels)
        t2, n2, ok2, _ = wavefront_alloc(
            cfg, cfg.empty_tree(), levels, jnp.ones(4, bool)
        )
        assert (np.asarray(t1) == np.asarray(t2)).all()
        assert (np.asarray(n1) == np.asarray(n2)).all()

    @pytest.mark.parametrize("depth,K,F,seed,layout", [
        (6, 16, 8, 0, "unpacked"), (8, 33, 16, 1, "unpacked"),
        (9, 64, 64, 2, "unpacked"), (7, 24, 12, 3, "packed"),
    ])
    def test_mixed_step_matches_jnp(self, depth, K, F, seed, layout):
        """Kernel mixed alloc+free rounds (tree state VMEM-resident for
        the whole step) vs the jnp wavefront_step oracle — both tree
        layouts (the packed case keeps uint32 bunch words in VMEM)."""
        cfg = TreeConfig(depth=depth, max_level=0, layout=_LAYOUTS[layout])
        rng = np.random.default_rng(seed)
        # fragment first so frees exercise real coalescing
        tree, nodes, ok, _ = wavefront_alloc(
            cfg, cfg.empty_tree(),
            jnp.asarray(rng.integers(2, depth + 1, size=2 * F), jnp.int32),
            jnp.ones(2 * F, bool),
        )
        fn = jnp.asarray(np.asarray(nodes)[:F], jnp.int32)
        fa = jnp.asarray(np.asarray(ok)[:F])
        levels = jnp.asarray(rng.integers(1, depth + 1, size=K), jnp.int32)
        t1, n1, ok1, s1 = wavefront_step(
            cfg, tree, fn, fa, levels, jnp.ones(K, bool)
        )
        t2, n2, ok2, s2 = wavefront_step_pallas(cfg, tree, fn, fa, levels)
        assert (np.asarray(t1) == np.asarray(t2)).all()
        assert (np.asarray(n1) == np.asarray(n2)).all()
        assert int(s2[3]) == int(s1["free_merged_writes"])
        assert int(s2[4]) == int(s1["free_logical_rmws"])
        assert int(s2[5]) == int(s1["freed"])

    def test_mixed_step_ops_dispatch(self):
        cfg = TreeConfig(depth=6, max_level=0)
        tree, nodes, ok, _ = wavefront_alloc(
            cfg, cfg.empty_tree(), jnp.full(8, 6, jnp.int32), jnp.ones(8, bool)
        )
        fn, fa = nodes[:4], jnp.ones(4, bool)
        levels = jnp.asarray([2, 5, 6], jnp.int32)
        t1, n1, ok1, s1 = nbbs_wavefront_step(
            cfg, tree, fn, fa, levels, impl="interpret"
        )
        t2, n2, ok2, s2 = nbbs_wavefront_step(
            cfg, tree, fn, fa, levels, impl="reference"
        )
        assert (np.asarray(t1) == np.asarray(t2)).all()
        assert (np.asarray(n1) == np.asarray(n2)).all()
        assert int(s1["free_merged_writes"]) == int(s2["free_merged_writes"])


class TestPooledNBBSKernel:
    """Grid-over-shards pooled kernel vs the in-graph pool router."""

    def test_s1_bit_identical_to_single_tree_kernel(self):
        cfg = TreeConfig(depth=6, max_level=0)
        pcfg = PoolConfig(cfg, 1)
        rng = np.random.default_rng(4)
        tree, nodes, ok, _ = wavefront_alloc(
            cfg, cfg.empty_tree(),
            jnp.asarray(rng.integers(2, 7, size=16), jnp.int32),
            jnp.ones(16, bool),
        )
        fn, fa = nodes[:8], ok[:8]
        levels = jnp.asarray(rng.integers(1, 7, size=12), jnp.int32)
        t1, n1, ok1, _ = wavefront_step_pallas(cfg, tree, fn, fa, levels)
        t2, n2, sh2, ok2, _ = nbbs_pool_wavefront_step(
            pcfg, tree[None, :], fn, jnp.zeros(8, jnp.int32), fa, levels,
            impl="interpret",
        )
        assert (np.asarray(t1) == np.asarray(t2[0])).all()
        assert (np.asarray(n1) == np.asarray(n2)).all()
        assert not np.asarray(sh2).any()

    @pytest.mark.parametrize("S,depth,K,seed,layout", [
        (2, 6, 16, 0, "unpacked"), (4, 5, 20, 1, "unpacked"),
        (2, 6, 16, 2, "packed"),
    ])
    def test_no_overflow_matches_reference_pool(self, S, depth, K, seed, layout):
        """Without overflow the attempt-granular kernel linearization is
        the same linearization as the lockstep in-graph router, so the
        results must be bit-identical (both tree layouts)."""
        pcfg = PoolConfig(TreeConfig(depth=depth, layout=_LAYOUTS[layout]), S)
        rng = np.random.default_rng(seed)
        # ample capacity: mid-to-leaf levels, no shard can exhaust
        levels = jnp.asarray(
            rng.integers(depth - 2, depth + 1, size=K), jnp.int32
        )
        fz = jnp.zeros(4, jnp.int32)
        fza = jnp.zeros(4, bool)
        r = nbbs_pool_wavefront_step(
            pcfg, pcfg.empty_trees(), fz, fz, fza, levels, impl="reference"
        )
        p = nbbs_pool_wavefront_step(
            pcfg, pcfg.empty_trees(), fz, fz, fza, levels, impl="interpret"
        )
        for a, b in zip(r[:4], p[:4]):
            assert (np.asarray(a) == np.asarray(b)).all()
        assert int(r[4]["overflows"]) == 0
        assert int(p[4]["overflows"]) == 0

    def test_pooled_mixed_step_with_frees(self):
        """Frees land on their recorded shard inside the kernel launch
        and the freed capacity is reusable by the same launch's allocs."""
        S, depth = 2, 5
        pcfg = PoolConfig(TreeConfig(depth=depth), S)
        # fill both shards completely at the leaf level
        K0 = S << depth
        lv0 = jnp.full(K0, depth, jnp.int32)
        from repro.core.pool import pool_wavefront_alloc

        trees, nodes, shard, ok, _ = pool_wavefront_alloc(
            pcfg, pcfg.empty_trees(), lv0, jnp.ones(K0, bool)
        )
        assert bool(ok.all())
        # free half of each shard, then allocate one level-(depth-1)
        # chunk per shard through the pooled kernel
        keep = np.arange(K0) % 2 == 0
        fn = jnp.asarray(np.asarray(nodes)[keep], jnp.int32)
        fs = jnp.asarray(np.asarray(shard)[keep], jnp.int32)
        fa = jnp.ones(fn.shape[0], bool)
        levels = jnp.full(2, depth, jnp.int32)
        trees2, n2, sh2, ok2, stats = nbbs_pool_wavefront_step(
            pcfg, trees, fn, fs, fa, levels, impl="interpret"
        )
        assert bool(ok2.all())
        assert int(stats["freed"]) == fn.shape[0]
