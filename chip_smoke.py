"""On-chip smoke test: serve stablelm-3b at full width on one TPU.

    python chip_smoke.py

Builds the engine the way the serving entry point does
(`repro.launch.serve.build_engine`: compile cache, bf16 weights from a
seed, `JitServeEngine`), at the published widths of
`configs/stablelm_3b.py` (32 layers, d_model 2560, 32 heads of dim 80,
d_ff 6912, vocab 50304).  Then, in this one process:

  1. the paged-attention kernel at the real widths against
     `kernels.ref.paged_attention_reference` (computed at highest matmul
     precision) on a random page pool;
  2. one `paged_decode_step` of the whole model through the kernel:
     logits of the right shape, all finite;
  3. `engine_step` compiled ahead of time: its memory analysis, and
     `tpu_custom_call` in its compiled text (the Pallas kernel, not the
     reference, is what the served step runs);
  4. eight requests (prompts of 16-256 tokens, 32 new tokens each)
     served to completion in fused chunks: every request gets its full
     budget, none overflows, every page returns to the pool;
  5. the kernel with a sliding window at Mellum2-12B's heads (32 q over
     4 kv heads of dim 128, window 1024, 160-page tables) against the
     reference, at contexts on both sides of the window and of a page
     boundary.

Earlier lines report the device, compile seconds per jitted function,
the memory figures and the kernel's error.  The last line is
`{"ok": true, "device": {...}}` and is printed only when every check
passed.  Without a TPU the script exits non-zero before any phase.
Nothing here is a benchmark: the wall times include compilation.
"""

from __future__ import annotations

import json
import os
import sys
import time
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.ref import paged_attention_reference  # noqa: E402
from repro.launch.serve import CHUNK, build_engine  # noqa: E402
from repro.serve.engine import Request  # noqa: E402
from repro.serve.jit_engine import engine_step  # noqa: E402
from repro.serve.paged_decode import paged_decode_step  # noqa: E402

SEED = 0
PROMPT_LENS = (16, 24, 40, 64, 100, 128, 200, 256)  # 5 prefill buckets
MAX_NEW = 32
PAGE_TOKENS = 16
NUM_PAGES = 512  # 8,192 tokens: ~2.7 GB of bf16 K+V for stablelm-3b
MAX_BATCH = 8
MAX_LANE_PAGES = 32
# bf16 inputs and output; the same bound as the interpret-mode bf16
# sweep in tests/test_kernels.py
KERNEL_ATOL = 3e-2
KERNEL_CALL = "tpu_custom_call"  # what a Pallas kernel compiles to

# the jitted entries this run compiles, reported one by one
ENTRIES = (
    "init_serving_params", "paged_attention", "paged_decode_step",
    "engine_step", "serve_prefill", "admit_pages", "prefill_insert",
    "engine_run", "clear_lanes",
)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def kernel_error(cfg, rng, max_pages=MAX_LANE_PAGES, window=0,
                 contexts=()) -> float:
    """Max abs error of the paged-attention kernel at the model's widths
    against the reference, on the engine's pool and lane geometry:
    distinct random pages per lane, -1 padded, a context inside the
    mapped pages (the given `contexts` first, then random ones), and an
    idle last lane (context 0) as the engine passes for an inactive
    lane; with `window`, a sliding window layer's."""
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    pool_shape = (NUM_PAGES, PAGE_TOKENS, Hkv, D)
    kp = jax.random.normal(keys[0], pool_shape, jnp.bfloat16)
    vp = jax.random.normal(keys[1], pool_shape, jnp.bfloat16)
    q = jax.random.normal(keys[2], (MAX_BATCH, H, D), jnp.bfloat16)
    tables = np.full((MAX_BATCH, max_pages), -1, np.int32)
    ctx = np.zeros((MAX_BATCH,), np.int32)
    for b in range(MAX_BATCH - 1):
        if b < len(contexts):
            ctx[b] = contexts[b]
            n = -(-ctx[b] // PAGE_TOKENS)
            tables[b, :n] = rng.choice(NUM_PAGES, size=n, replace=False)
            continue
        n = int(rng.integers(1, max_pages + 1))
        tables[b, :n] = rng.choice(NUM_PAGES, size=n, replace=False)
        ctx[b] = int(rng.integers(1, n * PAGE_TOKENS + 1))
    args = (jnp.asarray(tables), jnp.asarray(ctx), window)
    out = ops.paged_attention(q, kp[None], vp[None], 0, *args)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_reference(q, kp, vp, *args)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    check(out.shape == (MAX_BATCH, H, D), f"kernel output shape {out.shape}")
    check(np.isfinite(out).all(), "kernel output has non-finite values")
    # an idle lane skips every page and emits zeros; the reference's
    # softmax over an empty context is undefined there
    live = ctx > 0
    check(not out[~live].any(), "idle lane produced non-zero attention")
    return float(np.abs(out[live] - ref[live]).max())


def decode_logits(cfg, params, rng) -> np.ndarray:
    """One full-model paged decode step through the kernel, on a small
    random pool: two pages per lane."""
    shape = (cfg.n_layers, 2 * MAX_BATCH, PAGE_TOKENS, cfg.n_kv_heads,
             cfg.head_dim)
    kk, kv = jax.random.split(jax.random.PRNGKey(SEED + 2))
    pool = {
        "k": jax.random.normal(kk, shape, jnp.bfloat16),
        "v": jax.random.normal(kv, shape, jnp.bfloat16),
    }
    tables = jnp.arange(2 * MAX_BATCH, dtype=jnp.int32).reshape(MAX_BATCH, 2)
    ctx = jnp.asarray(
        rng.integers(0, 2 * PAGE_TOKENS, size=MAX_BATCH), jnp.int32
    )
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=MAX_BATCH), jnp.int32
    )
    lg, _, _ = paged_decode_step(
        cfg, params, pool, tables, ctx, tokens,
        page_tokens=PAGE_TOKENS, dtype=jnp.bfloat16,
    )
    return np.asarray(lg)


def smoke(cfg) -> None:
    """Every phase and check, in one process; exits non-zero on the
    first failure."""
    compile_s = defaultdict(float)
    cache_hits = [0]

    def on_duration(event, duration, **kw):
        if event == COMPILE_EVENT:
            compile_s[kw.get("fun_name", "?")] += duration

    def on_event(event, **kw):
        if event == CACHE_HIT_EVENT:
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    rng = np.random.default_rng(SEED)

    t0 = time.perf_counter()
    eng = build_engine(
        cfg, seed=SEED, dtype=jnp.bfloat16, num_pages=NUM_PAGES,
        page_tokens=PAGE_TOKENS, max_batch=MAX_BATCH,
        max_lane_pages=MAX_LANE_PAGES, max_out=MAX_NEW,
    )
    jax.block_until_ready(eng.params)
    print(f"engine built in {time.perf_counter() - t0:.3f} s "
          f"({cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads x {cfg.head_dim}, vocab {cfg.vocab_size}, "
          f"bf16; pool {NUM_PAGES} pages x {PAGE_TOKENS} tokens)")

    # 1. the kernel against the reference at the real widths
    err = kernel_error(cfg, rng)
    print(f"paged-attention kernel max abs error vs reference: {err!r} "
          f"(tolerance {KERNEL_ATOL})")
    check(err <= KERNEL_ATOL, f"kernel error {err} > {KERNEL_ATOL}")

    # 2. full-model decode logits through the kernel
    lg = decode_logits(cfg, eng.params, rng)
    print(f"decode logits: shape {lg.shape}, "
          f"max |logit| {float(np.abs(lg).max())!r}")
    check(lg.shape == (MAX_BATCH, cfg.vocab_size), f"logits shape {lg.shape}")
    check(np.isfinite(lg).all(), "decode logits have non-finite values")

    # 3. the served step, compiled ahead of time
    compiled = engine_step.lower(eng.ecfg, eng.params, eng.state).compile()
    mem = compiled.memory_analysis()
    print("engine_step memory_analysis: " + json.dumps({
        k: getattr(mem, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes",
        )
    }))
    check(KERNEL_CALL in compiled.as_text(),
          f"compiled engine_step has no {KERNEL_CALL}: the paged-attention "
          "kernel is not on the served path")
    print(f"engine_step contains {KERNEL_CALL}: True")

    # 4. serve to completion through the fused decode loop
    for i, plen in enumerate(PROMPT_LENS):
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        eng.submit(Request(i, prompt, max_new_tokens=MAX_NEW))
    t0 = time.perf_counter()
    eng.run_to_completion(chunk=CHUNK)
    wall = time.perf_counter() - t0
    done = eng.completed
    n_tok = sum(len(r.out_tokens) for r in done.values())
    free = eng.device_free_pages()
    print(f"served {len(done)} requests: decode steps {eng.stats['steps']}, "
          f"tokens {n_tok}, wall {wall!r} s (includes compilation)")
    print(f"overflow_retired {eng.stats['overflow_retired']}, "
          f"rejected {eng.stats['rejected']}, free pages {free}/{NUM_PAGES}")
    check(len(done) == len(PROMPT_LENS) and not eng.waiting,
          f"{len(done)} of {len(PROMPT_LENS)} requests completed")
    short = {i: len(r.out_tokens) for i, r in done.items()
             if len(r.out_tokens) != MAX_NEW}
    check(not short, f"requests without their full budget: {short}")
    check(all(0 <= t < cfg.vocab_size
              for r in done.values() for t in r.out_tokens),
          "generated token outside the vocabulary")
    check(eng.stats["overflow_retired"] == 0, "a lane overflowed")
    check(eng.stats["rejected"] == 0, "a request was rejected")
    check(free == NUM_PAGES, f"{NUM_PAGES - free} pages never returned")

    for name in ENTRIES:
        print(f"compile {name}: {compile_s.pop(f'jit({name})', 0.0)!r} s")
    print(f"compile other ({len(compile_s)} eager ops): "
          f"{sum(compile_s.values())!r} s; persistent compile cache hits: "
          f"{cache_hits[0]}")
    stats = jax.devices()[0].memory_stats() or {}
    check("peak_bytes_in_use" in stats, "device reports no memory stats")
    print(f"peak_bytes_in_use: {stats['peak_bytes_in_use']} "
          f"(bytes_limit {stats.get('bytes_limit')})")


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found {dev.platform!r}")
    print(f"device: {dev.platform} {dev.device_kind} "
          f"(count {len(jax.devices())})")
    smoke(get_config("stablelm-3b"))
    # 5. the window mask at Mellum2-12B's heads
    mellum = get_config("mellum2-12b")
    window = mellum.window_pattern[0]
    err = kernel_error(
        mellum, np.random.default_rng(SEED + 5), max_pages=160,
        window=window,
        contexts=(window - 1, window, window + 1, window + PAGE_TOKENS + 1,
                  2 * window - 1, 2560),
    )
    print(f"windowed kernel ({mellum.name} heads, window {window}) max abs "
          f"error vs reference: {err!r} (tolerance {KERNEL_ATOL})")
    check(err <= KERNEL_ATOL, f"windowed kernel error {err} > {KERNEL_ATOL}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
