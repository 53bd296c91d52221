"""Serving launcher: the jit-resident engine on NBBS-paged KV memory.

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --reduced \
      --requests 16 --max-new 8

`build_engine` is the one way an engine is set up for serving: this
CLI and `chip_smoke.py` both call it.  It places JAX's persistent
compile cache, makes the weights in the serving dtype and builds a
`JitServeEngine`.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.models import init_params
from repro.serve.engine import Request
from repro.serve.jit_engine import JitServeEngine

# a fixed path inside the checkout: the cache key includes the
# directory, so a cache that moves between runs never hits
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
CHUNK = 8  # decode steps per fused dispatch


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at `.jax_cache/` in the
    checkout, unless `JAX_COMPILATION_CACHE_DIR` places it (JAX reads
    that variable itself).  Returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


@functools.partial(jax.jit, static_argnums=(0, 2))
def init_serving_params(cfg: ArchConfig, key: jax.Array, dtype) -> dict:
    """Random weights made directly in the serving dtype: the float32
    master copy of stablelm-3b (11.2 GB) does not fit one v5e beside
    its KV pool, the bf16 one (5.6 GB) does."""
    return jax.tree.map(lambda a: a.astype(dtype), init_params(cfg, key))


def build_engine(
    cfg: ArchConfig, *, seed: int, dtype, **engine_kw
) -> JitServeEngine:
    """The serving setup shared by every entry point."""
    enable_compile_cache()
    dtype = jnp.dtype(dtype)
    params = init_serving_params(cfg, jax.random.PRNGKey(seed), dtype)
    return JitServeEngine(cfg, params, dtype=dtype, **engine_kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--num-pages", type=int, default=256)
    ap.add_argument("--page-tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dtype = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    eng = build_engine(
        cfg,
        seed=args.seed,
        dtype=dtype,
        num_pages=args.num_pages,
        page_tokens=args.page_tokens,
        max_batch=args.max_batch,
        max_out=args.max_new,
    )
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = int(rng.integers(2, args.prompt_len + 1))
        eng.submit(
            Request(
                i,
                rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32),
                max_new_tokens=args.max_new,
            )
        )
    t0 = time.perf_counter()
    eng.run_to_completion(chunk=CHUNK)
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in eng.completed.values())
    free = eng.device_free_pages()
    print(
        json.dumps(
            {
                "completed": len(eng.completed),
                "generated_tokens": toks,
                "tokens_per_s": toks / dt,
                "device": jax.devices()[0].platform,
                "engine_stats": eng.stats,
                "kv": {
                    "num_pages": args.num_pages,
                    "free_pages": free,
                    "used_pages": args.num_pages - free,
                },
            }
        )
    )


if __name__ == "__main__":
    main()
