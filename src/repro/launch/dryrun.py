import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any jax-importing import (jax locks the
device count on first init); do not move them.

For each cell this driver:
  1. builds the production mesh ((16,16) or (2,16,16));
  2. constructs the jitted step (train_step / prefill / decode_step)
     with NamedSharding in/out specs from the model's partitioning
     rules (FSDP x TP params, DP batch, sequence-sharded KV);
  3. `.lower(**ShapeDtypeStructs).compile()` — nothing is allocated;
  4. records `memory_analysis()` (fits-per-device proof),
     `cost_analysis()` (XLA's numbers, loop bodies counted once), and
     the loop-aware roofline terms from `roofline.hlo_analysis` (trip-
     count-corrected flops / bytes / collective bytes per device);
  5. writes one JSON per cell under experiments/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama4-scout-17b-a16e \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, input_specs, ARCH_NAMES
from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models.sharding import MeshAxes, param_specs
from repro.models.transformer import decode_step, init_cache, init_params, prefill
from repro.roofline.hlo_analysis import HW_V5E, analyze_hlo, roofline_terms
from repro.train.trainer import TrainConfig, TrainState, make_train_step
from repro.optim import adamw


def _dp(axes_tuple):
    return axes_tuple if len(axes_tuple) > 1 else axes_tuple[0]


def batch_specs(batch_tree, dp, batch_divisible: bool):
    def one(leaf):
        if not batch_divisible:
            return P()
        return P(dp, *([None] * (leaf.ndim - 1)))

    return jax.tree.map(one, batch_tree)


def cache_pspecs(cfg: ArchConfig, cache_tree, dp, tp, batch_divisible: bool):
    """KV caches: [sites, B, S, Hkv, D] -> B on dp, S on tp (flash-decode
    partial-softmax falls out of SPMD); batch-1 cells shard S over
    everything instead. States (mamba/rwkv): heads on tp."""

    def one(path, leaf):
        names = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        last = names[-1] if names else ""
        if last in ("k", "v") and leaf.ndim == 5:
            if batch_divisible:
                return P(None, dp, tp, None, None)
            allaxes = (dp if isinstance(dp, tuple) else (dp,)) + (tp,)
            return P(None, None, allaxes, None, None)
        if last == "ssm" and leaf.ndim >= 4:  # [G,A,B,H,N,P]
            lead = leaf.ndim - 4
            return P(*([None] * lead), None if not batch_divisible else dp,
                     tp, None, None)
        if last == "conv" and leaf.ndim >= 3:  # [G,A,B,K-1,convdim]
            lead = leaf.ndim - 3
            return P(*([None] * lead), None if not batch_divisible else dp,
                     None, tp)
        if last == "wkv" and leaf.ndim == 5:  # [L,B,H,P,P]
            return P(None, dp if batch_divisible else None, tp, None, None)
        if last in ("tm_x", "cm_x") and leaf.ndim == 3:  # [L,B,d]
            return P(None, dp if batch_divisible else None, tp)
        return P()

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def build_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, multi_pod: bool,
               variant: str = "baseline"):
    """Returns (lowered, meta) for one cell.

    variant='opt' enables the beyond-paper optimizations recorded in
    docs/experiments.md §Perf: block-local MoE dispatch aligned to the data
    shards, capacity 2.0 serving dispatch, bf16-once parameter casting
    (bf16 FSDP gathers + bf16 gradient wire), gradient sharding
    constraints (reduce-scatter), and bf16 serving weights."""
    dpa = dp_axes(multi_pod)
    axes = MeshAxes(dp=dpa, tp="model", fsdp=True)
    dp = _dp(dpa)
    dp_size = 1
    for a in dpa:
        dp_size *= mesh.shape[a]
    batch_div = shape.global_batch % dp_size == 0
    ns = lambda spec: NamedSharding(mesh, spec)
    flags = set(variant.split("+")) if variant != "baseline" else set()
    if "opt" in flags:
        flags = {"einsum", "servecf", "bf16serve"}
    if cfg.n_experts:
        group = cfg.dispatch_group
        for f in flags:
            if f.startswith("g") and f[1:].isdigit():
                group = int(f[1:])  # e.g. g512: einsum dispatch group size
        cfg = dataclasses.replace(
            cfg,
            dispatch_blocks=(dp_size if batch_div and "blocks" in flags else 1),
            serve_capacity_factor=(2.0 if "servecf" in flags else 0.0),
            dispatch_mode=("einsum" if "einsum" in flags else "scatter"),
            dispatch_group=group,
        )

    params_shape = jax.eval_shape(
        lambda k: init_params(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    if "bf16serve" in flags and shape.kind != "train":
        # bf16 serving weights (no f32 masters at inference)
        params_shape = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)
            if a.dtype == jnp.float32 and len(a.shape) >= 2
            else a,
            params_shape,
        )
    pspecs = param_specs(axes, params_shape)

    if shape.kind == "train":
        tcfg = TrainConfig(
            microbatches=1, remat=True, dtype=jnp.bfloat16,
            cast_params_once="cast" in flags,
            constrain_grads="rsgrads" in flags,
        )
        step = make_train_step(cfg, tcfg, axes)
        opt_shape = jax.eval_shape(adamw.init, params_shape)
        state_shape = TrainState(params_shape, opt_shape, {})
        state_specs = param_specs(axes, state_shape)
        batch = input_specs(cfg, shape)
        bspecs = batch_specs(batch, dp, batch_div)
        fn = jax.jit(
            step,
            in_shardings=(
                jax.tree.map(ns, state_specs),
                jax.tree.map(ns, bspecs),
            ),
            donate_argnums=0,
        )
        lowered = fn.lower(state_shape, batch)
        tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        bspecs = batch_specs(batch, dp, batch_div)

        def pf(params, batch):
            return prefill(
                cfg, params, batch, max_len=shape.seq_len, axes=axes,
                dtype=jnp.bfloat16,
            )

        cache_shape = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len)
        )
        cspecs = cache_pspecs(cfg, cache_shape, dp, "model", batch_div)
        fn = jax.jit(
            pf,
            in_shardings=(jax.tree.map(ns, pspecs), jax.tree.map(ns, bspecs)),
            out_shardings=(
                ns(P(dp if batch_div else None, "model")),
                jax.tree.map(ns, cspecs),
            ),
        )
        lowered = fn.lower(params_shape, batch)
        tokens = shape.global_batch * shape.seq_len
    elif shape.kind == "decode":
        cache_shape = jax.eval_shape(
            lambda: init_cache(cfg, shape.global_batch, shape.seq_len)
        )
        cspecs = cache_pspecs(cfg, cache_shape, dp, "model", batch_div)
        toks = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)

        def dec(params, cache, tokens):
            return decode_step(
                cfg, params, cache, tokens, axes=axes, dtype=jnp.bfloat16
            )

        fn = jax.jit(
            dec,
            in_shardings=(
                jax.tree.map(ns, pspecs),
                jax.tree.map(ns, cspecs),
                ns(P(dp if batch_div else None)),
            ),
            out_shardings=(
                ns(P(dp if batch_div else None, "model")),
                jax.tree.map(ns, cspecs),
            ),
            donate_argnums=1,
        )
        lowered = fn.lower(params_shape, cache_shape, toks)
        tokens = shape.global_batch  # one token per sequence
    else:
        raise ValueError(shape.kind)
    return lowered, {"tokens": tokens}


def model_flops(cfg: ArchConfig, shape: ShapeSpec, tokens: int) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    return 2.0 * n_active * tokens


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             variant: str = "baseline") -> dict:
    cfg = get_config(arch)
    shapes = cfg.supported_shapes()
    if shape_name not in shapes:
        return {
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "skipped",
            "reason": "long_500k requires sub-quadratic attention "
                      "(docs/design.md §5)",
        }
    shape = shapes[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered, meta = build_cell(cfg, shape, mesh, multi_pod, variant)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = analyze_hlo(compiled.as_text())
    mf = model_flops(cfg, shape, meta["tokens"])
    terms = roofline_terms(hlo)
    per_dev_model_flops = mf / n_chips
    result = {
        "arch": arch,
        "variant": variant,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes_per_device": getattr(mem, "alias_size_in_bytes", None),
        },
        "xla_cost_analysis": {
            "flops": ca.get("flops"),
            "bytes accessed": ca.get("bytes accessed"),
            "note": "XLA counts while bodies once; see hlo_walk for "
                    "trip-count-corrected numbers",
        },
        "hlo_walk_per_device": {
            "flops": hlo["flops"],
            "bytes": hlo["bytes"],
            "collective_bytes": hlo["collective_bytes"],
            "per_collective": hlo["per_collective"],
            "warnings": hlo["warnings"],
        },
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_device": per_dev_model_flops,
        "useful_flops_ratio": (
            per_dev_model_flops / hlo["flops"] if hlo["flops"] else None
        ),
        "hw": HW_V5E,
    }
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(
        out_dir,
        f"{arch.replace('/', '_')}__{shape_name}__"
        f"{'multi' if multi_pod else 'single'}.json",
    )
    with open(fn, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_NAMES if args.all or args.arch is None else [args.arch]
    shape_names = (
        ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
        if args.shape is None
        else [args.shape]
    )
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for sn in shape_names:
            for mp in meshes:
                tag = f"{arch:28s} {sn:12s} {'2x16x16' if mp else '16x16 '}"
                fn = os.path.join(
                    args.out,
                    f"{arch.replace('/', '_')}__{sn}__"
                    f"{'multi' if mp else 'single'}.json",
                )
                if args.skip_existing and os.path.exists(fn):
                    with open(fn) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[cached ] {tag}")
                        continue
                try:
                    r = run_cell(arch, sn, mp, args.out, args.variant)
                    if r["status"] == "skipped":
                        print(f"[skipped] {tag} — {r['reason']}")
                    else:
                        tms = r["roofline"]
                        print(
                            f"[ok     ] {tag} compile={r['compile_s']:.0f}s "
                            f"dom={tms['dominant']:<12s} "
                            f"c/m/coll(ms)={tms['compute_s']*1e3:.1f}/"
                            f"{tms['memory_s']*1e3:.1f}/"
                            f"{tms['collective_s']*1e3:.1f}"
                        )
                except Exception as e:
                    failures += 1
                    print(f"[FAIL   ] {tag}: {e}")
                    traceback.print_exc()
                    os.makedirs(args.out, exist_ok=True)
                    with open(fn, "w") as f:
                        json.dump(
                            {"arch": arch, "shape": sn,
                             "mesh": "2x16x16" if mp else "16x16",
                             "status": "fail", "error": str(e)}, f)
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
