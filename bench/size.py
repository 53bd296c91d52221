"""How much of one v5e each cell's programs need, without a chip.

    JAX_PLATFORMS=cpu python3 bench/size.py [--pages N] <cell> [<cell> ...]

Compiles, for a described TPU v5e (the TPU compiler is installed here;
no chip is attached), the cell's fused `engine_run` of CHUNK decode
steps with the Pallas paged-attention kernel in bf16, and its largest
prefill bucket, and prints each program's `memory_analysis()`. The sum
argument + output - alias + temp is what the program needs on the
device while it runs; a compile that does not fit raises the TPU
compiler's own error, which is printed. `--pages` overrides the
configuration's pool size, to find where the pool stops.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
          "alias_size_in_bytes", "temp_size_in_bytes",
          "generated_code_size_in_bytes")


def _report(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in FIELDS}
    out["needs_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          - out["alias_size_in_bytes"]
                          + out["temp_size_in_bytes"])
    return {name: out}


def size_cell(name: str, pages: int | None, one_chip) -> dict:
    import jax
    import jax.numpy as jnp

    import run
    import spec
    import traffic
    from repro.launch.serve import CHUNK, init_serving_params
    from repro.serve.jit_engine import EngineConfig, engine_run, init_engine_state
    from repro.serve.paged_decode import serve_prefill

    cell = spec.load_cell(name)
    arch = run.arch_config(cell.config)
    dtype = jnp.dtype(cell.config["torch_dtype"])
    ecfg = EngineConfig(
        arch=arch, num_pages=pages or cell.config["num_pages"],
        page_tokens=cell.config["page_tokens"],
        max_batch=cell.cell["max_batch"],
        max_lane_pages=cell.traffic["max_lane_pages"],
        max_out=cell.traffic["max_out"], impl="pallas", dtype=dtype.name,
    )

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = placed(jax.eval_shape(
        lambda k: init_serving_params(arch, k, dtype), key))
    state = placed(jax.eval_shape(lambda: init_engine_state(ecfg)))
    out = {"cell": name, "num_pages": ecfg.num_pages}
    try:
        out.update(_report("engine_run", engine_run.lower(
            ecfg, params, state, CHUNK).compile()))
    except Exception as e:  # the TPU compiler's refusal is the finding
        out["engine_run"] = f"refused: {str(e).splitlines()[0][:300]}"
    longest = int(traffic.length_set(cell.traffic)["prompt"].max())
    spad = 1 << (longest - 1).bit_length()
    toks = placed({"tokens": jax.ShapeDtypeStruct((1, spad), jnp.int32)})
    try:
        out.update(_report(f"serve_prefill[{spad}]", serve_prefill.lower(
            arch, params, toks, max_len=spad, dtype=dtype).compile()))
    except Exception as e:
        out[f"serve_prefill[{spad}]"] = (
            f"refused: {str(e).splitlines()[0][:300]}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--pages", type=int, default=None)
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in args.cells:
        print(json.dumps(size_cell(name, args.pages, one_chip)), flush=True)


if __name__ == "__main__":
    main()
