"""Elastic rescale demo: train on a (4,2) mesh, checkpoint, restore onto a
(2,4) mesh and continue — the code path a pod uses after losing (or
gaining) slices.  Runs in a subprocess with 8 forced host devices.

    PYTHONPATH=src python examples/elastic_restart.py
"""

import os
import subprocess
import sys

SCRIPT = r"""
import tempfile, jax, jax.numpy as jnp
from jax.sharding import NamedSharding
from repro.launch.mesh import make_test_mesh
from repro.configs import get_config
from repro.models.sharding import MeshAxes, param_specs
from repro.train.trainer import TrainConfig, init_train_state, make_train_step
from repro.data.pipeline import SyntheticLM
from repro.ckpt.checkpoint import CheckpointManager

cfg = get_config("stablelm-3b").reduced()
tcfg = TrainConfig(remat=True, dtype=jnp.float32)
axes = MeshAxes(dp=("data",), tp="model")
data = SyntheticLM(cfg.vocab_size, 16, 8)

def run_steps(mesh, state, n, start):
    ns = lambda s: NamedSharding(mesh, s)
    state = jax.device_put(state, jax.tree.map(ns, param_specs(axes, state)))
    step = jax.jit(make_train_step(cfg, tcfg, axes), donate_argnums=0)
    with jax.set_mesh(mesh):
        for i in range(start, start + n):
            state, m = step(state, data.batch_at(i))
            print(f"  mesh={tuple(mesh.shape.values())} step {i} "
                  f"loss {float(m['loss']):.4f}")
    return state

state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
print("phase 1: (data=4, model=2) — 256 chips' worth of topology, scaled")
mesh42 = make_test_mesh((4, 2), ("data", "model"))
state = run_steps(mesh42, state, 4, 0)

with tempfile.TemporaryDirectory() as d:
    CheckpointManager(d, async_io=False).save(4, state)
    print("checkpoint saved; simulating topology change (lost a slice)...")
    mesh24 = make_test_mesh((2, 4), ("data", "model"))
    like = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    ns = lambda s: NamedSharding(mesh24, s)
    restored = CheckpointManager(d, async_io=False).restore(
        4, like=like, shardings=jax.tree.map(ns, param_specs(axes, like))
    )
    print("phase 2: restored onto (data=2, model=4), training continues")
    run_steps(mesh24, restored, 4, 4)
print("elastic rescale OK")
"""

env = dict(os.environ)
env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
sys.exit(subprocess.run([sys.executable, "-c", SCRIPT], env=env).returncode)
