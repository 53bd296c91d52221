"""Distribution tests run in subprocesses with forced host device counts
(jax locks the device count at first init): pipeline parallelism via
ppermute, compressed psum on a mesh, sharded train step on a 2x2 mesh,
elastic restore across mesh sizes, and the dry-run cell builder on a
small production-mesh-shaped mesh."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(n, code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=540,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_pipeline_parallel_forward_and_grad():
    out = run_with_devices(4, """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_test_mesh
from repro.train.pp import pipeline_apply

mesh = make_test_mesh((4,), ("pipe",))
L, n_micro, mb, d = 8, 4, 2, 16
key = jax.random.PRNGKey(0)
W = jax.random.normal(key, (L, d, d)) * 0.3

def body(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.fold_in(key, 1), (n_micro, mb, d))
y = pipeline_apply(body, W, x, mesh)
# reference: plain sequential layers
ref = x
for l in range(L):
    ref = jnp.tanh(ref @ W[l])
np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

# differentiable through the pipeline
def loss(W):
    return jnp.square(pipeline_apply(body, W, x, mesh)).sum()
g = jax.grad(loss)(W)
gref = jax.grad(lambda W: jnp.square(
    jnp.tanh(jnp.tanh(x @ W[0]) @ W[1]) if False else loss_ref(W)))(W) if False else None
def loss_ref(W):
    r = x
    for l in range(L):
        r = jnp.tanh(r @ W[l])
    return jnp.square(r).sum()
gref = jax.grad(loss_ref)(W)
np.testing.assert_allclose(np.asarray(g), np.asarray(gref), atol=1e-4)
print("PP OK")
""")
    assert "PP OK" in out


def test_compressed_psum_on_mesh():
    out = run_with_devices(4, """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.optim.compression import compressed_psum

mesh = make_test_mesh((4,), ("dp",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 512))

f = jax.shard_map(
    lambda g: compressed_psum(g[0], "dp"),
    mesh=mesh, in_specs=P("dp", None), out_specs=P(),
)
out = f(x)
ref = x.sum(0)
err = float(jnp.abs(out - ref).max())
rel = err / float(jnp.abs(ref).max())
assert rel < 0.02, (err, rel)  # int8 quantization error bound
print("CPSUM OK", rel)
""")
    assert "CPSUM OK" in out


def test_sharded_train_step_and_elastic_restore():
    out = run_with_devices(8, """
import tempfile, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.configs import get_config
from repro.models.sharding import MeshAxes, param_specs
from repro.train.trainer import TrainConfig, init_train_state, make_train_step
from repro.data.pipeline import SyntheticLM
from repro.ckpt.checkpoint import CheckpointManager

cfg = get_config("stablelm-3b").reduced()
tcfg = TrainConfig(microbatches=1, remat=True, dtype=jnp.float32)
axes = MeshAxes(dp=("data",), tp="model", fsdp=True)
data = SyntheticLM(cfg.vocab_size, 16, 8)

def steps_on_mesh(mesh, state, n, start):
    ns = lambda s: NamedSharding(mesh, s)
    specs = param_specs(axes, state)
    state = jax.device_put(state, jax.tree.map(ns, specs))
    step = jax.jit(make_train_step(cfg, tcfg, axes), donate_argnums=0)
    with jax.set_mesh(mesh):
        for i in range(start, start + n):
            state, m = step(state, data.batch_at(i))
    return state, float(m["loss"])

mesh42 = make_test_mesh((4, 2), ("data", "model"))
state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
state, loss1 = steps_on_mesh(mesh42, state, 3, 0)

# elastic: save on (4,2), restore on (2,4), keep training
with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d, async_io=False)
    mgr.save(3, state)
    mesh24 = make_test_mesh((2, 4), ("data", "model"))
    like = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    ns2 = lambda s: NamedSharding(mesh24, s)
    shardings = jax.tree.map(ns2, param_specs(axes, like))
    restored = mgr.restore(3, like=like, shardings=shardings)
    state2, loss2 = steps_on_mesh(mesh24, restored, 3, 3)
assert np.isfinite(loss1) and np.isfinite(loss2)
print("ELASTIC OK", loss1, loss2)
""")
    assert "ELASTIC OK" in out


def test_single_device_vs_sharded_same_loss():
    out = run_with_devices(4, """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding
from repro.launch.mesh import make_test_mesh
from repro.configs import get_config
from repro.models.sharding import MeshAxes, param_specs
from repro.models import init_params
from repro.models.transformer import train_loss
from repro.data.pipeline import SyntheticLM

cfg = get_config("stablelm-3b").reduced()
data = SyntheticLM(cfg.vocab_size, 16, 4)
params = init_params(cfg, jax.random.PRNGKey(0))
batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
l_single = float(train_loss(cfg, params, batch, dtype=jnp.float32, remat=False))

mesh = make_test_mesh((2, 2), ("data", "model"))
axes = MeshAxes(dp=("data",), tp="model", fsdp=True)
ns = lambda s: NamedSharding(mesh, s)
p_sh = jax.device_put(params, jax.tree.map(ns, param_specs(axes, params)))
with jax.set_mesh(mesh):
    l_shard = float(jax.jit(
        lambda p, b: train_loss(cfg, p, b, axes=axes, dtype=jnp.float32,
                                remat=False)
    )(p_sh, batch))
assert abs(l_single - l_shard) < 1e-3, (l_single, l_shard)
print("SPMD-EQUIV OK", l_single, l_shard)
""")
    assert "SPMD-EQUIV OK" in out


def test_dryrun_cell_builder_on_small_mesh():
    """The launch-layer cell builder (shardings, specs, step functions)
    lowers AND compiles on a small production-shaped mesh for a reduced
    arch — the fast CI version of the 512-device dry-run."""
    out = run_with_devices(8, """
import jax
from jax.sharding import Mesh
from repro.launch.mesh import make_test_mesh
from repro.launch import dryrun
from repro.configs import get_config
from repro.configs.base import ShapeSpec

cfg = get_config("stablelm-3b").reduced()
mesh = make_test_mesh((4, 2), ("data", "model"))
for spec in (ShapeSpec("t", 32, 8, "train"),
             ShapeSpec("p", 32, 8, "prefill"),
             ShapeSpec("d", 32, 8, "decode")):
    with jax.set_mesh(mesh):
        lowered, meta = dryrun.build_cell(cfg, spec, mesh, False)
        compiled = lowered.compile()
        assert compiled.cost_analysis() is not None
    print("cell", spec.kind, "OK")
print("BUILDER OK")
""")
    assert "BUILDER OK" in out
