"""End-to-end behaviour tests: tiny-LM training convergence through the
full stack (trainer + supervisor + checkpoints + failure injection) and
the serving engine driven through the public launch CLIs."""

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def run_cli(args, timeout=540, env=ENV):
    r = subprocess.run(
        [sys.executable, "-m"] + args, env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_train_cli_loss_decreases_with_failure_recovery():
    with tempfile.TemporaryDirectory() as d:
        out = run_cli([
            "repro.launch.train", "--arch", "stablelm-3b", "--reduced",
            "--steps", "40", "--batch", "8", "--seq", "32", "--lr", "3e-3",
            "--ckpt-dir", d, "--ckpt-every", "10", "--fail-at", "17",
        ])
        stats = json.loads(out.strip().splitlines()[-1])
        assert stats["last_loss"] < stats["first_loss"]
        # a checkpoint survived
        assert any(n.startswith("step_") for n in os.listdir(d))


def test_serve_cli_completes_requests(tmp_path):
    # the CLI's compile cache goes to a scratch directory, not the checkout
    out = run_cli([
        "repro.launch.serve", "--arch", "stablelm-3b", "--reduced",
        "--requests", "6", "--max-new", "4", "--num-pages", "64",
        "--page-tokens", "4",
    ], env=dict(ENV, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache")))
    stats = json.loads(out.strip().splitlines()[-1])
    assert stats["completed"] == 6
    assert stats["kv"]["used_pages"] == 0  # everything freed + coalesced


def test_compile_cache_defaults_to_checkout(monkeypatch):
    import jax

    from repro.launch import serve

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    here = os.path.realpath(os.path.join(REPO, ".jax_cache"))
    try:
        assert serve.enable_compile_cache() == here
        assert jax.config.jax_compilation_cache_dir == here
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_placed_from_outside(monkeypatch):
    import jax

    from repro.launch import serve

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert serve.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX
