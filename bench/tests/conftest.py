import os

import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _restore_jax_cache_config():
    """The harness points JAX's persistent cache at its own directory;
    give later tests in this process the settings they had."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    cc.reset_cache()
