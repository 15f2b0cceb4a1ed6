"""util/compile_cache.py: JAX's persistent cache goes where
JAX_COMPILATION_CACHE_DIR says and only there; without the variable, to
the fixed <checkout>/.jax_cache.  Each case runs in a fresh interpreter
(the suite itself keeps the cache off, see conftest.py)."""

import os
import subprocess
import sys

from seaweedfs_tpu.util.compile_cache import CHECKOUT

_COMPILE = """
import jax, jax.numpy as jnp
from seaweedfs_tpu.util.compile_cache import place_compile_cache
print(place_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(64)).block_until_ready()
"""


def _run(code: str, **env) -> str:
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=CHECKOUT, timeout=120,
        env={**base, "JAX_PLATFORMS": "cpu",
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0", **env})
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_env_dir_is_honoured_and_only_it(tmp_path):
    default = os.path.join(CHECKOUT, ".jax_cache")
    before = set(os.listdir(default)) if os.path.isdir(default) else set()
    d = str(tmp_path / "cache")
    assert _run(_COMPILE, JAX_COMPILATION_CACHE_DIR=d) == d
    assert os.listdir(d), "the compile was not cached in the env dir"
    after = set(os.listdir(default)) if os.path.isdir(default) else set()
    assert after == before


def test_default_dir_is_the_checkout(tmp_path):
    want = os.path.join(CHECKOUT, ".jax_cache")
    # placed before JAX is imported (the CLI's case) ...
    code = ("from seaweedfs_tpu.util.compile_cache import "
            "place_compile_cache as p; p(); import jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    assert _run(code) == want
    # ... and after (chip_smoke.py imports JAX first)
    code = ("import jax; from seaweedfs_tpu.util.compile_cache import "
            "place_compile_cache as p; p(); "
            "print(jax.config.jax_compilation_cache_dir)")
    assert _run(code) == want
