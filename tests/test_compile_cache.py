"""The persistent compile cache lands where the entry points put it:
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else the one fixed
directory in the checkout.  Each case compiles in a child process, so
the cache setting never reaches the other tests."""
import os
import secrets
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import compile_cache

SCRIPT = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache
    print("DIR", compile_cache.enable())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    k = int(sys.argv[1])        # a fresh program: never a cache hit
    jax.jit(lambda x: x * k + 1)(jnp.arange(7)).block_until_ready()
""")


def _files(d: Path) -> set:
    return set(d.iterdir()) if d.is_dir() else set()


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_lands_in_one_place(tmp_path, from_env):
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    want = tmp_path if from_env else compile_cache.CACHE_DIR
    before = _files(compile_cache.CACHE_DIR)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(secrets.randbelow(2 ** 30))],
        capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert f"DIR {want}" in r.stdout
    new_default = _files(compile_cache.CACHE_DIR) - before
    try:
        if from_env:
            assert _files(tmp_path) and not new_default
        else:
            assert new_default
    finally:
        for f in new_default:
            f.unlink()
