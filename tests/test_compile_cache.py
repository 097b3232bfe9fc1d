"""Where the persistent compilation cache lands.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it and every entry lands
there, none in the checkout. Unset: the entry points point the cache at
one fixed, git-ignored directory inside the checkout.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import serve

_COMPILE = """
import jax, jax.numpy as jnp
from repro.launch.serve import use_compile_cache
print(use_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.ones(5)).block_until_ready()
"""


def _listing(d: Path):
    return sorted(os.listdir(d)) if d.is_dir() else []


def test_env_cache_dir_takes_every_entry(tmp_path):
    fixed_before = _listing(serve.COMPILE_CACHE_DIR)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(Path(serve.__file__).resolve().parents[2]),
         env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", _COMPILE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split()[-1] == str(tmp_path / "cc")
    assert any(n.endswith("-cache") for n in _listing(tmp_path / "cc"))
    assert _listing(serve.COMPILE_CACHE_DIR) == fixed_before


def test_unset_cache_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = serve.use_compile_cache()
        assert got == str(serve.COMPILE_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert serve.use_compile_cache() == got       # no tmp/pid/time part
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = serve.COMPILE_CACHE_DIR.parent
    ignored = (root / ".gitignore").read_text().split()
    assert f"/{serve.COMPILE_CACHE_DIR.name}/" in ignored
