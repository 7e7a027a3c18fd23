"""launch/compile_cache.py: the persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, and to ``<checkout>/.jax_cache``
only when it is unset.  Each case compiles a uniquely named function in
a child process and looks for its entry."""
import os
import subprocess
import sys
import uuid
from pathlib import Path

from repro.launch.compile_cache import DEFAULT_CACHE_DIR

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
exec("def {name}(x):\\n    return x * 3 + 1")
jax.jit({name})(jnp.arange(8.0)).block_until_ready()
"""


def _probe(tmp_path, cache_env):
    name = f"probe_{uuid.uuid4().hex[:12]}"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               HOME=str(tmp_path / "home"))
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    proc = subprocess.run([sys.executable, "-c", PROBE.format(name=name)],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return name, proc.stdout.strip().splitlines()[-1]


def _entries(root: Path, name: str):
    return [p for p in root.rglob(f"*{name}*")] if root.exists() else []


def test_cache_dir_from_environment(tmp_path):
    cache = tmp_path / "placed"
    name, path = _probe(tmp_path, cache)
    assert path == str(cache)
    assert _entries(cache, name)
    # nowhere else: not in the checkout default, not under cwd or HOME
    assert not _entries(DEFAULT_CACHE_DIR, name)
    assert [p for p in _entries(tmp_path, name)
            if cache not in p.parents] == []


def test_cache_dir_defaults_to_checkout(tmp_path):
    name, path = _probe(tmp_path, None)
    assert path == str(ROOT / ".jax_cache") == str(DEFAULT_CACHE_DIR)
    found = _entries(DEFAULT_CACHE_DIR, name)
    try:
        assert found
        assert not _entries(tmp_path, name)
    finally:
        for p in found:
            p.unlink()
