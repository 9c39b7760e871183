"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: JAX reads
``JAX_COMPILATION_CACHE_DIR`` when it starts.
"""
import os
import pathlib
import subprocess
import sys

from repro.launch.compile_cache import CACHE_DIR

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

_SCRIPT = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: x * {salt!r} + 1.0)(jnp.arange(8.0)).block_until_ready()
"""


def _compile_once(cache_env):
    """Compile a program no earlier run has cached; returns the cache
    directory the helper reported."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC)] + [p for p in [env.get("PYTHONPATH")] if p])
    salt = 1.0 + int.from_bytes(os.urandom(4), "little") / 2**40
    out = subprocess.run([sys.executable, "-c", _SCRIPT.format(salt=salt)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def _entries(path):
    path = pathlib.Path(path)
    return set(os.listdir(path)) if path.is_dir() else set()


def test_cache_follows_the_environment_variable(tmp_path):
    cache = tmp_path / "xla_cache"
    assert _compile_once(cache) == str(cache)
    assert any(name.startswith("jit_") for name in _entries(cache))


def test_cache_defaults_to_the_checkout_path():
    before = _entries(CACHE_DIR)
    assert _compile_once(None) == str(CACHE_DIR)
    assert CACHE_DIR.parent == SRC.parent
    assert _entries(CACHE_DIR) - before           # a new entry landed there
