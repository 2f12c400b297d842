"""The persistent compilation cache goes where the environment places it:
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``."""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

SRC = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(compile_cache.__file__))))

_COMPILE = (
    "import jax, jax.numpy as jnp\n"
    "from repro.launch.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()\n")


def test_cache_dir_from_environment(tmp_path):
    """A process started with the variable set writes its compiles there
    (the suite's own processes keep the cache off)."""
    where = tmp_path / "cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(where),
               JAX_ENABLE_COMPILATION_CACHE="true", JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", _COMPILE], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=120, check=True)
    assert out.stdout.split()[-1] == str(where)
    assert compile_cache.cache_entries(str(where))


def test_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_hlo_source_file_canonicalization_regex")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(os.path.dirname(SRC), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
