"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls ``enable_compile_cache``
before its first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads that directory itself and nothing here overrides it; otherwise
the cache lives at ``<checkout>/.jax_cache`` — a fixed path (listed in
``.gitignore``), because the directory is part of what a later process
must find again.  Every compile is cached, however short: a Pallas
kernel compiles in about a second, under JAX's default threshold.

A cache key must not depend on where the checkout lies.  JAX strips
source locations from the program before hashing it, but a Pallas TPU
kernel travels inside the program as serialized Mosaic IR whose
locations name each traced frame's file by its absolute path; two
checkouts of one commit would then never share an entry.  The helper
sets ``jax_hlo_source_file_canonicalization_regex`` to cut the checkout
prefix from those file names, so they are relative to the checkout.

``cache_counts`` reports the hits and misses of this process, read from
JAX's monitoring events.

The test suite leaves the cache off (``tests/conftest.py``): a program
compiled there for a described TPU that is not attached can be written
but not read back.
"""
from __future__ import annotations

import os
import re

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")
# what JAX cuts from the file names in source locations (the checkout)
SOURCE_PREFIX = "^" + re.escape(CHECKOUT + os.sep)


_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_COUNTS = {_HIT: 0, _MISS: 0}
_listening = False


def _count(event: str, **_) -> None:
    if event in _COUNTS:
        _COUNTS[event] += 1


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      SOURCE_PREFIX)
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_count)
        _listening = True
    return path


def cache_counts() -> tuple[int, int]:
    """(hits, misses) of the persistent cache in this process so far."""
    return _COUNTS[_HIT], _COUNTS[_MISS]


def cache_entries(path: str) -> set[str]:
    """Names of the entries in the cache directory (empty when it is
    absent).  Each entry is one ``<key>-cache`` file; a size-capped cache
    (``JAX_COMPILATION_CACHE_MAX_SIZE``) adds an access-time file per
    entry and evicts old entries as it writes new ones, so the entries
    written between two calls are the new names, not the change in the
    number of files."""
    if not os.path.isdir(path):
        return set()
    return {f for f in os.listdir(path) if f.endswith("-cache")}
