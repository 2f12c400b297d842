"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches see
the real 1-device CPU backend; only launch/dryrun.py forces 512 devices.

The persistent compilation cache stays off in tests, and in the entry
points they start as subprocesses (which inherit the variable)."""
import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import jax  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture
def host_mesh():
    """1-device mesh with production axis names — exercises the pjit/
    shard_map code paths on this container."""
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh()
