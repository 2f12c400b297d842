"""The main-path Pallas kernels compile for a TPU v5e at the deployed
widths (rank 3, k = 16, 38 item fields, a corpus of 8192 items).

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests run on the CPU: each lowers a
kernel through Mosaic and the v5e's XLA, and fails on what the chip's
compiler refuses (an unsupported primitive, a misaligned block, VMEM
exhausted).  Interpret mode cannot show any of these.  Nothing runs, so
results are covered by the interpret-mode tests.

The topology is described inside a fixture (never at import), and the
persistent compilation cache is off around the compiles: a program built
for a described chip can be written to it but not read back.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

RHO, K_EMB, M_I, N, TOPK = 3, 16, 38, 8192, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _assert_compiles(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


def _corpus_shapes(Bq):
    f32 = jnp.float32
    return [((N, RHO, K_EMB), f32), ((N,), f32), ((RHO,), f32),
            ((Bq, RHO, K_EMB), f32), ((Bq,), f32), ((N,), jnp.bool_)]


@pytest.mark.parametrize("Bq", [1, 16])
def test_corpus_topk_compiles_for_v5e(one_chip, no_compile_cache, Bq):
    _assert_compiles(
        lambda Q, a, e, P, aC, v: ops.dplr_corpus_score(
            Q, a, e, P, aC, v, topk=TOPK, interpret=False),
        one_chip, *_corpus_shapes(Bq))


def test_corpus_full_mode_compiles_for_v5e(one_chip, no_compile_cache):
    _assert_compiles(
        lambda Q, a, e, P, aC, v: ops.dplr_corpus_score(
            Q, a, e, P, aC, v, interpret=False),
        one_chip, *_corpus_shapes(16))


def test_corpus_multi_compiles_for_v5e(one_chip, no_compile_cache):
    S, Bq, f32 = 4, 4, jnp.float32
    _assert_compiles(
        lambda Q, a, v, e, P, aC: ops.dplr_corpus_score_multi(
            (Q,) * S, (a,) * S, (v,) * S, e, P, aC, topk=TOPK,
            interpret=False),
        one_chip, ((N, RHO, K_EMB), f32), ((N,), f32), ((N,), jnp.bool_),
        ((S, RHO), f32), ((S, Bq, RHO, K_EMB), f32), ((S, Bq), f32))


def test_score_items_compiles_for_v5e(one_chip, no_compile_cache):
    f32 = jnp.float32
    _assert_compiles(
        lambda V, U, e, d, P, s: ops.dplr_score_items(
            V, U, e, d, P, s, interpret=False),
        one_chip, ((N, M_I, K_EMB), f32), ((RHO, M_I), f32), ((RHO,), f32),
        ((M_I,), f32), ((RHO, K_EMB), f32), ((), f32))


def test_kernel_locations_relative_to_checkout(one_chip, no_compile_cache):
    """A cache key must not depend on where the checkout lies.  The
    Mosaic kernel rides inside the program as serialized IR whose source
    locations JAX does not strip before hashing; with the prefix the
    compile-cache helper sets, they name files relative to the checkout."""
    import base64
    import re

    from repro.launch import compile_cache

    name = "jax_hlo_source_file_canonicalization_regex"
    prev = getattr(jax.config, name)
    jax.config.update(name, compile_cache.SOURCE_PREFIX)
    try:
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in _corpus_shapes(4)]
        text = jax.jit(lambda Q, a, e, P, aC, v: ops.dplr_corpus_score(
            Q, a, e, P, aC, v, topk=TOPK, interpret=False)).lower(
                *args).as_text()
    finally:
        jax.config.update(name, prev)
    # the kernel body is base64 MLIR bytecode, whose magic is "ML\xefR"
    bodies = [base64.b64decode(b) for b in re.findall(
        r"TUzvUg[A-Za-z0-9+/=]+", text)]
    assert bodies, "no serialized Mosaic kernel in the program"
    for body in bodies:
        assert compile_cache.CHECKOUT.encode() not in body
        assert b"src/repro/kernels/dplr_corpus_score.py" in body
