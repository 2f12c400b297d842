"""The paper's mathematical core: Identity 1, Proposition 1, and the
equivalences between all interaction implementations."""
import numpy as np
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.dplr import (DPLRParams, dplr_diagonal, init_dplr,
                             materialize_R, posthoc_dplr,
                             posthoc_error_spectrum)
from repro.core.interactions import (dplr_pairwise, dplr_pairwise_explicit_d,
                                     fm_pairwise, fwfm_pairwise,
                                     pruned_pairwise_dense,
                                     pruned_pairwise_sparse)
from repro.core.pruning import kept_fraction, matched_param_count, prune_matched


def _rand_V(rng, B, m, k):
    return jnp.asarray(rng.standard_normal((B, m, k), dtype=np.float32))


@settings(deadline=None, max_examples=25)
@given(m=st.integers(3, 24), k=st.integers(1, 16), rho=st.integers(1, 5),
       B=st.integers(1, 8), seed=st.integers(0, 2**31 - 1))
def test_dplr_equals_fwfm_with_materialized_R(m, k, rho, B, seed):
    """Proposition 1: the O(rho m k) path == the O(m^2 k) path on R(U, e)."""
    rng = np.random.default_rng(seed)
    p = init_dplr(jax.random.PRNGKey(seed), m, rho)
    V = _rand_V(rng, B, m, k)
    fast = dplr_pairwise(V, p)
    slow = fwfm_pairwise(V, materialize_R(p))
    np.testing.assert_allclose(fast, slow, rtol=2e-4, atol=2e-4)


@settings(deadline=None, max_examples=25)
@given(m=st.integers(2, 24), k=st.integers(1, 16), B=st.integers(1, 8),
       seed=st.integers(0, 2**31 - 1))
def test_rank1_ones_is_plain_fm(m, k, B, seed):
    """Eq. (7): R_FM = 11^T - I, i.e. DPLR with U=1, e=1 is a plain FM."""
    rng = np.random.default_rng(seed)
    p = DPLRParams(U=jnp.ones((1, m)), e=jnp.ones((1,)))
    V = _rand_V(rng, B, m, k)
    np.testing.assert_allclose(dplr_pairwise(V, p), fm_pairwise(V),
                               rtol=2e-4, atol=2e-4)


def test_structural_zero_diagonal():
    """diag(R) == 0 by construction (Eq. 10), for random U, e."""
    for seed in range(5):
        p = init_dplr(jax.random.PRNGKey(seed), 13, 4)
        R = materialize_R(p)
        np.testing.assert_allclose(np.diag(np.asarray(R)), 0.0, atol=1e-5)
        np.testing.assert_allclose(R, R.T, atol=1e-5)   # symmetric


def test_dplr_diagonal_formula():
    p = init_dplr(jax.random.PRNGKey(1), 9, 3)
    low = jnp.einsum("rm,r,rn->mn", p.U, p.e, p.U)
    np.testing.assert_allclose(dplr_diagonal(p), -jnp.diag(low), rtol=1e-5)


@settings(deadline=None, max_examples=20)
@given(m=st.integers(4, 20), rank=st.integers(1, 4), seed=st.integers(0, 10**6))
def test_pruned_dense_equals_sparse(m, rank, seed):
    rng = np.random.default_rng(seed)
    R = rng.standard_normal((m, m)).astype(np.float32)
    R = 0.5 * (R + R.T)
    np.fill_diagonal(R, 0)
    pr = prune_matched(R, m, rank)
    V = _rand_V(rng, 6, m, 8)
    dense = pruned_pairwise_dense(V, jnp.asarray(R), pr.mask)
    sparse = pruned_pairwise_sparse(V, pr.entries_i, pr.entries_j, pr.entries_r)
    np.testing.assert_allclose(dense, sparse, rtol=2e-4, atol=2e-4)


def test_matched_param_count_table1_protocol():
    # Section 5.1: rank-rho DPLR has rho(m+1) interaction params
    assert matched_param_count(39, 1) == 40
    assert matched_param_count(39, 5) == 200
    # Criteo row of Table 1: rank 1 -> 5.4% of interactions kept
    assert abs(kept_fraction(39, 1) - 0.054) < 0.002
    # capped at the full upper triangle
    assert matched_param_count(5, 100) == 10


def test_fm_identity_rendle():
    """Eq. (1)/(2c): the linear-time identity vs the explicit double sum."""
    rng = np.random.default_rng(3)
    V = _rand_V(rng, 4, 10, 8)
    explicit = 0.0
    Vn = np.asarray(V)
    explicit = sum(
        (Vn[:, i] * Vn[:, j]).sum(-1)
        for i in range(10) for j in range(i + 1, 10)
    )
    np.testing.assert_allclose(fm_pairwise(V), explicit, rtol=2e-4)


def test_posthoc_dplr_beats_nothing_but_not_training(rng):
    """Section 5.4 mechanics: the alternating DPLR fit reduces the error
    spectrum vs rank-truncation-only, and the error is nonzero for a
    full-rank R (why post-hoc is dominated by direct training)."""
    m = 16
    R = rng.standard_normal((m, m)).astype(np.float32)
    R = 0.5 * (R + R.T)
    np.fill_diagonal(R, 0)
    U, e, d = posthoc_dplr(R, rank=4, n_iters=30)
    approx = (U.T * e) @ U + np.diag(d)
    spec = posthoc_error_spectrum(R, approx)
    # fitting rank+diag must do at least as well as plain eigen-truncation
    w, Q = np.linalg.eigh(R)
    idx = np.argsort(-np.abs(w))[:4]
    trunc = (Q[:, idx] * w[idx]) @ Q[:, idx].T
    spec_trunc = posthoc_error_spectrum(R, trunc)
    assert spec.sum() <= spec_trunc.sum() + 1e-5
    assert spec[0] > 1e-3   # full-rank teacher: post-hoc can't be exact


def test_posthoc_exact_on_true_dplr_matrix():
    """When R truly IS DPLR of rank r, the post-hoc fit recovers it."""
    p = init_dplr(jax.random.PRNGKey(7), 12, 2)
    R = np.asarray(materialize_R(p))
    U, e, d = posthoc_dplr(R, rank=2, n_iters=50, polish_steps=2000)
    approx = (U.T * e) @ U + np.diag(d)
    np.testing.assert_allclose(approx, R, atol=5e-3)


def test_dplr_explicit_d_matches():
    p = init_dplr(jax.random.PRNGKey(9), 10, 3)
    rng = np.random.default_rng(9)
    V = _rand_V(rng, 5, 10, 8)
    d = dplr_diagonal(p)
    np.testing.assert_allclose(
        dplr_pairwise(V, p),
        dplr_pairwise_explicit_d(V, p.U, p.e, d), rtol=1e-5, atol=1e-5)
