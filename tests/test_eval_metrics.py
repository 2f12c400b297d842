"""Oracle parity for ``repro.eval.metrics``: every jitted metric against
its float64 numpy reference in ``eval/ref.py`` (the pairing the analyzer's
MET-ORACLE/MET-TEST rules statically require), property-swept over random
shapes/seeds plus the adversarial edges the conventions define — all-tie
scores, single-class labels, k > n cutoffs, empty batches, bf16 scores.
Also the streaming contract: ``MetricAccumulator`` results are
bit-identical under batch-order permutation and any merge tree."""
import math

import numpy as np
import pytest

import jax.numpy as jnp

from hypothesis import given, settings, strategies as st
from repro.eval import metrics as M
from repro.eval import ref

TOL = 1e-6


def _pointwise_case(n: int, seed: int, pos_rate: float = 0.5):
    rng = np.random.default_rng(seed)
    labels = (rng.random(n) < pos_rate).astype(np.int32)
    logits = rng.normal(scale=3.0, size=n).astype(np.float32)
    return labels, logits


def _assert_pointwise_parity(labels, logits, tol=TOL):
    y, z = jnp.asarray(labels), jnp.asarray(logits)
    assert abs(float(M.auc(y, z)) - ref.auc_ref(labels, logits)) <= tol
    assert abs(float(M.logloss(y, z))
               - ref.logloss_ref(labels, logits)) <= tol
    got_c = float(M.calibration_ratio(y, z))
    want_c = ref.calibration_ratio_ref(labels, logits)
    if math.isinf(want_c):
        assert math.isinf(got_c)
    else:
        assert abs(got_c - want_c) <= tol


# ---------------------------------------------------------------------------
# pointwise metrics vs oracles
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 4000), seed=st.integers(0, 10**6))
def test_pointwise_oracle_parity(n, seed):
    labels, logits = _pointwise_case(n, seed)
    _assert_pointwise_parity(labels, logits)


def test_auc_known_values():
    y = np.array([0, 0, 1, 1])
    assert float(M.auc(jnp.asarray(y), jnp.asarray([0., 1., 2., 3.]))) == 1.0
    assert float(M.auc(jnp.asarray(y), jnp.asarray([3., 2., 1., 0.]))) == 0.0
    # one discordant pair out of four: AUC = 3/4
    s = np.array([0.0, 2.0, 1.0, 3.0], np.float32)
    assert float(M.auc(jnp.asarray(y), jnp.asarray(s))) == 0.75
    assert ref.auc_ref(y, s) == 0.75


def test_auc_all_tied_scores():
    # every pair is a tie -> midrank AUC is exactly 0.5 on both sides
    labels, _ = _pointwise_case(257, 3)
    scores = np.full(257, 0.125, np.float32)
    assert float(M.auc(jnp.asarray(labels), jnp.asarray(scores))) == 0.5
    assert ref.auc_ref(labels, scores) == 0.5


def test_auc_tie_blocks_parity():
    # heavy but non-degenerate ties: quantized scores
    rng = np.random.default_rng(11)
    labels = (rng.random(1000) < 0.3).astype(np.int32)
    scores = np.round(rng.normal(size=1000), 1).astype(np.float32)
    got = float(M.auc(jnp.asarray(labels), jnp.asarray(scores)))
    assert abs(got - ref.auc_ref(labels, scores)) <= TOL


def test_single_class_auc_is_half():
    _, logits = _pointwise_case(64, 5)
    for y in (np.zeros(64, np.int32), np.ones(64, np.int32)):
        assert float(M.auc(jnp.asarray(y), jnp.asarray(logits))) == 0.5
        assert ref.auc_ref(y, logits) == 0.5


def test_empty_batch_conventions():
    y = np.zeros(0, np.int32)
    z = np.zeros(0, np.float32)
    assert float(M.auc(jnp.asarray(y), jnp.asarray(z))) == 0.5
    assert float(M.logloss(jnp.asarray(y), jnp.asarray(z))) == 0.0
    assert float(M.calibration_ratio(jnp.asarray(y), jnp.asarray(z))) == 1.0
    assert ref.auc_ref(y, z) == 0.5
    assert ref.logloss_ref(y, z) == 0.0
    assert ref.calibration_ratio_ref(y, z) == 1.0


def test_calibration_no_positives_is_inf():
    y = np.zeros(16, np.int32)
    z = np.zeros(16, np.float32)          # sigmoid mass, no positives
    assert math.isinf(float(M.calibration_ratio(jnp.asarray(y),
                                                jnp.asarray(z))))
    assert math.isinf(ref.calibration_ratio_ref(y, z))


def test_bf16_scores_parity():
    # bf16 quantization creates tie blocks; both sides see the SAME
    # f32 values (the jitted side casts, the oracle gets the cast array)
    labels, logits = _pointwise_case(512, 7)
    z16 = jnp.asarray(logits, jnp.bfloat16)
    z32 = np.asarray(z16.astype(jnp.float32))
    assert np.unique(z32).size < 512       # quantization actually tied
    got = float(M.auc(jnp.asarray(labels), z16))
    assert abs(got - ref.auc_ref(labels, z32)) <= TOL
    got_ll = float(M.logloss(jnp.asarray(labels), z16))
    assert abs(got_ll - ref.logloss_ref(labels, z32)) <= 1e-5


# ---------------------------------------------------------------------------
# ranking metrics vs oracles
# ---------------------------------------------------------------------------

def _ranking_case(B: int, n: int, seed: int):
    rng = np.random.default_rng(seed)
    rel = (rng.random((B, n)) * 3).astype(np.float32)
    rel[rng.random((B, n)) < 0.5] = 0.0           # sparse relevance
    if B > 1:
        rel[0] = 0.0                              # a zero-relevance query
    scores = rng.normal(size=(B, n)).astype(np.float32)
    return rel, scores


@settings(max_examples=10, deadline=None)
@given(B=st.integers(1, 16), n=st.integers(1, 128),
       k=st.integers(1, 200), seed=st.integers(0, 10**6))
def test_ranking_oracle_parity(B, n, k, seed):
    rel, scores = _ranking_case(B, n, seed)
    rel01 = (rel > 0).astype(np.float32)
    r, r01, s = jnp.asarray(rel), jnp.asarray(rel01), jnp.asarray(scores)
    assert abs(float(M.ndcg_at_k(r, s, k=k))
               - ref.ndcg_at_k_ref(rel, scores, k)) <= TOL
    assert abs(float(M.precision_at_k(r01, s, k=k))
               - ref.precision_at_k_ref(rel01, scores, k)) <= TOL
    assert abs(float(M.recall_at_k(r01, s, k=k))
               - ref.recall_at_k_ref(rel01, scores, k)) <= TOL
    assert abs(float(M.mrr(r01, s)) - ref.mrr_ref(rel01, scores)) <= TOL


def test_k_larger_than_n_items_clamps():
    rel, scores = _ranking_case(4, 7, 0)
    r, s = jnp.asarray(rel), jnp.asarray(scores)
    assert float(M.ndcg_at_k(r, s, k=500)) == float(M.ndcg_at_k(r, s, k=7))
    assert float(M.precision_at_k(r, s, k=500)) == \
        float(M.precision_at_k(r, s, k=7))
    assert ref.ndcg_at_k_ref(rel, scores, 500) == \
        ref.ndcg_at_k_ref(rel, scores, 7)


def test_ranking_tied_scores_stable_order():
    # all scores equal: both sides must fall back to index order
    rel = np.array([[0., 1., 0., 2.], [2., 0., 0., 0.]], np.float32)
    scores = np.ones((2, 4), np.float32)
    for k in (1, 2, 4):
        got = float(M.ndcg_at_k(jnp.asarray(rel), jnp.asarray(scores), k=k))
        assert abs(got - ref.ndcg_at_k_ref(rel, scores, k)) <= TOL
    got = float(M.mrr(jnp.asarray(rel), jnp.asarray(scores)))
    assert got == ref.mrr_ref(rel, scores) == 0.5 * (1 / 2 + 1 / 1)


def test_ranking_empty_and_zero_relevance():
    empty = np.zeros((0, 8), np.float32)
    assert float(M.ndcg_at_k(jnp.asarray(empty), jnp.asarray(empty),
                             k=3)) == 0.0
    assert ref.ndcg_at_k_ref(empty, empty, 3) == 0.0
    # zero-relevance queries contribute 0, not NaN
    rel = np.zeros((3, 5), np.float32)
    scores = np.random.default_rng(0).normal(size=(3, 5)).astype(np.float32)
    for fn, rf in ((M.ndcg_at_k, ref.ndcg_at_k_ref),
                   (M.recall_at_k, ref.recall_at_k_ref)):
        assert float(fn(jnp.asarray(rel), jnp.asarray(scores), k=2)) == 0.0
        assert rf(rel, scores, 2) == 0.0
    assert float(M.mrr(jnp.asarray(rel), jnp.asarray(scores))) == 0.0


def test_ranking_rejects_non_2d():
    flat = np.zeros(8, np.float32)
    with pytest.raises(ValueError, match="must be"):
        M.ndcg_at_k(jnp.asarray(flat), jnp.asarray(flat), k=3)
    with pytest.raises(ValueError, match="must be"):
        ref.ndcg_at_k_ref(flat, flat, 3)
    with pytest.raises(ValueError, match="must be"):
        M.mrr(jnp.asarray(flat), jnp.asarray(flat))


# ---------------------------------------------------------------------------
# streaming partials + MetricAccumulator
# ---------------------------------------------------------------------------

def test_pointwise_partials_histograms_exact_midbin():
    # probabilities planted mid-bin: half a bin (2.4e-4) of slack vs the
    # ~1-ulp XLA-vs-numpy sigmoid difference, so the histograms must
    # agree EXACTLY (boundary-straddling data is tested tolerantly below)
    n_bins = ref.DEFAULT_BINS
    rng = np.random.default_rng(0)
    p = (rng.integers(0, n_bins, 4096) + 0.5) / n_bins
    logits = np.log(p / (1 - p)).astype(np.float32)
    labels = (rng.random(4096) < p).astype(np.int32)
    got = M.pointwise_partials(jnp.asarray(labels), jnp.asarray(logits))
    want = ref.pointwise_partials_ref(labels, logits)
    assert int(got["n"]) == want["n"]
    assert int(got["n_pos"]) == want["n_pos"]
    assert np.array_equal(np.asarray(got["pos_hist"]), want["pos_hist"])
    assert np.array_equal(np.asarray(got["neg_hist"]), want["neg_hist"])
    assert abs(float(got["bce_sum"]) - want["bce_sum"]) <= 1e-2  # f32 sum
    assert abs(float(got["p_sum"]) - want["p_sum"]) <= 1e-2


def test_pointwise_partials_random_binned_auc_tolerant():
    # arbitrary logits may straddle bin boundaries by 1 ulp: counts are
    # conserved exactly, the binned AUC is tolerance-bounded
    labels, logits = _pointwise_case(8192, 13)
    got = M.pointwise_partials(jnp.asarray(labels), jnp.asarray(logits))
    want = ref.pointwise_partials_ref(labels, logits)
    pos, neg = np.asarray(got["pos_hist"]), np.asarray(got["neg_hist"])
    assert pos.sum() == want["pos_hist"].sum() == want["n_pos"]
    assert neg.sum() == want["neg_hist"].sum() == want["n"] - want["n_pos"]
    assert abs(ref.binned_auc(pos, neg)
               - ref.binned_auc(want["pos_hist"], want["neg_hist"])) <= 1e-6
    # and the binned stream approximates the exact AUC
    exact = ref.auc_ref(labels, logits)
    assert abs(ref.binned_auc(pos, neg) - exact) <= 5e-3


def test_ranking_partials_fold_matches_whole_batch():
    rel, scores = _ranking_case(12, 32, 21)
    whole = M.ranking_partials(jnp.asarray(rel), jnp.asarray(scores), k=5)
    want = ref.ranking_partials_ref(rel, scores, 5)
    assert int(whole["n_queries"]) == want["n_queries"]
    for key in ("ndcg_sum", "prec_sum", "rec_sum", "mrr_sum"):
        assert abs(float(whole[key]) - want[key]) <= 1e-4


def _filled_accumulator(batches, rank_batches, order):
    acc = M.MetricAccumulator(k=5)
    for i in order:
        acc.update(*batches[i])
    for rb in rank_batches:
        acc.update_ranking(*rb)
    return acc


def test_accumulator_order_invariance_bitwise():
    rng = np.random.default_rng(2)
    batches = [_pointwise_case(int(rng.integers(1, 700)), s)
               for s in range(8)]
    rank_batches = [_ranking_case(3, 16, 50 + s) for s in range(3)]
    a = _filled_accumulator(batches, rank_batches, range(8))
    b = _filled_accumulator(batches, rank_batches, reversed(range(8)))
    ra, rb_ = a.result(), b.result()
    assert ra == rb_                       # bit-identical, not just close


def test_accumulator_merge_tree_matches_sequential():
    batches = [_pointwise_case(300, s) for s in range(6)]
    seq = M.MetricAccumulator(k=5)
    for lb, lg in batches:
        seq.update(lb, lg)
    shards = []
    for lo in range(0, 6, 2):
        sh = M.MetricAccumulator(k=5)
        for lb, lg in batches[lo:lo + 2]:
            sh.update(lb, lg)
        shards.append(sh)
    merged = shards[0].merge(shards[1]).merge(shards[2])
    assert merged.result() == seq.result()


def test_accumulator_matches_whole_split_metrics():
    labels, logits = _pointwise_case(20000, 9)
    acc = M.MetricAccumulator()
    for i in range(0, 20000, 4096):
        acc.update(labels[i:i + 4096], logits[i:i + 4096])
    out = acc.result()
    assert out["n"] == 20000
    assert out["n_pos"] == int(labels.sum())
    assert abs(out["logloss"] - ref.logloss_ref(labels, logits)) <= 1e-5
    assert abs(out["calibration_ratio"]
               - ref.calibration_ratio_ref(labels, logits)) <= 1e-5
    assert abs(out["auc"] - ref.auc_ref(labels, logits)) <= 5e-3


def test_accumulator_empty_and_mismatch():
    acc = M.MetricAccumulator(k=5)
    out = acc.result()
    assert out["auc"] == 0.5 and out["logloss"] == 0.0
    assert out["calibration_ratio"] == 1.0 and out["mrr"] == 0.0
    with pytest.raises(ValueError, match="k/n_bins"):
        acc.merge(M.MetricAccumulator(k=7))
