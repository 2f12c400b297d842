"""MIND for the benchmark: its layout, weights, ids, float64 reference,
work counts and kernel name.

A configuration names this module by ``"bench_model": "mind"``.  Like
``reference.py`` it imports nothing of the program: the layout comes from
the configuration's ``model`` block, the weights are drawn here from the
seed, and the reference is given the ids the benchmark wrote.

The model (Li et al. 2019, arXiv:1904.08030, as the program serves it):
a query row is ``m_p`` profile ids, then ``seq_len`` history item ids
(``-1`` pads).  With ``e_l`` the arena rows of the history and ``n`` its
length,

    K'  = max(1, min(K, floor(log2 n)))            live interests
    low = e_l S                                     (L, d), padding zeroed
    b   = b_init;  3 rounds:  w = softmax over the K' live interests of b
          (padding positions dropped),  c_k = squash(sum_l w_kl low_l),
          b += c_k . low_l
    u_k = relu(relu([c_k, p_1, .., p_m] W1 + b1) W2 + b2)
    score(item) = max over the live k of u_k . e_item

with ``squash(v) = |v|^2 / (1 + |v|^2) v / sqrt(|v|^2 + 1e-9)`` and
``p_f`` the profile fields' arena rows.  The reference computes all of
it in float64 from the float32 draws.

``absum`` is the scale at which float32 rounding of this computation
lives: ``max over live k of ||ua_k|| ||e_item||``, where ``ua`` is the
tower evaluated on absolute values (each capsule's ``squash`` factor
times ``sum_l w_kl |e_l| |S|``, then ``|W1|``, ``|b1|``, ``|W2|``,
``|b2|``, with no ReLU gating).  A float32 product or sum errs by a few
ulps of the sum of its terms' magnitudes, and each layer of the tower
carries its inputs' errors forward through the same absolute weights, so
an interest vector errs by a few ulps of ``ua`` and a served score, a
d-term dot product with the item row, by a small multiple of float32
epsilon times ``sum_d ua_d |e_d| <= ||ua|| ||e||`` (the bound is taken
because it costs one norm per item, not a second product); the routing
weights' own rounding (softmax of logits of order 1) moves the capsules
by a few ulps as well.

Work counts, of what MIND retrieval needs:

* a launch reads the live slab once: per slot ``d`` f32 and the validity
  mask (one 32-bit word), plus each query row's ``K x d`` interest
  vectors in and the ``rows x K`` f32 + i32 top-K out;
* a served (query, item) pair costs ``2 K d + K`` FLOPs: K dot products
  of length d, and the K-way max with the liveness mask;
* a request's tower costs its products: ``2 L d^2`` for ``e S``, per
  routing round ``4 K L d`` (the weighted sum and the logit update), and
  per interest ``2 (1 + m_p) d h + 2 h d`` for the two layers.
"""
from __future__ import annotations

import re

import numpy as np

from bench import reference, traffic

KERNEL_EVENT = re.compile(r"mind_corpus_score")
NEG = -1e30


class Layout:
    """The arena (profile fields first, then the item field) and the
    tower's widths, from a configuration's model block."""

    def __init__(self, model: dict):
        self.v_prof = [int(v) for v in model["profile_vocab"]]
        self.n_items = int(model["item_vocab"])
        self.m_p = len(self.v_prof)
        self.d = int(model["embed_dim"])
        self.K = int(model["n_interests"])
        self.iters = int(model["capsule_iters"])
        self.L = int(model["seq_len"])
        self.hidden = int(model["hidden"])
        self.query_width = self.m_p + self.L
        starts = np.concatenate([[0], np.cumsum(self.v_prof)])
        self.off_prof = starts[:-1].astype(np.int64)
        self.off_item = int(starts[-1])
        self.total = self.off_item + self.n_items
        self.rows = -(-self.total // 2048) * 2048   # the arena's padded rows


def check(cfg, lay: Layout) -> None:
    """The program's configuration is the one the reference was given."""
    got = cfg.layout
    vocab = [f.vocab_size for f in got.fields]
    if (got.n_context, got.n_item, cfg.embed_dim, cfg.n_interests,
            cfg.capsule_iters, cfg.seq_len, cfg.hidden) != (
            lay.m_p, 1, lay.d, lay.K, lay.iters, lay.L, lay.hidden) or \
            vocab != lay.v_prof + [lay.n_items] or \
            int(got.field_offsets[lay.m_p]) != lay.off_item:
        raise ValueError("the model factory's configuration differs from "
                         "the configuration file's model block")


def draw(lay: Layout, seed: int):
    """Two params snapshots, drawn on the device in one jitted call: the
    served one and a refresh's (sharing the embedding arena).  Arena rows
    N(0, 1/d); S N(0, 1/d); routing logits N(0, 1); the two layers He
    normal with biases N(0, 0.01), so every term of the tower is live."""
    import jax
    import jax.numpy as jnp

    state = np.random.SeedSequence([int(seed), traffic.WEIGHTS])
    key = jax.random.PRNGKey(int(state.generate_state(1)[0]))
    d, x, h = lay.d, lay.d * (1 + lay.m_p), lay.hidden

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, 13)
        emb = jax.random.normal(ks[0], (lay.rows, d)) / np.sqrt(d)

        def snapshot(k):
            n = lambda i, shape: jax.random.normal(k[i], shape)  # noqa: E731
            return {
                "embedding": emb,
                "S": n(0, (d, d)) / np.sqrt(d),
                "b_init": n(1, (lay.K, lay.L)),
                "W1": n(2, (x, h)) * np.sqrt(2.0 / x),
                "b1": 0.1 * n(3, (h,)),
                "W2": n(4, (h, d)) * np.sqrt(2.0 / h),
                "b2": 0.1 * n(5, (d,)),
            }
        return snapshot(ks[1:7]), snapshot(ks[7:13])

    return draw(key)


def _ranks(n: int, g: np.random.Generator, a: float, size) -> np.ndarray:
    """0-based ranks of a power law of exponent ``a`` bounded to ``n``:
    rank ``floor(r) - 1`` of a continuous ``r`` on ``[1, n)`` with density
    ``~ r^-a`` (``r = n^u`` when ``a`` is 1)."""
    u = g.random(size)
    if a == 1.0:
        r = n ** u
    else:
        r = (1.0 + u * (n ** (1.0 - a) - 1.0)) ** (1.0 / (1.0 - a))
    return np.minimum(np.floor(r).astype(np.int64) - 1, n - 1)


def query_rows(model: dict, n: int, g: np.random.Generator,
               a: float) -> np.ndarray:
    """(n, m_p + L) int32 user rows: profile ids uniform per field, then
    a history of ``min(L, ceil(LogNormal(ln median, 1)))`` distinct item
    ids, drawn by rank from a power law of exponent ``a`` through a seeded
    permutation of the ids, and ``-1`` padding."""
    lay = Layout(model)
    prof = np.stack([g.integers(0, v, n) for v in lay.v_prof], axis=1)
    lengths = np.minimum(lay.L, np.ceil(g.lognormal(
        np.log(float(model["history_median"])), 1.0, n))).astype(np.int64)
    perm = g.permutation(lay.n_items)
    hist = np.full((n, lay.L), -1, np.int64)
    for q in range(n):
        got = np.empty(0, np.int64)
        while len(got) < lengths[q]:
            cand = np.concatenate([got, _ranks(lay.n_items, g, a,
                                               4 * lay.L)])
            _, first = np.unique(cand, return_index=True)
            got = cand[np.sort(first)]
        hist[q, :lengths[q]] = perm[got[:lengths[q]]]
    return np.concatenate([prof, hist], axis=1).astype(np.int32)


def item_rows(model: dict, n: int, g: np.random.Generator,
              a: float) -> np.ndarray:
    """(n, 1) int32 item records: the first ``n`` of a seeded permutation
    of the ids (the whole catalogue, each id once, when ``n`` is the
    vocabulary)."""
    return g.permutation(int(model["item_vocab"]))[:n].astype(
        np.int32).reshape(n, 1)


class Reference:
    """Host copies of one params snapshot, in float64 but the arena, and
    the scores they give."""

    def __init__(self, lay: Layout, snapshot: dict):
        self.emb = np.asarray(snapshot["embedding"])    # (rows, d) float32
        self.p = {k: np.asarray(v, np.float64) for k, v in snapshot.items()
                  if k != "embedding"}
        self.lay = lay

    @classmethod
    def of(cls, lay: Layout, snapshots) -> list:
        """One reference per snapshot of ``draw``, all on one host copy of
        the embedding arena, which every snapshot holds alike."""
        emb = np.asarray(snapshots[0]["embedding"])
        return [cls(lay, dict(s, embedding=emb)) for s in snapshots]

    def tower(self, queries: np.ndarray):
        """((C, K, d) interest vectors, the same on absolute values, (C,
        K) live interests) of (C, m_p + L) query rows."""
        lay, p = self.lay, self.p
        prof, hist = queries[:, :lay.m_p], queries[:, lay.m_p:]
        mask = hist >= 0
        e = self.emb[np.where(mask, hist, 0) + lay.off_item]
        e = e.astype(np.float64) * mask[..., None]          # (C, L, d)
        low = e @ p["S"]
        lowa = np.abs(e) @ np.abs(p["S"])
        n = mask.sum(1)
        k_live = np.maximum(sum((n >= (1 << j)).astype(np.int64)
                                for j in range(1, lay.K + 1)), 1)
        live = np.arange(lay.K)[None] < k_live[:, None]     # (C, K)
        open_ = mask[:, None, :] & live[:, :, None]         # (C, K, L)
        b = np.broadcast_to(p["b_init"], open_.shape).copy()
        for _ in range(lay.iters):
            logit = np.where(open_, b, NEG)
            w = np.exp(logit - logit.max(1, keepdims=True))
            w = w / w.sum(1, keepdims=True) * mask[:, None, :]
            v = np.einsum("ckl,cld->ckd", w, low)
            n2 = (v * v).sum(-1, keepdims=True)
            f = n2 / (1.0 + n2) / np.sqrt(n2 + 1e-9)
            caps, capsa = f * v, f * np.einsum("ckl,cld->ckd", w, lowa)
            b = b + np.einsum("ckd,cld->ckl", caps, low)
        pe = self.emb[prof + lay.off_prof].astype(np.float64)  # (C, m_p, d)
        pe = np.broadcast_to(pe.reshape(len(queries), 1, -1),
                             (*caps.shape[:2], lay.m_p * lay.d))
        x = np.concatenate([caps, pe], -1)
        xa = np.concatenate([capsa, np.abs(pe)], -1)
        u = np.maximum(np.maximum(x @ p["W1"] + p["b1"], 0.0) @ p["W2"]
                       + p["b2"], 0.0)
        ua = (xa @ np.abs(p["W1"]) + np.abs(p["b1"])) @ np.abs(p["W2"]) \
            + np.abs(p["b2"])
        return u, ua, live

    def scores(self, queries: np.ndarray, items: np.ndarray):
        """((queries, items) float64 scores, same-shape absolute sums)."""
        lay = self.lay
        u, ua, live = self.tower(queries)
        C, K, d = u.shape
        n = len(items)
        score, absum = np.empty((C, n)), np.empty((C, n))
        # interest rows k-major, so the max over k runs over K row blocks
        uk = np.where(live.T[..., None], u.transpose(1, 0, 2), np.nan)
        uk = uk.reshape(K * C, d)
        dead = np.isnan(uk[:, 0])
        uk[dead] = 0.0
        # |sum_d ua_d e_d| <= ||ua|| ||e||: the scale of the dot products
        scale = np.where(live, np.linalg.norm(ua, axis=-1), 0.0).max(1)

        def block(a, b):
            E = self.emb[items[a:b, 0].astype(np.int64)
                         + lay.off_item].astype(np.float64)
            s = uk @ E.T                                     # (K C, nb)
            s[dead] = -np.inf
            best = s[:C]
            for k in range(1, K):
                np.maximum(best, s[k * C:(k + 1) * C], out=best)
            score[:, a:b] = best
            absum[:, a:b] = scale[:, None] * np.linalg.norm(E, axis=1)[None]

        reference.blocked(n, block)
        return score, absum


def pair_flops(lay: Layout) -> int:
    return 2 * lay.K * lay.d + lay.K


def tower_flops(lay: Layout) -> int:
    d, K, L, h = lay.d, lay.K, lay.L, lay.hidden
    return (2 * L * d * d + lay.iters * 4 * K * L * d
            + K * (2 * (1 + lay.m_p) * d * h + 2 * h * d))


def request_flops(lay: Layout, n_items: int) -> int:
    """FLOPs of one request: its tower once, then every live item."""
    return n_items * pair_flops(lay) + tower_flops(lay)


def launch_flops(lay: Layout, n_items: int, rows: float) -> float:
    """Kernel FLOPs of one launch of ``rows`` query rows."""
    return rows * n_items * pair_flops(lay)


def launch_bytes(lay: Layout, capacity: int, rows: float, K: int) -> float:
    """HBM bytes one launch must move: the slab and its mask once, the
    interest vectors in and the top-K out."""
    return capacity * (lay.d + 1) * 4 + rows * lay.K * lay.d * 4 \
        + rows * K * 8
