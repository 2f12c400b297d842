"""DPLR-FwFM for the benchmark: its layout, weights, ids, float64
reference, work counts and kernel name.

A configuration names this module by ``"bench_model": "dplr_fwfm"`` in
its ``model`` block; the harness knows no model and calls only what is
defined here.  Like ``reference.py``, it imports nothing of the program:
the layout comes from the configuration file's vocabulary tiers, the
weights are drawn here from the seed, and the reference is given the
ids the benchmark wrote.

The model (the paper's Proposition 1 with
R = U^T diag(e) U - diag(U^T diag(e) U)):

    score = bias + lin_C + lin_I + 0.5 (s_C + t_I + sum_r e_r |P_r + Q_r|^2)
    P = U_C V_C,  Q = U_I V_I,  s = sum_f d_f |v_f|^2,  d = -diag(U^T e U)

with ``v_f`` the arena row ``offset(f) + id`` and every feature weight 1.
The reference expands ``|P_r + Q_r|^2`` to ``|P_r|^2 + 2 P_r.Q_r +
|Q_r|^2`` so a batch of contexts meets the whole catalogue in one float64
matrix product; in float64 the expansion's rounding is some 1e-16 of the
terms, far below the float32 program's.  Each score comes with
``absum``: the same expression over the absolute value of every term,
the scale at which float32 rounding of this computation lives.

The work counts are of what DPLR-FwFM corpus scoring needs, not of what
an implementation happens to do, so a rewrite of the kernel leaves them
alone:

* a launch reads the live slab once: per slot the rank-space projection
  Q_I (rho x k f32), the scalar addend a_I (f32) and the validity mask
  (one 32-bit word), plus the context rows (rho x k + 1 f32 per query
  row), the eigen-weights and the (rows x K) f32 + i32 outputs;
* a served (query, item) pair needs ``3 rho k + 2 rho + 5`` FLOPs: the
  rho x k adds of P_C + Q_I, the rho x k squares, the rho x k sums over
  k, the rho weightings and rho sums over the ranks, and the five scalar
  operations of ``a_C + a_I + 0.5 x (.)`` with the mask;
* each request needs its context projection once: for m_C context
  fields, ``2 rho m_C k`` FLOPs for ``U_C V_C``, ``3 m_C k`` for the
  squared norms and their weighting by ``d``, and ``2 m_C`` for the
  first-order term.
"""
from __future__ import annotations

import re

import numpy as np

from bench import reference, traffic

# what the kernel's events are called in a device trace (the custom call
# is named after the jitted wrapper, the body after its function)
KERNEL_EVENT = re.compile(r"dplr_corpus_score|_kernel_topk")


class Layout:
    """Field vocabularies and arena offsets from a configuration's model
    block (context fields first, then item fields)."""

    def __init__(self, model: dict):
        self.v_ctx = traffic.vocabs(model["context_tiers"])
        self.v_item = traffic.vocabs(model["item_tiers"])
        self.m_ctx, self.m_item = len(self.v_ctx), len(self.v_item)
        self.query_width = self.m_ctx
        self.k = int(model["embed_dim"])
        self.rank = int(model["rank"])
        starts = np.concatenate([[0], np.cumsum(self.v_ctx + self.v_item)])
        self.off_ctx = starts[:self.m_ctx].astype(np.int64)
        self.off_item = starts[self.m_ctx:-1].astype(np.int64)
        self.total = int(starts[-1])
        self.rows = -(-self.total // 2048) * 2048   # the arena's padded rows


def check(cfg, lay: Layout) -> None:
    """The program's layout is the one the reference was given."""
    got = cfg.layout
    vocab = [f.vocab_size for f in got.fields]
    offsets = np.asarray(got.field_offsets)
    want = np.concatenate([lay.off_ctx, lay.off_item])
    if (got.n_context, got.n_item, cfg.embed_dim, cfg.rank) != (
            lay.m_ctx, lay.m_item, lay.k, lay.rank) or \
            vocab != lay.v_ctx + lay.v_item or \
            not np.array_equal(offsets, want):
        raise ValueError("the model factory's layout differs from the "
                         "configuration file's tiers")


def draw(lay: Layout, seed: int):
    """Two params snapshots of the model, drawn on the device in one
    jitted call: the served one and the one a mid-window refresh
    installs (sharing the embedding arena).  The laws follow the model's
    ``fwfm.init`` (embeddings N(0, 1/k), U = noise/sqrt(m) + 1 on rank
    0), with non-zero first-order weights and eigen-weights so every
    term of the score is exercised."""
    import jax
    import jax.numpy as jnp

    m = lay.m_ctx + lay.m_item
    state = np.random.SeedSequence([int(seed), traffic.WEIGHTS])
    key = jax.random.PRNGKey(int(state.generate_state(1)[0]))

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, 7)
        emb = jax.random.normal(ks[0], (lay.rows, lay.k)) / np.sqrt(lay.k)

        def snapshot(k_lin, k_u, k_e, bias, e_spread):
            U = jax.random.normal(k_u, (lay.rank, m)) / np.sqrt(m)
            return {
                "bias": jnp.asarray(bias, jnp.float32),
                "linear": 0.1 * jax.random.normal(k_lin, (lay.rows,)),
                "embedding": emb,
                "U": U.at[0].add(1.0),
                "e": 1.0 + e_spread * jax.random.uniform(
                    k_e, (lay.rank,), minval=-1.0, maxval=1.0),
            }
        return (snapshot(ks[1], ks[2], ks[3], 0.0, 0.0),
                snapshot(ks[4], ks[5], ks[6], 0.5, 0.5))

    return draw(key)


def query_rows(model: dict, n: int, g: np.random.Generator,
               a: float) -> np.ndarray:
    """(n, context fields) int32 context ids."""
    return traffic.id_rows(model["context_tiers"], n, g, a)


def item_rows(model: dict, n: int, g: np.random.Generator,
              a: float) -> np.ndarray:
    """(n, item fields) int32 item records."""
    return traffic.id_rows(model["item_tiers"], n, g, a)


class Side:
    """float64 one-side terms of rows of ids: projection ``P`` (n, rank,
    k), its absolute version, ``s`` (the d-term), ``lin``, and their
    absolute versions."""

    __slots__ = ("P", "Pa", "s", "sa", "lin", "lina")

    def __init__(self, P, Pa, s, sa, lin, lina):
        self.P, self.Pa, self.s, self.sa = P, Pa, s, sa
        self.lin, self.lina = lin, lina


class Reference:
    """Host copies of one params snapshot, in float64 where small, and
    the corpus scores they give."""

    def __init__(self, lay: Layout, snapshot: dict):
        self.emb = np.asarray(snapshot["embedding"])    # (rows, k) float32
        self.linear = np.asarray(snapshot["linear"], np.float64)
        self.U = np.asarray(snapshot["U"], np.float64)
        self.e = np.asarray(snapshot["e"], np.float64)
        self.bias = float(np.asarray(snapshot["bias"]))
        self.d = -np.einsum("r,rm,rm->m", self.e, self.U, self.U)
        self.lay = lay

    @classmethod
    def of(cls, lay: Layout, snapshots) -> list:
        """One reference per snapshot of ``draw``, all on one host copy of
        the embedding arena, which every snapshot holds alike."""
        emb = np.asarray(snapshots[0]["embedding"])
        return [cls(lay, dict(s, embedding=emb)) for s in snapshots]

    def side(self, ids: np.ndarray, item: bool) -> Side:
        lay = self.lay
        off = lay.off_item if item else lay.off_ctx
        cols = slice(lay.m_ctx, None) if item else slice(0, lay.m_ctx)
        U, d = self.U[:, cols], self.d[cols]
        n = len(ids)
        P = np.empty((n, lay.rank, lay.k))
        Pa = np.empty_like(P)
        s, sa, lin, lina = (np.empty(n) for _ in range(4))
        Ua = np.abs(U).astype(np.float32)
        m, k = U.shape[1], lay.k

        def block(a, b):
            rows = (ids[a:b].astype(np.int64) + off).T          # (m, nb)
            nb = rows.shape[1]
            Vf = self.emb[rows].reshape(m, nb * k)              # float32
            V = Vf.astype(np.float64)
            # sum_m U_rm v_bmk as one (rank, m) x (m, b k) product; the
            # absolute version only scales errors, so float32 serves it
            P[a:b] = (U @ V).reshape(lay.rank, nb, k).transpose(1, 0, 2)
            Pa[a:b] = (Ua @ np.abs(Vf)).reshape(
                lay.rank, nb, k).transpose(1, 0, 2)
            V = V.reshape(m, nb, k)
            sq = np.einsum("mbk,mbk->mb", V, V)
            s[a:b] = d @ sq
            sa[a:b] = np.abs(d) @ sq
            w = self.linear[rows]
            lin[a:b] = w.sum(0)
            lina[a:b] = np.abs(w).sum(0)

        reference.blocked(n, block)
        return Side(P, Pa, s, sa, lin, lina)

    def scores(self, queries: np.ndarray, items: np.ndarray):
        """((queries, items) float64 scores, same-shape absolute sums)."""
        ctx = self.side(queries, item=False)
        item = self.side(items, item=True)
        e, ea = self.e, np.abs(self.e)
        n, rho, k = item.P.shape

        def pair(P, Q, w):
            # sum_r w_r |P_r + Q_r|^2, expanded, for every (context, item)
            pp = np.einsum("crk,r->c", P * P, w)
            qq = np.einsum("nrk,r->n", Q * Q, w)
            cross = (P * w[None, :, None]).reshape(len(P), rho * k) @ \
                Q.reshape(n, rho * k).T
            return pp[:, None] + 2.0 * cross + qq[None, :]

        score = (self.bias + ctx.lin[:, None] + item.lin[None, :]
                 + 0.5 * (ctx.s[:, None] + item.s[None, :]
                          + pair(ctx.P, item.P, e)))
        absum = (abs(self.bias) + ctx.lina[:, None] + item.lina[None, :]
                 + 0.5 * (ctx.sa[:, None] + item.sa[None, :]
                          + pair(ctx.Pa, item.Pa, ea)))
        return score, absum


def pair_flops(rho: int, k: int) -> int:
    return 3 * rho * k + 2 * rho + 5


def context_flops(m_c: int, rho: int, k: int) -> int:
    return 2 * rho * m_c * k + 3 * m_c * k + 2 * m_c


def request_flops(lay: Layout, n_items: int) -> int:
    """FLOPs of one request: its context once, then every live item."""
    return n_items * pair_flops(lay.rank, lay.k) + context_flops(
        lay.m_ctx, lay.rank, lay.k)


def launch_flops(lay: Layout, n_items: int, rows: float) -> float:
    """Kernel FLOPs of one launch of ``rows`` query rows."""
    return rows * n_items * pair_flops(lay.rank, lay.k)


def launch_bytes(lay: Layout, capacity: int, rows: float, K: int) -> float:
    """HBM bytes one launch must move: the slab once, the context rows in
    and the top-K out."""
    rho, k = lay.rank, lay.k
    slab = capacity * (rho * k + 2) * 4
    return slab + rows * (rho * k + 1) * 4 + rho * 4 + rows * K * 8
