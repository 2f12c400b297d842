"""The plain reference: DPLR-FwFM corpus scores in float64 NumPy, and the
comparison of served replies with it.

It imports nothing of the program and takes nothing the program made:
the layout comes from the configuration file's vocabulary tiers, the
weights are the ones the benchmark drew from the seed, and the item in
each slot is what the benchmark wrote there.  The model (the paper's
Proposition 1 with R = U^T diag(e) U - diag(U^T diag(e) U)):

    score = bias + lin_C + lin_I + 0.5 (s_C + t_I + sum_r e_r |P_r + Q_r|^2)
    P = U_C V_C,  Q = U_I V_I,  s = sum_f d_f |v_f|^2,  d = -diag(U^T e U)

with ``v_f`` the arena row ``offset(f) + id`` and every feature weight 1.
``|P_r + Q_r|^2`` is expanded to ``|P_r|^2 + 2 P_r.Q_r + |Q_r|^2`` so a
batch of contexts meets the whole catalogue in one float64 matrix
product; in float64 the expansion's rounding is some 1e-16 of the terms,
far below the float32 program's.  The item side is computed in blocks of
rows, so a catalogue of a million items fits in host memory.

Each score comes with ``absum``: the same expression over the absolute
value of every term.  Errors are reported as a share of it — the scale
at which float32 rounding of this computation lives.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import traffic

BLOCK = 1 << 15
THREADS = 4


class Layout:
    """Field vocabularies and arena offsets from a configuration's model
    block (context fields first, then item fields)."""

    def __init__(self, model: dict):
        self.v_ctx = traffic.vocabs(model["context_tiers"])
        self.v_item = traffic.vocabs(model["item_tiers"])
        self.m_ctx, self.m_item = len(self.v_ctx), len(self.v_item)
        self.k = int(model["embed_dim"])
        self.rank = int(model["rank"])
        starts = np.concatenate([[0], np.cumsum(self.v_ctx + self.v_item)])
        self.off_ctx = starts[:self.m_ctx].astype(np.int64)
        self.off_item = starts[self.m_ctx:-1].astype(np.int64)
        self.total = int(starts[-1])
        self.rows = -(-self.total // 2048) * 2048   # the arena's padded rows


class Side:
    """float64 one-side terms of rows of ids: projection ``P`` (n, rank,
    k), its absolute version, ``s`` (the d-term), ``lin``, and their
    absolute versions."""

    __slots__ = ("P", "Pa", "s", "sa", "lin", "lina")

    def __init__(self, P, Pa, s, sa, lin, lina):
        self.P, self.Pa, self.s, self.sa = P, Pa, s, sa
        self.lin, self.lina = lin, lina


class Weights:
    """Host copies of one params snapshot, in float64 where small."""

    def __init__(self, lay: Layout, emb, linear, U, e, bias):
        self.emb = emb                              # (rows, k) float32
        self.linear = np.asarray(linear, np.float64)
        self.U = np.asarray(U, np.float64)
        self.e = np.asarray(e, np.float64)
        self.bias = float(bias)
        self.d = -np.einsum("r,rm,rm->m", self.e, self.U, self.U)
        self.lay = lay

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

        def block(a):
            rows = (ids[a:a + BLOCK].astype(np.int64) + off).T   # (m, b)
            b = rows.shape[1]
            Vf = self.emb[rows].reshape(m, b * k)               # float32
            V = Vf.astype(np.float64)
            # sum_m U_rm v_bmk as one (rank, m) x (m, b k) product; the
            # absolute version only scales errors, so float32 serves it
            P[a:a + BLOCK] = (U @ V).reshape(lay.rank, b, k).transpose(
                1, 0, 2)
            Pa[a:a + BLOCK] = (Ua @ np.abs(Vf)).reshape(
                lay.rank, b, k).transpose(1, 0, 2)
            V = V.reshape(m, b, k)
            sq = np.einsum("mbk,mbk->mb", V, V)
            s[a:a + BLOCK] = d @ sq
            sa[a:a + BLOCK] = np.abs(d) @ sq
            w = self.linear[rows]
            lin[a:a + BLOCK] = w.sum(0)
            lina[a:a + BLOCK] = np.abs(w).sum(0)

        with ThreadPoolExecutor(THREADS) as pool:
            list(pool.map(block, range(0, n, BLOCK)))
        return Side(P, Pa, s, sa, lin, lina)

    def scores(self, ctx: Side, item: Side):
        """((contexts, items) scores, same-shape absolute sums)."""
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


class Catalogue:
    """One tenant's slab over time: which item record sits in each slot
    at each version.  Version 0 is the state when the window opens; each
    write or refresh in the window makes the next version, live from
    somewhere inside its call to its return."""

    def __init__(self, ids: np.ndarray, capacity: int):
        self.records = [np.asarray(ids, np.int32)]
        self.n_records = len(ids)
        slot = np.full(capacity, -1, np.int64)
        slot[:len(ids)] = np.arange(len(ids))
        self.states = [slot]                 # slot -> record, per version
        self.params = [0]                    # params snapshot, per version
        self.calls: list[tuple[float, float]] = []   # per version >= 1

    def _next(self, t_call, t_return, slot, params):
        self.states.append(slot)
        self.params.append(params)
        self.calls.append((t_call, t_return))

    def remove(self, t_call, t_return, slots) -> None:
        slot = self.states[-1].copy()
        slot[np.asarray(slots)] = -1
        self._next(t_call, t_return, slot, self.params[-1])

    def write(self, t_call, t_return, slots, ids) -> None:
        """``ids`` now sit in ``slots`` (an add or an update)."""
        ids = np.atleast_2d(np.asarray(ids, np.int32))
        slot = self.states[-1].copy()
        slot[np.asarray(slots)] = self.n_records + np.arange(len(ids))
        self.records.append(ids)
        self.n_records += len(ids)
        self._next(t_call, t_return, slot, self.params[-1])

    def refresh(self, t_call, t_return, params: int) -> None:
        self._next(t_call, t_return, self.states[-1].copy(), params)

    def rebase(self) -> None:
        """Forget the history: the current state becomes version 0."""
        self.states, self.params = [self.states[-1]], [self.params[-1]]
        self.calls = []

    def all_records(self) -> np.ndarray:
        return np.concatenate(self.records)

    def candidates(self, t_send: float, t_recv: float) -> range:
        """Versions that may have served a request sent at ``t_send`` and
        answered at ``t_recv``: from the last one whose call returned
        before the send to the last one whose call began before the
        reply."""
        lo = sum(1 for _, r in self.calls if r < t_send)
        hi = sum(1 for c, _ in self.calls if c < t_recv)
        return range(lo, max(hi, lo) + 1)


def judge(scores, slots, K, ref, absum, live):
    """One reply at one version: ``(score_err, topk_short, dead, bad)``.

    ``score_err``: the largest gap between a served score and the
    reference's score of its slot, as a share of that score's absolute
    sum.  ``topk_short``: how far the weakest served slot lies below the
    reference's K-th best live score, as a share of the larger absolute
    sum of the two (0 when the served set is the true top-K up to
    rounding).  ``dead``: served slots not live.  ``bad``: 1 if the row
    has the wrong length, repeats a slot or is not sorted best-first."""
    bad = int(len(slots) != K or len(set(slots.tolist())) != len(slots)
              or bool(np.any(np.diff(scores) > 0)))
    ok = (slots >= 0) & (slots < len(live))
    ok[ok] = live[slots[ok]]
    dead = int((~ok).sum())
    s = slots[ok]
    if len(s) == 0:
        return np.inf, np.inf, dead, bad
    err = float(np.max(np.abs(scores[ok] - ref[s]) / absum[s]))
    masked = np.where(live, ref, -np.inf)
    kth_slot = np.argpartition(-masked, K - 1)[K - 1]
    weakest = s[np.argmin(ref[s])]
    gap = masked[kth_slot] - ref[weakest]
    short = max(0.0, float(gap / max(absum[kth_slot], absum[weakest])))
    return err, short, dead, bad
