"""What the plain reference of every model shares: the catalogue's
history, the verdict on one reply, and the blocking of a large catalogue.

A model's own float64 reference lives in its module (``bench/models``),
which, like this file, imports nothing of the program and takes nothing
the program made: the layout comes from the configuration file, the
weights are the ones the benchmark drew from the seed, and the item in
each slot is what the benchmark wrote there.  ``Catalogue`` says which
item sat in each slot at each version; ``judge`` compares one served
reply with the reference's scores at one version.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 15
THREADS = 4


def blocked(n: int, block) -> None:
    """Call ``block(a, b)`` over ``[0, n)`` in blocks of ``BLOCK`` rows on
    ``THREADS`` threads, so a catalogue of a million items fits in host
    memory; each call writes its own rows of the caller's arrays."""
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(lambda a: block(a, min(a + BLOCK, n)),
                      range(0, n, BLOCK)))


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
