"""Traffic generation from a mix file and a seed (numpy only: the load
generator's child process imports this and never imports JAX).

Every draw comes from ``np.random.SeedSequence([seed, stream])`` with a
fixed stream number per purpose, so the parent (which needs the context
pool and the corpus for the reference) and the child (which sends the
requests) derive the same values independently.

Work is fixed across seeds: an open loop sends exactly ``rate x
seconds`` requests whose gaps are the quantiles of the exponential law
(a Poisson process's inter-arrival law) in an order drawn from the seed,
and its K values are one fixed multiset of the K law, shuffled.  A seed
changes which contexts are asked and in what order, not how much work
arrives.
"""
from __future__ import annotations

import numpy as np

# stream numbers: one per use of the seed
POOL, ITEMS, SCHEDULE, WARMUP, WRITER, SAMPLE, WEIGHTS = range(1, 8)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def vocabs(tiers) -> list[int]:
    """Per-field vocabulary sizes from ``[[count, vocab], ...]`` tiers."""
    return [int(v) for c, v in tiers for _ in range(int(c))]


def id_rows(tiers, n: int, g: np.random.Generator, a: float) -> np.ndarray:
    """(n, fields) int32 local ids: per field ``(zipf(a) - 1) % vocab``
    (the id law of the repo's chip smoke run: a heavy head, every id
    reachable)."""
    vs = vocabs(tiers)
    out = np.empty((n, len(vs)), np.int32)
    for j, v in enumerate(vs):
        out[:, j] = (g.zipf(a, n) - 1) % v
    return out


def popular(g: np.random.Generator, law: dict, n: int, size: int):
    """``size`` indices into ``n`` choices under a popularity law:
    ``{"law": "zipf", "a": a}`` (rank ``(zipf(a) - 1) % n``) or
    ``{"law": "uniform"}``."""
    if n == 1:
        return np.zeros(size, np.int64)
    if law["law"] == "zipf":
        return (g.zipf(float(law["a"]), size) - 1) % n
    if law["law"] == "uniform":
        return g.integers(0, n, size)
    raise ValueError(f"unknown popularity law {law!r}")


def k_values(g: np.random.Generator, law: dict, size: int) -> np.ndarray:
    """A fixed multiset of K values for ``size`` requests, shuffled:
    ``{"law": "fixed", "value": k}`` or ``{"law": "uniform", "low": a,
    "high": b}`` (each K in [a, b] equally often)."""
    if law["law"] == "fixed":
        return np.full(size, int(law["value"]), np.int64)
    if law["law"] == "uniform":
        ks = np.resize(np.arange(int(law["low"]), int(law["high"]) + 1), size)
        return g.permutation(ks)
    raise ValueError(f"unknown K law {law!r}")


def k_buckets(law: dict) -> list[int]:
    """Power-of-two K buckets a batch of requests under ``law`` can
    reach (a batch dispatches at ``next_pow2`` of its largest K)."""
    if law["law"] == "fixed":
        ks = [int(law["value"])]
    else:
        ks = range(int(law["low"]), int(law["high"]) + 1)
    return sorted({1 << max(k - 1, 0).bit_length() for k in ks})


def arrival_offsets(g: np.random.Generator, rate: float, seconds: float,
                    bursts: dict | None = None) -> np.ndarray:
    """Send offsets in [0, seconds) of an open loop: ``rate x seconds``
    arrivals whose gaps are the exponential law's quantiles, shuffled.
    ``bursts`` = ``{"block": b, "every": e, "factor": f}`` divides the
    gaps of every e-th block of b requests by f (load arriving f times
    as fast), then rescales so the mean rate stays ``rate``."""
    n = max(int(round(rate * seconds)), 1)
    u = (np.arange(n) + 0.5) / n
    gaps = g.permutation(-np.log1p(-u))
    if bursts:
        block = np.arange(n) // int(bursts["block"])
        gaps = np.where(block % int(bursts["every"]) == 0,
                        gaps / float(bursts["factor"]), gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / (t[-1] + gaps[-1]))


def open_schedule(mix: dict, seed: int, seconds: float, stream: int,
                  pool: int, tenants: int) -> dict:
    """The open loop's requests: ``t`` (send offset, s), ``ctx`` (pool
    index), ``k``, ``tenant`` (index), ``conn`` (connection)."""
    g = rng(seed, stream)
    t = arrival_offsets(g, float(mix["rate"]), seconds, mix.get("bursts"))
    n = len(t)
    return {
        "t": t,
        "ctx": popular(g, mix["context"], pool, n),
        "k": k_values(g, mix["k"], n),
        "tenant": popular(g, mix.get("tenant", {"law": "uniform"}),
                          tenants, n),
        "conn": np.arange(n) % int(mix["connections"]),
    }


class ClientStream:
    """One closed-loop client's endless request sequence (seed stream
    ``stream``): ``next()`` gives ``(ctx, k, tenant)``."""

    def __init__(self, mix: dict, seed: int, stream: int, pool: int,
                 tenants: int):
        self._g = rng(seed, stream)
        self._mix, self._pool, self._tenants = mix, pool, tenants
        self._buf: list = []

    def next(self):
        if not self._buf:
            n = 256
            self._buf = list(zip(
                popular(self._g, self._mix["context"], self._pool, n),
                k_values(self._g, self._mix["k"], n),
                popular(self._g, self._mix.get("tenant", {"law": "uniform"}),
                        self._tenants, n)))[::-1]
        return self._buf.pop()


def writer_times(writer: dict, seconds: float) -> np.ndarray:
    """Call offsets of the catalogue writer: evenly spaced at its rate."""
    n = int(round(float(writer["rate"]) * seconds))
    return np.arange(n) / float(writer["rate"])
