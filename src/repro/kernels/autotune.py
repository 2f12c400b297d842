"""Shape-cell autotuner for ``dplr_corpus_score`` (+ the hardware table).

The corpus scorer historically ran one hand-picked tile
(``blocks.CORPUS_TILE_N``) and f32 accumulation for every shape and
dtype.  This module sweeps the tile size — and bf16 score accumulation
where the slab dtype already is bf16 — per ``(n, rho, k, Bq, K, dtype,
backend)`` cell, gates EVERY candidate on the ref oracles (a faster
wrong kernel never wins), and registers the winner in
``blocks.register_tuned_tile`` so every call site that leaves
``block_n=None`` (runtime, sharded bodies, fused multi-segment path)
inherits it with zero retraces — provided tuning runs BEFORE warmup,
because the registry is consulted when the calling jit traces.

Parity gates (per candidate, never sampled):

  * f32 accumulation — indices EXACTLY equal to ``dplr_corpus_topk_ref``
    and values allclose at f32 epsilon: the tile size must be
    numerically invisible.
  * bf16 accumulation — the returned indices must select items whose
    REF scores are within ``bf16_tol`` of the ref top-K values (rank
    displacement is allowed only between near-ties the tolerance
    covers); returned values must match the ref scores of the returned
    items within the same tolerance.

Clamp visibility: candidates larger than ``n`` are clamped by
``blocks.clamp_tile``; the events are drained per candidate and carried
on the result so benchmarks report requested-vs-effective divergence
instead of hiding it (the "no silent caps" rule).

``HW_PROFILES`` is the single source of per-chip peak numbers, keyed by
``jax.devices()[0].device_kind`` — ``benchmarks/roofline.py`` reads its
peaks from here and the autotuner uses the same profile to report each
winner's distance from the memory roofline.  ``hw_profile`` raises for a
device kind the table does not hold: an unknown chip gets no borrowed
peaks.

In-process results cache per cell; ``save_cache``/``load_cache``
round-trip the registry through a small JSON file so a warm process can
skip the sweep entirely.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass

import numpy as np

from repro.kernels import blocks

# Per-chip peaks keyed by ``device_kind``: bf16 FLOP/s, HBM bytes/s and
# chip-to-chip interconnect bytes/s.  Source: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI.  Add a
# chip here, with its source, before measuring on it.
HW_PROFILES: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9,
                    "ici_bw": 1600e9 / 8},
}


def hw_profile(kind: str | None = None) -> dict[str, float]:
    """Peaks of ``kind`` (default: this process's first device).  A
    device kind the table does not hold raises — no default profile."""
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    if kind not in HW_PROFILES:
        raise ValueError(f"no peak rates for device kind {kind!r}; "
                         f"have {sorted(HW_PROFILES)}")
    return HW_PROFILES[kind]


# Default tile sweep: the named default plus its pow2 neighbours.  Cells
# smaller than a candidate clamp (visibly — see clamp events).
DEFAULT_CANDIDATES = (512, 1024, 2048, 4096, 8192)


@dataclass(frozen=True)
class CandidateResult:
    """One swept (block_n, acc_dtype) configuration of a cell."""
    block_n: int                # requested tile
    effective_block_n: int      # after clamp_tile (== block_n when n >= tile)
    acc_dtype: str
    us: float                   # best-of-repeats wall time, microseconds
    parity_ok: bool
    parity_error: str | None = None
    clamps: tuple = ()          # drained blocks.drain_clamp_events dicts


@dataclass(frozen=True)
class TunedTile:
    """A cell's sweep outcome: the parity-gated winner vs the default."""
    cell: tuple                 # blocks.tile_cell key
    block_n: int
    acc_dtype: str
    us: float                   # winner's time
    default_us: float           # CORPUS_TILE_N/f32 time on the same cell
    swept: tuple = ()           # every CandidateResult, winners and losers
    device_kind: str = ""       # the device the sweep ran on
    bytes_per_call: int = 0     # slab + output traffic, for roofline frac

    @property
    def speedup(self) -> float:
        return self.default_us / self.us if self.us > 0 else 1.0

    @property
    def roofline_frac(self) -> float:
        """Fraction of the HBM roofline of the device the sweep ran on
        (raises where that device has no profile, e.g. the CPU)."""
        bw = hw_profile(self.device_kind)["hbm_bw"]
        ideal_us = self.bytes_per_call / bw * 1e6
        return ideal_us / self.us if self.us > 0 else 0.0


# in-process memo: tile_cell -> TunedTile (sweeps are not free; a warmup
# that touches the same cell twice must pay once)
_RESULTS: dict[tuple, TunedTile] = {}


def clear_results() -> None:
    """Drop the in-process sweep memo (tests / benchmark hygiene)."""
    _RESULTS.clear()


def _mk_inputs(n, rho, k, Bq, dtype, seed):
    r = np.random.default_rng(seed)
    Q = r.normal(size=(n, rho, k)).astype(dtype)
    a = r.normal(size=(n,)).astype(np.float32)
    e = r.normal(size=(rho,)).astype(np.float32)
    P = r.normal(size=(Bq, rho, k)).astype(dtype)
    aC = r.normal(size=(Bq,)).astype(np.float32)
    valid = (r.random(n) > 0.1)
    valid[: max(1, n // 8)] = True      # K live items guaranteed
    return Q, a, e, P, aC, valid


def _check_parity(vals, idx, ref_scores, ref_vals, acc_dtype, ref_idx,
                  bf16_tol):
    """Gate one candidate's output against the oracle.  Returns an error
    string (None = pass)."""
    vals = np.asarray(vals)
    idx = np.asarray(idx)
    if acc_dtype == "float32":
        if not np.array_equal(idx, ref_idx):
            return "f32 indices diverge from dplr_corpus_topk_ref"
        if not np.allclose(vals, ref_vals, rtol=1e-5, atol=1e-5):
            return "f32 values beyond epsilon of dplr_corpus_topk_ref"
        return None
    # bf16 accumulation: judge the returned ITEMS by their ref scores
    got = np.take_along_axis(ref_scores, idx, axis=1)
    if not np.allclose(got, ref_vals, rtol=0, atol=bf16_tol):
        return "bf16 indices select items outside tolerance of ref top-K"
    if not np.allclose(vals, got, rtol=0, atol=bf16_tol):
        return "bf16 values beyond tolerance of the selected items' ref"
    return None


def _time_call(fn, repeats: int) -> float:
    """Best-of-repeats microseconds; each call blocks on the result."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        for leaf in out if isinstance(out, tuple) else (out,):
            leaf.block_until_ready()
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def tune_corpus_score(n: int, rho: int, k: int, Bq: int, K: int, *,
                      dtype: str = "float32",
                      candidates=DEFAULT_CANDIDATES,
                      sweep_bf16_acc: bool | None = None,
                      bf16_tol: float = 5e-2,
                      repeats: int = 3, seed: int = 0,
                      register: bool = True,
                      interpret: bool | None = None) -> TunedTile:
    """Sweep ``dplr_corpus_score`` tiles for one shape cell and return
    the parity-gated winner (registered into ``blocks`` unless
    ``register=False``).

    ``sweep_bf16_acc=None`` (default) sweeps bf16 accumulation exactly
    when the slab ``dtype`` is bfloat16 — a f32 slab never trades
    accumulation precision.  Every swept configuration is oracle-gated;
    a candidate that fails parity is recorded (``parity_ok=False``) and
    excluded from the podium no matter how fast it ran.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.ref import dplr_corpus_score_ref, dplr_corpus_topk_ref

    # the cell's backend key MUST be what ops._resolve_tile uses at the
    # real call sites, or registered winners would never be looked up
    cell = blocks.tile_cell(n, rho, k, Bq, K, dtype, jax.default_backend())
    hit = _RESULTS.get(cell)
    if hit is not None:
        if register:
            blocks.register_tuned_tile(cell, hit.block_n, hit.acc_dtype)
        return hit

    Q, a, e, P, aC, valid = _mk_inputs(n, rho, k, Bq, dtype, seed)
    ref_scores = np.asarray(dplr_corpus_score_ref(
        jnp.asarray(Q, jnp.float32), a, e,
        jnp.asarray(P, jnp.float32), aC, valid))
    rv, ri = dplr_corpus_topk_ref(
        jnp.asarray(Q, jnp.float32), a, e,
        jnp.asarray(P, jnp.float32), aC, K, valid)
    ref_vals, ref_idx = np.asarray(rv), np.asarray(ri)

    if sweep_bf16_acc is None:
        sweep_bf16_acc = jnp.dtype(dtype) == jnp.bfloat16
    accs = ("float32", "bfloat16") if sweep_bf16_acc else ("float32",)

    # the sweep stays inside the scoped-VMEM rule on every backend: a
    # block_n=None call caps its tile by that rule (ops._resolve_tile),
    # so a larger winner would be registered and never run
    default_bn = blocks.corpus_vmem_tile(blocks.CORPUS_TILE_N, Bq)
    candidates = [bn for bn in candidates
                  if bn == blocks.corpus_vmem_tile(bn, Bq)]
    sweep = dict.fromkeys(candidates)       # ordered, deduped
    sweep[default_bn] = None                # the default always competes
    results: list[CandidateResult] = []
    for bn in sweep:
        for acc in accs:
            blocks.drain_clamp_events()     # isolate this candidate's
            call = lambda: ops.dplr_corpus_score(    # noqa: E731
                Q, a, e, P, aC, valid=valid, topk=K, block_n=bn,
                interpret=interpret, acc_dtype=acc)
            vals, idx = call()
            clamps = tuple(blocks.drain_clamp_events())
            err = _check_parity(vals, idx, ref_scores, ref_vals, acc,
                                ref_idx, bf16_tol)
            us = _time_call(call, repeats) if err is None else float("inf")
            results.append(CandidateResult(
                block_n=bn, effective_block_n=min(bn, n), acc_dtype=acc,
                us=us, parity_ok=err is None, parity_error=err,
                clamps=clamps))

    passing = [r for r in results if r.parity_ok]
    if not passing:
        raise RuntimeError(
            f"autotune cell {cell}: no candidate passed the parity gate")
    winner = min(passing, key=lambda r: r.us)
    default_us = min(r.us for r in passing
                     if r.block_n == default_bn
                     and r.acc_dtype == "float32")
    itemsize = jnp.dtype(dtype).itemsize
    slab_bytes = n * rho * k * itemsize + n * (itemsize + 1)
    out_bytes = Bq * K * 8 + Bq * rho * k * itemsize
    tuned = TunedTile(cell=cell, block_n=winner.block_n,
                      acc_dtype=winner.acc_dtype, us=winner.us,
                      default_us=default_us, swept=tuple(results),
                      device_kind=jax.devices()[0].device_kind,
                      bytes_per_call=slab_bytes + out_bytes)
    _RESULTS[cell] = tuned
    if register:
        blocks.register_tuned_tile(cell, tuned.block_n, tuned.acc_dtype)
    return tuned


# -- optional on-disk registry cache ----------------------------------------

def save_cache(path) -> int:
    """Write every in-process sweep winner to ``path`` (JSON).  Returns
    the number of cells written."""
    payload = {json.dumps(t.cell): {"block_n": t.block_n,
                                    "acc_dtype": t.acc_dtype,
                                    "us": t.us,
                                    "default_us": t.default_us}
               for t in _RESULTS.values()}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    return len(payload)


def load_cache(path, *, register: bool = True) -> int:
    """Re-register winners from a ``save_cache`` file (a warm process
    skips the sweep).  Returns the number of cells loaded; silently 0
    when the file does not exist — a cold cache is not an error."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except FileNotFoundError:
        return 0
    for cell_s, rec in payload.items():
        cell = tuple(json.loads(cell_s))
        if register:
            blocks.register_tuned_tile(cell, int(rec["block_n"]),
                                       str(rec["acc_dtype"]))
    return len(payload)
