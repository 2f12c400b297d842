"""The algorithm's work, counted from shapes, and the chip's peaks.

The counts are of what DPLR-FwFM corpus scoring needs, not of what an
implementation happens to do, so a rewrite of the kernel leaves them
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

Peaks are the published ones (``peaks.json``), keyed by
``device_kind``; a kind the table does not hold is an error.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(kind: str) -> dict:
    table = json.load(open(PEAKS_FILE))["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[kind]


def pair_flops(rho: int, k: int) -> int:
    return 3 * rho * k + 2 * rho + 5


def context_flops(m_c: int, rho: int, k: int) -> int:
    return 2 * rho * m_c * k + 3 * m_c * k + 2 * m_c


def request_flops(n_items: int, m_c: int, rho: int, k: int) -> int:
    """FLOPs of one request: its context once, then every live item."""
    return n_items * pair_flops(rho, k) + context_flops(m_c, rho, k)


def launch_bytes(capacity: int, rows: float, K: int, rho: int,
                 k: int) -> float:
    """HBM bytes one launch must move: the slab once, the context rows in
    and the top-K out."""
    slab = capacity * (rho * k + 2) * 4
    return slab + rows * (rho * k + 1) * 4 + rho * 4 + rows * K * 8


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """(least seconds, the bound that sets it: 'flops' or 'bytes')."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
