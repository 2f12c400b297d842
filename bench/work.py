"""The chip's peaks, and the least time a piece of work can take on it.

What a model's work is (the FLOPs of a request, the FLOPs and bytes of a
launch) is counted from shapes in the model's module (``bench/models``);
this file holds what every model shares.  Peaks are the published ones
(``peaks.json``), keyed by ``device_kind``; a kind the table does not
hold is an error.
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


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """(least seconds, the bound that sets it: 'flops' or 'bytes')."""
    tf = flops / peak["bf16_flops_per_s"]
    tb = nbytes / peak["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
