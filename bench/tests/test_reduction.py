"""The reduction from a device trace to the per-layer metrics, on a
hand-made trace and on an extract recorded on a TPU v5e, and the
arithmetic of the work counts and metric readers."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import devtrace, harness, work
from bench.harness import Run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000
DPLR = harness.model_module("dplr_fwfm")


def _run(trace=None, **kw):
    base = dict(seconds=2.0, setup_s=10.0, latency_ms=np.array([1.0, 3.0]),
                replies_in_window=100, write_ms=np.array([]),
                frontend={"completed": 40, "dispatches": 10},
                request_flops=1e9, launch_flops=4e9, launch_bytes=8.19e8,
                peak=work.peaks("TPU v5 lite"), trace=trace)
    base.update(kw)
    return Run(**base)


def test_hand_made_trace():
    # window [0, 10 ms]; ops at [1, 3] and [2, 4] overlap, [6, 7] is a
    # kernel, [9, 12] runs past the close; busy = 3 + 1 + 1 = 5 ms
    trace = {"window": (0, 10 * MS),
             "device": {"/device:TPU:0": [
                 ("fusion.1", 1 * MS, 2 * MS), ("fusion.2", 2 * MS, 2 * MS),
                 ("_kernel_topk", 6 * MS, 1 * MS), ("copy.3", 9 * MS, 3 * MS),
                 ("early", -5 * MS, 1 * MS)]},
             "host": [("python", "PjitFunction(f)", 4 * MS, 2 * MS)]}
    got = devtrace.reduce(trace, DPLR.KERNEL_EVENT)
    assert got["window_s"] == pytest.approx(0.010)
    assert got["busy_s"] == pytest.approx(0.005)
    assert got["kernel_s"] == pytest.approx(0.001)
    assert got["kernel_launches"] == 1
    assert got["device_ops"][0] == ["fusion", pytest.approx(0.004)]
    assert got["idle_gaps"][0] == [
        "python: PjitFunction(f) (100% of the gap)", pytest.approx(0.002)]
    # idle: [0, 1], [4, 6], [7, 9] ms
    assert sorted(s for _, s in got["idle_gaps"]) == pytest.approx(
        [0.001, 0.002, 0.002])
    run = _run(trace=got)
    assert harness.reader("device_idle.auction")(run) == pytest.approx(50.0)
    # one launch of 8.19e8 bytes at 819 GB/s is 1 ms, the kernel took 1 ms
    assert harness.reader("kernel_roofline.auction")(run) == pytest.approx(
        100.0)


def test_work_counts_and_readers():
    rho, k = 3, 16
    assert DPLR.pair_flops(rho, k) == 155
    assert DPLR.context_flops(44, rho, k) == 2 * 3 * 44 * 16 + 3 * 44 * 16 \
        + 2 * 44
    config = json.load(open(os.path.join(
        harness.BENCH, "configs", "dplr-fwfm-auction8k.json")))
    lay = DPLR.Layout(config["model"])
    assert (lay.rank, lay.k, lay.query_width) == (rho, k, 44)
    assert DPLR.launch_bytes(lay, 8192, 0, 16) == 8192 * 50 * 4 + 12
    assert DPLR.request_flops(lay, 8192) == 8192 * 155 + DPLR.context_flops(
        44, rho, k)
    assert DPLR.launch_flops(lay, 8192, 2.5) == 2.5 * 8192 * 155
    least, bound = work.roofline_seconds(1.97e12, 0.0, work.peaks(
        "TPU v5 lite"))
    assert (least, bound) == (pytest.approx(0.01), "flops")
    with pytest.raises(KeyError):
        work.peaks("TPU v9")
    run = _run()
    assert harness.reader("p50_ms")(run) == 2.0
    assert harness.reader("p50_ms.open")(run) == 2.0
    assert harness.reader("req_per_s")(run) == 50.0
    assert harness.reader("rows_per_dispatch.auction")(run) == 4.0
    assert harness.reader("write_p90_ms")(run) is None
    assert harness.reader("device_idle.auction")(run) is None
    peak = run.peak["bf16_flops_per_s"]
    for name in ("mfu.auction", "mfu.open"):
        assert harness.reader(name)(run) == pytest.approx(
            100 * 1e9 / (2e-3 * peak))
    assert harness.reader("mfu.retrieval")(run) == pytest.approx(
        100 * 1e9 * 50 / peak)


def test_recorded_v5e_trace():
    """30 ms of an auction8k.steady window traced on a TPU v5e: the
    reduction agrees with a recount on a 1 us grid."""
    raw = json.load(open(os.path.join(DATA, "trace_v5e.json")))
    got = devtrace.reduce(raw, DPLR.KERNEL_EVENT)
    lo, hi = raw["window"]
    events = raw["device"]["/device:TPU:0"]
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    kernel = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[(a - lo) // 1000:(b - lo + 999) // 1000] = True
            if "dplr_corpus_score" in name:
                kernel.append(b - a)
    assert got["window_s"] == pytest.approx(0.030)
    assert got["busy_s"] == pytest.approx(grid.sum() * 1e-6,
                                          abs=2 * len(events) * 1e-6)
    assert got["kernel_launches"] == len(kernel) > 0
    assert got["kernel_s"] == pytest.approx(sum(kernel) * 1e-9)
    assert all(" = " not in name for name, _ in got["device_ops"])
    assert got["device_ops"][0][0].startswith("dplr_corpus_score f32[")
    assert len(got["idle_gaps"]) == 10
    assert sum(s for _, s in got["idle_gaps"]) <= got["window_s"] - \
        got["busy_s"] + 1e-12
