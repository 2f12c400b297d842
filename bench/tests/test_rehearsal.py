"""CPU rehearsal of the benchmark at smoke size (run with
``pytest bench/tests``): the traffic generator, the wire client, the
blocked reference, the draws of the DPLR model module, and whole runs of
the harness with the chip check skipped — sound, under the
lower-precision control, through a configuration's own model module, and
with the timed path broken in each way a serving cell can break.  Every
run but the sound one must come out not correct."""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, traffic, wire

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**31 + 11
DPLR = harness.model_module("dplr_fwfm")
# SHA-256 of what the smoke configuration draws from SEED, recorded from
# the harness before the DPLR code moved into bench/models: two tenants'
# corpora, the context pool, five churn updates of 2 rows, and both
# weight snapshots.
DIGESTS = {
    "pool": "d152e650bcae1f6b7d38ffcb5ddcdc76589beb5f6a4c81f931d77627db938a40",
    "items": "3ea450526b906f2fa01bc31bb671b60f6c42b3866d0a9c007dab6b31d450493a",
    "updates": "59201bbb5bbc9d908d731f0145affc1988f68a791d64973d8e7220e0f8ca3574",
    "snapshot0": "727704d287c7161f1c5fd69da4c69c0c55366b05eb3d96e80f280f871ac25a9f",
    "snapshot1": "0cf91b2bc2fe666a61cbe2095f8a8186b25b160b0dc2b8e474eb08e264b63bea",
}
# The float64 reference's (score, absum) of 8 contexts against tenant 0's
# corpus under each snapshot, recorded from the same harness: the sum, the
# norm and a position-weighted sum of each (``_moments``).  A float64
# matrix product rounds its last bits by the BLAS and CPU it runs on, so
# these are compared to a part in 1e12, not by digest.
REFERENCE_MOMENTS = [
    [[2053.2289086519436, 158.63517971888453, 3488378.817938227],
     [214538.13808251004, 3429.601261405494, 406927482.1589571]],
    [[5003.869476371945, 281.42288572530003, 10852922.64227169],
     [338840.3584467792, 5421.180547000574, 652191462.8693483]],
]


def _load(name):
    return json.load(open(os.path.join(DATA, name + ".json")))


def _run(mix, control=None, faults=None, seconds=1.5, config="smoke"):
    bm = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    specs = [m for m in bm["end_to_end"] if m["name"] in ("setup_s",
                                                          "p50_ms")]
    out, = harness.run_cell(_load(config), _load(mix),
                            specs, SEED, seconds, False, time.monotonic(),
                            require_tpu=False, control=control,
                            faults=faults, log=lambda s: None)
    return out


def _failed(out):
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


def test_open_schedule_fixes_the_work_across_seeds():
    mix = dict(_load("churn"), bursts=None)
    a = traffic.open_schedule(mix, 1, 2.0, traffic.SCHEDULE, 64, 1)
    b = traffic.open_schedule(mix, 2**31 + 5, 2.0, traffic.SCHEDULE, 64, 1)
    assert len(a["t"]) == len(b["t"]) == 300
    gaps = [np.sort(np.append(np.diff(x["t"]), 2.0 - x["t"][-1]))
            for x in (a, b)]
    assert np.allclose(*gaps)
    assert sorted(a["k"]) == sorted(b["k"])
    assert 0 <= a["t"].min() and a["t"].max() < 2.0
    assert not np.array_equal(a["ctx"], b["ctx"])
    again = traffic.open_schedule(mix, 1, 2.0, traffic.SCHEDULE, 64, 1)
    assert all(np.array_equal(a[f], again[f]) for f in a)


def test_wire_frames_match_the_server_codec():
    from repro.serving import rpc

    ctx = np.arange(7, dtype=np.int32)
    rq = rpc.decode_rank_request(wire.rank_frame(77, ctx, 5, "t3")[4:])
    assert (rq.request_id, rq.k, rq.tenant) == (77, 5, "t3")
    assert np.array_equal(rq.ctx, ctx) and rq.w is None
    buf = bytearray(rpc.frame(rpc.encode_ok_reply(
        9, np.array([3.0, 1.0], np.float32), np.array([4, 2], np.int32))))
    buf += rpc.frame(rpc.encode_error_reply(10, ValueError("x")))[:5]
    (rid, status, scores, slots), = wire.parse_replies(buf)
    assert (rid, status, scores.tolist(), slots.tolist()) == (
        9, 0, [3.0, 1.0], [4, 2])
    assert len(buf) == 5          # the partial frame waits for its rest


def test_loadgen_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import bench.loadgen; "
            "assert 'jax' not in sys.modules" % harness.ROOT)
    subprocess.run([sys.executable, "-c", code], check=True)


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _moments(x) -> list[float]:
    w = np.arange(1, x.size + 1).reshape(x.shape)
    return [float(x.sum()), float(np.linalg.norm(x)), float((w * x).sum())]


def test_moved_code_reproduces_the_seed_digests():
    config = _load("smoke")
    model, corpus = config["model"], config["corpus"]
    a = float(corpus["id_zipf_a"])
    g = traffic.rng(SEED, traffic.ITEMS)
    items = [DPLR.item_rows(model, corpus["n_items"], g, a) for _ in "ab"]
    pool = DPLR.query_rows(model, corpus["context_pool"],
                           traffic.rng(SEED, traffic.POOL), a)
    g = traffic.rng(SEED, traffic.WRITER)
    updates = [DPLR.item_rows(model, 2, g, a) for _ in range(5)]
    lay = DPLR.Layout(model)
    got = {"pool": _digest(pool), "items": _digest(*items),
           "updates": _digest(*updates)}
    moments = []
    for i, snap in enumerate(DPLR.draw(lay, SEED)):
        got[f"snapshot{i}"] = _digest(*(snap[k] for k in sorted(snap)))
        S, A = DPLR.Reference(lay, snap).scores(pool[:8], items[0])
        assert S.shape == A.shape == (8, corpus["n_items"])
        moments.append([_moments(S), _moments(A)])
    assert got == DIGESTS
    np.testing.assert_allclose(moments, REFERENCE_MOMENTS, rtol=1e-12, atol=0)


def test_reference_agrees_with_the_program_on_the_cpu():
    import jax

    from repro.serving import CorpusRankingEngine

    config = _load("smoke")
    lay = DPLR.Layout(config["model"])
    cfg = harness._factory(config["model"]["factory"])()
    DPLR.check(cfg, lay)
    snap, _ = DPLR.draw(lay, SEED)
    g = traffic.rng(SEED, traffic.ITEMS)
    items = DPLR.item_rows(config["model"], 300, g, 1.3)
    ctx = DPLR.query_rows(config["model"], 5, g, 1.3)
    eng = CorpusRankingEngine(cfg, items, capacity=512)
    eng.refresh(snap, step=0)
    got = np.asarray(eng.score(ctx))[:, :300]
    want, absum = DPLR.Reference(lay, snap).scores(ctx, items)
    assert np.max(np.abs(got - want) / absum) < 1e-6
    assert jax.default_backend() == "cpu"


def test_sound_run_is_correct_and_control_is_not():
    out = _run("churn")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 225
    ctl = _run("churn", control="bfloat16")
    assert not ctl["correct"]
    assert "score_err" in _failed(ctl)


def test_two_tenants_from_the_configuration_alone():
    out = _run("closed", config="smoke2")
    assert out["correct"], out["checks"]


def test_comparison_goes_through_the_configurations_model_module():
    """``smoke_scaled`` names a test-only module whose reference scores
    are DPLR-FwFM's times (1 + 1e-5): the sound program then reads as
    not correct on ``score_err``, and on nothing else."""
    out = _run("closed", config="smoke_scaled")
    assert not out["correct"]
    assert _failed(out) == {"score_err"}


def _alter_answers(sv):
    """An answer altered where it is produced: every score the kernel
    returns is raised by a part in ten thousand."""
    rt = sv.runtime
    inner = rt.kernel_score

    def altered(*a, **kw):
        vals, idx = (np.asarray(x) for x in inner(*a, **kw))
        return vals + 1e-4 * np.abs(vals), idx
    rt.kernel_score = altered


def _drop_writes(sv):
    """A write that returns its state unchanged: add and update leave the
    device slab as it was."""
    sv.runtime.write_rows = lambda params, cache, *a: cache


def _lose_refresh(sv):
    """A refresh that keeps the old item slab under the new model."""
    old = sv.states[""].cache
    sv.runtime.build = lambda *a: old


@pytest.mark.parametrize("mix,fault,caught", [
    ("closed", _alter_answers, "score_err"),
    ("churn", _alter_answers, "score_err"),
    ("churn", _drop_writes, None),
    ("churn", _lose_refresh, "score_err"),
])
def test_broken_timed_path_is_not_correct(mix, fault, caught):
    out = _run(mix, faults=fault)
    assert not out["correct"]
    failed = _failed(out)
    assert "window_compiles" not in failed
    if caught:
        assert caught in failed
