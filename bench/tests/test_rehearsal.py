"""CPU rehearsal of the benchmark at smoke size (run with
``pytest bench/tests``): the traffic generator, the wire client, the
blocked reference, and whole runs of the harness with the chip check
skipped — sound, under the lower-precision control, and with the timed
path broken in each way a serving cell can break.  Every run but the
sound one must come out not correct."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, reference, traffic, wire

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**31 + 11


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


def test_reference_agrees_with_the_program_on_the_cpu():
    import jax

    from repro.serving import CorpusRankingEngine

    config = _load("smoke")
    lay = reference.Layout(config["model"])
    cfg = harness._factory(config["model"]["factory"])()
    harness._check_layout(cfg, lay)
    snap, _ = harness.make_weights(lay, SEED)
    g = traffic.rng(SEED, traffic.ITEMS)
    items = traffic.id_rows(config["model"]["item_tiers"], 300, g, 1.3)
    ctx = traffic.id_rows(config["model"]["context_tiers"], 5, g, 1.3)
    eng = CorpusRankingEngine(cfg, items, capacity=512)
    eng.refresh(snap, step=0)
    got = np.asarray(eng.score(ctx))[:, :300]
    w = reference.Weights(lay, np.asarray(snap["embedding"]),
                          *(np.asarray(snap[f]) for f in
                            ("linear", "U", "e", "bias")))
    want, absum = w.scores(w.side(ctx, item=False), w.side(items, item=True))
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
