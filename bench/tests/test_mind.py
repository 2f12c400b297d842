"""CPU rehearsal of the MIND model module at smoke size (run with
``pytest bench/tests``): its float64 reference against the program, and
whole runs of the harness that must come out correct when sound, and not
correct under the bfloat16 control or with one interest dropped from the
timed path."""
from __future__ import annotations

import json
import os
import time

import numpy as np

from bench import harness, traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**31 + 23
MIND = harness.model_module("mind")


def _load(name):
    return json.load(open(os.path.join(DATA, name + ".json")))


def _run(control=None, faults=None, seconds=1.5):
    bm = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    specs = [m for m in bm["end_to_end"] if m["name"] in ("setup_s",
                                                          "req_per_s")]
    out, = harness.run_cell(_load("smoke_mind"), _load("closed"), specs,
                            SEED, seconds, False, time.monotonic(),
                            require_tpu=False, control=control,
                            faults=faults, log=lambda s: None)
    return out


def _failed(out):
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


def test_draws_and_laws():
    model = _load("smoke_mind")["model"]
    lay = MIND.Layout(model)
    a, b = (MIND.query_rows(model, 200, traffic.rng(SEED, traffic.POOL),
                            1.0) for _ in "ab")
    assert np.array_equal(a, b) and a.shape == (200, lay.query_width)
    hist = a[:, lay.m_p:]
    n = (hist >= 0).sum(1)
    assert n.min() >= 1 and n.max() <= lay.L
    assert (hist[:, 1:][hist[:, :-1] < 0] < 0).all()      # padding trails
    for row, k in zip(hist, n):
        assert len(set(row[:k].tolist())) == k              # distinct
    assert (a[:, :lay.m_p] < np.array(lay.v_prof)).all()
    items = MIND.item_rows(model, lay.n_items,
                           traffic.rng(SEED, traffic.ITEMS), 1.0)
    assert sorted(items[:, 0].tolist()) == list(range(lay.n_items))


def test_reference_agrees_with_the_program_on_the_cpu():
    from repro.serving import CorpusState

    config = _load("smoke_mind")
    model = config["model"]
    lay = MIND.Layout(model)
    cfg = harness._factory(model["factory"])()
    MIND.check(cfg, lay)
    snap, _ = MIND.draw(lay, SEED)
    items = MIND.item_rows(model, 200, traffic.rng(SEED, traffic.ITEMS), 1.0)
    users = MIND.query_rows(model, 12, traffic.rng(SEED, traffic.POOL), 1.0)
    eng = CorpusState(cfg, items, capacity=256)
    eng.refresh(snap, step=0)
    got = np.asarray(eng.score(users))[:, :200]
    want, absum = MIND.Reference(lay, snap).scores(users, items)
    assert np.max(np.abs(got - want) / absum) < 1e-6
    # the interests the program scores are the reference's live ones
    _, live = eng.runtime.model.query(snap, users, None)
    _, _, ref_live = MIND.Reference(lay, snap).tower(users)
    assert np.array_equal(np.asarray(live), ref_live)


def test_sound_run_is_correct_and_control_is_not():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    ctl = _run(control="bfloat16")
    assert not ctl["correct"]
    assert "score_err" in _failed(ctl)


def _drop_interest(sv):
    """A launch that scores one interest too few: each user's last live
    interest is dropped (where the user has two or more), in a program
    compiled for every warm shape before the window."""
    import jax
    import jax.numpy as jnp

    rt = sv.runtime
    model = rt.model

    def launch(params, cache, ids, w, *, K=None):
        U, live = model.query(params, ids, w)
        n = live.sum(1, keepdims=True)
        live = live & ((jnp.arange(live.shape[1]) < n - 1) | (n == 1))
        return model.kernel(params, cache, (U, live), K, rt.block_n)

    broken = jax.jit(launch, static_argnames=("K",))
    st = sv.states[""]
    for bq in (1, 2, 4):
        ids = jnp.asarray(np.broadcast_to(sv.pool[0], (bq, sv.pool.shape[1])))
        jax.block_until_ready(broken(st.params, st.cache, ids,
                                     jnp.ones(ids.shape), K=sv.max_k))
    rt.kernel_score = broken


def test_dropped_interest_is_not_correct():
    out = _run(faults=_drop_interest)
    assert not out["correct"]
    failed = _failed(out)
    assert "window_compiles" not in failed
    assert failed & {"score_err", "topk_short"}
