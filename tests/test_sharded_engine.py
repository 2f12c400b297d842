"""Sharded corpus slab: bit-exact parity vs the single-device engine.

The engine's sharded mode must be OBSERVATIONALLY IDENTICAL to the
unsharded engine — same slot assignments, bit-exact scores and merged
top-K (ties included), zero scorer retraces across churn + refresh — while
each device holds only capacity/D slab rows.  These tests run the same op
sequences through both engines and compare.

Device count adapts to the runtime: on a plain 1-device CPU run the mesh
is (1, 1) — same shard_map code path, D=1 — and under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the CI
configuration, and the subprocess test at the bottom) the slab genuinely
shards 4 ways.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.fields import uniform_layout
from repro.data.synthetic_ctr import SyntheticCTR
from repro.launch.mesh import make_host_mesh
from repro.models.recsys import fwfm
from repro.serving import CorpusRankingEngine


def _setup(nC=5, nI=4, vocab=50, k=8, rho=2, n=37, seed=0):
    layout = uniform_layout(nC, nI, vocab)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=k, interaction="dplr",
                          rank=rho)
    params = fwfm.init(jax.random.PRNGKey(seed), cfg)
    data = SyntheticCTR(layout, embed_dim=4, seed=seed)
    q = {k_: jnp.asarray(v) for k_, v in data.ranking_query(n, seed).items()}
    return layout, cfg, params, data, q


def _mesh():
    return make_host_mesh(model=jax.device_count())


def _pair(cfg, params, q, data=None, capacity=None, **kw):
    """(sharded, single-device) engines over the same initial corpus."""
    mesh = _mesh()
    sh = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0],
                             capacity=capacity, mesh=mesh, **kw)
    ref = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0],
                              capacity=capacity, **kw)
    sh.refresh(params, step=0)
    ref.refresh(params, step=0)
    return sh, ref


def _churn_both(engines, data):
    """Mirror a representative add/remove/update sequence onto both
    engines; returns the slots each reported for the adds."""
    out = []
    for e in engines:
        added = e.add_items(data.ranking_query(7, 90)["item_ids"][0])
        e.remove_items([1, 3, 5, int(added[0]), int(added[3])])
        upd = data.ranking_query(4, 91)
        e.update_items([0, 2, int(added[1]), int(added[6])],
                       upd["item_ids"][0], upd["item_weights"][0])
        added2 = e.add_items(data.ranking_query(3, 92)["item_ids"][0])
        out.append((added, added2))
    return out


# ---------------------------------------------------------------------------
# Parity: score + merged top-K bit-exact vs the single-device engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True])
def test_sharded_bitexact_vs_single_device(use_pallas):
    _, cfg, params, data, q = _setup(n=37)
    kw = dict(use_pallas_kernel=use_pallas, block_n=8) if use_pallas else {}
    sh, ref = _pair(cfg, params, q, **kw)
    D = sh.n_shards
    assert sh.capacity == ref.capacity and sh.local_capacity * D == sh.capacity

    got = np.asarray(sh.score(q["context_ids"], q["context_weights"]))
    want = np.asarray(ref.score(q["context_ids"], q["context_weights"]))
    np.testing.assert_array_equal(got, want)

    for K in (1, 5, sh.n_items):
        gv, gi = sh.topk(q["context_ids"], K, q["context_weights"])
        wv, wi = ref.topk(q["context_ids"], K, q["context_weights"])
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_sharded_churn_parity_and_identical_slot_assignment(use_pallas):
    _, cfg, params, data, q = _setup(n=20)
    kw = dict(use_pallas_kernel=use_pallas, block_n=8) if use_pallas else {}
    sh, ref = _pair(cfg, params, q, capacity=32, **kw)
    (s_add, s_add2), (r_add, r_add2) = _churn_both((sh, ref), data)
    # identical lowest-free-global-slot allocation order on both engines
    np.testing.assert_array_equal(s_add, r_add)
    np.testing.assert_array_equal(s_add2, r_add2)
    np.testing.assert_array_equal(sh.valid_slots, ref.valid_slots)

    got = np.asarray(sh.score(q["context_ids"], q["context_weights"]))
    want = np.asarray(ref.score(q["context_ids"], q["context_weights"]))
    np.testing.assert_array_equal(got, want)

    K = sh.n_items                  # the hardest mask case for the merge
    gv, gi = sh.topk(q["context_ids"], K, q["context_weights"])
    wv, wi = ref.topk(q["context_ids"], K, q["context_weights"])
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_model_placed_on_every_shard_at_refresh():
    """``refresh`` replicates the model onto the mesh once, so a dispatch
    whose context is already on the mesh moves no array between devices
    (left on one device, the whole model would be copied to every shard
    on every call)."""
    _, cfg, params, data, q = _setup(n=20)
    sh, _ = _pair(cfg, params, q, capacity=32)
    replicated = NamedSharding(sh.mesh, P())
    ctx, w = jax.device_put((q["context_ids"], q["context_weights"]),
                            replicated)
    jax.block_until_ready(sh.topk(ctx, 5, w))               # trace + compile
    with jax.transfer_guard_device_to_device("disallow"):
        jax.block_until_ready(sh.topk(ctx, 5, w))
        jax.block_until_ready(sh.score(ctx, w))


# ---------------------------------------------------------------------------
# Ownership: churn deltas land on the shard that owns the slot
# ---------------------------------------------------------------------------

def test_churn_lands_on_owning_shard():
    _, cfg, params, data, q = _setup(n=20)
    sh, ref = _pair(cfg, params, q, capacity=32)
    _churn_both((sh, ref), data)
    D = sh.n_shards
    cap = sh.capacity

    # striped ownership arithmetic is the public contract
    np.testing.assert_array_equal(sh.shard_of(np.arange(cap)),
                                  np.arange(cap) % D)

    # each device's cache slice must hold exactly the striped global rows
    # it owns — i.e. every delta was scattered on its owner, nowhere else
    ref_Q = np.asarray(ref.cache.Q_I)
    ref_valid = np.asarray(ref.cache.valid)
    shards = sorted(sh.cache.Q_I.addressable_shards,
                    key=lambda s: s.index[1].start or 0)
    vshards = sorted(sh.cache.valid.addressable_shards,
                     key=lambda s: s.index[1].start or 0)
    assert len(shards) == D
    for s in range(D):
        blk = np.asarray(shards[s].data)
        assert blk.shape[0] == sh.local_capacity and blk.shape[1] == 1
        live = ref_valid[s::D]      # compare live rows (dead rows may hold
        # stale values on either engine — unspecified by the slab contract)
        np.testing.assert_array_equal(blk[:, 0][live], ref_Q[s::D][live])
        np.testing.assert_array_equal(np.asarray(vshards[s].data)[:, 0],
                                      np.asarray(sh._valid_np)[s::D])


def test_grouped_deltas_layout_and_uneven_shard_parity():
    """Shard-grouped churn: ``group_deltas`` lays the delta out per owning
    shard (each device computes/scatters only its own rows), and a delta
    that lands ENTIRELY on one shard — the maximally uneven grouping,
    where every other shard receives pure filler — stays bit-exact vs the
    unsharded engine."""
    from repro.serving.sharded import group_deltas

    # layout unit check: 3 slots for shard 0 of D=2, 1 for shard 1 =>
    # bucket to the busiest shard's next_pow2(3) = 4 local rows
    slots = np.array([0, 2, 3, 4])
    ids = np.arange(8, dtype=np.int32).reshape(4, 2)
    w = np.ones((4, 2), np.float32)
    li, ids_g, w_g = group_deltas(slots, ids, w, D=2, local_cap=16)
    assert li.shape == (4, 2) and ids_g.shape == (4, 2, 2)
    np.testing.assert_array_equal(li[:, 0], [0, 1, 2, 16])  # g//D + filler
    np.testing.assert_array_equal(li[:, 1], [1, 16, 16, 16])
    np.testing.assert_array_equal(ids_g[:3, 0], ids[[0, 1, 3]])
    np.testing.assert_array_equal(ids_g[0, 1], ids[2])
    assert (ids_g[1:, 1] == 0).all() and (w_g[1:, 1] == 1.0).all()

    # end-to-end: every updated slot owned by shard 0 (g % D == 0)
    _, cfg, params, data, q = _setup(n=20)
    sh, ref = _pair(cfg, params, q, capacity=32)
    D = sh.n_shards
    victims = [g for g in range(0, 20, D)][:4]     # all on shard 0
    upd = data.ranking_query(len(victims), 55)
    for e in (sh, ref):
        e.update_items(victims, upd["item_ids"][0], upd["item_weights"][0])
    got = np.asarray(sh.score(q["context_ids"], q["context_weights"]))
    want = np.asarray(ref.score(q["context_ids"], q["context_weights"]))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Growth: slab doubling is shard-aware and never renumbers a slot
# ---------------------------------------------------------------------------

def test_sharded_growth_preserves_slots_and_parity():
    _, cfg, params, data, q = _setup(n=20)
    sh, ref = _pair(cfg, params, q, capacity=32)
    before = np.asarray(sh.score(q["context_ids"], q["context_weights"]))

    grow = data.ranking_query(20, 77)
    s_slots = sh.add_items(grow["item_ids"][0])
    r_slots = ref.add_items(grow["item_ids"][0])
    np.testing.assert_array_equal(s_slots, r_slots)
    assert sh.capacity == 64 and sh.n_items == 40
    assert sh.local_capacity == 64 // sh.n_shards

    got = np.asarray(sh.score(q["context_ids"], q["context_weights"]))
    want = np.asarray(ref.score(q["context_ids"], q["context_weights"]))
    np.testing.assert_array_equal(got, want)
    # pre-existing slots kept their rows bit-for-bit across the doubling
    np.testing.assert_array_equal(got[:, :20], before[:, :20])

    gv, gi = sh.topk(q["context_ids"], 40, q["context_weights"])
    wv, wi = ref.topk(q["context_ids"], 40, q["context_weights"])
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


# ---------------------------------------------------------------------------
# Merge never surfaces a dead slot — even from a nearly-empty shard
# ---------------------------------------------------------------------------

def test_no_dead_slot_wins_across_merge():
    """Empty out (almost) all of one shard's slots: its device-local top-K
    is then padded with NEG_INF dead candidates, which the merge must rank
    below every live candidate from the other shards."""
    _, cfg, params, data, q = _setup(n=32)
    sh, ref = _pair(cfg, params, q, capacity=32)
    D = sh.n_shards
    # kill every slot shard 0 owns except the single lowest
    victims = [g for g in range(32) if g % D == 0][1:]
    if victims:
        sh.remove_items(victims)
        ref.remove_items(victims)
    K = sh.n_items
    gv, gi = sh.topk(q["context_ids"], K, q["context_weights"])
    gi = np.asarray(gi)
    assert sh.is_live(gi).all(), f"merge surfaced a dead slot: {gi}"
    wv, wi = ref.topk(q["context_ids"], K, q["context_weights"])
    np.testing.assert_array_equal(gi, np.asarray(wi))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    with pytest.raises(ValueError):
        sh.topk(q["context_ids"], K + 1, q["context_weights"])


# ---------------------------------------------------------------------------
# Zero retraces across churn + model refresh (sharded)
# ---------------------------------------------------------------------------

def test_sharded_trace_flat_across_churn_and_refresh(tmp_path):
    from repro.checkpoint import CheckpointManager

    _, cfg, params, data, q = _setup(n=20)
    mesh = _mesh()
    eng = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0],
                              capacity=64, mesh=mesh)
    eng.refresh(params, step=0)
    eng.score(q["context_ids"], q["context_weights"])
    eng.topk(q["context_ids"], 5, q["context_weights"])
    traced = eng.trace_count
    rng = np.random.default_rng(0)
    for s in range(12):
        kind = s % 3
        if kind == 0 and eng.n_items + 4 <= eng.capacity:
            eng.add_items(data.ranking_query(4, 200 + s)["item_ids"][0])
        elif kind == 1 and eng.n_items > 10:
            eng.remove_items(rng.choice(eng.valid_slots, 3, replace=False))
        else:
            upd = data.ranking_query(2, 300 + s)
            eng.update_items(rng.choice(eng.valid_slots, 2, replace=False),
                             upd["item_ids"][0], upd["item_weights"][0])
        eng.score(q["context_ids"], q["context_weights"])
        eng.topk(q["context_ids"], 5, q["context_weights"])
    mgr = CheckpointManager(str(tmp_path))
    bumped = dict(params)
    bumped["bias"] = params["bias"] + 1.0
    mgr.save({"params": bumped}, step=1, blocking=True)
    assert eng.maybe_refresh(mgr, {"params": params},
                             select=lambda t: t["params"])
    eng.score(q["context_ids"], q["context_weights"])
    eng.topk(q["context_ids"], 5, q["context_weights"])
    assert eng.trace_count == traced, \
        f"sharded scorer retraced under churn/refresh ({eng.trace_count})"


# ---------------------------------------------------------------------------
# Constructor validation
# ---------------------------------------------------------------------------

def test_sharded_capacity_validation():
    _, cfg, params, data, q = _setup(n=20)
    D = jax.device_count()
    if D > 1:
        # power of two, >= the 2-item corpus, but < D => not D-divisible;
        # must hit the shard-divisibility check, not the capacity<n one
        with pytest.raises(ValueError, match="not divisible"):
            CorpusRankingEngine(cfg, q["item_ids"][0][:2],
                                q["item_weights"][0][:2],
                                capacity=2, mesh=_mesh())
    # auto capacity rounds up to at least one slot per shard
    eng = CorpusRankingEngine(cfg, q["item_ids"][0][:1],
                              q["item_weights"][0][:1], mesh=_mesh())
    assert eng.capacity >= D and eng.capacity % D == 0


# ---------------------------------------------------------------------------
# The 4-virtual-device configuration, from a plain 1-device test run
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_suite_on_four_virtual_devices():
    """Re-run this module with XLA_FLAGS forcing 4 host devices so a plain
    ``pytest`` invocation still exercises a genuinely sharded mesh (CI
    additionally runs the whole file under that flag directly)."""
    if os.environ.get("REPRO_SHARDED_SUBPROC") or jax.device_count() > 1:
        pytest.skip("already running multi-device")
    env = dict(os.environ)
    # strip any caller-set forced device count: XLA parses the LAST
    # occurrence, so prepending ours would lose to it
    inherited = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = \
        f"{inherited} --xla_force_host_platform_device_count=4".strip()
    env["REPRO_SHARDED_SUBPROC"] = "1"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", os.path.abspath(__file__),
         "-k", "not four_virtual_devices"],
        env=env, cwd=root, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, \
        f"4-device run failed:\n{r.stdout[-4000:]}\n{r.stderr[-2000:]}"
