"""Sharded-corpus weak scaling + top-K merge overhead (the PR-3 claim).

The sharded slab's promise is that corpus CAPACITY scales with the mesh
while per-query cost does not: each of D devices scores its own
capacity/D slice — O(n rho k / D) FLOPs and bytes per device — and the
only cross-device step is the merge of D·K top-K candidates, O(D·K)
traffic regardless of corpus size.

This benchmark measures both on the paper's deployed geometry (63 fields /
38 item-side, k=16, rho=3), weak-scaling style: devices and capacity grow
TOGETHER at a fixed capacity-per-shard, so flat latency across rows means
capacity scaled for free.  Every mesh size runs in this one process, on
a mesh over ``jax.devices()[:D]`` — the chips of a four-chip host, or on
the CPU the virtual devices of
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` set by the caller;
a D larger than the device count is reported as skipped.  Every run also
checks the merged top-K is BIT-exact vs a single-device engine over the
same corpus.

Output lines:
    shard: <D>,<capacity>,<K>,<topk_ms>,<score_ms>,<parity>
    shard: <D>,skipped,<n> devices

Caveat: on the CPU the D "devices" are host threads sharing one socket,
so weak scaling there demonstrates flat per-device WORK (and exercises
the real mesh code path); flat wall-clock needs real devices.
"""
from __future__ import annotations

import numpy as np


def leg(devices: int, per_shard: int, ks: list[int], reps: int) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks._common import time_stream
    from repro.core.fields import uniform_layout
    from repro.data.synthetic_ctr import SyntheticCTR
    from repro.launch.mesh import make_host_mesh
    from repro.models.recsys import fwfm
    from repro.serving import CorpusRankingEngine

    capacity = per_shard * devices
    n = capacity * 3 // 4                 # realistic partially-full slab
    layout = uniform_layout(25, 38, 1000)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=16, interaction="dplr",
                          rank=3)
    params = fwfm.init(jax.random.PRNGKey(0), cfg)
    data = SyntheticCTR(layout, embed_dim=8, seed=0)
    corpus = data.ranking_query(n, 0)
    mesh = make_host_mesh(model=devices)

    eng = CorpusRankingEngine(cfg, corpus["item_ids"][0],
                              corpus["item_weights"][0],
                              capacity=capacity, mesh=mesh)
    eng.refresh(params, step=0)
    ref = CorpusRankingEngine(cfg, corpus["item_ids"][0],
                              corpus["item_weights"][0], capacity=capacity)
    ref.refresh(params, step=0)

    queries = [data.context_query(100 + r) for r in range(reps)]
    ctxs = [(jnp.asarray(q["context_ids"]), jnp.asarray(q["context_weights"]))
            for q in queries]

    def score(r):
        c, w = ctxs[r % reps]
        return eng.score(c, w)

    score_ms = time_stream(score, reps)

    for K in ks:
        def topk(r):
            c, w = ctxs[r % reps]
            return eng.topk(c, K, w)

        topk_ms = time_stream(topk, reps)
        c, w = ctxs[0]
        gv, gi = (np.asarray(x) for x in eng.topk(c, K, w))
        wv, wi = (np.asarray(x) for x in ref.topk(c, K, w))
        parity = "ok" if ((gv == wv).all() and (gi == wi).all()) else "FAIL"
        print(f"shard: {devices},{capacity},{K},{topk_ms:.3f},"
              f"{score_ms:.3f},{parity}", flush=True)
        if parity != "ok":
            raise SystemExit(f"sharded top-K diverged from single-device "
                             f"at D={devices}, K={K}")


def main(quick: bool = False) -> None:
    import jax

    mesh_sizes = [1, 4] if quick else [1, 2, 4]
    per_shard = 1024 if quick else 4096
    ks = [8, 64] if quick else [8, 64, 256]
    reps = 5 if quick else 10
    for d in mesh_sizes:
        if d > jax.device_count():
            print(f"shard: {d},skipped,{jax.device_count()} devices",
                  flush=True)
            continue
        leg(d, per_shard, ks, reps)


if __name__ == "__main__":
    import sys

    main(quick="--quick" in sys.argv)
