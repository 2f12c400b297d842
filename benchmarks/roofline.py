"""Roofline analysis (deliverable g): derive the three per-device roofline
terms for every (arch x shape x mesh) cell from the dry-run artifacts.

    compute    = HLO_FLOPs / peak_FLOPs            (e.g. 197 TFLOP/s, v5e)
    memory     = HLO_bytes / HBM_bw                (819 GB/s)
    collective = collective_bytes / ICI_bw         (1,600 Gbit/s, v5e)

All inputs are PER-DEVICE (the compiled HLO is the per-device program;
launch/hlo_cost.py multiplies while-loop trip counts, which XLA's own
cost_analysis does not).  The bottleneck is the max term; the "useful
fraction" MODEL_FLOPS / HLO_FLOPs exposes remat/dispatch/masking waste.

The per-chip peak numbers come from ``repro.kernels.autotune.HW_PROFILES``
— one table shared with the kernel autotuner, keyed by ``device_kind`` —
for the device this process runs on; ``--hw KIND`` overrides it
explicitly (the dry-run records describe a chip that need not be
attached).  A device kind the table does not hold is an error.
"""
from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.kernels.autotune import HW_PROFILES, hw_profile

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "dryrun")


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS per cell (global, forward-only algorithmic cost;
# train cells multiply by 3 for fwd+bwd)
# ---------------------------------------------------------------------------

def _lm_model_flops(arch, shape_dims, kind):
    from repro.configs import REGISTRY

    cfg = REGISTRY[arch].make_config()
    n_active = cfg.n_active_params()
    if kind == "train":
        D = shape_dims["batch"] * shape_dims["seq"]
        return 6 * n_active * D
    if kind == "prefill":
        D = shape_dims["batch"] * shape_dims["seq"]
        return 2 * n_active * D
    # decode: one token per sequence + attention reads over the cache
    B, S = shape_dims["batch"], shape_dims["seq"]
    attn = 4 * cfg.n_layers * cfg.n_heads * cfg.hd * S * B
    return 2 * n_active * B + attn


def _recsys_model_flops(arch, shape_dims, kind):
    from repro.configs import REGISTRY

    cfg = REGISTRY[arch].make_config()
    lay = cfg.layout
    m = lay.n_fields
    if arch == "dplr-fwfm":
        k, rho = cfg.embed_dim, cfg.rank
        per_row = 2 * rho * m * k + 2 * m * k
    elif arch == "wide-deep":
        k = cfg.embed_dim
        dims = [m * k, *cfg.mlp_dims, 1]
        per_row = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    elif arch == "autoint":
        k, da = cfg.embed_dim, cfg.d_attn
        per_layer = 2 * m * k * da * 3 + 2 * m * m * da * 2 + 2 * m * da * da
        per_row = cfg.n_attn_layers * per_layer + 2 * m * da
    elif arch == "bst":
        k, T = cfg.embed_dim, cfg.n_tokens
        blk = 2 * T * k * k * 4 + 4 * T * T * k + 2 * T * k * cfg.ffn_mult * k * 2
        dims = [T * k + lay.n_context * k, *cfg.mlp_dims, 1]
        per_row = cfg.n_blocks * blk + sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    else:  # mind
        k, K, L = cfg.embed_dim, cfg.n_interests, cfg.seq_len
        per_query = 2 * L * k * k + cfg.capsule_iters * (4 * K * L * k)
        per_row = 2 * K * k           # per-candidate: K interest dots
        if kind == "train":
            return 3 * shape_dims["batch"] * (per_query + per_row * (1 + cfg.n_neg))
        if kind == "pointwise":
            return shape_dims["batch"] * (per_query + per_row)
        nq = shape_dims["n_queries"]
        return nq * per_query + nq * shape_dims["n_items"] * per_row
    if kind == "train":
        n = shape_dims["batch"]
        return 3 * n * per_row
    if kind == "pointwise":
        return shape_dims["batch"] * per_row
    # rank: context side once + item side per item (the paper's split)
    n = shape_dims["n_queries"] * shape_dims["n_items"]
    return n * per_row  # upper bound: per-item full row (DPLR does less)


def _gnn_model_flops(arch, shape_dims, kind):
    from repro.configs import REGISTRY
    from repro.configs.pna import shape_config

    spec = REGISTRY["pna"]
    shape = next(s for s in spec.shapes if s.dims == shape_dims)
    cfg = shape_config(spec.make_config(), shape)
    d = cfg.d_hidden
    if shape.name == "minibatch_lg":
        from repro.models.gnn.sampler import subgraph_shapes
        N, E = subgraph_shapes(shape_dims["batch_nodes"],
                               tuple(shape_dims["fanouts"]),
                               shape_dims["d_feat"])
    elif shape.name == "molecule":
        N = shape_dims["n_graphs"] * shape_dims["nodes_per_graph"]
        E = shape_dims["n_graphs"] * shape_dims["edges_per_graph"]
    else:
        N, E = shape_dims["n_nodes"], shape_dims["n_edges"]
    per_layer = 2 * E * (2 * d) * d + 2 * N * (13 * d) * d
    enc = 2 * N * shape_dims["d_feat"] * d
    return 3 * (cfg.n_layers * per_layer + enc)


def model_flops(arch, shape_name, mesh_name) -> float:
    from repro.configs import REGISTRY

    spec = REGISTRY[arch]
    shape = next(s for s in spec.shapes if s.name == shape_name)
    fam = spec.family
    if fam == "lm":
        return _lm_model_flops(arch, shape.dims, shape.kind)
    if fam == "recsys":
        return _recsys_model_flops(arch, shape.dims, shape.kind)
    return _gnn_model_flops(arch, shape.dims, shape.kind)


def hbm_bytes(rec: dict) -> float:
    """HBM traffic estimate.  XLA's 'bytes accessed' is fusion-aware but
    counts while bodies once; the parsed flops ratio supplies the trip
    multiplier (loops dominate both flops and bytes in these programs).
    Falls back to the parsed per-op upper bound for loop-free programs or
    old records."""
    candidates = [rec["traffic_bytes"]]
    if rec.get("out_bytes", 0) > 0:
        # every output byte written once + read ~once downstream
        candidates.append(2.0 * rec["out_bytes"])
    xb = rec.get("xla_bytes_body_once", -1)
    xf = rec.get("xla_flops_body_once", 0)
    if xb > 0 and xf > 0 and rec["flops"] > 0:
        candidates.append(xb * max(rec["flops"] / xf, 1.0))
    return min(candidates)


def analyze_record(rec: dict, peaks: dict) -> dict:
    n_dev = rec["n_devices"]
    compute_s = rec["flops"] / peaks["peak_flops"]
    memory_s = hbm_bytes(rec) / peaks["hbm_bw"]
    coll_s = rec["collectives"]["total_bytes"] / peaks["ici_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    step_s = max(terms.values())
    mf = model_flops(rec["arch"], rec["shape"], rec["mesh"])
    hlo_global = rec["flops"] * n_dev
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s,
        "bottleneck": bottleneck,
        "roofline_frac": compute_s / step_s if step_s > 0 else 0.0,
        "model_flops": mf,
        "useful_flops_ratio": mf / hlo_global if hlo_global else 0.0,
        "hbm_gib": (rec["memory"]["argument_bytes"]
                    + rec["memory"]["temp_bytes"]) / 2**30,
        "ok": rec.get("ok", False),
    }


def load_records(mesh: str = "single", include_tagged: bool = False):
    """The successful dry-run records of one mesh."""
    recs = []
    for f in sorted(glob.glob(os.path.join(RESULTS_DIR, f"*__{mesh}.json"))):
        if "+" in os.path.basename(f) and not include_tagged:
            continue   # optimized §Perf variants live in their own table
        rec = json.load(open(f))
        if rec.get("ok"):
            recs.append(rec)
    return recs


def render_markdown(rows) -> str:
    out = ["| arch | shape | compute s | memory s | collective s | "
           "bottleneck | roofline frac | useful FLOPs | HBM GiB |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"**{r['bottleneck']}** | {r['roofline_frac']:.3f} | "
            f"{r['useful_flops_ratio']:.3f} | {r['hbm_gib']:.1f} |")
    return "\n".join(out)


def main(quick: bool = False, hw: str | None = None):
    recs = load_records("single")
    if not recs:
        print("roofline: no dry-run records found — run "
              "`python -m repro.launch.dryrun` first")
        return []
    # only records need peaks: a process with no records (the CPU smoke
    # run) never asks which chip it is on
    peaks = hw_profile(hw)
    print(f"roofline: peaks of {hw or 'this device'} "
          f"(peak {peaks['peak_flops']/1e12:.0f} TFLOP/s, HBM "
          f"{peaks['hbm_bw']/1e9:.0f} GB/s, ICI {peaks['ici_bw']/1e9:.0f} "
          f"GB/s)")
    rows = [analyze_record(rec, peaks) for rec in recs]
    print("roofline: arch | shape | compute_s | memory_s | coll_s | "
          "bottleneck | frac | useful")
    for r in rows:
        print(f"roofline: {r['arch']:22s} | {r['shape']:14s} | "
              f"{r['compute_s']:.3e} | {r['memory_s']:.3e} | "
              f"{r['collective_s']:.3e} | {r['bottleneck']:10s} | "
              f"{r['roofline_frac']:.3f} | {r['useful_flops_ratio']:.3f}")
    md = render_markdown(rows)
    path = os.path.join(RESULTS_DIR, "..", "roofline.md")
    with open(path, "w") as f:
        f.write(md + "\n")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--hw", choices=sorted(HW_PROFILES), default=None,
                    help="device kind whose peaks to use (default: the "
                         "device this process runs on)")
    args = ap.parse_args()
    main(hw=args.hw)
