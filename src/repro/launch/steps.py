"""Builds the lowerable (step_fn, abstract args, shardings) for every
(architecture x shape) cell — the single source of truth shared by the
multi-pod dry-run, the roofline harness, and the trainer.

Everything here is ALLOCATION-FREE: parameters come from jax.eval_shape,
inputs are ShapeDtypeStructs.  Only launch/train.py and the examples ever
materialize arrays.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.configs import registry as reg
from repro.embedding.sharded import _local_masked_take
from repro.sharding import rules
from repro.sharding import shard_map


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _dp_size(mesh) -> int:
    n = 1
    for a in rules.dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def auto_leaf_spec(shape, mesh, min_shard: int = 1024) -> P:
    """Shard the largest dim that divides the DP extent and is big enough;
    replicate otherwise.  Deterministic from static shapes — the lookup
    take_fn and the batch in_shardings both use this rule, so no resharding
    happens between host feed and the embedding gather."""
    dp = rules.dp_axes(mesh)
    n_dp = _dp_size(mesh)
    best, best_dim = None, min_shard - 1
    for i, d in enumerate(shape):
        if d >= max(min_shard, n_dp) and d % n_dp == 0 and d > best_dim:
            best, best_dim = i, d
    spec = [None] * len(shape)
    if best is not None:
        spec[best] = dp
    return P(*spec)


def auto_batch_specs(tree_of_sds, mesh):
    return jax.tree.map(lambda s: auto_leaf_spec(s.shape, mesh), tree_of_sds)


def make_auto_take(mesh):
    """take_fn for model-sharded arenas; batch-dim sharding per
    ``auto_leaf_spec`` over the ids' own (static) shape."""

    def take_fn(table, ids):
        ispec = auto_leaf_spec(ids.shape, mesh)
        out_spec = P(*(tuple(ispec) + (None,)))
        fn = partial(_local_masked_take, axis_name="model")
        return shard_map(
            fn, mesh=mesh,
            in_specs=(P("model", None), ispec),
            out_specs=out_spec,
        )(table, ids)

    return take_fn


@dataclasses.dataclass
class Lowerable:
    """One compile cell."""

    name: str
    fn: Callable
    args: tuple                 # abstract args
    in_shardings: tuple
    out_shardings: Any = None
    donate_argnums: tuple = ()
    static_meta: dict = dataclasses.field(default_factory=dict)

    def jitted(self):
        kw = {}
        if self.out_shardings is not None:
            kw["out_shardings"] = self.out_shardings
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       donate_argnums=self.donate_argnums, **kw)

    def lower(self):
        return self.jitted().lower(*self.args)


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def _lm_abstract_state(cfg, optimizer):
    from repro.models.transformer import model as tm
    params = jax.eval_shape(lambda: tm.init(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(optimizer.init, params)
    return params, opt_state


def _seq_shard_constraint(mesh, spec_fn):
    """Sharding-constraint hook: applies spec_fn(shape)->P when the sequence
    axis divides the model axis; identity otherwise (decode S=1)."""
    msz = mesh.shape["model"]

    def fn(x):
        if x.shape[1] % msz != 0:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec_fn(x.ndim)))

    return fn


def build_lm(arch: reg.ArchSpec, shape: reg.ShapeSpec, mesh,
             cfg=None, opts=None) -> Lowerable:
    from repro.models.transformer import model as tm

    cfg = cfg or arch.make_config()
    if (opts or {}).get("moe_scatter") and cfg.is_moe:
        # §Perf: scatter/gather MoE dispatch — no (g, E, C) one-hot matmuls,
        # so the dispatch all-reduce of expert inputs disappears.
        cfg = dataclasses.replace(cfg, moe_impl="scatter")
    if (opts or {}).get("moe_fused") and cfg.is_moe:
        # §Perf: combine-before-psum reassociation (see moe.MoEConfig).
        cfg = dataclasses.replace(cfg, moe_fused_combine=True)
    dp = rules.dp_axes(mesh)
    # prefill kv collection: per-layer k/v constrained so the collected
    # cache is BORN in the cache layout (S over model) instead of being
    # resharded by a giant copy at the end (see EXPERIMENTS.md §Dry-run).
    cfg = dataclasses.replace(
        cfg,
        kv_constraint=_seq_shard_constraint(
            mesh, lambda nd: P(dp, "model", None, None)),
    )
    pspecs = rules.lm_param_specs(cfg, mesh)
    B, S = shape.dims["batch"], shape.dims["seq"]

    if shape.kind == "train":
        optimizer = optim.adamw(weight_decay=0.1)
        params, opt_state = _lm_abstract_state(cfg, optimizer)
        ospecs = rules.opt_state_specs(pspecs, opt_state)
        # cap microbatches so each microbatch still divides the DP extent
        n_micro = min(cfg.micro_batches, max(B // _dp_size(mesh), 1))

        pshard = named(mesh, pspecs)
        constrain = lambda tree: jax.lax.with_sharding_constraint(tree, pshard)  # noqa: E731

        def train_step(params, opt_state, batch, lr):
            if n_micro > 1:
                loss, grads = optim.gradient_accumulation(
                    lambda p, b: tm.lm_loss(p, cfg, b), n_micro,
                    constrain=constrain)(params, batch)
            else:
                loss, grads = jax.value_and_grad(tm.lm_loss)(params, cfg, batch)
            params, opt_state = optimizer.update(grads, opt_state, params, lr)
            return loss, params, opt_state

        batch = {"tokens": _sds((B, S), jnp.int32),
                 "labels": _sds((B, S), jnp.int32)}
        bspec = {"tokens": P(dp, None), "labels": P(dp, None)}
        return Lowerable(
            name=f"{arch.name}/{shape.name}",
            fn=train_step,
            args=(params, opt_state, batch, _sds((), jnp.float32)),
            in_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                          named(mesh, bspec), NamedSharding(mesh, P())),
            out_shardings=(NamedSharding(mesh, P()), named(mesh, pspecs),
                           named(mesh, ospecs)),
            donate_argnums=(0, 1),
        )

    # serving carries bf16 weights (the production serving checkpoint);
    # the f32 master copy exists only in training jobs.
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        tm.init(jax.random.PRNGKey(0), cfg)))
    cache_sds = _sds(
        (cfg.n_layers, 2, B, S, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    cache_spec = rules.lm_cache_spec(mesh, B)
    tok_spec = P(dp, None) if B % _dp_size(mesh) == 0 else P(None, None)
    logit_spec = (P(dp, None, "model") if B % _dp_size(mesh) == 0
                  else P(None, None, "model"))

    if shape.kind == "prefill":
        def serve_prefill(params, tokens):
            return tm.prefill(params, cfg, tokens, S)

        return Lowerable(
            name=f"{arch.name}/{shape.name}",
            fn=serve_prefill,
            args=(params, _sds((B, S), jnp.int32)),
            in_shardings=(named(mesh, pspecs), NamedSharding(mesh, tok_spec)),
            out_shardings=(NamedSharding(mesh, logit_spec),
                           NamedSharding(mesh, cache_spec)),
        )

    # decode: one new token against a full cache
    def serve_decode(params, cache, tokens, cache_index):
        return tm.decode_step(params, cfg, tokens, cache, cache_index)

    return Lowerable(
        name=f"{arch.name}/{shape.name}",
        fn=serve_decode,
        args=(params, cache_sds, _sds((B, 1), jnp.int32), _sds((), jnp.int32)),
        in_shardings=(named(mesh, pspecs), NamedSharding(mesh, cache_spec),
                      NamedSharding(mesh, tok_spec), NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, logit_spec),
                       NamedSharding(mesh, cache_spec)),
        donate_argnums=(1,),
    )


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------

_RECSYS_MODULES = {
    "dplr-fwfm": "repro.models.recsys.fwfm",
    "wide-deep": "repro.models.recsys.wide_deep",
    "autoint": "repro.models.recsys.autoint",
    "bst": "repro.models.recsys.bst",
    "mind": "repro.models.recsys.mind",
}


def _recsys_module(name):
    import importlib
    return importlib.import_module(_RECSYS_MODULES[name])


def _recsys_train_batch(arch_name, cfg, B):
    lay = cfg.layout
    if arch_name == "mind":
        return {
            "profile_ids": _sds((B, lay.n_context), jnp.int32),
            "hist_ids": _sds((B, cfg.seq_len), jnp.int32),
            "hist_mask": _sds((B, cfg.seq_len), jnp.float32),
            "target_id": _sds((B,), jnp.int32),
            "neg_ids": _sds((B, cfg.n_neg), jnp.int32),
        }
    batch = {
        "ids": _sds((B, lay.n_slots), jnp.int32),
        "weights": _sds((B, lay.n_slots), jnp.float32),
        "label": _sds((B,), jnp.float32),
    }
    if arch_name == "bst":
        batch["hist_ids"] = _sds((B, cfg.seq_len), jnp.int32)
        batch["hist_mask"] = _sds((B, cfg.seq_len), jnp.float32)
    return batch


def _recsys_rank_query(arch_name, cfg, n_queries, n_items):
    lay = cfg.layout
    ctx = lay.subset("context")
    item = lay.subset("item")
    q = {
        "context_ids": _sds((n_queries, ctx.n_slots), jnp.int32),
        "context_weights": _sds((n_queries, ctx.n_slots), jnp.float32),
        "item_ids": _sds((n_queries, n_items, item.n_slots), jnp.int32),
        "item_weights": _sds((n_queries, n_items, item.n_slots), jnp.float32),
    }
    if arch_name in ("bst", "mind"):
        q["hist_ids"] = _sds((n_queries, cfg.seq_len), jnp.int32)
        q["hist_mask"] = _sds((n_queries, cfg.seq_len), jnp.float32)
    if arch_name == "mind":
        q["profile_ids"] = q.pop("context_ids")
        q.pop("context_weights"), q.pop("item_weights")
    return q


def build_recsys(arch: reg.ArchSpec, shape: reg.ShapeSpec, mesh,
                 cfg=None, opts=None) -> Lowerable:
    mod = _recsys_module(arch.name)
    cfg = cfg or arch.make_config()
    params = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), cfg))
    if (opts or {}).get("serve_bf16") and shape.kind in ("rank", "pointwise"):
        # §Perf: bf16 serving tables — halves arena HBM residency, lookup
        # traffic, and every cross-shard psum byte.  Training keeps f32.
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, jnp.bfloat16 if a.dtype == jnp.float32 else a.dtype),
            params)
    pspecs = rules.recsys_param_specs(params, mesh)
    take_fn = make_auto_take(mesh)

    if shape.kind == "train":
        optimizer = optim.adagrad()
        opt_state = jax.eval_shape(optimizer.init, params)
        ospecs = rules.opt_state_specs(pspecs, opt_state)
        B = shape.dims["batch"]
        batch = _recsys_train_batch(arch.name, cfg, B)
        bspec = auto_batch_specs(batch, mesh)

        def train_step(params, opt_state, batch, lr):
            loss, grads = jax.value_and_grad(mod.loss)(params, cfg, batch,
                                                       take_fn=take_fn)
            params, opt_state = optimizer.update(grads, opt_state, params, lr)
            return loss, params, opt_state

        return Lowerable(
            name=f"{arch.name}/{shape.name}",
            fn=train_step,
            args=(params, opt_state, batch, _sds((), jnp.float32)),
            in_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                          named(mesh, bspec), NamedSharding(mesh, P())),
            out_shardings=(NamedSharding(mesh, P()), named(mesh, pspecs),
                           named(mesh, ospecs)),
            donate_argnums=(0, 1),
        )

    if shape.kind == "pointwise":
        B = shape.dims["batch"]
        batch = _recsys_train_batch(arch.name, cfg, B)
        batch.pop("label", None)
        if arch.name == "mind":
            batch.pop("neg_ids")
        bspec = auto_batch_specs(batch, mesh)

        def serve_pointwise(params, batch):
            if arch.name == "mind":
                return mod.apply(params, cfg, batch)
            return mod.apply(params, cfg, batch, take_fn=take_fn)

        return Lowerable(
            name=f"{arch.name}/{shape.name}",
            fn=serve_pointwise,
            args=(params, batch),
            in_shardings=(named(mesh, pspecs), named(mesh, bspec)),
        )

    # rank: Algorithm-1-style candidate scoring
    nq, ni = shape.dims["n_queries"], shape.dims["n_items"]
    query = _recsys_rank_query(arch.name, cfg, nq, ni)
    qspec = auto_batch_specs(query, mesh)

    if (opts or {}).get("mp_scoring") and arch.name == "dplr-fwfm":
        # §Perf optimization: model-parallel DPLR scoring — the rank-rho
        # projection runs inside the sharded lookup, so the model-axis psum
        # moves (rho*k + 2) floats per item instead of (m_I*k + m_I + 2).
        item_spec = qspec["item_ids"]

        def serve_rank_mp(params, query):
            return mod.rank_items_mp(params, cfg, query, mesh=mesh,
                                     item_spec=item_spec)

        return Lowerable(
            name=f"{arch.name}/{shape.name}+mp",
            fn=serve_rank_mp,
            args=(params, query),
            in_shardings=(named(mesh, pspecs), named(mesh, qspec)),
        )

    def serve_rank(params, query):
        return mod.rank_items(params, cfg, query, take_fn=take_fn)

    return Lowerable(
        name=f"{arch.name}/{shape.name}",
        fn=serve_rank,
        args=(params, query),
        in_shardings=(named(mesh, pspecs), named(mesh, qspec)),
    )


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------

def _pad_to(n, mult):
    return ((n + mult - 1) // mult) * mult


def _gnn_batch(shape: reg.ShapeSpec, mesh):
    d = shape.dims
    total = 1
    for a in mesh.axis_names:
        total *= mesh.shape[a]
    if shape.name == "minibatch_lg":
        from repro.models.gnn.sampler import subgraph_shapes
        n_nodes, n_edges = subgraph_shapes(d["batch_nodes"], tuple(d["fanouts"]),
                                           d["d_feat"])
    elif shape.name == "molecule":
        n_nodes = d["n_graphs"] * d["nodes_per_graph"]
        n_edges = d["n_graphs"] * d["edges_per_graph"]
    else:
        n_nodes, n_edges = d["n_nodes"], d["n_edges"]
    n_nodes_p = _pad_to(n_nodes, total)
    n_edges_p = _pad_to(n_edges, total)
    batch = {
        "node_feat": _sds((n_nodes_p, d["d_feat"]), jnp.float32),
        "edge_src": _sds((n_edges_p,), jnp.int32),
        "edge_dst": _sds((n_edges_p,), jnp.int32),
        "edge_mask": _sds((n_edges_p,), jnp.float32),
        "labels": _sds((d["n_graphs"],) if d["task"] == "graph" else (n_nodes_p,),
                       jnp.int32),
        "label_mask": _sds((d["n_graphs"],) if d["task"] == "graph" else (n_nodes_p,),
                           jnp.float32),
    }
    if d["task"] == "graph":
        batch["graph_ids"] = _sds((n_nodes_p,), jnp.int32)
    return batch, (n_nodes_p, n_edges_p)


def build_gnn(arch: reg.ArchSpec, shape: reg.ShapeSpec, mesh,
              cfg=None, opts=None) -> Lowerable:
    from repro.configs.pna import shape_config
    from repro.models.gnn import pna

    base = cfg or arch.make_config()
    cfg = shape_config(base, shape)
    all_axes = tuple(mesh.axis_names)
    total_shards = int(np.prod([mesh.shape[a] for a in all_axes]))

    def node_constraint(h):
        if h.shape[0] % total_shards != 0:
            return h
        return jax.lax.with_sharding_constraint(
            h, NamedSharding(mesh, P(all_axes, *([None] * (h.ndim - 1)))))

    cfg = dataclasses.replace(cfg, remat=False, node_constraint=node_constraint,
                              compute_dtype=jnp.bfloat16)

    if (opts or {}).get("partitioned") and shape.dims["task"] == "node":
        # §Perf optimization: destination-partitioned message passing —
        # scatters become device-local; cross-device traffic is one bf16
        # all-gather of node states per layer (reduce-scatter in bwd).
        batch, (n_nodes_p, n_edges_p) = _gnn_batch(shape, mesh)
        e_loc = -(-int(n_edges_p * 1.25) // total_shards)   # 25% skew slack
        pbatch = {
            "node_feat": batch["node_feat"],
            "src_global": _sds((total_shards * e_loc,), jnp.int32),
            "dst_local": _sds((total_shards * e_loc,), jnp.int32),
            "edge_mask": _sds((total_shards * e_loc,), jnp.float32),
            "labels": batch["labels"],
            "label_mask": batch["label_mask"],
        }
        pbspec = {k: P(all_axes, *([None] * (len(v.shape) - 1)))
                  for k, v in pbatch.items()}
        optimizer = optim.adamw()
        params = jax.eval_shape(lambda: pna.init(jax.random.PRNGKey(0), cfg))
        pspecs = rules.gnn_param_specs(params, mesh)
        opt_state = jax.eval_shape(optimizer.init, params)
        ospecs = rules.opt_state_specs(pspecs, opt_state)

        def train_step_part(params, opt_state, batch, lr):
            loss, grads = jax.value_and_grad(
                lambda p, b: pna.loss_partitioned(p, cfg, b, mesh=mesh,
                                                  axes=all_axes))(params, batch)
            params, opt_state = optimizer.update(grads, opt_state, params, lr)
            return loss, params, opt_state

        return Lowerable(
            name=f"{arch.name}/{shape.name}+part",
            fn=train_step_part,
            args=(params, opt_state, pbatch, _sds((), jnp.float32)),
            in_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                          named(mesh, pbspec), NamedSharding(mesh, P())),
            out_shardings=(NamedSharding(mesh, P()), named(mesh, pspecs),
                           named(mesh, ospecs)),
            donate_argnums=(0, 1),
        )
    optimizer = optim.adamw()
    params = jax.eval_shape(lambda: pna.init(jax.random.PRNGKey(0), cfg))
    pspecs = rules.gnn_param_specs(params, mesh)
    opt_state = jax.eval_shape(optimizer.init, params)
    ospecs = rules.opt_state_specs(pspecs, opt_state)

    batch, _ = _gnn_batch(shape, mesh)
    bspec = {}
    for k, v in batch.items():
        spec = [None] * len(v.shape)
        if v.shape[0] % int(np.prod([mesh.shape[a] for a in all_axes])) == 0:
            spec[0] = all_axes
        elif v.shape[0] % _dp_size(mesh) == 0:
            spec[0] = rules.dp_axes(mesh)
        bspec[k] = P(*spec)

    task = shape.dims["task"]

    def loss_fn(params, batch):
        b = dict(batch)
        if task == "graph":
            b["n_graphs"] = shape.dims["n_graphs"]
        return pna.loss(params, cfg, b)

    def train_step(params, opt_state, batch, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state = optimizer.update(grads, opt_state, params, lr)
        return loss, params, opt_state

    return Lowerable(
        name=f"{arch.name}/{shape.name}",
        fn=train_step,
        args=(params, opt_state, batch, _sds((), jnp.float32)),
        in_shardings=(named(mesh, pspecs), named(mesh, ospecs),
                      named(mesh, bspec), NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, P()), named(mesh, pspecs),
                       named(mesh, ospecs)),
        donate_argnums=(0, 1),
    )


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

_BUILDERS = {"lm": build_lm, "recsys": build_recsys, "gnn": build_gnn}


def build(arch_name: str, shape_name: str, mesh, cfg=None,
          opts=None) -> Lowerable:
    arch = reg.get(arch_name)
    shape = next(s for s in arch.shapes if s.name == shape_name)
    if shape.skip:
        raise ValueError(f"{arch_name}/{shape_name} is N/A: {shape.skip}")
    return _BUILDERS[arch.family](arch, shape, mesh, cfg=cfg, opts=opts)


def all_cells(include_skipped: bool = False):
    for arch in reg.REGISTRY.values():
        for shape in arch.shapes:
            if shape.skip and not include_skipped:
                continue
            yield arch.name, shape.name, shape
