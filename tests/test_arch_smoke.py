"""Per-architecture smoke tests (deliverable f): every assigned arch
instantiates a REDUCED config and runs one forward/train step on CPU,
asserting output shapes and finiteness."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY

LM_ARCHS = [n for n, s in REGISTRY.items() if s.family == "lm"]
RECSYS_ARCHS = [n for n, s in REGISTRY.items() if s.family == "recsys"]


def _finite(x):
    return bool(jnp.isfinite(x).all())


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_smoke_forward_and_train(arch, rng):
    from repro.models.transformer import model as tm

    cfg = REGISTRY[arch].make_smoke()
    params = tm.init(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)).astype(np.int32))
    logits = tm.forward(params, cfg, toks)
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert _finite(logits.astype(jnp.float32))

    batch = {"tokens": toks, "labels": toks}
    loss, grads = jax.value_and_grad(tm.lm_loss)(params, cfg, batch)
    assert _finite(loss)
    assert all(_finite(g.astype(jnp.float32)) for g in jax.tree.leaves(grads))

    # decode one step against a prefilled cache
    lg, cache = tm.prefill(params, cfg, toks[:, :8], S)
    lg2, cache = tm.decode_step(params, cfg, toks[:, 8:9], cache,
                                jnp.asarray(8))
    assert lg2.shape == (B, 1, cfg.vocab_padded)
    assert _finite(lg2.astype(jnp.float32))


@pytest.mark.parametrize("arch", RECSYS_ARCHS)
def test_recsys_smoke_train_step(arch, rng):
    from repro.launch.steps import _recsys_module
    from repro import optim

    spec = REGISTRY[arch]
    cfg = spec.make_smoke()
    mod = _recsys_module(arch)
    params = mod.init(jax.random.PRNGKey(0), cfg)
    B = 8
    lay = cfg.layout
    if arch == "mind":
        item_vocab = lay.fields[-1].vocab_size
        batch = {
            "profile_ids": jnp.asarray(rng.integers(0, 16, (B, lay.n_context)).astype(np.int32)),
            "hist_ids": jnp.asarray(rng.integers(0, item_vocab, (B, cfg.seq_len)).astype(np.int32)),
            "hist_mask": jnp.ones((B, cfg.seq_len), jnp.float32),
            "target_id": jnp.asarray(rng.integers(0, item_vocab, B).astype(np.int32)),
            "neg_ids": jnp.asarray(rng.integers(0, item_vocab, (B, cfg.n_neg)).astype(np.int32)),
        }
    else:
        batch = {
            "ids": jnp.asarray(rng.integers(0, 16, (B, lay.n_slots)).astype(np.int32)),
            "weights": jnp.ones((B, lay.n_slots), jnp.float32),
            "label": jnp.asarray(rng.integers(0, 2, B).astype(np.float32)),
        }
        if arch == "bst":
            item_vocab = lay.fields[-1].vocab_size
            batch["hist_ids"] = jnp.asarray(
                rng.integers(0, item_vocab, (B, cfg.seq_len)).astype(np.int32))
            batch["hist_mask"] = jnp.ones((B, cfg.seq_len), jnp.float32)

    opt = optim.adagrad()
    state = opt.init(params)
    loss0, grads = jax.value_and_grad(mod.loss)(params, cfg, batch)
    params2, _ = opt.update(grads, state, params, 0.1)
    loss1 = mod.loss(params2, cfg, batch)
    assert _finite(loss0) and _finite(loss1)
    assert float(loss1) < float(loss0)   # one step on one batch must descend


@pytest.mark.parametrize("shape_name", ["full_graph_sm", "molecule"])
def test_gnn_smoke(shape_name, rng):
    import dataclasses as dc

    from repro.configs.pna import shape_config
    from repro.models.gnn import pna

    spec = REGISTRY["pna"]
    shape = next(s for s in spec.shapes if s.name == shape_name)
    cfg = dc.replace(shape_config(spec.make_smoke(), shape), d_feat=10,
                     n_classes=3)
    params = pna.init(jax.random.PRNGKey(0), cfg)
    N, E = 40, 120
    batch = {
        "node_feat": jnp.asarray(rng.standard_normal((N, 10), dtype=np.float32)),
        "edge_src": jnp.asarray(rng.integers(0, N, E).astype(np.int32)),
        "edge_dst": jnp.asarray(rng.integers(0, N, E).astype(np.int32)),
    }
    if cfg.task == "graph":
        G = 4
        batch["graph_ids"] = jnp.asarray(np.repeat(np.arange(G), N // G).astype(np.int32))
        batch["n_graphs"] = G
        batch["labels"] = jnp.asarray(rng.integers(0, 3, G).astype(np.int32))
        want_shape = (G, 3)
    else:
        batch["labels"] = jnp.asarray(rng.integers(0, 3, N).astype(np.int32))
        want_shape = (N, 3)
    out = pna.forward(params, cfg, batch)
    assert out.shape == want_shape
    assert _finite(out)
    loss, grads = jax.value_and_grad(pna.loss)(
        params, cfg, {k: v for k, v in batch.items()})
    assert _finite(loss)
    assert all(_finite(g) for g in jax.tree.leaves(grads))


def test_registry_covers_all_assigned_archs():
    assigned = {
        "starcoder2-7b", "yi-9b", "gemma3-1b", "granite-moe-1b-a400m",
        "mixtral-8x7b", "pna", "mind", "autoint", "bst", "wide-deep",
    }
    assert assigned.issubset(set(REGISTRY)), assigned - set(REGISTRY)
    # 40 assigned cells total (+ the paper's own arch as extra)
    n_cells = sum(len(s.shapes) for n, s in REGISTRY.items() if n in assigned)
    assert n_cells == 40
