"""Substrate tests: embedding bag, sharded lookup, optimizers, schedules,
gradient accumulation, int8 compression, data pipeline."""
import numpy as np
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.fields import FeatureLayout, FieldSpec, uniform_layout
from repro.data.pipeline import ShardedPipeline, host_shard_seed
from repro.data.synthetic_ctr import SyntheticCTR
from repro.embedding.bag import lookup_field_embeddings, padded_rows
from repro.embedding.sharded import make_sharded_take
from repro import optim
from repro.sharding import shard_map


def test_multi_hot_field_averages(rng, key):
    layout = FeatureLayout((
        FieldSpec("user", 100, "context"),
        FieldSpec("genre", 20, "context", multiplicity=3),
        FieldSpec("ad", 50, "item"),
    ))
    from repro.embedding.bag import init_embedding_table
    table = init_embedding_table(key, layout.total_vocab, 8)
    B = 4
    ids = jnp.asarray(
        rng.integers(0, 20, (B, layout.n_slots)).astype(np.int32)
        % np.array([100, 20, 20, 20, 50]))
    w = jnp.ones((B, layout.n_slots)).at[:, 1:4].set(1 / 3.0)
    V = lookup_field_embeddings(table, layout, ids, w)
    assert V.shape == (B, 3, 8)
    genre_rows = table[layout.field_offsets[1] + ids[:, 1:4]]
    np.testing.assert_allclose(V[:, 1], genre_rows.mean(1), rtol=1e-5,
                               atol=1e-6)


def test_sharded_take_equals_dense(rng, host_mesh):
    """shard_map masked-take+psum == jnp.take (on the 1-device mesh the
    collective is trivial but the code path is identical)."""
    table = jnp.asarray(rng.standard_normal((64, 8), dtype=np.float32))
    ids = jnp.asarray(rng.integers(0, 64, (6, 5)).astype(np.int32))
    take = make_sharded_take(host_mesh, {2: P(None, None)})
    np.testing.assert_array_equal(take(table, ids), jnp.take(table, ids, axis=0))


def test_padded_rows():
    assert padded_rows(1) == 2048
    assert padded_rows(2048) == 2048
    assert padded_rows(2049) == 4096


@settings(deadline=None, max_examples=15)
@given(seed=st.integers(0, 10**6), n=st.integers(1, 4))
def test_grad_accumulation_equals_full_batch(seed, n):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal(5).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((8 * n, 5)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal(8 * n).astype(np.float32))

    def loss(p, b):
        return ((b["x"] @ p["w"] - b["y"]) ** 2).mean()

    p0 = {"w": w}
    batch = {"x": x, "y": y}
    l_full, g_full = jax.value_and_grad(loss)(p0, batch)
    l_acc, g_acc = optim.gradient_accumulation(loss, n)(p0, batch)
    np.testing.assert_allclose(l_full, l_acc, rtol=1e-5)
    np.testing.assert_allclose(g_full["w"], g_acc["w"], rtol=1e-4, atol=1e-5)


def test_adagrad_and_adamw_converge():
    for opt, lr in ((optim.adagrad(), 0.5), (optim.adamw(), 0.05)):
        params = {"w": jnp.array([4.0, -2.0])}
        state = opt.init(params)
        for _ in range(400):
            g = jax.grad(lambda p: ((p["w"] - 1.0) ** 2).sum())(params)
            params, state = opt.update(g, state, params, lr)
        np.testing.assert_allclose(params["w"], 1.0, atol=1e-1)


def test_warmup_cosine_schedule():
    sched = optim.warmup_cosine(1.0, warmup_steps=10, total_steps=100)
    assert float(sched(jnp.asarray(0))) == 0.0
    assert abs(float(sched(jnp.asarray(10))) - 1.0) < 1e-6
    assert float(sched(jnp.asarray(100))) <= 0.11


def test_int8_compression_roundtrip_and_error_feedback(rng):
    x = jnp.asarray(rng.standard_normal(1000).astype(np.float32))
    q, scale = optim.int8_compress(x)
    x_hat = optim.int8_decompress(q, scale)
    # quantization error bounded by scale/2 per element
    assert float(jnp.abs(x - x_hat).max()) <= float(scale) * 0.51 + 1e-7
    # error feedback: repeated compression of a CONSTANT gradient with
    # error carry-over must average to the true value
    err = jnp.zeros_like(x)
    acc = jnp.zeros_like(x)
    n = 64
    for _ in range(n):
        corr = x + err
        q, scale = optim.int8_compress(corr)
        deq = optim.int8_decompress(q, scale)
        err = corr - deq
        acc = acc + deq
    np.testing.assert_allclose(acc / n, x, atol=2e-3)


def test_compressed_psum_single_device(host_mesh, rng):
    from repro.optim.compression import compressed_psum

    x = jnp.asarray(rng.standard_normal((8, 4)).astype(np.float32))
    err0 = jnp.zeros_like(x)
    fn = shard_map(
        lambda a, b: compressed_psum(a, "data", b),
        mesh=host_mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    out, err = fn(x, err0)
    np.testing.assert_allclose(out, x, atol=2e-2)
    np.testing.assert_allclose(out + err, x, atol=1e-6)  # exact w/ feedback


def test_pipeline_determinism_and_resume():
    data = SyntheticCTR(uniform_layout(3, 2, 50), embed_dim=4, seed=1)
    a = [data.batch(16, s)["ids"] for s in range(5)]
    b = [data.batch(16, s)["ids"] for s in range(5)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)   # replayable by (seed, step)

    pipe = ShardedPipeline(lambda step: data.batch(16, step)).start(from_step=3)
    step, batch = pipe.get()
    pipe.stop()
    assert step == 3
    np.testing.assert_array_equal(batch["ids"], a[3])


def test_host_shard_seeds_disjoint():
    seeds = {host_shard_seed(0, h, 7) for h in range(64)}
    assert len(seeds) == 64


def test_synthetic_teacher_is_learnable():
    """A DPLR student with rank >= teacher rank fits the synthetic data far
    better than chance — the property Table 1's reproduction relies on."""
    from repro.models.recsys import fwfm

    layout = uniform_layout(4, 3, 30)
    data = SyntheticCTR(layout, embed_dim=4, teacher_rank=2, seed=0)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=4, interaction="dplr",
                          rank=2)
    params = fwfm.init(jax.random.PRNGKey(0), cfg)
    opt = optim.adagrad()
    state = opt.init(params)

    @jax.jit
    def step(params, state, batch):
        loss, g = jax.value_and_grad(fwfm.loss)(params, cfg, batch)
        params, state = opt.update(g, state, params, 0.1)
        return params, state, loss

    losses = []
    for s in range(150):
        batch = {k: jnp.asarray(v) for k, v in data.batch(512, s).items()}
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.02
