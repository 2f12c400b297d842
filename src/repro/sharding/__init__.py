import jax as _jax

from repro.sharding.rules import (  # noqa: F401
    dp_axes, lm_param_specs, recsys_param_specs, gnn_param_specs,
    opt_state_specs, lm_cache_spec, corpus_cache_specs, corpus_slab_spec,
    corpus_slab_axis,
)

shard_map = _jax.shard_map


def shard_map_norep(f, *, mesh, in_specs, out_specs):
    """``shard_map`` with replication checking disabled: required whenever
    the body contains a ``pallas_call`` (jax has no replication rule for
    it, so ``check_vma=True`` — the default — fails at trace time)."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)
