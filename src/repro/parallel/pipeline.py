"""Pipeline parallelism over the ``pod`` axis (GPipe-style, shard_map).

Inter-pod ICI/DCN links are the slow tier of a multi-pod mesh; running the
layer stack as P pipeline stages (one per pod) turns the per-layer inter-pod
traffic of pure data parallelism into one boundary activation transfer per
microbatch, hidden behind microbatch compute.

Schedule: standard GPipe fill/drain — T = n_micro + n_stages - 1 ticks; at
each tick stage s computes microbatch (t - s) if in range, then the boundary
activation moves s -> s+1 via ``collective_permute``.  Implemented with
``shard_map`` over the pod axis so each pod holds only its stage's weights
(the stage dim of the stacked params is sharded over ``pod``).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_micro, *, mesh,
                   axis_name: str = "pod"):
    """Run microbatches through pipeline stages.

    stage_fn(params_one_stage, x) -> y   (same shape as x)
    stage_params: pytree with leading [n_stages] dim (sharded over pod)
    x_micro: (n_micro, mb, ...) microbatched input (replicated over pod)
    Returns (n_micro, mb, ...) outputs (replicated over pod).
    """
    n_stages = mesh.shape[axis_name]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_pod(params_stage, xs):
        # params_stage: [1, ...] slice for this pod; xs: full microbatches
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        sidx = jax.lax.axis_index(axis_name)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)          # current activation
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            mb_idx = t - sidx                         # microbatch at stage
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # stage 0 ingests microbatch t from xs
            x_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(sidx == 0, x_in, buf)
            y = stage_fn(params_stage, inp)
            y = jnp.where(active, y, buf)
            # last stage emits into outs at mb_idx
            emit = active & (sidx == n_stages - 1)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb_idx, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # shift boundary activations one stage forward
            buf = jax.lax.ppermute(y, axis_name, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # only the last pod holds real outputs; share them
        outs = jax.lax.psum(
            jnp.where(sidx == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis_name)
        return outs

    other_axes = tuple(a for a in mesh.axis_names if a != axis_name)
    in_specs = (jax.tree.map(lambda _: P(axis_name), stage_params),
                P())
    return shard_map(
        per_pod, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False)(stage_params, x_micro)
