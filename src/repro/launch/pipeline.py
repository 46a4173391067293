"""Pipeline parallelism (GPipe schedule) via shard_map + collective_permute.

The assigned production mesh is ("pod","data","model") so PP is not one
of the 40-cell axes; it is provided as a first-class feature for meshes
with a "pipe" axis (tested on the 8-device CPU mesh and dry-runnable via
``pp_dryrun``).

Schedule: layers are split into S stages (stage s owns a contiguous
slab). The global batch is split into M microbatches. For T = M + S − 1
ticks, every stage applies its slab to the activation it holds, then the
ring ``ppermute`` shifts activations stage s → s+1. Stage s processes
microbatch m at tick t = m + s; outputs are collected at the last stage.
Bubble fraction = (S−1)/T, the standard GPipe cost. Differentiable:
``jax.grad`` through ppermute gives the reverse schedule automatically.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(layer_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, x_micro: jnp.ndarray, mesh: Mesh,
                   axis: str = "pipe") -> jnp.ndarray:
    """Run microbatched inputs through a layer pipeline.

    layer_fn(params_slab, x) -> x   — one stage's computation
    stage_params: pytree with leading dim S (one slab per stage)
    x_micro: (M, mb, ...) microbatched inputs
    Returns (M, mb, ...) outputs (as produced by the last stage).
    """
    s = mesh.shape[axis]
    m = x_micro.shape[0]
    ticks = m + s - 1

    def body(params_slab, xm):
        stage = jax.lax.axis_index(axis)
        params_slab = jax.tree.map(lambda a: a[0], params_slab)  # local slab

        buf = jnp.zeros_like(xm[0])                   # activation in flight
        outs = jnp.zeros_like(xm)                     # collected at last stage

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            feed = jnp.where(t < m, jnp.clip(t, 0, m - 1), 0)
            buf = jnp.where(stage == 0, xm[feed], buf)
            buf = layer_fn(params_slab, buf)
            # last stage emits microbatch t-(s-1)
            emit = t - (s - 1)
            do_emit = (stage == s - 1) & (emit >= 0)
            outs = jax.lax.cond(
                do_emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, buf, jnp.clip(emit, 0, m - 1), 0),
                lambda o: o, outs)
            # shift ring: stage i -> i+1
            buf = jax.lax.ppermute(buf, axis,
                                   [(i, (i + 1) % s) for i in range(s)])
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # outputs were collected on the last stage only; all other stages
        # hold zeros, so a psum over the pipe axis replicates the result.
        # rpr-ok: RPR002 one nonzero term per element (last stage) + zeros elsewhere — zero-padded fp adds are exact
        return jax.lax.psum(outs, axis)

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),      # params sharded by stage; data replicated
        out_specs=P(),
        check_vma=False,
    )
    return mapped(stage_params, x_micro)


def sequential_apply(layer_fn, stage_params, x_micro) -> jnp.ndarray:
    """Reference: same computation without the pipeline (for tests)."""
    s = jax.tree_util.tree_leaves(stage_params)[0].shape[0]

    def run_one(xm):
        for i in range(s):
            slab = jax.tree.map(lambda a: a[i], stage_params)
            xm = layer_fn(slab, xm)
        return xm

    return jax.vmap(run_one)(x_micro)
