"""Empirical Fisher (EF) trace estimation — the heart of FIT.

Paper (Prop. 5):  Tr(Î(θ)) = (1/N) Σ_i ||∇_θ f(z_i, θ)||²  — a single
backward pass per sample, no second derivatives.

Weight traces
-------------
Per-sample gradients are obtained with ``vmap(grad)`` over microbatches
(``lax.map`` across chunks bounds memory at ``microbatch × |params|``).
The per-block row-squared-norm reduction is the ``ef_sqnorm`` Pallas
kernel on TPU.

Activation traces
-----------------
Activations join the statistical manifold via zero-valued additive "taps"
at every activation site (Sec. 3.2.1): the model computes ``a + tap`` and
we differentiate w.r.t. the tap. Because sample i's loss depends only on
sample i's activation row, ONE batched backward pass yields all
per-sample activation gradients:

    ∂(1/N Σ_j f_j)/∂a_i = (1/N) ∇_{a_i} f_i
    ⇒ Tr(Î(â)) = (1/N) Σ_i ||∇_{â} f_i||² = N · Σ_i ||G_i||²

where G is the tap gradient of the mean loss. No vmap needed — activation
traces are as cheap as one training step.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.utils.pytree import named_leaves

LossFn = Callable[[Any, Any], jnp.ndarray]          # (params, batch) -> scalar mean loss
TapLossFn = Callable[[Any, Mapping[str, jnp.ndarray], Any], jnp.ndarray]


def _block_sqnorms(grads: Any) -> Dict[str, jnp.ndarray]:
    """Per-block per-sample squared norms.

    grads: pytree whose leaves are (B, *param_shape) per-sample gradients.
    Returns {block_path: (B,) float32 squared norms}.
    """
    out = {}
    for name, g in named_leaves(grads):
        b = g.shape[0]
        out[name] = kops.ef_sqnorm(g.reshape(b, -1))
    return out


def ef_trace_weights(
    loss_fn: LossFn,
    params: Any,
    batch: Any,
    microbatch: Optional[int] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    mesh_axis: str = "data",
) -> Dict[str, float]:
    """EF trace per parameter block: (1/N) Σ_i ||∇_θl f(z_i)||².

    ``batch`` is a pytree with leading batch dim N on every leaf.
    ``loss_fn(params, batch)`` must return the MEAN loss over the batch.

    Passing ``mesh`` enables the data-parallel mode: the batch axis is
    sharded over ``mesh_axis`` via shard_map, each device reduces its
    shard's per-block squared norms locally, and a single psum of
    #blocks scalars combines them — per-sample gradients never leave
    their device. Identical estimate (the EF trace is a plain mean over
    samples), #devices× less per-device work.
    """
    if mesh is not None and int(mesh.shape[mesh_axis]) > 1:
        return _ef_trace_weights_sharded(loss_fn, params, batch, mesh,
                                         mesh_axis, microbatch)
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    return _mean_traces(_sqnorm_program(loss_fn, n, microbatch or n)(
        params, batch))


def _mean_traces(sq: Dict[str, jnp.ndarray]) -> Dict[str, float]:
    return {k: float(jnp.mean(v)) for k, v in sq.items()}


def _sqnorm_program(loss_fn: LossFn, n: int, mb: int) -> Callable:
    """Jitted ``(params, batch) -> {block: per-sample squared norms}``
    over a batch of ``n`` samples in microbatches of ``mb``. ``params``
    is an argument of the program — closed over, a model's weights would
    be baked into the program as constants (gigabytes at LLM scale) —
    so one program serves every batch of a stream."""
    assert n % mb == 0, f"batch {n} not divisible by microbatch {mb}"

    def single_loss(p, z):
        zb = jax.tree.map(lambda a: a[None], z)
        return loss_fn(p, zb)

    per_sample_grad = jax.vmap(jax.grad(single_loss), in_axes=(None, 0))

    def chunk_sqnorms(p, z_chunk):
        return _block_sqnorms(per_sample_grad(p, z_chunk))

    if mb == n:
        return jax.jit(chunk_sqnorms)

    def mapped(p, batch):
        chunks = jax.tree.map(
            lambda a: a.reshape(n // mb, mb, *a.shape[1:]), batch)
        return jax.lax.map(lambda c: chunk_sqnorms(p, c), chunks)

    return jax.jit(mapped)


def _ef_trace_weights_sharded(
    loss_fn: LossFn,
    params: Any,
    batch: Any,
    mesh: jax.sharding.Mesh,
    mesh_axis: str,
    microbatch: Optional[int],
) -> Dict[str, float]:
    """Data-parallel EF trace: shard the batch, psum per-block sums."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    ndev = int(mesh.shape[mesh_axis])
    assert n % ndev == 0, f"batch {n} not divisible by {ndev} devices"
    local = n // ndev
    mb = microbatch or local
    assert local % mb == 0, \
        f"local batch {local} not divisible by microbatch {mb}"

    def single_loss(p, z):
        zb = jax.tree.map(lambda a: a[None], z)
        return loss_fn(p, zb)

    per_sample_grad = jax.vmap(jax.grad(single_loss), in_axes=(None, 0))

    def chunk_sums(p, z_chunk):
        sq = _block_sqnorms(per_sample_grad(p, z_chunk))
        return {k: jnp.sum(v) for k, v in sq.items()}

    def local_fn(p, z):
        if mb == local:
            sums = chunk_sums(p, z)
        else:
            chunks = jax.tree.map(
                lambda a: a.reshape(local // mb, mb, *a.shape[1:]), z)
            per = jax.lax.map(lambda c: chunk_sums(p, c), chunks)
            sums = {k: jnp.sum(v) for k, v in per.items()}
        # rpr-ok: RPR002 fp32 Fisher-trace statistics — an estimator (Prop. 5 Monte-Carlo), not a bit-exactness surface; summation order is part of its noise floor
        return jax.lax.psum(sums, mesh_axis)

    # check_vma=False: pallas_call (the ef_sqnorm kernel in interpret
    # mode) has no replication rule; we psum explicitly so the check is
    # redundant here.
    f = jax.jit(shard_map(local_fn, mesh=mesh,
                          in_specs=(P(), P(mesh_axis)), out_specs=P(),
                          check_vma=False))
    sums = f(params, batch)
    return {k: float(v) / n for k, v in sums.items()}


def ef_trace_weights_streaming(
    loss_fn: LossFn,
    params: Any,
    batches,
    microbatch: Optional[int] = None,
    tolerance: Optional[float] = None,
    min_batches: int = 4,
    mesh: Optional[jax.sharding.Mesh] = None,
    mesh_axis: str = "data",
) -> Tuple[Dict[str, float], int]:
    """Streaming EF trace over a batch iterator with early stopping.

    Mirrors the paper's fixed-tolerance protocol (Sec. 4.3: "EF trace
    computation is stopped at a tolerance of 0.01"): stop when the
    relative moving std of the running mean trace drops below tolerance.
    ``mesh`` shards each batch data-parallel (see ``ef_trace_weights``).
    Returns (traces, batches_consumed).
    """
    sums: Dict[str, float] = {}
    totals: list[float] = []
    count = 0
    program, program_n = None, None
    for batch in batches:
        n = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if mesh is not None and int(mesh.shape[mesh_axis]) > 1:
            t = ef_trace_weights(loss_fn, params, batch, microbatch,
                                 mesh=mesh, mesh_axis=mesh_axis)
        else:
            if n != program_n:       # one compiled program per batch shape
                program = _sqnorm_program(loss_fn, n, microbatch or n)
                program_n = n
            t = _mean_traces(program(params, batch))
        count += 1
        for k, v in t.items():
            sums[k] = sums.get(k, 0.0) + v
        totals.append(sum(t.values()))
        if tolerance is not None and count >= min_batches:
            arr = np.array(totals, dtype=np.float64)
            mean = arr.mean()
            sem = arr.std(ddof=1) / np.sqrt(count) if count > 1 else np.inf
            if mean > 0 and sem / mean < tolerance:
                break
    return {k: v / count for k, v in sums.items()}, count


def ef_trace_activations(
    tap_loss_fn: TapLossFn,
    params: Any,
    tap_shapes: Mapping[str, jax.ShapeDtypeStruct],
    batch: Any,
) -> Dict[str, float]:
    """EF trace per activation site via the tap trick (one backward pass).

    ``tap_loss_fn(params, taps, batch)`` computes the mean loss with each
    activation site adding its tap. Tap leading dim must be the batch dim.
    """
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]
    taps = {k: jnp.zeros(s.shape, s.dtype) for k, s in tap_shapes.items()}

    @jax.jit
    def tap_grads(p, t, z):
        return jax.grad(lambda tt: tap_loss_fn(p, tt, z))(t)

    g = tap_grads(params, taps, batch)
    out: Dict[str, float] = {}
    for name, gi in g.items():
        rows = kops.ef_sqnorm(gi.reshape(gi.shape[0], -1))
        # ∇_{a_i} f_i = N * row_i  ⇒  (1/N) Σ_i N²||row_i||² = N Σ_i ||row_i||²
        out[name] = float(n * jnp.sum(rows))
    return out


def fisher_trace_exact(loss_fn: LossFn, params: Any, batch: Any) -> Dict[str, float]:
    """Exact EF trace by materializing every per-sample gradient (tests only)."""
    n = jax.tree_util.tree_leaves(batch)[0].shape[0]

    def single_loss(p, z):
        zb = jax.tree.map(lambda a: a[None], z)
        return loss_fn(p, zb)

    g = jax.vmap(jax.grad(single_loss), in_axes=(None, 0))(params, batch)
    out = {}
    for name, gi in named_leaves(g):
        gi = gi.reshape(n, -1).astype(jnp.float32)
        out[name] = float(jnp.mean(jnp.sum(gi * gi, axis=-1)))
    return out
