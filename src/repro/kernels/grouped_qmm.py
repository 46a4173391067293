"""Pallas TPU kernel: grouped ragged quantized matmul for MoE serving
(W{8,6,4,3}A8) — every expert's FFN projection in ONE kernel dispatch.

``kernels.qmm`` serves one (K, N) block per call; a Mixture-of-Experts
layer has E of them and the dense loop pays E dispatches (and E weight
streams' worth of latency) per projection per decode step. This kernel
consumes the capacity-sorted segment layout ``models.moe`` builds —
activations gathered into (S, C, K) token→expert segments with a ragged
``counts`` vector — plus the WHOLE packed expert stack
(``qtensor.quantize_experts``: payload (E, K*, N), per-expert scales
(E, G, N)), and streams it in one grid:

    grid = (segment, C/bm, N/bn, group)      # group innermost

Two scalar-prefetch vectors steer the grid (``PrefetchScalarGridSpec``):
``expert_ids[s]`` picks which expert's payload/scale rows segment s
DMAs — the index maps read it, so the weight stream is gathered at
block-fetch time and no dense per-segment weight copy ever exists — and
``counts[s]`` masks the ragged tail: row tiles past a segment's count
skip the MXU entirely (empty experts cost zero dots) and the final
write forces them to exact 0.0.

Everything else is ``kernels.qmm`` verbatim — in-VMEM sub-byte
``unpack_rows``, one exact int32 dot per (tile, group) folded into an
fp32 VMEM accumulator scaled by that group's per-channel scales, per-row
activation scales applied once on the last group — so each segment's
valid rows are bit-identical to a ``qmm_pallas`` call against
``expert_slice(w, expert_ids[s])``. The dense-loop-vs-grouped parity
tests and the MoE engine's oracle contract rest on exactly that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.bounds import require_group_dot_safe
from repro.qtensor import PACKED_BITS, logical_size, packed_size, unpack_rows

DEFAULT_BM, DEFAULT_BN = 256, 256
MAX_GROUP = 4096          # VMEM guard: one group's int8 tile must fit


def _validate_grouped(name: str, x_q, w_data, w_scale, x_scale, counts,
                      expert_ids, bits: int, k: int) -> int:
    """Trace-time shape/numerics validation; returns the group count.
    Raises ValueError (NOT assert — asserts vanish under ``python -O``
    and these guard exactness, RPR007/RPR201)."""
    if x_q.ndim != 3 or x_q.shape[2] != k:
        raise ValueError(f"{name}: x_q {x_q.shape} is not (S, C, k={k})")
    s, c = x_q.shape[0], x_q.shape[1]
    if w_data.ndim != 3:
        raise ValueError(f"{name}: w_data {w_data.shape} is not (E, K*, N)")
    e, kp, n = w_data.shape
    if kp != packed_size(k, bits):
        raise ValueError(
            f"{name}: packed payload {w_data.shape} inconsistent with "
            f"logical K={k} at {bits} bits "
            f"(expected {packed_size(k, bits)} rows)")
    if w_scale.ndim != 3 or w_scale.shape[0] != e or w_scale.shape[2] != n:
        raise ValueError(
            f"{name}: scales {w_scale.shape} are not per-expert (E, G, N) "
            f"for payload {w_data.shape} — quantize expert stacks with "
            "qtensor.quantize_experts")
    n_groups = w_scale.shape[1]
    if k % n_groups:
        raise ValueError(
            f"{name}: {n_groups} scale groups do not divide K={k}")
    bk = k // n_groups
    if bk > MAX_GROUP:
        raise ValueError(
            f"{name}: group_size {bk} too large for one VMEM tile; "
            f"requantize with group_size <= {MAX_GROUP}")
    if logical_size(packed_size(bk, bits), bits) != bk:
        raise ValueError(
            f"{name}: group_size {bk} splits a {bits}-bit pack unit — "
            "quantize with a group size that is a multiple of the pack "
            "unit")
    if x_scale.shape != (s, c, 1):
        raise ValueError(
            f"{name}: x_scale {x_scale.shape} is not per-row ({s}, {c}, 1)")
    if counts.shape != (s,) or expert_ids.shape != (s,):
        raise ValueError(
            f"{name}: counts {counts.shape} / expert_ids "
            f"{expert_ids.shape} must both be ({s},)")
    # int32 overflow proof: worst-case group dot must stay below 2^31
    # (A8 activations — the engine's only dynamic activation grid)
    require_group_dot_safe(bits, 8, bk, where=name)
    return n_groups


def _grouped_qmm_kernel(cnt_ref, eid_ref, x_ref, w_ref, ws_ref, xs_ref,
                        o_ref, acc_ref, *, n_groups: int, bits: int, bm: int):
    del eid_ref                      # consumed by the index maps
    s, i, g = pl.program_id(0), pl.program_id(1), pl.program_id(3)
    count = cnt_ref[s]

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * bm < count)         # ragged tail: empty tiles skip the MXU
    def _compute():
        w = w_ref[0]
        if bits in PACKED_BITS:
            w = unpack_rows(w, bits)           # (bk, bn) int8, in-VMEM
        prod = jax.lax.dot_general(
            x_ref[0], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc_ref[...] += prod.astype(jnp.float32) * ws_ref[0, 0]

    @pl.when(g == n_groups - 1)
    def _finalize():
        rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        val = acc_ref[...] * xs_ref[0]
        o_ref[0] = jnp.where(rows < count, val, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "k", "bm", "bn",
                                             "out_dtype", "interpret"))
def grouped_qmm_pallas(x_q: jnp.ndarray, w_data: jnp.ndarray,
                       x_scale: jnp.ndarray, w_scale: jnp.ndarray,
                       counts: jnp.ndarray, expert_ids: jnp.ndarray,
                       bits: int, k: int,
                       bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                       out_dtype=jnp.float32, interpret: bool = False):
    """x_q: (S, C, K) int8 segments; w_data: packed payload (E, K*, N)
    of a logical (E, K, N) ``quantize_experts`` stack; w_scale: (E, G, N)
    fp32 per-expert group scales; x_scale: (S, C, 1) per-row fp32;
    counts/expert_ids: (S,) int32 scalar-prefetch steering (valid rows
    per segment / expert feeding each segment). Returns (S, C, N)
    ``out_dtype`` with rows >= counts[s] exactly 0.0.
    """
    n_groups = _validate_grouped(
        "grouped_qmm_pallas", x_q, w_data, w_scale, x_scale, counts,
        expert_ids, bits, k)
    s, c = x_q.shape[0], x_q.shape[1]
    n = w_data.shape[2]
    bk = k // n_groups                          # one group per K step
    bkp = packed_size(k, bits) // n_groups      # packed rows per step
    bm, bn = min(bm, c), min(bn, n)
    # pad C and N to block multiples (K is never padded: groups are exact;
    # padded rows land past counts[s] and are masked to exact 0.0)
    pc, pn = (-c) % bm, (-n) % bn
    if pc:
        x_q = jnp.pad(x_q, ((0, 0), (0, pc), (0, 0)))
        x_scale = jnp.pad(x_scale, ((0, 0), (0, pc), (0, 0)))
    if pn:
        w_data = jnp.pad(w_data, ((0, 0), (0, 0), (0, pn)))
        w_scale = jnp.pad(w_scale, ((0, 0), (0, 0), (0, pn)))
    c2, n2 = c + pc, n + pn
    grid = (s, pl.cdiv(c2, bm), pl.cdiv(n2, bn), n_groups)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # counts, expert_ids
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk),
                         lambda s, i, j, g, cnt, eid: (s, i, g)),
            # the gather: segment s's weight tiles come from ITS expert's
            # payload/scale rows, selected at block-fetch time
            pl.BlockSpec((1, bkp, bn),
                         lambda s, i, j, g, cnt, eid: (eid[s], g, j)),
            # scales ride as (E, G, 1, N): a (1, 1, 1, bn) block keeps its
            # last two dims (full, lane-aligned) — the TPU block-shape rule
            pl.BlockSpec((1, 1, 1, bn),
                         lambda s, i, j, g, cnt, eid: (eid[s], g, 0, j)),
            pl.BlockSpec((1, bm, 1),
                         lambda s, i, j, g, cnt, eid: (s, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda s, i, j, g, cnt, eid: (s, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_qmm_kernel, n_groups=n_groups, bits=bits,
                          bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, c2, n2), out_dtype),
        interpret=interpret,
    )(counts.astype(jnp.int32), expert_ids.astype(jnp.int32),
      x_q, w_data, w_scale.astype(jnp.float32)[:, :, None, :], x_scale)
    return out[:, :c, :n]
