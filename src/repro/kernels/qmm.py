"""Pallas TPU kernel: fused grouped-scale quantized matmul (W{8,6,4,3}A8).

The QTensor serving hot path: activations are int8 with per-ROW dynamic
scales (batch-composition invariance, like ``int8_matmul``); weights are
a packed ``repro.qtensor`` payload — int8 bytes at W8, 2-per-byte
nibbles at W4/W3, 4-values-in-3-bytes at W6 — with per-output-channel
per-group scales ``(G, N)`` along the K axis.

Sub-byte weights stay packed in HBM *and* in the VMEM tile: each K step
DMAs one group's packed bytes (0.5–0.75 B/element instead of 1–2) and
expands them to int8 in-kernel right before the MXU dot. That is the
bandwidth win FIT's sub-8-bit allocations pay for: at W4A8 the weight
stream is 4× smaller than fp16 and 2× smaller than int8.

Grouped dequantization is fused into the accumulation: the grid is
(M/bm, N/bn, G) with the GROUP axis innermost and bk = K/G, so each K
step computes one group's exact int32 partial dot and folds it into an
fp32 VMEM accumulator scaled by that group's (1, bn) weight scales:

    acc_f32 += int32_dot(x_tile, unpack(w_tile)) * w_scale[g]

On the last group the per-row activation scales multiply once and the
tile is written out. No dense int8 (let alone fp) copy of the weight
ever exists in any memory space.
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


def _validate(name: str, x_q, w_data, w_scale, bits: int, k: int) -> int:
    """Shared trace-time shape/numerics validation; returns the group
    size. Raises ValueError (NOT assert — asserts vanish under
    ``python -O`` and these guard exactness, RPR007/RPR201)."""
    m, k_in = x_q.shape
    if k_in != k:
        raise ValueError(f"{name}: x_q {x_q.shape} does not match k={k}")
    kp, n = w_data.shape
    if kp != packed_size(k, bits):
        raise ValueError(
            f"{name}: packed payload {w_data.shape} inconsistent with "
            f"logical K={k} at {bits} bits "
            f"(expected {packed_size(k, bits)} rows)")
    n_groups = w_scale.shape[0]
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
    # int32 overflow proof: worst-case group dot must stay below 2^31
    # (A8 activations — the engine's only dynamic activation grid)
    require_group_dot_safe(bits, 8, bk, where=name)
    return n_groups


def _scale_rows(w_scale):
    """(G, N) group scales -> (G, 1, N) fp32: one group's scale row is
    then a (1, 1, bn) block whose last two dims are legal TPU tiles."""
    return w_scale.astype(jnp.float32)[:, None, :]


def _qmm_kernel(x_ref, w_ref, ws_ref, xs_ref, o_ref, acc_ref,
                *, n_groups: int, bits: int):
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    if bits in PACKED_BITS:
        w = unpack_rows(w, bits)               # (bk, bn) int8, in-VMEM
    prod = jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    # fused grouped dequant: this group's exact int32 dot scaled into the
    # fp32 accumulator by its per-channel scales
    acc_ref[...] += prod.astype(jnp.float32) * ws_ref[0]

    @pl.when(g == n_groups - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] * xs_ref[...]).astype(o_ref.dtype)


def _qmm_groups_kernel(x_ref, w_ref, ws_ref, o_ref, *, bits: int):
    w = w_ref[...]
    if bits in PACKED_BITS:
        w = unpack_rows(w, bits)               # (bk, bn) int8, in-VMEM
    prod = jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    o_ref[0] = prod.astype(jnp.float32) * ws_ref[0]


@functools.partial(jax.jit, static_argnames=("bits", "k", "bm", "bn",
                                             "interpret"))
def qmm_groups_pallas(x_q: jnp.ndarray, w_data: jnp.ndarray,
                      w_scale: jnp.ndarray, bits: int, k: int,
                      bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                      interpret: bool = False):
    """Per-group scaled partial products: (M, K) int8 x packed (K*, N)
    -> (G, M, N) fp32 with NO group reduction (``ref.qmm_group_products``
    semantics; the tensor-parallel shard-local form of ``qmm_pallas``,
    where each shard runs over ITS group-scale rows and the engine
    combines shards with an exact zero-padded psum + canonical sum).
    """
    n_groups = _validate("qmm_groups_pallas", x_q, w_data, w_scale, bits, k)
    m, n = x_q.shape[0], w_data.shape[1]
    bk = k // n_groups
    bkp = packed_size(k, bits) // n_groups
    bm, bn = min(bm, m), min(bn, n)
    pm, pn = (-m) % bm, (-n) % bn
    if pm:
        x_q = jnp.pad(x_q, ((0, pm), (0, 0)))
    if pn:
        w_data = jnp.pad(w_data, ((0, 0), (0, pn)))
        w_scale = jnp.pad(w_scale, ((0, 0), (0, pn)))
    m2, n2 = m + pm, n + pn
    grid = (pl.cdiv(m2, bm), pl.cdiv(n2, bn), n_groups)

    out = pl.pallas_call(
        functools.partial(_qmm_groups_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, g: (i, g)),
            pl.BlockSpec((bkp, bn), lambda i, j, g: (g, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, g: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, j, g: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_groups, m2, n2), jnp.float32),
        interpret=interpret,
    )(x_q, w_data, _scale_rows(w_scale))
    return out[:, :m, :n]


@functools.partial(jax.jit, static_argnames=("bits", "k", "bm", "bn",
                                             "out_dtype", "interpret"))
def qmm_pallas(x_q: jnp.ndarray, w_data: jnp.ndarray, x_scale: jnp.ndarray,
               w_scale: jnp.ndarray, bits: int, k: int,
               bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
               out_dtype=jnp.float32, interpret: bool = False):
    """x_q: (M, K) int8; w_data: packed payload of a logical (K, N)
    QTensor (K*, N) where K* = packed_size(K, bits); w_scale: (G, N)
    fp32 with G | K; x_scale: scalar or (M,)/(M, 1) per-row fp32.
    Returns (M, N) ``out_dtype``.
    """
    n_groups = _validate("qmm_pallas", x_q, w_data, w_scale, bits, k)
    m, n = x_q.shape[0], w_data.shape[1]
    bk = k // n_groups                          # one group per K step
    bkp = packed_size(k, bits) // n_groups      # packed rows per step
    bm, bn = min(bm, m), min(bn, n)
    # pad M and N to block multiples (K is never padded: groups are exact)
    pm, pn = (-m) % bm, (-n) % bn
    if pm:
        x_q = jnp.pad(x_q, ((0, pm), (0, 0)))
    if pn:
        w_data = jnp.pad(w_data, ((0, 0), (0, pn)))
        w_scale = jnp.pad(w_scale, ((0, 0), (0, pn)))
    x_scale = jnp.asarray(x_scale, jnp.float32).reshape(-1)
    if x_scale.size == 1:
        x_scale = jnp.broadcast_to(x_scale, (m,))
    x_scale = jnp.pad(x_scale, (0, pm))
    m2, n2 = m + pm, n + pn
    grid = (pl.cdiv(m2, bm), pl.cdiv(n2, bn), n_groups)

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_groups=n_groups, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, g: (i, g)),
            pl.BlockSpec((bkp, bn), lambda i, j, g: (g, j)),
            pl.BlockSpec((1, 1, bn), lambda i, j, g: (g, 0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, g: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, g: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m2, n2), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x_q, w_data, _scale_rows(w_scale), x_scale.reshape(m2, 1))
    return out[:m, :n]


def saturation_stats(x_q):
    """(saturated, total) element counts of an int8 activation block —
    |x| == 127 means the row-wise quantizer clipped (the activation
    outgrew its per-row scale). Sampled into the ``act_sat`` /
    ``act_elems`` device counters by the obs-enabled engine; f32 so the
    running sums stay cheap on the VPU."""
    sat = jnp.sum((jnp.abs(x_q.astype(jnp.int32)) >= 127)
                  .astype(jnp.float32))
    return sat, jnp.float32(x_q.size)
