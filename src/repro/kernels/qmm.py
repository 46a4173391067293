"""Pallas TPU kernel: fused grouped-scale quantized matmul (W{8,6,4,3}A8).

The QTensor serving hot path: activations are int8 with per-ROW dynamic
scales (batch-composition invariance, like ``int8_matmul``); weights are
a packed ``repro.qtensor`` payload — int8 bytes at W8, 2-per-byte
nibbles at W4/W3, 4-values-in-3-bytes at W6 — with per-output-channel
per-group scales ``(G, N)`` along the K axis.

Sub-byte weights stay packed in HBM *and* in the VMEM tile: each K step
DMAs one packed weight tile (0.5–0.75 B/element instead of 1–2) and
expands it to int8 in-kernel, one scale group at a time, right before
the MXU dot. That is the bandwidth win FIT's sub-8-bit allocations pay
for: at W4A8 the weight stream is 4× smaller than fp16 and 2× smaller
than int8.

Grouped dequantization is fused into the accumulation: the grid is
(M/bm, N/bn, K/bk) with the K axis innermost and bk = gps × group, so
one K step streams ``gps`` whole scale groups (a 0.5–2 MiB tile where
the shapes allow: a grid step has a fixed cost of a few tenths of a µs,
so one group per step spent far more time stepping than streaming).
Inside a step a loop visits the step's groups in K order; each
computes its exact int32 partial dot and folds it into the fp32
accumulator scaled by that group's (1, bn) weight scales:

    acc_f32 += int32_dot(x[:, grp], unpack(w[grp, :])) * w_scale[grp]

That is the one-group-per-step kernel's order of operations, so the
result is bit-identical whatever ``gps``. On the last K step the per-row
activation scales multiply once and the tile is written out. M and N
are covered with ``cdiv`` grids: a partial last block reads unspecified
values past the array's edge, which reach only output rows / columns
past the edge, and those are never written. No dense int8 (let alone
fp) copy of the weight ever exists in any memory space.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.bounds import require_group_dot_safe
from repro.qtensor import PACKED_BITS, logical_size, packed_size, unpack_rows

DEFAULT_BM, DEFAULT_BN = 256, 256   # qmm_groups_pallas's tiles
MAX_GROUP = 4096          # VMEM guard: one group's int8 tile must fit
# qmm_pallas's own tiles (choose_tiles): rows and lanes of one block at
# most, the packed weight bytes one K step streams at most, and the VMEM
# a step may hold (under the 16 MiB the TPU compiler scopes by default)
MAX_BM, MAX_BN = 256, 1024
W_TILE_BYTES = 2 << 20
VMEM_BUDGET = 12 << 20
CHUNK = 128               # rows of one unpack-and-dot (_chunk_rows)
# a step of at most this many weights unrolls its group loop; a larger one
# loops, which keeps the TPU compile of a 2 MiB step near a second (9-23 s
# unrolled), while XLA's CPU backend miscompiles a tiny int8 dot inside
# that nested loop (a 4x8 by 8x8 dot, in interpret mode)
UNROLL_WEIGHTS = 128 * 1024


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


def _chunk_rows(group: int) -> int:
    """Rows one unpack-and-dot of ``qmm_pallas`` takes: 128-row chunks of
    a larger group, since the TPU compiler's scratch for unpacking one
    sub-byte tile grows past the tiles themselves (a 2048-row 4-bit tile
    256 lanes wide took 15 MiB)."""
    return CHUNK if group % CHUNK == 0 else group


def _scale_rows(w_scale):
    """(G, N) group scales -> (G, 1, N) fp32: a block of whole groups'
    scale rows is then (groups, 1, bn), whose last two dims are legal TPU
    tiles."""
    return w_scale.astype(jnp.float32)[:, None, :]


def _qmm_kernel(x_ref, w_ref, ws_ref, xs_ref, o_ref, acc_ref,
                *, n_steps: int, gps: int, group: int, bits: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    chunk = _chunk_rows(group)
    cp, cpg = packed_size(chunk, bits), group // chunk

    def dot_rows(c):                           # int32 dot of the c-th chunk
        w = w_ref[pl.ds(pl.multiple_of(c * cp, cp), cp), :]
        if bits in PACKED_BITS:
            w = unpack_rows(w, bits)           # (chunk, bn) int8, in-VMEM
        x = x_ref[:, pl.ds(pl.multiple_of(c * chunk, chunk), chunk)]
        return jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    def fold(g, acc):                          # this step's groups, in K order
        # the group's exact int32 dot, chunk by chunk (int32 sums are
        # exact and bounded by the group's, so the split changes no bit)
        prod = dot_rows(g * cpg)
        if cpg > 1:
            prod = jax.lax.fori_loop(
                1, cpg, lambda c, p: p + dot_rows(g * cpg + c), prod)
        # fused grouped dequant: this group's exact int32 dot scaled into
        # the fp32 accumulator by its per-channel scales. Product first:
        # a compiler that contracts the multiply-add into an FMA then
        # contracts this group's product for any gps, as it does for the
        # one-group-per-step `acc += prod * scale`
        return prod.astype(jnp.float32) * ws_ref[g] + acc

    unroll = gps * group * acc_ref.shape[1] <= UNROLL_WEIGHTS
    acc_ref[...] = jax.lax.fori_loop(0, gps, fold, acc_ref[...], unroll=unroll)

    @pl.when(s == n_steps - 1)
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


def _rup(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def vmem_bytes(bm: int, bk: int, bn: int, bits: int, group: int) -> int:
    """An upper bound on the VMEM one ``qmm_pallas`` step holds, each
    buffer rounded up to the TPU tile its dtype is laid out in ((32, 128)
    for 8-bit, (8, 128) for 32-bit, a (1, bn) scale row taken as a whole
    8-sublane tile): the double-buffered activation, weight, scale and
    output blocks, the fp32 accumulator, and one chunk's working set: 24 B
    per unpacked sub-byte weight (the TPU compiler's scratch for the
    unpack, up to ~14 B per weight for a 128 x 1024 4-bit chunk compiled
    for a v5e), the int8 tile at 8 bits, and the int32 product with its
    fp32 copies."""
    gps = bk // group
    lanes_n, rows_m = _rup(bn, 128), _rup(bm, 8)
    x = _rup(bm, 32) * _rup(bk, 128)
    w = _rup(packed_size(bk, bits), 32) * lanes_n
    ws = gps * 8 * lanes_n * 4
    xs = rows_m * 128 * 4
    out = rows_m * lanes_n * 4
    acc = rows_m * lanes_n * 4
    per_weight = 24 if bits in PACKED_BITS else 1
    work = _rup(_chunk_rows(group), 32) * lanes_n * per_weight \
        + 3 * rows_m * lanes_n * 4
    return 2 * (x + w + ws + xs + out) + acc + work


def choose_tiles(m: int, k: int, n: int, bits: int,
                 group: int) -> tuple[int, int, int]:
    """(bm, bk, bn) for a ``qmm_pallas`` call, from its shapes alone.

    The fewest grid steps whose packed weight tile stays at or under
    ``W_TILE_BYTES`` and whose ``vmem_bytes`` stay under ``VMEM_BUDGET``:
    the widest ``bn`` (N itself when N <= MAX_BN, else a multiple of 128
    from MAX_BN / 2 to MAX_BN dividing N, else MAX_BN with a partial last
    block; narrower only where VMEM forces it), then the most whole scale
    groups per K step. Every block is legal for TPU tiling: rows of 8-bit
    blocks a multiple of 32 or the whole dimension, lanes a multiple of
    128 or the whole dimension. Where nothing fits, the smallest legal
    tiles.
    """
    bm = m if m <= MAX_BM else MAX_BM
    if n <= MAX_BN:
        widths = [n]
    else:
        widths = [b for b in range(MAX_BN, MAX_BN // 2 - 1, -128)
                  if n % b == 0] or [MAX_BN]
    widths += [b for b in (512, 256, 128) if b < widths[0]]
    n_groups = k // group
    for bn in widths:
        for gps in range(n_groups, 0, -1):
            bk = gps * group
            if n_groups % gps or not (
                    bk == k or (bk % 128 == 0
                                and packed_size(bk, bits) % 32 == 0)):
                continue
            tiles = bm, bk, bn
            if (packed_size(bk, bits) * bn <= W_TILE_BYTES
                    and vmem_bytes(bm, bk, bn, bits, group) <= VMEM_BUDGET):
                return tiles
    return tiles


@functools.partial(jax.jit, static_argnames=("bits", "k", "bm", "bn", "bk",
                                             "out_dtype", "interpret"))
def qmm_pallas(x_q: jnp.ndarray, w_data: jnp.ndarray, x_scale: jnp.ndarray,
               w_scale: jnp.ndarray, bits: int, k: int,
               bm: int | None = None, bn: int | None = None,
               bk: int | None = None, out_dtype=jnp.float32,
               interpret: bool = False):
    """x_q: (M, K) int8; w_data: packed payload of a logical (K, N)
    QTensor (K*, N) where K* = packed_size(K, bits); w_scale: (G, N)
    fp32 with G | K; x_scale: scalar or (M,)/(M, 1) per-row fp32.
    Returns (M, N) ``out_dtype``.

    Tiles left as None come from ``choose_tiles``; ``bk`` must be a
    whole number of scale groups that divides K.
    """
    n_groups = _validate("qmm_pallas", x_q, w_data, w_scale, bits, k)
    m, n = x_q.shape[0], w_data.shape[1]
    group = k // n_groups
    cm, ck, cn = choose_tiles(m, k, n, bits, group)
    bm, bn, bk = min(bm or cm, m), min(bn or cn, n), bk or ck
    if bk % group or k % bk:
        raise ValueError(f"qmm_pallas: bk={bk} is not a whole number of "
                         f"{group}-row groups dividing K={k}")
    gps, bkp = bk // group, packed_size(bk, bits)
    x_scale = jnp.asarray(x_scale, jnp.float32).reshape(-1)
    if x_scale.size == 1:
        x_scale = jnp.broadcast_to(x_scale, (m,))
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), k // bk)

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_steps=k // bk, gps=gps,
                          group=group, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bkp, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((gps, 1, bn), lambda i, j, s: (s, 0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, s: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x_q, w_data, _scale_rows(w_scale), x_scale.reshape(m, 1))
    return out


def saturation_stats(x_q):
    """(saturated, total) element counts of an int8 activation block —
    |x| == 127 means the row-wise quantizer clipped (the activation
    outgrew its per-row scale). Sampled into the ``act_sat`` /
    ``act_elems`` device counters by the obs-enabled engine; f32 so the
    running sums stay cheap on the VPU."""
    sat = jnp.sum((jnp.abs(x_q.astype(jnp.int32)) >= 127)
                  .astype(jnp.float32))
    return sat, jnp.float32(x_q.size)
