"""Pallas TPU kernel: paged-attention decode with in-kernel dequant.

One-token GQA decode where the KV cache lives in a paged pool: physical
pages of ``page_size`` tokens, per-slot page tables mapping logical
positions to pages (``repro.kvcache``). The kernel walks the page table
via scalar prefetch — the table is available before the body runs, so
each grid step's BlockSpec index_map DMAs exactly the page it needs —
and never materializes the gathered (B, T, KV, Dh) view the jnp
reference builds.

Pools are kv-head-major, ``(P, KV, page, Dh')``: one grid step DMAs one
whole page (every kv head) as a ``(1, KV, page, Dh')`` block whose last
two dims are the array's own — the TPU block-shape rule — and the heads
are independent static slices of it (no cross-head math anywhere).

Quantized pages dequantize in-kernel: int8 or packed uint8 loads on the
``repro.qtensor`` byte layout (1 / 0.75 / 0.5 byte per element at
8 / 6 / 4-or-3 bits) expand to fp32 only in VMEM, with the per-page
per-kv-head scale fetched alongside the page. Packed bytes are never
interleaved back into logical order: byte lane r of a 4-bit page holds
head dims 2r and 2r+1, so the kernel splits the page into "planes" (all
low nibbles, all high nibbles), the wrapper hands q over in the same
plane order, and the output comes back plane-major — a dot product is
invariant to a permutation applied to both of its operands.

Grid: (B, NP) with the page axis innermost; fp32 online-softmax running
stats (m, l) and the output accumulator live in VMEM scratch across page
steps. Pages whose positions are entirely past a slot's length still run
(grid shapes are static) but are fully masked.

Tensor-parallel serving (``EngineConfig(mesh=...)``) shards the page
pools by kv-head: a shard simply invokes this kernel on its local (P,
KV/tp, page, Dh') pool block and local (P, KV/tp) scales — the decode is
purely local per shard and the engine concatenates head outputs with an
all-gather (exact, so the sharded read path stays bit-identical to the
replicated one).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.qtensor import PACKED_BITS, packed_size

NEG_INF = -1e30
# fp32-faithful MXU passes: a decode step's dots have G query rows, so the
# extra passes cost nothing next to streaming the pages
_F32 = jax.lax.Precision.HIGHEST


def _planes(bits: int) -> int:
    """Logical head dims per packed unit (1 for unpacked storage)."""
    return {6: 4, 4: 2, 3: 2}.get(bits, 1)


def _unpack_planes(raw, bits: int):
    """Page bytes (page, Dh') -> ``_planes(bits)`` int32 arrays of
    (page, Dh / planes): plane i holds head dims ``planes * r + i`` —
    the ``repro.qtensor`` byte layout read without a lane interleave."""
    u = raw.astype(jnp.int32)
    if bits in (4, 3):
        lo, hi = u & 0xF, (u >> 4) & 0xF
        return [jnp.where(v >= 8, v - 16, v) for v in (lo, hi)]
    # 6-bit: byte 3r + j of every 3-byte group, gathered by a one-hot
    # matmul (exact: bytes < 256 are exact in every MXU pass) — the TPU
    # has no strided lane slice
    n = u.shape[1] // 3
    src = jax.lax.broadcasted_iota(jnp.int32, (3 * n, n), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (3 * n, n), 1)
    uf = u.astype(jnp.float32)
    b0, b1, b2 = (jax.lax.dot_general(
        uf, (src == 3 * dst + j).astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32).astype(jnp.int32)
        for j in range(3))
    vs = [b0 & 0x3F,
          ((b0 >> 6) & 0x3) | ((b1 & 0xF) << 2),
          ((b1 >> 4) & 0xF) | ((b2 & 0x3) << 4),
          (b2 >> 2) & 0x3F]
    return [jnp.where(v >= 32, v - 64, v) for v in vs]


def _paged_attn_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, o_ref, m_ref, l_ref, acc_ref,
                       *, page: int, bits: int, kvh: int, dh: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_planes = _planes(bits)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(kvh):                           # static: heads independent
        k, v = k_ref[0, h], v_ref[0, h]            # (page, Dh')
        if bits in PACKED_BITS:
            kp, vp = _unpack_planes(k, bits), _unpack_planes(v, bits)
        else:
            kp, vp = [k], [v]
        if bits < 16:
            ks = ks_ref[0, 0, h]                   # SMEM page-head scalars
            vs = vs_ref[0, 0, h]
            kp = [x.astype(jnp.float32) * ks for x in kp]
            vp = [x.astype(jnp.float32) * vs for x in vp]
        else:
            kp = [x.astype(jnp.float32) for x in kp]
            vp = [x.astype(jnp.float32) for x in vp]

        s = None
        for i in range(n_planes):
            qi = q_ref[0, h, i].astype(jnp.float32)   # (G, Dh / planes)
            si = jax.lax.dot_general(qi, kp[i], (((1,), (1,)), ((), ())),
                                     precision=_F32,
                                     preferred_element_type=jnp.float32)
            s = si if s is None else s + si
        s = s * (dh ** -0.5)                       # (G, page)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < len_ref[b], s, NEG_INF)

        m_prev = m_ref[h]                          # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[h] = m_new
        for i in range(n_planes):
            acc_ref[h, i] = acc_ref[h, i] * alpha + jax.lax.dot_general(
                p, vp[i], (((1,), (0,)), ((), ())), precision=_F32,
                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        for h in range(kvh):
            l = jnp.maximum(l_ref[h], 1e-30)
            for i in range(n_planes):
                o_ref[0, h, i] = (acc_ref[h, i] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def paged_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, table: jnp.ndarray,
                           lengths: jnp.ndarray,
                           k_scale=None, v_scale=None,
                           bits: int = 16, interpret: bool = False):
    """q: (B, KV, G, Dh); k_pages/v_pages: (P, KV, page, Dh') where
    Dh' = qtensor.packed_size(Dh, bits); table: (B, NP) page ids (>= P allowed —
    clipped, those pages are masked); lengths: (B,) valid token counts.
    k_scale/v_scale: (P, KV) fp32 (required when bits < 16).
    Returns (B, KV, G, Dh)."""
    b, kvh, g, dh = q.shape
    num_pages, page, dhp = k_pages.shape[0], k_pages.shape[2], k_pages.shape[3]
    if k_pages.shape[1] != kvh or dhp != packed_size(dh, bits):
        raise ValueError(
            f"paged_attention_pallas: pool {k_pages.shape} is not "
            f"(P, KV={kvh}, page, {packed_size(dh, bits)}) for q {q.shape} "
            f"at {bits} bits")
    npg = table.shape[1]
    table = jnp.clip(table.astype(jnp.int32), 0, num_pages - 1)
    lengths = lengths.astype(jnp.int32)
    if k_scale is None:
        k_scale = jnp.ones((num_pages, kvh), jnp.float32)
    if v_scale is None:
        v_scale = jnp.ones((num_pages, kvh), jnp.float32)
    n_planes = _planes(bits)
    w = dh // n_planes
    # q in plane order: plane i holds head dims n_planes * r + i
    qp = q.reshape(b, kvh, g, w, n_planes).transpose(0, 1, 4, 2, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, npg),
        in_specs=[
            pl.BlockSpec((1, kvh, n_planes, g, w),
                         lambda bi, j, t, ln: (bi, 0, 0, 0, 0)),
            pl.BlockSpec((1, kvh, page, dhp),
                         lambda bi, j, t, ln: (t[bi, j], 0, 0, 0)),
            pl.BlockSpec((1, kvh, page, dhp),
                         lambda bi, j, t, ln: (t[bi, j], 0, 0, 0)),
            # one page's per-head scales are scalars, so they live in SMEM;
            # as (P, 1, KV) a (1, 1, KV) block is full in its last two dims
            pl.BlockSpec((1, 1, kvh), lambda bi, j, t, ln: (t[bi, j], 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, kvh), lambda bi, j, t, ln: (t[bi, j], 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, kvh, n_planes, g, w),
                               lambda bi, j, t, ln: (bi, 0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, g, 1), jnp.float32),           # running max
            pltpu.VMEM((kvh, g, 1), jnp.float32),           # running denom
            pltpu.VMEM((kvh, n_planes, g, w), jnp.float32),  # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, page=page, bits=bits, kvh=kvh,
                          dh=dh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, n_planes, g, w), q.dtype),
        interpret=interpret,
    )(table, lengths, qp, k_pages, v_pages,
      k_scale.astype(jnp.float32)[:, None, :],
      v_scale.astype(jnp.float32)[:, None, :])
    return out.transpose(0, 1, 3, 4, 2).reshape(b, kvh, g, dh)


def read_token_stats(pos):
    """Total KV tokens attended this call (sum over batch of pos + 1) —
    the ``paged_tokens_read`` device counter's per-call increment, f32."""
    return jnp.sum(pos.astype(jnp.float32) + 1.0)
