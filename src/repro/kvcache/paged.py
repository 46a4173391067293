"""Device-side paged KV storage: per-layer page pools + page-table state.

Layout (see the package docstring for the page-table diagram): each
attention layer owns a kv-head-major ``(P, KV, page, Dh')`` pool for k
and v — one page of one head is a contiguous ``(page, Dh')`` tile, the
block the Pallas paged-attention kernel DMAs. Layers
are kept as a dict (not stacked on a leading axis) so every layer can
store at its OWN bit width — the FIT-allocated mixed-precision KV cache
stores an 8-bit layer as int8 bytes and sub-byte layers as packed uint8
(``repro.qtensor`` layouts: Dh/2 bytes at 4/3 bits, 3·Dh/4 at 6), which
a single stacked array could not express. This mirrors the unrolled
(``scan_layers=False``) parameter layout that quantized serving already
requires.

Pages speak the framework-wide QTensor convention: packing/unpacking and
the symmetric grid come from ``repro.qtensor`` — the SAME byte layout
and ±(2^(b-1)−1) grid the weight path packs — with per-page per-kv-head
scales stored as ``(P, KV)`` fp32 alongside each pool (a grouped QTensor
scale of shape (P, KV, 1, 1); ``LayerPages.k_qt`` exposes the view).
Scales are materialized from the sensitivity report's calibrated
activation ranges (``repro.core.report.act_ranges`` at the ``attn/k`` /
``attn/v`` tap sites) — the AIMET-style calibrated-range pattern — with
a static fallback matching the legacy dense int8 KV path. Widths without
a packed layout (7, 5) use the reduced symmetric grid inside int8,
exactly like the weight materializers.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.qtensor import (
    PACKED_BITS, QTensor, bytes_per_element, logical_size, pack,
    packed_size, qmax_for_bits as _qt_qmax, quantize_values, unpack)

# Fallback |activation| max when no calibrated range is supplied: matches
# the legacy dense int8 KV path's static scale (0.05 * 127 ≈ 6.35).
DEFAULT_KV_AMAX = 6.35


def kv_layer_count(cfg: ModelConfig) -> int:
    """Number of attention layers holding KV state (0 for pure SSM)."""
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        return cfg.num_layers
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.attn_period
    return 0


def qmax_for_bits(bits: int) -> float:
    return _qt_qmax(bits)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LayerPages:
    """One attention layer's page pool. ``bits`` is static pytree aux
    data (it selects storage dtype and quantization grid, which must be
    trace-time constants under jit). Payloads and scales follow the
    QTensor convention (pack axis = Dh, per-page per-kv-head scale
    groups); ``k_qt``/``v_qt`` expose the pool as actual QTensors."""

    k: jnp.ndarray          # (P, KV, page, Dh) fp/int8 | (P, KV, page, Dh') uint8
    v: jnp.ndarray
    k_scale: jnp.ndarray    # (P, KV) fp32 per-page per-kv-head dequant scale
    v_scale: jnp.ndarray
    bits: int = 16

    def tree_flatten(self):
        return (self.k, self.v, self.k_scale, self.v_scale), self.bits

    @classmethod
    def tree_unflatten(cls, bits, children):
        return cls(*children, bits=bits)

    @property
    def num_pages(self) -> int:
        return self.k.shape[0]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    def _logical_shape(self) -> Tuple[int, ...]:
        p, kv, page, hd = self.k.shape
        if self.bits < 16:
            hd = logical_size(hd, self.bits)
        return (p, kv, page, hd)

    def _as_qtensor(self, data: jnp.ndarray, scale: jnp.ndarray) -> QTensor:
        p, kv = data.shape[:2]
        return QTensor(data, scale.reshape(p, kv, 1, 1), self.bits,
                       self._logical_shape(), 3)

    @property
    def k_qt(self) -> QTensor:
        """The k pool as a QTensor (quantized pools only)."""
        return self._as_qtensor(self.k, self.k_scale)

    @property
    def v_qt(self) -> QTensor:
        return self._as_qtensor(self.v, self.v_scale)


class PagedState(NamedTuple):
    """Paged KV component of a decode state (slots share one pool)."""

    layers: Dict[str, LayerPages]   # attn-layer index (as str) -> pool
    table: jnp.ndarray              # (S, NP) int32; entries >= P = unmapped
    write_limit: jnp.ndarray        # (S,) int32 — positions >= limit drop


@dataclasses.dataclass(frozen=True)
class PagedKVConfig:
    """Static shape of a paged KV cache pool."""

    page_size: int                  # tokens per page
    num_pages: int                  # pool size (shared by all slots)
    pages_per_slot: int             # NP — page-table width (max_len / page)
    kv_bits: Tuple[int, ...]        # per attention layer (16 = fp)

    @classmethod
    def build(cls, cfg: ModelConfig, max_len: int, slots: int,
              page_size: int = 16, num_pages: Optional[int] = None,
              kv_bits=None) -> "PagedKVConfig":
        """``kv_bits``: None/int uniform, or a mapping {layer index ->
        bits} (missing layers stay fp) — e.g. from ``fit.allocate_kv_bits``."""
        n = kv_layer_count(cfg)
        if n == 0:
            raise ValueError(f"family {cfg.family!r} holds no KV cache")
        if max_len % page_size:
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of page_size "
                f"({page_size}) — the paged-vs-dense parity contract needs "
                "equal attention spans")
        if kv_bits is None:
            bits = (16,) * n
        elif isinstance(kv_bits, int):
            bits = (kv_bits,) * n
        else:
            bits = tuple(int(kv_bits.get(i, kv_bits.get(str(i), 16)))
                         for i in range(n))
        for b in bits:
            if b in PACKED_BITS and logical_size(packed_size(cfg.head_dim, b),
                                                 b) != cfg.head_dim:
                raise ValueError(
                    f"packed {b}-bit KV needs head_dim ({cfg.head_dim}) "
                    f"divisible by its pack unit")
        nps = max_len // page_size
        return cls(page_size=page_size,
                   num_pages=num_pages if num_pages else slots * nps,
                   pages_per_slot=nps, kv_bits=bits)


def _scale_from_ranges(ranges, site: str, bits: int) -> float:
    if ranges is not None and site in ranges:
        lo, hi = ranges[site]
        amax = max(abs(float(lo)), abs(float(hi)), 1e-8)
    else:
        amax = DEFAULT_KV_AMAX
    return amax / qmax_for_bits(bits)


def kv_sites_for_layer(cfg: ModelConfig, i: int) -> Tuple[str, str]:
    """Scoped tap paths of layer ``i``'s k/v activation sites — the names
    the unrolled forward emits (and the sensitivity report records)."""
    base = f"shared/{i}/attn" if cfg.family == "hybrid" else f"layers/{i}/attn"
    return f"{base}/k", f"{base}/v"


def init_paged_kv(cfg: ModelConfig, pcfg: PagedKVConfig, slots: int,
                  ranges: Optional[Mapping[str, Tuple[float, float]]] = None
                  ) -> PagedState:
    """Zeroed pools + unmapped page tables. ``ranges`` (site -> (lo, hi),
    from ``SensitivityReport.act_ranges``) calibrate the dequant scales."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    layers: Dict[str, LayerPages] = {}
    for i, bits in enumerate(pcfg.kv_bits):
        if bits >= 16:
            dtype, last = cfg.param_dtype, hd
        elif bits in PACKED_BITS:
            dtype, last = jnp.uint8, packed_size(hd, bits)
        else:
            dtype, last = jnp.int8, hd          # grid-reduced int8 (7, 5, 8)
        shape = (pcfg.num_pages, kv, pcfg.page_size, last)
        ksite, vsite = kv_sites_for_layer(cfg, i)
        layers[str(i)] = LayerPages(
            k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
            k_scale=jnp.full((pcfg.num_pages, kv),
                             _scale_from_ranges(ranges, ksite, bits),
                             jnp.float32),
            v_scale=jnp.full((pcfg.num_pages, kv),
                             _scale_from_ranges(ranges, vsite, bits),
                             jnp.float32),
            bits=bits)
    return PagedState(
        layers=layers,
        table=jnp.full((slots, pcfg.pages_per_slot), pcfg.num_pages,
                       jnp.int32),
        write_limit=jnp.zeros(slots, jnp.int32))


def quantize_kv(x: jnp.ndarray, scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Float (..., KV, Dh) -> page storage dtype at ``bits`` on the
    QTensor grid/byte layout. ``scale``: (..., KV) per-kv-head."""
    q = quantize_values(x, scale[..., None], bits)
    return pack(q, bits, axis=-1) if bits in PACKED_BITS else q


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Inverse of ``quantize_kv`` (fp32 output)."""
    q = unpack(q, bits, axis=-1)
    return q.astype(jnp.float32) * scale[..., None]


def gather_layer(lp: LayerPages, row: jnp.ndarray, n_tokens,
                 out_dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Page row -> dense (NP*page, KV, Dh) cache span, zero past
    ``n_tokens`` (the prefix-reuse read: seeds a dense scratch state so
    suffix prefill attends to a shared prefix without recomputing it)."""
    ids = jnp.clip(row, 0, lp.num_pages - 1)
    kg = lp.k[ids].transpose(0, 2, 1, 3)           # (NP, page, KV, Dh')
    vg = lp.v[ids].transpose(0, 2, 1, 3)
    if lp.bits < 16:
        kg = dequantize_kv(kg, lp.k_scale[ids][:, None, :], lp.bits)
        vg = dequantize_kv(vg, lp.v_scale[ids][:, None, :], lp.bits)
    t = row.shape[0] * lp.page_size
    kg = kg.reshape(t, *kg.shape[2:]).astype(out_dtype)
    vg = vg.reshape(t, *vg.shape[2:]).astype(out_dtype)
    valid = (jnp.arange(t) < n_tokens)[:, None, None]
    return jnp.where(valid, kg, 0), jnp.where(valid, vg, 0)


def scatter_span(lp: LayerPages, row: jnp.ndarray, k_span: jnp.ndarray,
                 v_span: jnp.ndarray, start, stop) -> LayerPages:
    """Write dense tokens [start, stop) of (T, KV, Dh) spans into the
    pages of ``row`` (the admission insert: prefilled KV -> pool)."""
    t = k_span.shape[0]
    pos = jnp.arange(t)
    cols = pos // lp.page_size
    valid = (pos >= start) & (pos < stop)
    pids = jnp.where(valid, row[jnp.clip(cols, 0, row.shape[0] - 1)],
                     lp.num_pages)
    offs = pos % lp.page_size
    sp = jnp.clip(pids, 0, lp.num_pages - 1)
    if lp.bits < 16:
        kq = quantize_kv(k_span, lp.k_scale[sp], lp.bits)
        vq = quantize_kv(v_span, lp.v_scale[sp], lp.bits)
    else:
        kq, vq = k_span.astype(lp.k.dtype), v_span.astype(lp.v.dtype)
    return dataclasses.replace(
        lp,
        k=lp.k.at[pids, :, offs].set(kq, mode="drop"),
        v=lp.v.at[pids, :, offs].set(vq, mode="drop"))


def copy_page(lp: LayerPages, src, dst) -> LayerPages:
    """Physical page copy (the copy-on-write primitive)."""
    return dataclasses.replace(
        lp,
        k=lp.k.at[dst].set(lp.k[src]),
        v=lp.v.at[dst].set(lp.v[src]),
        k_scale=lp.k_scale.at[dst].set(lp.k_scale[src]),
        v_scale=lp.v_scale.at[dst].set(lp.v_scale[src]))


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------

def _bytes_per_elem(cfg: ModelConfig, bits: int) -> float:
    return bytes_per_element(bits, jnp.dtype(cfg.param_dtype).itemsize)


def layer_page_bytes(cfg: ModelConfig, page_size: int, bits: int) -> float:
    """Bytes of ONE page (k + v) of one layer at ``bits``."""
    elems = page_size * cfg.num_kv_heads * cfg.head_dim
    return 2 * elems * _bytes_per_elem(cfg, bits)


def page_bytes_all_layers(cfg: ModelConfig, pcfg: PagedKVConfig) -> float:
    """Bytes one logical page costs summed over every layer's pool."""
    return sum(layer_page_bytes(cfg, pcfg.page_size, b) for b in pcfg.kv_bits)


def pool_bytes(cfg: ModelConfig, pcfg: PagedKVConfig) -> float:
    """Total HBM of the paged pools (scales excluded — O(P*KV) fp32)."""
    return pcfg.num_pages * page_bytes_all_layers(cfg, pcfg)


def per_shard_pool_bytes(cfg: ModelConfig, pcfg: PagedKVConfig,
                         tp_shards: int = 1) -> float:
    """HBM one device holds for the paged pools under tensor-parallel
    serving: pools shard by kv-head when ``num_kv_heads % tp_shards ==
    0`` (each shard stores 1/tp of every page), else they replicate and
    every device pays the full pool."""
    total = pool_bytes(cfg, pcfg)
    if tp_shards > 1 and cfg.num_kv_heads % tp_shards == 0:
        return total / tp_shards
    return total


def dense_kv_bytes(cfg: ModelConfig, slots: int, max_len: int,
                   bits: int = 16) -> float:
    """HBM of the dense per-slot cache this subsystem replaces."""
    n = kv_layer_count(cfg)
    elems = slots * max_len * cfg.num_kv_heads * cfg.head_dim
    return n * 2 * elems * _bytes_per_elem(cfg, bits)
