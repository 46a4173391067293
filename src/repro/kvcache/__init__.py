"""repro.kvcache — paged, FIT-quantized KV-cache subsystem.

The serving engine's attention state, reorganized from one dense
``(layers, slots, max_len, KV, Dh)`` buffer into a pool of fixed-size
pages plus per-slot page tables:

            physical page pool (per attention layer)
            ┌────┬────┬────┬────┬────┬────┬────┬────┐
    k/v     │ p0 │ p1 │ p2 │ p3 │ p4 │ p5 │ p6 │ …  │  (P, KV, page, Dh)
            └────┴────┴────┴────┴────┴────┴────┴────┘
              ▲     ▲     ▲           ▲     ▲
    slot 0:  [p0,   p1,   p2,  ·  ]   │     │   table (S, NP) int32
    slot 1:  [p0,   p1,   p4,  p5 ]───┴─────┘   (· = sentinel >= P)
              └── shared prefix (refcounted, copy-on-write)

  * ``allocator`` — host-side block allocator: free-list recycling,
    per-request page tables, hash-based prefix sharing (identical prompt
    prefixes resolve to the same physical pages) with copy-on-write when
    a shared page must diverge, and reservation accounting so admission
    never deadlocks mid-decode.
  * ``paged`` — device-side storage: per-layer page arrays at per-layer
    bit widths on the framework-wide ``repro.qtensor`` packed layouts
    (fp / int8 / 6-bit / nibble 4- and 3-bit, per-page per-kv-head
    dequant scales), page-table state, write/gather/copy primitives,
    and HBM accounting.
  * ``fit`` — FIT-driven KV bit allocation: the per-layer k/v cache
    entries are activation sites of the sensitivity report (the KV cache
    is a persistent activation — paper Sec. 3.2), so
    ``repro.core.mpq.allocate_act_sites`` assigns per-layer KV bit
    widths under an HBM budget exactly like the weight allocators.

A slot's logical position ``t`` lives at page ``table[slot, t // page]``,
offset ``t % page``. Reads walk the table (``kernels.paged_attention``
on TPU, the gather-based jnp oracle elsewhere); decode writes scatter
one token into the slot's current page. Memory is O(actual tokens), not
O(slots x max_len) — short requests stop paying for long ones.
"""
from repro.kvcache.allocator import BlockAllocator
from repro.kvcache.fit import (
    allocate_kv_bits, kv_bit_config, kv_bits_from_config, kv_report_fns,
    kv_sites)
from repro.kvcache.paged import (
    LayerPages, PagedKVConfig, PagedState, dense_kv_bytes, init_paged_kv,
    kv_layer_count, layer_page_bytes, per_shard_pool_bytes, pool_bytes)

__all__ = [
    "BlockAllocator", "LayerPages", "PagedKVConfig", "PagedState",
    "allocate_kv_bits", "dense_kv_bytes", "init_paged_kv", "kv_bit_config",
    "kv_bits_from_config", "kv_layer_count", "kv_report_fns", "kv_sites",
    "layer_page_bytes", "per_shard_pool_bytes", "pool_bytes",
]
