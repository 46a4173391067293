"""QTensor: the ONE quantized-tensor storage format of this framework.

A ``QTensor`` is a registered pytree holding

  * ``data``  — the packed payload (``int8`` for 8-bit, ``uint8`` for the
    sub-byte widths),
  * ``scale`` — fp32 symmetric dequantization scales whose shape encodes
    the granularity (see *scale semantics* below),
  * ``bits`` / ``shape`` / ``axis`` — static aux data: bit width, the
    LOGICAL array shape, and the axis the payload is packed along.

Every quantized storage consumer (``repro.serve`` weight blocks,
``repro.kvcache`` KV pages, ``repro.checkpoint`` round-trips) speaks this
format, so there is exactly one pack/unpack/scale convention in the
codebase and the Pallas kernels (``kernels.qmm``,
``kernels.paged_attention``) dequantize it in-kernel.

Storage layout per bit width (``bytes_per_element``):

  bits   payload             bytes/elem   grid
  16     (caller keeps fp)   2.0          —
  8      int8                1.0          ±127
  7, 5   int8 (grid-reduced) 1.0          ±63 / ±15
  6      3 bytes per 4 vals  0.75         ±31
  4      uint8 nibbles       0.5          ±7
  3      uint8 nibbles       0.5          ±3   (4-bit container)

Packing runs along ``axis``: adjacent logical elements share a byte
(pairs for 4/3-bit, little-endian 4-value/3-byte groups for 6-bit), so a
slice taken along any OTHER axis owns whole bytes — the property both
consumers rely on (a KV page write never read-modify-writes another
token's byte; a K-tile of a weight matmul DMAs contiguous rows).

Scale semantics: ``scale.ndim == len(shape)``; every dim is either 1
(broadcast), the full logical dim (per-element), or a divisor g of it
(g contiguous groups along that dim). ``expand_scale`` materializes the
broadcastable view. Weight blocks use per-output-channel-per-group
scales ``(K/group, N)`` for a ``(K, N)`` matmul; KV pages use per-page
per-kv-head scales ``(P, KV, 1, 1)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

# Widths with a true sub-int8 byte layout. Other widths below 16 store
# on the reduced symmetric grid inside int8 bytes (grid-reduced).
PACKED_BITS = (6, 4, 3)

# values-per-unit, bytes-per-unit of the packed byte layout
_UNITS = {6: (4, 3), 4: (2, 1), 3: (2, 1)}


def qmax_for_bits(bits: int) -> float:
    """Largest grid magnitude of the symmetric b-bit quantizer: the grid
    is the odd set {-qmax, .., -1, 0, 1, .., qmax} with qmax = 2^(b-1)-1
    (the integer-zero-point convention ``QuantSpec(symmetric=True)``
    shares — see ``repro.quant.quantizer``)."""
    return float(2 ** (min(bits, 8) - 1) - 1)


def bytes_per_element(bits: int, fp_bytes: float = 2.0) -> float:
    """Realized storage bytes per logical element at ``bits``."""
    if bits >= 16:
        return float(fp_bytes)
    if bits in _UNITS:
        vals, nbytes = _UNITS[bits]
        return nbytes / vals
    return 1.0


def packed_size(n: int, bits: int) -> int:
    """Length of the packed axis for ``n`` logical elements."""
    if bits not in _UNITS:
        return n
    vals, nbytes = _UNITS[bits]
    return -(-n // vals) * nbytes


def logical_size(packed_n: int, bits: int) -> int:
    """Inverse of ``packed_size`` (exact when the axis was not padded)."""
    if bits not in _UNITS:
        return packed_n
    vals, nbytes = _UNITS[bits]
    return packed_n * vals // nbytes


def _pack_last(q: jnp.ndarray, bits: int) -> jnp.ndarray:
    """int8 grid values -> packed uint8 bytes along the LAST axis."""
    vals, _ = _UNITS[bits]
    n = q.shape[-1]
    pad = (-n) % vals
    if pad:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])
    u = q.astype(jnp.int32)
    if bits in (4, 3):
        # byte r = (element 2r in the low nibble, element 2r+1 high) —
        # 3-bit values ride the same 4-bit container
        lo, hi = u[..., 0::2] & 0xF, u[..., 1::2] & 0xF
        return (lo | (hi << 4)).astype(jnp.uint8)
    # 6-bit: 4 values -> 3 bytes, little-endian within the group
    g = (u & 0x3F).reshape(u.shape[:-1] + ((n + pad) // 4, 4))
    v0, v1, v2, v3 = g[..., 0], g[..., 1], g[..., 2], g[..., 3]
    b0 = v0 | ((v1 & 0x3) << 6)
    b1 = (v1 >> 2) | ((v2 & 0xF) << 4)
    b2 = (v2 >> 4) | (v3 << 2)
    out = jnp.stack([b0, b1, b2], axis=-1)
    return out.reshape(u.shape[:-1] + (3 * (n + pad) // 4,)).astype(jnp.uint8)


def _unpack_last(p: jnp.ndarray, bits: int,
                 n: Optional[int] = None) -> jnp.ndarray:
    """Inverse of ``_pack_last``; ``n`` trims padding (defaults to the
    full unpacked length)."""
    u = p.astype(jnp.int32)
    if bits in (4, 3):
        v = jnp.stack([u & 0xF, (u >> 4) & 0xF], axis=-1)
        v = v.reshape(u.shape[:-1] + (2 * u.shape[-1],))
        v = jnp.where(v >= 8, v - 16, v)
    else:
        g = u.reshape(u.shape[:-1] + (u.shape[-1] // 3, 3))
        b0, b1, b2 = g[..., 0], g[..., 1], g[..., 2]
        v0 = b0 & 0x3F
        v1 = ((b0 >> 6) & 0x3) | ((b1 & 0xF) << 2)
        v2 = ((b1 >> 4) & 0xF) | ((b2 & 0x3) << 4)
        v3 = (b2 >> 2) & 0x3F
        v = jnp.stack([v0, v1, v2, v3], axis=-1)
        v = v.reshape(u.shape[:-1] + (4 * (u.shape[-1] // 3),))
        v = jnp.where(v >= 32, v - 64, v)
    if n is not None:
        v = v[..., :n]
    return v.astype(jnp.int8)


def pack(q: jnp.ndarray, bits: int, axis: int = -1) -> jnp.ndarray:
    """Pack int8 grid values into sub-byte storage along ``axis``.

    ``bits`` 8/7/5 are a no-op int8 cast (grid-reduced storage); 6/4/3
    produce the byte layouts documented in the module docstring.
    """
    if bits not in _UNITS:
        return q.astype(jnp.int8)
    ax = axis % q.ndim
    if ax == q.ndim - 1:
        return _pack_last(q, bits)
    return jnp.moveaxis(_pack_last(jnp.moveaxis(q, ax, -1), bits), -1, ax)


def unpack(p: jnp.ndarray, bits: int, size: Optional[int] = None,
           axis: int = -1) -> jnp.ndarray:
    """Packed payload -> int8 grid values (inverse of ``pack``).

    ``size`` is the logical length of ``axis`` (trims pack padding).
    """
    if bits not in _UNITS:
        return p
    ax = axis % p.ndim
    if ax == p.ndim - 1:
        return _unpack_last(p, bits, size)
    return jnp.moveaxis(_unpack_last(jnp.moveaxis(p, ax, -1), bits, size),
                        -1, ax)


def unpack_rows(p: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Axis-0 unpack of a 2-D payload, written for in-kernel use.

    (Kp, N) packed bytes -> (K, N) int8 values using only reshapes that
    keep the lane (last) dim intact plus a leading-dim interleave — the
    form the Pallas ``qmm`` kernel lowers. Equivalent to
    ``unpack(p, bits, axis=0)``.
    """
    u = p.astype(jnp.int32)
    kp, n = u.shape
    if bits in (4, 3):
        v = jnp.stack([u & 0xF, (u >> 4) & 0xF], axis=1)    # (Kp, 2, N)
        v = v.reshape(2 * kp, n)
        v = jnp.where(v >= 8, v - 16, v)
    elif bits == 6:
        g = u.reshape(kp // 3, 3, n)
        b0, b1, b2 = g[:, 0], g[:, 1], g[:, 2]
        v0 = b0 & 0x3F
        v1 = ((b0 >> 6) & 0x3) | ((b1 & 0xF) << 2)
        v2 = ((b1 >> 4) & 0xF) | ((b2 & 0x3) << 4)
        v3 = (b2 >> 2) & 0x3F
        v = jnp.stack([v0, v1, v2, v3], axis=1)             # (Kp/3, 4, N)
        v = v.reshape(4 * (kp // 3), n)
        v = jnp.where(v >= 32, v - 64, v)
    else:
        return p
    return v.astype(jnp.int8)


def expand_scale(scale: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Materialize a grouped scale as a broadcastable view of ``shape``:
    dims of size 1 or full broadcast as-is; a divisor dim g repeats each
    scale over its contiguous group of ``shape[d] // g`` elements."""
    s = scale
    for d, (sd, full) in enumerate(zip(s.shape, shape)):
        if sd not in (1, full):
            if full % sd:
                raise ValueError(
                    f"scale dim {d} ({sd}) does not divide logical {full}")
            s = jnp.repeat(s, full // sd, axis=d)
    return s


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """Packed quantized tensor (see module docstring).

    ``bits``/``shape``/``axis`` are static pytree aux data — they select
    byte layout and grid, which must be trace-time constants under jit.
    """

    data: jnp.ndarray        # packed payload (int8 or uint8)
    scale: jnp.ndarray       # fp32, grouped per the module scale semantics
    bits: int
    shape: Tuple[int, ...]   # logical shape
    axis: int                # pack axis (normalized, static)

    def tree_flatten(self):
        return (self.data, self.scale), (self.bits, self.shape, self.axis)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale = children
        bits, shape, axis = aux
        return cls(data, scale, bits, shape, axis)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def nbytes(self) -> int:
        """Payload HBM bytes (scales excluded — see ``scale_bytes``)."""
        import numpy as _np
        return int(_np.prod(self.data.shape)) * jnp.dtype(self.data.dtype).itemsize

    @property
    def scale_bytes(self) -> int:
        import numpy as _np
        return int(_np.prod(self.scale.shape)) * 4

    @property
    def group_size(self) -> int:
        """Elements per scale group along the pack axis."""
        return self.shape[self.axis] // self.scale.shape[self.axis]

    def unpack(self) -> jnp.ndarray:
        """Payload -> int8 grid values at the logical shape."""
        return unpack(self.data, self.bits, self.shape[self.axis], self.axis)

    def dequantize(self, dtype=jnp.float32) -> jnp.ndarray:
        """Unpack and apply scales -> dense array of ``dtype``.

        At 8 bits with a single scale group this computes exactly
        ``data.astype(f32) * scale`` then casts — bit-identical to the
        legacy int8 serving path.
        """
        q = self.unpack()
        s = expand_scale(self.scale, self.shape)
        return (q.astype(jnp.float32) * s).astype(dtype)


def quantize_values(x: jnp.ndarray, scale: jnp.ndarray,
                    bits: int) -> jnp.ndarray:
    """Float values -> int8 grid at ``bits`` with caller-supplied
    (broadcastable) scales: ``clip(round(x / scale), ±qmax)``."""
    qmax = qmax_for_bits(bits)
    x32 = x.astype(jnp.float32)
    return jnp.clip(jnp.round(x32 / scale), -qmax, qmax).astype(jnp.int8)


def quantize(x: jnp.ndarray, bits: int, group_size: Optional[int] = None,
             axis: Optional[int] = None,
             scale: Optional[jnp.ndarray] = None) -> QTensor:
    """Symmetric per-(group, out-channel) quantization -> packed QTensor.

    The out-channel is the LAST axis (one scale per column); groups run
    along ``axis`` (default: second-to-last, the matmul reduction axis).
    ``group_size=None`` uses one group — per-output-channel scales, the
    legacy serving granularity (bit-identical to it at 8 bits). A
    caller-supplied ``scale`` (shaped per the module scale semantics)
    skips calibration — the KV-page path with calibrated ranges.
    """
    if x.ndim < 2:
        raise ValueError("QTensor quantization needs a matrix-like input "
                         f"(got shape {x.shape}); vectors stay fp")
    ax = (x.ndim - 2 if axis is None else axis % x.ndim)
    if ax == x.ndim - 1:
        raise ValueError("pack axis cannot be the out-channel (last) axis")
    k = x.shape[ax]
    gs = k if group_size is None else min(group_size, k)
    if k % gs:
        raise ValueError(f"group_size {gs} does not divide axis {ax} ({k})")
    if bits in _UNITS:
        if k % _UNITS[bits][0]:
            raise ValueError(
                f"{bits}-bit packing needs axis {ax} ({k}) divisible by "
                f"{_UNITS[bits][0]}")
        if gs % _UNITS[bits][0]:
            # a scale group must hold whole pack units, or the qmm
            # kernel's per-group payload tiles split a byte/3-byte unit
            raise ValueError(
                f"group_size {gs} must be a multiple of the {bits}-bit "
                f"pack unit ({_UNITS[bits][0]})")
    qmax = qmax_for_bits(bits)
    x32 = x.astype(jnp.float32)
    if scale is None:
        # |max| per (group, out-channel), reduced over everything else
        a = jnp.moveaxis(jnp.abs(x32), ax, 0)
        a = a.reshape((k // gs, gs) + a.shape[1:])
        red = tuple(range(1, a.ndim - 1))            # keep groups + channel
        amax = jnp.max(a, axis=red)                  # (G, C)
        sshape = [1] * x.ndim
        sshape[ax], sshape[-1] = k // gs, x.shape[-1]
        scale = (jnp.maximum(amax, 1e-12) / qmax).reshape(sshape)
    q = quantize_values(x32, expand_scale(scale, x.shape), bits)
    return QTensor(pack(q, bits, ax), scale.astype(jnp.float32), bits,
                   tuple(x.shape), ax)


def quantize_experts(x: jnp.ndarray, bits: int,
                     group_size: Optional[int] = None) -> QTensor:
    """Quantize a stacked expert weight tensor (E, K, N) with PER-EXPERT
    per-(group, out-channel) scales -> packed QTensor.

    ``quantize`` on a 3-D input reduces |max| over the leading dims too,
    sharing one (1, G, N) scale grid across all experts — fine for a
    fp-dequant einsum but it couples every expert's grid to the loudest
    one and makes the stack unshardable by expert (a shard would need
    scales it does not own). This variant keeps the expert dim in the
    scale grid, (E, G, N), so slicing expert ``e`` yields exactly
    ``quantize(x[e], bits, group_size)`` bit-for-bit: the per-expert 2-D
    view IS a valid ``kernels.qmm`` block, and expert-parallel sharding
    along dim 0 carries whole self-contained experts
    (``shard_error(qt, n, 0) is None`` whenever ``n`` divides E).
    """
    if x.ndim != 3:
        raise ValueError(f"expert stacks are 3-D (E, K, N); got {x.shape}")
    e, k, n = x.shape
    gs = k if group_size is None else min(group_size, k)
    if k % gs:
        raise ValueError(f"group_size {gs} does not divide K ({k})")
    if bits in _UNITS:
        if k % _UNITS[bits][0]:
            raise ValueError(
                f"{bits}-bit packing needs K ({k}) divisible by "
                f"{_UNITS[bits][0]}")
        if gs % _UNITS[bits][0]:
            raise ValueError(
                f"group_size {gs} must be a multiple of the {bits}-bit "
                f"pack unit ({_UNITS[bits][0]})")
    qmax = qmax_for_bits(bits)
    x32 = x.astype(jnp.float32)
    a = jnp.abs(x32).reshape(e, k // gs, gs, n)
    amax = jnp.max(a, axis=2)                    # (E, G, N) — expert kept
    scale = (jnp.maximum(amax, 1e-12) / qmax).astype(jnp.float32)
    q = quantize_values(x32, expand_scale(scale, x.shape), bits)
    return QTensor(pack(q, bits, 1), scale, bits, tuple(x.shape), 1)


def expert_slice(qt: QTensor, e: int) -> QTensor:
    """Expert ``e`` of a ``quantize_experts`` stack as a self-contained
    2-D (K, N) QTensor — the dense-loop oracle's per-expert ``qmm``
    block. Pack axis 1 means the expert dim owns whole bytes, so this is
    a pure slice of payload and scales."""
    if qt.ndim != 3:
        raise ValueError(f"expert_slice needs a 3-D QTensor; got {qt.shape}")
    scale = qt.scale[e] if qt.scale.shape[0] == qt.shape[0] else qt.scale[0]
    return QTensor(qt.data[e], scale, qt.bits, qt.shape[1:],
                   qt.axis - 1 if qt.axis else 0)


def pack_unit(bits: int) -> int:
    """Logical elements per indivisible pack unit (1 for unpacked widths)."""
    return _UNITS[bits][0] if bits in _UNITS else 1


def shard_error(qt: QTensor, n: int, axis: int) -> Optional[str]:
    """Why ``qt`` cannot be split into ``n`` equal shards along logical
    ``axis`` — or None if it can.

    The rules the tensor-parallel serving path relies on:

      * the logical dim must divide evenly into ``n`` shards;
      * on the PACK axis a shard boundary must not split a pack unit
        (the 6-bit 3-byte/4-value group is the sharp case) and must
        align with scale-group boundaries — each shard owns whole
        groups, so per-shard dequantization needs no neighbour's scale
        (the ``qmm`` sharded path's per-shard group-scale offsets);
      * on any other axis, a grouped scale dim must itself split evenly
        (dims of size 1 broadcast and need no split).
    """
    ax = axis % qt.ndim
    d = qt.shape[ax]
    if n < 1:
        return f"shard count must be >= 1 (got {n})"
    if d % n:
        return f"logical dim {ax} ({d}) does not divide into {n} shards"
    span = d // n
    if ax == qt.axis:
        unit = pack_unit(qt.bits)
        if span % unit:
            return (f"shard span {span} splits a {qt.bits}-bit pack unit "
                    f"({unit} values) on the pack axis")
        g = qt.scale.shape[ax]
        if g not in (1, d) and g % n:
            return (f"{g} scale groups do not align with {n} shard "
                    "boundaries on the pack axis")
        if g == 1 and n > 1:
            return ("a single scale group spans the whole pack axis and "
                    "cannot be split — requantize with group boundaries "
                    "aligned to shard boundaries (group_size a divisor "
                    f"of {span})")
    else:
        sd = qt.scale.shape[ax]
        if sd not in (1, d) and sd % n:
            return (f"scale dim {ax} ({sd} groups) does not divide into "
                    f"{n} shards")
    return None


def shard(qt: QTensor, n: int, axis: int) -> Tuple[QTensor, ...]:
    """Split a QTensor into ``n`` equal shards along logical ``axis``.

    Payload bytes are sliced in PACKED coordinates (whole pack units per
    shard — validated) and the grouped scales are co-sharded along the
    same axis, so every shard is a self-contained QTensor:
    ``jnp.concatenate([s.dequantize() for s in shards], axis)`` is
    bit-identical to ``qt.dequantize()``. Raises ValueError with the
    reason from ``shard_error`` when the split is impossible.
    """
    err = shard_error(qt, n, axis)
    if err:
        raise ValueError(f"cannot shard QTensor{qt.shape} "
                         f"{qt.bits}-bit x{n} on axis {axis}: {err}")
    ax = axis % qt.ndim
    span = qt.shape[ax] // n
    dspan = qt.data.shape[ax] // n          # packed span (whole units)
    sd = qt.scale.shape[ax]
    sspan = sd // n if sd > 1 else 0

    def slc(arr, lo, width):
        idx = [slice(None)] * arr.ndim
        idx[ax] = slice(lo, lo + width)
        return arr[tuple(idx)]

    out = []
    shape = list(qt.shape)
    shape[ax] = span
    for i in range(n):
        data = slc(qt.data, i * dspan, dspan)
        scale = slc(qt.scale, i * sspan, sspan) if sspan else qt.scale
        out.append(QTensor(data, scale, qt.bits, tuple(shape), qt.axis))
    return tuple(out)


def is_qtensor(x: Any) -> bool:
    return isinstance(x, QTensor)


def tree_has_qtensor(tree: Any) -> bool:
    """True if any node of ``tree`` is a QTensor."""
    return any(isinstance(l, QTensor)
               for l in jax.tree_util.tree_leaves(tree, is_leaf=is_qtensor))


def storage_summary(tree: Any) -> dict:
    """Byte accounting of a tree's QUANTIZED blocks (QTensor nodes only),
    in every format the benchmarks compare:

      packed_bytes       realized packed payload + fp32 scales
      int8_backed_bytes  the same blocks int8-backed (1 B/elem) + scales
      fp16_bytes         the same blocks at fp16
      predicted_bytes    the BitConfig's promise, bits x elems / 8
      bit_histogram      {bits: block count}

    The single source of truth for the packed-vs-int8-vs-fp16 numbers in
    ``benchmarks/serve_bench.py`` and the examples.
    """
    import numpy as _np
    out = {"packed_bytes": 0.0, "int8_backed_bytes": 0.0, "fp16_bytes": 0.0,
           "predicted_bytes": 0.0, "bit_histogram": {}}
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_qtensor):
        if not isinstance(leaf, QTensor):
            continue
        elems = float(_np.prod(leaf.shape))
        out["packed_bytes"] += leaf.nbytes + leaf.scale_bytes
        out["int8_backed_bytes"] += elems + leaf.scale_bytes
        out["fp16_bytes"] += 2 * elems
        out["predicted_bytes"] += leaf.bits * elems / 8
        out["bit_histogram"][leaf.bits] = \
            out["bit_histogram"].get(leaf.bits, 0) + 1
    return out


def tree_payload_bytes(tree: Any) -> int:
    """Total storage bytes of a parameter tree: QTensor payloads at their
    packed size, plain arrays at their dtype size (the realized-HBM
    number the benchmarks report)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_qtensor):
        if isinstance(leaf, QTensor):
            total += leaf.nbytes + leaf.scale_bytes
        else:
            import numpy as _np
            total += int(_np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total
