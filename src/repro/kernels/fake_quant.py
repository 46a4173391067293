"""Pallas TPU kernel: fused quantize–dequantize (fake quantization).

The QAT inner-loop hot spot: elementwise, memory-bound. One pass over the
tensor in VMEM tiles, with the (scale, zero_point) scalars resident in
SMEM. Per-channel scales use a broadcast tile.

Target: TPU v5e — tiles are (BLOCK_ROWS, 128·k) aligned to the (8, 128)
VPU lane layout; default block 512×1024 ≈ 2 MiB fp32 in/out, well inside
the ~16 MiB/core VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = (512, 1024)


def _fq_kernel(x_ref, scale_ref, zp_ref, o_ref, *, levels: float):
    x = x_ref[...]
    scale = scale_ref[0, 0]                    # SMEM scalars
    zp = zp_ref[0, 0]
    inv = 1.0 / scale
    q = jnp.round(x.astype(jnp.float32) * inv + zp)
    q = jnp.clip(q, 0.0, levels)
    o_ref[...] = ((q - zp) * scale).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bits", "levels", "block", "interpret"))
def fake_quant_pallas(x: jnp.ndarray, scale: jnp.ndarray, zero_point: jnp.ndarray,
                      bits: int, levels: float = None, block=DEFAULT_BLOCK,
                      interpret: bool = False):
    """Per-tensor fake-quant. x: any shape; scale/zero_point: scalars.
    ``levels``: largest grid index (default affine 2^bits − 1; pass
    2^bits − 2 for the odd symmetric grid)."""
    orig_shape = x.shape
    n = x.size
    cols = block[1]
    rows = pl.cdiv(n, cols)
    pad = rows * cols - n
    x2 = jnp.pad(x.reshape(-1), (0, pad)).reshape(rows, cols)

    scale2 = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    zp2 = jnp.asarray(zero_point, jnp.float32).reshape(1, 1)

    block_rows = min(block[0], rows)
    grid = (pl.cdiv(rows, block_rows),)

    out = pl.pallas_call(
        functools.partial(
            _fq_kernel,
            # rpr-ok: RPR004 `levels` is a static python argument (jit static_argnames), never a tracer
            levels=float(levels) if levels is not None else 2.0 ** bits - 1.0),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        interpret=interpret,
    )(x2, scale2, zp2)
    return out.reshape(-1)[:n].reshape(orig_shape)


def _fq_pc_kernel(x_ref, scale_ref, zp_ref, o_ref, *, levels: float):
    x = x_ref[...]
    scale = scale_ref[...]  # (1, block_cols)
    zp = zp_ref[...]
    q = jnp.round(x.astype(jnp.float32) / scale + zp)
    q = jnp.clip(q, 0.0, levels)
    o_ref[...] = ((q - zp) * scale).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bits", "levels", "block", "interpret"))
def fake_quant_per_channel_pallas(x: jnp.ndarray, scale: jnp.ndarray,
                                  zero_point: jnp.ndarray, bits: int,
                                  levels: float = None,
                                  block=(256, 512), interpret: bool = False):
    """Per-channel (last axis) fake-quant. x: (..., C); scale/zp: (C,).
    ``levels`` as in ``fake_quant_pallas``."""
    orig_shape = x.shape
    c = x.shape[-1]
    rows = x.size // c
    x2 = x.reshape(rows, c)
    block_rows = min(block[0], rows)
    block_cols = min(block[1], c)
    grid = (pl.cdiv(rows, block_rows), pl.cdiv(c, block_cols))

    out = pl.pallas_call(
        functools.partial(
            _fq_pc_kernel,
            # rpr-ok: RPR004 `levels` is a static python argument (jit static_argnames), never a tracer
            levels=float(levels) if levels is not None else 2.0 ** bits - 1.0),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_cols), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_cols), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, c), x.dtype),
        interpret=interpret,
    )(x2, scale.reshape(1, c).astype(jnp.float32),
      zero_point.reshape(1, c).astype(jnp.float32))
    return out.reshape(orig_shape)


def clip_stats(x, scale, zero_point, bits: int, levels=None):
    """(clipped, total) f32 element counts for one fake-quant call: how
    many grid indices ``round(x/scale + zp)`` fell outside [0, levels]
    and were clamped. Feeds the ``fq_clip`` / ``fq_elems`` device
    counters — a rising clip rate means serving traffic has outgrown
    the calibrated quantization ranges (the FIT drift signal's cheap
    in-band cousin)."""
    lv = (2.0 ** bits - 1.0) if levels is None else levels * 1.0
    q = jnp.round(x.astype(jnp.float32) / scale + zero_point)
    clipped = jnp.sum(((q < 0.0) | (q > lv)).astype(jnp.float32))
    return clipped, jnp.float32(x.size)
