"""Jit'd dispatch layer over the Pallas kernels.

On TPU backends the Pallas implementations run natively; elsewhere (this
CPU container, dry-run lowering) the pure-jnp references are used so the
same model code lowers everywhere. ``force`` overrides for tests:
  REPRO_KERNELS=interpret  -> Pallas kernels in interpret mode (CPU exec)
  REPRO_KERNELS=ref        -> always references
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.analysis.bounds import require_full_k_safe, require_group_dot_safe
from repro.kernels import ref as _ref
from repro.kernels.fake_quant import (
    clip_stats, fake_quant_pallas, fake_quant_per_channel_pallas)
from repro.kernels.ef_sqnorm import ef_sqnorm_pallas
from repro.kernels.int8_matmul import activation_saturation, int8_matmul_pallas
from repro.kernels.grouped_qmm import grouped_qmm_pallas
from repro.kernels.qmm import qmm_groups_pallas, qmm_pallas, saturation_stats
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (
    paged_attention_pallas, read_token_stats)
from repro.obs import runtime as obs_rt


def _mode() -> str:
    env = os.environ.get("REPRO_KERNELS", "auto")
    if env in ("ref", "interpret", "tpu"):
        return env
    return "tpu" if jax.default_backend() == "tpu" else "ref"


def fake_quant(x, scale, zero_point, bits: int, levels=None):
    """``levels``: largest grid index — default affine 2^bits − 1; pass
    ``QuantSpec.levels`` (2^bits − 2) for symmetric specs so values past
    the calibrated range clip to the odd symmetric grid."""
    mode = _mode()
    per_channel = getattr(scale, "ndim", 0) and scale.size > 1
    if obs_rt.emitting_stats():
        # clip-rate sample for the obs device counters — the stats graph
        # is only built when a CounterSink is actively collecting AND this
        # burst is a sampled one (ObsConfig.stats_every)
        clipped, total = clip_stats(x, scale, zero_point, bits, levels)
        obs_rt.emit("fq_clip", clipped)
        obs_rt.emit("fq_elems", total)
    if mode == "ref":
        return _ref.fake_quant(x, scale, zero_point, bits, levels=levels)
    interp = mode == "interpret"
    if per_channel:
        c = x.shape[-1]
        return fake_quant_per_channel_pallas(
            x, jnp.reshape(scale, (c,)), jnp.reshape(zero_point, (c,)), bits,
            levels=levels, interpret=interp)
    return fake_quant_pallas(x, jnp.reshape(scale, ()), jnp.reshape(zero_point, ()),
                             bits, levels=levels, interpret=interp)


def ef_sqnorm(g):
    mode = _mode()
    if mode == "ref":
        return _ref.ef_sqnorm(g)
    return ef_sqnorm_pallas(g, interpret=(mode == "interpret"))


def int8_matmul(x_q, w_q, x_scale, w_scale, out_dtype=jnp.float32):
    """x_scale: scalar (per-tensor) or (M,)/(M,1) per-row — per-row scales
    keep each batch row's dequantization independent of its batch-mates
    (continuous-batching parity)."""
    mode = _mode()
    # static overflow proof on EVERY route (the pallas wrapper re-checks)
    require_full_k_safe(8, 8, x_q.shape[-1], where="ops.int8_matmul")
    if obs_rt.emitting():
        obs_rt.emit("int8mm_calls", 1.0)
        if obs_rt.emitting_stats():
            sat, total = activation_saturation(x_q)
            obs_rt.emit("act_sat", sat)
            obs_rt.emit("act_elems", total)
    x_scale = jnp.asarray(x_scale, jnp.float32)
    if x_scale.size > 1:
        x_scale = x_scale.reshape(-1, 1)          # (M, 1) for row broadcast
    if mode == "ref":
        return _ref.int8_matmul(x_q, w_q, x_scale, w_scale, out_dtype)
    return int8_matmul_pallas(x_q, w_q, x_scale, w_scale, out_dtype=out_dtype,
                              interpret=(mode == "interpret"))


def qmm(x_q, w, x_scale, out_dtype=jnp.float32):
    """Fused grouped-scale quantized matmul over a packed QTensor weight.

    x_q: (M, K) int8; ``w``: ``repro.qtensor.QTensor`` of logical (K, N)
    packed along axis 0 (scales (G, N)); x_scale: scalar or (M,)/(M, 1)
    per-row fp32. Sub-byte payloads are expanded in-kernel — HBM and
    VMEM both see only the packed bytes.
    """
    mode = _mode()
    # static overflow proof on EVERY route (the pallas wrapper re-checks)
    require_group_dot_safe(w.bits, 8, w.group_size, where="ops.qmm")
    if obs_rt.emitting():
        obs_rt.emit("qmm_calls", 1.0)
        if obs_rt.emitting_stats():
            sat, total = saturation_stats(x_q)
            obs_rt.emit("act_sat", sat)
            obs_rt.emit("act_elems", total)
    x_scale = jnp.asarray(x_scale, jnp.float32)
    if x_scale.size > 1:
        x_scale = x_scale.reshape(-1, 1)          # (M, 1) for row broadcast
    if mode == "ref":
        return _ref.qmm(x_q, w, x_scale, out_dtype)
    k, n = w.shape
    return qmm_pallas(x_q, w.data, x_scale,
                      w.scale.reshape(w.scale.shape[w.axis], n),
                      bits=w.bits, k=k, out_dtype=out_dtype,
                      interpret=(mode == "interpret"))


def grouped_qmm(x_q, w, x_scale, counts, expert_ids=None,
                out_dtype=jnp.float32):
    """Grouped ragged quantized MoE matmul over a packed expert stack.

    x_q: (S, C, K) int8 capacity-sorted segments; ``w``: a
    ``qtensor.quantize_experts`` QTensor of logical (E, K, N) packed
    along axis 1 (per-expert scales (E, G, N)); x_scale: (S, C, 1)
    per-row fp32; counts: (S,) valid rows per segment; expert_ids: (S,)
    expert feeding each segment (default ``arange(S)``). Rows past a
    segment's count come back exactly 0.0; sub-byte payloads are
    expanded in-kernel — HBM and VMEM both see only the packed bytes.
    """
    mode = _mode()
    # static overflow proof on EVERY route (the pallas wrapper re-checks)
    require_group_dot_safe(w.bits, 8, w.group_size, where="ops.grouped_qmm")
    if obs_rt.emitting():
        obs_rt.emit("qmm_calls", 1.0)
        if obs_rt.emitting_stats():
            sat, total = saturation_stats(x_q)
            obs_rt.emit("act_sat", sat)
            obs_rt.emit("act_elems", total)
    counts = counts.astype(jnp.int32)
    if expert_ids is not None:
        expert_ids = expert_ids.astype(jnp.int32)
    if mode == "ref":
        return _ref.grouped_qmm(x_q, w, x_scale, counts, expert_ids,
                                out_dtype)
    e, k, n = w.shape
    ws = w.scale
    if ws.shape[0] != e:                  # legacy shared-scale stack
        ws = jnp.broadcast_to(ws, (e,) + ws.shape[1:])
    if expert_ids is None:
        expert_ids = jnp.arange(x_q.shape[0], dtype=jnp.int32)
    return grouped_qmm_pallas(x_q, w.data, x_scale, ws, counts, expert_ids,
                              bits=w.bits, k=k, out_dtype=out_dtype,
                              interpret=(mode == "interpret"))


def qmm_group_products(x_q, w):
    """Per-group scaled partial products (G, M, N) fp32, no group sum —
    the shard-local half of a K-sharded (row-parallel) ``qmm``.

    Off-TPU this always takes the jnp oracle, even in interpret mode:
    the tensor-parallel engine's tp-vs-tp=1 BIT-IDENTICAL parity
    contract is stated on the oracle's exact int32-dot-per-group terms,
    and an interpreted kernel inside the engine's per-step scan would be
    ruinously slow. Interpret-mode kernel coverage lives in
    ``tests/test_qtensor.py::test_qmm_groups_pallas_matches_group_products``,
    which calls ``qmm_groups_pallas`` directly (bit-exact vs the oracle).
    """
    mode = _mode()
    require_group_dot_safe(w.bits, 8, w.group_size,
                           where="ops.qmm_group_products")
    if mode != "tpu":
        return _ref.qmm_group_products(x_q, w)
    k, n = w.shape
    return qmm_groups_pallas(x_q, w.data,
                             w.scale.reshape(w.scale.shape[w.axis], n),
                             bits=w.bits, k=k)


def flash_attention(q, k, v, causal: bool = True):
    mode = _mode()
    if mode == "ref":
        return _ref.flash_attention(q, k, v, causal=causal)
    return flash_attention_pallas(q, k, v, causal=causal,
                                  interpret=(mode == "interpret"))


def paged_attention(q, k_pages, v_pages, table, pos, k_scale=None,
                    v_scale=None, bits: int = 16):
    """Decode GQA over paged KV. q: (B, 1, H, Dh) -> (B, KV, G, Dh).

    Off-TPU this always takes the jnp oracle, even in interpret mode: the
    serving engine's paged-vs-dense BIT-IDENTICAL parity contract holds
    on the oracle path only (the flash-style kernel accumulates online),
    and an interpreted kernel inside the engine's per-step scan would be
    ruinously slow. Interpret-mode kernel coverage lives in the dedicated
    kernel tests, which call ``paged_attention_pallas`` directly.
    """
    mode = _mode()
    if obs_rt.emitting():
        obs_rt.emit("paged_calls", 1.0)
        obs_rt.emit("paged_tokens_read", read_token_stats(pos))
    if mode != "tpu":
        return _ref.paged_attention(q, k_pages, v_pages, table, pos,
                                    k_scale, v_scale, bits)
    kvh = k_pages.shape[1]
    b, _, h, dh = q.shape
    qh = q.reshape(b, kvh, h // kvh, dh)
    return paged_attention_pallas(qh, k_pages, v_pages, table, pos + 1,
                                  k_scale, v_scale, bits=bits)
