"""Forward-pass context: the single interception point for FIT and QAT.

Every weight matmul calls ``ctx.matmul(name, x, w)`` (which defaults to
``x @ ctx.qw(name, w)``) and every designated activation site calls
``ctx.tap(name, a)``. The context decides what happens there:

  * plain forward            — identity
  * QAT forward              — STE fake-quant with per-block bit widths
                               (per-layer bits under scan are traced
                               "levels" scalars, so one compiled layer
                               body serves all layers)
  * FIT activation traces    — add a zero-valued tap parameter
  * calibration              — record min/max statistics
  * quantized serving        — ``DequantContext``: weights live as packed
                               ``repro.qtensor.QTensor`` storage (or
                               legacy int8 + scales dict); ``matmul``
                               either dequantizes at the point of use
                               (fp path) or quantizes the activation
                               row-wise and dispatches to the fused
                               quantized MXU kernels (``kernels.ops``)

Names are scoped with ``ctx.scope("layers/attn")`` so block paths align
with the parameter-tree paths used by QuantPolicy / SensitivityReport.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Dict, List, Mapping

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.qtensor import QTensor


def _dynamic_fake_quant_ste(x: jnp.ndarray, levels: jnp.ndarray) -> jnp.ndarray:
    """Fake-quant where the number of levels (2^b−1) is a traced scalar.

    Needed under scan-stacked layers with per-layer bit widths: the bits
    become data, not structure. levels >= 2^15 disables quantization
    (identity) via jnp.where so the op stays branch-free.
    """
    lo = jnp.minimum(jnp.min(x), 0.0).astype(jnp.float32)
    hi = jnp.maximum(jnp.max(x), 0.0).astype(jnp.float32)
    scale = jnp.maximum((hi - lo) / levels, 1e-12)
    zp = jnp.round(-lo / scale)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale + zp), 0.0, levels)
    fq = ((q - zp) * scale).astype(x.dtype)
    big = levels >= 32767.0
    y = jnp.where(big, x, fq)
    return x + jax.lax.stop_gradient(y - x)   # STE


class Context:
    """Identity context (plain forward)."""

    def __init__(self, scope_prefix: str = ""):
        self._scope: List[str] = [scope_prefix] if scope_prefix else []

    @contextmanager
    def scope(self, name: str):
        self._scope.append(name)
        try:
            yield self
        finally:
            self._scope.pop()

    def path(self, name: str) -> str:
        return "/".join(self._scope + [name])

    def qw(self, name: str, w: jnp.ndarray) -> jnp.ndarray:
        return w

    def matmul(self, name: str, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
        """The weight-matmul interception point: ``x @ qw(name, w)``.

        Subclasses override to change the *compute* (not just the weight
        value) — e.g. DequantContext routes int8-stored blocks through
        the int8 MXU kernel instead of dequantize-then-fp-matmul."""
        return x @ self.qw(name, w)

    def expert_matmul(self, name: str, buf: jnp.ndarray, w,
                      counts: jnp.ndarray) -> jnp.ndarray:
        """The MoE expert-stack interception point.

        ``buf``: (E, C, D) capacity-sorted token segments (rows past
        ``counts[e]`` are zero); ``w``: (E, D, F) stacked expert weights;
        ``counts``: (E,) int32 valid rows per expert. Returns (E, C, F)
        with rows past ``counts[e]`` still (exactly) zero — the combine
        gather relies on dropped slots contributing nothing.

        Default: the batched fp einsum over ``qw`` (zero rows in, zero
        rows out), which preserves QAT/tap/FIT semantics unchanged.
        ``DequantContext`` overrides to dispatch packed expert stacks to
        the grouped ragged quantized kernel.
        """
        del counts
        return jnp.einsum("ecd,edf->ecf", buf, self.qw(name, w))

    def tap(self, name: str, a: jnp.ndarray) -> jnp.ndarray:
        return a


class RecordTaps:
    """Delegating wrapper that records every ``tap`` site's value while
    leaving all other context behavior (scoping, matmul routing, weight
    handling) to the wrapped context.

    ``obs.drift`` uses this to collect the QUANTIZED engine's activation
    taps (e.g. ``router_logits``) through the engine's own
    ``DequantContext`` — ``CollectContext`` can't, because it would also
    replace the quantized matmul routing being probed.
    """

    def __init__(self, inner: Context):
        self._inner = inner
        self.acts: Dict[str, jnp.ndarray] = {}

    def __getattr__(self, name):
        return getattr(self._inner, name)

    @contextmanager
    def scope(self, name: str):
        with self._inner.scope(name):
            yield self

    def tap(self, name: str, a: jnp.ndarray) -> jnp.ndarray:
        a = self._inner.tap(name, a)
        self.acts[self._inner.path(name)] = a
        return a


class QATContext(Context):
    """Fake-quantize weights and activations with per-block bit widths.

    ``weight_levels`` / ``act_levels`` map block path -> levels value
    (2^bits − 1), which may be python floats or traced scalars (the scan
    path passes a slice of a per-layer levels array).
    """

    def __init__(self, weight_levels: Mapping[str, Any],
                 act_levels: Mapping[str, Any], scope_prefix: str = ""):
        super().__init__(scope_prefix)
        self.weight_levels = weight_levels
        self.act_levels = act_levels

    def _lookup(self, table: Mapping[str, Any], path: str):
        if path in table:
            return table[path]
        # fall back to the unscoped tail (shared-block invocations)
        tail = path.split("/")[-1]
        return table.get(tail)

    def qw(self, name: str, w: jnp.ndarray) -> jnp.ndarray:
        lv = self._lookup(self.weight_levels, self.path(name))
        if lv is None:
            return w
        return _dynamic_fake_quant_ste(w, jnp.asarray(lv, jnp.float32))

    def tap(self, name: str, a: jnp.ndarray) -> jnp.ndarray:
        lv = self._lookup(self.act_levels, self.path(name))
        if lv is None:
            return a
        return _dynamic_fake_quant_ste(a, jnp.asarray(lv, jnp.float32))


class TapContext(Context):
    """Add zero-valued tap params at activation sites (FIT activation EF)."""

    def __init__(self, taps: Mapping[str, jnp.ndarray], scope_prefix: str = ""):
        super().__init__(scope_prefix)
        self.taps = taps

    def tap(self, name: str, a: jnp.ndarray) -> jnp.ndarray:
        t = self.taps.get(self.path(name))
        return a if t is None else a + t


class CollectContext(Context):
    """Record activation values (shape probes / calibration)."""

    def __init__(self, scope_prefix: str = ""):
        super().__init__(scope_prefix)
        self.acts: Dict[str, jnp.ndarray] = {}

    def tap(self, name: str, a: jnp.ndarray) -> jnp.ndarray:
        self.acts[self.path(name)] = a
        return a


class DequantContext(Context):
    """Serve-time quantized execution over packed quantized weights.

    Quantized matmul blocks arrive in one of two storage forms:

      * ``repro.qtensor.QTensor`` — truly packed W{8,6,4,3} payload with
        grouped scales carried inside the leaf (``serve.quantized
        .quantize_params``). ``matmul`` routes these to the fused
        grouped-scale kernel ``kernels.ops.qmm`` (``int8_compute=True``)
        or dequantizes at the point of use (fp path); HBM reads stay at
        the packed byte width either way.
      * legacy int8 leaves + a path-keyed ``scales`` dict
        (``quantize_params_int8``), kept for the storage-format A/B in
        the benchmarks; these take the original ``int8_matmul`` route.

    With ``int8_compute=True`` the activation is quantized with a
    dynamic per-ROW scale before dispatch — per-row (not per-tensor)
    scales keep every batch row's numerics independent of its
    batch-mates, which is what makes continuous-batching output
    bit-identical to isolated decode.

    Path-keyed scales require the unrolled (``scan_layers=False``)
    parameter layout — under scan one compiled body serves all layers
    and per-layer scales cannot be looked up by path. QTensor leaves
    carry their scales with them but need the unrolled layout for the
    same reason: per-layer payload shapes differ by bit width.
    """

    def __init__(self, scales: Mapping[str, jnp.ndarray], dtype,
                 int8_compute: bool = False, moe_dispatch: str = "grouped",
                 scope_prefix: str = ""):
        super().__init__(scope_prefix)
        self.scales = scales
        self.dtype = dtype
        self.int8_compute = int8_compute
        if moe_dispatch not in ("grouped", "dense", "einsum"):
            raise ValueError(f"moe_dispatch must be grouped|dense|einsum, "
                             f"got {moe_dispatch!r}")
        self.moe_dispatch = moe_dispatch

    def _rowquant(self, x2: jnp.ndarray):
        # dynamic symmetric per-row activation scale: row b's quantization
        # depends only on row b, preserving batch-composition invariance
        amax = jnp.max(jnp.abs(x2), axis=-1, keepdims=True)
        xs = jnp.maximum(amax, 1e-8) / 127.0                      # (M, 1)
        xq = jnp.clip(jnp.round(x2 / xs), -127, 127).astype(jnp.int8)
        return xq, xs

    def qw(self, name: str, w) -> jnp.ndarray:
        if isinstance(w, QTensor):
            return w.dequantize(self.dtype)
        s = self.scales.get(self.path(name))
        if s is None or w.dtype != jnp.int8:
            return w
        return (w.astype(jnp.float32) * s).astype(self.dtype)

    def matmul(self, name: str, x: jnp.ndarray, w) -> jnp.ndarray:
        from repro.kernels import ops as kops  # avoid import cycle at module load
        if isinstance(w, QTensor):
            if not self.int8_compute or len(w.shape) != 2:
                return x @ w.dequantize(self.dtype)
            lead = x.shape[:-1]
            xq, xs = self._rowquant(x.reshape(-1, x.shape[-1]).astype(jnp.float32))
            y = kops.qmm(xq, w, xs, out_dtype=jnp.float32)
            return y.astype(self.dtype).reshape(lead + (w.shape[-1],))
        s = self.scales.get(self.path(name))
        if s is None or w.dtype != jnp.int8:
            return x @ w
        if not self.int8_compute or w.ndim != 2:
            return x @ (w.astype(jnp.float32) * s).astype(self.dtype)
        lead = x.shape[:-1]
        xq, xs = self._rowquant(x.reshape(-1, x.shape[-1]).astype(jnp.float32))
        y = kops.int8_matmul(xq, w, xs, s.reshape(1, -1),
                             out_dtype=jnp.float32)
        return y.astype(self.dtype).reshape(lead + (w.shape[-1],))

    def expert_matmul(self, name: str, buf: jnp.ndarray, w,
                      counts: jnp.ndarray) -> jnp.ndarray:
        """Packed expert stacks dispatch to the grouped ragged quantized
        kernel (``moe_dispatch="grouped"``) or the dense per-expert
        ``qmm`` loop (``"dense"`` — the bit-identity oracle the parity
        tests pin the grouped path against); everything else (fp
        weights, legacy int8 stacks, legacy shared-scale QTensors,
        ``"einsum"``) falls back to the fp-dequant einsum.

        Activation rows are quantized with the SAME dynamic per-row
        scales as 2-D ``matmul`` sites — each token row's numerics
        depend only on itself, so capacity-sorted batching preserves the
        engine's batch-composition invariance inside MoE layers too.
        """
        from repro.kernels import ops as kops
        if (not isinstance(w, QTensor) or not self.int8_compute
                or len(w.shape) != 3 or self.moe_dispatch == "einsum"
                or w.scale.shape[0] != w.shape[0]):
            return super().expert_matmul(name, buf, w, counts)
        e, c, d = buf.shape
        n = w.shape[-1]
        xq, xs = self._rowquant(
            buf.reshape(-1, d).astype(jnp.float32))
        xq, xs = xq.reshape(e, c, d), xs.reshape(e, c, 1)
        cnt = counts.astype(jnp.int32)
        if self.moe_dispatch == "dense":
            from repro.qtensor import expert_slice
            y = jnp.stack([
                kops.qmm(xq[ei], expert_slice(w, ei), xs[ei],
                         out_dtype=jnp.float32)
                for ei in range(e)], axis=0)
            rows = jnp.arange(c, dtype=jnp.int32)[None, :, None]
            y = jnp.where(rows < cnt[:, None, None], y, 0.0)
        else:
            y = kops.grouped_qmm(xq, w, xs, cnt, out_dtype=jnp.float32)
        return y.astype(self.dtype)


class ShardedDequantContext(DequantContext):
    """Tensor-parallel ``DequantContext``: quantized matmuls execute
    under ``shard_map`` over a 1-D device mesh, BIT-IDENTICAL to the
    single-device path for every tp degree.

    ``shard_plan`` (from ``repro.serve.quantized.shard_params``) maps a
    scoped block path to its layout: ``"col"`` (output dim sharded),
    ``"row"`` (reduction dim sharded), or ``"ep"`` (3-D expert stacks
    sharded by expert — expert parallelism, see ``expert_matmul``);
    unplanned blocks are replicated and fall through to the parent.
    Activations stay replicated between blocks — the per-row activation
    quantization therefore sees the identical full-row values at every
    tp degree.

    Why this is exact (the tp-vs-tp=1 parity contract):

      * column-parallel — each shard computes its output columns with
        the FULL reduction axis local; integer dots are exact and every
        later op is elementwise per column, so the all-gather is a pure
        concatenation of the tp=1 values.
      * row-parallel — each shard owns whole scale groups (enforced at
        materialization). Its per-group terms ``f32(int32 dot) * scale``
        are exact and shard-invariant; they are scattered into a zeroed
        (G, M, N) buffer at the shard's group-scale offset and combined
        with ONE psum (summing one nonzero term + zeros per element —
        exact regardless of reduction order), after which every device
        applies the oracle's canonical ``sum(axis=0) * x_scale``. The
        legacy int8 path psums the raw int32 accumulator (integer adds
        are associative) before the elementwise dequant.

    The fp-dequant route cannot be sharded this way (a float psum is
    not associative), so sharded serving requires ``int8_compute=True``
    — enforced by the Engine.

    Two scoping notes. (1) The BIT-IDENTICAL contract is stated on the
    oracle dispatch route (``REPRO_KERNELS=ref``, where tp=1 uses
    ``ref.qmm`` — the same canonical ``sum(axis=0)`` fold): on real TPU
    the tp=1 ``qmm_pallas`` kernel folds groups sequentially in-VMEM
    while the sharded path reduces the gathered stack with ``jnp.sum``,
    so tp-vs-tp=1 there matches within kernel-vs-ref fp32 summation-
    order noise, like every other Pallas kernel in this repo. (2) The
    row-parallel psum moves a (G, M, N) buffer — G× the output. G is a
    quantization-granularity knob: shard alignment needs tp | G, so
    quantize row-parallel blocks with ``group_size = K / tp`` (G = tp,
    the minimum) when communication matters; fine-grained groups buy
    accuracy at proportional psum volume.

    ``kv_shards`` > 1 additionally tells ``attention_decode_paged`` to
    run its page pools kv-head-sharded (see ``repro.models.attention``).
    """

    def __init__(self, scales: Mapping[str, jnp.ndarray], dtype,
                 mesh, shard_plan: Mapping[str, str],
                 int8_compute: bool = True, kv_shards: int = 1,
                 moe_dispatch: str = "grouped",
                 axis_name: str = "tp", scope_prefix: str = ""):
        super().__init__(scales, dtype, int8_compute=int8_compute,
                         moe_dispatch=moe_dispatch,
                         scope_prefix=scope_prefix)
        self.mesh = mesh
        self.shard_plan = dict(shard_plan)
        self.axis_name = axis_name
        self.kv_shards = kv_shards
        self.n_shards = mesh.shape[axis_name]

    # -- shard-local kernels (bodies run under shard_map) ---------------
    def _qmm_col(self, xq, wd, ws, xs, *, bits, k, n):
        from repro.kernels import ops as kops
        nl = n // self.n_shards
        w_local = QTensor(wd, ws, bits, (k, nl), 0)
        y = kops.qmm(xq, w_local, xs, out_dtype=jnp.float32)
        return jax.lax.all_gather(y, self.axis_name, axis=1, tiled=True)

    def _qmm_row(self, xq, wd, ws, xs, *, bits, k, n, groups):
        from repro.kernels import ops as kops
        s = self.n_shards
        kl, gl = k // s, groups // s
        i = jax.lax.axis_index(self.axis_name)
        xl = jax.lax.dynamic_slice_in_dim(xq, i * kl, kl, axis=1)
        w_local = QTensor(wd, ws, bits, (kl, n), 0)
        terms = kops.qmm_group_products(xl, w_local)        # (gl, M, N)
        full = jnp.zeros((groups,) + terms.shape[1:], jnp.float32)
        full = jax.lax.dynamic_update_slice(full, terms, (i * gl, 0, 0))
        # ONE psum per down-projection: disjoint group slots + zeros, so
        # the float reduction is exact for any shard count
        # rpr-ok: RPR002 fp32 operand is zeros + disjoint per-shard dynamic_update_slice slots (exact zero-padded adds)
        full = jax.lax.psum(full, self.axis_name)
        y = jnp.sum(full, axis=0)
        return y * jnp.asarray(xs, jnp.float32)

    def _qmm_ep(self, xq, xs, cnt, wd, ws, *, bits, e, k, n, cap):
        """Expert-parallel grouped qmm (runs under shard_map).

        Routing, capacity assignment and per-row activation quantization
        all happened on the REPLICATED token buffer, so every shard
        holds identical (E, cap, K) segments; expert weights are the
        only sharded operand. Shard i slices ITS experts' segments out
        of the replicated buffer (the all_to_all dispatch of the
        classical EP layout degenerates to a local slice when tokens are
        replicated — nothing to exchange), runs the grouped kernel over
        its self-contained expert blocks, and the combine is a scatter
        into disjoint expert slots of a zero buffer + ONE exact psum.
        Each expert's segment is computed by exactly one shard with the
        same int32 dots / fp32 folds the unsharded grouped call does —
        bit-identical for every tp degree.
        """
        from repro.kernels import ops as kops
        el = e // self.n_shards
        i = jax.lax.axis_index(self.axis_name)
        xl = jax.lax.dynamic_slice_in_dim(xq, i * el, el, axis=0)
        xsl = jax.lax.dynamic_slice_in_dim(xs, i * el, el, axis=0)
        cl = jax.lax.dynamic_slice_in_dim(cnt, i * el, el, axis=0)
        w_local = QTensor(wd, ws, bits, (el, k, n), 1)
        y = kops.grouped_qmm(xl, w_local, xsl, cl,
                             out_dtype=jnp.float32)      # (el, cap, N)
        full = jnp.zeros((e, cap, n), jnp.float32)
        full = jax.lax.dynamic_update_slice(full, y, (i * el, 0, 0))
        # ONE psum per MoE projection: each expert slot is written by
        # exactly one shard, everything else is zero, so the float
        # reduction is exact for any shard count
        # rpr-ok: RPR002 fp32 operand is zeros + disjoint per-expert dynamic_update_slice slots (each expert computed on exactly one shard)
        return jax.lax.psum(full, self.axis_name)

    def _int8_col(self, xq, w, s, xs):
        from repro.kernels import ops as kops
        y = kops.int8_matmul(xq, w, xs, s.reshape(1, -1),
                             out_dtype=jnp.float32)
        return jax.lax.all_gather(y, self.axis_name, axis=1, tiled=True)

    def _int8_row(self, xq, w, s, xs, *, k):
        kl = k // self.n_shards
        i = jax.lax.axis_index(self.axis_name)
        xl = jax.lax.dynamic_slice_in_dim(xq, i * kl, kl, axis=1)
        acc = jax.lax.dot_general(
            xl, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        # rpr-ok: RPR002 int32 operand — integer adds are exact
        acc = jax.lax.psum(acc, self.axis_name)
        # identical elementwise dequant to kernels.ref.int8_matmul
        return (acc.astype(jnp.float32) * xs.reshape(-1, 1)
                * s.reshape(1, -1))

    # -- dispatch --------------------------------------------------------
    def matmul(self, name: str, x: jnp.ndarray, w) -> jnp.ndarray:
        mode = self.shard_plan.get(self.path(name))
        if mode is None:
            return super().matmul(name, x, w)
        from repro.obs import runtime as obs_rt
        mesh, ax = self.mesh, self.axis_name
        lead = x.shape[:-1]
        xq, xs = self._rowquant(
            x.reshape(-1, x.shape[-1]).astype(jnp.float32))
        xs = jnp.asarray(xs, jnp.float32).reshape(-1, 1)
        if obs_rt.emitting():
            obs_rt.emit("qmm_calls" if isinstance(w, QTensor)
                        else "int8mm_calls", 1.0)
            if obs_rt.emitting_stats():
                # clip stats come from the REPLICATED pre-shard activation,
                # so the counters are tp-invariant; the kernel-site emits
                # inside the shard_map bodies are suspended below (their
                # values belong to the inner trace and must not leak into
                # the sink)
                from repro.kernels.qmm import saturation_stats
                sat, total = saturation_stats(xq)
                obs_rt.emit("act_sat", sat)
                obs_rt.emit("act_elems", total)
        if isinstance(w, QTensor):
            k, n = w.shape
            groups = w.scale.shape[w.axis]
            ws2 = w.scale.reshape(groups, n)
            if mode == "col":
                fn = shard_map(
                    lambda a, d, sc, axs: self._qmm_col(
                        a, d, sc, axs, bits=w.bits, k=k, n=n),
                    mesh=mesh,
                    in_specs=(P(None, None), P(None, ax), P(None, ax),
                              P(None, None)),
                    out_specs=P(None, None), check_vma=False)
            else:
                fn = shard_map(
                    lambda a, d, sc, axs: self._qmm_row(
                        a, d, sc, axs, bits=w.bits, k=k, n=n,
                        groups=groups),
                    mesh=mesh,
                    in_specs=(P(None, None), P(ax, None), P(ax, None),
                              P(None, None)),
                    out_specs=P(None, None), check_vma=False)
            with obs_rt.suspended():
                y = fn(xq, w.data, ws2, xs)
            return y.astype(self.dtype).reshape(lead + (n,))
        # legacy int8 leaf + path-keyed scale
        s = self.scales.get(self.path(name))
        n = w.shape[-1]
        if mode == "col":
            fn = shard_map(
                lambda a, wl, sl, axs: self._int8_col(a, wl, sl, axs),
                mesh=mesh,
                in_specs=(P(None, None), P(None, ax), P(None, ax),
                          P(None, None)),
                out_specs=P(None, None), check_vma=False)
            with obs_rt.suspended():
                y = fn(xq, w, s.reshape(1, -1), xs)
        else:
            fn = shard_map(
                lambda a, wl, sl, axs: self._int8_row(
                    a, wl, sl, axs, k=w.shape[0]),
                mesh=mesh,
                in_specs=(P(None, None), P(ax, None), P(None, None),
                          P(None, None)),
                out_specs=P(None, None), check_vma=False)
            with obs_rt.suspended():
                y = fn(xq, w, s.reshape(1, -1), xs)
        return y.astype(self.dtype).reshape(lead + (n,))

    def expert_matmul(self, name: str, buf: jnp.ndarray, w,
                      counts: jnp.ndarray) -> jnp.ndarray:
        """Expert-parallel MoE dispatch: blocks the shard plan marks
        ``"ep"`` (3-D ``quantize_experts`` stacks sharded by expert) run
        ``_qmm_ep`` under shard_map; everything else falls through to
        the parent's replicated grouped/dense/einsum dispatch, so the
        engine stays bit-identical to tp=1 either way."""
        if (self.shard_plan.get(self.path(name)) != "ep"
                or not isinstance(w, QTensor)
                or self.moe_dispatch == "einsum"):
            return super().expert_matmul(name, buf, w, counts)
        from repro.obs import runtime as obs_rt
        e, c, d = buf.shape
        k, n = w.shape[1], w.shape[2]
        xq, xs = self._rowquant(
            buf.reshape(-1, d).astype(jnp.float32))
        if obs_rt.emitting():
            # counters come from the REPLICATED pre-shard activation (the
            # kernel-site emits inside the shard_map body are suspended)
            obs_rt.emit("qmm_calls", 1.0)
            if obs_rt.emitting_stats():
                from repro.kernels.qmm import saturation_stats
                sat, total = saturation_stats(xq)
                obs_rt.emit("act_sat", sat)
                obs_rt.emit("act_elems", total)
        xq, xs = xq.reshape(e, c, d), xs.reshape(e, c, 1)
        cnt = counts.astype(jnp.int32)
        ax = self.axis_name
        fn = shard_map(
            lambda a, axs, cl, dta, sc: self._qmm_ep(
                a, axs, cl, dta, sc, bits=w.bits, e=e, k=k, n=n, cap=c),
            mesh=self.mesh,
            in_specs=(P(None, None, None), P(None, None, None), P(None),
                      P(ax, None, None), P(ax, None, None)),
            out_specs=P(None, None, None), check_vma=False)
        with obs_rt.suspended():
            y = fn(xq, xs, cnt, w.data, w.scale)
        return y.astype(self.dtype)
