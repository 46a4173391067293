"""Continuous-batching inference engine.

Architecture (see README "Serving" and ROADMAP.md):

    loadgen ──> arrival queue ──> admission ──> slots [0..S) ──> finished
                                   │                 ▲
                                   │ chunked prefill │ eviction on
                                   ▼ (batch-1 scan)  │ EOS / max-len,
                              state_insert_slot ─────┘ immediate backfill

Two compiled step functions drive everything, regardless of how many
requests flow through:

  * ``engine_step`` — ONE decode step × ``steps`` (a fused ``lax.scan``
    burst) for the whole slot batch: per-slot positions, per-request
    seeded sampling, masked output-buffer writes. Inactive slots ride
    along (their position is frozen; their state is fully overwritten at
    backfill), so the shape never changes and nothing recompiles.
  * ``prefill_chunk`` — ``models.decode.prefill_into``'s lax.scan over
    one prompt chunk at batch 1. Chunking bounds both compile count
    (≤ chunk_size distinct shapes, cached across requests) and the
    decode-latency bubble a long prompt would otherwise cause: the
    scheduler interleaves in-flight decode bursts between chunks.

Numerics contract: every batch row is computed independently (row-wise
matmuls, per-row cache scatter, per-row causal mask, per-row activation
scales on the int8 path, per-request sampling keys), so a request's
tokens are bit-identical to running it alone — the property the parity
tests in ``tests/test_serve.py`` pin down.

Quantized serving: build params with ``repro.serve.quantized`` — either
packed QTensor storage (``quantize_params``, detected automatically) or
legacy int8 + ``scales`` — and the engine runs the whole decode graph
through a ``DequantContext``: packed weight storage, optionally fused
quantized MXU matmuls (``int8_compute=True``, W{8,6,4,3}A8 via
``kernels.qmm`` for QTensor blocks).

Tensor-parallel serving (``mesh=``, see ``launch.mesh.make_tp_mesh``):
the quantized weight blocks shard column/row-wise across a 1-D "tp"
mesh (``serve.quantized.shard_params``) and execute under ``shard_map``
through ``ShardedDequantContext``; paged KV pools shard by kv-head when
the head count divides the mesh. Every cross-shard reduction is exact
(int32 psums / zero-padded group psums / pure concatenation), so engine
outputs are BIT-IDENTICAL across tp degrees on the oracle kernel route
(``REPRO_KERNELS=ref``; see ``ShardedDequantContext`` for the TPU
nuance) — the contract ``tests/test_sharded_serve.py`` fuzzes. Slot
tables, token buffers and batch-1 prefill scratch states replicate
across the mesh.

Paged KV cache (``kv_cache="paged"``, see ``repro.kvcache``): attention
state moves from the dense per-slot buffer into fixed-size pages with
per-slot page tables — KV memory becomes O(actual tokens) instead of
O(slots x max_len), per-layer bit widths (int8 / packed int4) come from
FIT's activation sensitivities, and identical prompt prefixes are stored
once (hash-matched full pages are refcount-shared; the boundary page is
copied on write). Admission gathers a shared prefix out of the pool into
the batch-1 scratch state and prefills only the suffix. At fp page
precision the engine's outputs remain bit-identical to the dense-cache
engine (and therefore to isolated decode).
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.models.attention import KVCache
from repro.models.context import Context, DequantContext
from repro.models.decode import (
    DecodeState, decode_step, init_decode_state, init_paged_decode_state,
    prefill_into, state_insert_slot)
from repro.kvcache.allocator import BlockAllocator
from repro.qtensor import tree_has_qtensor
from repro.kvcache.paged import (
    PagedKVConfig, copy_page, gather_layer, kv_layer_count,
    page_bytes_all_layers, scatter_span)
from repro.obs import DeviceCounters, ObsConfig, Tracer, init_counters
from repro.obs import runtime as obs_rt
from repro.obs.trace import ENGINE_TID, counting
from repro.serve.metrics import EngineMetrics
from repro.serve.request import Request, RequestStatus
from repro.serve.sampling import greedy_tokens, request_keys, sample_tokens
from repro.serve.spec import (
    SpecConfig, accept_drafts, derive_draft_params, quantize_dense_kv)
from repro.utils.logging import get_logger

log = get_logger("repro.serve.engine")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine shape: slot count, KV capacity, scheduling grain."""

    max_slots: int = 4
    max_len: int = 256            # per-slot KV / position capacity
    max_new_tokens: int = 128     # output-buffer width
    prefill_chunk: int = 32       # prompt tokens per compiled prefill call
    decode_burst: int = 16        # decode steps fused per compiled dispatch
    interleave_steps: int = 4     # decode steps run between prefill chunks
    clock: str = "steps"          # "steps" (deterministic) | "wall" (seconds)
    int8_compute: bool = False    # route int8 blocks through the MXU kernel
    # MoE expert dispatch for packed expert stacks (int8_compute only):
    # "grouped" — one grouped ragged kernel over the whole expert stack
    # (the fast path); "dense" — per-expert qmm loop (the bit-identity
    # oracle the parity tests pin "grouped" against); "einsum" —
    # fp-dequant batched einsum (the pre-grouped fallback, also what
    # non-int8_compute and legacy int8 expert stacks always use)
    moe_dispatch: str = "grouped"
    # ---- paged KV cache (repro.kvcache) ----
    kv_cache: str = "dense"       # "dense" | "paged"
    page_size: int = 16           # tokens per KV page
    kv_pages: Optional[int] = None  # pool size; None = full capacity
    prefix_sharing: bool = True   # hash-share identical prompt prefixes
    # ---- tensor-parallel serving (1-D device mesh, axis "tp") ----
    # Shards 2-D quantized weight blocks column/row-wise and (paged mode,
    # when kv heads divide) the KV page pools by kv-head. Outputs stay
    # BIT-IDENTICAL to the tp=1 engine: every cross-shard reduction is
    # integer-exact or a pure concatenation (see ShardedDequantContext).
    # Requires int8_compute for quantized trees (the fp-dequant route
    # has no exact cross-shard reduction). Slot tables / token buffers /
    # dense scratch state are replicated across the mesh.
    mesh: Optional[object] = None   # jax.sharding.Mesh, 1-D, axis "tp"
    tp_axis: str = "tp"
    # ---- observability (repro.obs; everything defaults OFF) ----
    # obs.device_metrics threads a counter dict through the engine_step
    # carry (accumulated INSIDE the jit'd burst, drained in bulk every
    # obs.drain_every bursts — the decode hot path stays zero-sync);
    # obs.trace records request/dispatch spans + a jsonl event log.
    obs: Optional[ObsConfig] = None
    # ---- self-speculative decoding (repro.serve.spec) ----
    # spec.k > 1 replaces every decode burst with a draft/verify
    # dispatch: k+1 cheap draft steps at the spec widths (a second
    # DequantContext over the SAME QTensor tree, own low-bit KV lane)
    # plus ONE fused (k+1)-token verify of the serving config. Emitted
    # tokens stay bit-identical to spec=None serving in every sampling
    # mode; only tokens-per-dispatch changes.
    spec: Optional[SpecConfig] = None


class Engine:
    """Slot-based continuous-batching engine over ``decode_step``."""

    def __init__(self, params, cfg: ModelConfig, ecfg: EngineConfig,
                 scales: Optional[Dict[str, jnp.ndarray]] = None,
                 kv_bits=None,
                 kv_ranges: Optional[Mapping] = None):
        """``kv_bits`` (paged mode): None/int uniform or {layer -> bits}
        from ``repro.kvcache.fit.allocate_kv_bits``. ``kv_ranges``:
        calibrated activation ranges (``SensitivityReport.act_ranges``)
        for the per-page dequant scales."""
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        self.scales = dict(scales) if scales else {}
        self._audio = cfg.family == "audio"
        # ---- observability (all off by default; see repro.obs) ----
        self._obs: Optional[ObsConfig] = ecfg.obs
        self._obs_counters = bool(ecfg.obs and ecfg.obs.device_metrics)
        self.tracer = Tracer(enabled=bool(ecfg.obs and ecfg.obs.trace))
        self.counters = DeviceCounters()
        self._drift = None              # optional obs.drift.DriftMonitor
        self._runnable = 0              # slots with work available (obs)
        # QTensor-packed weight blocks carry their scales inside the leaf
        # (repro.qtensor) — they need the DequantContext even when no
        # path-keyed scales dict is supplied
        self._qt_params = tree_has_qtensor(params)

        # ---- tensor-parallel mesh mode ----
        self._mesh = ecfg.mesh
        self._tp_axis = ecfg.tp_axis
        self._shard_plan: Dict[str, str] = {}
        self._tp = 1
        if self._mesh is not None:
            if self._tp_axis not in self._mesh.shape:
                raise ValueError(
                    f"EngineConfig.mesh must carry the {self._tp_axis!r} "
                    f"axis (got axes {tuple(self._mesh.shape)}) — build it "
                    "with repro.launch.mesh.make_tp_mesh")
            self._tp = int(self._mesh.shape[self._tp_axis])
            if ((self._qt_params or self.scales)
                    and not ecfg.int8_compute):
                raise ValueError(
                    "tensor-parallel serving of quantized weights needs "
                    "int8_compute=True: only the integer kernel route has "
                    "an exact (bit-identical) cross-shard reduction — the "
                    "fp-dequant path would psum floats")
            from repro.serve.quantized import shard_params
            self.params, self.scales, self._shard_plan = shard_params(
                params, self._mesh, self.scales, axis_name=self._tp_axis)
            self._repl = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec())

        self._paged = ecfg.kv_cache == "paged"
        self._pcfg: Optional[PagedKVConfig] = None
        self._kv_ranges = dict(kv_ranges) if kv_ranges else None
        if self._paged:
            if cfg.family == "ssm":
                raise ValueError("ssm family holds no KV cache to page")
            layers = params.get("layers") or params.get("groups") or {}
            if not (isinstance(layers, dict) and "0" in layers):
                raise ValueError(
                    "paged KV serving needs the unrolled parameter layout "
                    "(init_params with scan_layers=False)")
            self._pcfg = PagedKVConfig.build(
                cfg, ecfg.max_len, ecfg.max_slots, page_size=ecfg.page_size,
                num_pages=ecfg.kv_pages, kv_bits=kv_bits)
            self._n_kv_layers = kv_layer_count(cfg)
            self._share = ecfg.prefix_sharing and cfg.family != "hybrid"
            if ecfg.prefix_sharing and cfg.family == "hybrid":
                # a shared prefix would also need the SSM state at the
                # split point, which is not cached — attention pages
                # still paged, prefix reuse off
                log.info("hybrid family: prefix sharing disabled "
                         "(SSM state at the split is not cached)")

        # ---- self-speculative decoding (repro.serve.spec) ----
        spec = ecfg.spec
        self._spec = spec if (spec is not None and spec.enabled) else None
        if spec is not None and self._spec is None:
            log.info("spec.k=%d: running the plain burst scheduler "
                     "(speculation needs k > 1)", spec.k)
        self._draft_params = None
        self._draft_plain = False
        self._dpcfg: Optional[PagedKVConfig] = None
        if self._spec is not None:
            if cfg.family in ("ssm", "hybrid"):
                raise ValueError(
                    "speculative decoding needs a rollback-able cache: "
                    f"the {cfg.family} family's recurrent state cannot "
                    "rewind rejected draft tokens")
            if self._mesh is not None:
                raise NotImplementedError(
                    "speculative decoding under tensor-parallel serving "
                    "is not wired up yet (the draft lane needs its own "
                    "shard plan)")
            if self._spec.draft_bits is not None:
                if not self._qt_params:
                    raise ValueError(
                        "spec.draft_bits re-packs QTensor weight storage "
                        "— build params with serve.quantized."
                        "quantize_params")
                self._draft_params = derive_draft_params(
                    self.params, self._spec.draft_bits)
            else:
                self._draft_params = self.params  # low-bit-KV-only draft
            if (self._spec.materialize_draft and not self._spec.int8_compute
                    and tree_has_qtensor(self._draft_params)):
                # dequantize-once draft cache: the draft pays the plain
                # fp forward per step instead of re-dequantizing every
                # block k times per dispatch. Values (and the FIT
                # accept-rate trade) are unchanged — dequantize is
                # deterministic.
                from repro.qtensor import is_qtensor
                self._draft_params = jax.jit(lambda t: jax.tree_util.tree_map(
                    lambda l: (l.dequantize(cfg.param_dtype)
                               if is_qtensor(l) else l),
                    t, is_leaf=is_qtensor))(self._draft_params)
                self._draft_plain = True
            if self._paged:
                # the draft KV lane: a second set of page pools with the
                # same geometry at the draft width, driven by the LIVE
                # serving page table (injected per dispatch) so prefix
                # sharing / COW / recycling carry over page-for-page
                self._dpcfg = PagedKVConfig.build(
                    cfg, ecfg.max_len, ecfg.max_slots,
                    page_size=ecfg.page_size, num_pages=ecfg.kv_pages,
                    kv_bits=self._spec.draft_kv_bits)
            elif self._spec.draft_kv_bits not in (8, 16):
                raise ValueError(
                    "dense serving's draft KV lane supports 8 (static-"
                    f"scale int8) or 16 bits, got "
                    f"{self._spec.draft_kv_bits}; packed sub-byte widths "
                    "need kv_cache='paged'")

        S, G = ecfg.max_slots, ecfg.max_new_tokens
        cb = (cfg.num_codebooks,) if self._audio else ()
        self._tok_shape = (S, 1) + cb
        self._out_shape = (S, G) + cb

        # KV page pools shard by kv-head when the head count divides the
        # mesh; otherwise they stay replicated (still bit-identical)
        self._kv_shards = 1
        if (self._mesh is not None and self._paged
                and self._tp > 1 and cfg.num_kv_heads % self._tp == 0):
            self._kv_shards = self._tp
        if self._mesh is not None and self._paged:
            log.info("paged KV pools: %s across tp=%d",
                     f"sharded /{self._kv_shards} by kv-head"
                     if self._kv_shards > 1 else "replicated", self._tp)

        def make_ctx(scales):
            if self._mesh is not None:
                from repro.models.context import ShardedDequantContext
                return ShardedDequantContext(
                    scales, cfg.param_dtype, self._mesh, self._shard_plan,
                    int8_compute=ecfg.int8_compute,
                    kv_shards=self._kv_shards,
                    moe_dispatch=ecfg.moe_dispatch,
                    axis_name=self._tp_axis)
            if not scales and not self._qt_params:
                return Context()
            return DequantContext(scales, cfg.param_dtype,
                                  int8_compute=ecfg.int8_compute,
                                  moe_dispatch=ecfg.moe_dispatch)

        def make_draft_ctx(scales):
            # the draft pass runs its (optionally re-packed) tree under
            # its own context. Default is fp-dequant matmuls: on the CPU
            # oracle the ref integer route is the EXPENSIVE one, so the
            # fp draft is the cheap lane; flip spec.int8_compute on
            # hardware where the integer kernels win.
            if self._spec is None or self._draft_plain or (
                    not scales and not tree_has_qtensor(self._draft_params)):
                return Context()
            md = ecfg.moe_dispatch if self._spec.int8_compute else "einsum"
            return DequantContext(scales, cfg.param_dtype,
                                  int8_compute=self._spec.int8_compute,
                                  moe_dispatch=md)

        def prefill_fn(params, scales, state, toks):
            return prefill_into(params, state, toks, cfg, ctx=make_ctx(scales))

        def sample_first_fn(scales, logits_last, seed, temp, top_k, top_p):
            del scales
            lg = logits_last[..., :cfg.vocab_size]
            keys = request_keys(seed, jnp.zeros_like(seed))
            return sample_tokens(lg, keys, temp, top_k, top_p)

        def insert_fn(state, sub, slot, tok, tok0, out, slots, seed, temp,
                      top_k, top_p, budget):
            """Admit into ``slot``: scatter the prefilled state + write the
            slot-table row. All slot bookkeeping lives on device so decode
            bursts take no host->device transfers."""
            state = state_insert_slot(cfg, state, sub, slot)
            tok = tok.at[slot].set(tok0)
            out = out.at[slot, 0].set(tok0[0])
            slots = {
                "active": slots["active"].at[slot].set(True),
                "nwritten": slots["nwritten"].at[slot].set(1),
                "seeds": slots["seeds"].at[slot].set(seed),
                "temps": slots["temps"].at[slot].set(temp),
                "top_ks": slots["top_ks"].at[slot].set(top_k),
                "top_ps": slots["top_ps"].at[slot].set(top_p),
                "budget": slots["budget"].at[slot].set(budget),
            }
            return state, tok, out, slots

        def deactivate_fn(slots, slot):
            return dict(slots, active=slots["active"].at[slot].set(False))

        def engine_step_fn(params, scales, state, tok, out, slots, ctr,
                           steps, mode, stats=False):
            ctx = make_ctx(scales)
            active, nwritten = slots["active"], slots["nwritten"]
            act_tok = active.reshape((-1,) + (1,) * (tok.ndim - 1))
            # ``ctr`` is {} when device metrics are off — the branch is
            # static, so the off path compiles to the exact old graph.
            # ``stats`` (static too) selects the burst flavor: sampled
            # bursts additionally build the element-wise clip-stat
            # reductions (ObsConfig.stats_every cadence).
            with_ctr = bool(ctr)

            def body(carry, i):
                state, tok, ctr = carry
                if with_ctr:
                    # kernel-site emits (clip rates, call counts) land in
                    # the sink while decode_step traces; fold merges the
                    # traced sums into the scan carry — all on device
                    sink = obs_rt.CounterSink(stats=stats)
                    with obs_rt.collecting(sink):
                        logits, new = decode_step(params, state, tok, cfg,
                                                  ctx=ctx)
                    ctr = obs_rt.fold(ctr, sink)
                    ctr = obs_rt.ctr_add(ctr, "decode_steps", 1)
                    # per-step emitted-token count: mirrors the post-scan
                    # budget clamp exactly (parity-tested vs the host
                    # mirror in tests/test_obs.py)
                    emitted = active & (nwritten + i < slots["budget"])
                    ctr = obs_rt.ctr_add(
                        ctr, "decode_tokens",
                        jnp.sum(emitted.astype(jnp.int32)))
                else:
                    logits, new = decode_step(params, state, tok, cfg,
                                              ctx=ctx)
                # inactive slots: freeze position (cache/ssm writes are
                # harmless — fully overwritten at backfill)
                new = new._replace(pos=jnp.where(active, new.pos, state.pos))
                lg = logits[:, 0, ..., :cfg.vocab_size]
                # ``mode`` statically specializes the sampler to what the
                # ACTIVE requests need: per-row outputs are identical
                # across modes, so the specialization is invisible to
                # parity — it only removes dead compute (sorts / PRNG)
                if mode == "greedy":
                    nxt = greedy_tokens(lg)
                else:
                    keys = request_keys(slots["seeds"], nwritten + i)
                    nxt = sample_tokens(lg, keys, slots["temps"],
                                        slots["top_ks"], slots["top_ps"],
                                        skip_filters=(mode == "nofilter"))
                tok = jnp.where(act_tok, nxt[:, None], tok)
                return (new, tok, ctr), nxt

            (state, tok, ctr), ys = jax.lax.scan(
                body, (state, tok, ctr), jnp.arange(steps))
            if with_ctr:
                ctr = obs_rt.ctr_add(ctr, "decode_bursts", 1)
                bucket = min(max(steps.bit_length() - 1, 0),
                             obs_rt.HIST_BUCKETS - 1)    # steps is static
                ctr = obs_rt.ctr_add(ctr, "burst_size_hist", 1, idx=bucket)
            # one scatter per burst (a per-step scatter in the scan body
            # costs ~2x the whole decode step on CPU): ys is (steps, S
            # [, CB]). Inactive slots and columns past a slot's token
            # budget get an out-of-range column and are dropped — bursts
            # may overshoot a nearly-done slot so the batch keeps moving.
            cols = nwritten[None, :] + jnp.arange(steps)[:, None]
            keep = active[None, :] & (cols < slots["budget"][None, :])
            cols = jnp.where(keep, cols, out.shape[1])
            rows = jnp.broadcast_to(jnp.arange(ecfg.max_slots)[None, :],
                                    cols.shape)
            out = out.at[rows, cols].set(ys, mode="drop")
            slots = dict(slots, nwritten=jnp.minimum(
                nwritten + steps * active, slots["budget"]))
            return state, tok, out, slots, ctr

        def spec_step_fn(params, scales, draft_params, state, dstate, ptok,
                         tok, out, slots, ctr, k, mode, stats=False):
            """One speculative dispatch (static ``k``): k draft
            invocations at the draft config (one fused 2-token catch-up
            + k-1 single-token steps), ONE fused (k+1)-token verify at
            the serving config, coupled-rejection accept, positional
            rollback of both lanes. Each active slot emits
            min(matched prefix + 1, remaining budget) tokens — bitwise
            the tokens ``engine_step_fn`` would have produced, whatever
            the sampling mode, because every verify column re-samples
            token index nwritten+i from bitwise-identical logits with
            the same fold_in(seed, t) key and the same sampler."""
            ctx = make_ctx(scales)
            dctx = make_draft_ctx(scales)
            active, nwritten = slots["active"], slots["nwritten"]
            act_tok = active.reshape((-1,) + (1,) * (tok.ndim - 1))
            with_ctr = bool(ctr)
            if self._paged:
                # draft pools mirror the serving pools page-for-page:
                # driving them with the LIVE serving table/limits makes
                # prefix sharing, COW and recycling carry over for free
                dstate = dstate._replace(paged=dstate.paged._replace(
                    table=state.paged.table,
                    write_limit=state.paged.write_limit))

            def sample_col(lg_col, i):
                # EXACTLY the non-speculative sampler for token index
                # nwritten + i (key, filters, mode specialization)
                if mode == "greedy":
                    return greedy_tokens(lg_col)
                keys = request_keys(slots["seeds"], nwritten + i)
                return sample_tokens(lg_col, keys, slots["temps"],
                                     slots["top_ks"], slots["top_ps"],
                                     skip_filters=(mode == "nofilter"))

            # ---- draft: k invocations for k proposals. The draft lane
            # LAGS the emitted stream by one position: the first
            # invocation is a fused 2-token catch-up over (second-last,
            # last) emitted tokens — it re-writes the lane's KV at the
            # lag position (bitwise the value already there mid-stream:
            # same token, same prefix, same route) and writes the KV the
            # previous dispatch's bonus/correction token never got. A
            # lockstep lane would need k+1 single-token steps for the
            # same k proposals (the extra step existed ONLY to write
            # that trailing KV; its sampled token was discarded). ----
            def draft_call(dst, toks, ctr):
                if with_ctr:
                    sink = obs_rt.CounterSink(stats=stats)
                    with obs_rt.collecting(sink):
                        lg_, dnew = decode_step(draft_params, dst, toks,
                                                cfg, ctx=dctx)
                    ctr = obs_rt.fold(ctr, sink)
                else:
                    lg_, dnew = decode_step(draft_params, dst, toks, cfg,
                                            ctx=dctx)
                dnew = dnew._replace(
                    pos=jnp.where(active, dnew.pos, dst.pos))
                return lg_, dnew, ctr

            pair = jnp.concatenate([ptok, tok], axis=1)  # (S, 2[, CB])
            lg2, dnew, ctr = draft_call(dstate, pair, ctr)
            p0 = sample_col(lg2[:, 1, ..., :cfg.vocab_size], 0)

            def draft_body(carry, i):
                dst, dtok, ctr = carry
                lg_, dnw, ctr = draft_call(dst, dtok, ctr)
                nxt = sample_col(lg_[:, 0, ..., :cfg.vocab_size], i)
                dtok = jnp.where(act_tok, nxt[:, None], dtok)
                return (dnw, dtok, ctr), nxt

            dtok0 = jnp.where(act_tok, p0[:, None], tok)
            (dfin, _, ctr), dts = jax.lax.scan(
                draft_body, (dnew, dtok0, ctr), jnp.arange(1, k))
            drafts = jnp.concatenate(
                [p0[:, None], jnp.moveaxis(dts, 0, 1)], axis=1)  # (S, k)

            # ---- verify: ONE fused multi-token serving forward ----
            vtoks = jnp.concatenate([tok, drafts], axis=1)
            if with_ctr:
                sink = obs_rt.CounterSink(stats=stats)
                with obs_rt.collecting(sink):
                    logits, vnew = decode_step(params, state, vtoks, cfg,
                                               ctx=ctx)
                ctr = obs_rt.fold(ctr, sink)
            else:
                logits, vnew = decode_step(params, state, vtoks, cfg,
                                           ctx=ctx)
            lg = logits[..., :cfg.vocab_size]            # (S, k+1[,CB],V)
            tgt = jnp.stack([sample_col(lg[:, i], i)
                             for i in range(k + 1)], axis=1)

            n_emit, n_match = accept_drafts(drafts, tgt, active, nwritten,
                                            slots["budget"])

            # ---- emit: matched prefix + correction/bonus token ----
            cols = nwritten[:, None] + jnp.arange(k + 1)[None, :]
            keep = active[:, None] & (jnp.arange(k + 1)[None, :]
                                      < n_emit[:, None])
            cols = jnp.where(keep, cols, out.shape[1])
            rows = jnp.broadcast_to(
                jnp.arange(ecfg.max_slots)[:, None], cols.shape)
            out = out.at[rows, cols].set(tgt, mode="drop")

            # next input token = the last emitted target token (frozen
            # when nothing was emitted: inactive or out of budget)
            last = jnp.maximum(n_emit - 1, 0)
            idx = jnp.broadcast_to(
                last.reshape((last.shape[0], 1) + (1,) * (tgt.ndim - 2)),
                (last.shape[0], 1) + tgt.shape[2:])
            ntok = jnp.take_along_axis(tgt, idx, axis=1)
            emitted = (n_emit > 0).reshape(
                (-1,) + (1,) * (tok.ndim - 1))
            # second-last stream token (position P + n_emit - 1) — the
            # catch-up pair's first element on the NEXT dispatch
            last2 = jnp.maximum(n_emit - 2, 0)
            idx2 = jnp.broadcast_to(
                last2.reshape((last2.shape[0], 1) + (1,) * (tgt.ndim - 2)),
                (last2.shape[0], 1) + tgt.shape[2:])
            two = (n_emit >= 2).reshape((-1,) + (1,) * (tok.ndim - 1))
            ptok = jnp.where(
                act_tok & emitted,
                jnp.where(two, jnp.take_along_axis(tgt, idx2, axis=1), tok),
                ptok)
            tok = jnp.where(act_tok & emitted, ntok, tok)

            # ---- rollback: both lanes rewind to P + n_emit. Rejected
            # KV writes stay in the caches past the rolled-back position
            # — masked by the per-row causal mask / write limits, and
            # overwritten as the stream advances. ----
            vnew = vnew._replace(
                pos=jnp.where(active, state.pos + n_emit, state.pos))
            dfin = dfin._replace(
                pos=jnp.where(active, dstate.pos + n_emit, dstate.pos))

            slots = dict(slots, nwritten=nwritten + n_emit)
            if with_ctr:
                n_act = jnp.sum(active.astype(jnp.int32))
                ctr = obs_rt.ctr_add(ctr, "decode_bursts", 1)
                ctr = obs_rt.ctr_add(ctr, "decode_steps", k + 1)
                ctr = obs_rt.ctr_add(ctr, "decode_tokens",
                                     jnp.sum(n_emit))
                bucket = min(max((k + 1).bit_length() - 1, 0),
                             obs_rt.HIST_BUCKETS - 1)
                ctr = obs_rt.ctr_add(ctr, "burst_size_hist", 1, idx=bucket)
                ctr = obs_rt.ctr_add(ctr, "spec_proposed", k * n_act)
                ctr = obs_rt.ctr_add(
                    ctr, "spec_accepted",
                    jnp.sum(jnp.where(active, n_match, 0)))
            return vnew, dfin, ptok, tok, out, slots, ctr, n_emit

        self._prefill = jax.jit(prefill_fn, donate_argnums=(2,))
        self._sample_first = jax.jit(sample_first_fn)
        self._insert = jax.jit(insert_fn, donate_argnums=(0, 3, 5, 6))
        self._deactivate = jax.jit(deactivate_fn, donate_argnums=(0,))
        self._engine_step = jax.jit(engine_step_fn,
                                    static_argnames=("steps", "mode",
                                                     "stats"),
                                    donate_argnums=(2, 3, 4, 5, 6))
        self._warmed_modes: set = set()
        self._make_ctx = make_ctx       # reused by obs.drift's probes

        if self._spec is not None:
            self._spec_step = jax.jit(
                spec_step_fn, static_argnames=("k", "mode", "stats"),
                donate_argnums=(3, 4, 5, 6, 7, 8, 9))
            dkb = self._spec.draft_kv_bits

            def insert_draft_fn(dstate, sub, slot):
                """Seed the dense draft lane at admission: the TARGET
                prefill's KV quantized onto the draft lane's grid, so
                the draft attends to the full prompt from step one. The
                lane starts one position BEHIND the serving stream —
                the first dispatch's catch-up pair lands on the last
                prompt token (see ``spec_step_fn``)."""
                if dkb != 16:
                    sub = sub._replace(kv=quantize_dense_kv(sub.kv, dkb))
                sub = sub._replace(pos=sub.pos - 1)
                return state_insert_slot(cfg, dstate, sub, slot)

            if self._paged:
                nl_d = kv_layer_count(cfg)

                def insert_draft_paged_fn(dstate, sub, row, slot, start,
                                          plen):
                    """Paged draft admission: scatter the prefilled KV
                    span [start, plen) into the DRAFT pools at the same
                    page rows the serving insert used (quantized to the
                    draft width by scatter_span)."""
                    ps = dstate.paged
                    layers = dict(ps.layers)
                    for i in range(nl_d):
                        layers[str(i)] = scatter_span(
                            layers[str(i)], row, sub.kv.k[i, 0],
                            sub.kv.v[i, 0], start, plen)
                    # one behind the serving stream (see spec_step_fn)
                    return dstate._replace(
                        pos=dstate.pos.at[slot].set(plen - 1),
                        paged=ps._replace(layers=layers))

                def copy_page_draft_fn(dstate, src, dst):
                    # COW mirror: when the serving pool copies a shared
                    # boundary page, the draft pool must copy the SAME
                    # page ids so the lanes keep mirroring page-for-page
                    ps = dstate.paged
                    layers = {n: copy_page(lp, src, dst)
                              for n, lp in ps.layers.items()}
                    return dstate._replace(paged=ps._replace(layers=layers))

                self._insert_draft_paged = jax.jit(
                    insert_draft_paged_fn, donate_argnums=(0,))
                self._copy_page_draft = jax.jit(copy_page_draft_fn,
                                                donate_argnums=(0,))
            else:
                self._insert_draft = jax.jit(insert_draft_fn,
                                             donate_argnums=(0,))

        if self._paged:
            nl = self._n_kv_layers

            def insert_paged_fn(state, sub, slot, row, start, plen, limit,
                                tok, tok0, out, slots, seed, temp, top_k,
                                top_p, budget):
                """Paged admission: scatter the scratch-prefilled KV span
                [start, plen) into the slot's pages (tokens < start came
                from a shared prefix and are already in the pool), map
                the slot's page-table row, and write the slot-table row
                exactly like the dense insert."""
                ps = state.paged
                layers = dict(ps.layers)
                for i in range(nl):
                    layers[str(i)] = scatter_span(
                        layers[str(i)], row, sub.kv.k[i, 0], sub.kv.v[i, 0],
                        start, plen)
                pos = state.pos.at[slot].set(plen)
                ssm = rest = None
                if state.ssm is not None:
                    ax = 2 if cfg.family == "hybrid" else 1

                    def put(a):
                        def one(dst, src):
                            idx = (slice(None),) * a + (slot,)
                            return dst.at[idx].set(
                                jax.lax.index_in_dim(src, 0, a,
                                                     keepdims=False))
                        return one
                    ssm = jax.tree.map(put(ax), state.ssm, sub.ssm)
                    if state.rest is not None:
                        rest = jax.tree.map(put(1), state.rest, sub.rest)
                state = DecodeState(
                    pos=pos, ssm=ssm, rest=rest,
                    paged=ps._replace(
                        layers=layers,
                        table=ps.table.at[slot].set(row),
                        write_limit=ps.write_limit.at[slot].set(limit)))
                tok = tok.at[slot].set(tok0)
                out = out.at[slot, 0].set(tok0[0])
                slots = {
                    "active": slots["active"].at[slot].set(True),
                    "nwritten": slots["nwritten"].at[slot].set(1),
                    "seeds": slots["seeds"].at[slot].set(seed),
                    "temps": slots["temps"].at[slot].set(temp),
                    "top_ks": slots["top_ks"].at[slot].set(top_k),
                    "top_ps": slots["top_ps"].at[slot].set(top_p),
                    "budget": slots["budget"].at[slot].set(budget),
                }
                return state, tok, out, slots

            def gather_fn(state, row, shared_len):
                """Shared prefix -> dense batch-1 scratch cache (suffix
                prefill attends to it without recomputation)."""
                ks, vs = [], []
                for i in range(nl):
                    kg, vg = gather_layer(state.paged.layers[str(i)], row,
                                          shared_len, cfg.param_dtype)
                    ks.append(kg)
                    vs.append(vg)
                kvd = KVCache(jnp.stack(ks)[:, None], jnp.stack(vs)[:, None])
                if self._mesh is not None:
                    # the batch-1 scratch state is replicated: without the
                    # constraint the pool's kv-head sharding would leak
                    # into the prefill graph's fp attention
                    kvd = jax.lax.with_sharding_constraint(kvd, self._repl)
                return kvd

            def copy_page_fn(state, src, dst):
                ps = state.paged
                layers = {k: copy_page(lp, src, dst)
                          for k, lp in ps.layers.items()}
                return state._replace(paged=ps._replace(layers=layers))

            def set_table_fn(state, table):
                return state._replace(
                    paged=state.paged._replace(table=table))

            def clear_slot_fn(state, slot):
                ps = state.paged
                return state._replace(paged=ps._replace(
                    table=ps.table.at[slot].set(self._pcfg.num_pages),
                    write_limit=ps.write_limit.at[slot].set(0)))

            self._insert_paged = jax.jit(insert_paged_fn,
                                         donate_argnums=(0, 7, 9, 10))
            self._gather = jax.jit(gather_fn)
            self._copy_page = jax.jit(copy_page_fn, donate_argnums=(0,))
            self._set_table = jax.jit(set_table_fn, donate_argnums=(0,))
            self._clear_slot = jax.jit(clear_slot_fn, donate_argnums=(0,))

    def _put_repl(self, tree):
        """Mesh mode: commit a fresh host-built tree replicated across the
        tp mesh (slot tables, token/output buffers, batch-1 scratch
        states) so jit never has to guess a placement."""
        if self._mesh is None:
            return tree
        return jax.device_put(tree, self._repl)

    def _place_state(self, state: DecodeState) -> DecodeState:
        """Mesh mode: paged pools shard by kv-head (payload and scale
        axis 1), everything else replicates."""
        if self._mesh is None:
            return state
        if state.paged is None or self._kv_shards == 1:
            return self._put_repl(state)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kvcache.paged import LayerPages
        ax = self._tp_axis
        ns_pool = NamedSharding(self._mesh, P(None, ax, None, None))
        ns_scale = NamedSharding(self._mesh, P(None, ax))
        layers = {
            k: LayerPages(jax.device_put(lp.k, ns_pool),
                          jax.device_put(lp.v, ns_pool),
                          jax.device_put(lp.k_scale, ns_scale),
                          jax.device_put(lp.v_scale, ns_scale),
                          bits=lp.bits)
            for k, lp in state.paged.layers.items()}
        paged = state.paged._replace(
            layers=layers,
            table=jax.device_put(state.paged.table, self._repl),
            write_limit=jax.device_put(state.paged.write_limit, self._repl))
        rest = self._put_repl(DecodeState(state.pos, state.kv, state.ssm,
                                          state.rest, None))
        return rest._replace(paged=paged)

    def _fresh_slot_table(self) -> Dict[str, jnp.ndarray]:
        S = self.ecfg.max_slots
        return self._put_repl({
            "active": jnp.zeros(S, bool),
            "nwritten": jnp.zeros(S, jnp.int32),
            "seeds": jnp.zeros(S, jnp.int32),
            "temps": jnp.zeros(S, jnp.float32),
            "top_ks": jnp.zeros(S, jnp.int32),
            "top_ps": jnp.ones(S, jnp.float32),
            "budget": jnp.zeros(S, jnp.int32),
        })

    def _fresh_counters(self) -> Dict[str, jnp.ndarray]:
        """Device counter carry for engine_step: the FULL registry (the
        scan-carry structure must never change) when device metrics are
        on, ``{}`` (compiles to the unobserved graph) when off."""
        if not self._obs_counters:
            return {}
        return self._put_repl(init_counters())

    def attach_drift(self, monitor) -> None:
        """Register a ``repro.obs.drift.DriftMonitor`` — its cadenced tap
        runs after decode bursts (never inside the dispatch)."""
        self._drift = monitor

    @staticmethod
    def _mode_for(sampling_params) -> str:
        """The cheapest sampler specialization that serves these requests
        exactly (see engine_step_fn: outputs are mode-invariant)."""
        if all(s.temperature <= 0 for s in sampling_params):
            return "greedy"
        if all(s.top_k <= 0 and s.top_p >= 1 for s in sampling_params):
            return "nofilter"
        return "full"

    def _fresh_state(self) -> DecodeState:
        if self._paged:
            return self._place_state(init_paged_decode_state(
                self.cfg, self._pcfg, self.ecfg.max_slots,
                self._kv_ranges))
        return self._place_state(init_decode_state(
            self.cfg, self.ecfg.max_slots, self.ecfg.max_len,
            per_slot_pos=True))

    def _fresh_draft_state(self) -> DecodeState:
        """The draft lane's KV state (see repro.serve.spec): paged — a
        second set of page pools at the draft width; dense — a per-slot
        cache on ``attention_decode``'s static-scale int8 grid (or fp at
        16 bits)."""
        if self._paged:
            return init_paged_decode_state(
                self.cfg, self._dpcfg, self.ecfg.max_slots,
                self._kv_ranges)
        st = init_decode_state(self.cfg, self.ecfg.max_slots,
                               self.ecfg.max_len, per_slot_pos=True)
        if self._spec.draft_kv_bits != 16:
            st = st._replace(kv=jax.tree.map(
                lambda a: jnp.zeros(a.shape, jnp.int8), st.kv))
        return st

    def warmup(self, modes: Sequence[str] = ("greedy",)) -> None:
        """Compile every shape the serving loop dispatches: all power-of-
        two burst sizes (per sampler mode), the full prefill chunk, and
        the per-request admission helpers. Without this the first
        requests pay compile time inside the latency/throughput numbers.
        ``run`` calls this with the modes its request set needs."""
        modes = [m for m in modes if m not in self._warmed_modes]
        if not modes and self._warmed_modes:
            return
        cfg, ecfg = self.cfg, self.ecfg
        state = self._fresh_state()
        tok = self._put_repl(jnp.zeros(self._tok_shape, jnp.int32))
        out = self._put_repl(jnp.zeros(self._out_shape, jnp.int32))
        slots = self._fresh_slot_table()
        ctr = self._fresh_counters()        # scratch: discarded after warmup
        # with counters on, warm BOTH burst flavors (plain + sampled
        # clip-stats) so the stats_every cadence never compiles mid-run
        stats_variants = (False, True) if ctr else (False,)
        dstate = self._fresh_draft_state() if self._spec is not None \
            else None
        ptok = self._put_repl(jnp.zeros(self._tok_shape, jnp.int32)) \
            if self._spec is not None else None
        for mode in modes:
            if self._spec is not None:
                # spec mode replaces every decode burst with the one
                # draft/verify dispatch shape — no pow2 ladder to warm
                for stats in stats_variants:
                    (state, dstate, ptok, tok, out, slots, ctr,
                     _) = self._spec_step(
                        self.params, self.scales, self._draft_params,
                        state, dstate, ptok, tok, out, slots, ctr,
                        k=self._spec.k, mode=mode, stats=stats)
            else:
                k = 1
                while k <= ecfg.decode_burst:
                    for stats in stats_variants:
                        state, tok, out, slots, ctr = self._engine_step(
                            self.params, self.scales, state, tok, out,
                            slots, ctr, steps=k, mode=mode, stats=stats)
                    k *= 2
            self._warmed_modes.add(mode)
        cb = self._tok_shape[2:]
        ps = self._put_repl(init_decode_state(cfg, 1, ecfg.max_len))
        logits, ps = self._prefill(
            self.params, self.scales, ps,
            jnp.zeros((1, ecfg.prefill_chunk) + cb, jnp.int32))
        z1 = jnp.zeros(1, jnp.int32)
        tok0 = self._sample_first(self.scales, logits[:, -1], z1,
                                  jnp.zeros(1, jnp.float32), z1,
                                  jnp.ones(1, jnp.float32))
        if self._paged:
            row = jnp.full(self._pcfg.pages_per_slot, self._pcfg.num_pages,
                           jnp.int32)
            if self._share:
                kvd = self._gather(state, row, jnp.int32(0))
                ps = ps._replace(kv=kvd)
                state = self._copy_page(state, jnp.int32(0), jnp.int32(0))
            state, tok, out, slots = self._insert_paged(
                state, ps, jnp.int32(0), row, jnp.int32(0), jnp.int32(1),
                jnp.int32(2), tok, tok0, out, slots, jnp.int32(0),
                jnp.float32(0), jnp.int32(0), jnp.float32(1), jnp.int32(1))
            if self._spec is not None:
                dstate = self._insert_draft_paged(
                    dstate, ps, row, jnp.int32(0), jnp.int32(0),
                    jnp.int32(1))
                if self._share:
                    dstate = self._copy_page_draft(dstate, jnp.int32(0),
                                                   jnp.int32(0))
            state = self._set_table(
                state, jnp.full((ecfg.max_slots, self._pcfg.pages_per_slot),
                                self._pcfg.num_pages, jnp.int32))
            state = self._clear_slot(state, jnp.int32(0))
        else:
            state, tok, out, slots = self._insert(
                state, ps, jnp.int32(0), tok, tok0, out, slots, jnp.int32(0),
                jnp.float32(0), jnp.int32(0), jnp.float32(1), jnp.int32(1))
            if self._spec is not None:
                dstate = self._insert_draft(dstate, ps, jnp.int32(0))
        slots = self._deactivate(slots, jnp.int32(0))
        jax.block_until_ready(slots["active"])

    def prefill_logits(self, prompt) -> jnp.ndarray:
        """(V,) logits the engine samples a request's first token from:
        its own compiled chunked prefill over ``prompt`` on a fresh
        batch-1 scratch state (no prefix reuse) — what oracle checks
        compare against an independent reference forward."""
        ecfg = self.ecfg
        ps = self._put_repl(init_decode_state(self.cfg, 1, ecfg.max_len))
        toks = jnp.asarray(prompt)[None]
        logits = None
        for lo in range(0, toks.shape[1], ecfg.prefill_chunk):
            logits, ps = self._prefill(self.params, self.scales, ps,
                                       toks[:, lo:lo + ecfg.prefill_chunk])
        return logits[0, -1, ..., :self.cfg.vocab_size]

    # ------------------------------------------------------------------
    # clock
    # ------------------------------------------------------------------
    def _now(self) -> float:
        if self.ecfg.clock == "wall":
            return time.perf_counter() - self._t0
        return float(self._ticks)

    def _advance_to(self, t: float) -> None:
        if self.ecfg.clock == "wall":
            dt = t - self._now()
            if dt > 0:
                time.sleep(min(dt, 0.05))
        else:
            self._ticks = max(self._ticks, int(math.ceil(t)))

    # ------------------------------------------------------------------
    # run loop
    # ------------------------------------------------------------------
    def run(self, requests: Sequence[Request]
            ) -> Tuple[List[Request], EngineMetrics]:
        """Serve ``requests`` to completion; returns (finished, metrics).
        The metrics are fresh per run, and count every compile the run
        makes, per host phase (``repro.obs.trace``)."""
        self.metrics = EngineMetrics(max_slots=self.ecfg.max_slots)
        with counting(self.metrics):
            finished = self._serve(requests)
        return finished, self.metrics

    def _serve(self, requests: Sequence[Request]) -> List[Request]:
        # the aggregate mode is correct for any subset of the requests; a
        # burst uses the cheapest warmed mode its active slots allow
        self._run_mode = (self._mode_for([r.sampling for r in requests])
                          if requests else "greedy")
        self.warmup({"greedy", self._run_mode})
        cfg, ecfg = self.cfg, self.ecfg
        S = ecfg.max_slots
        self._state = self._fresh_state()
        if self._spec is not None:
            self._dstate = self._fresh_draft_state()
            self._ptok = self._put_repl(
                jnp.zeros(self._tok_shape, jnp.int32))
        # host-side speculation tallies (the drift gauge / bench read
        # these; exact per-dispatch counts live in the device counters)
        self.spec_stats = {"proposed": 0, "accepted": 0, "dispatches": 0}
        self._tok = self._put_repl(jnp.zeros(self._tok_shape, jnp.int32))
        self._out = self._put_repl(jnp.zeros(self._out_shape, jnp.int32))
        # device-resident slot table (bursts take zero host->device
        # transfers) + host mirrors for scheduling decisions
        self._dslots = self._fresh_slot_table()
        self._slots: List[Optional[Request]] = [None] * S
        self._active = np.zeros(S, bool)
        self._nwritten = np.zeros(S, np.int64)
        self._budget = np.zeros(S, np.int64)
        if self._paged:
            self._alloc = BlockAllocator(self._pcfg.num_pages,
                                         self._pcfg.page_size,
                                         prefix_sharing=self._share)
            self._rows: List[List[int]] = [[] for _ in range(S)]
            self._pos_h = np.zeros(S, np.int64)
            self._limit_h = np.zeros(S, np.int64)
            self._page_bytes = page_bytes_all_layers(cfg, self._pcfg)
        self._ticks = 0
        self._t0 = time.perf_counter()
        if self._paged:
            self.metrics.kv_total_pages = self._pcfg.num_pages
            self.metrics.kv_page_bytes = self._page_bytes
        self._ctr = self._fresh_counters()
        self._burst_i = 0
        run_sid = self.tracer.begin("run", cat="engine", tid=ENGINE_TID) \
            if self.tracer.enabled else None
        finished: List[Request] = []

        pending = collections.deque(
            sorted(requests, key=lambda r: (r.arrival_time, r.id)))

        while pending or self._active.any():
            # slots that HAVE work this iteration: active + arrived-but-
            # waiting requests (the honest occupancy denominator — idle
            # tail steps where nothing could run are not a scheduling
            # failure; see EngineMetrics.summary)
            n_arrived = 0
            for r in pending:
                if r.arrival_time > self._now():
                    break
                n_arrived += 1
            self._runnable = min(S, int(self._active.sum()) + n_arrived)
            # ---- admission: fill free slots with arrived requests ----
            while (pending and not self._active.all()
                   and pending[0].arrival_time <= self._now()):
                if not self._admit(pending[0]):
                    # KV pool full: defer, keep decoding to free pages
                    self.metrics.record_deferral()
                    self.tracer.event("admission_deferred",
                                      req=pending[0].id,
                                      pages_free=self._alloc.available())
                    break
                pending.popleft()
                self._harvest(finished)          # max_new_tokens == 1
            if not self._active.any():
                if pending:
                    if (self._paged
                            and pending[0].arrival_time <= self._now()):
                        raise RuntimeError(
                            f"KV page pool ({self._pcfg.num_pages} pages) "
                            f"cannot hold request {pending[0].id} even "
                            "with every slot idle — raise kv_pages or "
                            "lower max_new_tokens")
                    with self.tracer.phase("engine.wait_arrival",
                                           self.metrics):
                        self._advance_to(pending[0].arrival_time)
                continue

            # ---- decode burst ----
            # size by the SOONEST-finishing active slot (zero overshoot,
            # freed slot backfills right after), but floor at 4 steps so
            # dispatch overhead amortizes — a nearly-done slot overshoots
            # at most 3 steps, and the budget clamp drops those writes
            remaining = (self._budget - self._nwritten)[self._active]
            k = min(ecfg.decode_burst, int(remaining.min()))
            if k < 4:
                k = min(ecfg.decode_burst, 4, int(remaining.max()))
            if (pending and not self._active.all()
                    and self.ecfg.clock == "steps"):
                # a free slot exists: don't decode past the next arrival.
                # Only meaningful in the step clock, where the gap IS a
                # step count; in wall mode a burst is ~ms, so admission
                # latency is bounded by the burst itself.
                gap = pending[0].arrival_time - self._now()
                if gap > 0:
                    k = max(1, min(k, int(math.ceil(gap))))
            self._burst(max(k, 1))
            self._harvest(finished)

        if self._obs_counters:
            with self.tracer.phase("engine.drain", self.metrics):
                self.counters.drain(self._ctr)   # final end-of-run drain
            self.tracer.event("drain", n=self.counters.n_drains)
        if run_sid is not None:
            self.tracer.end(run_sid, {"requests": len(finished),
                                      "deferrals":
                                      self.metrics.admission_deferrals})
        finished.sort(key=lambda r: r.id)
        return finished

    # ------------------------------------------------------------------
    def _pad_row(self, ids: List[int]) -> jnp.ndarray:
        row = np.full(self._pcfg.pages_per_slot, self._pcfg.num_pages,
                      np.int32)
        row[:len(ids)] = ids
        return jnp.asarray(row)

    def _plan_pages(self, slot: int, req: Request):
        """Allocator side of paged admission: match the prompt's prefix
        against resident pages, claim/allocate, and reserve the decode
        growth. Returns None (admission deferred) if the pool cannot
        also cover the request's worst-case decode — reserving up front
        is what makes mid-decode page exhaustion impossible."""
        alloc, page = self._alloc, self._pcfg.page_size
        plen = req.prompt_len
        prompt = np.asarray(req.prompt)
        limit = min(plen + req.max_new_tokens, self.ecfg.max_len)
        total_pages = -(-limit // page)
        full_ids, shared_len, partial_src = ([], 0, None)
        if self._share:
            full_ids, shared_len, partial_src = alloc.match_prefix(
                prompt, plen - 1)
        n_prompt_pages = -(-plen // page)
        new_now = n_prompt_pages - len(full_ids)
        future = total_pages - n_prompt_pages
        if alloc.available() < new_now + future:
            return None
        alloc.claim(full_ids)
        fresh = alloc.allocate(new_now)
        alloc.reserve(slot, future)
        alloc.shared_tokens += shared_len
        if partial_src is not None:
            alloc.cow_copies += 1
        row = list(full_ids) + list(fresh)
        gather_ids = list(full_ids) + ([partial_src]
                                       if partial_src is not None else [])
        return shared_len, partial_src, row, gather_ids

    def _admit(self, req: Request) -> bool:
        """Prefill ``req`` into a free slot; False (nothing changed) when
        the KV pool cannot hold it yet. Three kinds of phase span: the
        admission itself, one per prefill chunk (decode bursts may run
        between them), and the insert into the slot."""
        ecfg, tr, m = self.ecfg, self.tracer, self.metrics
        with tr.phase("engine.admit", m):
            slot = int(np.flatnonzero(~self._active)[0])
            if req.prompt_len >= ecfg.max_len:
                raise ValueError(
                    f"request {req.id}: prompt ({req.prompt_len}) does not "
                    f"fit the engine's max_len ({ecfg.max_len})")
            # token budget is bounded by BOTH the KV capacity and the
            # output buffer width — without the latter, tokens past the
            # buffer would be computed and then scatter-dropped silently
            budget = min(ecfg.max_len - req.prompt_len, ecfg.max_new_tokens)
            if req.max_new_tokens > budget:
                log.warning("request %d: max_new_tokens %d clipped to %d "
                            "(max_len %d, max_new_tokens %d)", req.id,
                            req.max_new_tokens, budget, ecfg.max_len,
                            ecfg.max_new_tokens)
                req.max_new_tokens = budget

            shared_len, partial_src, row, gather_ids = 0, None, None, None
            if self._paged:
                plan = self._plan_pages(slot, req)
                if plan is None:
                    return False               # pool full — try later
                shared_len, partial_src, row, gather_ids = plan
            req.slot, req.status = slot, RequestStatus.PREFILLING
            req.t_admitted = self._now()
            rtid = tr.request_tid(req.id) if tr.enabled else ENGINE_TID
            if tr.enabled:
                # the request's lifecycle span (one per tid row in
                # Perfetto); closed at eviction in _harvest
                req.obs_span = tr.begin(f"request {req.id}", cat="request",
                                        tid=rtid,
                                        args={"prompt_len": req.prompt_len})
            admit_sid = tr.begin("admit", cat="admit", tid=rtid) \
                if tr.enabled else None

            pstate = self._put_repl(init_decode_state(self.cfg, 1,
                                                      ecfg.max_len))
            if shared_len > 0:
                # prefix reuse: seed the scratch cache from the shared
                # pages and prefill only the suffix (the engine's
                # prefill saving)
                with tr.span("gather_prefix", cat="admit", tid=rtid,
                             args={"shared_len": shared_len}):
                    kvd = self._gather(self._state,
                                       self._pad_row(gather_ids),
                                       jnp.int32(shared_len))
                pstate = pstate._replace(pos=jnp.int32(shared_len), kv=kvd)
            prompt = jnp.asarray(req.prompt)[None]           # (1, P[, CB])

        logits = None
        for lo in range(shared_len, req.prompt_len, ecfg.prefill_chunk):
            n_decoding = int(self._active.sum())
            sid = tr.begin("prefill_chunk", cat="prefill", tid=rtid) \
                if tr.enabled else None
            with tr.phase("engine.prefill_chunk", m) as ph:
                chunk = prompt[:, lo:lo + ecfg.prefill_chunk]
                logits, pstate = self._prefill(self.params, self.scales,
                                               pstate, chunk)
                jax.block_until_ready(logits)
            ph.note(tokens=int(chunk.shape[1]), req=req.id,
                    n_decoding=n_decoding)
            if sid is not None:
                tr.end(sid, {"tokens": int(chunk.shape[1]), "lo": lo})
            m.record_prefill(ph.s, chunk.shape[1], n_decoding)
            if self.ecfg.clock == "steps":
                self._ticks += chunk.shape[1]
            # chunked prefill: keep in-flight decodes moving between
            # chunks — but only once the batch is nearly full (during the
            # initial ramp it's better to fill slots first and decode at
            # full occupancy than to burn low-occupancy bursts)
            if (ecfg.interleave_steps
                    and int(self._active.sum()) >= max(1, ecfg.max_slots - 1)
                    and lo + ecfg.prefill_chunk < req.prompt_len):
                rem = (self._budget - self._nwritten)[self._active]
                self._burst(min(ecfg.interleave_steps, int(rem.min())))

        with tr.phase("engine.insert", m):
            s = req.sampling
            tok0 = self._sample_first(
                self.scales, logits[:, -1],
                jnp.asarray([s.seed], jnp.int32),
                jnp.asarray([s.temperature], jnp.float32),
                jnp.asarray([s.top_k], jnp.int32),
                jnp.asarray([s.top_p], jnp.float32))
            if self._paged:
                if partial_src is not None:
                    # copy-on-write: own the partially-filled boundary
                    # page before the suffix insert writes into it
                    dst = row[len(gather_ids) - 1]
                    self._state = self._copy_page(self._state,
                                                  jnp.int32(partial_src),
                                                  jnp.int32(dst))
                plen = req.prompt_len
                limit = min(plen + req.max_new_tokens, ecfg.max_len)
                self._state, self._tok, self._out, self._dslots = \
                    self._insert_paged(
                        self._state, pstate, jnp.int32(slot),
                        self._pad_row(row), jnp.int32(shared_len),
                        jnp.int32(plen), jnp.int32(limit), self._tok, tok0,
                        self._out, self._dslots, jnp.int32(s.seed),
                        jnp.float32(s.temperature), jnp.int32(s.top_k),
                        jnp.float32(s.top_p), jnp.int32(req.max_new_tokens))
                self._alloc.register_prompt(np.asarray(req.prompt), row, plen)
                self._rows[slot] = row
                self._pos_h[slot] = plen
                self._limit_h[slot] = limit
                m.record_kv_usage(self._alloc.pages_in_use)
                m.kv_shared_tokens = self._alloc.shared_tokens
                m.kv_cow_copies = self._alloc.cow_copies
            else:
                self._state, self._tok, self._out, self._dslots = \
                    self._insert(
                        self._state, pstate, jnp.int32(slot), self._tok,
                        tok0, self._out, self._dslots, jnp.int32(s.seed),
                        jnp.float32(s.temperature), jnp.int32(s.top_k),
                        jnp.float32(s.top_p), jnp.int32(req.max_new_tokens))

            if self._spec is not None:
                # seed the draft lane from the SAME prefilled scratch
                # state: target-computed prompt KV quantized onto the
                # draft grid
                if self._paged:
                    if partial_src is not None:
                        # mirror the serving COW copy before the suffix
                        # scatter writes into the owned boundary page
                        dst = row[len(gather_ids) - 1]
                        self._dstate = self._copy_page_draft(
                            self._dstate, jnp.int32(partial_src),
                            jnp.int32(dst))
                    self._dstate = self._insert_draft_paged(
                        self._dstate, pstate, self._pad_row(row),
                        jnp.int32(slot), jnp.int32(shared_len),
                        jnp.int32(req.prompt_len))
                else:
                    self._dstate = self._insert_draft(self._dstate, pstate,
                                                      jnp.int32(slot))
                # the catch-up pair's first element for the first
                # dispatch: the LAST PROMPT token (stream position
                # prompt_len - 1, where the lagged draft lane starts)
                cb = self._tok_shape[2:]
                self._ptok = self._ptok.at[slot].set(
                    jnp.asarray(np.asarray(req.prompt)[-1],
                                jnp.int32).reshape((1,) + cb))

            self._slots[slot] = req
            self._active[slot] = True
            self._nwritten[slot] = 1
            self._budget[slot] = req.max_new_tokens
            req.t_first_token = self._now()
            req.status = RequestStatus.RUNNING
            if admit_sid is not None:
                tr.end(admit_sid, {"slot": slot, "shared_len": shared_len})
            tr.event("admit", req=req.id, slot=slot, shared_len=shared_len,
                     prompt_len=req.prompt_len)
        return True

    # ------------------------------------------------------------------
    def _grow_tables(self, steps: int) -> None:
        """Before a paged burst: extend each active slot's page row to
        cover its next ``steps`` writes (reservations made at admission
        guarantee the pages exist). All grown rows push to the device in
        ONE full-table upload — (S, NP) int32 is tiny, and one dispatch
        beats one per slot on the decode hot path. At most
        ceil(steps/page) new pages per slot per burst."""
        with self.tracer.phase("engine.grow_tables", self.metrics):
            page = self._pcfg.page_size
            grew = False
            for b in np.flatnonzero(self._active):
                need = -(-min(self._pos_h[b] + steps, self._limit_h[b])
                         // page)
                have = len(self._rows[b])
                if need <= have:
                    continue
                ids = self._alloc.allocate(need - have, owner=int(b))
                assert ids is not None, "reservation accounting broken"
                self._rows[b] += ids
                grew = True
            if grew:
                table = np.full((self.ecfg.max_slots,
                                 self._pcfg.pages_per_slot),
                                self._pcfg.num_pages, np.int32)
                for b in np.flatnonzero(self._active):
                    table[b, :len(self._rows[b])] = self._rows[b]
                self._state = self._set_table(self._state, jnp.asarray(table))
                self.metrics.record_kv_usage(self._alloc.pages_in_use)

    def _burst(self, steps: int) -> None:
        if steps <= 0:
            return
        if self._spec is not None:
            # EVERY decode burst routes through the draft/verify
            # dispatch (a plain burst would advance the serving lane
            # without the draft lane and desync their positions); the
            # per-slot budget clamp absorbs the caller's steps bound
            return self._spec_burst()
        # round down to a power of two: callers pass upper bounds, and a
        # bounded set of burst shapes keeps the compile count at
        # O(log decode_burst) instead of one per distinct remaining-count
        steps = 1 << (steps.bit_length() - 1)
        if self._paged:
            self._grow_tables(steps)
        exact = self._mode_for([self._slots[b].sampling
                                for b in np.flatnonzero(self._active)])
        mode = exact if exact in self._warmed_modes else self._run_mode
        n_active = int(self._active.sum())
        # sampled clip-stat cadence: every stats_every-th burst carries
        # the element-wise saturation reductions; the rest run the cheap
        # counter graph (scalar call/token adds only)
        stats = bool(self._ctr) and \
            self._burst_i % self._obs.stats_every == 0
        # the span's synced wall IS the burst-latency measurement
        with self.tracer.phase("engine.decode_burst", self.metrics) as ph:
            (self._state, self._tok, self._out, self._dslots,
             self._ctr) = self._engine_step(
                self.params, self.scales, self._state, self._tok, self._out,
                self._dslots, self._ctr, steps=steps, mode=mode, stats=stats)
            jax.block_until_ready(self._tok)  # rpr-ok: RPR008 timed sync — the burst latency metric is this wait
        # host mirror of the device-side clamp (tokens past a slot's
        # budget were dropped)
        before = self._nwritten[self._active]
        after = np.minimum(before + steps, self._budget[self._active])
        self._nwritten[self._active] = after
        if self._paged:
            self._pos_h[self._active] += steps
        n_tokens = int((after - before).sum())
        ph.note(steps=steps, mode=mode, n_active=n_active, tokens=n_tokens,
                tp=self._tp)
        self.metrics.record_burst(ph.s, steps, n_active,
                                  n_tokens=n_tokens,
                                  n_runnable=max(n_active, self._runnable),
                                  per_slot_tokens=[int(x)
                                                   for x in after - before])
        self._after_burst(steps)

    def _after_burst(self, steps: int) -> None:
        """Per-burst bookkeeping: the step clock, the cadenced bulk
        counter drain (the ONE audited host-transfer site on the serving
        loop, see obs.counters) and the drift tap."""
        if self.ecfg.clock == "steps":
            self._ticks += steps
        self._burst_i += 1
        de = self._obs.drain_every if self._obs is not None else 0
        if self._obs_counters and de and self._burst_i % de == 0:
            with self.tracer.phase("engine.drain", self.metrics):
                self.counters.drain(self._ctr)
        if self._drift is not None:
            with self.tracer.phase("engine.drift", self.metrics):
                self._drift.observe(steps)

    def _spec_burst(self) -> None:
        """One draft/verify dispatch (see ``spec_step_fn``). The only
        decode-loop host transfer is the per-slot accepted-token fetch —
        the scheduler cannot size budgets or grow page tables without
        it, and it doubles as the burst-latency timing sync that
        ``_burst`` gets from ``block_until_ready``."""
        k = self._spec.k
        if self._paged:
            # the verify writes up to k+1 serving positions (the draft
            # lane mirrors them through the injected table)
            self._grow_tables(k + 1)
        exact = self._mode_for([self._slots[b].sampling
                                for b in np.flatnonzero(self._active)])
        mode = exact if exact in self._warmed_modes else self._run_mode
        n_active = int(self._active.sum())
        stats = bool(self._ctr) and \
            self._burst_i % self._obs.stats_every == 0
        with self.tracer.phase("engine.spec_burst", self.metrics) as ph:
            (self._state, self._dstate, self._ptok, self._tok, self._out,
             self._dslots, self._ctr, n_emit) = self._spec_step(
                self.params, self.scales, self._draft_params, self._state,
                self._dstate, self._ptok, self._tok, self._out, self._dslots,
                self._ctr, k=k, mode=mode, stats=stats)
            ne = np.asarray(jax.device_get(n_emit))  # rpr-ok: RPR008 timed sync — scheduler control dependency + the burst latency metric
        # exact host mirror of the device update (n_emit is already
        # budget-clamped and zero for inactive slots)
        self._nwritten[self._active] += ne[self._active]
        if self._paged:
            self._pos_h[self._active] += ne[self._active]
        n_tokens = int(ne.sum())
        self.spec_stats["dispatches"] += 1
        self.spec_stats["proposed"] += k * n_active
        # host accept tally: emitted minus the always-emitted correction
        # token — undercounts only when the budget clamp truncated a
        # match run (the device spec_accepted counter is exact)
        self.spec_stats["accepted"] += int(
            np.maximum(ne[self._active] - 1, 0).sum())
        ph.note(k=k, mode=mode, n_active=n_active, tokens=n_tokens)
        self.metrics.record_burst(
            ph.s, k + 1, n_active, n_tokens=n_tokens,
            n_runnable=max(n_active, self._runnable),
            per_slot_tokens=[int(x) for x in ne[self._active]])
        self._after_burst(k + 1)

    # ------------------------------------------------------------------
    def _harvest(self, finished: List[Request]) -> None:
        """Evict finished slots (max-len/max-new or EOS) and record them."""
        if not self._active.any():
            return
        if ((self._nwritten < self._budget)[self._active].all()
                and all(self._slots[b].eos_id is None
                        for b in np.flatnonzero(self._active))):
            return                      # nothing can have finished
        with self.tracer.phase("engine.harvest", self.metrics):
            for b in np.flatnonzero(self._active):
                req = self._slots[b]
                count = int(self._nwritten[b])
                done = count >= self._budget[b]
                toks = None
                if done or req.eos_id is not None:
                    toks = np.asarray(self._out[b, :count])
                    if req.eos_id is not None:
                        flat = toks if toks.ndim == 1 else toks[:, 0]
                        hits = np.flatnonzero(flat == req.eos_id)
                        if hits.size:
                            toks = toks[:hits[0] + 1]
                            done = True
                if not done:
                    continue
                req.output_tokens = toks
                req.t_finished = self._now()
                req.status = RequestStatus.FINISHED
                self.metrics.record_request(req)
                finished.append(req)
                tr = self.tracer
                evict_sid = tr.begin("evict", cat="evict",
                                     tid=tr.request_tid(req.id),
                                     args={"slot": int(b)}) \
                    if tr.enabled else None
                self._slots[b] = None      # slot freed: backfilled by the
                self._active[b] = False    # admission loop next iteration
                self._dslots = self._deactivate(self._dslots, jnp.int32(b))
                if self._paged:
                    # recycle the request's pages (shared pages survive via
                    # their refcount) and unmap the slot's device row so a
                    # stale slot can never touch a recycled page
                    self.metrics.record_kv_request(
                        len(self._rows[b]) * self._page_bytes)
                    self._alloc.release(self._rows[b])
                    self._alloc.unreserve(int(b))
                    self._rows[b] = []
                    self._pos_h[b] = self._limit_h[b] = 0
                    self._state = self._clear_slot(self._state, jnp.int32(b))
                if evict_sid is not None:
                    tr.end(evict_sid)
                    span = getattr(req, "obs_span", None)
                    if span is not None:
                        tr.end(span, {"tokens": int(len(toks))})
                tr.event("finish", req=req.id, slot=int(b),
                         tokens=int(len(toks)))
