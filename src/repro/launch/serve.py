"""Serving CLI: thin driver over the ``repro.serve`` continuous-batching
engine.

Two traffic shapes:

  * closed-loop (default) — ``--batch`` identical requests at t=0, the
    legacy benchmark shape; returns a dense ``generated`` matrix.
  * open-loop — ``--requests N --rate R`` Poisson arrivals through the
    load generator, exercising admission/eviction/backfill under load.

Quantization: ``--weight-bits B`` fake-quantizes in fp storage (PTQ
numerics check, any layout); adding ``--int8`` materializes REAL int8
storage + a DequantContext (unrolled layout); adding ``--packed``
instead materializes truly packed QTensor storage (``repro.qtensor`` —
sub-byte widths actually shrink HBM: 0.75 B/elem at W6, 0.5 at W4/W3)
and ``--int8-compute`` routes those matmuls through the fused quantized
MXU kernel path (``kernels.qmm`` for QTensor, ``int8_matmul`` legacy).

KV cache: ``--paged`` switches the dense per-slot cache for the paged
pool (``repro.kvcache``) with ``--page-size`` token pages, ``--kv-bits``
storage (8 = int8, 4 = packed int4), an optional ``--kv-pages`` pool
budget, and hash-based prefix sharing (``--shared-prefix N`` makes the
generated prompts actually share one).

Tensor parallelism: ``--tp N`` shards packed/int8 weight blocks
column/row-wise and (when kv heads divide) the paged KV pools by
kv-head across a 1-D device mesh — outputs stay bit-identical to
``--tp 1`` (see README "Tensor-parallel serving"). Implies
``--int8-compute`` for quantized weights.

MoE archs (deepseek_moe_16b, olmoe_1b_7b): packed expert stacks serve
through the grouped ragged quantized kernel by default; ``--moe-dispatch
dense`` selects the per-expert loop oracle (bit-identical outputs) and
``--tp N`` additionally shards the expert stacks expert-parallel.

Speculative decoding: ``--spec-k K`` (K >= 2) turns on the
self-speculative draft/verify loop (``repro.serve.spec``) — emitted
token streams stay bit-identical to non-speculative serving;
``--spec-bits B`` additionally narrows the packed QTensor tree to B-bit
draft weights (requires ``--packed``; pass ``--spec-bits fit:AVG`` to
FIT-allocate a mixed draft config at AVG average bits from a fresh
sensitivity report), and ``--spec-kv-bits`` sets the draft KV lane's
storage width (8/16 dense, any paged width when ``--paged``).

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b \\
      --smoke --batch 8 --prompt-len 64 --gen-len 32 --weight-bits 8
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b \\
      --smoke --batch 4 --requests 8 --rate 0.05 --paged --kv-bits 8 \\
      --shared-prefix 32
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b \\
      --smoke --batch 2 --requests 6 --rate 0.05 --packed \\
      --weight-bits 4 --group-size 8 --paged --kv-bits 8 --tp 2
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Dict, Optional

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.models import init_params
from repro.quant.policy import QuantPolicy
from repro.quant.quantizer import QuantSpec, fake_quant_ref
from repro.serve import (
    Engine, EngineConfig, SamplingParams, poisson_requests, quantize_params,
    quantize_params_int8, trace_requests, weight_storage_bytes)
from repro.utils.compile_cache import use_compile_cache
from repro.utils.logging import get_logger
from repro.utils.pytree import map_with_names

log = get_logger("repro.serve")


def quantize_weights(params, weight_bits: Optional[int],
                     policy: Optional[QuantPolicy] = None):
    """PTQ: fake-quantize matmul weights to ``weight_bits`` (fp storage).

    Pinning comes from ``QuantPolicy`` (DEFAULT_PINNED) — the same rule
    set MPQ search uses, so serving and search never disagree about which
    blocks stay high-precision.
    """
    if weight_bits is None or weight_bits >= 16:
        return params
    policy = policy or QuantPolicy()

    def one(name, leaf):
        if not policy.quantizable(name, leaf.ndim):
            return leaf
        return fake_quant_ref(leaf, QuantSpec(bits=weight_bits))

    return map_with_names(one, params)


def serve(arch: str, smoke: bool, batch: int, prompt_len: int, gen_len: int,
          weight_bits: Optional[int], seed: int = 0, int8: bool = False,
          packed: bool = False,
          int8_compute: bool = False, n_requests: Optional[int] = None,
          rate: float = 1.0, sampling: Optional[SamplingParams] = None,
          prefill_chunk: int = 32, decode_burst: int = 16,
          clock: str = "steps", paged: bool = False, page_size: int = 16,
          kv_bits: Optional[int] = None, kv_pages: Optional[int] = None,
          prefix_sharing: bool = True, shared_prefix: int = 0,
          tp: int = 1, group_size: Optional[int] = None,
          moe_dispatch: str = "grouped",
          trace_path: Optional[str] = None,
          events_path: Optional[str] = None,
          metrics_file: Optional[str] = None,
          metrics_port: Optional[int] = None, drain_every: int = 8,
          drift_every: int = 0, drift_stale: float = 1.0,
          drift_threshold: float = 1.5, spec_k: int = 0,
          spec_bits: Optional[str] = None,
          spec_kv_bits: Optional[int] = None) -> Dict:
    """Build the model + engine, run the load, return results + metrics."""
    cfg = smoke_config(arch) if smoke else get_config(arch)
    spec_fit = spec_bits is not None and str(spec_bits).startswith("fit:")
    if int8 or packed or paged or drift_every:
        # per-layer dequant scales / page pools / payload shapes are
        # path-keyed: needs the unrolled layer layout (drift's per-site
        # probes key on unrolled paths too)
        cfg = dataclasses.replace(cfg, scan_layers=False)
    params = init_params(cfg, jax.random.key(seed))
    # pre-PTQ fp reference: drift probes + the FIT draft-bits report
    fp_params = params if (drift_every or spec_fit) else None

    mesh = None
    if tp > 1:
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(tp)
        if (int8 or packed) and not int8_compute:
            # sharded quantized matmuls only exist on the integer kernel
            # route (the exact cross-shard reduction) — switch it on
            log.info("--tp %d with quantized weights: enabling "
                     "--int8-compute (required for sharded execution)", tp)
            int8_compute = True

    scales = None
    policy = QuantPolicy()
    if (int8 or packed) and weight_bits is None:
        weight_bits = 8          # --int8/--packed alone means W8 storage
    if weight_bits is not None and weight_bits < 16:
        if packed:
            params, _ = quantize_params(params, weight_bits, policy,
                                        group_size=group_size)
            log.info("packed QTensor weights: %.0f bytes realized",
                     weight_storage_bytes(params))
        elif int8:
            params, scales = quantize_params_int8(params, weight_bits, policy)
        else:
            params = quantize_weights(params, weight_bits, policy)

    spec = None
    draft_plan = None
    if spec_k and spec_k > 1:
        from repro.serve import SpecConfig
        draft_bits = None
        if spec_bits is not None:
            if not packed:
                raise ValueError(
                    "--spec-bits narrows the packed QTensor tree for the "
                    "draft pass; it requires --packed")
            if spec_fit:
                # FIT-allocated mixed draft config: smoke sensitivity
                # report on synthetic calibration batches, then the
                # greedy knapsack at the requested average draft budget
                from repro.core import allocate_draft_bits, build_report
                from repro.data.synthetic import LMStreamConfig, lm_batches
                from repro.models import loss_fn as model_loss
                avg = float(str(spec_bits).split(":", 1)[1])
                stream = lm_batches(LMStreamConfig(
                    vocab_size=cfg.vocab_size, seq_len=32, global_batch=4,
                    seed=seed))
                report = build_report(
                    lambda p, b: model_loss(p, b, cfg), None, None, None,
                    fp_params, [next(stream) for _ in range(2)],
                    microbatch=4, tolerance=None, max_batches=2)
                draft_plan = allocate_draft_bits(report, policy,
                                                 avg_bits=avg)
                draft_bits = draft_plan.bits
                log.info("FIT draft plan: %.2f avg bits, KL proxy %.4g, "
                         "accept proxy %.2f", draft_plan.avg_bits,
                         draft_plan.kl_proxy, draft_plan.accept_proxy)
            else:
                draft_bits = int(spec_bits)
        spec = SpecConfig(k=spec_k, draft_bits=draft_bits,
                          draft_kv_bits=spec_kv_bits if spec_kv_bits
                          is not None else 8)

    sampling = sampling or SamplingParams()
    if n_requests is None:
        reqs = trace_requests(cfg, [(0.0, prompt_len, gen_len)] * batch,
                              sampling=sampling, seed=seed,
                              prefix_len=shared_prefix)
    else:
        reqs = poisson_requests(
            cfg, n_requests, rate,
            prompt_len=(max(1, prompt_len // 2), prompt_len),
            gen_len=(max(1, gen_len // 2), gen_len),
            sampling=sampling, seed=seed, prefix_len=shared_prefix)

    max_len = prompt_len + gen_len
    if paged:
        max_len = -(-max_len // page_size) * page_size    # page multiple
    obs = None
    if trace_path or events_path or metrics_file or metrics_port is not None:
        from repro.obs import ObsConfig
        obs = ObsConfig(trace=bool(trace_path or events_path),
                        device_metrics=True, drain_every=drain_every,
                        trace_path=trace_path, events_path=events_path,
                        metrics_file=metrics_file, metrics_port=metrics_port)
    ecfg = EngineConfig(
        max_slots=batch, max_len=max_len, max_new_tokens=gen_len,
        prefill_chunk=min(prefill_chunk, max(prompt_len, 1)),
        decode_burst=decode_burst, clock=clock, int8_compute=int8_compute,
        kv_cache="paged" if paged else "dense", page_size=page_size,
        kv_pages=kv_pages, prefix_sharing=prefix_sharing, mesh=mesh,
        moe_dispatch=moe_dispatch, obs=obs, spec=spec)
    engine = Engine(params, cfg, ecfg, scales=scales, kv_bits=kv_bits)

    monitor = None
    if drift_every:
        # FIT drift demo: fp reference + self-calibrating ranges;
        # --drift-stale S shrinks the calibration S x to simulate serving
        # past a stale SensitivityReport (flags every affected layer)
        from repro.obs.drift import DriftMonitor
        monitor = DriftMonitor(fp_params, {}, every=drift_every,
                               ratio_threshold=drift_threshold,
                               calibration_scale=1.0 / drift_stale)
        monitor.attach(engine)

    server = None
    if obs is not None and obs.metrics_port is not None:
        from repro.obs import MetricsServer
        from repro.obs import snapshot as obs_snapshot
        server = MetricsServer(obs.metrics_port,
                               lambda: obs_snapshot(engine))
        log.info("live /metrics endpoint on http://127.0.0.1:%d/metrics",
                 server.port)

    try:
        finished, metrics = engine.run(reqs)
    finally:
        if server is not None:
            server.close()
    summ = metrics.summary()

    out = {
        "prefill_s": metrics.prefill_s,
        "decode_s": metrics.decode_s,
        "tokens_per_s": summ["decode_tokens_per_s"] or 0.0,
        "metrics": summ,
        "requests": finished,
    }
    if obs is not None:
        from repro.obs import GAUGE_HELP
        from repro.obs import snapshot as obs_snapshot
        from repro.obs import write_snapshot
        if obs.trace_path:
            engine.tracer.write(obs.trace_path)
            log.info("chrome trace (%d events) -> %s  [open in "
                     "https://ui.perfetto.dev]", engine.tracer.n_events,
                     obs.trace_path)
        if obs.events_path:
            engine.tracer.write_events(obs.events_path)
        if obs.metrics_file:
            write_snapshot(obs.metrics_file, obs_snapshot(engine),
                           GAUGE_HELP)
            log.info("metrics snapshot -> %s (+ .json)", obs.metrics_file)
        out["observability"] = {
            "trace_events": engine.tracer.n_events,
            "counter_drains": engine.counters.n_drains,
            "counters": engine.counters.totals(),
            "rates": engine.counters.rates(),
        }
    if spec is not None:
        st = engine.spec_stats
        rate = st["accepted"] / max(st["proposed"], 1)
        out["spec"] = {"k": spec.k, "draft_bits": str(spec.draft_bits),
                       "draft_kv_bits": spec.draft_kv_bits,
                       "dispatches": st["dispatches"],
                       "proposed": st["proposed"],
                       "accepted": st["accepted"], "accept_rate": rate}
        if draft_plan is not None:
            out["spec"]["fit_avg_bits"] = draft_plan.avg_bits
            out["spec"]["fit_kl_proxy"] = draft_plan.kl_proxy
            out["spec"]["fit_accept_proxy"] = draft_plan.accept_proxy
        log.info("spec decode: k=%d, %d dispatches, accept rate %.0f%% "
                 "(%d/%d drafts)", spec.k, st["dispatches"], 100 * rate,
                 st["accepted"], st["proposed"])
    if monitor is not None:
        rep = monitor.drift_report()
        out["drift"] = rep
        log.info("drift: %d samples, kl mean %s, %s", rep["n_samples"],
                 f"{rep['kl_mean']:.3g}" if rep["kl_mean"] is not None
                 else "n/a",
                 "IN calibration" if rep["in_calibration"] else
                 f"FLAGGED layers: {', '.join(rep['flagged_layers'])}")
    if n_requests is None:
        # closed-loop: uniform lengths -> legacy dense (B, G) matrix
        out["generated"] = np.stack([r.output_tokens for r in finished])
    log.info("%s slots=%d bits=%s%s | prefill %.2fs, decode %.2fs "
             "(%.1f tok/s, occupancy %.0f%%)", cfg.name, batch, weight_bits,
             " int8" if int8 else "", metrics.prefill_s, metrics.decode_s,
             out["tokens_per_s"], 100 * (summ["slot_occupancy"] or 0))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8,
                    help="slot count (batch capacity)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--weight-bits", type=int, default=None)
    ap.add_argument("--int8", action="store_true",
                    help="real int8 storage + DequantContext")
    ap.add_argument("--packed", action="store_true",
                    help="truly packed QTensor storage (sub-byte widths "
                         "shrink weight HBM; repro.qtensor)")
    ap.add_argument("--int8-compute", action="store_true",
                    help="route int8 blocks through the MXU kernel path")
    ap.add_argument("--requests", type=int, default=None,
                    help="open-loop: number of Poisson requests")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="open-loop arrival rate (requests per clock unit)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (repro.kvcache): page pool + "
                         "prefix sharing instead of the dense per-slot cache")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV page size in tokens (paged mode)")
    ap.add_argument("--kv-bits", type=int, default=None,
                    help="uniform KV storage width: 16 (fp), 8 (int8), "
                         "4 (packed int4); per-layer FIT allocation via "
                         "examples/serve_quantized.py")
    ap.add_argument("--kv-pages", type=int, default=None,
                    help="page-pool size (default: full slot capacity)")
    ap.add_argument("--no-prefix-sharing", action="store_true")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give all generated prompts a common prefix of "
                         "this many tokens (exercises prefix sharing)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard quantized weight "
                         "blocks (and, when kv heads divide, the paged KV "
                         "pools) across a 1-D device mesh; outputs stay "
                         "bit-identical to --tp 1. On CPU hosts set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count")
    ap.add_argument("--group-size", type=int, default=None,
                    help="scale-group size along the reduction axis for "
                         "--packed (row-parallel sharding needs each "
                         "shard to own whole groups)")
    ap.add_argument("--moe-dispatch",
                    choices=("grouped", "dense", "einsum"),
                    default="grouped",
                    help="MoE expert dispatch for quantized stacks: one "
                         "grouped ragged kernel per projection (default), "
                         "the dense per-expert qmm loop (bit-identical "
                         "oracle), or the fp-dequant einsum fallback")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft tokens proposed per "
                         "dispatch (>= 2 enables the draft/verify loop; "
                         "emitted streams stay bit-identical to "
                         "non-speculative serving)")
    ap.add_argument("--spec-bits", default=None,
                    help="draft weight widths: an int narrows every "
                         "quantizable QTensor block for the draft pass "
                         "(requires --packed); 'fit:AVG' FIT-allocates a "
                         "mixed draft config at AVG average bits from a "
                         "smoke sensitivity report; default reuses the "
                         "serving tree")
    ap.add_argument("--spec-kv-bits", type=int, default=None,
                    help="draft KV lane storage width (default 8; dense "
                         "serving supports 8/16, --paged any of 16/8/6/4/3)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clock", choices=("steps", "wall"), default="steps")
    ap.add_argument("--json", default=None, help="write metrics JSON here")
    # ---- observability (repro.obs; README "Observability") ----
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON here (load in "
                         "https://ui.perfetto.dev); also enables the "
                         "zero-sync device counters")
    ap.add_argument("--events", default=None, metavar="PATH",
                    help="write the structured jsonl event log here")
    ap.add_argument("--metrics-file", default=None, metavar="PATH",
                    help="write a Prometheus text snapshot (+ sibling "
                         ".json) at end of run")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve a live /metrics endpoint on this port "
                         "during the run (0 = ephemeral)")
    ap.add_argument("--drain-every", type=int, default=8,
                    help="decode bursts between device-counter drains")
    ap.add_argument("--drift-every", type=int, default=0,
                    help="FIT drift monitor: sample one fp-reference "
                         "forward every N decode steps (0 = off)")
    ap.add_argument("--drift-stale", type=float, default=1.0,
                    help="simulate S-x stale calibration (ranges "
                         "shrunk S x; > --drift-threshold flags)")
    ap.add_argument("--drift-threshold", type=float, default=1.5,
                    help="activation-range ratio that flags a site")
    args = ap.parse_args()
    use_compile_cache()

    out = serve(args.arch, args.smoke, args.batch, args.prompt_len,
                args.gen_len, args.weight_bits, seed=args.seed,
                int8=args.int8, packed=args.packed,
                int8_compute=args.int8_compute,
                n_requests=args.requests, rate=args.rate,
                sampling=SamplingParams(temperature=args.temperature,
                                        top_k=args.top_k, top_p=args.top_p,
                                        seed=args.seed),
                clock=args.clock, paged=args.paged, page_size=args.page_size,
                kv_bits=args.kv_bits, kv_pages=args.kv_pages,
                prefix_sharing=not args.no_prefix_sharing,
                shared_prefix=args.shared_prefix, tp=args.tp,
                group_size=args.group_size,
                moe_dispatch=args.moe_dispatch, trace_path=args.trace,
                events_path=args.events, metrics_file=args.metrics_file,
                metrics_port=args.metrics_port,
                drain_every=args.drain_every,
                drift_every=args.drift_every, drift_stale=args.drift_stale,
                drift_threshold=args.drift_threshold, spec_k=args.spec_k,
                spec_bits=args.spec_bits, spec_kv_bits=args.spec_kv_bits)
    dump = {"metrics": out["metrics"]}
    for k in ("observability", "drift", "spec"):
        if k in out:
            dump[k] = out[k]
    print(json.dumps(dump, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(dump, f, indent=2)


if __name__ == "__main__":
    main()
