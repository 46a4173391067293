"""Performance profiling CLI (README "Performance profiling").

Runs one FIT-quantized serve on the packed QTensor + paged-KV stack
with tracing and device counters on, then joins three views per kernel
site:

  measured  — the engine's decode-burst seconds (its phase spans);
  predicted — the analytic QTensor cost model's bytes-moved / op
              counts from the realized packed layouts;
  quality   — per-site FIT scores from a calibrated SensitivityReport.

and emits the site -> (FIT score, predicted bytes, measured ms share)
table, the per-phase timing of the engine's host loop (spans, wall
seconds, compiles and tokens per phase), a Chrome trace on the
profiler's epoch clock (validated), and a schema-versioned JSON
payload.

  PYTHONPATH=src python -m repro.launch.profile --arch internlm2_1_8b \\
      --smoke --weight-bits 4 --group-size 8 --kv-bits 8 --requests 6 \\
      --json profile.json --trace profile_trace.json
  # FIT mixed-precision allocation instead of a uniform width:
  PYTHONPATH=src python -m repro.launch.profile --arch internlm2_1_8b \\
      --smoke --avg-bits 4.5 --kv-bits 8 --requests 6
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Any, Dict, Optional

import jax

from repro.configs import get_config, smoke_config
from repro.core import build_report
from repro.data.synthetic import LMStreamConfig, lm_batches
from repro.models import init_params, loss_fn
from repro.obs import ObsConfig, validate_chrome_trace
from repro.obs.perf import attribute, format_table, roofline, \
    site_costs_from_tree
from repro.quant.policy import QuantPolicy
from repro.serve import (
    Engine, EngineConfig, bit_config_from_report, poisson_requests,
    quantize_params)
from repro.utils.compile_cache import use_compile_cache
from repro.utils.logging import get_logger

log = get_logger("repro.launch.profile")

PROFILE_SCHEMA = 1


def profile(arch: str = "internlm2_1_8b", smoke: bool = True,
            batch: int = 2, prompt_len: int = 24, gen_len: int = 12,
            n_requests: int = 6, rate: float = 0.05,
            weight_bits: int = 4, avg_bits: Optional[float] = None,
            group_size: Optional[int] = 8, kv_bits: int = 8,
            page_size: int = 8, top: int = 12,
            seed: int = 0, trace_path: Optional[str] = None,
            json_path: Optional[str] = None) -> Dict[str, Any]:
    """One profiled serve; returns (and optionally writes) the joined
    per-site payload.  See module docstring."""
    cfg = smoke_config(arch) if smoke else get_config(arch)
    cfg = dataclasses.replace(cfg, scan_layers=False)
    params = init_params(cfg, jax.random.key(seed))

    # calibrated sensitivity: FIT column + activation ranges for the
    # per-page KV dequant scales (same recipe as benchmarks/serve_bench)
    stream = lm_batches(LMStreamConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                       global_batch=4, seed=seed))
    report = build_report(lambda p, b: loss_fn(p, b, cfg), None, None, None,
                          params, [next(stream) for _ in range(2)],
                          microbatch=4, tolerance=None, max_batches=2)
    policy = QuantPolicy(allowed_bits=(8, 6, 4, 3))
    if avg_bits is not None:
        bits = bit_config_from_report(report, policy, avg_bits=avg_bits)
        qparams, _ = quantize_params(params, bits, policy)
    else:
        qparams, _ = quantize_params(params, weight_bits,
                                     group_size=group_size)

    obs = ObsConfig(trace=True, device_metrics=True, drain_every=4)
    max_len = prompt_len + gen_len
    max_len += (-max_len) % page_size
    ecfg = EngineConfig(max_slots=batch, max_len=max_len,
                        max_new_tokens=gen_len, prefill_chunk=8,
                        decode_burst=8, int8_compute=True,
                        kv_cache="paged", page_size=page_size, obs=obs)
    engine = Engine(qparams, cfg, ecfg, kv_bits=kv_bits,
                    kv_ranges=report.act_ranges)
    reqs = poisson_requests(
        cfg, n_requests, rate,
        prompt_len=(max(4, prompt_len // 2), prompt_len),
        gen_len=(max(2, gen_len // 2), gen_len), seed=seed)
    finished, metrics = engine.run(reqs)
    summ = metrics.summary()

    # the analytic cost model at this run's decode shape: full batch,
    # mid-generation context (prompt + half the new tokens)
    costs = site_costs_from_tree(
        qparams, batch, context=prompt_len + gen_len // 2,
        kv_bits=kv_bits if kv_bits else 16, page_size=page_size, cfg=cfg)
    rows = attribute(costs, metrics.decode_s, report=report)
    rl = roofline(costs)

    print(f"\n{cfg.name}: {len(finished)} requests, "
          f"{summ.get('decode_tokens', 0)} decode tokens, "
          f"{summ.get('decode_tokens_per_s', 0.0):.1f} tok/s")
    print(format_table(rows, top=top))
    phases = metrics.phase_table()
    for name, st in phases.items():
        print(f"{name:>22}: n={st['count']:<4} wall={st['wall_s']:.4f}s "
              f"compiles={st['compiles']:<3} tokens={st['tokens']}")

    payload = {
        "schema": PROFILE_SCHEMA,
        "arch": cfg.name,
        "weight_bits": None if avg_bits is not None else weight_bits,
        "avg_bits": avg_bits,
        "kv_bits": kv_bits,
        "group_size": group_size,
        "n_requests": len(finished),
        "sites": [r.as_dict() for r in rows],
        "phases": phases,
        "roofline_totals": rl["totals"],
        "metrics": summ,
    }
    if trace_path:
        engine.tracer.write(trace_path)
        problems = validate_chrome_trace(engine.tracer.chrome_trace())
        if problems:
            raise AssertionError(f"invalid chrome trace: {problems[:3]}")
        log.info("chrome trace -> %s", trace_path)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        log.info("profile payload -> %s", json_path)
    return payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen-len", type=int, default=12)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--rate", type=float, default=0.05)
    ap.add_argument("--weight-bits", type=int, default=4)
    ap.add_argument("--avg-bits", type=float, default=None,
                    help="FIT mixed-precision target instead of uniform")
    ap.add_argument("--group-size", type=int, default=8)
    ap.add_argument("--kv-bits", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--top", type=int, default=12,
                    help="table rows before the tail is folded")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="Chrome trace JSON (engine phases and requests)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="schema-versioned profile payload")
    a = ap.parse_args()
    use_compile_cache()
    profile(arch=a.arch, smoke=a.smoke, batch=a.batch,
            prompt_len=a.prompt_len, gen_len=a.gen_len,
            n_requests=a.requests, rate=a.rate, weight_bits=a.weight_bits,
            avg_bits=a.avg_bits, group_size=a.group_size, kv_bits=a.kv_bits,
            page_size=a.page_size, top=a.top,
            seed=a.seed, trace_path=a.trace, json_path=a.json)


if __name__ == "__main__":
    main()
