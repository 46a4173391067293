import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

MUST be run as a module entry point; the XLA_FLAGS line above precedes
every other import because jax locks the device count at first init.

Per (arch × shape × mesh) cell:
  1. FULL lowering — scan-stacked layers, production shardings —
     ``.lower().compile()``: proves the distribution config is coherent;
     ``memory_analysis()`` proves it fits; HLO text gives the collective
     schedule.
  2. COST lowerings — the same step with layers UNROLLED at two small
     depths (n1, n2) and identical shardings. XLA's cost analysis counts
     scan bodies once, so exact totals are reconstructed as
        total = f(n1) + (f(n2) − f(n1)) · M
     with M chosen so n1 + M·(n2−n1) equals the real depth (layer costs
     are homogeneous by construction).
  3. Roofline terms + analytic MODEL_FLOPS (launch/roofline.py).

Results land in experiments/dryrun/<cell>.json (consumed by
EXPERIMENTS.md and benchmarks/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch llama3_8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, ModelConfig, ShapeSpec, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import RooflineTerms, model_flops, param_counts
from repro.launch.sharding import ShardOptions
from repro.launch.steps import build_step
from repro.utils.hlo import CollectiveStats, collective_bytes
from repro.utils.logging import get_logger

log = get_logger("repro.dryrun")
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _cost_pair(cfg: ModelConfig, shape: ShapeSpec,
               chunk: Optional[int] = None
               ) -> Tuple[ModelConfig, ModelConfig, float]:
    """Two unrolled configs (n1, n2 units) + extrapolation multiplier M.

    ``chunk`` overrides attn_chunk: the FLOPs pair uses chunk=seq_len (the
    attention kv-scan body is counted once by cost analysis, so removing
    the loop makes FLOPs exact); the bytes/collectives pair keeps the real
    chunk so no S×S score tensor inflates traffic.
    """
    kw = {"scan_layers": False}
    if chunk is not None:
        kw["attn_chunk"] = chunk
    if cfg.family == "hybrid":
        period = cfg.attn_period
        n_groups, rest = divmod(cfg.num_layers, period)
        c1 = dataclasses.replace(cfg, num_layers=1 * period + rest, **kw)
        c2 = dataclasses.replace(cfg, num_layers=2 * period + rest, **kw)
        return c1, c2, float(n_groups - 2)
    c1 = dataclasses.replace(cfg, num_layers=1, **kw)
    c2 = dataclasses.replace(cfg, num_layers=2, **kw)
    return c1, c2, float(cfg.num_layers - 2)


def _lower(cfg: ModelConfig, shape: ShapeSpec, mesh, opts: ShardOptions):
    build = build_step(cfg, shape, mesh, opts)
    lowered = build.fn.lower(*build.args)
    return lowered


def _analyze(lowered, f32_as_bf16: bool = True) -> Dict:
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, f32_as_bf16=f32_as_bf16)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll,
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "collective_counts": coll.count_by_kind,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             opts: ShardOptions = ShardOptions(),
             opts_tag: str = "baseline",
             cfg_overrides: Optional[Dict] = None) -> Dict:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    # 1) full lowering: coherence + memory + schedule
    full_lowered = _lower(cfg, shape, mesh, opts)
    full = _analyze(full_lowered)
    t_full = time.time() - t0

    # 2) cost extrapolation pairs: real-chunk (bytes/collectives) + no-loop
    #    chunk=seq (FLOPs) — see _cost_pair docstring.
    c1, c2, mult = _cost_pair(cfg, shape)
    a1 = _analyze(_lower(c1, shape, mesh, opts))
    a2 = _analyze(_lower(c2, shape, mesh, opts))
    bytes_ = a2["bytes"] + (a2["bytes"] - a1["bytes"]) * mult
    coll: CollectiveStats = a2["coll"].scaled_diff(a1["coll"], mult)

    needs_flops_pair = (shape.kind != "decode" and cfg.num_heads > 0
                        and shape.seq_len > cfg.attn_chunk)
    if needs_flops_pair:
        f1, f2, _ = _cost_pair(cfg, shape, chunk=shape.seq_len)
        af1 = _analyze(_lower(f1, shape, mesh, opts))
        af2 = _analyze(_lower(f2, shape, mesh, opts))
        flops = af2["flops"] + (af2["flops"] - af1["flops"]) * mult
    else:
        flops = a2["flops"] + (a2["flops"] - a1["flops"]) * mult

    terms = RooflineTerms(
        flops_per_chip=flops,           # SPMD cost analysis is per-device
        bytes_per_chip=bytes_,
        ici_traffic_per_chip=coll.total_traffic,
        chips=chips,
        model_flops=model_flops(cfg, shape),
    )

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "opts": opts_tag,
        "status": "ok",
        "compile_s": round(t_full, 1),
        "memory": full["mem"],
        "hbm_per_device_gib": round(
            (full["mem"]["argument_bytes"] + full["mem"]["temp_bytes"]
             + full["mem"]["output_bytes"] - full["mem"]["alias_bytes"]) / 2 ** 30, 3),
        "full_module": {
            "flops_per_chip_raw": full["flops"],
            "collective_counts": full["collective_counts"],
            "collective_bytes_raw": full["coll"].bytes_by_kind,
        },
        "extrapolated": {
            "flops_per_chip": flops,
            "bytes_per_chip": bytes_,
            "collective_bytes": coll.bytes_by_kind,
            "collective_traffic_per_chip": coll.traffic_by_kind,
        },
        "roofline": terms.to_dict(),
        "param_counts": param_counts(cfg),
    }
    return result


def save_result(result: Dict, out_dir: str = OUT_DIR) -> str:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{result['arch']}__{result['shape']}__{result['mesh']}__{result['opts']}.json"
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump(result, f, indent=1, default=str)
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--strategy", default="tp")
    ap.add_argument("--seq-parallel", type=int, default=1)
    ap.add_argument("--decode-quant", default=None)
    ap.add_argument("--moe-mode", default="ep")
    ap.add_argument("--zero1", type=int, default=0)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--ssm-bf16", type=int, default=0)
    args = ap.parse_args()
    overrides = {}
    if args.ssm_chunk:
        overrides["ssm_chunk"] = args.ssm_chunk
    if args.attn_chunk:
        overrides["attn_chunk"] = args.attn_chunk
    if args.ssm_bf16:
        overrides["ssm_compute_dtype"] = "bfloat16"

    opts = ShardOptions(strategy=args.strategy,
                        seq_parallel=bool(args.seq_parallel),
                        moe_mode=args.moe_mode, zero1=bool(args.zero1),
                        decode_quant=args.decode_quant)

    cells = []
    if args.all:
        for a in ARCH_IDS:
            cfg = get_config(a)
            for s in SHAPES:
                if s in cfg.skip_shapes:
                    continue
                cells.append((a, s))
    else:
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag_mesh = "2x16x16" if mp else "16x16"
            out_name = os.path.join(
                args.out, f"{arch}__{shape}__{tag_mesh}__{args.tag}.json")
            if args.skip_existing and os.path.exists(out_name):
                log.info("skip existing %s", out_name)
                continue
            log.info("=== %s × %s × %s ===", arch, shape, tag_mesh)
            try:
                res = run_cell(arch, shape, multi_pod=mp, opts=opts,
                               opts_tag=args.tag, cfg_overrides=overrides)
                path = save_result(res, args.out)
                rl = res["roofline"]
                log.info("ok: hbm/dev=%.2fGiB compute=%.4fs memory=%.4fs "
                         "coll=%.4fs bottleneck=%s (compile %.1fs) -> %s",
                         res["hbm_per_device_gib"], rl["compute_s"],
                         rl["memory_s"], rl["collective_s"], rl["bottleneck"],
                         res["compile_s"], path)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((arch, shape, tag_mesh, repr(e)))
                log.error("FAILED %s × %s × %s: %s", arch, shape, tag_mesh, e)
                traceback.print_exc()
                save_result({"arch": arch, "shape": shape, "mesh": tag_mesh,
                             "opts": args.tag, "status": "failed",
                             "error": repr(e)}, args.out)
    if failures:
        log.error("%d cells failed: %s", len(failures), failures)
        raise SystemExit(1)
    log.info("all cells passed")


if __name__ == "__main__":
    main()
