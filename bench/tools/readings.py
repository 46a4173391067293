"""Readings that a serving cell's limit is set from, on the chip at the
cell's own size: for each seed, the program serves the cell's mix for a
short window at the cell's own load, and its widest gap against the
reference is read, and beside it the control's (the reference with
int4 activations, ``reference.CONTROL_BITS``, one notch below the int8
the configuration states).

    python bench/tools/readings.py --workload internlm2.chat \
        --seeds 11,12,13 --short 15

The window keeps the requests of the cell's full schedule that arrive in
the first ``--short`` seconds, and the schedule's longest request, due
at 0, so the check compares as many served tokens as a run does. One
engine serves every seed: the packed weights of each seed replace the
last's, in the same shapes, so nothing compiles again. One JSON line per
seed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from harness import program, serve  # noqa: E402
from yardstick import reference, registry, traffic, weights  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--short", type=float, default=15.0)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 3
    import run as bench_run
    bench_run.use_cache()
    from repro.serve.request import Request
    from repro.serve.sampling import SamplingParams

    bj = registry.load_benchmark()
    cell = registry.workload(bj, args.workload)
    conf = registry.load_config(cell["config"])
    mix = registry.load_traffic(cell["traffic"])
    dims = registry.model_dims(conf)
    alloc = conf["allocation"]["weight_bits"]
    group = conf["engine"]["group_size"]
    seeds = [int(s) for s in args.seeds.split(",")]
    engine = None
    for seed in seeds:
        full = traffic.requests(mix, bj["run_seconds"], dims["vocab_size"], seed)
        longest = max(full, key=lambda s: s.max_new_tokens)
        specs = [s for s in full if s.arrival_s <= args.short and s is not longest]
        specs.append(dataclasses.replace(longest, arrival_s=0.0))
        t0 = time.perf_counter()
        qp = program.packed_weights(conf, dims, seed)
        if engine is None:
            engine = program.engine(conf, program.model_config(conf), qp)
            engine.warmup()
        else:
            engine.params = qp
        serve.warm_eager_shapes(engine, specs, weights.vocab_rows(dims))
        setup = time.perf_counter() - t0
        reqs = [Request(id=s.id, prompt=s.prompt, max_new_tokens=s.max_new_tokens,
                        arrival_time=s.arrival_s,
                        sampling=SamplingParams(temperature=0.0, seed=s.id))
                for s in specs]
        t0 = time.perf_counter()
        fin, _ = engine.run(reqs)
        wall = time.perf_counter() - t0
        check = serve.sample_for_check(fin, seed)
        prompts = [np.asarray(r.prompt) for r in check]
        outputs = [np.asarray(r.output_tokens) for r in check]
        engine.params = None
        del qp
        gc.collect()
        t0 = time.perf_counter()
        g = reference.served_gaps(dims, alloc, group, seed, prompts, outputs,
                                  control_bits=reference.CONTROL_BITS)
        print(json.dumps({
            "seed": seed,
            "requests": len(specs), "finished": len(fin),
            "tokens": int(len(g["served"])),
            "widest_gap_sd": float(g["served"].max()),
            "control_widest_gap_sd": float(g["control"].max()),
            "served_p99": float(np.percentile(g["served"], 99)),
            "control_p50": float(np.median(g["control"])),
            "setup_s": setup, "serve_s": wall,
            "check_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
