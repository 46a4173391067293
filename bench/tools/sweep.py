"""Find a serving configuration's knee on the chip: one process, one set
of weights and one warmed engine, then the chat mix offered at each rate
in turn for ``--seconds`` of arrivals, each drained before the next.

    python bench/tools/sweep.py --config internlm2_1_8b --mix chat-0.40rps \
        --rates 0.3,0.4,0.5,0.6 --seconds 51

Per rate it prints, as one JSON line, the request count, the time to
first token (median, 95th percentile), the admission wait of the first
and the last third of the arrivals (a wait that grows through the window
is a backlog), the 95th percentile of the per-request token gap, output
tokens per second and the drain after the last arrival.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from harness import program, serve  # noqa: E402
from yardstick import registry, traffic, weights  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--seed", type=int, default=101)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    import run as bench_run
    bench_run.use_cache()
    from repro.serve.request import Request
    from repro.serve.sampling import SamplingParams

    conf = registry.load_config(args.config)
    mix = registry.load_traffic(args.mix)
    dims = registry.model_dims(conf)
    t0 = time.perf_counter()
    qp = program.packed_weights(conf, dims, args.seed)
    engine = program.engine(conf, program.model_config(conf), qp)
    engine.warmup()
    print(f"setup {time.perf_counter() - t0:.1f} s", flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, arrivals={"rate_per_s": rate})
        specs = traffic.requests(m, args.seconds, dims["vocab_size"], args.seed)
        serve.warm_eager_shapes(engine, specs, weights.vocab_rows(dims))
        reqs = [Request(id=s.id, prompt=s.prompt, max_new_tokens=s.max_new_tokens,
                        arrival_time=s.arrival_s,
                        sampling=SamplingParams(temperature=0.0))
                for s in specs]
        t0 = time.perf_counter()
        fin, met = engine.run(reqs)
        wall = time.perf_counter() - t0
        fin.sort(key=lambda r: r.arrival_time)
        ttft = [r.t_first_token - r.arrival_time for r in fin]
        wait = [r.t_admitted - r.arrival_time for r in fin]
        tpot = [(r.t_finished - r.t_first_token) / (r.num_generated - 1)
                for r in fin if r.num_generated > 1]
        third = max(1, len(fin) // 3)
        last = max(r.t_finished for r in fin)
        print(json.dumps({
            "rate": rate, "n": len(specs), "finished": len(fin),
            "ttft_p50_s": float(np.median(ttft)),
            "ttft_p95_s": float(np.percentile(ttft, 95)),
            "wait_first_third_s": float(np.mean(wait[:third])),
            "wait_last_third_s": float(np.mean(wait[-third:])),
            "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95)),
            "output_tokens_per_s": sum(r.num_generated for r in fin) / last,
            "drain_s": last - max(r.arrival_time for r in fin),
            "prefill_ms_per_token": 1e3 * met.prefill_s / max(met.prefill_tokens, 1),
            "decode_step_ms": 1e3 * met.decode_s / max(met.decode_steps, 1),
            "wall_s": wall}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
