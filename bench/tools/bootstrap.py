"""One-off chip run that makes a configuration's bit allocation, and
optionally times a decode step at several numbers of active slots.

    python bench/tools/bootstrap.py --config internlm2_1_8b --fit --out out/
    python bench/tools/bootstrap.py --config minitron_4b --out out/ \
        --alloc-from out/alloc_internlm2_1_8b.json
    python bench/tools/bootstrap.py --config variant.json --out out/ \
        --slots-probe 8,64

``--fit`` builds the FIT report of the seeded bf16 model with the repo's
``build_report`` (2 batches x 2 samples of 128 tokens, microbatch 1,
tolerance off) and allocates 6.0 average bits over {8, 6, 4, 3} with
``bit_config_from_report``. ``--alloc-from`` maps another model's
allocation onto this one by relative depth and block role. Either way
the allocation is written to ``--out`` for the configuration file to
store. ``--slots-probe`` serves the packed model at each number of
active slots and prints the decode step's time; ``--config`` may then
name a ``.json`` variant of a configuration (16-token pages, say).
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from harness import program  # noqa: E402
from yardstick import registry, weights, work  # noqa: E402


def fit_allocation(cfg, dims, key):
    from repro.core import build_report
    from repro.data.synthetic import LMStreamConfig, lm_batches
    from repro.models import loss_fn
    from repro.quant.policy import QuantPolicy
    from repro.serve import bit_config_from_report

    params = jax.jit(lambda k: weights.make_params(dims, k))(key)
    stream = lm_batches(LMStreamConfig(vocab_size=cfg.vocab_size,
                                       seq_len=128, global_batch=2, seed=0))
    batches = [next(stream) for _ in range(2)]
    t0 = time.perf_counter()
    report = build_report(lambda p, b: loss_fn(p, b, cfg), None, None, None,
                          params, batches, microbatch=1, tolerance=None,
                          max_batches=2)
    print(f"fit: {time.perf_counter() - t0:.3f} s", flush=True)
    bit_cfg = bit_config_from_report(
        report, QuantPolicy(allowed_bits=(8, 6, 4, 3)), avg_bits=6.0)
    del params
    return dict(bit_cfg.weight_bits), {k: float(v) for k, v in
                                       report.weight_traces.items()}


def mapped_allocation(dims, src_path):
    src = json.loads(Path(src_path).read_text())["weight_bits"]
    n_src = 1 + max(int(k.split("/")[1]) for k in src if k.startswith("layers/"))
    out = {"head": src["head"]}
    n = dims["num_hidden_layers"]
    for i in range(n):
        j = i * n_src // n
        for path, _ in weights.layer_leaves(dims):
            out[f"layers/{i}/{path}"] = src[f"layers/{j}/{path}"]
    return out


def matrix_bits(dims, wb):
    """Bits of every served matrix (the program quantizes only these)."""
    keep = {"head"} | {f"layers/{i}/{p}" for i in range(dims["num_hidden_layers"])
                       for p, _ in weights.layer_leaves(dims)}
    return {k: int(v) for k, v in wb.items() if k in keep and int(v) < 16}


def slots_probe(conf, dims, cfg, seed, slot_counts):
    """Decode step time at each number of active slots (16-token prompts,
    40 new tokens each), after one warm-up run."""
    from repro.serve.request import Request
    from repro.serve.sampling import SamplingParams

    engine = program.engine(conf, cfg, program.packed_weights(conf, dims, seed))
    engine.warmup()
    rng = np.random.default_rng(0)

    def reqs(n):
        return [Request(id=i, max_new_tokens=40, arrival_time=0.0,
                        prompt=rng.integers(0, dims["vocab_size"], 16).astype(np.int32),
                        sampling=SamplingParams(temperature=0.0))
                for i in range(n)]

    engine.run(reqs(4))
    for n in slot_counts:
        _, m = engine.run(reqs(n))
        print(f"{n} active: {1e3 * m.decode_s / m.decode_steps:.2f} ms/step, "
              f"prefill {1e3 * m.prefill_s / m.prefill_tokens:.2f} ms/token",
              flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True,
                    help="a configuration name, or a .json path")
    ap.add_argument("--fit", action="store_true")
    ap.add_argument("--alloc-from")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True, type=Path)
    ap.add_argument("--slots-probe", default="",
                    type=lambda v: [int(x) for x in v.split(",") if x])
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("bootstrap: no TPU", file=sys.stderr)
        return 3
    import run as bench_run
    bench_run.use_cache()

    conf = (json.loads(Path(args.config).read_text())
            if args.config.endswith(".json") else registry.load_config(args.config))
    dims = registry.model_dims(conf)
    cfg = program.model_config(conf)
    if args.fit:
        wb, traces = fit_allocation(cfg, dims, weights.seed_key(args.seed))
        made = {"weight_bits": matrix_bits(dims, wb), "weight_traces": traces}
    elif args.alloc_from:
        made = {"weight_bits": matrix_bits(dims, mapped_allocation(
            dims, args.alloc_from))}
    else:
        made = None
    if made:
        wb = made["weight_bits"]
        sizes = {k: a * b for k, (a, b) in work.matrices(dims).items()}
        avg = sum(wb[k] * sizes[k] for k in sizes) / sum(sizes.values())
        print(f"allocation: {dict(sorted(collections.Counter(wb.values()).items()))}"
              f" blocks, {avg:.4f} avg bits over served matrices", flush=True)
        args.out.mkdir(parents=True, exist_ok=True)
        (args.out / f"alloc_{conf['name']}.json").write_text(
            json.dumps(made, indent=1))
        conf["allocation"] = {"allowed_bits": [8, 6, 4, 3], "weight_bits": wb}
    if args.slots_probe:
        slots_probe(conf, dims, cfg, args.seed, args.slots_probe)
    return 0


if __name__ == "__main__":
    sys.exit(main())
