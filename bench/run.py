"""Benchmark entry: one run of one cell on the chip(s) it is started on.

    python3 bench/run.py --workload internlm2.chat --seed 7 --seconds 51 --trace 0

Finds the cell in ``BENCHMARK.json``, its configuration under
``bench/configs``, its traffic mix under ``bench/traffic`` and its
per-layer metric readers under ``bench/metrics``, all by name. It sets
up, measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints as the last line of standard
output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each compared number beside its limit. It exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from yardstick import peaks, registry  # noqa: E402
from yardstick import trace as tr  # noqa: E402

CACHE_DIR = BENCH / ".jax_cache"


class NoChip(RuntimeError):
    pass


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if os.environ.get("REPRO_KERNELS", "auto") not in ("auto", "tpu"):
        raise NoChip("REPRO_KERNELS would route the kernels off the chip "
                     f"({os.environ['REPRO_KERNELS']!r})")
    return {"platform": d0.platform, "kind": d0.device_kind, "count": chips}


def use_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program,
    the sub-second ones too."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def per_layer(bench_json, cell, record) -> dict:
    out = {}
    for m in registry.metrics_for(bench_json, "per_layer", cell):
        v = registry.load_metric(m["name"]).read(record)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_json = registry.load_benchmark()
    cell = registry.workload(bench_json, args.workload)
    conf = registry.load_config(cell["config"])
    mix = registry.load_traffic(cell["traffic"])
    try:
        dev = device_info(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    kind_peaks = peaks.peaks_for(dev["kind"])
    use_cache()

    if mix["kind"] == "serve":
        from harness import serve as runner
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    res = runner.run_cell(conf, mix, args.seed, args.seconds, bool(args.trace),
                          T_START, conf["limits"])
    rec = res["record"]
    rec["peaks"] = kind_peaks
    rec["dims"] = registry.model_dims(conf)

    dev["memory_peak_bytes"] = res["memory_peak_bytes"]
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"]}
    if args.trace:
        t = rec.get("trace")
        if t is None:
            raise RuntimeError("the run ended before its traced stretch began")
        out["metrics"] = per_layer(bench_json, args.workload, rec)
        dev["busy_s"] = t["busy_s"]
        dev["window_s"] = t["window_s"]
        out["device"] = dev
        out["breakdown"] = {"device_ops": tr.top(t["op_s"]),
                            "idle_gaps": tr.top(t["idle_gaps"])}
    else:
        out["metrics"] = {
            m["name"]: {"value": res["e2e"][m["name"]], "unit": m["unit"]}
            for m in registry.metrics_for(bench_json, "end_to_end",
                                          args.workload)
            if res["e2e"].get(m["name"]) is not None}
        out["device"] = dev
    out["checks"] = res["checks"]

    diag = {k: v for k, v in rec.items() if k not in ("requests", "trace")}
    print("record: " + json.dumps(diag), file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
