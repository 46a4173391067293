"""Find a cell's parts by name: configuration, traffic mix and per-layer
metric readers each live in a file of their own under ``bench/``, so a
new cell or metric is new files plus entries in ``BENCHMARK.json``."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "configs" / f"{name}.json").read_text())


def load_traffic(name: str, bench: Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def load_metric(name: str, bench: Path = BENCH) -> ModuleType:
    """The reader module of one per-layer metric: it declares ``LAYER``,
    ``UNIT``, ``SOURCE`` and ``MOVES`` and defines ``read(record)``, which
    returns the value or None when the run gave it nothing to read."""
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload(bench_json: dict, name: str) -> dict:
    for w in bench_json["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench_json: dict, section: str, cell: str) -> List[dict]:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that this
    cell reports: those without a ``workloads`` key, and those that name
    the cell in it."""
    return [m for m in bench_json[section]
            if "workloads" not in m or cell in m["workloads"]]


def model_dims(conf: dict) -> Dict[str, object]:
    """The sizes the reference and the weight generator read, under the
    published config's own key names."""
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "hidden_act", "rms_norm_eps", "rope_theta")
    return {k: conf[k] for k in keys}
