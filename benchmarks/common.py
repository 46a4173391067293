"""Shared benchmark utilities: testbed training, steady-state timing,
CSV/JSON emission, and the in-process record registry the bench-history
trajectory writer (benchmarks/history.py) snapshots."""
from __future__ import annotations

import json
import time
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import ClassifyConfig, batched, classify_dataset
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn
from repro.utils.compile_cache import use_compile_cache

# every benchmark script imports this module before its first compile
use_compile_cache()

# every emit()/emit_json() lands here so a bench module can snapshot
# its own metrics for the trajectory file without re-plumbing returns
_RECORDS: List[Tuple[str, float, str]] = []


def emit(name: str, us_per_call: float, derived: str) -> str:
    _RECORDS.append((name, float(us_per_call), derived))
    line = f"{name},{us_per_call:.2f},{derived}"
    print(line, flush=True)
    return line


def emit_json(name: str, payload: Dict) -> str:
    """One machine-readable result line: ``<name> {json}`` (the serving
    benchmarks report structured metrics — TTFT percentiles, tok/s,
    occupancy — that don't fit the us-per-call CSV shape)."""
    line = f"{name} {json.dumps(payload, sort_keys=True, default=str)}"
    print(line, flush=True)
    return line


def records(prefix: str = "") -> List[Tuple[str, float, str]]:
    """Snapshot of the emitted CSV records (optionally name-filtered)."""
    return [r for r in _RECORDS if r[0].startswith(prefix)]


def steady_median(samples: Sequence[float], discard: int = 1) -> float:
    """Median after dropping the first ``discard`` samples — the
    steady-state report (first iterations carry cache/allocator warmup
    that the median of a short run does not wash out)."""
    xs = list(samples)
    if len(xs) > discard + 1:
        xs = xs[discard:]
    return float(np.median(xs))


def timeit_stats(fn: Callable, iters: int = 10, warmup: int = 2,
                 repeats: int = 1, discard: int = 0) -> Dict[str, float]:
    """Steady-state timing of ``fn`` with full dispersion info.

    ``warmup`` calls compile and populate caches; then ``repeats``
    rounds of ``iters`` synced samples each are collected, the first
    ``discard`` samples of every round dropped, and robust stats taken
    over the pooled remainder: {median_us, min_us, mad_us, n}.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn())
    pooled: List[float] = []
    for _ in range(max(repeats, 1)):
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        pooled.extend(ts[discard:] if len(ts) > discard else ts)
    med = float(np.median(pooled))
    return {"median_us": med * 1e6,
            "min_us": float(np.min(pooled)) * 1e6,
            "mad_us": float(np.median(np.abs(np.array(pooled) - med))) * 1e6,
            "n": float(len(pooled))}


def timeit(fn: Callable, iters: int = 10, warmup: int = 2,
           repeats: int = 1, discard: int = 0) -> float:
    """Steady-state median wall time per call in microseconds."""
    return timeit_stats(fn, iters=iters, warmup=warmup, repeats=repeats,
                        discard=discard)["median_us"]


def train_cnn_testbed(seed: int = 0, batchnorm: bool = True, steps: int = 300,
                      input_hw: int = 8, num_classes: int = 4,
                      filters: int = 8, n_train: int = 2048,
                      lr: float = 3e-3):
    """Train the paper's small CNN (App. D) on the synthetic classify set."""
    dcfg = ClassifyConfig(input_hw=input_hw, num_classes=num_classes, seed=seed)
    xtr, ytr = classify_dataset(dcfg, n_train)
    xte, yte = classify_dataset(dcfg, 512, split_seed=101)
    params = init_cnn(jax.random.key(seed), num_classes=num_classes,
                      input_hw=input_hw, filters=filters, batchnorm=batchnorm)

    @jax.jit
    def step(p, b):
        loss, g = jax.value_and_grad(cnn_loss)(p, b)
        return jax.tree.map(lambda a, gg: a - lr * gg, p, g), loss

    for i, b in enumerate(batched(xtr, ytr, 128, seed=seed)):
        if i >= steps:
            break
        params, _ = step(params, (jnp.asarray(b[0]), jnp.asarray(b[1])))
    acc = cnn_accuracy(params, jnp.asarray(xte), jnp.asarray(yte))
    return params, (xtr, ytr), (xte, yte), acc
