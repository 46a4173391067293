"""A serving cell: set up the packed model and engine, offer the mix's
open-loop load for the window, let the engine drain, then judge what it
served against the reference.

Timeline of one run (host clock):
  set-up   process start -> weights (one jitted call) -> engine ->
           every shape the mix uses warmed
  window   ``Engine.run`` over the mix's requests, due in [0, seconds]
           on the engine's clock; the run ends when the last one has
           finished (the drain). With ``trace`` a steady stretch of it
           (``TRACE_AT`` to ``TRACE_AT + TRACE_FOR`` seconds in) is
           profiled from a side thread: a whole run is millions of
           device events, which take minutes to write and read.
  check    peak memory read, the engine freed, the reference run over the
           longest finished request and a seeded sample of the others
"""
from __future__ import annotations

import gc
import glob
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from harness import program
from yardstick import reference, registry, traffic, weights, work
from yardstick import trace as tr

CHECK_TOKENS = 512
# the traced stretch of a run, in seconds after the window opens (capped
# to the window's middle third for a short ``--seconds``)
TRACE_AT, TRACE_FOR = 20.0, 10.0


class TracedStretch:
    """Profiles [at, at + length] seconds after ``start()``, from its own
    thread, inside the ``trace.WINDOW`` annotation. ``at_s`` is when the
    annotation opened, on the clock ``start()`` was given."""

    def __init__(self, tdir: str, at: float, length: float):
        self.tdir, self.at, self.length = tdir, at, length
        self.at_s = None
        self._go = threading.Event()
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self, t0: float) -> None:
        self._t0 = t0
        self._thread.start()

    def _run(self) -> None:
        if self._done.wait(max(0.0, self._t0 + self.at - time.perf_counter())):
            return                                  # the run ended first
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.tdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            self.at_s = time.perf_counter() - self._t0
            self._done.wait(self.length)
        jax.profiler.stop_trace()

    def join(self) -> None:
        self._done.set()
        self._thread.join()


class CompileCounter:
    """Counts XLA backend compiles (JAX's own monitoring event) while
    ``on``: the window should have none."""

    def __init__(self):
        self.on = False
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def warm_eager_shapes(engine, specs, vocab_rows: int) -> None:
    """Compile, before the window, the shapes the engine's host loop uses
    per request length (the prefill of each prompt's last, shorter chunk;
    the slices it cuts prompts and logits with; the output rows it reads
    back at eviction) and the per-admission sampling arrays."""
    jnp = jax.numpy
    jnp.asarray([0], jnp.int32).block_until_ready()
    jnp.asarray([0.0], jnp.float32).block_until_ready()
    e = engine.ecfg
    chunk = e.prefill_chunk
    tails = set()
    for plen in sorted({len(s.prompt) for s in specs}):
        prompt = jnp.asarray(np.zeros(plen, np.int32))[None]
        for lo in range(0, plen, chunk):
            tails.add(prompt[:, lo:lo + chunk].shape[1])
    for c in sorted(tails):
        engine.prefill_logits(np.zeros(c, np.int32))
        jnp.zeros((1, c, vocab_rows), jnp.float32)[:, -1].block_until_ready()
    out = jnp.zeros((e.max_slots, e.max_new_tokens), jnp.int32)
    for g in sorted({s.max_new_tokens for s in specs}):
        np.asarray(out[0, :g])


def sample_for_check(finished, seed: int) -> List:
    """The longest finished request, then others drawn from ``seed``
    until they add ``CHECK_TOKENS`` served tokens of their own: requests
    served in other slots, beside other requests."""
    order = sorted(finished, key=lambda r: (-r.num_generated, r.id))
    pick, rest = [order[0]], order[1:]
    rng = np.random.default_rng(seed)
    rng.shuffle(rest)
    others = 0
    for r in rest:
        if others >= CHECK_TOKENS:
            break
        pick.append(r)
        others += r.num_generated
    return pick


def pct(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run_cell(conf: dict, mix: dict, seed: int, seconds: float,
             trace: bool, t_start: float, limits: Dict[str, float],
             trace_dir: Optional[str] = None) -> dict:
    from repro.serve.request import Request
    from repro.serve.sampling import SamplingParams

    dims = registry.model_dims(conf)
    cfg = program.model_config(conf)
    alloc = conf["allocation"]["weight_bits"]
    eng = conf["engine"]
    specs = traffic.requests(mix, seconds, dims["vocab_size"], seed)

    t = {}
    t0 = time.perf_counter()
    qparams = program.packed_weights(conf, dims, seed)
    jax.block_until_ready(qparams)
    t["weights_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine = program.engine(conf, cfg, qparams)
    engine.warmup()
    warm_eager_shapes(engine, specs, weights.vocab_rows(dims))
    t["warmup_s"] = time.perf_counter() - t0

    reqs = [Request(id=s.id, prompt=s.prompt, max_new_tokens=s.max_new_tokens,
                    arrival_time=s.arrival_s,
                    sampling=SamplingParams(temperature=0.0, seed=s.id))
            for s in specs]
    counter = CompileCounter()
    stretch = None
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="trace_")
        at = min(TRACE_AT, seconds / 3)
        stretch = TracedStretch(tdir, at, min(TRACE_FOR, seconds / 3))
    setup_s = time.perf_counter() - t_start
    counter.on = True
    t_run = time.perf_counter()
    if stretch:
        stretch.start(t_run)
    finished, m = engine.run(reqs)
    counter.on = False
    if stretch:
        stretch.join()

    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    fin_ids = {r.id for r in finished}
    ttft = [r.t_first_token - r.arrival_time for r in finished]
    tpot = [(r.t_finished - r.t_first_token) / (r.num_generated - 1) * 1e3
            for r in finished if r.num_generated > 1]
    elapsed = max(r.t_finished for r in finished) if finished else float("nan")
    out_tokens = sum(r.num_generated for r in finished)
    rec = {
        "requests": [{"id": r.id, "arrival": r.arrival_time,
                      "admitted": r.t_admitted, "first": r.t_first_token,
                      "finished": r.t_finished, "prompt": r.prompt_len,
                      "output": r.num_generated} for r in finished],
        "engine": {"prefill_s": m.prefill_s, "prefill_tokens": m.prefill_tokens,
                   "prefill_dispatches": m.prefill_dispatches,
                   "decode_s": m.decode_s, "decode_steps": m.decode_steps,
                   "decode_tokens": m.decode_tokens},
        "elapsed_s": elapsed,
        "work": work.serve_flops(
            dims, [(r.prompt_len, r.num_generated) for r in finished]),
        "window_compiles": counter.compiles,
    }
    e2e = {
        "setup_s": setup_s,
        "ttft_p95_s": pct(ttft, 95) if ttft else None,
        "tpot_p95_ms": pct(tpot, 95) if tpot else None,
        "output_tokens_per_s": out_tokens / elapsed if finished else None,
    }

    # the check: free the program's state first, so the reference sets no
    # peak of its own and has the chip's memory
    check = sample_for_check(finished, seed) if finished else []
    prompts = [np.asarray(r.prompt) for r in check]
    outputs = [np.asarray(r.output_tokens) for r in check]
    del engine, qparams, finished, reqs
    gc.collect()
    t0 = time.perf_counter()
    gaps = reference.served_gaps(dims, alloc, eng["group_size"], seed,
                                 prompts, outputs)["served"] if check else None
    t["check_s"] = time.perf_counter() - t0
    widest = float(np.max(gaps)) if gaps is not None else float("inf")
    unfinished = len(specs) - len(fin_ids)
    checks = {
        "widest_gap_sd": {"value": widest, "limit": limits["widest_gap_sd"]},
        "unfinished": {"value": unfinished, "limit": 0},
    }
    correct = widest <= limits["widest_gap_sd"] and unfinished == 0
    rec["check_tokens"] = int(sum(len(o) for o in outputs))
    rec["timings"] = t
    if stretch and stretch.at_s is not None:
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        rec["trace"] = tr.reduce_trace(path, keep=work.KERNELS)
        rec["trace"]["at_s"] = stretch.at_s
        rec["trace_work"] = work.traced_serve_work(
            rec["trace"], rec["requests"], dims, eng)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
    return {"correct": correct, "attempted": len(specs),
            "failed": unfinished, "e2e": e2e, "record": rec,
            "memory_peak_bytes": peak, "checks": checks,
            "sample": {"prompts": prompts, "outputs": outputs}}
