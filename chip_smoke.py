#!/usr/bin/env python3
"""Run the FIT -> packed-serving path once on a TPU chip, at the
published widths of internlm2-1.8B (24 layers, d_model 2048, GQA 16/8,
d_ff 8192, vocab 92544, bf16), and check what comes out.

    python chip_smoke.py            # one chip: the whole main path
    python chip_smoke.py --tp 4     # four chips: tp=4 vs tp=1 serving only

One process holds the chip for its whole life. Weights and calibration
data are random, made from ``--seed``; nothing is downloaded. Phases, in
order (one chip):

  device    platform / device kind / count; refuses anything but a TPU
            whose Pallas kernels run natively (``REPRO_KERNELS`` unset
            or ``tpu``)
  fit       ``core.build_report`` on 2 synthetic calibration batches
            (seq 128, batch 2, microbatch 1) — the ef_sqnorm kernel
  allocate  ``bit_config_from_report`` (greedy knapsack) at 6 average
            bits over {8, 6, 4, 3}
  quantize  ``quantize_params(..., group_size=128)`` -> packed QTensors
  serve     ``Engine`` with int8 compute, paged KV at 8 bits, 16-token
            pages: 8 greedy requests, prompt 128 / gen 32, over 8 slots
  oracle    each kernel against ``kernels/ref.py`` at the real shapes;
            the engine's first-step logits and tokens against the same
            engine traced on the jnp oracles

With ``--tp N`` only the tensor-parallel comparison runs: the same
FIT-packed model and paged KV — full width, depth cut to ``TP_LAYERS`` —
served at tp=N (KV heads sharded N ways, row-parallel groups aligned at
128) and at tp=1 on device 0.

Earlier lines report phase wall times with compile time apart and peak
HBM; the last line of stdout is one JSON object naming the device. Any
failed check exits non-zero. The compile cache follows
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import build_report  # noqa: E402
from repro.data.synthetic import LMStreamConfig, lm_batches  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.models import init_params, loss_fn  # noqa: E402
from repro.models.decode import init_decode_state, prefill_into  # noqa: E402
from repro.qtensor import is_qtensor  # noqa: E402
from repro.quant.policy import QuantPolicy  # noqa: E402
from repro.serve import (  # noqa: E402
    Engine, EngineConfig, SamplingParams, bit_config_from_report,
    kv_report_fns, make_dequant_context, quantize_params, trace_requests)
from repro.utils.compile_cache import use_compile_cache  # noqa: E402
from repro.utils.pytree import named_leaves  # noqa: E402

ARCH = "internlm2_1_8b"
SLOTS, PROMPT, GEN, PAGE = 8, 128, 32, 16
GROUP, AVG_BITS, KV_BITS = 128, 6.0, 8
CALIB_SEQ, CALIB_BATCH, CALIB_BATCHES = 128, 2, 2
EPS32 = 2.0 ** -24

# First-step logits of two routes through the same packed model (kernels
# vs jnp oracles, tp=N vs tp=1). Rounding-level differences do not stay
# small: once a bf16 activation rounds the other way, the per-row int8
# activation grid (a step is 1/127 of the row's max) flips codes, and the
# flips compound layer by layer — 6.7e-2 rel-L2 on a v5e between the
# engine's batch-1 prefill and a batch-8 oracle prefill of the 24-layer
# model, whose qmm calls agree bit for bit. A wrong layout, scale, mask
# or shard offset corrupts whole layers: O(1) of the norm.
LOGIT_REL_TOL = 0.25
# --tp runs the full width at this depth: the comparison is layer by
# layer, and every second on four chips costs four chip-seconds
TP_LAYERS = 4
# paged attention, kernel vs f32 reference at "highest" precision: both
# are fp32 softmax-weighted means of the same dequantized values; online
# rescaling over 10 pages and the TPU's own exp differ from the one-shot
# softmax by ~1e-6 of the value scale. 2e-4 of max|V| leaves room for
# the exp unit; a layout or masking bug is O(1) of max|V|.
PAGED_REL_TOL = 2e-4
# ef_sqnorm: the kernel accumulates N/2048 positive block sums in
# sequence (worst case (N/2048)·2^-24 relative: 4.9e-4 at w_up's 16.8M
# elements); the reference reduces in XLA's own order. Twice that bound.
EF_REL_TOL = 1e-3

# lowering to StableHLO and the XLA backend compile (or persistent-cache
# read); tracing is left in "execute" — nested jits would count it twice
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class Phases:
    """Phase wall clock, with JAX's own compile-time events (lowering,
    backend compile or persistent-cache read) summed apart."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)
        jax.monitoring.register_event_listener(self._count)

    def _event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.compile_s += duration

    def _count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        print(f"[{name}] ...", flush=True)
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        print(f"[{name}] wall {wall:.3f} s = compile {comp:.3f} s + "
              f"execute {wall - comp:.3f} s "
              f"({self.cache_hits - h0} persistent-cache hits)", flush=True)


@contextlib.contextmanager
def oracle_route():
    """Trace with the jnp oracles in place of the Pallas kernels
    (``kernels.ops`` reads ``REPRO_KERNELS`` at trace time)."""
    prev = os.environ.get("REPRO_KERNELS")
    os.environ["REPRO_KERNELS"] = "ref"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["REPRO_KERNELS"]
        else:
            os.environ["REPRO_KERNELS"] = prev


def device_check(need: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if d0.platform != "tpu":
        raise CheckFailed(f"no TPU: JAX found platform {d0.platform!r} "
                          f"({d0.device_kind})")
    if kops._mode() != "tpu":
        raise CheckFailed(
            f"Pallas kernels would not run natively: kernel route is "
            f"{kops._mode()!r} (REPRO_KERNELS="
            f"{os.environ.get('REPRO_KERNELS')!r})")
    if len(devs) < need:
        raise CheckFailed(f"need {need} TPU devices, JAX found {len(devs)}")
    return dev


def model_config():
    return dataclasses.replace(get_config(ARCH), scan_layers=False)


def requests(cfg, seed):
    return trace_requests(cfg, [(0.0, PROMPT, GEN)] * SLOTS,
                          sampling=SamplingParams(temperature=0.0),
                          seed=seed)


def engine_config(mesh=None) -> EngineConfig:
    return EngineConfig(max_slots=SLOTS, max_len=PROMPT + GEN,
                        max_new_tokens=GEN, prefill_chunk=32,
                        decode_burst=8, int8_compute=True,
                        kv_cache="paged", page_size=PAGE, mesh=mesh)


def fit_and_quantize(cfg, params, seed, ph: Phases):
    stream = lm_batches(LMStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=CALIB_SEQ,
        global_batch=CALIB_BATCH, seed=seed))
    batches = [next(stream) for _ in range(CALIB_BATCHES)]
    tap_loss, tap_shapes, act_fn = kv_report_fns(cfg)
    with ph.phase("fit"):
        report = build_report(
            lambda p, b: loss_fn(p, b, cfg), tap_loss,
            lambda b: tap_shapes(params, b), act_fn, params, batches,
            microbatch=1, tolerance=None, max_batches=CALIB_BATCHES)
        traces = np.array(list(report.weight_traces.values()))
        check(traces.size > 0 and bool(np.all(np.isfinite(traces)))
              and bool(np.all(traces >= 0)) and traces.sum() > 0,
              "FIT weight traces must be finite, >= 0 and not all zero")
        print(f"  {traces.size} weight traces, total {traces.sum():.6g}; "
              f"{len(report.act_traces)} KV activation traces", flush=True)

    with ph.phase("allocate"):
        policy = QuantPolicy(allowed_bits=(8, 6, 4, 3))
        bit_cfg = bit_config_from_report(report, policy, avg_bits=AVG_BITS)
        hist = {}
        for b in bit_cfg.weight_bits.values():
            hist[b] = hist.get(b, 0) + 1
        sizes = report.param_sizes
        avg = (sum(bit_cfg.weight_bits.get(k, 16) * n
                   for k, n in sizes.items()) / sum(sizes.values()))
        print(f"  blocks per width {dict(sorted(hist.items()))}; "
              f"{avg:.3f} average bits/param (budget {AVG_BITS})",
              flush=True)
        check(avg <= AVG_BITS + 1e-6, "allocation over its bit budget")

    with ph.phase("quantize"):
        qparams, _ = quantize_params(params, bit_cfg, policy,
                                     group_size=GROUP)
        jax.block_until_ready(qparams)
    return report, qparams


def serve(engine, reqs, ph: Phases, name: str):
    with ph.phase(f"{name}_compile"):
        engine.warmup()
    with ph.phase(f"{name}_run"):
        fin, m = engine.run(reqs)
    s = m.summary()
    print(f"  {s['n_finished']} finished, {s['decode_tokens_per_s']:.1f} "
          f"decode tok/s (host clock), prefill {m.prefill_s:.3f} s, "
          f"decode {m.decode_s:.3f} s", flush=True)
    check(s["n_finished"] == SLOTS, f"{name}: not every request finished")
    out = np.stack([r.output_tokens for r in fin])
    check(out.shape == (SLOTS, GEN) and bool(np.all(out >= 0))
          and bool(np.all(out < engine.cfg.vocab_size)),
          f"{name}: output tokens out of range")
    return fin, out


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def first_divergence(a, b) -> int:
    """Index of the first differing token, or -1 when identical."""
    diff = np.flatnonzero(np.asarray(a) != np.asarray(b))
    return int(diff[0]) if diff.size else -1


def report_agreement(name, got, want) -> None:
    agree = float(np.mean(got == want))
    firsts = [first_divergence(g, w) for g, w in zip(got, want)]
    print(f"  {name}: token agreement {agree:.4f}; first divergence per "
          f"request {firsts} (-1 = identical)", flush=True)


def compare_logits(name, got, want) -> None:
    rel = rel_l2(got, want)
    mx = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    top1 = float(np.mean(np.argmax(got, -1) == np.argmax(want, -1)))
    print(f"  {name}: first-step logits rel-L2 {rel:.3e} (tol "
          f"{LOGIT_REL_TOL:.3e}), max |diff| {mx:.4e}, top-1 agreement "
          f"{top1:.3f}", flush=True)
    check(rel <= LOGIT_REL_TOL, f"{name}: logits rel-L2 {rel:.3e} over "
          f"{LOGIT_REL_TOL:.3e}")


def lowered_kernels(fn, *args) -> int:
    """Pallas TPU custom calls in the program ``fn`` lowers to."""
    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def kernel_checks(qparams, rng) -> None:
    # qmm at each width the allocation used, on a real block of that width
    widths = {}
    for name, leaf in named_leaves(qparams, is_leaf=is_qtensor):
        if is_qtensor(leaf) and leaf.ndim == 2:
            widths.setdefault(leaf.bits, (name, leaf))
    check(bool(widths), "no packed QTensor block to check")
    for bits, (name, w) in sorted(widths.items()):
        k, n = w.shape
        xq = jnp.asarray(rng.integers(-127, 128, (SLOTS, k)), jnp.int8)
        xs = jnp.asarray(rng.uniform(1e-3, 2e-2, (SLOTS, 1)), jnp.float32)
        check(lowered_kernels(kops.qmm, xq, w, xs) > 0,
              f"qmm W{bits}: no native kernel in the lowered program")
        got = np.asarray(kops.qmm(xq, w, xs))
        want = np.asarray(kref.qmm(xq, w, xs))
        terms = np.asarray(kref.qmm_group_products(xq, w))   # (G, M, N)
        g = terms.shape[0]
        # identical exact int32 group dots and fp32 scale products; only
        # the order of the G fp32 adds differs: (G+1)·2^-24·Σ|terms|
        bound = (g + 1) * EPS32 * np.abs(terms).sum(0) * np.asarray(xs)
        err = np.abs(got - want)
        print(f"  qmm W{bits} {name} ({k}x{n}, {g} groups): max |diff| "
              f"{err.max():.3e}, worst diff/bound {np.max(err / (bound + 1e-30)):.3f}",
              flush=True)
        check(bool(np.all(err <= bound)),
              f"qmm W{bits}: kernel differs from the oracle beyond fp32 "
              "group-fold reordering")

    # paged attention at KV8, the engine's pool geometry
    cfg = model_config()
    kvh, dh, g = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads // cfg.num_kv_heads
    npg = (PROMPT + GEN) // PAGE
    pages = SLOTS * npg
    k = jnp.asarray(rng.integers(-127, 128, (pages, kvh, PAGE, dh)), jnp.int8)
    v = jnp.asarray(rng.integers(-127, 128, (pages, kvh, PAGE, dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.05, (pages, kvh)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.05, (pages, kvh)), jnp.float32)
    table = jnp.asarray(rng.permutation(pages).reshape(SLOTS, npg), jnp.int32)
    pos = jnp.asarray(rng.integers(0, PROMPT + GEN, SLOTS), jnp.int32)
    q = jnp.asarray(rng.normal(size=(SLOTS, 1, kvh * g, dh)), jnp.float32)
    args = (q, k, v, table, pos, ks, vs)
    check(lowered_kernels(lambda *a: kops.paged_attention(*a, KV_BITS),
                          *args) > 0,
          "paged_attention: no native kernel in the lowered program")
    got = np.asarray(kops.paged_attention(*args, KV_BITS))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(
            lambda *a: kref.paged_attention(*a, bits=KV_BITS))(*args))
    vmax = 127.0 * float(jnp.max(vs))
    err = float(np.max(np.abs(got - want)))
    print(f"  paged_attention KV{KV_BITS} (B={SLOTS}, KV={kvh}, G={g}, "
          f"{pages} pages): max |diff| {err:.3e} = {err / vmax:.3e} of "
          f"max|V| (tol {PAGED_REL_TOL:.1e})", flush=True)
    check(err <= PAGED_REL_TOL * vmax,
          "paged_attention: kernel differs from the f32 oracle")

    # ef_sqnorm on one per-sample gradient row of a w_up block
    gr = jnp.asarray(rng.normal(size=(1, cfg.d_model * cfg.d_ff)),
                     jnp.bfloat16)
    check(lowered_kernels(kops.ef_sqnorm, gr) > 0,
          "ef_sqnorm: no native kernel in the lowered program")
    got = float(kops.ef_sqnorm(gr)[0])
    want = float(kref.ef_sqnorm(gr)[0])
    rel = abs(got - want) / want
    print(f"  ef_sqnorm (1 x {gr.shape[1]}): rel diff {rel:.3e} (tol "
          f"{EF_REL_TOL:.0e})", flush=True)
    check(rel <= EF_REL_TOL, "ef_sqnorm: kernel differs from the oracle")


def batched_oracle_logits(cfg, qparams, prompts):
    """First-step logits of every prompt through the jnp oracle route in
    ONE batch-8, one-chunk ``prefill_into`` — the engine prefills batch-1
    chunks, so comparing the two shows how much the chip's numerics
    depend on batch shape alone."""
    ctx = make_dequant_context(cfg, int8_compute=True)
    state = init_decode_state(cfg, len(prompts), PROMPT + GEN)
    toks = jnp.asarray(np.stack(prompts), jnp.int32)
    with oracle_route():
        fn = jax.jit(lambda p, s, t: prefill_into(
            p, s, t, cfg, ctx=ctx)[0][:, -1, :cfg.vocab_size]
        ).lower(qparams, state, toks).compile()
    return np.asarray(fn(qparams, state, toks), np.float32)


def peak_hbm(devs) -> str:
    out = []
    for d in devs:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            out.append(f"{d.id}: {st['peak_bytes_in_use'] / 2**30:.3f} GiB")
    return ", ".join(out) or "not reported by this backend"


def run_one_chip(seed: int, ph: Phases) -> None:
    cfg = model_config()
    with ph.phase("init"):
        params = init_params(cfg, jax.random.key(seed))
        jax.block_until_ready(params)
    report, qparams = fit_and_quantize(cfg, params, seed, ph)
    del params                                 # serving holds only qparams

    reqs = requests(cfg, seed)
    engine = Engine(qparams, cfg, engine_config(), kv_bits=KV_BITS,
                    kv_ranges=report.act_ranges)
    fin, out = serve(engine, reqs, ph, "serve")

    with ph.phase("oracle_kernels"):
        kernel_checks(qparams, np.random.default_rng(seed))
    with oracle_route():                       # every program traced on ref
        oracle = Engine(qparams, cfg, engine_config(), kv_bits=KV_BITS,
                        kv_ranges=report.act_ranges)
        _, oout = serve(oracle, requests(cfg, seed), ph, "oracle_serve")
    with ph.phase("oracle_logits"):
        prompts = [np.asarray(r.prompt) for r in fin]
        eng_lg = np.stack([np.asarray(engine.prefill_logits(p), np.float32)
                           for p in prompts])
        check(bool(np.all(np.isfinite(eng_lg))), "engine logits not finite")
        check(bool(np.all(np.argmax(eng_lg, -1) == out[:, 0])),
              "the engine's first tokens are not the argmax of its own "
              "first-step logits")
        with oracle_route():
            ora_lg = np.stack([np.asarray(oracle.prefill_logits(p),
                                          np.float32) for p in prompts])
            b8_lg = batched_oracle_logits(cfg, qparams, prompts)
        compare_logits("engine vs oracle-route engine", eng_lg, ora_lg)
        print(f"  oracle route, batch-8 one-chunk vs the engine's batch-1 "
              f"chunks: rel-L2 {rel_l2(b8_lg, ora_lg):.3e} (batch shape "
              "alone; not a check)", flush=True)
    report_agreement("engine vs oracle-route engine", out, oout)


def run_tp(tp: int, seed: int, ph: Phases) -> None:
    from repro.launch.mesh import make_tp_mesh

    cfg = dataclasses.replace(model_config(), num_layers=TP_LAYERS)
    with ph.phase("init"):
        params = init_params(cfg, jax.random.key(seed))
        jax.block_until_ready(params)
    report, qparams = fit_and_quantize(cfg, params, seed, ph)
    del params

    sharded = Engine(qparams, cfg, engine_config(make_tp_mesh(tp)),
                     kv_bits=KV_BITS, kv_ranges=report.act_ranges)
    check(sharded._kv_shards == tp,
          f"KV pools not sharded {tp} ways by kv-head")
    plan = sharded._shard_plan
    print(f"  tp={tp}: {sum(v == 'col' for v in plan.values())} column- and "
          f"{sum(v == 'row' for v in plan.values())} row-parallel blocks, "
          f"KV heads {cfg.num_kv_heads // tp} per chip", flush=True)
    _, out_tp = serve(sharded, requests(cfg, seed), ph, f"tp{tp}_serve")
    single = Engine(qparams, cfg, engine_config(), kv_bits=KV_BITS,
                    kv_ranges=report.act_ranges)
    _, out_1 = serve(single, requests(cfg, seed), ph, "tp1_serve")
    with ph.phase("compare"):
        prompts = [np.asarray(r.prompt) for r in requests(cfg, seed)]
        lg_tp = np.stack([np.asarray(sharded.prefill_logits(p), np.float32)
                          for p in prompts])
        lg_1 = np.stack([np.asarray(single.prefill_logits(p), np.float32)
                         for p in prompts])
        compare_logits(f"tp={tp} vs tp=1", lg_tp, lg_1)
        report_agreement(f"tp={tp} vs tp=1", out_tp, out_1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel comparison: tp=N "
                         "against tp=1 on device 0 (needs N chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        dev = device_check(args.tp)
    except CheckFailed as e:
        print(f"chip_smoke: {e}", file=sys.stderr, flush=True)
        return 2
    cache = use_compile_cache()
    print(f"compile cache: {cache}", flush=True)
    ph = Phases()
    t0 = time.perf_counter()
    try:
        if args.tp > 1:
            run_tp(args.tp, args.seed, ph)
        else:
            run_one_chip(args.seed, ph)
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr, flush=True)
        return 1
    total = time.perf_counter() - t0
    print(f"total: wall {total:.3f} s, compile {ph.compile_s:.3f} s, "
          f"{ph.cache_hits} persistent-cache hits; "
          f"peak HBM {peak_hbm(jax.devices()[:max(args.tp, 1)])}",
          flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
