"""Performance observability: the analytic QTensor cost model, the
engine's phase spans, and the bench-history regression gate.

The load-bearing guarantees:

  * EXACTNESS — the cost model's closed-form byte counts equal
    ``qtensor.storage_summary`` of the realized packed blocks, to the
    byte, for every width x group size (qmm weights and paged KV
    pools).  The roofline is an accounting, not an estimate.
  * ZERO-GRAPH-IMPACT — a traced engine compiles the exact same
    decode/prefill computation as an uninstrumented one (all phase
    timing is host-side around the audited syncs).
  * the merged trace (engine phases + request tracks) still passes the
    Chrome-trace nesting validator, and trajectory files survive
    corrupt/missing states.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kvcache.paged import PagedKVConfig, init_paged_kv
from repro.models import init_params
from repro.obs import ObsConfig, Tracer, validate_chrome_trace
from repro.obs.perf import (
    attribute, check_regression, format_table,
    grouped_qmm_cost, grouped_qmm_weight_bytes, kv_pool_bytes, load_history,
    metric_direction, qmm_cost, qmm_weight_bytes, roofline,
    site_costs_from_tree)
from repro.obs.perf.history import append_run
from repro.obs.trace import ENGINE_TID
from repro.qtensor import is_qtensor, quantize, storage_summary
from repro.serve import Engine, EngineConfig, quantize_params, trace_requests
from repro.serve.metrics import EngineMetrics

TRACE = [(0, 8, 5), (0, 12, 7), (3, 6, 4)]
ECFG = dict(max_slots=2, max_len=64, max_new_tokens=16,
            prefill_chunk=4, decode_burst=4)


def _perf_engine(obs, seed=0):
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    params = init_params(cfg, jax.random.key(seed))
    qparams, scales = quantize_params(params, 4, group_size=8)
    ecfg = EngineConfig(**ECFG, int8_compute=True, kv_cache="paged",
                        page_size=8, obs=obs)
    return cfg, Engine(qparams, cfg, ecfg, scales=scales)


# ---------------------------------------------------------------------------
# cost model vs realized storage — exact, every width x group size
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 6, 4, 3])
@pytest.mark.parametrize("group_size", [8, 16, None])
def test_qmm_weight_bytes_match_storage_exactly(bits, group_size):
    k, n = 32, 24
    w = jnp.asarray(np.random.default_rng(0).normal(size=(k, n)),
                    jnp.float32)
    qt = quantize(w, bits, group_size=group_size)
    summary = storage_summary([qt])
    assert qmm_weight_bytes(k, n, bits, group_size) == \
        summary["packed_bytes"], (bits, group_size)
    # and through the KernelCost composition
    c = qmm_cost("w", 4, k, n, bits, group_size)
    assert c.bytes_weight == summary["packed_bytes"]


@pytest.mark.parametrize("bits", [8, 6, 4, 3])
@pytest.mark.parametrize("group_size", [8, 16, None])
def test_grouped_qmm_weight_bytes_match_storage_exactly(bits, group_size):
    """The (E, K, N) expert stack's cost-model bytes == realized packed
    storage of the stack AND E x the per-expert slice storage (the
    dense-loop equivalence: one grouped dispatch streams exactly what E
    per-expert dispatches would)."""
    from repro.qtensor import expert_slice, quantize_experts
    e, k, n = 4, 32, 24
    w = jnp.asarray(np.random.default_rng(0).normal(size=(e, k, n)),
                    jnp.float32)
    stack = quantize_experts(w, bits, group_size=group_size)
    want = storage_summary([stack])["packed_bytes"]
    assert grouped_qmm_weight_bytes(e, k, n, bits, group_size) == want
    per_expert = storage_summary([expert_slice(stack, 0)])["packed_bytes"]
    assert want == e * per_expert, (bits, group_size)
    # and through the KernelCost composition
    c = grouped_qmm_cost("moe/w_up", e, 4, k, n, bits, group_size)
    assert c.kind == "grouped_qmm" and c.bytes_weight == want


def test_site_costs_moe_tree_has_grouped_rows():
    """A quantized MoE tree: expert stacks cost as grouped_qmm rows at
    the config's capacity, 2-D blocks as qmm — and summed weight bytes
    still cover the tree's realized storage exactly."""
    cfg = dataclasses.replace(smoke_config("deepseek_moe_16b"),
                              scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    qparams, _ = quantize_params(params, 4, group_size=8)
    costs = site_costs_from_tree(qparams, 4, cfg=cfg)
    kinds = {c.kind for c in costs.values()}
    assert "grouped_qmm" in kinds and "qmm" in kinds
    grouped = {s: c for s, c in costs.items() if c.kind == "grouped_qmm"}
    # one row per expert-stack projection (w_up/w_gate/w_down x layers)
    assert len(grouped) == 3 * cfg.num_layers
    cap = int(cfg.capacity_factor * 4 * cfg.top_k / cfg.num_experts + 0.999)
    for s, c in grouped.items():
        assert s.split("/")[-1] in ("w_up", "w_gate", "w_down")
        e, k, n = qparams["layers"]["0"]["moe"][s.split("/")[-1]].shape
        assert c.bytes_act == max(cap, 1) * e * (k + 4)
    total = sum(c.bytes_weight for c in costs.values()
                if c.kind in ("qmm", "grouped_qmm"))
    assert total == storage_summary(qparams)["packed_bytes"]


@pytest.mark.parametrize("bits", [8, 6, 4, 3])
def test_site_costs_cover_tree_storage_exactly(bits):
    """Summed per-site weight bytes == storage_summary of the whole
    quantized tree: every packed block is costed, none double-counted."""
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    qparams, _ = quantize_params(params, bits, group_size=8)
    costs = site_costs_from_tree(qparams, 4)
    total = sum(c.bytes_weight for c in costs.values()
                if c.kind == "qmm")
    assert total == storage_summary(qparams)["packed_bytes"]
    n_qt = sum(is_qtensor(leaf) for leaf in jax.tree_util.tree_leaves(
        qparams, is_leaf=is_qtensor))
    assert len(costs) == n_qt


@pytest.mark.parametrize("bits", [8, 6, 4, 3])
def test_kv_pool_bytes_match_live_pages_exactly(bits):
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    pcfg = PagedKVConfig.build(cfg, max_len=64, slots=2, page_size=8,
                               kv_bits=bits)
    state = init_paged_kv(cfg, pcfg, slots=2)
    lp = state.layers["0"]
    want = storage_summary([lp.k_qt, lp.v_qt])["packed_bytes"]
    got = kv_pool_bytes(pcfg.num_pages, pcfg.page_size, cfg.num_kv_heads,
                        cfg.head_dim, bits)
    assert got == want, (bits, got, want)


def test_kv_pool_bytes_fp_dense():
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    pcfg = PagedKVConfig.build(cfg, max_len=64, slots=2, page_size=8,
                               kv_bits=None)
    state = init_paged_kv(cfg, pcfg, slots=2)
    lp = state.layers["0"]
    want = lp.k.nbytes + lp.v.nbytes
    fp_bytes = jnp.dtype(cfg.param_dtype).itemsize
    assert kv_pool_bytes(pcfg.num_pages, pcfg.page_size, cfg.num_kv_heads,
                         cfg.head_dim, 16, fp_bytes=fp_bytes) == want


def test_roofline_and_attribution_consistency():
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    qparams, _ = quantize_params(params, 4, group_size=8)
    costs = site_costs_from_tree(qparams, 4, context=48, kv_bits=8,
                                 page_size=8, cfg=cfg)
    assert any(c.kind == "paged_attention" for c in costs.values())
    rl = roofline(costs)
    assert rl["totals"]["step_time_s"] > 0
    assert rl["totals"]["memory_bound_sites"] + \
        rl["totals"]["compute_bound_sites"] == len(costs)
    # attribution: shares partition the measured wall
    rows = attribute(costs, decode_s=2.0)
    assert abs(sum(r.measured_ms for r in rows) - 2000.0) < 1e-6
    assert abs(sum(r.time_share for r in rows) - 1.0) < 1e-9
    assert abs(sum(r.byte_share for r in rows) - 1.0) < 1e-9
    # the table renders every row plus a fold line
    table = format_table(rows, top=3)
    assert "site" in table and "FIT" in table and "more sites" in table


# ---------------------------------------------------------------------------
# engine phase spans
# ---------------------------------------------------------------------------

def test_profiled_engine_device_track_validates():
    """A full profiled serve: the merged trace (engine phases + request
    tracks) passes the nesting validator, and its phase events are the
    spans the metrics booked."""
    obs = ObsConfig(trace=True, device_metrics=True, drain_every=2)
    _, eng = _perf_engine(obs)
    finished, metrics = eng.run(trace_requests(eng.cfg, TRACE))
    assert len(finished) == len(TRACE)
    trace = eng.tracer.chrome_trace()
    assert validate_chrome_trace(trace) == []
    phases = [e for e in trace["traceEvents"]
              if e.get("tid") == ENGINE_TID and e.get("ph") == "X"
              and e["name"].startswith("engine.")]
    names = {e["name"] for e in phases}
    assert {"engine.prefill_chunk", "engine.decode_burst",
            "engine.drain"} <= names
    assert metrics.phase_n == {n: sum(e["name"] == n for e in phases)
                               for n in names}
    table = metrics.phase_table()
    # decode tokens measured == engine bookkeeping
    assert table["engine.decode_burst"]["tokens"] == metrics.decode_tokens
    assert sum(e["args"]["tokens"] for e in phases
               if e["name"] == "engine.decode_burst") == \
        metrics.decode_tokens
    # drains were timed too (drain_every=2 cadence + final drain)
    assert table["engine.drain"]["count"] >= 2


def _decode_jaxpr_str(eng) -> str:
    import functools as ft
    state = eng._fresh_state()
    tok = eng._put_repl(jnp.zeros(eng._tok_shape, jnp.int32))
    out = eng._put_repl(jnp.zeros(eng._out_shape, jnp.int32))
    slots = eng._fresh_slot_table()
    ctr = eng._fresh_counters()
    step = ft.partial(eng._engine_step, steps=2, mode="greedy",
                      stats=bool(ctr))
    return str(jax.make_jaxpr(lambda *a: step(*a))(
        eng.params, eng.scales, state, tok, out, slots, ctr))


def test_perf_off_is_compile_identical():
    """The phase instrumentation never touches the jit'd graphs: an
    obs-off engine and a traced engine lower the IDENTICAL decode-step
    jaxpr — all phase timing is host-side around the audited syncs."""
    _, eng_off = _perf_engine(None)
    _, eng_on = _perf_engine(ObsConfig(trace=True))
    assert eng_on.tracer.enabled and not eng_off.tracer.enabled
    assert _decode_jaxpr_str(eng_on) == _decode_jaxpr_str(eng_off)


def test_engine_without_perf_has_no_timer():
    """Phase timing takes no switch: with tracing off a phase span still
    books its wall and count, but writes no trace event."""
    _, eng = _perf_engine(None)
    assert not hasattr(eng, "perf") and not eng.tracer.enabled
    m = EngineMetrics()
    with eng.tracer.phase("engine.harvest", m) as ph:
        pass
    assert m.phase_n == {"engine.harvest": 1}
    assert m.phase_s == {"engine.harvest": ph.s} and ph.s >= 0
    assert eng.tracer.n_events == 0


# ---------------------------------------------------------------------------
# bench history + regression gate
# ---------------------------------------------------------------------------

def test_history_round_trip(tmp_path):
    path = os.path.join(tmp_path, "BENCH_x.json")
    assert load_history(path)["runs"] == []            # missing -> fresh
    for i in range(4):
        append_run(path, "x", {"tok_per_s": 100.0 + i, "lat_us": 50.0},
                   meta={"i": i}, now=1000.0 + i)
    hist = load_history(path)
    assert hist["schema"] == 1 and hist["bench"] == "x"
    assert len(hist["runs"]) == 4
    assert hist["runs"][2]["meta"]["i"] == 2
    assert hist["runs"][0]["ts"] == 1000.0
    # no regression: last run is the best yet
    assert check_regression(hist) == []


def test_history_regression_detected_with_direction(tmp_path):
    path = os.path.join(tmp_path, "BENCH_y.json")
    for i in range(5):
        append_run(path, "y", {"tok_per_s": 100.0 + 0.1 * i,
                               "lat_us": 50.0 + 0.1 * i}, now=float(i))
    # throughput collapse + latency blowup, both flagged with direction
    probs = check_regression(load_history(path),
                             {"tok_per_s": 40.0, "lat_us": 500.0})
    got = {p["metric"]: p["direction"] for p in probs}
    assert got == {"tok_per_s": "higher", "lat_us": "lower"}
    # within-band drift is not flagged
    assert check_regression(load_history(path),
                            {"tok_per_s": 99.0, "lat_us": 52.0}) == []


def test_history_needs_min_runs(tmp_path):
    path = os.path.join(tmp_path, "BENCH_z.json")
    append_run(path, "z", {"tok_per_s": 100.0}, now=0.0)
    append_run(path, "z", {"tok_per_s": 100.0}, now=1.0)
    # only 2 prior runs: the gate stays silent
    assert check_regression(load_history(path),
                            {"tok_per_s": 1.0}) == []


def test_history_corrupt_and_foreign_files_degrade(tmp_path):
    bad = os.path.join(tmp_path, "BENCH_bad.json")
    with open(bad, "w") as f:
        f.write("{not json")
    hist = load_history(bad)
    assert hist["runs"] == [] and "note" in hist
    # appending over a corrupt file starts a fresh trajectory
    append_run(bad, "bad", {"m_s": 1.0}, now=0.0)
    assert len(load_history(bad)["runs"]) == 1
    # wrong schema version is discarded, not misread
    foreign = os.path.join(tmp_path, "BENCH_v9.json")
    with open(foreign, "w") as f:
        json.dump({"schema": 99, "runs": [{"metrics": {"m_s": 1}}]}, f)
    assert load_history(foreign)["runs"] == []
    # non-finite metrics are dropped on append
    p2 = os.path.join(tmp_path, "BENCH_nan.json")
    append_run(p2, "nan", {"ok_s": 1.0, "bad": float("nan"),
                           "worse": float("inf"), "str": "x"}, now=0.0)
    assert set(load_history(p2)["runs"][0]["metrics"]) == {"ok_s"}


def test_metric_direction_conventions():
    assert metric_direction("decode_tokens_per_s") == "higher"
    assert metric_direction("obs_on_over_off") == "higher"
    assert metric_direction("kernel.qmm.ref_w4a8_us") == "lower"
    assert metric_direction("drain_s") == "lower"
    assert metric_direction("slot_occupancy") == "both"
