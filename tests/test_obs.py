"""End-to-end observability: zero-sync device counters, span tracing,
Prometheus exposition, and the live FIT drift monitor.

The load-bearing guarantee: the device counter carry (accumulated
INSIDE the jit'd decode burst, drained in bulk on a cadence) is
BIT-EXACT against independent host bookkeeping — useful decode tokens,
steps, burst histogram — across staggered arrivals, eviction and
backfill, at tp=1 and tp=2.  The static side of the same contract
(no host syncs in the burst dispatch) is pinned by analysis rules
RPR008/RPR103; this file pins the numbers.
"""
import dataclasses
import json
import urllib.request

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.core import build_report
from repro.core.rankcorr import spearman
from repro.data.synthetic import LMStreamConfig, lm_batches
from repro.models import init_params, loss_fn
from repro.obs import (
    DeviceCounters, MetricsServer, ObsConfig, Tracer, ctr_get,
    init_counters, parse, render, validate_chrome_trace, write_snapshot)
from repro.obs.drift import DriftMonitor
from repro.obs.gauges import snapshot
from repro.serve import Engine, EngineConfig, quantize_params, trace_requests
from repro.serve.metrics import EngineMetrics

# staggered arrivals + more requests than slots: queueing, mid-flight
# admission, eviction on completion, immediate backfill — the schedule
# the counter-parity contract must survive
TRACE = [(0, 8, 5), (0, 12, 7), (3, 6, 4), (10, 10, 6), (11, 5, 8)]
ECFG = dict(max_slots=2, max_len=64, max_new_tokens=16,
            prefill_chunk=4, decode_burst=4)


def _obs_engine(obs=None, mesh=None, seed=0):
    """Smoke W4 qtensor engine on the paged KV cache (the serving mode
    the counters instrument most heavily: qmm + paged-attention taps)."""
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    params = init_params(cfg, jax.random.key(seed))
    qparams, scales = quantize_params(params, 4, group_size=8)
    ecfg = EngineConfig(**ECFG, int8_compute=True, kv_cache="paged",
                        page_size=8, mesh=mesh,
                        obs=obs or ObsConfig(device_metrics=True,
                                             drain_every=2))
    return params, Engine(qparams, cfg, ecfg, scales=scales)


# ---------------------------------------------------------------------------
# device counters
# ---------------------------------------------------------------------------

def test_device_counter_drain_parity():
    """Drained device counters == independent host bookkeeping, exactly.

    The host mirror (``metrics.decode_tokens`` / ``decode_steps``) is
    computed from numpy slot tables on the host, never from the device
    counters — agreement is two bookkeepers closing the same ledger.
    """
    _, eng = _obs_engine()
    finished, metrics = eng.run(trace_requests(eng.cfg, TRACE))
    assert len(finished) == len(TRACE)

    totals = eng.counters.totals()
    assert totals["decode_tokens"] == metrics.decode_tokens
    assert totals["decode_steps"] == metrics.decode_steps
    # the burst histogram partitions the bursts
    assert sum(totals["burst_size_hist"]) == totals["decode_bursts"]
    assert totals["decode_bursts"] > 0
    # quantized serving actually went through the instrumented kernels
    assert totals["qmm_calls"] > 0 and totals["act_elems"] > 0
    assert totals["paged_calls"] > 0 and totals["paged_tokens_read"] > 0
    assert 0.0 <= totals["fq_clip"] <= totals["fq_elems"]
    # cadenced drains happened during the run, not only at shutdown
    assert eng.counters.n_drains >= 2
    rates = eng.counters.rates()
    assert 0.0 <= rates["act_clip_rate"] <= 1.0


def test_counters_off_compiles_away():
    """obs=None serves the legacy 6-tuple graph: no counter carry at
    all, and the ledger stays empty."""
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    params = init_params(cfg, jax.random.key(0))
    qparams, scales = quantize_params(params, 4, group_size=8)
    eng = Engine(qparams, cfg, EngineConfig(**ECFG, int8_compute=True,
                                            kv_cache="paged", page_size=8),
                 scales=scales)
    assert eng._fresh_counters() == {}
    finished, _ = eng.run(trace_requests(cfg, TRACE))
    assert len(finished) == len(TRACE)
    assert eng.counters.totals() == {} and eng.counters.n_drains == 0


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices (run under XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8)")
def test_device_counters_tp_invariant():
    """tp=2 drains the SAME counter values as tp=1, bit for bit (emits
    come from replicated pre-shard values; ops-level emits inside
    shard_map bodies are suspended) — and the outputs stay bit-equal."""
    from repro.launch.mesh import make_tp_mesh
    _, e1 = _obs_engine()
    _, e2 = _obs_engine(mesh=make_tp_mesh(2))
    f1, _ = e1.run(trace_requests(e1.cfg, TRACE))
    f2, _ = e2.run(trace_requests(e2.cfg, TRACE))
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(a.output_tokens, b.output_tokens)
    t1, t2 = e1.counters.totals(), e2.counters.totals()
    assert set(t1) == set(t2) and t1
    for k in t1:
        np.testing.assert_array_equal(t1[k], t2[k], err_msg=k)


def test_counter_registry_shapes():
    """The packed buffer is exactly two flat arrays (one per kind) —
    the burst-dispatch carry stays small — and every registered counter
    addresses its declared shape/dtype through ``ctr_get``."""
    ctr = init_counters()
    assert set(ctr) == {"i32", "f32"}
    assert ctr["i32"].ndim == 1 and ctr["f32"].ndim == 1
    assert ctr_get(ctr, "burst_size_hist").shape == (8,)
    assert ctr_get(ctr, "decode_tokens").dtype == jnp.int32
    assert ctr_get(ctr, "qmm_calls").dtype == jnp.float32
    dc = DeviceCounters()
    assert dc.drain({}) == {} and dc.totals() == {}


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_trace_schema_and_request_nesting(tmp_path):
    """The exported Chrome trace validates (schema + per-track nesting)
    and carries the request lifecycle: request span > admit / prefill
    chunks / evict children on the request's own track."""
    obs = ObsConfig(trace=True, device_metrics=True, drain_every=2)
    _, eng = _obs_engine(obs=obs)
    finished, _ = eng.run(trace_requests(eng.cfg, TRACE))

    obj = eng.tracer.chrome_trace()
    assert validate_chrome_trace(obj) == []
    names = {e["name"] for e in obj["traceEvents"] if e.get("ph") == "X"}
    for want in ("run", "admit", "prefill_chunk", "engine.decode_burst",
                 "engine.drain", "evict"):
        assert want in names, (want, names)
    assert any(n.startswith("request") for n in names)
    # every request's children live inside its request span, per track
    by_tid = {}
    for e in obj["traceEvents"]:
        if e.get("ph") == "X":
            by_tid.setdefault(e["tid"], []).append(e)
    req_tracks = [evs for evs in by_tid.values()
                  if any(e["name"].startswith("request") for e in evs)]
    assert len(req_tracks) == len(TRACE)
    for evs in req_tracks:
        req = next(e for e in evs if e["name"].startswith("request"))
        lo, hi = req["ts"], req["ts"] + req["dur"]
        for e in evs:
            assert lo - 1e-6 <= e["ts"] and \
                e["ts"] + e["dur"] <= hi + 1e-6, e["name"]

    # file export round-trips through json
    p = tmp_path / "trace.json"
    eng.tracer.write(str(p))
    assert validate_chrome_trace(json.loads(p.read_text())) == []
    # the structured event log covers admission and completion
    ep = tmp_path / "events.jsonl"
    eng.tracer.write_events(str(ep))
    kinds = [json.loads(l)["kind"] for l in ep.read_text().splitlines()]
    assert kinds.count("admit") == len(TRACE)
    assert kinds.count("finish") == len(TRACE)


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"nope": 1}) != []
    bad_dur = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0}]}
    assert any("ts/dur" in p for p in validate_chrome_trace(bad_dur))
    overlap = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0},
        {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 5.0, "dur": 10.0},
    ]}
    assert any("nest" in p for p in validate_chrome_trace(overlap))
    nested = {"traceEvents": [
        {"ph": "X", "name": "a", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0},
        {"ph": "X", "name": "b", "pid": 1, "tid": 1, "ts": 2.0, "dur": 3.0},
    ]}
    assert validate_chrome_trace(nested) == []


def test_tracer_disabled_is_free():
    tr = Tracer(enabled=False)
    sid = tr.begin("x")
    tr.end(sid)
    tr.event("admit", req=1)
    with tr.span("y"):
        pass
    assert tr.n_events == 0 and tr.chrome_trace()["traceEvents"] == []


# ---------------------------------------------------------------------------
# metrics exposition (prometheus text + endpoint) and gauges
# ---------------------------------------------------------------------------

def test_prom_render_parse_roundtrip():
    samples = {"decode_tokens": 123, "tok_rate": 45.5, "flag": True,
               "skipped": None, "burst_size_hist": [1, 2, 0],
               "bad name-1": 7}
    text = render(samples, {"decode_tokens": "useful decode tokens"})
    assert "# HELP repro_decode_tokens useful decode tokens" in text
    parsed = parse(text)
    assert parsed[("repro_decode_tokens", "")] == 123
    assert parsed[("repro_tok_rate", "")] == 45.5
    assert parsed[("repro_flag", "")] == 1
    assert parsed[("repro_burst_size_hist", 'bucket="1"')] == 2
    assert parsed[("repro_bad_name_1", "")] == 7
    assert ("repro_skipped", "") not in parsed
    with pytest.raises(ValueError):
        parse("not a metric line at all\n")


def test_metrics_server_and_snapshot(tmp_path):
    """The /metrics endpoint serves a parseable exposition of the live
    engine snapshot (gauges + drained counters)."""
    _, eng = _obs_engine()
    eng.run(trace_requests(eng.cfg, TRACE))
    snap = snapshot(eng)
    assert snap["ctr_decode_tokens"] == eng.metrics.decode_tokens
    assert snap["kv_pages_total"] > 0
    srv = MetricsServer(0, lambda: snapshot(eng))
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics") as r:
            body = r.read().decode()
    finally:
        srv.close()
    parsed = parse(body)
    assert parsed[("repro_ctr_decode_tokens", "")] == \
        eng.metrics.decode_tokens
    # file snapshot writes the same exposition plus a sibling json dump
    p = tmp_path / "metrics.prom"
    write_snapshot(str(p), snap)
    assert parse(p.read_text())[("repro_ctr_decode_tokens", "")] == \
        eng.metrics.decode_tokens
    assert json.loads((tmp_path / "metrics.prom.json").read_text())[
        "ctr_decode_tokens"] == eng.metrics.decode_tokens


def test_metrics_runnable_occupancy_and_deferrals():
    """Occupancy divides by runnable slots (slots that HAD work), not
    all slots; the raw all-slots figure survives as _raw."""
    m = EngineMetrics(max_slots=4)
    m.record_burst(0.1, 4, 2, n_tokens=8, n_runnable=2)
    m.record_deferral()
    s = m.summary()
    assert s["slot_occupancy"] == pytest.approx(1.0)      # 8 / (4*2)
    assert s["slot_occupancy_raw"] == pytest.approx(0.5)  # 8 / (4*4)
    assert s["admission_deferrals"] == 1
    # legacy callers (no n_runnable) keep the all-slots denominator
    m2 = EngineMetrics(max_slots=4)
    m2.record_burst(0.1, 4, 2, n_tokens=8)
    assert m2.summary()["slot_occupancy"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# FIT drift monitor
# ---------------------------------------------------------------------------

def _calibrated_ranges(cfg, fp_params):
    """Per-site (lo, hi) from one fp forward over a calibration batch —
    the offline half of the drift check (what a SensitivityReport's
    act_ranges hold for these tap sites)."""
    from repro.models.context import CollectContext
    from repro.models.transformer import forward
    stream = lm_batches(LMStreamConfig(vocab_size=cfg.vocab_size,
                                       seq_len=32, global_batch=4, seed=1))
    ctx = CollectContext()
    forward(fp_params, next(stream), cfg, ctx=ctx)
    return {k: (float(jnp.minimum(jnp.min(a), 0.0)),
                float(jnp.maximum(jnp.max(a), 0.0)))
            for k, a in ctx.acts.items()}


def test_drift_monitor_quiet_in_calibration():
    """Properly calibrated ranges: serving traffic from the calibration
    distribution must NOT flag drift."""
    fp_params, eng = _obs_engine()
    mon = DriftMonitor(fp_params, _calibrated_ranges(eng.cfg, fp_params),
                       every=4, ratio_threshold=1.5).attach(eng)
    eng.run(trace_requests(eng.cfg, TRACE))
    rep = mon.drift_report()
    assert rep["n_samples"] >= 2
    assert rep["in_calibration"] and rep["flagged_sites"] == []
    assert rep["kl_max"] is not None and rep["kl_max"] >= 0.0


def test_drift_monitor_flags_stale_calibration():
    """Self-calibration scaled to 1/3 (the --drift-stale 3 demo knob,
    simulating 3x-stale calibration): the monitor must flag the drifted
    sites and group them per layer."""
    fp_params, eng = _obs_engine()
    mon = DriftMonitor(fp_params, {}, every=4, ratio_threshold=1.5,
                       calibration_scale=1.0 / 3.0).attach(eng)
    eng.run(trace_requests(eng.cfg, TRACE))
    rep = mon.drift_report()
    assert not rep["in_calibration"] and rep["flagged_sites"]
    assert rep["flagged_layers"]
    assert all(l.startswith("layers/") for l in rep["flagged_layers"])
    flagged = [s for s, d in rep["sites"].items() if d["flagged"]]
    assert flagged == rep["flagged_sites"]
    assert max(d["max_ratio"] for d in rep["sites"].values()) > 1.5


def test_drift_site_kl_ranks_like_offline_fit():
    """The drift demo's FIT-vs-reality check: per-weight-block ONLINE
    logit KL on the live serving state rank-correlates with the OFFLINE
    FIT score ``trace x noise_power`` (paper Sec. 3) at W4."""
    fp_params, eng = _obs_engine()
    mon = DriftMonitor(fp_params, {}, every=8).attach(eng)

    cfg = eng.cfg
    stream = lm_batches(LMStreamConfig(vocab_size=cfg.vocab_size,
                                       seq_len=32, global_batch=4, seed=0))
    report = build_report(lambda p, b: loss_fn(p, b, cfg), None, None,
                          None, fp_params,
                          [next(stream) for _ in range(2)],
                          tolerance=None, max_batches=2)

    # the sweep must see LIVE state (slots mid-decode with KV history):
    # after run() every slot is evicted and attention collapses to the
    # current token, zeroing the q/k sites' effect — so capture it from
    # the monitor's own sampling cadence, exactly where the launch demo
    # would run it
    kls = {}
    orig_sample = mon._sample

    def tap(slot):
        if not kls:
            kls.update(mon.site_kls(sorted(report.weight_traces), bits=4))
        orig_sample(slot)

    mon._sample = tap
    eng.run(trace_requests(cfg, TRACE))
    assert mon.samples            # the cadence fired while slots were live
    assert len(kls) >= 15                 # every 2-D weight block scored
    fits = [report.fit_weights({s: 4}) for s in kls]
    rho = spearman(fits, list(kls.values()))
    assert rho >= 0.6, (rho, kls)
