"""The engine's host phases (``repro.obs.trace.Tracer.phase``): flat,
disjoint spans of the run loop whose totals are the engine's own synced
walls, whose compiles are counted per phase, and which a JAX profile of
the run holds on its host plane at the Chrome JSON's epoch times."""
import dataclasses
import glob
import os
import time

import jax
import pytest

from repro.configs import smoke_config
from repro.models import init_params
from repro.obs import ObsConfig, Tracer
from repro.obs.trace import ENGINE_TID, NO_PHASE
from repro.serve import Engine, EngineConfig, trace_requests

TRACE = [(0, 8, 5), (0, 12, 7), (3, 6, 4)]
PHASES = {"engine.admit", "engine.prefill_chunk", "engine.insert",
          "engine.grow_tables", "engine.decode_burst", "engine.harvest"}


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A traced paged-KV engine: a first run to compile, then a second
    run under the JAX profiler. Returns the engine, the second run's
    metrics, wall seconds and Chrome trace, and the xplane path."""
    cfg = dataclasses.replace(smoke_config("internlm2_1_8b"),
                              scan_layers=False)
    eng = Engine(init_params(cfg, jax.random.key(0)), cfg,
                 EngineConfig(max_slots=2, max_len=64, max_new_tokens=16,
                              prefill_chunk=4, decode_burst=4,
                              kv_cache="paged", page_size=8,
                              obs=ObsConfig(trace=True)))
    eng.run(trace_requests(cfg, TRACE))
    eng.tracer = Tracer(enabled=True)
    tdir = str(tmp_path_factory.mktemp("xplane"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.perf_counter()
    _, m = eng.run(trace_requests(cfg, TRACE))
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return eng, m, wall, eng.tracer.chrome_trace(), path


def _json_phases(trace):
    return sorted((e for e in trace["traceEvents"]
                   if e.get("ph") == "X" and e["name"].startswith("engine.")),
                  key=lambda e: e["ts"])


def test_phase_spans_flat_disjoint_within_wall(profiled):
    _, m, wall, trace, _ = profiled
    evs = _json_phases(trace)
    assert {e["name"] for e in evs} == PHASES
    assert all(e["tid"] == ENGINE_TID for e in evs)
    for a, b in zip(evs, evs[1:]):
        assert a["ts"] + a["dur"] <= b["ts"], (a, b)
    assert m.phase_n == {n: sum(e["name"] == n for e in evs)
                         for n in PHASES}
    assert 0 < sum(m.phase_s.values()) <= wall


def test_phase_totals_are_the_synced_walls(profiled):
    m = profiled[1]
    assert m.phase_s["engine.prefill_chunk"] == m.prefill_s
    assert m.phase_s["engine.decode_burst"] == m.decode_s
    assert m.phase_n["engine.prefill_chunk"] == m.prefill_dispatches
    assert m.decode_slot_s > 0 and m.stall_slot_s >= 0
    table = m.phase_table()
    assert table["engine.prefill_chunk"]["tokens"] == m.prefill_tokens
    assert table["engine.decode_burst"]["wall_s"] == m.decode_s


def test_compiles_counted_per_phase(profiled):
    eng, m = profiled[:2]
    assert m.compiles == {}                      # warmed shapes: none
    cfg = eng.cfg
    # a 9-token prompt ends in a 1-token chunk, a shape no run had
    _, m2 = eng.run(trace_requests(cfg, [(0, 9, 3)]))
    assert m2.compiles.get("engine.prefill_chunk", 0) >= 1
    assert sum(m2.compiles.values()) >= m2.compiles["engine.prefill_chunk"]
    assert set(m2.compiles) <= PHASES | {NO_PHASE}
    _, m3 = eng.run(trace_requests(cfg, [(0, 9, 3)]))
    assert m3.compiles == {}


def test_profile_holds_phases_at_json_times(profiled):
    from jax.profiler import ProfileData
    trace, path = profiled[3:]
    pd = ProfileData.from_file(path)
    start = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                 if p.name == "Task Environment")
    host = sorted(((e.name, start + e.start_ns, e.duration_ns)
                   for p in pd.planes if p.name == "/host:CPU"
                   for line in p.lines for e in line.events
                   if e.name.startswith("engine.")), key=lambda h: h[1])
    for a, b in zip(host, host[1:]):
        assert a[1] + a[2] <= b[1]               # flat on the profiler too
    origin = trace["otherData"]["origin_ns"]
    evs = _json_phases(trace)
    assert [h[0] for h in host] == [e["name"] for e in evs]
    for (name, t_ns, _), e in zip(host, evs):
        assert abs(origin + 1e3 * e["ts"] - t_ns) < 50e3, name
