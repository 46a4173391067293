"""The scheduler-layer readers on synthetic records, the engine phase
counters they read, and idle gaps named by the engine's phase spans."""
import copy
import types

import pytest

import _paths  # noqa: F401
from yardstick import phases, registry
from yardstick import trace as tr

NEW = ("host_share.serve", "decode_stall_share")
OLD = ("queue_wait_p95_s", "prefill_ms_per_token", "decode_step_ms",
       "serve_mfu", "qmm_roofline", "paged_attention_roofline",
       "device_idle.serve")


def _record(**engine_extra):
    """A traced serving record as the harness writes it."""
    return {
        "requests": [{"id": i, "arrival": 1.0 * i, "admitted": 1.5 * i,
                      "first": 2.0 * i, "finished": 9.0 + i, "prompt": 40,
                      "output": 100} for i in range(4)],
        "engine": {"prefill_s": 4.0, "prefill_tokens": 160,
                   "prefill_dispatches": 8, "decode_s": 60.0,
                   "decode_steps": 480, "decode_tokens": 400,
                   **engine_extra},
        "elapsed_s": 100.0,
        "work": {"model_flops": 4e15},
        "window_compiles": 0,
        "peaks": {"int8_ops": 393e12, "bf16_flops": 197e12,
                  "hbm_bytes_per_s": 819e9},
        "trace": {"window_s": 10.0, "busy_s": 9.5},
        "trace_work": {"qmm_n": 10, "qmm_s": 1.0, "qmm_ops": 1e12,
                       "qmm_bytes": 1e10, "attn_n": 5, "attn_s": 0.5,
                       "attn_ops": 1e9, "attn_bytes": 1e9},
    }


COUNTERS = {"phase_s": {"engine.prefill_chunk": 4.0,
                        "engine.decode_burst": 60.0,
                        "engine.wait_arrival": 30.0,
                        "engine.admit": 2.0, "engine.harvest": 1.0},
            "phase_n": {"engine.prefill_chunk": 8,
                        "engine.decode_burst": 60},
            "compiles": {"(none)": 1},
            "stall_slot_s": 12.0, "decode_slot_s": 108.0}


def test_new_readers_on_synthetic_records():
    rec = _record(**COUNTERS)
    host = registry.load_metric("host_share.serve")
    stall = registry.load_metric("decode_stall_share")
    # 100 s elapsed, 4 + 60 + 30 held by syncs and the arrival wait
    assert host.read(rec) == pytest.approx(6.0)
    assert stall.read(rec) == pytest.approx(10.0)
    for mod in (host, stall):
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == \
            ("scheduler", "%", "program_span", "tpot_p95_ms")


@pytest.mark.parametrize("name", NEW)
def test_new_readers_none_without_counters(name, monkeypatch):
    import repro.obs.trace as otr
    monkeypatch.setattr(otr._BOOKS, "last", None)
    mod = registry.load_metric(name)
    assert mod.read(_record()) is None
    assert mod.read({}) is None


def test_new_readers_none_where_nothing_ran():
    host = registry.load_metric("host_share.serve")
    stall = registry.load_metric("decode_stall_share")
    assert host.read({**_record(**COUNTERS), "elapsed_s": float("nan")}) \
        is None
    assert stall.read(_record(**{**COUNTERS, "stall_slot_s": 0.0,
                                 "decode_slot_s": 0.0})) is None


def test_counters_from_the_programs_book_of_the_same_run(monkeypatch):
    import repro.obs.trace as otr
    monkeypatch.setattr(otr._BOOKS, "last", None)     # restored after
    assert phases.engine_counters(_record()) is None
    book = types.SimpleNamespace(prefill_s=4.0, decode_s=60.0, **COUNTERS)
    with otr.counting(book):
        pass
    assert otr.last_counted() is book
    assert phases.engine_counters(_record()) == COUNTERS
    other = _record()
    other["engine"]["decode_s"] = 61.0            # another run's record
    assert phases.engine_counters(other) is None


@pytest.mark.parametrize("name", OLD)
def test_existing_readers_ignore_the_added_keys(name):
    mod = registry.load_metric(name)
    plain = _record()
    added = _record(**copy.deepcopy(COUNTERS))
    assert mod.read(plain) is not None
    assert mod.read(added) == mod.read(plain)


def test_gap_inside_a_phase_span_is_named_after_it():
    # the device idles in [40, 60] while the host harvests in [35, 70];
    # the lifecycle of the whole run is no host event, so nothing wider
    # than the phase spans the gap
    host = [(tr.WINDOW, 0, 100), ("engine.decode_burst", 0, 35),
            ("engine.harvest", 35, 70), ("PjitFunction(slice)", 45, 48),
            ("engine.decode_burst", 70, 100)]
    dev = [("fusion", 0, 40), ("fusion", 60, 100)]
    r = tr.reduce_events(host, [dev])
    assert r["idle_gaps"] == {"engine.harvest": pytest.approx(20e-9)}
