"""Share of the whole run, from the window's start to the last
completion, in which the engine's host loop had work but was neither
waiting on a device sync nor sleeping for an arrival: the elapsed time
less the engine's ``engine.prefill_chunk``, ``engine.decode_burst``,
``engine.spec_burst`` and ``engine.wait_arrival`` spans
(``EngineMetrics.phase_s``), over the elapsed time. What is left is
scheduling, page planning, inserts, harvests, table uploads and code in
no span."""
from yardstick import phases

LAYER = "scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"
HELD = ("engine.prefill_chunk", "engine.decode_burst", "engine.spec_burst",
        "engine.wait_arrival")


def read(rec):
    c = phases.engine_counters(rec)
    t = rec.get("elapsed_s")
    if c is None or not (t and t > 0):
        return None
    return 100.0 * (t - sum(c["phase_s"].get(k, 0.0) for k in HELD)) / t
