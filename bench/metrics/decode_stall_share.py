"""Share of the slot-seconds of decoding requests spent waiting behind
another request's prefill: each ``engine.prefill_chunk`` span's wall
times the slots mid-decode (``EngineMetrics.stall_slot_s``), over that
plus each decode burst's wall times its active slots
(``EngineMetrics.decode_slot_s``)."""
from yardstick import phases

LAYER = "scheduler"
UNIT = "%"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(rec):
    c = phases.engine_counters(rec)
    if c is None:
        return None
    total = c["stall_slot_s"] + c["decode_slot_s"]
    return 100.0 * c["stall_slot_s"] / total if total > 0 else None
