"""Time per decode step: the engine's synced wall time around its decode
bursts (``EngineMetrics.decode_s``) over the steps they ran."""
LAYER = "model step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tpot_p95_ms"


def read(rec):
    e = rec.get("engine") or {}
    if not e.get("decode_steps"):
        return None
    return 1e3 * e["decode_s"] / e["decode_steps"]
