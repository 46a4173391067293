"""Prefill time per prompt token: the engine's own synced wall time
around its prefill dispatches (``EngineMetrics.prefill_s``) over the
prompt tokens it prefilled."""
LAYER = "model step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "ttft_p95_s"


def read(rec):
    e = rec.get("engine") or {}
    if not e.get("prefill_tokens"):
        return None
    return 1e3 * e["prefill_s"] / e["prefill_tokens"]
