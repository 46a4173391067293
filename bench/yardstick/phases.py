"""The engine's phase counters for one run (``EngineMetrics.phase_s``,
``phase_n``, ``compiles``, ``stall_slot_s``, ``decode_slot_s``): what the
scheduler-layer metrics read.

They are taken from the record's ``engine`` dict where the harness put
them there. Otherwise they are taken from the program's own book of the
last run it counted in this process (``repro.obs.trace.last_counted``),
and only if that book is the recorded run's: its synced prefill and
decode seconds equal the record's. A program that keeps no such book
gives None.
"""
from __future__ import annotations

from typing import Optional

KEYS = ("phase_s", "phase_n", "compiles", "stall_slot_s", "decode_slot_s")


def engine_counters(rec: dict) -> Optional[dict]:
    e = rec.get("engine") or {}
    if all(k in e for k in KEYS):
        return {k: e[k] for k in KEYS}
    try:
        from repro.obs.trace import last_counted
    except ImportError:
        return None
    book = last_counted()
    if (book is None or "prefill_s" not in e
            or (book.prefill_s, book.decode_s) != (e["prefill_s"],
                                                   e["decode_s"])):
        return None
    return {k: getattr(book, k) for k in KEYS}
