"""95th percentile over all finished requests of the time each waited
for admission: the engine's ``Request.t_admitted`` minus the time it was
due. Where the scheduler holds a request behind other prefills, this is
the part of its time to first token spent waiting."""
import numpy as np

LAYER = "scheduler"
UNIT = "s"
SOURCE = "program_span"
MOVES = "ttft_p95_s"


def read(rec):
    waits = [r["admitted"] - r["arrival"] for r in rec.get("requests", [])
             if r.get("admitted") is not None]
    return float(np.percentile(waits, 95)) if waits else None
