"""Share of its roofline that ``kernels/qmm.py`` reaches in the traced
stretch: the least time the chip could take for the work those qmm calls
needed (the larger of their int8 operations over the int8 peak and their
bytes over the HBM bandwidth; ``yardstick.work.traced_serve_work``) over
the summed device time of the calls."""
LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(rec):
    w = rec.get("trace_work")
    if not w or not w["qmm_n"] or w["qmm_s"] <= 0:
        return None
    p = rec["peaks"]
    least = max(w["qmm_ops"] / p["int8_ops"],
                w["qmm_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / w["qmm_s"]
