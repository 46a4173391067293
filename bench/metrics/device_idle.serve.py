"""Share of the traced serving run in which no operation ran on the
device: one minus the union of the device's op intervals over the traced
window (``yardstick.trace``)."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(rec):
    t = rec.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
