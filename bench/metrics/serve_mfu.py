"""Whole-step utilization of serving: the operations the model needs for
every prompt and output token served (two per matmul weight, plus
attention over each token's context; ``yardstick.work``) per second of
the run, from the window's start to the last completion, over the
chip's int8 peak."""
LAYER = "model step"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "tpot_p95_ms"


def read(rec):
    w, t = rec.get("work"), rec.get("elapsed_s")
    if not w or not t or not w.get("model_flops"):
        return None
    return 100.0 * w["model_flops"] / t / rec["peaks"]["int8_ops"]
