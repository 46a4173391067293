"""Share of its roofline that ``kernels/paged_attention.py`` reaches in
the traced stretch: the least time for the attention those calls needed
(K and V of every decoding request's context at the pool's width, plus
the touched pages' scales, and 4·context·heads·head_dim operations
against the bf16 peak; ``yardstick.work.traced_serve_work``) over the
summed device time of the calls."""
LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tpot_p95_ms"


def read(rec):
    w = rec.get("trace_work")
    if not w or not w["attn_n"] or w["attn_s"] <= 0 or not w["attn_bytes"]:
        return None
    p = rec["peaks"]
    least = max(w["attn_ops"] / p["bf16_flops"],
                w["attn_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / w["attn_s"]
