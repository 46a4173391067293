"""Operations and bytes the served algorithm needs, from the model's
shapes and the tokens a run served and the kernel calls it made: never
padding, copies or rows computed for idle slots.

The formulas are those of the repo's analytic cost model, kept here so
that the yardstick cannot move with the program:

- qmm (a W{b}A8 matmul of an (m, k) int8 activation with a packed (k, n)
  weight): ``2 m k n`` integer operations; the packed payload
  (``packed_size(k, b) * n`` bytes) plus one fp32 scale per group and
  column, streamed once per call, ``m k + 4 m`` bytes of activations and
  scales in, ``4 m n`` out.
- paged attention (one query token over ``c`` cached tokens, per
  layer): K and V read at the cache's width, ``2 c kv d`` bytes at 8
  bits, plus the fp32 scale of each page and kv head touched;
  ``4 c h d`` operations.

A kernel's roofline share is judged per call it made (how close each
call came to its own bound); how many calls a step makes, such as one
weight pass per prompt token in today's prefill, shows in the step and
end-to-end metrics instead.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

from yardstick import weights

# values, bytes per packed unit (the QTensor layouts of 6, 4 and 3 bits)
UNITS = {6: (4, 3), 4: (2, 1), 3: (2, 1)}


def packed_size(n: int, bits: int) -> int:
    if bits not in UNITS:
        return n
    vals, nbytes = UNITS[bits]
    return -(-n // vals) * nbytes


def qmm_weight_bytes(k: int, n: int, bits: int, group: int) -> float:
    return float(packed_size(k, bits) * n + (k // min(group, k)) * n * 4)


def matrices(dims) -> Dict[str, Tuple[int, int]]:
    """(K, N) of every matrix the served model multiplies by."""
    out = {"head": (dims["hidden_size"], weights.vocab_rows(dims))}
    for i in range(dims["num_hidden_layers"]):
        for p, s in weights.layer_leaves(dims):
            out[f"layers/{i}/{p}"] = s
    return out


def matmul_params(dims) -> float:
    return float(sum(k * n for k, n in matrices(dims).values()))


def serve_flops(dims, requests: List[Tuple[int, int]]) -> Dict[str, float]:
    """Model operations of a run that served ``requests`` ((prompt,
    output) tokens each): two per matmul weight for every row through the
    model (each prompt token, and each output token but the last, which
    is sampled and never fed back), plus attention over each row's
    context (the row at position p attends to p + 1 tokens)."""
    l, h, d = (dims["num_hidden_layers"], dims["num_attention_heads"],
               dims["head_dim"])
    rows = ctx = 0.0
    for p, g in requests:
        n = p + max(g - 1, 0)
        rows += n
        ctx += n * (n + 1) / 2
    return {"model_flops": 2.0 * rows * matmul_params(dims) + 4.0 * ctx * h * d * l,
            "rows": rows}


# ---------------------------------------------------------------------------
# work of the kernel calls in a traced stretch of a serving run
# ---------------------------------------------------------------------------

# base names of the kernel ops whose events the reduction keeps
KERNELS = r"qmm|paged_attention"
QMM = re.compile(r"^(?!grouped).*qmm")
SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _shapes(hlo: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in SHAPE.findall(hlo)]


def live(requests, t: float) -> List[Tuple[int, float]]:
    """(prompt, context) of the requests decoding at engine time ``t``:
    first token out, not yet finished. The context grows from prompt + 1
    at the first token to prompt + output at the last, at the request's
    own average pace."""
    out = []
    for r in requests:
        if r["first"] is None or not r["first"] <= t < r["finished"]:
            continue
        frac = (t - r["first"]) / max(r["finished"] - r["first"], 1e-9)
        out.append((r["prompt"], r["prompt"] + 1 + (r["output"] - 1) * frac))
    return out


def qmm_call(hlo: str, dims, group: int, rows: int) -> Tuple[float, float]:
    """(ops, bytes) one qmm call needs, from its HLO text: the int8
    activation (M, K), the payload (K', N') whose byte width gives the
    bits, and the model's own N for that K (the payload may be padded),
    at ``rows`` useful rows."""
    shapes = _shapes(hlo)
    _, (m, k) = shapes[1]
    pdt, (kp, np_) = shapes[2]
    n = max((nn for kk, nn in matrices(dims).values() if kk == k and nn <= np_),
            default=np_)
    bits = 8 if pdt == "s8" or kp == k else (6 if 4 * kp == 3 * k else 4)
    wbytes = qmm_weight_bytes(k, n, bits, group)
    return 2.0 * rows * k * n, wbytes + rows * (k + 4.0) + 4.0 * rows * n


def traced_serve_work(trace: dict, requests, dims, eng) -> Dict[str, float]:
    """Need of the qmm and paged-attention calls the trace kept: each qmm
    call at its rows (a decode call's rows are the requests decoding
    then, not the engine's idle slots); each paged-attention call (one
    layer of one decode step) over the context of every request decoding
    then, K and V at the pool's width plus each touched page's scales."""
    group, page = eng["group_size"], eng["page_size"]
    kv_bytes = {8: 1.0, 6: 0.75, 4: 0.5, 3: 0.5}.get(eng["kv_bits"], 2.0)
    h, kv, d = (dims["num_attention_heads"], dims["num_key_value_heads"],
                dims["head_dim"])
    out = {"qmm_ops": 0.0, "qmm_bytes": 0.0, "qmm_s": 0.0, "qmm_n": 0,
           "attn_ops": 0.0, "attn_bytes": 0.0, "attn_s": 0.0, "attn_n": 0}
    for hlo, start, dur in trace["kept"]:
        base = hlo.split(" = ", 1)[0].lstrip("%")
        t = trace["at_s"] + start
        if QMM.search(base):
            m = _shapes(hlo)[1][1][0]
            rows = max(1, len(live(requests, t))) if m == eng["max_slots"] else m
            ops, nbytes = qmm_call(hlo, dims, group, rows)
            out["qmm_ops"] += ops
            out["qmm_bytes"] += nbytes
            out["qmm_s"] += dur
            out["qmm_n"] += 1
        elif "paged_attention" in base:
            for _, ctx in live(requests, t):
                out["attn_ops"] += 4.0 * ctx * h * d
                out["attn_bytes"] += (2 * ctx * kv * d * kv_bytes
                                      + 2 * -(-ctx // page) * kv * 4.0)
            out["attn_s"] += dur
            out["attn_n"] += 1
    return out
