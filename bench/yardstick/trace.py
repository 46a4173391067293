"""Reduce one profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read: device busy time as the union of op intervals,
the traced window, device time per op name, and the device's idle gaps,
each named by what the host was doing meanwhile.

Device ops are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane. The window is the benchmark's own host
annotation (``WINDOW``), on the same clock as the device planes. A gap
is named after the longest host event (outside the window annotation)
that spans its midpoint; host events are JAX's dispatch and transfer
marks on the host plane.
"""
from __future__ import annotations

import collections
import heapq
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench_window"
# control-flow ops whose events span the ops of their bodies: kept in the
# busy union, left out of per-op time so nothing is counted twice
CONTAINERS = frozenset({"while", "conditional", "call"})
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
NS = 1e-9


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps_between(busy, lo, hi):
    """Idle (start, end) spans of [lo, hi] outside the merged ``busy``."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def name_gaps(gaps, host_events) -> Dict[str, float]:
    """Seconds of idle time per host activity. ``host_events`` are
    (name, start, end). A gap goes to the longest host event that spans
    its midpoint (the outermost thing the host was doing then, such as a
    jit dispatch), else to ``"(none)"``: the host was in code that marks
    nothing."""
    evs = sorted(host_events, key=lambda x: x[1])
    out: Dict[str, float] = collections.defaultdict(float)
    heap: List[Tuple[float, float, str]] = []      # (-duration, end, name)
    i = 0
    for gs, ge in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (gs + ge) / 2
        while i < len(evs) and evs[i][1] <= mid:
            name, s, e = evs[i]
            heapq.heappush(heap, (-(e - s), e, name))
            i += 1
        while heap and heap[0][1] <= mid:
            heapq.heappop(heap)
        out[heap[0][2] if heap else "(none)"] += (ge - gs) * NS
    return dict(out)


def op_base(name: str) -> str:
    """An XLA op event's name (the HLO text, ``%qmm_pallas.12 = f32[..]
    custom-call(..)``) -> the op's base name (``qmm_pallas``)."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def reduce_trace(path: str, window: Optional[Tuple[float, float]] = None,
                 keep: Optional[str] = None) -> dict:
    """The trace at ``path`` -> {"window_s", "busy_s" (mean over device
    planes), "devices", "op_s" {op base name: seconds}, "op_n" {op base
    name: count}, "idle_gaps" {host activity: seconds}, "kept" [[HLO
    text, start s after the window opened, device s], ...] of the ops
    whose base name matches ``keep``}. Ops are counted inside the window;
    the window is the ``WINDOW`` annotation unless given."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append(_events(line))
    return reduce_events(host, devices, window, keep)


def reduce_events(host, devices, window=None, keep=None) -> dict:
    """``reduce_trace`` on events already read: ``host`` is a list of
    (name, start_ns, end_ns), ``devices`` one such list per chip."""
    if window is None:
        marks = [(s, e) for n, s, e in host if n == WINDOW]
        if not marks:
            raise ValueError(f"no {WINDOW!r} annotation in the trace")
        window = (min(s for s, _ in marks), max(e for _, e in marks))
    lo, hi = window
    if not devices or not any(devices):
        raise ValueError("no device op events in the trace")
    op_s: Dict[str, float] = collections.defaultdict(float)
    op_n: Dict[str, int] = collections.defaultdict(int)
    kept: List[List[object]] = []
    keep_rx = re.compile(keep) if keep else None
    busy_total, gaps_all = 0.0, []
    for evs in devices:
        inside = clip([(s, e) for _, s, e in evs], lo, hi)
        busy = union_ns(inside)
        busy_total += sum(e - s for s, e in busy)
        gaps_all.extend(gaps_between(busy, lo, hi))
        for name, s, e in evs:
            base = op_base(name)
            if e > lo and s < hi and base not in CONTAINERS:
                op_s[base] += (min(e, hi) - max(s, lo)) * NS
                op_n[base] += 1
                if keep_rx and keep_rx.search(base):
                    kept.append([name, (s - lo) * NS,
                                 (min(e, hi) - max(s, lo)) * NS])
    host_in = [(n, s, e) for n, s, e in host if n != WINDOW and e > lo and s < hi]
    return {"window_s": (hi - lo) * NS,
            "busy_s": busy_total * NS / len(devices),
            "devices": len(devices),
            "op_s": dict(op_s), "op_n": dict(op_n), "kept": kept,
            "idle_gaps": name_gaps(gaps_all, host_in)}


def top(d: Dict[str, float], n: int = 10) -> List[List[object]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
