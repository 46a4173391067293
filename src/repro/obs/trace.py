"""Span tracing: the engine's host phases, request lifecycles, and the
compiles each phase caused.

Phases (``Tracer.phase``) are the flat, disjoint spans of the engine's
host loop — ``engine.admit``, ``engine.prefill_chunk``,
``engine.insert``, ``engine.grow_tables``, ``engine.decode_burst`` /
``engine.spec_burst``, ``engine.harvest``, ``engine.drain``,
``engine.drift``, ``engine.wait_arrival``. Whether tracing is on or off,
each one adds its wall time and count to the metrics object it is given
(``phase_s`` / ``phase_n``), and enters a ``jax.profiler.TraceAnnotation``
of its name, so any profile of the process holds it on the host plane,
on the device trace's clock. With tracing on it also writes a complete
event on the engine track. A listener on JAX's backend-compile event
books each compile made inside ``counting`` to the phase open at that
moment, or to ``(none)``.

Request lifecycles (one track per request: admit / prefill-chunk /
evict children inside the request span) and the structured jsonl event
log are recorded only with tracing on; they are not annotations.

Timestamps are microseconds since ``origin_ns`` on the epoch clock
(``time.time_ns``), the clock the profiler places its planes on through
``profile_start_time``. The Chrome JSON carries the origin under
``otherData``: a span starts at ``origin_ns + 1e3 * ts`` nanoseconds,
which is ``profile_start_time + start_ns`` for the same span in an
xplane of the run. Export is Chrome trace-event JSON (open in Perfetto:
https://ui.perfetto.dev, "Open trace file") plus the jsonl log.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

ENGINE_TID = 0          # the engine's host loop: phases and the run span
_REQ_TID_BASE = 1       # request r -> tid r + 1
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
NO_PHASE = "(none)"     # compiles of a counted run made outside any phase


class _Books:
    """Where the compile listener books: the metrics of the run being
    counted, the phase open now, and the last run counted."""
    run: Any = None
    phase: Optional[str] = None
    last: Any = None


_BOOKS = _Books()


def _on_duration(event: str, duration_secs: float, **_) -> None:
    book = _BOOKS.run
    if event == COMPILE_EVENT and book is not None:
        key = _BOOKS.phase or NO_PHASE
        book.compiles[key] = book.compiles.get(key, 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


@contextlib.contextmanager
def counting(book):
    """Book every XLA backend compile made inside the block to
    ``book.compiles`` (a dict: phase name -> count)."""
    _BOOKS.run = book
    try:
        yield book
    finally:
        _BOOKS.run = _BOOKS.phase = None
        _BOOKS.last = book


def last_counted():
    """The book of the last run that ``counting`` finished in this
    process (None before the first)."""
    return _BOOKS.last


class Phase:
    """One phase span (``Tracer.phase``). ``s`` is its wall time in
    seconds once it has ended; ``note`` adds arguments to its trace
    event."""

    __slots__ = ("_tracer", "name", "_book", "s", "event", "_ann", "_ts",
                 "_t0")

    def __init__(self, tracer: "Tracer", name: str, book):
        self._tracer, self.name, self._book = tracer, name, book
        self.s = 0.0
        self.event: Optional[Dict[str, Any]] = None

    def __enter__(self) -> "Phase":
        _BOOKS.phase = self.name
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        if self._tracer.enabled:
            self._ts = self._tracer._us()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = s = time.perf_counter() - self._t0
        name, tr = self.name, self._tracer
        if tr.enabled:
            self.event = {"ph": "X", "name": name, "cat": "engine",
                          "pid": tr.pid, "tid": ENGINE_TID, "ts": self._ts,
                          "dur": tr._us() - self._ts, "args": {}}
            tr._events.append(self.event)
        self._ann.__exit__(*exc)
        _BOOKS.phase = None
        book = self._book
        book.phase_s[name] = book.phase_s.get(name, 0.0) + s
        book.phase_n[name] = book.phase_n.get(name, 0) + 1

    def note(self, **args) -> None:
        if self.event is not None:
            self.event["args"].update(args)


class Tracer:
    """Chrome-trace span recorder + jsonl event log."""

    def __init__(self, enabled: bool = True, pid: int = 1):
        self.enabled = enabled
        self.pid = pid
        self.origin_ns = time.time_ns()
        self._events: List[Dict[str, Any]] = []      # trace events
        self._log: List[Dict[str, Any]] = []         # jsonl records
        self._open: Dict[int, Tuple[str, str, int, float, Dict]] = {}
        self._next_id = 0
        self._named_tids: set = set()
        if enabled:
            self._meta("process_name", {"name": "repro.serve"})
            self._name_tid(ENGINE_TID, "engine")

    # -- clock ----------------------------------------------------------
    def _us(self) -> float:
        return (time.time_ns() - self.origin_ns) / 1e3

    # -- chrome metadata ------------------------------------------------
    def _meta(self, name: str, args: Dict, tid: int = 0) -> None:
        self._events.append({"ph": "M", "name": name, "pid": self.pid,
                             "tid": tid, "args": args})

    def _name_tid(self, tid: int, name: str) -> None:
        if tid not in self._named_tids:
            self._named_tids.add(tid)
            self._meta("thread_name", {"name": name}, tid=tid)

    def request_tid(self, req_id: int) -> int:
        tid = _REQ_TID_BASE + int(req_id)
        if self.enabled:
            self._name_tid(tid, f"req {int(req_id)}")
        return tid

    # -- spans ----------------------------------------------------------
    def phase(self, name: str, book) -> Phase:
        """A phase span of the engine's host loop, booked to ``book``
        (``phase_s`` / ``phase_n`` dicts) whether tracing is on or off;
        see the module docstring."""
        return Phase(self, name, book)

    def begin(self, name: str, cat: str = "serve", tid: int = ENGINE_TID,
              args: Optional[Dict] = None) -> Optional[int]:
        """Open a span; returns a handle for :meth:`end` (None if off)."""
        if not self.enabled:
            return None
        sid = self._next_id
        self._next_id += 1
        self._open[sid] = (name, cat, tid, self._us(), dict(args or {}))
        return sid

    def end(self, sid: Optional[int],
            args: Optional[Dict] = None) -> None:
        if sid is None or sid not in self._open:
            return
        name, cat, tid, ts, a = self._open.pop(sid)
        if args:
            a.update(args)
        self._events.append({
            "ph": "X", "name": name, "cat": cat, "pid": self.pid,
            "tid": tid, "ts": ts, "dur": max(self._us() - ts, 0.0),
            "args": a})

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "serve", tid: int = ENGINE_TID,
             args: Optional[Dict] = None):
        if not self.enabled:
            yield None
            return
        sid = self.begin(name, cat, tid, args)
        try:
            yield sid
        finally:
            self.end(sid)

    def instant(self, name: str, tid: int = ENGINE_TID,
                args: Optional[Dict] = None) -> None:
        if not self.enabled:
            return
        self._events.append({"ph": "i", "name": name, "cat": "serve",
                             "pid": self.pid, "tid": tid, "ts": self._us(),
                             "s": "t", "args": dict(args or {})})

    # -- structured event log -------------------------------------------
    def event(self, kind: str, **fields) -> None:
        if not self.enabled:
            return
        rec = {"ts_us": self._us(), "kind": kind}
        rec.update(fields)
        self._log.append(rec)

    # -- export ---------------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        """The Perfetto-loadable trace object (open spans are dropped);
        ``otherData.origin_ns`` is the epoch time of ``ts`` 0."""
        return {"traceEvents": list(self._events), "displayTimeUnit": "ms",
                "otherData": {"origin_ns": self.origin_ns}}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    def write_events(self, path: str) -> None:
        with open(path, "w") as f:
            for rec in self._log:
                f.write(json.dumps(rec) + "\n")

    @property
    def n_events(self) -> int:
        return len(self._events)


# ---------------------------------------------------------------------------
# validation (tests + the CI obs smoke step)
# ---------------------------------------------------------------------------

def validate_chrome_trace(obj: Any) -> List[str]:
    """Schema + nesting check; returns a list of problems (empty = ok).

    * top level: ``{"traceEvents": [...]}``;
    * every complete event (``ph == "X"``) carries numeric ``ts``/``dur``
      (``dur >= 0``), a ``name``, ``pid``/``tid``;
    * per (pid, tid), complete events NEST: sorted by start (ties: longer
      first), each event lies fully inside the enclosing open span —
      request spans must contain their admit/prefill/evict children.
    """
    problems: List[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be an object with a traceEvents list"]
    events = obj["traceEvents"]
    if not isinstance(events, list):
        return ["traceEvents must be a list"]
    complete: Dict[Tuple[Any, Any], List[Tuple[float, float, str]]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph is None or "name" not in ev:
            problems.append(f"event {i}: missing ph/name")
            continue
        if ph == "X":
            ts, dur = ev.get("ts"), ev.get("dur")
            if not isinstance(ts, (int, float)) or \
                    not isinstance(dur, (int, float)) or dur < 0:
                problems.append(
                    f"event {i} ({ev.get('name')}): ts/dur must be "
                    f"numeric with dur >= 0 (got ts={ts!r} dur={dur!r})")
                continue
            if "pid" not in ev or "tid" not in ev:
                problems.append(f"event {i} ({ev.get('name')}): no pid/tid")
                continue
            complete.setdefault((ev["pid"], ev["tid"]), []).append(
                (float(ts), float(dur), str(ev["name"])))
    for (pid, tid), evs in sorted(complete.items(), key=lambda kv: (
            str(kv[0][0]), str(kv[0][1]))):
        evs.sort(key=lambda e: (e[0], -e[1]))
        stack: List[Tuple[float, float, str]] = []
        for ts, dur, name in evs:
            while stack and ts >= stack[-1][0] + stack[-1][1] - 1e-9:
                stack.pop()
            if stack:
                p_ts, p_dur, p_name = stack[-1]
                if ts + dur > p_ts + p_dur + 1e-6:
                    problems.append(
                        f"tid {tid}: span '{name}' [{ts:.1f}, "
                        f"{ts + dur:.1f}] overlaps but does not nest "
                        f"inside '{p_name}' [{p_ts:.1f}, "
                        f"{p_ts + p_dur:.1f}]")
            stack.append((ts, dur, name))
    return problems
