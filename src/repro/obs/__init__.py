"""repro.obs — serving observability (see README "Observability").

Five layers over the continuous-batching engine:

  1. span tracing (``trace``)        — the engine's host phases (always
     timed and annotated for the JAX profiler, compiles counted per
     phase) and per-request lifecycles, Chrome trace-event JSON on the
     profiler's epoch clock (Perfetto) + jsonl log;
  2. zero-sync device metrics (``runtime``/``counters``) — counters
     accumulated INSIDE the jit'd decode burst, drained in bulk on a
     cadence (the only audited host transfer);
  3. gauges + exposition (``gauges``/``prom``) — page pool, prefix
     sharing, per-shard HBM, jit-cache churn, Prometheus text format;
  4. FIT drift monitoring (``drift``) — online logit KL + activation-
     range drift vs the calibrated SensitivityReport, closing the loop
     between FIT's offline prediction and the live system;
  5. performance profiling (``perf``) — the analytic QTensor cost
     model, per-site FIT/bytes/ms attribution, and bench-history
     regression gating. See README "Performance profiling".

``repro.obs.drift`` imports the model stack, which imports this
package's ``runtime`` — import it as ``repro.obs.drift`` directly
(kept out of this namespace to stay cycle-free); ``repro.obs.perf``
is likewise imported directly (its cost/attrib modules reach the
serve/quant stacks lazily).
"""
from repro.obs.config import ObsConfig
from repro.obs.counters import DeviceCounters
from repro.obs.gauges import GAUGE_HELP, collect_gauges, snapshot
from repro.obs.prom import MetricsServer, parse, render, write_snapshot
from repro.obs.runtime import (
    COUNTERS, CounterSink, collecting, ctr_add, ctr_get, emit, emitting,
    emitting_stats, fold, init_counters, suspended, unpack_counters)
from repro.obs.trace import Tracer, validate_chrome_trace

__all__ = [
    "COUNTERS", "CounterSink", "DeviceCounters", "GAUGE_HELP",
    "MetricsServer", "ObsConfig", "Tracer", "collect_gauges", "collecting",
    "ctr_add", "ctr_get", "emit", "emitting", "emitting_stats", "fold",
    "init_counters", "parse", "render", "snapshot", "suspended",
    "unpack_counters", "validate_chrome_trace", "write_snapshot",
]
