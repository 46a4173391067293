"""Observability switches for ``EngineConfig(obs=...)``.

Frozen + hashable so it can live inside the (frozen) EngineConfig.
Everything defaults OFF: an engine built without an ObsConfig pays
nothing on the hot path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """What to record and where to put it."""

    trace: bool = False           # Chrome trace JSON + jsonl event log
    device_metrics: bool = False  # in-jit counter accumulation + drains
    drain_every: int = 8          # bursts between counter drains (0: end only)
    stats_every: int = 4          # bursts between element-wise clip-stat
    #                               samples (act_sat / fq_clip reductions);
    #                               1 = every burst. Exact i32 counters
    #                               (tokens/steps/bursts) are never sampled.
    trace_path: Optional[str] = None    # Chrome trace JSON output
    events_path: Optional[str] = None   # structured jsonl log output
    metrics_file: Optional[str] = None  # Prometheus text snapshot output
    metrics_port: Optional[int] = None  # live /metrics endpoint (0 = ephemeral)

    @property
    def enabled(self) -> bool:
        return self.trace or self.device_metrics

    def __post_init__(self):
        if self.drain_every < 0:
            raise ValueError("drain_every must be >= 0")
        if self.stats_every < 1:
            raise ValueError("stats_every must be >= 1")
