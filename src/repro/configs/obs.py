"""Observability presets: how much telemetry a deployment pays for.

``ObsConfig`` is declarative; ``repro.obs.configure(cfg)`` applies it to
the process-global tracer/profiler state.  Metrics are always on (they
are a handful of locked adds); tracing and jax annotations are the two
knobs with real cost, so they default off.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    # Span tracing on/off. When on with trace_path=None an in-memory
    # ListSink is installed (useful for demos/tests).
    trace: bool = False
    # JSONL sink path for span trees + events; opened append.
    trace_path: Optional[str] = None
    # Mirror every entered span as a jax.profiler.TraceAnnotation.
    jax_annotations: bool = False

    def __post_init__(self):
        if self.trace_path is not None and not self.trace:
            raise ValueError("trace_path set but trace=False")


OBS_CONFIGS: Dict[str, ObsConfig] = {
    "off": ObsConfig(),
    "memory": ObsConfig(trace=True),
    "jsonl": ObsConfig(trace=True, trace_path="obs_trace.jsonl"),
    "profile": ObsConfig(trace=True, jax_annotations=True),
}
