"""repro.obs — unified tracing, metrics registry, and profiling hooks.

The serving stack's telemetry layer (DESIGN.md §15):

* :mod:`repro.obs.clock` — the one monotonic clock behind deadlines,
  waits, and span timestamps (fakeable in tests);
* :mod:`repro.obs.trace` — span trees over the request lifecycle,
  ~zero-cost when disabled;
* :mod:`repro.obs.metrics` — the process-global counter/gauge/histogram
  registry that absorbs ``dispatch_counter``/``sweep_counter``/cache and
  service stats;
* :mod:`repro.obs.export` — JSONL sink + Prometheus text render;
* :mod:`repro.obs.profiling` — opt-in ``jax.profiler`` annotations
  mirroring every entered span;
* :mod:`repro.obs.compiles` — the XLA compile counters and
  ``xla_compile`` spans, from one ``jax.monitoring`` listener.

Quick start::

    from repro import obs
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    ... run requests ...
    obs.disable_tracing()
    root = sink.spans[0]          # closed span tree
    print(obs.render_prometheus())
"""
from repro.obs import clock  # noqa: F401  (re-exported submodule)
from repro.obs.compiles import install_compile_listener
from repro.obs.export import (JsonlSink, ListSink, parse_jsonl,
                              render_prometheus, span_from_dict)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               registry)
from repro.obs.profiling import (disable_jax_annotations,
                                 enable_jax_annotations,
                                 jax_annotations_enabled)
from repro.obs.trace import (NOOP_SPAN, Span, Tracer, event, get_tracer,
                             span, tracer)

__all__ = [
    "clock", "Span", "Tracer", "NOOP_SPAN", "tracer", "get_tracer",
    "span", "event", "enable_tracing", "disable_tracing",
    "tracing_enabled", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "registry", "render_prometheus", "JsonlSink",
    "ListSink", "parse_jsonl", "span_from_dict",
    "enable_jax_annotations", "disable_jax_annotations",
    "jax_annotations_enabled", "install_compile_listener", "configure",
]


def enable_tracing(sink=None) -> None:
    """Turn on span collection globally; ``sink`` receives finished root
    trees (``None`` collects nothing but spans still form)."""
    tracer.enable(sink)


def disable_tracing() -> None:
    tracer.disable()


def tracing_enabled() -> bool:
    return tracer.enabled


def configure(cfg) -> None:
    """Apply an :class:`repro.configs.obs.ObsConfig` to global state."""
    if cfg.trace:
        sink = JsonlSink(cfg.trace_path) if cfg.trace_path else ListSink()
        enable_tracing(sink)
    else:
        disable_tracing()
    if cfg.jax_annotations:
        enable_jax_annotations()
    else:
        disable_jax_annotations()
