"""XLA compiles, counted where JAX reports them.

One ``jax.monitoring`` duration listener, installed once per process by
:func:`install_compile_listener`, hears JAX's
``/jax/core/compile/backend_compile_duration`` event, which JAX records
on the compiling thread only when a jitted program reaches the backend
(a first call, or a retrace), never on a cached call. A load from the
persistent compilation cache reports the same event, just after its own
``cache_retrieval_time_sec``; it is no compile and is left out of the
counters.

Per compile it adds the duration to ``repro_compile_seconds_total`` and
one to ``repro_xla_compiles_total``. With tracing on it also attaches a
closed ``xla_compile`` span (attrs ``fun_name``, ``cache_load``) to the
compiling thread's innermost open span, so a compile inside a served
unit is a labelled child of that unit's ``dispatch`` stage.
"""
from __future__ import annotations

import threading

from repro.obs import clock as _clock
from repro.obs.metrics import registry
from repro.obs.trace import tracer

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_M_COMPILES = registry.counter(
    "repro_xla_compiles_total", "XLA backend compiles (cache loads excluded)")
_M_COMPILE_S = registry.counter(
    "repro_compile_seconds_total", "seconds spent in XLA backend compiles")

_installed = False
_install_lock = threading.Lock()
_tls = threading.local()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == CACHE_RETRIEVAL_EVENT:
        _tls.cache_load = True
        return
    if event != BACKEND_COMPILE_EVENT:
        return
    cache_load = getattr(_tls, "cache_load", False)
    _tls.cache_load = False
    if not cache_load:
        _M_COMPILES.inc()
        _M_COMPILE_S.inc(duration)
    if tracer.enabled:
        now = _clock.now()
        tracer.attach("xla_compile", now - duration, now,
                      fun_name=str(kwargs.get("fun_name", "")),
                      cache_load=cache_load)


def install_compile_listener() -> None:
    """Register the listener with ``jax.monitoring`` (once per process)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
