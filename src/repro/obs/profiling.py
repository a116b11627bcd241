"""Opt-in bridge from obs spans to ``jax.profiler`` trace annotations.

When enabled (``enable_jax_annotations()``), every span entered as a
context manager (``with obs.span(...)``) also enters a
``jax.profiler.TraceAnnotation`` of the same name and attributes, so the
span's interval shows up on the host timeline of a captured profile, on
the device trace's own clock. Annotations ride on spans: with tracing
off there is no span and so no annotation. Disabled (the default) no
jax import happens and a span's enter/exit pays one flag read.
"""
from __future__ import annotations

_jax_annotations_enabled = False


def enable_jax_annotations() -> None:
    global _jax_annotations_enabled
    _jax_annotations_enabled = True


def disable_jax_annotations() -> None:
    global _jax_annotations_enabled
    _jax_annotations_enabled = False


def jax_annotations_enabled() -> bool:
    return _jax_annotations_enabled


def annotation(name: str, attrs: dict):
    """An entered-by-the-caller ``TraceAnnotation`` for one span; its
    attributes become the profiler event's stats."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **attrs)
