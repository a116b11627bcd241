"""Readings of the backend's stage spans: the children of each unit's
``dispatch`` span that split the executable call into ``pack`` (host
int8 conversion), ``h2d`` (host to device copy), ``device`` (launch until
the outputs are ready), ``d2h`` (device to host copy) and, on certified
units, ``host_finalize``. The split pipeline records one set per graph.
"""
from __future__ import annotations

from bench.spans import _key


def stage_seconds(spans, name: str):
    """(seconds, graphs): the summed duration of the spans named ``name``
    directly under every unit's ``dispatch``, and the graphs those units
    answered, over the units ``bench.spans.units`` counts. Each unit is
    found once by its shared ``exec`` subtree; a unit without such a span
    counts 0 seconds, so a program that records no stages reads 0. None
    where there is no unit."""
    by_exec: dict = {}
    for root in spans:
        ex = root.find("exec")
        if ex is not None:
            by_exec.setdefault(_key(ex), [ex, 0])[1] += 1
    seconds, graphs = 0.0, 0
    for ex, live in by_exec.values():
        unit = ex.find("unit")
        if unit is None:
            continue
        graphs += live
        dispatch = unit.find("dispatch")
        if dispatch is not None:
            seconds += sum(c.duration_ms for c in dispatch.children
                           if c.name == name) / 1e3
    return (seconds, graphs) if graphs else None
