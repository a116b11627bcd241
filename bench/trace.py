"""Reduction of a JAX profiler trace to device busy time, kernel time and
the longest idle gaps, each labelled with what the host was doing.

A trace is first normalized to plain data, ``{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``,
so the reduction runs the same on a trace the profiler just wrote and on
a small recorded one kept with the tests.

Device planes are the planes named ``/device:<PLATFORM>:<id>``. Busy time
is the union of the intervals of that plane's op events, clipped to the
traced window, averaged over the chips used. A kernel's time is the
summed duration of the events on its line whose names match its pattern.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: The device line whose events are single operations.
OP_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


#: The device lines the reduction reads; every other line is skipped.
DEVICE_LINES = (OP_LINE, "XLA Modules")


def load(log_dir: str, host_events=("bench.window",)) -> dict:
    """Normalize the newest ``.xplane.pb`` under ``log_dir``, keeping the
    device lines the reduction reads and the host events it looks for."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            lines.append({"name": line.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events
                if device or e.name in host_events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(tr: dict) -> List[dict]:
    return sorted((p for p in tr["planes"] if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(2)))


def host_events(tr: dict) -> Iterable[Tuple[str, float, float]]:
    for p in tr["planes"]:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                for e in line["events"]:
                    yield e


def find_host_event(tr: dict, name: str) -> Optional[Tuple[float, float]]:
    """(start_ns, end_ns) of the first host event named ``name``."""
    for ev_name, start, dur in host_events(tr):
        if ev_name == name:
            return start, start + dur
    return None


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo: float, hi: float):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def busy(plane: dict, window: Tuple[float, float]) -> List[List[float]]:
    """Merged busy intervals of one device plane inside ``window``."""
    ops = ((s, s + d) for _, s, d in _line(plane, OP_LINE))
    return merge(_clip(ops, *window))


def gaps(busy_iv: List[List[float]], window: Tuple[float, float]):
    """Idle [start, end) intervals of the window between busy ones."""
    t = window[0]
    for s, e in busy_iv:
        if s > t:
            yield t, s
        t = max(t, e)
    if window[1] > t:
        yield t, window[1]


def kernel_seconds(planes: Sequence[dict], line: str, pattern: str,
                   until: Optional[float] = None):
    """(summed seconds, calls) of events on ``line`` matching ``pattern``
    over ``planes``; with ``until``, of those that ended by then."""
    rx = re.compile(pattern)
    total, calls = 0.0, 0
    for plane in planes:
        for name, s, d in _line(plane, line):
            if rx.search(name) and (until is None or s + d <= until):
                total += d
                calls += 1
    return total * 1e-9, calls


def top_ops(planes: Sequence[dict], window, k: int = 10):
    """The ``k`` op names that took most device time in the window."""
    acc: Dict[str, float] = {}
    for plane in planes:
        for name, s, d in _line(plane, OP_LINE):
            for cs, ce in _clip([(s, s + d)], *window):
                acc[name] = acc.get(name, 0.0) + (ce - cs) * 1e-9
    return sorted(([n, v] for n, v in acc.items()),
                  key=lambda x: -x[1])[:k]


def label_gaps(gap_iv, host: Sequence[Tuple[str, float, float]],
               k: int = 10):
    """The ``k`` longest gaps, each named by the innermost host interval
    (name, start_ns, end_ns) that covers its midpoint, or "no unit in
    flight" where none does."""
    out = []
    for s, e in sorted(gap_iv, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        cover = [h for h in host if h[1] <= mid < h[2]]
        name = min(cover, key=lambda h: h[2] - h[1])[0] if cover \
            else "no unit in flight"
        out.append([name, (e - s) * 1e-9])
    return out


def roofline_pct(dev: Optional[dict], peaks: Optional[dict],
                 kernel: str) -> Optional[float]:
    """Share, in %, of ``kernel``'s device time that moving its bytes
    takes at the published HBM bandwidth; None where it did not run."""
    k = (dev or {}).get("kernels", {}).get(kernel)
    if not k or not peaks or k["seconds"] <= 0 or k["bytes"] <= 0:
        return None
    return 100.0 * k["bytes"] / peaks["hbm_bytes_per_s"] / k["seconds"]
