"""Readings from the program's host spans (``repro.obs``): the finished
``request`` trees a traced run collects. Every request of one unit adopts
the same ``exec`` subtree (``exec > unit > realize, dispatch``), so a unit
is found once by that subtree's start and executor lane, which also holds
for trees read back from JSON lines.
"""
from __future__ import annotations


def _key(exec_span):
    return exec_span.t_start, exec_span.attrs.get("lane")


def host_intervals(spans, offset_ns: float):
    """(label, start_ns, end_ns) of each unit's host stages, on the trace's
    clock: ``exec`` (the executor lane holds a unit), and inside it
    ``unit``, ``realize`` and ``dispatch <backend>/<kind> n<n_pad>b<B>``."""
    seen, out = set(), []
    for root in spans:
        ex = root.find("exec")
        if ex is None or _key(ex) in seen:
            continue
        seen.add(_key(ex))
        for s in ex.walk():
            if s.t_end is None:
                continue
            label = s.name
            if s.name == "dispatch":
                unit = ex.find("unit")
                label = (f"dispatch {s.attrs.get('backend')}/"
                         f"{s.attrs.get('kind')} n{unit.attrs.get('n_pad')}"
                         f"b{unit.attrs.get('batch')}")
            out.append((label, s.t_start * 1e9 + offset_ns,
                        s.t_end * 1e9 + offset_ns))
    return out


def units(spans, until=None):
    """(n_pad, batch, kind, live requests, realize_s, dispatch_s) of every
    unit whose requests completed, from the shared ``exec`` subtrees; with
    ``until`` (span clock, s), of those whose ``dispatch`` ended by then."""
    by_exec: dict = {}
    for root in spans:
        ex = root.find("exec")
        if ex is not None:
            by_exec.setdefault(_key(ex), [ex, 0])[1] += 1
    out = []
    for ex, live in by_exec.values():
        unit = ex.find("unit")
        if unit is None:
            continue
        rz, dp = unit.find("realize"), unit.find("dispatch")
        if until is not None and (dp is None or dp.t_end > until):
            continue
        out.append((unit.attrs.get("n_pad"), unit.attrs.get("batch"),
                    unit.attrs.get("kind"), live,
                    rz.duration_ms / 1e3 if rz else 0.0,
                    dp.duration_ms / 1e3 if dp else 0.0))
    return out


def last_dispatch_end(spans, until: float):
    """The latest end (span clock, s) of a unit's ``dispatch`` span at or
    before ``until``, or None. With one executor lane units run one after
    another, so every kernel that ended by then belongs to a unit whose
    ``dispatch`` ended by then."""
    ends = [d.t_end for root in spans
            for d in [root.find("dispatch")]
            if d is not None and d.t_end is not None and d.t_end <= until]
    return max(ends, default=None)

