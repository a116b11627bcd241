"""Share of the measured window each device spends inside ``exec`` spans
(an executor lane holding a unit), in %, averaged over the devices. The
spans are grouped by their attr ``device`` (the cache scope the unit ran
in), or by their ``lane`` where a span names no device, clipped to the
window and merged where lanes share a device; each unit is found once by
its shared ``exec`` subtree, as in ``bench/spans.py``. None where there
is no ``exec`` span."""
from bench.spans import _key
from bench.trace import merge


def read(run):
    seen, busy = set(), {}
    for root in run.spans:
        ex = root.find("exec")
        if ex is None or ex.t_end is None or _key(ex) in seen:
            continue
        seen.add(_key(ex))
        where = ex.attrs.get("device", ("lane", ex.attrs.get("lane")))
        s, e = max(ex.t_start, run.t0), min(ex.t_end, run.t_end)
        iv = busy.setdefault(where, [])
        if e > s:
            iv.append((s, e))
    if not busy:
        return None
    window = run.t_end - run.t0
    shares = [sum(e - s for s, e in merge(iv)) / window
              for iv in busy.values()]
    return 100.0 * sum(shares) / len(shares)
