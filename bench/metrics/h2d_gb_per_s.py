"""Host to device bandwidth of the fused dispatches, in GB/s (1e9 bytes):
the host bytes put by the ``h2d`` spans under each unit's ``dispatch``
(attr ``bytes``) over those spans' seconds. On a mesh the put is split
over the chips' links (attr ``devices``) and the span lasts until the
last shard is on its chip, so this is the rate of the whole put. Each
unit is found once by its shared ``exec`` subtree, as in
``bench/stages.py``, and as there a program that records no such span
reads 0: here where no ``h2d`` span carries ``bytes``. None where there
is no unit."""
from bench.spans import _key


def read(run):
    seen, nbytes, seconds = set(), 0, 0.0
    for root in run.spans:
        ex = root.find("exec")
        if ex is None or _key(ex) in seen or ex.find("unit") is None:
            continue
        seen.add(_key(ex))
        dispatch = ex.find("dispatch")
        for s in dispatch.children if dispatch is not None else ():
            if s.name == "h2d" and "bytes" in s.attrs:
                nbytes += s.attrs["bytes"]
                seconds += s.duration_ms / 1e3
    if not seen:
        return None
    return nbytes / seconds / 1e9 if seconds > 0 else 0.0
