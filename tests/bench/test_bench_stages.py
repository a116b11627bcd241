"""The readers of the backend's stage spans (``bench/stages.py``,
``bench/metrics/{pack,h2d,device_wait,d2h}_us_per_graph.py``) on span
trees built by hand: a unit is counted once however many requests share
its ``exec`` subtree, a unit without stages reads 0, no unit reads
nothing."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec, stages  # noqa: E402
from repro.obs.trace import Span  # noqa: E402

READERS = {"pack_us_per_graph": "pack", "h2d_us_per_graph": "h2d",
           "device_wait_us_per_graph": "device", "d2h_us_per_graph": "d2h"}
CELL = "thesis-s7-2k.saturate"


def reader(name):
    return spec.load_cell(spec.load_bench(ROOT), CELL).per_layer[name][1]


def exec_tree(t0, lane, stage_ms):
    """``exec > unit > realize, dispatch > stages``, the stages back to
    back from ``t0`` for the given milliseconds."""
    ex = Span("exec", {"lane": lane}, t_start=t0)
    unit = ex.child("unit", t=t0, n_pad=2048, batch=32, kind="fused")
    unit.child("realize", t=t0).end(t0)
    dispatch = unit.child("dispatch", t=t0, backend="pallas_peo")
    t = t0
    for name, ms in stage_ms:
        dispatch.child(name, t=t).end(t + ms / 1e3)
        t += ms / 1e3
    for s in (dispatch, unit, ex):
        s.end(t)
    return ex


def requests(ex, n):
    roots = []
    for _ in range(n):
        root = Span("request", t_start=ex.t_start)
        root.adopt(ex)
        root.end(ex.t_end)
        roots.append(root)
    return roots


STAGE_MS = [("pack", 1.0), ("h2d", 4.0), ("device", 2.5), ("d2h", 0.5)]
SPLIT_MS = [("h2d", 3.0), ("device", 6.0), ("d2h", 0.25)] * 2


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_counts_a_shared_unit_once(name):
    spans = (requests(exec_tree(10.0, 0, STAGE_MS), 3)
             + requests(exec_tree(11.0, 0, SPLIT_MS), 1))
    want_ms = sum(ms for n, ms in STAGE_MS + SPLIT_MS if n == READERS[name])
    run = types.SimpleNamespace(spans=spans, dev=None, peaks=None)
    assert reader(name)(run) == pytest.approx(want_ms * 1e3 / 4)
    seconds, graphs = stages.stage_seconds(spans, READERS[name])
    assert graphs == 4 and seconds == pytest.approx(want_ms / 1e3)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_without_units_and_zero_without_stages(name):
    read = reader(name)
    assert read(types.SimpleNamespace(spans=[])) is None
    bare = requests(exec_tree(10.0, 0, []), 2)
    assert read(types.SimpleNamespace(spans=bare)) == 0.0
