"""The reader of the executor lanes' busy share (``lane_busy_pct``) on
span trees built by hand: per device, the union of its ``exec`` spans
clipped to the measured window, a unit counted once however many
requests share it, averaged over the devices; per lane where the spans
name no device (a program that records no ``device`` on ``exec``), and
nothing where there is no ``exec`` span."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from repro.obs.trace import Span  # noqa: E402

CELL = "thesis-s7-x4-replicas.saturate"


def reader():
    return spec.load_cell(spec.load_bench(ROOT), CELL).per_layer[
        "lane_busy_pct"][1]


def requests(t_start, t_end, lane, n=2, **attrs):
    """``n`` request roots sharing one ``exec`` span of ``lane``."""
    ex = Span("exec", {"lane": lane, **attrs}, t_start=t_start)
    ex.child("unit", t=t_start).end(t_end)
    ex.end(t_end)
    roots = []
    for _ in range(n):
        root = Span("request", t_start=t_start)
        root.adopt(ex)
        root.end(t_end)
        roots.append(root)
    return roots


def run(spans, t0=100.0, t_end=110.0):
    return types.SimpleNamespace(spans=spans, t0=t0, t_end=t_end)


def test_reads_each_devices_share_of_the_window():
    spans = (
        # tpu:0: 1 s + 2 s inside the window, and 1 s of a unit that
        # started before it: 4 s of 10
        requests(101.0, 102.0, 0, device="tpu:0")
        + requests(104.0, 106.0, 0, n=32, device="tpu:0")
        + requests(99.0, 101.0, 0, device="tpu:0")
        # tpu:1: two lanes on one chip overlap over 107-108, and a unit
        # runs past the window's end: 106-109 and 109.5-110, 3.5 s of 10
        + requests(106.0, 108.0, 1, device="tpu:1")
        + requests(107.0, 109.0, 5, device="tpu:1")
        + requests(109.5, 111.0, 1, device="tpu:1")
        # tpu:2: a unit wholly after the window counts as 0 s of 10
        + requests(110.5, 111.0, 2, device="tpu:2"))
    got = reader()(run(spans))
    assert got == pytest.approx(100.0 * (0.4 + 0.35 + 0.0) / 3)


def test_unit_spanning_the_whole_window_reads_a_full_lane():
    spans = requests(90.0, 120.0, 0, device="tpu:3")
    assert reader()(run(spans)) == pytest.approx(100.0)


def test_exec_spans_without_a_device_are_read_per_lane():
    spans = (requests(101.0, 103.0, 0) + requests(102.0, 106.0, 1)
             + requests(103.0, 104.0, 0))
    assert reader()(run(spans)) == pytest.approx(100.0 * (0.3 + 0.4) / 2)


@pytest.mark.parametrize("spans", [
    [],                                            # no request at all
    [Span("request", t_start=101.0)],              # a root with no exec
])
def test_reads_nothing_without_an_exec_span(spans):
    assert reader()(run(spans)) is None


def test_the_reader_applies_to_the_replica_cell_only():
    bench = spec.load_bench(ROOT)
    have = {w["name"] for w in bench["workloads"]
            if "lane_busy_pct" in spec.load_cell(bench, w["name"]).per_layer}
    assert have == {CELL}
