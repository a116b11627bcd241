"""The reader of the host to device bandwidth (``h2d_gb_per_s``) on span
trees built by hand: the ``bytes`` of the ``h2d`` spans under each unit's
``dispatch`` over their seconds, a unit counted once however many
requests share it, ``h2d`` spans without ``bytes`` left out, 0 where no
span carries ``bytes`` (as the stage readers read 0 without their span)
and nothing where there is no unit."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from repro.obs.trace import Span  # noqa: E402

UNIT_BYTES = 128 * 2048 * 2048          # one four-chip unit of int8


def reader(cell):
    return spec.load_cell(spec.load_bench(ROOT), cell).per_layer[
        "h2d_gb_per_s"][1]


def exec_tree(t0, lane, stages):
    """``exec > unit > realize, dispatch > stages``; each stage is
    (name, ms, attrs), back to back from ``t0``."""
    ex = Span("exec", {"lane": lane}, t_start=t0)
    unit = ex.child("unit", t=t0, n_pad=2048, batch=128, kind="fused")
    unit.child("realize", t=t0).end(t0)
    dispatch = unit.child("dispatch", t=t0, backend="sharded")
    t = t0
    for name, ms, attrs in stages:
        dispatch.child(name, t=t, **attrs).end(t + ms / 1e3)
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


def fused(h2d_ms, devices=4):
    return [("pack", 0.5, {}),
            ("h2d", h2d_ms, {"bytes": UNIT_BYTES, "devices": devices}),
            ("device", 90.0, {}), ("d2h", 0.1, {})]


SPLIT = [("h2d", 3.0, {}), ("device", 6.0, {}), ("d2h", 0.25, {})]


@pytest.mark.parametrize("cell", ["thesis-s7-2k.saturate",
                                  "thesis-s7-x4.saturate"])
def test_reads_bytes_over_seconds_once_per_unit(cell):
    spans = (requests(exec_tree(10.0, 0, fused(40.0)), 128)
             + requests(exec_tree(11.0, 0, fused(60.0)), 3)
             + requests(exec_tree(12.0, 0, SPLIT), 1))
    want = 2 * UNIT_BYTES / 0.1 / 1e9            # 100 ms of h2d in all
    got = reader(cell)(types.SimpleNamespace(spans=spans))
    assert got == pytest.approx(want)
    assert got == pytest.approx(10.737418, rel=1e-6)


@pytest.mark.parametrize("spans", [
    requests(exec_tree(10.0, 0, SPLIT), 2),       # no span carries bytes
    requests(exec_tree(10.0, 0, []), 2),          # a dispatch without stages
])
def test_reads_zero_without_bytes(spans):
    read = reader("thesis-s7-x4.saturate")
    assert read(types.SimpleNamespace(spans=spans)) == 0.0


def test_reads_nothing_without_units():
    read = reader("thesis-s7-x4.saturate")
    assert read(types.SimpleNamespace(spans=[])) is None


def test_the_reader_applies_to_the_two_saturate_cells_only():
    bench = spec.load_bench(ROOT)
    have = {w["name"] for w in bench["workloads"]
            if "h2d_gb_per_s" in spec.load_cell(bench, w["name"]).per_layer}
    assert have == {"thesis-s7-2k.saturate", "thesis-s7-x4.saturate"}
