"""The reduction from a profiler trace to busy time, kernel time and
labelled idle gaps (``bench/trace.py``), on a hand-made trace with known
answers and on a small trace recorded on a v5e chip."""
import gzip
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec, trace as T  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


def toy():
    ops = [["fusion.1", 100.0, 50.0], ["fusion.2", 120.0, 60.0],
           ["custom-call", 300.0, 100.0], ["copy", 390.0, 40.0],
           ["late", 900.0, 500.0]]
    modules = [["jit__fused(1)", 100.0, 80.0], ["jit__fused(2)", 300.0, 130.0],
               ["jit_other", 900.0, 500.0]]
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["bench.window", 50.0, 950.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", 0.0, 1000.0]]}]},
    ]}


def test_busy_is_the_union_clipped_to_the_window():
    tr = toy()
    win = T.find_host_event(tr, "bench.window")
    assert win == (50.0, 1000.0)
    dev0 = T.device_planes(tr)[0]
    assert T.busy(dev0, win) == [[100.0, 180.0], [300.0, 430.0],
                                 [900.0, 1000.0]]
    gaps = list(T.gaps(T.busy(dev0, win), win))
    assert gaps == [(50.0, 100.0), (180.0, 300.0), (430.0, 900.0)]
    assert [p["name"] for p in T.device_planes(tr)] == [
        "/device:TPU:0", "/device:TPU:1"]


def test_kernel_time_counts_matching_events():
    secs, calls = T.kernel_seconds(T.device_planes(tr := toy())[:1],
                                   "XLA Modules", r"jit__fused(_packed)?\b")
    assert calls == 2 and secs == pytest.approx(210e-9)
    assert T.kernel_seconds(T.device_planes(tr), "XLA Modules",
                            r"nothing")[1] == 0


def test_gaps_are_labelled_by_the_innermost_host_interval():
    host = [("exec", 150.0, 500.0), ("realize", 170.0, 320.0)]
    got = T.label_gaps([(180.0, 300.0), (430.0, 900.0), (50.0, 100.0)], host)
    assert [g[0] for g in got] == ["no unit in flight", "realize",
                                   "no unit in flight"]
    assert [g[1] for g in got] == pytest.approx([470e-9, 120e-9, 50e-9])


def test_top_ops_rank_device_time_in_the_window():
    tr = toy()
    top = T.top_ops(T.device_planes(tr)[:1], (50.0, 1000.0), k=2)
    assert [n for n, _ in top] == ["custom-call", "late"]
    assert top[0][1] == pytest.approx(100e-9)


def recorded():
    path = DATA / "v5e_saturate_trace.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def timeline_busy(plane, win, step=1000.0):
    """Busy time by brute force: a boolean timeline at 1 us resolution."""
    n = int((win[1] - win[0]) // step) + 1
    on = np.zeros(n, dtype=bool)
    for _, s, d in T._line(plane, T.OP_LINE):
        lo = int(max(s - win[0], 0) // step)
        hi = int(min(s + d - win[0], win[1] - win[0]) // step)
        if hi > lo:
            on[lo:hi] = True
    return on.sum() * step


def test_recorded_chip_trace_reduces_consistently():
    tr = recorded()
    win = T.find_host_event(tr, "bench.window")
    assert win is not None
    planes = T.device_planes(tr)
    assert planes, "no device plane in the recorded trace"
    busy = T.busy(planes[0], win)
    busy_ns = sum(e - s for s, e in busy)
    assert 0 < busy_ns <= win[1] - win[0]
    assert busy_ns == pytest.approx(timeline_busy(planes[0], win), rel=0.02)
    idle = sum(e - s for s, e in T.gaps(busy, win))
    assert busy_ns + idle == pytest.approx(win[1] - win[0])
    kern = spec.load_module([spec.BENCH_DIR], "kernels/lexbfs_fused.py",
                            "lexbfs_fused")
    secs, calls = T.kernel_seconds(planes[:1], kern.LINE, kern.PATTERN)
    everywhere = sum(e - s for s, e in T.busy(planes[0], (0.0, 1e30)))
    assert calls > 0 and 0.5 * everywhere < secs * 1e9 <= everywhere * 1.001
