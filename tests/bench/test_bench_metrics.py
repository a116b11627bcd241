"""Every metric reader of ``bench/metrics`` on spans and a trace recorded
by a traced run of the fused kernel's bucket (``thesis-s7-2k.saturate``)
on a v5e chip.

The recording is what a ``--trace 1`` run leaves in ``.bench_run``: the
request span trees as JSON lines (first line: the measured window), and
the normalized profiler trace, both cut to the first units of the window.
"""
import gzip
import json
import math
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as R, spec, spans as S  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "thesis-s7-2k.saturate"


def recorded_run():
    from repro.obs import span_from_dict

    with gzip.open(DATA / "v5e_saturate_spans.jsonl.gz", "rt") as f:
        lines = [json.loads(x) for x in f]
    t0, t_end = lines[0]["window"]
    roots = [span_from_dict(d) for d in lines[1:]]
    with gzip.open(DATA / "v5e_saturate_trace.json.gz", "rt") as f:
        tr = json.load(f)
    due = np.array([r.t_start for r in roots])
    done = np.array([r.t_end for r in roots])
    run = types.SimpleNamespace(
        seconds=t_end - t0, t0=t0, t_end=t_end, setup_s=1.0, due=due,
        sent=due, done=done, ok=np.ones(len(roots), dtype=bool),
        deadline=t_end + 60.0, spans=roots, dev=None,
        peaks=spec.peaks_for("TPU v5 lite"), trace_s=t_end - t0,
        trace_end=None)
    return run, tr


def test_every_reader_reads_the_recorded_run():
    bench = spec.load_bench(ROOT)
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)       # every reader, on this one run
    cell = spec.load_cell(bench, CELL)
    run, tr = recorded_run()
    run.dev = R.reduce_trace(tr, cell, run, 1)
    values = {}
    for name, (_, read) in {**cell.end_to_end, **cell.per_layer}.items():
        v = read(run)
        if name == "lexbfs_split_roofline":     # no split unit ran here
            assert v is None
            continue
        assert v is not None and math.isfinite(v), name
        values[name] = v
    assert values["graphs_per_s"] > 0
    assert 1 <= values["occupancy"] <= 32
    assert values["realize_us_per_graph"] > 0
    assert values["dispatch_us_per_graph"] > values["kernel_us_per_graph"] \
        * 0.5
    assert 0 < values["lexbfs_fused_roofline"] <= 100
    assert 0 <= values["device_idle_pct"] < 100
    assert values["p50_ms"] > 0
    assert values["queue_p50_ms.steady"] >= 0
    assert values["unit_p50_ms.steady"] > 0


def test_a_trace_stopped_early_counts_only_what_ended_before_the_stop():
    cell = spec.load_cell(spec.load_bench(ROOT), CELL)
    run, tr = recorded_run()
    full = R.reduce_trace(tr, cell, run, 1)
    run.trace_s = (run.t_end - run.t0) / 2
    run.trace_end = run.t0 + run.trace_s
    half = R.reduce_trace(tr, cell, run, 1)
    assert half["window_s"] * 2 == pytest.approx(full["window_s"], rel=1e-3)
    assert 0 < half["busy_s"] < full["busy_s"]
    k, kf = half["kernels"]["lexbfs_fused"], full["kernels"]["lexbfs_fused"]
    assert 0 < k["calls"] < kf["calls"] and 0 < k["units"] < kf["units"]
    assert k["calls"] == k["units"]         # one fused call per unit
    assert k["seconds"] < kf["seconds"] and k["graphs"] < kf["graphs"]
    cut = S.units(run.spans, until=run.trace_end)
    assert len(cut) == k["units"] and all(u[5] > 0 for u in cut)


def test_units_are_found_once_per_shared_exec_subtree():
    run, _ = recorded_run()
    units = S.units(run.spans)
    assert sum(u[3] for u in units) == len(run.spans)
    assert len({(u[0], u[1]) for u in units}) >= 1
    assert all(u[2] in ("fused", "fused_packed") for u in units)


def test_a_reader_with_nothing_to_read_returns_nothing():
    bench = spec.load_bench(ROOT)
    cell = spec.load_cell(bench, CELL)
    empty = types.SimpleNamespace(spans=[], dev=None, peaks=None)
    for name, (_, read) in cell.per_layer.items():
        assert read(empty) is None, name
