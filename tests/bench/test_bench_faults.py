"""The comparison that decides ``correct`` fails what it must.

Whole runs of small fixture cells on the CPU, past the harness's look for
a chip, with the timed path broken underneath: the executable the
compile cache hands the service is wrapped so that it returns wrong
answers in the way each fault would. Every fault has to turn ``correct``
false; the unbroken run has to stay correct. The control (the reference
with the PEO test over the unreversed order) has to read above the limit
on three seeds of the fixture's traffic, and put in the executable's
place it has to turn a whole run's ``correct`` false.
"""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import control, reference, run as R, spec  # noqa: E402

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


def fixture_cell(config="fixture-tiny", mix="tiny-closed"):
    b = spec.load_bench(ROOT)
    b["workloads"].append({"name": "fixture.cell", "config": config,
                           "traffic": mix, "chips": 1, "why": "fixture"})
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    return spec.load_cell(b, "fixture.cell", [FIXTURES])


def altered(out, prev, adjs):
    """An answer flipped where it is produced: slot 0 of every unit."""
    out[0] = ~out[0]
    return out


def half_batch(out, prev, adjs):
    """Half of the batch left out: its slots read the empty graph's
    answer (chordal) without being computed."""
    out[len(out) // 2:] = True
    return out


def exchange_left_out(out, prev, adjs):
    """The shards of three of four chips never gathered: their slots
    keep the output buffer's initial zeros."""
    out[-(-len(out) // 4):] = False
    return out


def stale(out, prev, adjs):
    """The step returns its state unchanged: the previous unit's answers
    come back for this one."""
    if prev is None or len(prev) != len(out):
        return out
    return prev.copy()


def broken(monkeypatch, fault):
    from repro.engine.planner import CompileCache

    real_get = CompileCache.get
    last = {}

    def get(self, backend, n_pad, batch, kind="verdict"):
        fn = real_get(self, backend, n_pad, batch, kind=kind)

        def run(*args):
            out = np.array(fn(*args), dtype=bool)
            if not args[0].any():        # warm-up probes pass untouched
                return out
            res = fault(out.copy(), last.get(batch), np.asarray(args[0]))
            last[batch] = out
            return res

        return run

    monkeypatch.setattr(CompileCache, "get", get)


@pytest.mark.parametrize("fault", [altered, half_batch, exchange_left_out,
                                   stale])
def test_each_fault_turns_correct_false(monkeypatch, fault):
    broken(monkeypatch, fault)
    res = R.run_cell(fixture_cell(), 2 ** 31 + 21, 1.0, False,
                     require_tpu=False)
    assert res["correct"] is False
    assert res["checks"]["wrong_verdicts"][0] > 0


def control_verdicts(out, prev, adjs):
    """The reference's control in the program's place: the PEO test over
    the LexBFS order itself, not its reverse."""
    return reference.chordal(adjs, reverse=False)


def test_the_control_in_the_programs_place_turns_correct_false(monkeypatch):
    broken(monkeypatch, control_verdicts)
    res = R.run_cell(fixture_cell(), 2 ** 31 + 23, 1.0, False,
                     require_tpu=False)
    assert res["correct"] is False
    assert res["checks"]["wrong_verdicts"][0] > 0


@pytest.mark.parametrize("mix", ["tiny-closed", "tiny-poisson"])
def test_the_unbroken_run_is_correct_in_both_arrival_modes(mix):
    res = R.run_cell(fixture_cell(mix=mix), 2 ** 31 + 21, 1.0, False,
                     require_tpu=False)
    assert res["correct"] is True, (mix, res["checks"])
    assert res["attempted"] > 0


def test_control_reads_above_the_limit():
    cfg = json.loads((FIXTURES / "configs" / "fixture-tiny.json").read_text())
    for seed in (2 ** 31 + 1, 2 ** 31 + 2, 7):
        row = control.control_reading(cfg, seed, 400)
        assert row["wrong_verdicts"] > row["limit"], row
        assert 0 < row["chordal_share"] < 1


def test_a_trace_cut_short_stops_the_profiler_once_inside_the_window(
        monkeypatch):
    import time

    import jax

    from bench import client

    real_stop = jax.profiler.stop_trace
    stops = []

    def stop_trace():
        stops.append(time.monotonic())
        real_stop()

    real_sleep_until = client.sleep_until
    window_ends = []

    def sleep_until(clock, t, *args):
        window_ends.append(t)
        real_sleep_until(clock, t, *args)

    monkeypatch.setattr(jax.profiler, "stop_trace", stop_trace)
    monkeypatch.setattr(client, "sleep_until", sleep_until)
    cell = fixture_cell()
    cell.config["trace_seconds"] = 0.5
    res = R.run_cell(cell, 2 ** 31 + 25, 1.5, True, require_tpu=False)
    assert res["correct"] is True, res["checks"]
    t_end = window_ends[-1]
    assert len(stops) == 1 and t_end - 1.0 <= stops[0] < t_end
    assert "breakdown" not in res        # the CPU trace has no TPU plane
