"""The benchmark's contract, checked on the CPU: the spec's schema, a new
cell loading from added files alone, the refusal without a chip, the
open-loop schedule, the traffic's no-repeat property and the last line's
schema."""
import hashlib
import json
import math
import os
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import client, spec, traffic  # noqa: E402

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load_bench(ROOT)


def fixture_bench(cells):
    """The spec plus fixture cells that BENCHMARK.json does not hold; every
    metric applies to them."""
    b = json.loads(json.dumps(BENCH))
    b["workloads"] += [{"name": n, "config": c, "traffic": t, "chips": 1,
                        "why": "fixture"} for n, c, t in cells]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    return b


def test_spec_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert (ROOT / p).is_dir()


def test_names_units_and_entries_keep_the_limits():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[group]}) == len(BENCH[group])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(BENCH["workloads"])
    for w in BENCH["workloads"]:
        cell = spec.load_cell(BENCH, w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        for name, (m, _) in cell.per_layer.items():
            assert m["moves"] in cell.end_to_end, (w["name"], name)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]


def test_run_seconds_fit_the_full_check():
    r = BENCH["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_a_new_cell_loads_from_added_files_alone():
    b = fixture_bench([("fixture.closed", "fixture-tiny", "tiny-closed")])
    cell = spec.load_cell(b, "fixture.closed", [FIXTURES])
    assert cell.config["family"]["pool_n"] == 64
    assert cell.traffic["mode"] == "closed"
    assert set(cell.end_to_end) == {m["name"] for m in b["end_to_end"]}
    assert set(cell.kernels) == {"lexbfs_batched"}
    with pytest.raises(spec.SpecError):
        spec.load_cell(b, "fixture.closed")      # not found without its dir


def test_peaks_are_keyed_by_device_kind():
    peaks = spec.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks_for("cpu")


def test_poisson_schedule_is_drawn_from_the_seed():
    a = traffic.poisson_due(56.0, 20.0, 2 ** 31 + 5)
    b = traffic.poisson_due(56.0, 20.0, 2 ** 31 + 5)
    c = traffic.poisson_due(56.0, 20.0, 9)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == 1120
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 20.0
    # every seed offers the same set of gaps, in another order
    ga = np.sort(np.diff(np.append(a, 20.0)))
    gc = np.sort(np.diff(np.append(c, 20.0)))
    assert np.allclose(ga, gc)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_open_loop_lateness_is_measured_from_the_due_time():
    from concurrent.futures import Future

    clock = FakeClock()
    rec = client.Record(clock)

    def sleep(d):                       # every wake-up comes 2 ms late
        clock.t += d + 0.002

    def submit(graph):
        f = Future()
        f.set_result(type("R", (), {"verdict": True})())
        return f

    due = np.array([0.0, 0.01, 0.05, 0.051])
    client.open_loop(submit, lambda i: i, due, 100.0, rec, sleep=sleep)
    d, s, done = rec.arrays()
    assert np.allclose(d, 100.0 + due)
    late = (s - d) * 1e3
    assert np.allclose(late, [0.0, 2.0, 2.0, 1.0])
    assert client.lateness_ms(rec)[0] == pytest.approx(1.5)
    assert np.all(done >= s)


def test_closed_loop_keeps_the_outstanding_count():
    import threading
    from concurrent.futures import Future

    import time
    pending, peak = [], [0]
    lock = threading.Lock()

    def submit(graph):
        f = Future()
        with lock:
            pending.append(f)
            peak[0] = max(peak[0], sum(not p.done() for p in pending))
        threading.Timer(0.002, f.set_result,
                        [type("R", (), {"verdict": True})()]).start()
        return f

    rec = client.Record(time.monotonic)
    t0 = time.monotonic()
    client.closed_loop(submit, lambda i: i, 4, t0, t0 + 0.3, rec)
    rec.wait(time.monotonic() + 5)
    assert peak[0] <= 4 and len(rec) > 20
    d, s, done = rec.arrays()
    assert np.all(d[:4] == t0) and np.all(done >= s)


def _content_key(n, adj):
    return n, hashlib.blake2b(np.packbits(adj[:n, :n]).tobytes(),
                              digest_size=16).digest()


def test_thesis_traffic_never_repeats_an_adjacency():
    cfg = spec.load_json([spec.BENCH_DIR], "configs/thesis-s7-2k.json")
    src = traffic.source(cfg, 2 ** 31 + 99)
    keys = {_content_key(*src.payload(i)) for i in range(600)}
    assert len(keys) == 600
    assert src.count == 5 * 7 * (1024 // 7)   # the clique limits it
    assert src.repeat_share(src.count) == 0.0
    assert src.repeat_share(src.count + 10) > 0.0      # past the pool: wraps
    lo, hi = cfg["family"]["n_range"]
    assert lo <= src.n_nodes.min() and src.n_nodes.max() <= hi


def test_every_block_sends_the_same_mix_of_sizes_and_classes():
    """One size from each stratum per block of ``strata`` requests, and
    every (class, stratum) pair once per len(classes) blocks, whatever
    the seed: the seed changes the graphs, not the work."""
    cfg = json.loads((FIXTURES / "configs" / "fixture-tiny.json")
                     .read_text())
    fam = cfg["family"]
    k, lo, hi = fam["strata"], *fam["n_range"]
    n_cls = len(fam["classes"])
    cum = np.cumsum([c["graphs"] for c in fam["classes"]])
    for seed in (2 ** 31 + 7, 3):
        src = traffic.source(cfg, seed)
        stratum = (src.n_nodes - lo) * k // (hi + 1 - lo)
        cls = np.searchsorted(cum, src.keys // (hi + 1), side="right")
        assert (np.sort(stratum.reshape(-1, k), axis=1) == np.arange(k)).all()
        pair = (cls * k + stratum).reshape(-1, n_cls * k)
        assert (np.sort(pair, axis=1) == np.arange(n_cls * k)).all()
        assert len(pair) == (hi + 1 - lo) // k   # the clique's prefixes
    a, b = traffic.source(cfg, 5), traffic.source(cfg, 6)
    assert not np.array_equal(a.n_nodes, b.n_nodes)
    # within a stratum the size is drawn: the mean size barely moves
    assert abs(a.n_nodes.mean() - b.n_nodes.mean()) < 0.02 * (hi - lo)


def test_full_range_strata_fill_the_buckets_one_two_four():
    """thesis-s7's seven strata of 1024 sizes pad to n_pad 2048 once,
    4096 twice and 8192 four times in every block."""
    cfg = spec.load_json([spec.BENCH_DIR], "configs/thesis-s7.json")
    lo, hi = cfg["family"]["n_range"]
    k = cfg["family"]["strata"]
    edges = lo + np.rint(np.arange(k + 1) * (hi + 1 - lo) / k).astype(int)
    pads = [1 << int(e - 1).bit_length() for e in edges[1:] - 1]
    assert pads == [2048, 4096, 4096, 8192, 8192, 8192, 8192]
    assert all(1 << int(e - 1).bit_length() == p
               for e, p in zip(edges[:-1], pads))


def test_seeded_sample_of_checked_answers():
    a = traffic.sample(1000, 96, 2 ** 31 + 1)
    assert np.array_equal(a, traffic.sample(1000, 96, 2 ** 31 + 1))
    assert len(set(a.tolist())) == 96 and a.max() < 1000
    assert np.array_equal(traffic.sample(50, 96, 1), np.arange(50))


def _run_script(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "thesis-s7-2k.saturate",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run_script(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_in_a_checkout_that_holds_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_script(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_last_line_schema_on_a_fixture_run():
    """A whole run of a tiny fixture cell on the CPU, past the chip check:
    the result object has the contract's keys and checks come last."""
    from bench import run as R

    b = fixture_bench([("fixture.closed", "fixture-tiny", "tiny-closed")])
    cell = spec.load_cell(b, "fixture.closed", [FIXTURES])
    res = R.run_cell(cell, 2 ** 31 + 11, 1.0, False, require_tpu=False)
    line = json.loads(json.dumps(res))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in b["end_to_end"]}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and math.isfinite(m["value"])
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(v <= lim for v, lim in line["checks"].values())


def test_generator_never_sleeps_a_negative_time():
    """The clock moves between reads: a wait computed from two reads can
    come out negative, which ``time.sleep`` refuses."""
    t = [0.0]

    def clock():
        t[0] += 0.0007
        return t[0]

    slept = []

    def sleep(d):
        assert d >= 0
        slept.append(d)
        t[0] += d

    for target in (0.001, 0.0021, 0.0035, 0.02):
        client.sleep_until(clock, target, sleep)
        assert t[0] >= target
    from concurrent.futures import Future

    rec = client.Record(clock)
    client.open_loop(lambda g: Future(), lambda i: i,
                     np.array([0.0, 0.0001, 0.0002, 0.01]), t[0], rec,
                     sleep=sleep)
    d, s, _ = rec.arrays()
    assert len(rec) == 4 and np.all(s >= d) and slept
