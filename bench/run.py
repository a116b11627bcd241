#!/usr/bin/env python3
"""Benchmark of the served chordality path on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process: builds the cell's
requests from the seed, starts ``AsyncChordalityEngine`` with the
configuration's service settings, warms every shape the traffic will use
from the persistent compile cache, then drives ``submit`` from the
client's side for ``--seconds``. Once the window has closed it waits for
every answer, reads the device's peak memory, shuts the service down and
checks a sample of the answers, drawn from the seed, against the plain
numpy reference in ``bench/reference.py``.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, read from host spans and a profiler
trace of the window), ``device`` and, traced, ``breakdown``; its last key,
``checks``, gives every number compared with its limit. The same checks
are the last lines of stderr. Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

#: Where runs keep what they write: the compile cache, the profiler's
#: trace and libtpu's logs, all inside the checkout at fixed paths.
RUN_DIR = ROOT / ".bench_run"
CACHE_DIR = RUN_DIR / "jax_cache"
#: Answers are waited for this long past the window's close.
ANSWER_GRACE_S = 60.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(**row) -> None:
    print(json.dumps(row, sort_keys=True), flush=True)


def prepare_env() -> None:
    """Fix the compile cache and libtpu's logs inside the checkout before
    JAX starts: a cache path is part of the cache key, so it never moves."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", str(RUN_DIR / "tpu_logs"))
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(1, src)


def devices_for(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


class GcPauses:
    """The garbage collector's collections, timed through ``gc.callbacks``:
    each stops every thread of the process, the service's included."""

    def __init__(self, clock):
        self.clock = clock
        self.started = None
        self.done = []                  # (generation, start, seconds)

    def __call__(self, phase, info):
        if phase == "start":
            self.started = self.clock()
        elif self.started is not None:
            self.done.append((info["generation"], self.started,
                              self.clock() - self.started))
            self.started = None

    def summary(self, lo: float, hi: float) -> dict:
        """Collections per generation, their total and the longest pause
        (ms, and its start in s from ``lo``) that began in [lo, hi]."""
        inside = [c for c in self.done if lo <= c[1] <= hi]
        longest = max(inside, key=lambda c: c[2], default=(0, lo, 0.0))
        return {"collections": [sum(c[0] == g for c in inside)
                                for g in range(3)],
                "gc_total_ms": sum(c[2] for c in inside) * 1e3,
                "gc_longest_ms": longest[2] * 1e3,
                "gc_longest_at_s": longest[1] - lo}


class TraceStop:
    """Stops the profiler once: after a delay, on a timer thread, or when
    called, whichever comes first."""

    def __init__(self, stop_trace):
        self.stop_trace = stop_trace
        self.lock = threading.Lock()
        self.stopped = False
        self.timer = None

    def after(self, seconds: float) -> None:
        self.timer = threading.Timer(seconds, self)
        self.timer.daemon = True
        self.timer.start()

    def __call__(self) -> None:
        with self.lock:
            if not self.stopped:
                self.stopped = True
                self.stop_trace()
        if self.timer is not None and self.timer is not \
                threading.current_thread():
            self.timer.cancel()
            self.timer.join()


def warm_sample(src, engine, cap: int):
    """Indices of requests that fill every (n_pad, batch) shape the
    traffic can reach: up to ``cap`` per padding bucket."""
    from repro.graphs.structure import bucket_npad

    want: dict = {}
    for i, n in enumerate(src.n_nodes):
        b = bucket_npad(max(int(n), 1), engine.buckets)
        got = want.setdefault(b, [])
        if len(got) < cap:
            got.append(i)
    return [i for idxs in want.values() for i in idxs]


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True) -> dict:
    """One run of ``cell``: returns the result object (see module doc)."""
    import numpy as np

    from bench import client, reference, traffic
    from bench import trace as trace_mod

    devs = devices_for(cell.chips, require_tpu)
    import jax

    from repro import obs
    from repro.configs.service import ServiceConfig
    from repro.engine import AsyncChordalityEngine

    src = traffic.source(cell.config, seed)
    scfg = ServiceConfig(**cell.config["service"])
    mix = cell.traffic
    svc = AsyncChordalityEngine(scfg)
    pauses = GcPauses(time.monotonic)
    try:
        t_warm = time.monotonic()
        cap = scfg.max_batch
        if mix["mode"] == "closed":      # no unit holds more than are sent
            cap = min(cap, int(mix["outstanding"]))
        svc.warmup([src.graph(i) for i in warm_sample(src, svc.engine, cap)])
        warm_s = time.monotonic() - t_warm
        keys_before = set(svc.engine.cache.keys())
        sink = None
        log_dir = str(RUN_DIR / "trace")
        if trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            sink = obs.ListSink()
            obs.enable_tracing(sink)
            obs.enable_jax_annotations()
            jax.profiler.start_trace(
                log_dir, profiler_options=_profile_options())
        rec = client.Record(time.monotonic)
        gc.callbacks.append(pauses)
        # The profiler traces the whole window, or its first trace_s
        # seconds where the configuration sets ``trace_seconds``.
        trace_s = min(seconds, float(cell.config.get("trace_seconds",
                                                     seconds)))
        stop = TraceStop(jax.profiler.stop_trace) if trace else None
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.monotonic()      # the marker's start is the window's
        setup_s = t0 - T_START
        t_end = t0 + seconds
        if stop is not None and trace_s < seconds:
            stop.after(trace_s)
        if mix["mode"] == "closed":
            client.closed_loop(svc.submit, src.graph,
                               int(mix["outstanding"]), t0, t_end, rec)
        elif mix["mode"] == "poisson":
            client.open_loop(svc.submit, src.graph,
                             traffic.poisson_due(float(mix["rate"]),
                                                 seconds, seed), t0, rec)
        else:
            raise spec.SpecError(f"unknown traffic mode {mix['mode']!r}")
        client.sleep_until(time.monotonic, t_end)
        rec.wait(t_end + ANSWER_GRACE_S)
        t_drained = time.monotonic()
        tr = None
        if trace:
            stop()
            obs.disable_tracing()
            obs.disable_jax_annotations()
            tr = trace_mod.load(log_dir)
            with open(RUN_DIR / "spans.jsonl", "w") as f:
                f.write(json.dumps({"window": [t0, t_end]}) + "\n")
                for root in sink.spans:
                    f.write(json.dumps(root.to_dict(), default=str) + "\n")
        compiled = sorted(set(svc.engine.cache.keys()) - keys_before)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
    finally:
        if pauses in gc.callbacks:
            gc.callbacks.remove(pauses)
        svc.shutdown(drain=False)

    due, sent, done = rec.arrays()
    outcomes = [rec.outcome(i) for i in range(len(rec))]
    ok = np.array([err is None for _, err in outcomes], dtype=bool)
    failed = int((~ok).sum())
    late50, late95 = client.lateness_ms(rec)
    log(phase="setup", setup_s=setup_s, warmup_s=warm_s,
        compile_cache=str(CACHE_DIR), seed=seed)
    answered = np.where(ok, done, t_end + ANSWER_GRACE_S) - due
    log(phase="generator", sent=len(rec), lateness_p50_ms=late50,
        latency_p95_ms=float(np.percentile(answered, 95)) * 1e3
        if len(rec) else 0.0,
        lateness_p95_ms=late95, drain_s=t_drained - t_end,
        repeat_share=src.repeat_share(len(rec)),
        distinct_requests=src.count,
        per_second=client.per_second(rec, t0, seconds))
    log(phase="gc", **pauses.summary(t0, t_end))
    if compiled:
        log(phase="compiled_in_window", keys=[list(map(str, k))
                                              for k in compiled])

    # Correctness: a seeded sample of every answer due in the window.
    t_check = time.monotonic()
    idx = traffic.sample(len(rec), int(cell.config["check_sample"]), seed)
    want = reference.verdicts([src.payload(int(i)) for i in idx])
    got = [outcomes[int(i)][0] for i in idx]
    wrong = int(sum(g is not None and g != w for g, w in zip(got, want)))
    checks = {
        "wrong_verdicts": [wrong, 0],
        "unanswered_or_failed": [failed, 0],
        "compiles_in_window": [len(compiled), 0],
    }
    correct = all(v <= lim for v, lim in checks.values())
    log(phase="check", sampled=len(idx), chordal_share=float(np.mean(want))
        if len(idx) else 0.0, wrong=wrong,
        check_s=time.monotonic() - t_check)

    run = types.SimpleNamespace(
        cell=cell, seconds=seconds, t0=t0, t_end=t_end, setup_s=setup_s,
        due=due, sent=sent, done=done, ok=ok,
        deadline=t_end + ANSWER_GRACE_S, trace_s=trace_s,
        trace_end=t0 + trace_s if trace_s < seconds else None,
        spans=list(sink.spans) if sink is not None else [],
        dev=None, peaks=None)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        run.peaks = spec.peaks_for(devs[0].device_kind, cell.dirs) \
            if require_tpu else None
        run.dev = reduce_trace(tr, cell, run, len(devs))
        if run.dev is None and require_tpu:
            raise RuntimeError("the trace holds no device plane")
    if run.dev is not None:
        device["busy_s"] = run.dev["busy_s"]
        device["window_s"] = run.dev["window_s"]
        breakdown = {"device_ops": run.dev["top_ops"],
                     "idle_gaps": run.dev["gaps"]}
        for name, kern in cell.kernels.items():
            log(phase="kernel", kernel=name, **run.dev["kernels"][name])
    metrics = {}
    for name, (entry, read) in cell.metrics(trace).items():
        value = read(run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": entry["unit"]}
    result = {"correct": bool(correct), "attempted": len(rec),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def reduce_trace(tr: dict, cell: spec.Cell, run, chips: int) -> dict:
    """Device busy share, kernel time and labelled idle gaps over the
    traced window of trace ``tr``: ``run.trace_s`` seconds from the start
    of the ``bench.window`` marker.

    Where the profiler ran on past the window until every answer was in,
    kernel time and the units it is set against both cover the whole run.
    Where it stopped early (``run.trace_end``), both are cut at the end of
    the last ``dispatch`` span that ended before it stopped: units by
    their ``dispatch`` end, kernel events by their own end."""
    from bench import spans, trace as T

    mark = T.find_host_event(tr, "bench.window")
    if mark is None:
        raise RuntimeError("the trace holds no bench.window annotation")
    win = (mark[0], mark[0] + run.trace_s * 1e9)
    offset = mark[0] - run.t0 * 1e9
    planes = T.device_planes(tr)[:chips]
    if not planes:
        return None
    busy = [T.busy(p, win) for p in planes]
    busy_s = sum(e - s for b in busy for s, e in b) * 1e-9 / len(planes)
    host = spans.host_intervals(run.spans, offset)
    gap_iv = list(T.gaps(busy[0], win))
    cut = None
    if run.trace_end is not None:
        last = spans.last_dispatch_end(run.spans, run.trace_end)
        cut = win[0] if last is None else last * 1e9 + offset
    done = spans.units(run.spans, until=run.trace_end)
    kernels = {}
    for name, kern in cell.kernels.items():
        secs, calls = T.kernel_seconds(planes, kern.LINE, kern.PATTERN,
                                       until=cut)
        units_k = [u for u in done if u[2] in kern.KINDS]
        kernels[name] = {
            "seconds": secs, "calls": calls, "units": len(units_k),
            "graphs": sum(u[3] for u in units_k),
            "bytes": sum(kern.bytes_moved(u[0], u[1]) for u in units_k),
            "vpu_elem_ops": sum(kern.vpu_ops(u[0], u[1]) for u in units_k),
        }
    return {"busy_s": busy_s, "window_s": (win[1] - win[0]) * 1e-9,
            "chips": len(planes), "kernels": kernels,
            "top_ops": T.top_ops(planes, win),
            "gaps": T.label_gaps(gap_iv, host)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program is not under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    try:
        cell = spec.load_cell(spec.load_bench(ROOT), args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    prepare_env()
    from repro.engine.persistent_cache import enable_persistent_cache

    enable_persistent_cache()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    for name, (value, limit) in result["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
