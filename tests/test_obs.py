"""Observability layer (DESIGN.md §15): the unified clock, span-tree
completeness over every terminal request path, registry thread-safety,
and the export formats (JSONL round-trip, Prometheus exposition text).
"""
import io
import json
import re
import threading
import time

import pytest

from repro import obs
from repro.core import generators as G
from repro.configs.obs import OBS_CONFIGS, ObsConfig
from repro.configs.service import AutotuneConfig, ServiceConfig
from repro.engine import AsyncChordalityEngine, ChordalityEngine, gather
from repro.obs.clock import FakeClock, reset_clock, set_clock
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NOOP_SPAN, Span


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Tracing off and the real clock back, no matter how a test exits —
    global obs state must never leak across tests."""
    yield
    obs.disable_tracing()
    reset_clock()


def _quiet_config(**kw):
    kw.setdefault("max_batch", 64)
    kw.setdefault("max_wait_ms", 60_000.0)
    return ServiceConfig(**kw)


def _request_roots(sink):
    return [s for s in sink.spans if s.name == "request"]


# ---------------------------------------------------------------------------
# The clock: swap, fake, and the PR 8 clock-mix regression.
# ---------------------------------------------------------------------------
def test_fake_clock_swap_and_reset():
    fake = FakeClock(start=500.0)
    prev = set_clock(fake)
    try:
        assert obs.clock.now() == 500.0
        fake.advance(2.5)
        assert obs.clock.now() == 502.5
        fake.set(600.0)
        assert obs.clock.now() == 600.0
    finally:
        set_clock(prev)
    t0 = obs.clock.now()
    assert t0 != 600.0 or obs.clock.now() >= t0  # real clock flows again


def test_default_clock_is_monotonic():
    reset_clock()
    a = obs.clock.now()
    b = obs.clock.now()
    assert b >= a


def test_deadlines_survive_perf_counter_divergence(monkeypatch):
    """The PR 8 bug class: the service measured time on two clocks
    (``time.monotonic`` at admission, ``time.perf_counter`` in stats),
    so a platform where they diverge stretched or shrank every deadline
    and queue-delay figure. With everything on ``repro.obs.clock``, an
    arbitrary perf_counter offset must change *nothing*: deadlined
    requests complete inside their generous budget instead of expiring
    on a 10^4-second phantom age, and queue delays stay sane."""
    monkeypatch.setattr(
        time, "perf_counter", lambda: time.monotonic() + 9_999.0)
    cfg = ServiceConfig(max_batch=8, max_wait_ms=1.0, backend="numpy_ref")
    with AsyncChordalityEngine(config=cfg) as svc:
        futs = [svc.submit(G.cycle(9), deadline_ms=60_000.0)
                for _ in range(6)]
        resps = gather(futs, timeout=60)
    assert [not r.verdict for r in resps] == [True] * 6
    assert svc.stats.n_expired == 0
    assert svc.stats.n_completed == 6
    # a clock mix would book the 9999 s offset as queue time
    assert svc.stats.p95_queue_ms < 60_000.0


def test_fake_clock_drives_deadline_expiry():
    """Deadline expiry runs on virtual time: advance the fake clock past
    a queued request's budget, wake the admission loop (Condition.wait
    sleeps *real* time — a waker submit is the wake signal), and the
    request expires without any wall-clock sleep near the deadline."""
    fake = FakeClock()
    set_clock(fake)
    svc = AsyncChordalityEngine(
        config=_quiet_config(), backend="numpy_ref")
    try:
        doomed = svc.submit(G.cycle(9), deadline_ms=50.0)
        fake.advance(1.0)                      # 1 virtual s >> 50 ms
        waker = svc.submit(G.clique(4), deadline_ms=3_600_000.0)
        deadline = time.monotonic() + 10
        while not doomed.cancelled() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert doomed.cancelled()
        assert svc.stats.n_expired == 1
        assert not waker.cancelled()           # its budget starts later
    finally:
        svc.shutdown(drain=False)


# ---------------------------------------------------------------------------
# Metrics registry: semantics + thread-safety hammer.
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help text")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    g = reg.gauge("g", "", labels=("n_pad",))
    g.set(7.0, n_pad=64)
    g.inc(1.0, n_pad=64)
    g.set(3.0, n_pad=128)
    assert g.value(n_pad=64) == 8.0
    assert g.value(n_pad=128) == 3.0
    h = reg.histogram("h_ms", "", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    snap = reg.snapshot()["h_ms"]["series"][0]
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(555.5)
    # cumulative bucket counts: <=1 holds 1, <=10 holds 2, <=100 holds 3
    assert list(snap["buckets"].values()) == [1, 2, 3]


def test_registry_rejects_kind_and_same_name_reuse():
    reg = MetricsRegistry()
    reg.counter("x_total", "")
    assert reg.counter("x_total", "") is reg.get("x_total")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x_total", "")


def test_counters_are_thread_safe_under_hammer():
    reg = MetricsRegistry()
    c = reg.counter("hammer_total", "")
    n_threads, per_thread = 8, 10_000

    def work():
        for _ in range(per_thread):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value() == n_threads * per_thread


def test_dispatch_and_sweep_counters_thread_safe_and_registry_backed():
    from repro.kernels import dispatch_counter
    from repro.recognition.sweeps import sweep_counter

    for counter in (dispatch_counter, sweep_counter):
        before = counter.count
        ts = [threading.Thread(
            target=lambda: [counter.tick() for _ in range(2_000)])
            for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert counter.delta(before) == 12_000
    # they publish into the global registry under their metric names
    names = set(obs.registry.snapshot())
    assert {"repro_dispatches_total", "repro_sweeps_total"} <= names


# ---------------------------------------------------------------------------
# Span mechanics: nesting, noop cheapness, manual stitching.
# ---------------------------------------------------------------------------
def test_spans_nest_by_thread_local_stack():
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    with obs.span("outer", a=1):
        with obs.span("inner"):
            pass
    obs.disable_tracing()
    (root,) = sink.spans
    assert root.name == "outer" and root.attrs["a"] == 1
    assert [c.name for c in root.children] == ["inner"]
    assert root.closed


def test_disabled_tracing_returns_noop_singleton():
    obs.disable_tracing()
    s = obs.span("anything", x=1)
    assert s is NOOP_SPAN
    with s as sp:
        sp.attrs["leak"] = True            # must not accumulate anywhere
        assert sp.child("c") is NOOP_SPAN
    assert NOOP_SPAN.attrs == {}           # fresh dict each read
    assert obs.get_tracer().start_span("manual") is None


def test_span_error_attr_on_exception():
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    obs.disable_tracing()
    (root,) = sink.spans
    assert root.closed and root.attrs["error"] == "RuntimeError"


def test_manual_children_partition_exactly():
    root = Span("request", t_start=10.0)
    a = root.child("queue", t=10.0)
    a.end(t=12.0)
    b = root.child("exec", t=12.0)
    b.end(t=15.0)
    root.end(t=15.0)
    assert root.closed
    parts = sum(c.duration_ms for c in root.children)
    assert parts == pytest.approx(root.duration_ms, abs=0.0)


# ---------------------------------------------------------------------------
# Trace completeness: every terminal request path closes its tree.
# ---------------------------------------------------------------------------
def _stage_sum_equals_wall(root):
    stages = {c.name: c for c in root.children}
    assert {"queue", "exec", "finalize"} <= set(stages)
    total = (stages["queue"].duration_ms + stages["exec"].duration_ms
             + stages["finalize"].duration_ms)
    assert total == pytest.approx(root.duration_ms, abs=1e-6)


@pytest.mark.parametrize("mode", ["verdict", "witness", "properties"])
def test_completed_request_trace_is_closed_and_partitions(mode):
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    kw = {"witness": {"want_witness": True},
          "properties": {"properties": ["proper_interval"]}}.get(mode, {})
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="jax_fast") as svc:
        resp = svc.submit(G.random_chordal(20, k=3, seed=0),
                          **kw).result(timeout=120)
    obs.disable_tracing()
    root = resp.trace
    assert root is not None and root.closed
    assert root.attrs["outcome"] == "completed"
    _stage_sum_equals_wall(root)
    unit = root.find("unit")
    assert unit is not None
    assert root.find("dispatch") is not None
    if mode == "witness":
        assert root.attrs["want_witness"]
        assert "witness" in unit.attrs["kind"]
    if mode == "properties":
        # submit normalizes the property set (chordal rides along)
        assert "proper_interval" in root.attrs["properties"]
        assert unit.attrs["kind"].startswith("recognition:")
    # the emitted sink copy is the same closed tree
    assert root in _request_roots(sink)


def test_router_sample_span_follows_unit_under_exec():
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="jax_fast") as svc:
        resp = svc.submit(G.random_chordal(20, k=3, seed=0)).result(
            timeout=120)
    obs.disable_tracing()
    root = resp.trace
    _stage_sum_equals_wall(root)
    exec_span = root.find("exec")
    names = [c.name for c in exec_span.children]
    assert names.count("unit") == 1 and names.count("router_sample") == 1
    unit = exec_span.find("unit")
    (sample,) = [c for c in exec_span.children if c.name == "router_sample"]
    assert sample.closed and sample.attrs["graphs"] == 1
    assert sample.children == []
    assert unit.t_end <= sample.t_start <= sample.t_end <= exec_span.t_end
    # unit still holds realize and dispatch, and nothing of the sample
    assert [c.name for c in unit.children
            if c.name in ("realize", "dispatch")] == ["realize", "dispatch"]
    for c in unit.children:
        assert unit.t_start <= c.t_start <= c.t_end <= unit.t_end
    assert unit.find("router_sample") is None


def test_cancelled_request_trace_closes_with_outcome():
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    svc = AsyncChordalityEngine(
        config=_quiet_config(), backend="numpy_ref")
    try:
        fut = svc.submit(G.cycle(9))
        assert fut.cancel()
    finally:
        svc.shutdown(drain=False)
    obs.disable_tracing()
    roots = _request_roots(sink)
    assert len(roots) == 1 and roots[0].closed
    assert roots[0].attrs["outcome"] == "cancelled"


def test_expired_request_trace_closes_with_outcome():
    fake = FakeClock()
    set_clock(fake)
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    svc = AsyncChordalityEngine(
        config=_quiet_config(), backend="numpy_ref")
    try:
        doomed = svc.submit(G.cycle(9), deadline_ms=50.0)
        fake.advance(1.0)
        svc.submit(G.clique(4), deadline_ms=3_600_000.0)  # waker
        deadline = time.monotonic() + 10
        while not doomed.cancelled() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert doomed.cancelled()
    finally:
        svc.shutdown(drain=False)
    obs.disable_tracing()
    outcomes = {r.attrs["outcome"] for r in _request_roots(sink)}
    assert "expired" in outcomes
    assert all(r.closed for r in _request_roots(sink))
    # expiry happened on virtual time: the expired root's wall is the
    # fake advance, not the real milliseconds the test took
    expired = next(r for r in _request_roots(sink)
                   if r.attrs["outcome"] == "expired")
    assert expired.duration_ms == pytest.approx(1_000.0)


def test_shed_request_trace_closes_with_outcome():
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    cfg = ServiceConfig(
        max_batch=16, max_wait_ms=60_000.0,
        autotune=AutotuneConfig(wait_max_ms=60_000.0,
                                interval_units=10**6))
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        svc._autotuner.observe_unit(16, 8, [1.0], 500.0)
        doomed = svc.submit(G.cycle(9), priority=0, deadline_ms=250.0)
        deadline = time.monotonic() + 10
        while svc.stats.n_shed < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert doomed.cancelled()
    finally:
        svc.shutdown(drain=False)
    obs.disable_tracing()
    roots = _request_roots(sink)
    assert any(r.attrs["outcome"] == "shed" for r in roots)
    assert all(r.closed for r in roots)


def test_sync_engine_traces_unit_trees():
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    eng = ChordalityEngine(backend="jax_fast", max_batch=4)
    eng.run([G.cycle(9), G.clique(9)])
    obs.disable_tracing()
    units = [s for s in sink.spans if s.name == "unit"]
    assert units and all(u.closed for u in units)
    names = {c.name for u in units for c in u.children}
    assert {"realize", "dispatch"} <= names


# ---------------------------------------------------------------------------
# Backend stages: pallas_peo splits its dispatch into pack / h2d / device /
# d2h (and host_finalize on certified units), synced only when tracing.
# ---------------------------------------------------------------------------
_STAGES = {"fused": ["pack", "h2d", "device", "d2h"],
           "fused_packed": ["pack", "h2d", "device", "d2h"],
           "verdict": ["h2d", "device", "d2h"],
           "fused_witness": ["pack", "h2d", "device", "d2h",
                             "host_finalize"]}


def _traced_units(graphs, pipeline, **run_kw):
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    eng = ChordalityEngine(backend="pallas_peo", max_batch=2,
                           pipeline=pipeline, interpret=True)
    res = eng.run(graphs, **run_kw)
    obs.disable_tracing()
    return res, [s for s in sink.spans if s.name == "unit"]


def _assert_stages_partition(unit):
    (dispatch,) = [c for c in unit.children if c.name == "dispatch"]
    stages = dispatch.children
    per_graph = _STAGES[unit.attrs["kind"]]
    sets = unit.attrs["batch"] if unit.attrs["kind"] == "verdict" else 1
    assert [s.name for s in stages] == per_graph * sets
    t = dispatch.t_start
    for s in stages:                       # in order, disjoint, inside
        assert s.closed and t <= s.t_start <= s.t_end
        t = s.t_end
    assert t <= dispatch.t_end
    # A realized unit is a bool array: pack views it, copying nothing.
    assert all(s.attrs["copied"] is False for s in stages
               if s.name == "pack")


@pytest.mark.parametrize("pipeline,n,kind", [
    ("fused", 9, "fused_packed"),
    ("fused", 70, "fused"),
    ("split", 9, "verdict"),
])
def test_stage_spans_partition_dispatch(pipeline, n, kind):
    graphs = [G.cycle(n), G.clique(n)]
    res, units = _traced_units(graphs, pipeline)
    assert list(res.verdicts) == [n <= 3, True]
    assert units and {u.attrs["kind"] for u in units} == {kind}
    for u in units:
        _assert_stages_partition(u)


def test_witness_stage_spans_end_with_host_finalize():
    res, units = _traced_units([G.cycle(9), G.random_chordal(12, k=3,
                                                             seed=0)],
                               "fused", witness=True)
    assert list(res.verdicts) == [False, True]
    assert units and {u.attrs["kind"] for u in units} == {"fused_witness"}
    for u in units:
        _assert_stages_partition(u)


@pytest.mark.parametrize("pipeline", ["fused", "split"])
def test_untraced_dispatch_adds_no_sync(monkeypatch, pipeline):
    import jax

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    eng = ChordalityEngine(backend="pallas_peo", max_batch=2,
                           pipeline=pipeline, interpret=True)
    graphs = [G.cycle(9), G.clique(9)]
    eng.run(graphs)
    assert calls == []                      # tracing off: no added sync
    obs.enable_tracing(obs.ListSink())
    eng.run(graphs)
    obs.disable_tracing()
    assert calls                            # the patch does see the syncs


# ---------------------------------------------------------------------------
# XLA compiles: counted by one jax.monitoring listener, not by the cache.
# ---------------------------------------------------------------------------
def _compile_counters():
    return (obs.registry.counter("repro_xla_compiles_total"),
            obs.registry.counter("repro_compile_seconds_total"))


def test_xla_compile_listener_counts_and_attaches_span():
    import jax
    import jax.numpy as jnp

    obs.install_compile_listener()
    obs.install_compile_listener()          # idempotent
    n_c, s_c = _compile_counters()
    x = jnp.arange(7)
    x.block_until_ready()
    n0, s0 = n_c.total(), s_c.total()
    sink = obs.ListSink()
    obs.enable_tracing(sink)

    def fresh_program_for_this_test(v):
        return v * 3 + 11

    f = jax.jit(fresh_program_for_this_test)
    with obs.span("outer"):
        f(x).block_until_ready()
    n1 = n_c.total()
    with obs.span("outer"):
        f(x).block_until_ready()            # cached call: no compile
    obs.disable_tracing()
    first, second = sink.spans
    compiles = [c for c in first.children if c.name == "xla_compile"]
    assert len(compiles) == n1 - n0 >= 1
    assert s_c.total() > s0
    assert any("fresh_program_for_this_test" in c.attrs["fun_name"]
               for c in compiles)
    for c in compiles:
        assert c.closed and not c.attrs["cache_load"]
        assert first.t_start <= c.t_start <= c.t_end <= first.t_end
    assert n_c.total() == n1
    assert not [c for c in second.children if c.name == "xla_compile"]


def test_persistent_cache_load_is_not_counted_as_compile():
    import jax.monitoring

    from repro.obs import compiles

    obs.install_compile_listener()
    n_c, s_c = _compile_counters()
    n0, s0 = n_c.total(), s_c.total()
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    with obs.span("outer"):
        jax.monitoring.record_event_duration_secs(
            compiles.CACHE_RETRIEVAL_EVENT, 0.001)
        jax.monitoring.record_event_duration_secs(
            compiles.BACKEND_COMPILE_EVENT, 0.002, fun_name="loaded")
    obs.disable_tracing()
    assert (n_c.total(), s_c.total()) == (n0, s0)
    (span,) = sink.spans[0].children
    assert span.name == "xla_compile" and span.attrs == {
        "fun_name": "loaded", "cache_load": True}


# ---------------------------------------------------------------------------
# Export: JSONL round-trip + Prometheus scraper grammar.
# ---------------------------------------------------------------------------
def test_span_dict_round_trip_is_identity():
    root = Span("request", {"n": 3}, t_start=1.0)
    c = root.child("queue", t=1.0)
    c.end(t=2.0)
    root.end(t=2.0)
    assert obs.span_from_dict(root.to_dict()).to_dict() == root.to_dict()


def test_jsonl_sink_round_trip_through_service():
    buf = io.StringIO()
    obs.enable_tracing(obs.JsonlSink(buf))
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        gather(svc.submit_many([G.cycle(9), G.clique(9)]), timeout=60)
    obs.disable_tracing()
    recs = obs.parse_jsonl(buf.getvalue())
    assert recs, "service burst wrote no JSONL records"
    spans = [obs.span_from_dict(r) for r in recs if r["type"] == "span"]
    roots = [s for s in spans if s.name == "request"]
    assert len(roots) == 2
    assert all(s.closed for s in spans)
    for r in roots:
        _stage_sum_equals_wall(r)
    # each line is independently valid JSON with a type tag
    for line in buf.getvalue().splitlines():
        assert json.loads(line)["type"] in ("span", "event")


def test_jsonl_sink_owns_path_and_appends(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    sink = obs.JsonlSink(path)
    obs.enable_tracing(sink)
    with obs.span("a"):
        pass
    obs.event("e", k=1)
    obs.disable_tracing()
    sink.close()
    recs = obs.parse_jsonl(open(path).read())
    assert [r["type"] for r in recs] == ["span", "event"]
    assert sink.n_written == 2


_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"          # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"  # optional label set
    r" (?:[0-9.eE+-]+|\+Inf|NaN)$")       # value


def test_prometheus_render_matches_scraper_grammar():
    # make sure at least one of each kind + a labeled histogram render
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        gather(svc.submit_many([G.cycle(9)]), timeout=60)
    obs.registry.gauge("test_render_gauge_bytes", "a labelled gauge",
                       labels=("n_pad",)).set(1024, n_pad=64)
    text = obs.render_prometheus()
    assert "repro_requests_total" in text
    assert "repro_queue_delay_ms_bucket" in text
    kinds = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            kinds[name] = kind
            continue
        if line.startswith("#"):
            continue
        assert _PROM_SAMPLE.match(line), f"unscrapeable line: {line!r}"
    assert kinds["repro_requests_total"] == "counter"
    assert kinds["repro_queue_delay_ms"] == "histogram"
    assert kinds["test_render_gauge_bytes"] == "gauge"


# ---------------------------------------------------------------------------
# Config + telemetry surfaces.
# ---------------------------------------------------------------------------
def test_obs_config_presets_and_validation():
    assert not OBS_CONFIGS["off"].trace
    assert OBS_CONFIGS["profile"].jax_annotations
    with pytest.raises(ValueError):
        ObsConfig(trace=False, trace_path="x.jsonl")
    obs.configure(ObsConfig(trace=True))
    assert obs.tracing_enabled()
    obs.configure(OBS_CONFIGS["off"])
    assert not obs.tracing_enabled()
    assert not obs.jax_annotations_enabled()


def test_engine_and_service_telemetry_shapes():
    eng = ChordalityEngine(backend="numpy_ref", max_batch=4)
    eng.run([G.cycle(9), G.clique(9)])
    tel = eng.telemetry()
    assert 0.0 <= tel["cache"]["hit_ratio"] <= 1.0
    assert "repro_dispatches_total" in tel["metrics"]
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        gather(svc.submit_many([G.cycle(9), G.clique(9)]), timeout=60)
        stel = svc.telemetry()
    assert stel["requests"]["completed"] == 2
    assert set(stel["stages"]) == {"queue_ms", "exec_ms"}
    assert sum(stel["backend_mix"].values()) == 2
    assert stel["units"]["executed"] >= 1


def test_profiling_bridge_is_nullcontext_when_disabled(monkeypatch):
    """Annotations ride on entered spans: none while disabled, one
    ``TraceAnnotation`` per entered span, with its attrs, once enabled."""
    from repro.obs import profiling

    made = []
    real = profiling.annotation
    monkeypatch.setattr(profiling, "annotation",
                        lambda name, attrs: made.append((name, dict(attrs)))
                        or real(name, attrs))
    obs.enable_tracing(obs.ListSink())
    obs.disable_jax_annotations()
    with obs.span("dispatch", backend="b"):
        pass                                # no jax import, no effect
    assert made == []
    obs.enable_jax_annotations()
    try:
        with obs.span("dispatch", backend="b"):
            with obs.span("h2d"):
                pass                        # real TraceAnnotation path
    finally:
        obs.disable_jax_annotations()
    assert made == [("dispatch", {"backend": "b"}), ("h2d", {})]


# ---------------------------------------------------------------------------
# Multi-lane executor (PR 10): exec spans carry the lane, every terminal
# path still closes its tree with n_lanes > 1.
# ---------------------------------------------------------------------------
def test_multilane_completed_traces_close_and_carry_lane():
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    cfg = ServiceConfig(max_batch=2, max_wait_ms=0.5, n_lanes=3)
    with AsyncChordalityEngine(config=cfg, backend="numpy_ref") as svc:
        futs = svc.submit_many(
            [G.cycle(9) for _ in range(10)] + [G.clique(5)])
        gather(futs, timeout=120)
    obs.disable_tracing()
    roots = _request_roots(sink)
    assert len(roots) == 11 and all(r.closed for r in roots)
    for r in roots:
        assert r.attrs["outcome"] == "completed"
        _stage_sum_equals_wall(r)
        ex = next(c for c in r.children if c.name == "exec")
        assert ex.attrs["lane"] in (0, 1, 2)


def test_multilane_cancelled_traces_close():
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    svc = AsyncChordalityEngine(
        config=_quiet_config(n_lanes=2), backend="numpy_ref")
    try:
        fut = svc.submit(G.cycle(9))
        assert fut.cancel()
    finally:
        svc.shutdown(drain=False)
    obs.disable_tracing()
    roots = _request_roots(sink)
    assert len(roots) == 1 and roots[0].closed
    assert roots[0].attrs["outcome"] == "cancelled"


def test_multilane_failed_unit_closes_traces():
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    cfg = ServiceConfig(max_batch=1, max_wait_ms=0.0, n_lanes=2)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        def boom(unit, graphs, device=None):
            raise RuntimeError("lane boom")

        svc.engine.execute_unit = boom
        futs = [svc.submit(G.cycle(9)) for _ in range(3)]
        for f in futs:
            with pytest.raises(RuntimeError, match="lane boom"):
                f.result(timeout=60)
    finally:
        svc.shutdown(drain=False)
    obs.disable_tracing()
    roots = _request_roots(sink)
    assert len(roots) == 3 and all(r.closed for r in roots)
    assert {r.attrs["outcome"] for r in roots} == {"failed"}


def test_exec_span_carries_the_device_it_ran_on():
    """``exec`` carries ``device``, the cache scope of the backend that
    ran the unit, next to ``lane``: ``host`` for numpy_ref, the default
    device's ``cpu:0`` for a jit backend on the one CPU device."""
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    cfg = ServiceConfig(max_batch=2, max_wait_ms=0.5, n_lanes=2)
    for backend, scope in (("numpy_ref", "host"), ("jax_fast", "cpu:0")):
        with AsyncChordalityEngine(config=cfg, backend=backend) as svc:
            gather(svc.submit_many([G.cycle(9), G.clique(5)]), timeout=120)
        roots = _request_roots(sink)
        assert len(roots) == 2
        for r in roots:
            ex = r.find("exec")
            assert ex.attrs["device"] == scope and ex.attrs["lane"] in (0, 1)
        sink.spans.clear()
    obs.disable_tracing()


def test_lane_steals_counter_counts_a_forced_steal():
    """Every unit lands on lane 0's queue and the first unit to run is
    held: whichever lane holds it, lane 1 takes exactly one of the two
    units from lane 0's queue, so ``repro_lane_steals_total{lane="1"}``
    moves by one and lane 0 steals nothing."""
    release, started = threading.Event(), threading.Event()
    first = threading.Lock()
    cfg = ServiceConfig(max_batch=1, max_wait_ms=0.0, n_lanes=2)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    before = [svc._m_steals.value(lane=k) for k in (0, 1)]
    orig = svc._execute

    def to_lane_0(au):
        with svc._lane_cv:
            svc._lane_queues[0].append(au)
            svc._lane_cv.notify_all()

    def gated(au, lane=0):
        if first.acquire(blocking=False):
            started.set()
            release.wait(timeout=60)
        return orig(au, lane)

    svc._dispatch_unit, svc._execute = to_lane_0, gated
    try:
        held = svc.submit(G.cycle(9))
        assert started.wait(timeout=30)
        assert svc.submit(G.clique(4)).result(timeout=60).verdict
        assert not held.done()
        release.set()
        assert held.result(timeout=60).verdict is False
        tel = svc.telemetry()
    finally:
        release.set()
        svc.shutdown()
    after = [svc._m_steals.value(lane=k) for k in (0, 1)]
    assert [a - b for a, b in zip(after, before)] == [0, 1]
    assert "repro_lane_steals_total" in tel["metrics"]
    assert re.search(r'^repro_lane_steals_total\{lane="1"\} [0-9.]+$',
                     obs.render_prometheus(), re.M)
