"""Async serving layer: queue bounds, micro-batching, cancellation, drain
semantics, stats accounting, and agreement with the synchronous session."""
import threading
import time
from concurrent.futures import CancelledError, Future

import numpy as np
import pytest

from repro.core import generators as G
from repro.configs.service import (
    SERVICE_CONFIGS,
    AutotuneConfig,
    ServiceConfig,
    service_config,
)
from repro.engine import (
    AsyncChordalityEngine,
    ChordalityEngine,
    QueueFullError,
    ServiceClosedError,
    gather,
    unit_for_chunk,
)
from repro.engine.service import ServiceStats, _BucketQueue, _Request

# Small n keeps every request in the 16/32 buckets: few jit shapes, fast.
def _stream():
    return [
        G.cycle(9), G.clique(9), G.random_chordal(21, k=3, seed=0),
        G.sparse_random(24, avg_degree=5, seed=1), G.cycle(4),
        G.random_tree(18, seed=2), G.cycle(11), G.clique(5),
    ]


def _quiet_config(**kw):
    """A config whose buckets never drain on their own (for queue tests):
    huge wait + batch, so the test controls draining via flush/shutdown."""
    kw.setdefault("max_batch", 64)
    kw.setdefault("max_wait_ms", 60_000.0)
    return ServiceConfig(**kw)


@pytest.fixture(scope="module")
def sync_verdicts():
    return ChordalityEngine(
        backend="numpy_ref", max_batch=8).run(_stream()).verdicts


# ---------------------------------------------------------------------------
# Core contract: same verdicts as the synchronous session, in order.
# ---------------------------------------------------------------------------
def test_async_agrees_with_sync_session(sync_verdicts):
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=8, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        resps = gather(svc.submit_many(_stream()), timeout=60)
    got = np.array([r.verdict for r in resps])
    np.testing.assert_array_equal(got, sync_verdicts)


def test_auto_backend_is_the_default_serving_path(sync_verdicts):
    svc = AsyncChordalityEngine(
        config=ServiceConfig(max_batch=8, max_wait_ms=1.0))
    assert svc.engine.router is not None           # config default: "auto"
    with svc:
        resps = gather(svc.submit_many(_stream()), timeout=120)
    got = np.array([r.verdict for r in resps])
    np.testing.assert_array_equal(got, sync_verdicts)
    served = set(svc.stats.backend_histogram)
    assert served <= set(svc.engine.router.candidates)


def test_submit_accepts_dense_adjacency(sync_verdicts):
    adjs = [g.with_dense().adj for g in _stream()]
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=8, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        resps = gather(svc.submit_many(adjs), timeout=60)
    got = np.array([r.verdict for r in resps])
    np.testing.assert_array_equal(got, sync_verdicts)


def test_response_metadata_names_unit_shape():
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        r = svc.submit(G.cycle(9)).result(timeout=60)
    assert r.backend == "numpy_ref"
    assert r.n_pad == 16                 # 9 -> bucket 16
    assert 1 <= r.occupancy <= r.batch <= 4
    assert r.queue_ms >= 0 and r.exec_ms >= 0
    assert r.certificate is None         # not requested


def test_want_certificate_attaches_witness():
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        good = svc.submit(G.clique(6), want_certificate=True).result(60)
        bad = svc.submit(G.cycle(12), want_certificate=True).result(60)
    assert good.certificate.chordal and good.verdict
    assert not bad.certificate.chordal and not bad.verdict
    assert bad.certificate.n_violations > 0


# ---------------------------------------------------------------------------
# Micro-batching: a full bucket drains without waiting out the window.
# ---------------------------------------------------------------------------
def test_full_bucket_drains_before_wait_window():
    cfg = _quiet_config(max_batch=4)     # wait=60s: only fills may drain
    with AsyncChordalityEngine(config=cfg, backend="numpy_ref") as svc:
        futs = svc.submit_many([G.cycle(9)] * 4)   # exactly one full bucket
        resps = gather(futs, timeout=30)           # must NOT take 60s
    assert [r.verdict for r in resps] == [False] * 4
    assert svc.stats.drain_reasons.get("full", 0) >= 1
    assert resps[0].occupancy == 4


def test_partial_bucket_drains_on_timeout():
    cfg = ServiceConfig(max_batch=64, max_wait_ms=50.0)
    with AsyncChordalityEngine(config=cfg, backend="numpy_ref") as svc:
        fut = svc.submit(G.cycle(9))               # alone in its bucket
        r = fut.result(timeout=30)
    assert r.occupancy == 1
    assert svc.stats.drain_reasons.get("timeout", 0) >= 1


def test_requests_batch_by_bucket_not_arrival_order(sync_verdicts):
    # Mixed sizes land in different n_pad buckets; verdicts still come
    # back aligned to submission order.
    graphs = _stream()
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=8, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        resps = gather(svc.submit_many(graphs), timeout=60)
    pads = {r.n_pad for r in resps}
    assert len(pads) > 1                  # really used multiple buckets
    np.testing.assert_array_equal(
        np.array([r.verdict for r in resps]), sync_verdicts)


# ---------------------------------------------------------------------------
# Bounded queue + admission control.
# ---------------------------------------------------------------------------
def test_bounded_queue_rejects_beyond_max_queue():
    cfg = _quiet_config(max_queue=3)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        futs = [svc.submit(G.cycle(5)) for _ in range(3)]
        with pytest.raises(QueueFullError):
            svc.submit(G.cycle(5))
        assert svc.stats.n_rejected == 1
        svc.flush(timeout=60)
        assert all(f.result(1).verdict is False for f in futs)
    finally:
        svc.shutdown()


def test_submit_timeout_waits_for_space():
    cfg = _quiet_config(max_queue=1)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        svc.submit(G.cycle(5))
        # A flusher thread frees the slot while submit blocks on it.
        t = threading.Thread(target=lambda: svc.flush(timeout=60))
        t.start()
        fut = svc.submit(G.cycle(7), timeout=30)
        t.join()
        svc.flush(timeout=60)
        assert fut.result(1).verdict is False
    finally:
        svc.shutdown()


def test_submit_timeout_expires_with_full_queue():
    cfg = _quiet_config(max_queue=1)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        svc.submit(G.cycle(5))
        t0 = time.perf_counter()
        with pytest.raises(QueueFullError):
            svc.submit(G.cycle(7), timeout=0.05)
        assert time.perf_counter() - t0 < 10
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# Cancellation.
# ---------------------------------------------------------------------------
def test_cancel_before_drain_skips_request():
    cfg = _quiet_config()
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        keep = svc.submit(G.cycle(9))
        drop = svc.submit(G.cycle(9))
        assert drop.cancel()
        svc.flush(timeout=60)
        assert keep.result(1).verdict is False
        assert drop.cancelled()
        assert svc.stats.n_cancelled == 1
        # The cancelled request never occupied a unit slot.
        assert svc.stats.occupancy_histogram == {1: 1}
    finally:
        svc.shutdown()


def test_cancel_after_execution_started_is_refused():
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=1, max_wait_ms=0.0),
            backend="numpy_ref") as svc:
        fut = svc.submit(G.cycle(9))
        fut.result(timeout=60)           # already resolved
        assert not fut.cancel()


def test_cancel_after_drain_does_not_count_in_occupancy():
    # Cancel while the unit sits between admission and execution: the
    # response's occupancy and the histogram must count live slots only.
    # A batch=1 unit ahead of the pair keeps the executor busy long
    # enough for a deterministic-ish window; retry if timing loses.
    cfg = ServiceConfig(max_batch=2, max_wait_ms=0.0)
    for _ in range(5):
        with AsyncChordalityEngine(config=cfg, backend="numpy_ref") as svc:
            pair = svc.submit_many([G.cycle(9), G.clique(9)])
            cancelled = pair[1].cancel()
            resp = pair[0].result(timeout=60)
        if cancelled:
            assert resp.occupancy == 1
            assert svc.stats.occupancy_histogram.get(2, 0) == 0
            assert svc.stats.n_cancelled == 1
            assert sum(k * v
                       for k, v in svc.stats.occupancy_histogram.items()) \
                == svc.stats.n_completed
            return
    pytest.skip("cancellation window never hit (executor too fast)")


def test_failing_certificate_fails_only_its_future():
    # A router whose candidates cannot produce certificates: the unit's
    # verdicts still resolve; only the want_certificate future gets the
    # exception — and the executor thread survives for later requests.
    from repro.engine import Router
    from repro.engine.router import BackendCost

    router = Router(cost_model={"sharded": BackendCost()},
                    candidates=("sharded",))
    cfg = ServiceConfig(max_batch=2, max_wait_ms=5.0)
    with AsyncChordalityEngine(config=cfg, router=router) as svc:
        plain = svc.submit(G.cycle(9))
        witness = svc.submit(G.cycle(9), want_certificate=True)
        assert plain.result(timeout=60).verdict is False
        with pytest.raises(ValueError, match="certificate"):
            witness.result(timeout=60)
        assert svc.stats.n_failed == 1
        # service still alive after the failure
        assert svc.submit(G.clique(5)).result(timeout=60).verdict is True


def test_routing_failure_fails_requests_not_the_service():
    # A router that cannot route at all (no capable candidate for the
    # plain batch) must fail the drained requests' futures and keep
    # admission alive.
    from repro.engine import Router
    from repro.engine.router import BackendCost

    class ExplodingRouter(Router):
        def annotate(self, plan, graphs):
            raise RuntimeError("router exploded")

    cfg = ServiceConfig(max_batch=2, max_wait_ms=0.0)
    with AsyncChordalityEngine(
            config=cfg,
            router=ExplodingRouter(
                cost_model={"numpy_ref": BackendCost()},
                candidates=("numpy_ref",))) as svc:
        fut = svc.submit(G.cycle(9))
        with pytest.raises(RuntimeError, match="router exploded"):
            fut.result(timeout=60)
        assert svc.stats.n_failed == 1
        assert svc.backlog == 0          # backlog accounting intact


# ---------------------------------------------------------------------------
# Drain / shutdown.
# ---------------------------------------------------------------------------
def test_flush_force_drains_partial_buckets():
    cfg = _quiet_config()                # nothing drains on its own
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        futs = svc.submit_many([G.cycle(9), G.clique(9)])
        t0 = time.perf_counter()
        svc.flush(timeout=60)
        assert time.perf_counter() - t0 < 50     # not the 60s window
        assert [f.result(1).verdict for f in futs] == [False, True]
        assert svc.backlog == 0
        assert svc.stats.drain_reasons.get("forced", 0) >= 1
    finally:
        svc.shutdown()


def test_flush_restores_windowed_batching():
    # After flush() returns, the force-drain flag must be down again:
    # the next lone request waits out its window (reason "timeout"),
    # it is not force-drained at occupancy 1.
    cfg = ServiceConfig(max_batch=64, max_wait_ms=100.0)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        svc.submit_many([G.cycle(9), G.clique(20)])   # two buckets
        svc.flush(timeout=60)
        assert svc._force_drain is False
        forced0 = svc.stats.drain_reasons.get("forced", 0)
        svc.submit(G.cycle(9)).result(timeout=60)
        assert svc.stats.drain_reasons.get("forced", 0) == forced0
        assert svc.stats.drain_reasons.get("timeout", 0) >= 1
    finally:
        svc.shutdown()


def test_shutdown_drain_resolves_everything():
    cfg = _quiet_config()
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    futs = svc.submit_many(_stream())
    svc.shutdown(drain=True)
    assert all(f.done() for f in futs)
    assert svc.stats.n_completed == len(futs)
    with pytest.raises(ServiceClosedError):
        svc.submit(G.cycle(5))


def test_shutdown_without_drain_cancels_pending():
    cfg = _quiet_config()
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    futs = svc.submit_many([G.cycle(9), G.cycle(9)])
    svc.shutdown(drain=False)
    assert all(f.cancelled() for f in futs)
    assert svc.stats.n_cancelled == 2


def test_shutdown_is_idempotent():
    svc = AsyncChordalityEngine(
        config=ServiceConfig(max_batch=2, max_wait_ms=1.0),
        backend="numpy_ref")
    svc.shutdown()
    svc.shutdown()


def test_context_manager_drains_on_exit():
    with AsyncChordalityEngine(
            config=_quiet_config(), backend="numpy_ref") as svc:
        fut = svc.submit(G.clique(7))
    assert fut.result(1).verdict is True


# ---------------------------------------------------------------------------
# Stats accounting.
# ---------------------------------------------------------------------------
def test_stats_account_for_every_request():
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        gather(svc.submit_many(_stream()), timeout=60)
    s = svc.stats
    assert s.n_submitted == len(_stream())
    assert s.n_completed == len(_stream())
    assert sum(s.backend_histogram.values()) == s.n_completed
    assert sum(k * v for k, v in s.occupancy_histogram.items()) \
        == s.n_completed
    assert sum(s.occupancy_histogram.values()) == s.n_units
    assert len(s.queue_delays_ms) == s.n_completed
    assert len(s.exec_latencies_ms) == s.n_units
    assert s.p50_queue_ms >= 0 and s.p95_queue_ms >= s.p50_queue_ms
    assert 1.0 <= s.mean_occupancy <= 4.0


def test_warmup_covers_partial_occupancy_shapes():
    # After warmup(sample), serving that sample must compile nothing more
    # no matter how occupancy lands — singles, partial and full batches.
    cfg = ServiceConfig(max_batch=4, max_wait_ms=1.0)
    sample = _stream()
    with AsyncChordalityEngine(config=cfg, backend="jax_fast") as svc:
        svc.warmup(sample)
        misses0 = svc.engine.cache.misses
        for g in sample[:3]:                      # singles
            svc.submit(g).result(timeout=60)
        gather(svc.submit_many(sample), timeout=60)   # batched
        assert svc.engine.cache.misses == misses0


def test_service_shares_compile_cache_across_requests():
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=1, max_wait_ms=0.0),
            backend="numpy_ref") as svc:
        svc.submit(G.cycle(9)).result(60)
        misses0 = svc.engine.cache.misses
        svc.submit(G.cycle(10)).result(60)   # same (16, 1) shape
        assert svc.engine.cache.misses == misses0
        assert svc.engine.cache.hits >= 1


# ---------------------------------------------------------------------------
# Concurrent submitters.
# ---------------------------------------------------------------------------
def test_concurrent_submitters_get_their_own_answers():
    # 4 threads interleave chordal/non-chordal submissions; every future
    # must carry the verdict for *its* graph, not a neighbor's.
    cfg = ServiceConfig(max_batch=8, max_wait_ms=2.0, max_queue=256)
    with AsyncChordalityEngine(config=cfg, backend="numpy_ref") as svc:
        results = {}

        def worker(tid):
            futs = []
            for j in range(8):
                g = G.cycle(8 + tid) if j % 2 else G.clique(6 + tid)
                futs.append((j % 2, svc.submit(g)))
            results[tid] = [
                (is_cycle, f.result(timeout=120))
                for is_cycle, f in futs]

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for tid, pairs in results.items():
        for is_cycle, resp in pairs:
            assert resp.verdict == (not is_cycle)
    assert svc.stats.n_completed == 32


# ---------------------------------------------------------------------------
# Config + construction validation.
# ---------------------------------------------------------------------------
def test_service_config_presets_and_validation():
    assert service_config("default") is SERVICE_CONFIGS["default"]
    assert service_config("smoke").max_batch == 8
    with pytest.raises(KeyError, match="unknown service config"):
        service_config("nope")
    with pytest.raises(ValueError):
        ServiceConfig(max_queue=0)
    with pytest.raises(ValueError):
        ServiceConfig(max_batch=0)
    with pytest.raises(ValueError):
        ServiceConfig(max_wait_ms=-1.0)


def test_injected_engine_must_match_config_batch():
    eng = ChordalityEngine(backend="numpy_ref", max_batch=8)
    with pytest.raises(ValueError, match="max_batch"):
        AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4), engine=eng)
    with pytest.raises(ValueError, match="not both"):
        AsyncChordalityEngine(
            config=ServiceConfig(max_batch=8), engine=eng,
            backend="numpy_ref")
    svc = AsyncChordalityEngine(config=ServiceConfig(max_batch=8),
                                engine=eng)
    try:
        assert svc.engine is eng
    finally:
        svc.shutdown()


def test_unit_for_chunk_contract():
    u = unit_for_chunk(32, 3, max_batch=8)
    assert u.n_pad == 32 and u.batch == 4 and u.indices == (0, 1, 2)
    with pytest.raises(ValueError, match="count"):
        unit_for_chunk(32, 0, max_batch=8)
    with pytest.raises(ValueError, match="exceeds"):
        unit_for_chunk(32, 9, max_batch=8)


# ---------------------------------------------------------------------------
# Witness responses: batched certificates through the serving path.
# ---------------------------------------------------------------------------
def test_want_witness_attaches_checkable_witness():
    from repro.witness import verify_witness

    chordal_g, cyclic_g = G.clique(6), G.cycle(12)
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        good = svc.submit(chordal_g, want_witness=True).result(60)
        bad = svc.submit(cyclic_g, want_witness=True).result(60)
        plain = svc.submit(chordal_g).result(60)
    assert good.witness.chordal and good.verdict
    assert good.witness.treewidth == 5 and good.witness.n_colors == 6
    assert verify_witness(chordal_g.adj, good.witness) is None
    assert not bad.witness.chordal and not bad.verdict
    assert verify_witness(cyclic_g.adj, bad.witness) is None
    assert plain.witness is None            # witness is opt-in


def test_witness_and_plain_requests_share_a_unit():
    """One want_witness request upgrades its whole unit; plain unit-mates
    still get plain responses (witness=None) with identical verdicts."""
    with AsyncChordalityEngine(
            config=_quiet_config(max_batch=4),
            backend="numpy_ref") as svc:
        futs = [svc.submit(G.cycle(9), want_witness=(i == 1))
                for i in range(3)]
        svc.flush()
        resps = gather(futs, timeout=60)
    assert [r.witness is not None for r in resps] == [False, True, False]
    assert all(not r.verdict for r in resps)
    # all three rode the same drained unit
    assert len({(r.n_pad, r.batch, r.occupancy) for r in resps}) == 1


# ---------------------------------------------------------------------------
# Per-request deadlines: queued-too-long requests drop, futures cancel.
# ---------------------------------------------------------------------------
def test_expired_requests_are_dropped_and_counted():
    svc = AsyncChordalityEngine(
        config=_quiet_config(deadline_ms=25.0), backend="numpy_ref")
    try:
        futs = [svc.submit(G.cycle(9)) for _ in range(4)]
        deadline = time.monotonic() + 10
        while svc.backlog and time.monotonic() < deadline:
            time.sleep(0.01)
        assert all(f.cancelled() for f in futs)
        assert svc.stats.n_expired == 4
        assert svc.backlog == 0
    finally:
        svc.shutdown()


def test_per_request_deadline_overrides_config():
    svc = AsyncChordalityEngine(
        config=_quiet_config(deadline_ms=25.0), backend="numpy_ref")
    try:
        doomed = svc.submit(G.cycle(9))
        survivor = svc.submit(G.clique(5), deadline_ms=120_000.0)
        deadline = time.monotonic() + 10
        while not doomed.cancelled() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert doomed.cancelled()
        svc.flush()
        assert survivor.result(60).verdict      # clique: chordal
        assert svc.stats.n_expired == 1
    finally:
        svc.shutdown()


def test_deadline_only_applies_while_queued():
    """A drained request executes even if its deadline passes mid-flight."""
    cfg = ServiceConfig(max_batch=1, max_wait_ms=0.0, deadline_ms=3_000.0,
                        backend="numpy_ref")
    with AsyncChordalityEngine(config=cfg) as svc:
        resps = gather(svc.submit_many(_stream()), timeout=60)
    assert len(resps) == len(_stream())
    assert svc.stats.n_expired == 0


def test_deadline_validation():
    with pytest.raises(ValueError, match="deadline_ms"):
        ServiceConfig(deadline_ms=0.0)
    with AsyncChordalityEngine(
            config=_quiet_config(), backend="numpy_ref") as svc:
        with pytest.raises(ValueError, match="deadline_ms"):
            svc.submit(G.cycle(4), deadline_ms=-1.0)


# ---------------------------------------------------------------------------
# asyncio adapter: thread-based futures awaited from an event loop.
# ---------------------------------------------------------------------------
def test_asubmit_resolves_on_the_event_loop(sync_verdicts):
    import asyncio

    async def drive(svc):
        futs = [svc.asubmit(g) for g in _stream()]
        return await asyncio.gather(*futs)

    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=8, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        resps = asyncio.run(drive(svc))
    got = np.array([r.verdict for r in resps])
    np.testing.assert_array_equal(got, sync_verdicts)


def test_asubmit_carries_witness_and_deadline_kwargs():
    import asyncio

    async def drive(svc):
        return await svc.asubmit(
            G.clique(6), want_witness=True, deadline_ms=60_000.0)

    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="numpy_ref") as svc:
        resp = asyncio.run(drive(svc))
    assert resp.verdict and resp.witness.chordal
    assert resp.witness.treewidth == 5


# ---------------------------------------------------------------------------
# White-box injection: craft a request and admit it while the caller holds
# the service lock. Public submit stamps t_submit before taking the lock,
# so it cannot place requests into a specific pre-pass queue state — the
# regression tests below need exactly that.
# ---------------------------------------------------------------------------
def _inject_locked(svc, graph, deadline_s=None, priority=None):
    now = time.perf_counter()
    req = _Request(
        graph=graph, future=Future(), t_submit=now,
        want_certificate=False,
        priority=svc.config.default_priority if priority is None
        else priority,
        deadline=None if deadline_s is None else now + deadline_s)
    svc._admit_locked(req)
    return req.future


# ---------------------------------------------------------------------------
# Regression (ISSUE 8 bugfix 1): a request that expired between the
# admission sweep and its bucket's drain must release its slot at drain
# time — never ride into a unit as dead weight.
# ---------------------------------------------------------------------------
def test_expired_requests_release_slots_at_drain():
    cfg = _quiet_config(max_batch=2)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        executed_pads = []
        orig_route = svc.engine.route_unit

        def slow_route(unit, graphs):
            # Stall the pass while it routes the *first* bucket: the
            # second bucket's deadlines lapse between the sweep and its
            # own drain — exactly the stale-clock window.
            if unit.n_pad == 32:
                time.sleep(0.4)
            return orig_route(unit, graphs)

        orig_exec = svc.engine.execute_unit

        def spy_exec(unit, graphs, device=None):
            executed_pads.append(unit.n_pad)
            return orig_exec(unit, graphs, device)

        svc.engine.route_unit = slow_route
        svc.engine.execute_unit = spy_exec

        # Both buckets fill inside one lock hold, so one admission pass
        # sweeps (nothing expired yet), then drains bucket 32 (slow),
        # then drains bucket 64 — after its requests' 150 ms deadlines.
        with svc._lock:
            alive = [_inject_locked(svc, G.cycle(20)) for _ in range(2)]
            dead = [_inject_locked(svc, G.cycle(40), deadline_s=0.15)
                    for _ in range(4)]
            svc._work_cv.notify_all()
        assert all(f.result(timeout=60).verdict is False for f in alive)
        deadline = time.monotonic() + 10
        while svc.backlog and time.monotonic() < deadline:
            time.sleep(0.01)
        assert all(f.cancelled() for f in dead)
        assert svc.stats.n_expired == 4
        assert svc.backlog == 0
        # the expired bucket never became a unit, partially dead or not
        assert executed_pads == [32]
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# Regression (ISSUE 8 bugfix 2): stats percentiles are degenerate-safe and
# read-only; the sample buffers are bounded windows.
# ---------------------------------------------------------------------------
def test_stats_percentiles_degenerate_and_pure():
    s = ServiceStats()
    assert s.p50_queue_ms == 0.0 and s.p95_queue_ms == 0.0
    assert s.p50_exec_ms == 0.0
    s.record_queue_delay(5.0)
    assert s.p50_queue_ms == 5.0 and s.p95_queue_ms == 5.0
    s.record_queue_delay(9.0)
    s.record_queue_delay(1.0)
    before = list(s.queue_delays_ms)
    assert s.p95_queue_ms >= s.p50_queue_ms > 0.0
    # reading percentiles must not sort or mutate the buffer
    assert s.queue_delays_ms == before == [5.0, 9.0, 1.0]


def test_stats_sample_buffers_are_bounded_windows():
    s = ServiceStats(window=8)
    for i in range(100):
        s.record_queue_delay(float(i))
        s.record_exec_latency(float(i))
    assert s.queue_delays_ms == [float(i) for i in range(92, 100)]
    assert s.exec_latencies_ms == [float(i) for i in range(92, 100)]
    # the service wires its config's window through
    svc = AsyncChordalityEngine(
        config=ServiceConfig(stats_window=17), backend="numpy_ref")
    try:
        assert svc.stats.window == 17
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# Regression (ISSUE 8 bugfix 4): after shutdown(drain=False) raises the
# no-drain flag, no interleaving may drain pending requests into units.
# ---------------------------------------------------------------------------
def test_admission_never_drains_after_no_drain_shutdown_flag():
    # A full bucket is drainable on the very next pass; raising the
    # closed+no-drain flags while it sits queued must cancel it, not
    # drain it (pre-fix, the pass drained the full bucket and executed).
    cfg = _quiet_config(max_batch=2)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    routed = []
    orig_route = svc.engine.route_unit
    svc.engine.route_unit = lambda unit, graphs: (
        routed.append(unit.n_pad), orig_route(unit, graphs))[1]
    with svc._lock:
        futs = [_inject_locked(svc, G.cycle(9)) for _ in range(2)]
        svc._closed = True
        svc._no_drain = True
        svc._work_cv.notify_all()
    for f in futs:
        with pytest.raises(CancelledError):
            f.result(timeout=30)
    assert routed == []
    assert svc.stats.n_cancelled == 2
    assert svc.backlog == 0
    svc.shutdown()          # joins the (already exiting) threads


def test_shutdown_no_drain_is_terminal():
    cfg = _quiet_config()
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    calls = []
    orig_exec = svc.engine.execute_unit
    svc.engine.execute_unit = lambda unit, graphs, device=None: (
        calls.append(unit.n_pad), orig_exec(unit, graphs, device))[1]
    futs = svc.submit_many([G.cycle(9), G.clique(9)])
    svc.shutdown(drain=False)
    # no executor work after shutdown returned, and no way to add any
    assert calls == []
    assert all(f.cancelled() for f in futs)
    assert not svc._executor.is_alive() and not svc._admitter.is_alive()
    with pytest.raises(ServiceClosedError):
        svc.submit(G.cycle(5))
    time.sleep(0.05)
    assert calls == []


# ---------------------------------------------------------------------------
# Priority classes: weighted-fair drain order, response echo, and the
# shedding policy's class accounting (ISSUE 8 tentpole + satellite tests).
# ---------------------------------------------------------------------------
def _dummy_request(priority):
    return _Request(graph=G.cycle(4), future=Future(),
                    t_submit=0.0, want_certificate=False,
                    priority=priority)


def test_bucket_queue_weighted_fair_order():
    bq = _BucketQueue((1.0, 2.0, 4.0))
    for p in (0, 0, 0, 2, 2, 2):
        bq.push(_dummy_request(p))
    order = [bq.pop().priority for _ in range(len(bq))]
    # class 2 holds 4x class 0's weight: it wins 2 of every 3 contested
    # pops, and class 0 never starves.
    assert order == [2, 2, 0, 2, 0, 0]
    with pytest.raises(IndexError):
        bq.pop()


def test_bucket_queue_removal_and_iteration_order():
    bq = _BucketQueue((1.0, 2.0))
    reqs = [_dummy_request(p) for p in (1, 0, 1)]
    for r in reqs:
        bq.push(r)
    assert [r.priority for r in bq.requests()] == [0, 1, 1]
    assert bq.remove(reqs[0]) and not bq.remove(reqs[0])
    assert len(bq) == 2
    assert [r.priority for r in bq.drain_all()] == [0, 1]
    assert len(bq) == 0


def test_priority_classes_drain_weighted_fair():
    cfg = _quiet_config(max_batch=3)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        unit_orders = []
        orig = svc._execute
        svc._execute = lambda au, lane=0: (
            unit_orders.append([r.priority for r in au.requests]),
            orig(au, lane))[1]
        # Both classes queued before any drain: two full units follow.
        with svc._lock:
            futs = [_inject_locked(svc, G.cycle(9), priority=p)
                    for p in (0, 0, 0, 2, 2, 2)]
            svc._work_cv.notify_all()
        resps = gather(futs, timeout=60)
        assert unit_orders == [[2, 2, 0], [2, 0, 0]]
        assert [r.priority for r in resps] == [0, 0, 0, 2, 2, 2]
    finally:
        svc.shutdown()


def test_priority_rides_witness_and_recognition_upgrades():
    # Mixed-extras unit: priorities echo per request and the unit takes
    # both whole-unit upgrades exactly once.
    cfg = _quiet_config(max_batch=8)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        f_plain0 = svc.submit(G.cycle(9), priority=0)
        f_wit = svc.submit(G.cycle(9), want_witness=True, priority=2)
        f_rec = svc.submit(G.cycle(9), properties=["interval"], priority=1)
        f_plain2 = svc.submit(G.cycle(9), priority=2)
        svc.flush(timeout=60)
        r0, rw, rr, r2 = gather(
            [f_plain0, f_wit, f_rec, f_plain2], timeout=10)
        assert [r0.priority, rw.priority, rr.priority, r2.priority] \
            == [0, 2, 1, 2]
        assert rw.witness is not None and not rw.witness.chordal
        assert r0.witness is None and r2.witness is None
        assert rr.properties == {"chordal": False, "interval": False}
        assert r0.properties is None
        assert svc.stats.witness_upgraded == 1
        assert svc.stats.recognition_upgraded == 1
        assert svc.stats.occupancy_histogram == {4: 1}
    finally:
        svc.shutdown()


def test_submit_priority_validation():
    with AsyncChordalityEngine(
            config=_quiet_config(), backend="numpy_ref") as svc:
        with pytest.raises(ValueError, match="priority"):
            svc.submit(G.cycle(4), priority=3)
        with pytest.raises(ValueError, match="priority"):
            svc.submit(G.cycle(4), priority=-1)
    with pytest.raises(ValueError, match="priority_weights"):
        ServiceConfig(priority_weights=())
    with pytest.raises(ValueError, match="default_priority"):
        ServiceConfig(priority_weights=(1.0,), default_priority=1)


def test_load_shedding_counts_by_priority_class():
    cfg = ServiceConfig(
        max_batch=16, max_wait_ms=60_000.0,
        autotune=AutotuneConfig(wait_max_ms=60_000.0,
                                interval_units=10**6))
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        # Seed the tuner's exec EMA: one unit "took" 500 ms, so any
        # queued request with < 500 ms of remaining deadline is
        # projected to miss.
        svc._autotuner.observe_unit(16, 8, [1.0], 500.0)
        lo = svc.submit_many([G.cycle(9)] * 4, priority=0,
                             deadline_ms=250.0)
        hi = svc.submit_many([G.cycle(9)] * 4, priority=2,
                             deadline_ms=60_000.0)
        deadline = time.monotonic() + 10
        while svc.stats.n_shed < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert all(f.cancelled() for f in lo)
        assert svc.stats.n_shed == 4
        assert svc.stats.shed_by_priority == {0: 4}
        assert svc.stats.n_expired == 0
        # the high class was never projected to miss: it still serves
        svc.flush(timeout=60)
        assert all(f.result(1).verdict is False for f in hi)
    finally:
        svc.shutdown()


def test_deadline_free_requests_are_never_shed():
    cfg = ServiceConfig(
        max_batch=16, max_wait_ms=60_000.0,
        autotune=AutotuneConfig(wait_max_ms=60_000.0,
                                interval_units=10**6))
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    try:
        svc._autotuner.observe_unit(16, 8, [1.0], 500.0)
        futs = svc.submit_many([G.cycle(9)] * 4, priority=0)  # no deadline
        time.sleep(0.1)
        assert svc.stats.n_shed == 0
        svc.flush(timeout=60)
        assert all(f.result(1).verdict is False for f in futs)
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# Executor lanes (PR 10): weighted dispatch, work-stealing, lane isolation.
# ---------------------------------------------------------------------------
def test_service_config_validates_lanes():
    with pytest.raises(ValueError, match="n_lanes"):
        ServiceConfig(n_lanes=0)
    with pytest.raises(ValueError, match="lane_weights length"):
        ServiceConfig(n_lanes=2, lane_weights=(1.0,))
    with pytest.raises(ValueError, match="positive"):
        ServiceConfig(n_lanes=2, lane_weights=(1.0, -1.0))
    assert ServiceConfig(n_lanes=1).lane_weights is None


def test_lane_dispatch_is_weighted_least_loaded():
    """Units land on the lane with the smallest backlog-per-weight, so a
    weight-2 lane accumulates twice the units of a weight-1 lane."""
    cfg = _quiet_config(n_lanes=3, lane_weights=(1.0, 1.0, 2.0))
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    svc.shutdown()      # lanes exited: the queues are ours to inspect
    for _ in range(8):
        svc._dispatch_unit(object())
    assert [len(q) for q in svc._lane_queues] == [2, 2, 4]


def test_idle_lane_steals_weighted_from_victim_tail():
    cfg = _quiet_config(n_lanes=2, lane_weights=(1.0, 3.0))
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    svc.shutdown()
    svc._lane_queues[0].extend([1, 2, 3, 4, 5])
    # Lane 1 (weight 3) is idle: steals 3 units from lane 0's tail,
    # runs the oldest of the stolen (3), keeps 4 and 5 on its own queue.
    with svc._lane_cv:
        got = svc._take_unit_locked(1)
    assert got == 3
    assert list(svc._lane_queues[1]) == [4, 5]
    assert list(svc._lane_queues[0]) == [1, 2]
    # The owner still drains its own head first.
    with svc._lane_cv:
        assert svc._take_unit_locked(0) == 1
    svc._lane_queues[0].clear()
    svc._lane_queues[1].clear()
    with svc._lane_cv:
        assert svc._take_unit_locked(0) is None


def test_slow_lane_does_not_stall_other_lanes():
    """One lane stuck mid-unit must not block admission or the other
    lane: later submissions complete while the first unit is wedged —
    the work-stealing rescue the lane scheduler exists for."""
    release, started = threading.Event(), threading.Event()
    flag_lock = threading.Lock()
    state = {"first": True}
    cfg = ServiceConfig(max_batch=1, max_wait_ms=0.0, n_lanes=2)
    svc = AsyncChordalityEngine(config=cfg, backend="numpy_ref")
    orig = svc._execute

    def gated(au, lane=0):
        with flag_lock:
            first, state["first"] = state["first"], False
        if first:
            started.set()
            release.wait(timeout=60)
        return orig(au, lane)

    svc._execute = gated
    try:
        slow = svc.submit(G.cycle(9))
        assert started.wait(timeout=30)
        fast = [svc.submit(G.clique(4)) for _ in range(4)]
        for f in fast:
            assert f.result(timeout=60).verdict
        assert not slow.done()
        release.set()
        assert slow.result(timeout=60).verdict is False
    finally:
        release.set()
        svc.shutdown()


def test_multilane_service_matches_sync_engine(sync_verdicts):
    cfg = ServiceConfig(max_batch=4, max_wait_ms=1.0, n_lanes=4)
    with AsyncChordalityEngine(config=cfg, backend="numpy_ref") as svc:
        got = [r.verdict for r in
               gather(svc.submit_many(_stream()), timeout=120)]
    np.testing.assert_array_equal(got, sync_verdicts)


def test_multilane_autotuner_sees_lane_feedback():
    cfg = ServiceConfig(max_batch=2, max_wait_ms=0.5, n_lanes=2,
                        autotune=AutotuneConfig())
    with AsyncChordalityEngine(config=cfg, backend="numpy_ref") as svc:
        gather(svc.submit_many([G.cycle(9)] * 8), timeout=120)
        tel = svc.telemetry()
        snap = svc._autotuner.lane_snapshot()
    assert tel["lanes"]["n_lanes"] == 2
    assert tel["lanes"]["weights"] == [1.0, 1.0]
    assert snap, "no lane reported an exec EMA"
    for lane, st in snap.items():
        assert lane in (0, 1)
        assert st["exec_ema_ms"] > 0
        assert 0.0 < st["occupancy_ema"] <= 1.0


def test_units_metric_carries_device_label():
    from repro import obs

    cfg = ServiceConfig(max_batch=2, max_wait_ms=0.5)
    with AsyncChordalityEngine(config=cfg, backend="numpy_ref") as svc:
        svc.submit(G.cycle(9)).result(timeout=60)
    series = obs.registry.snapshot()["repro_units_total"]["series"]
    host = [s for s in series if s["labels"].get("device") == "host"]
    assert host and sum(s["value"] for s in host) >= 1
