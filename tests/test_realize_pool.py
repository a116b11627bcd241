"""The realize pool: dense work units written into host buffers kept from
one unit to the next (``repro.engine.planner.RealizePool``).

A pooled unit must equal a fresh ``realize_unit`` bit for bit whatever
the last unit left in the buffer; a buffer is never out twice at once,
goes back only after the executable returned host verdicts, and is
dropped when a unit raises; its reuses and allocations are counted.
"""
import threading

import numpy as np
import pytest

from repro import obs
from repro.configs.service import ServiceConfig
from repro.core import generators as G
from repro.engine import (
    AsyncChordalityEngine,
    ChordalityEngine,
    RealizePool,
    WorkUnit,
    gather,
    realize_unit,
)
from repro.engine.backends import NumpyRefBackend
from repro.graphs.structure import Graph, pad_graph


def _graph(n: int, view: str, seed: int) -> Graph:
    """A dense graph of ``n`` vertices (about three quarters of its
    entries set, so stale bytes show), stored as ``view`` says: its own
    (n, n) matrix, a matrix padded past ``n_nodes``, or an edge list that
    realize densifies through ``with_dense``."""
    g = G.dense_random(n, p=0.75, seed=seed)
    if view == "padded":
        return pad_graph(g, n + 7)
    if view == "edges":
        src, dst = np.nonzero(g.adj)
        return Graph(n_nodes=n, edges=np.stack([src, dst]).astype(np.int32))
    return g


# Each case: units on one n_pad (32), as (batch, [(n, view), ...]).
_SEQUENCES = {
    "shrink_then_grow": [
        (2, [(32, "dense"), (30, "dense")]),
        (2, [(9, "dense"), (17, "dense")]),
        (2, [(4, "dense"), (31, "dense")]),
        (2, [(32, "dense"), (32, "dense")]),
    ],
    "full_then_partial": [
        (4, [(32, "dense"), (31, "dense"), (29, "dense"), (32, "dense")]),
        (4, [(12, "dense")]),
        (4, [(18, "dense"), (25, "dense"), (3, "dense")]),
    ],
    "batch_grows_then_shrinks": [
        (1, [(28, "dense")]),
        (4, [(10, "dense"), (32, "dense"), (20, "dense"), (5, "dense")]),
        (2, [(19, "dense"), (7, "dense")]),
        (8, [(32, "dense")] * 5),
        (1, [(2, "dense")]),
    ],
    "adj_padded_past_n_nodes": [
        (2, [(32, "dense"), (25, "padded")]),
        (2, [(17, "padded"), (24, "dense")]),
    ],
    "edge_view_through_with_dense": [
        (2, [(31, "edges"), (32, "dense")]),
        (2, [(11, "dense"), (20, "edges")]),
    ],
}


@pytest.mark.parametrize("case", sorted(_SEQUENCES))
def test_pooled_realize_equals_fresh_realize(case):
    pool = RealizePool()
    for k, (batch, specs) in enumerate(_SEQUENCES[case]):
        graphs = [_graph(n, view, seed=100 * k + i)
                  for i, (n, view) in enumerate(specs)]
        unit = WorkUnit(n_pad=32, batch=batch,
                        indices=tuple(range(len(graphs))))
        want = realize_unit(unit, graphs)
        with pool.realized(unit, graphs) as got:
            assert got.shape == want.shape and got.dtype == np.bool_
            assert got.flags.c_contiguous
            np.testing.assert_array_equal(got, want, err_msg=f"unit {k}")
    assert len(pool) == 1 and pool.leased() == []


def test_a_unit_that_raises_drops_its_buffer():
    """Realize raising part-way (a graph past the unit's n_pad) and an
    executable raising both drop the buffer; the next unit on the same
    n_pad is realized from a new one and equals the fresh realize."""
    pool = RealizePool()
    big = [G.clique(16), G.clique(20)]
    unit = WorkUnit(n_pad=16, batch=2, indices=(0, 1))
    with pytest.raises(ValueError):
        with pool.realized(unit, big):
            pass
    assert len(pool) == 0 and pool.leased() == []

    cliques = [G.clique(16), G.clique(15)]
    with pytest.raises(RuntimeError, match="executable"):
        with pool.realized(unit, cliques):
            raise RuntimeError("executable failed")
    assert len(pool) == 0 and pool.allocs == 2

    small = [G.cycle(5), G.path(9)]
    with pool.realized(unit, small) as got:
        np.testing.assert_array_equal(got, realize_unit(unit, small))
    assert (pool.allocs, pool.reuses, len(pool)) == (3, 0, 1)


def test_pool_refuses_a_buffer_returned_twice():
    pool = RealizePool()
    buf = pool._checkout(16, 2)
    pool._return(buf, keep=True)
    with pytest.raises(RuntimeError, match="returned twice"):
        pool._return(buf, keep=True)


class _FailingOnce(NumpyRefBackend):
    """numpy_ref whose executable raises on the first call."""

    def __init__(self):
        self.calls = 0

    def compile_batch(self, n_pad, batch):
        run = super().compile_batch(n_pad, batch)

        def once(adjs):
            self.calls += 1
            if self.calls == 1:
                raise RuntimeError("device lost")
            return run(adjs)

        return once


def test_engine_unit_after_a_failed_executable_is_correct():
    backend = _FailingOnce()
    eng = ChordalityEngine(backend=backend, max_batch=4)
    first = [G.clique(30), G.clique(29), G.clique(31), G.clique(32)]
    with pytest.raises(RuntimeError, match="device lost"):
        eng.run(first)
    assert len(eng.realize_pool) == 0
    then = [G.cycle(20), G.random_chordal(18, k=3, seed=1), G.cycle(4)]
    got = eng.run(then).verdicts
    ref = ChordalityEngine("numpy_ref", max_batch=4).run(then).verdicts
    np.testing.assert_array_equal(got, ref)
    assert got.tolist() == [False, True, False]


class _Rendezvous(NumpyRefBackend):
    """numpy_ref whose executable waits until two units are inside it,
    and records what the pool has out at that moment."""

    def __init__(self, pool_of):
        self._pool_of = pool_of
        self._barrier = threading.Barrier(2, timeout=60)
        self.seen = []

    def compile_batch(self, n_pad, batch):
        run = super().compile_batch(n_pad, batch)

        def meet(adjs):
            self._barrier.wait()
            self.seen.append((adjs, self._pool_of().leased()))
            self._barrier.wait()
            return run(adjs)

        return meet


def test_two_units_in_flight_hold_two_buffers():
    eng = None
    backend = _Rendezvous(lambda: eng.realize_pool)
    eng = ChordalityEngine(backend=backend, max_batch=2)
    units = [[G.clique(30), G.cycle(25)], [G.cycle(31), G.clique(27)]]
    plans = [eng.plan(gs) for gs in units]
    got, errs = [None, None], []

    def lane(k):
        try:
            (unit,) = plans[k].units
            got[k] = eng.execute_unit(unit, units[k])[0]
        except Exception as e:        # surfaced below, not swallowed
            errs.append(e)

    threads = [threading.Thread(target=lane, args=(k,)) for k in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    assert [g.tolist() for g in got] == [[True, False], [False, True]]
    for adjs, out in backend.seen:
        assert len(out) == 2
        assert not np.shares_memory(out[0], out[1])
        assert sum(np.shares_memory(adjs, a) for a in out) == 1
    assert len(eng.realize_pool) == 2 and eng.realize_pool.leased() == []
    assert (eng.realize_pool.allocs, eng.realize_pool.reuses) == (2, 0)


def test_two_lane_service_answers_like_numpy_ref_at_two_npads():
    graphs = []
    for i in range(24):
        n = (9, 14, 20, 27, 31, 16)[i % 6]
        graphs.append(G.clique(n) if i % 3 == 0 else
                      G.cycle(n) if i % 3 == 1 else
                      G.random_chordal(n, k=3, seed=i))
    want = ChordalityEngine("numpy_ref", max_batch=4).run(graphs).verdicts
    cfg = ServiceConfig(max_batch=4, max_wait_ms=0.5, n_lanes=2)
    with AsyncChordalityEngine(config=cfg, backend="numpy_ref") as svc:
        for _ in range(3):
            got = [r.verdict for r in
                   gather(svc.submit_many(graphs), timeout=120)]
            np.testing.assert_array_equal(got, want)
        pool = svc.engine.realize_pool
    assert pool.leased() == []
    assert pool.reuses > 0 and pool.allocs >= 2
    # At most one buffer per n_pad per lane.
    assert 2 <= len(pool) <= 4


def test_counters_count_allocs_then_reuses_and_are_exported():
    reuses = obs.registry.counter("repro_realize_buffer_reuses_total")
    allocs = obs.registry.counter("repro_realize_buffer_allocs_total")
    r0, a0 = reuses.value(), allocs.value()
    eng = ChordalityEngine("numpy_ref", max_batch=4)
    eng.run([G.cycle(9), G.clique(7)])
    assert (reuses.value() - r0, allocs.value() - a0) == (0, 1)
    eng.run([G.path(12), G.clique(3)])
    assert (reuses.value() - r0, allocs.value() - a0) == (1, 1)
    assert (eng.realize_pool.reuses, eng.realize_pool.allocs) == (1, 1)
    tel = eng.telemetry()
    for name in ("repro_realize_buffer_reuses_total",
                 "repro_realize_buffer_allocs_total"):
        assert name in tel["metrics"]
        assert f"# TYPE {name} counter" in obs.render_prometheus()


def test_pool_under_more_threads_than_cores():
    """Threads outnumbering the cores check buffers out and back at two
    n_pads with a short switch interval: no buffer is ever out twice (the
    pool raises if it were), every unit equals the fresh realize, no
    count is lost, and the pool holds no more buffers than threads."""
    import os
    import sys

    pool = RealizePool()
    n_threads = (os.cpu_count() or 4) + 2
    rounds = 50
    errs = []

    def worker(t):
        rng = np.random.default_rng(t)
        try:
            for _ in range(rounds):
                n_pad = int(rng.choice([16, 32]))
                sizes = rng.integers(1, n_pad + 1, int(rng.integers(1, 5)))
                graphs = [G.clique(int(n)) for n in sizes]
                unit = WorkUnit(n_pad=n_pad, batch=4,
                                indices=tuple(range(len(graphs))))
                want = realize_unit(unit, graphs)
                with pool.realized(unit, graphs) as got:
                    np.testing.assert_array_equal(got, want)
        except Exception as e:        # surfaced below, not swallowed
            errs.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errs, errs[:3]
    assert pool.reuses + pool.allocs == n_threads * rounds
    assert pool.leased() == [] and len(pool) <= 2 * n_threads
