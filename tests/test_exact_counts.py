"""Exact integer contracts of the hot path: sweeps and dispatches per unit.

Both counters are exact by construction, so these are equalities, not
timing bounds:

* **sweeps per work unit** — ``repro.recognition.sweep_counter`` ticks
  once per sweep the shared plan runs. Every non-empty subset of the
  property registry must run exactly ``len(plan_sweeps(S))`` sweeps per
  unit, never more than the standalone sum, and the sets whose sharing
  DESIGN.md §13 quotes must read those numbers (all five: 5, not 7;
  chordal + proper_interval: 3, not 4).
* **dispatches per work unit** — ``repro.kernels.dispatch_counter``
  ticks once per host-level device launch. Each executable family is
  built through the engine's own :class:`CompileCache`, called once to
  compile, then counted on a second call: the fused kinds and the
  witness executables are one launch per unit at any batch, the split
  pipeline is two per graph, and ``sharded`` on one device is one.

Pallas kernels run interpreted on a CPU host, so the shapes stay at
n_pad <= 128.
"""
import itertools

import numpy as np
import pytest

from repro.core import generators as G
from repro.engine import ChordalityEngine, CompileCache, make_backend
from repro.kernels import dispatch_counter
from repro.recognition import (
    normalize_properties,
    plan_sweeps,
    property_names,
    standalone_sweep_count,
    sweep_counter,
)

# ---------------------------------------------------------------------------
# Sweeps per work unit, over every property set.
# ---------------------------------------------------------------------------
_SUBSETS = [
    subset
    for r in range(1, len(property_names()) + 1)
    for subset in itertools.combinations(property_names(), r)
]

#: (shared sweeps, standalone sweeps) per unit for the sets DESIGN.md §13
#: quotes, keyed by the normalized property tuple.
_RECORDED_SWEEPS = {
    ("chordal",): (1, 1),
    ("chordal", "interval"): (1, 2),
    ("chordal", "lexdfs_order"): (2, 2),
    ("chordal", "mcs_peo"): (2, 2),
    ("chordal", "proper_interval"): (3, 4),
    normalize_properties(property_names()): (5, 7),
}


@pytest.fixture(scope="module")
def sweep_engine() -> ChordalityEngine:
    return ChordalityEngine(backend="numpy_ref", max_batch=8)


def test_subsets_cover_the_registry():
    assert len(_SUBSETS) == 2 ** len(property_names()) - 1 == 31
    assert len(set(_SUBSETS)) == 31


@pytest.mark.parametrize("subset", _SUBSETS, ids="+".join)
def test_sweeps_per_unit(sweep_engine, subset):
    graphs = [G.gnp(12, 0.3, seed=s) for s in range(4)]
    c0 = sweep_counter.count
    res = sweep_engine.run(graphs, properties=subset)
    sweeps = sweep_counter.delta(c0)
    assert res.stats.n_units == 1
    assert sweeps == len(plan_sweeps(subset))
    assert sweeps <= standalone_sweep_count(subset)
    recorded = _RECORDED_SWEEPS.get(normalize_properties(subset))
    if recorded is not None:
        assert (sweeps, standalone_sweep_count(subset)) == recorded


# ---------------------------------------------------------------------------
# Dispatches per work unit, per executable family.
# ---------------------------------------------------------------------------
_FUSED = {"interpret": True, "pipeline": "fused"}
_SPLIT = {"interpret": True, "pipeline": "split"}

#: (backend, backend options, cache kind, n_pad, batch, dispatches/unit).
_DISPATCH_CASES = [
    ("pallas_peo", _FUSED, "fused", 64, 1, 1),
    ("pallas_peo", _FUSED, "fused", 64, 4, 1),
    ("pallas_peo", _FUSED, "fused", 128, 1, 1),
    ("pallas_peo", _FUSED, "fused", 128, 4, 1),
    ("pallas_peo", _FUSED, "fused_packed", 16, 8, 1),
    ("pallas_peo", _FUSED, "fused_packed", 64, 8, 1),
    ("pallas_peo", _SPLIT, "verdict", 64, 1, 2),
    ("pallas_peo", _SPLIT, "verdict", 64, 4, 8),
    ("pallas_peo", _FUSED, "fused_witness", 64, 4, 1),
    ("pallas_peo", _FUSED, "fused_witness", 128, 4, 1),
    ("jax_fast", {}, "witness", 64, 4, 1),
    ("jax_faithful", {}, "witness", 64, 4, 1),
    ("sharded", {"n_devices": 1}, "verdict", 64, 4, 1),
]


def _dispatch_id(case):
    name, _, kind, n_pad, batch, _ = case
    return f"{name}-{kind}-{n_pad}-{batch}"


def _unit(n_pad: int, batch: int) -> np.ndarray:
    return np.stack([
        G.sparse_erdos_renyi(n_pad, c=6.0, seed=s).with_dense().adj
        for s in range(batch)])


@pytest.mark.parametrize("case", _DISPATCH_CASES, ids=_dispatch_id)
def test_dispatches_per_unit(case):
    name, opts, kind, n_pad, batch, want = case
    backend = make_backend(name, **opts)
    fn = CompileCache().get(backend, n_pad, batch, kind=kind)
    unit = _unit(n_pad, batch)
    args = (unit,)
    if "witness" in kind:
        args += (np.full(batch, n_pad, dtype=np.int32),)
    first = fn(*args)                       # compile outside the count
    c0 = dispatch_counter.count
    second = fn(*args)
    assert dispatch_counter.delta(c0) == want
    if "witness" in kind:
        np.testing.assert_array_equal(second.chordal, first.chordal)
    else:
        np.testing.assert_array_equal(second, first)
