"""Router: cost model, capability filtering, auto-engine plan metadata."""
import numpy as np
import pytest

from repro.core import generators as G
from repro.engine import (
    ChordalityEngine,
    DEFAULT_COST_MODEL,
    Router,
    fit_cost_model,
)
from repro.engine.router import BackendCost
from repro.graphs.structure import Graph


# ---------------------------------------------------------------------------
# Cost model mechanics
# ---------------------------------------------------------------------------
def test_cost_formula_terms():
    c = BackendCost(dispatch_us=100, per_graph_us=10, sweep_us=2,
                    n_us=1, n2_us=0.5, m_us=0.25)
    # n=4, density=0.5 (m=8), batch=2:
    # 100/2 + 10 + 2*4/2 + 1*4 + 0.5*16 + 0.25*8 = 50+10+4+4+8+2
    assert c.us_per_graph(4, 0.5, 2) == pytest.approx(78.0)


def test_batch_amortizes_dispatch_and_sweeps():
    c = DEFAULT_COST_MODEL["csr"]
    assert c.us_per_graph(256, 0.01, 32) < c.us_per_graph(256, 0.01, 1)


def test_fit_cost_model_recovers_orderings():
    # Synthetic samples from two known models; the fit must reproduce the
    # cheap/expensive ordering even if exact coefficients differ.
    true = {
        "a": BackendCost(per_graph_us=100.0),
        "b": BackendCost(per_graph_us=10.0, n2_us=0.01),
    }
    samples = []
    for name, c in true.items():
        for n in (8, 32, 128, 512):
            for b in (1, 8):
                samples.append(
                    (name, n, 0.1, b, c.us_per_graph(n, 0.1, b)))
    fitted = fit_cost_model(
        samples, feature_masks={"a": (1,), "b": (1, 4)})
    assert fitted["a"].us_per_graph(8, 0.1, 1) > \
        fitted["b"].us_per_graph(8, 0.1, 1)
    assert fitted["a"].us_per_graph(512, 0.1, 1) < \
        fitted["b"].us_per_graph(512, 0.1, 1)


# ---------------------------------------------------------------------------
# Capability filtering: never pick a backend lacking a required capability,
# no matter how cheap the cost model claims it is.
# ---------------------------------------------------------------------------
def test_choose_excludes_backends_missing_required_caps():
    model = dict(DEFAULT_COST_MODEL)
    model["sharded"] = BackendCost()          # free => always cheapest
    r = Router(cost_model=model,
               candidates=("numpy_ref", "jax_fast", "csr", "sharded"))
    assert r.choose(256, 0.1, 8) == "sharded"  # unconstrained: cheapest wins
    got = r.choose(256, 0.1, 8, require=("certificate",))
    assert got != "sharded"                    # sharded lacks certificates


def test_choose_requires_some_candidate():
    r = Router(cost_model={"sharded": BackendCost()},
               candidates=("sharded",))
    with pytest.raises(ValueError, match="certificate"):
        r.choose(64, 0.1, 1, require=("certificate",))


def test_router_rejects_candidates_without_cost_entries():
    with pytest.raises(ValueError, match="pallas_peo"):
        Router(cost_model={"csr": BackendCost()},
               candidates=("csr", "pallas_peo"))


# ---------------------------------------------------------------------------
# Regime routing with the fitted default model (plan metadata only — no
# execution, so the streams can be large).
# ---------------------------------------------------------------------------
def _edge_graph(n, c, seed):
    return G.sparse_erdos_renyi(n, c=c, seed=seed)


def test_default_model_routes_three_regimes():
    tiny = [G.cycle(10)]                                    # one-off request
    sparse = [_edge_graph(1024, 10, s) for s in range(32)]  # density ~0.01
    dense = [G.dense_random(200, p=0.4, seed=s) for s in range(32)]
    eng = ChordalityEngine(backend="auto", max_batch=32)
    plan = eng.plan(tiny + sparse + dense)
    by_npad = {u.n_pad: u.backend for u in plan.units}
    # Since the PR 6 wrapper restructure, jax_fast's dispatch floor beats
    # numpy_ref's per-graph python cost, so tiny one-off requests route
    # to jax_fast too; csr still owns the sparse-large regime.
    assert by_npad[16] == "jax_fast"       # tiny single request
    assert by_npad[1024] == "csr"          # sparse large
    assert by_npad[256] == "jax_fast"      # dense bulk
    # plan metadata exposes the choice per request
    assert plan.unit_of(0).backend == "jax_fast"
    assert plan.unit_of(1).backend == "csr"
    assert plan.unit_of(len(tiny) + len(sparse)).backend == "jax_fast"


def test_auto_run_executes_routed_plan_and_agrees():
    graphs = ([G.cycle(9)]
              + [_edge_graph(80, 5, s) for s in range(6)]
              + [G.dense_random(48, p=0.5, seed=s) for s in range(6)])
    auto = ChordalityEngine(backend="auto", max_batch=8)
    res = auto.run(graphs)
    ref = ChordalityEngine(backend="numpy_ref", max_batch=8).run(graphs)
    np.testing.assert_array_equal(res.verdicts, ref.verdicts)
    assert sum(res.stats.backend_histogram.values()) == len(graphs)
    assert set(res.stats.backend_histogram) == \
        {u.backend for u in res.plan.units}


def test_auto_certificate_routes_with_certificate_requirement():
    eng = ChordalityEngine(backend="auto")
    cert = eng.certificate(G.cycle(9))
    assert not cert.chordal and cert.n_violations > 0
    cert = eng.certificate(G.k_tree(24, k=3, seed=0))
    assert cert.chordal and cert.n_violations == 0


def test_auto_rejects_backend_opts():
    with pytest.raises(ValueError, match="auto"):
        ChordalityEngine(backend="auto", interpret=False)


def test_auto_warmup_requires_plan():
    eng = ChordalityEngine(backend="auto")
    with pytest.raises(ValueError, match="warmup_plan"):
        eng.warmup([16])


def test_auto_warmup_plan_precompiles_routed_shapes():
    graphs = [G.cycle(10), G.dense_random(40, p=0.5, seed=0)]
    eng = ChordalityEngine(backend="auto", max_batch=4)
    eng.warmup_plan(eng.plan(graphs))
    res = eng.run(graphs)
    assert res.stats.compile_misses == 0


def test_custom_router_overrides_choice():
    # A router that prices everything except csr at infinity.
    model = {
        "csr": BackendCost(),
        "jax_fast": BackendCost(per_graph_us=1e12),
        "numpy_ref": BackendCost(per_graph_us=1e12),
    }
    eng = ChordalityEngine(
        backend="auto", max_batch=4, router=Router(cost_model=model))
    res = eng.run([G.cycle(8), G.clique(8)])
    assert res.stats.backend_histogram == {"csr": 2}
    assert res.verdicts.tolist() == [False, True]


# ---------------------------------------------------------------------------
# Degenerate inputs: the cost model must not extrapolate below its fitted
# support — tiny n, zero-edge graphs, and batch=1 route like the nearest
# measured regime (ISSUE 3 satellite; clamp_features).
# ---------------------------------------------------------------------------
def test_choose_clamps_n_below_fitted_floor():
    r = Router()
    lo, _ = r.fit_n_range
    floor_choice = r.choose(lo, 0.0, 1)
    for n in (1, 2, 3, 5, lo - 1):
        assert r.choose(n, 0.0, 1) == floor_choice
    # Unclamped extrapolation used to hand these to csr; since the PR 6
    # wrapper restructure dropped jax_fast's dispatch floor below
    # numpy_ref's per-graph python cost, the measured floor regime
    # belongs to jax_fast.
    assert floor_choice == "jax_fast"


def test_choose_clamps_degenerate_density_and_batch():
    r = Router()
    # density > 1 (bogus caller math) and batch=0 must not blow up, and
    # must agree with their clamped twins.
    assert r.choose(4, 5.0, 0) == r.choose(16, 1.0, 1)
    assert r.choose(64, float("nan"), 1) == r.choose(64, 0.0, 1)
    assert r.choose(10 ** 9, 0.0, 8) == r.choose(r.fit_n_range[1], 0.0, 8)


def test_clamp_features_bounds():
    r = Router()
    lo, hi = r.fit_n_range
    assert r.clamp_features(1, -0.5, 0) == (lo, 0.0, 1)
    assert r.clamp_features(10 ** 9, 2.0, 7) == (hi, 1.0, 7)
    n, d, b = r.clamp_features(64, 0.25, 4)
    assert (n, d, b) == (64, 0.25, 4)      # in-range points untouched


def test_router_rejects_invalid_fit_range():
    with pytest.raises(ValueError, match="fit_n_range"):
        Router(fit_n_range=(0, 16))
    with pytest.raises(ValueError, match="fit_n_range"):
        Router(fit_n_range=(32, 16))


def test_degenerate_streams_execute_on_routed_backends():
    # n smaller than every bucket, zero-edge graphs, batch=1 — end to end
    # through the auto engine, agreeing with the reference.
    graphs = [
        G.cycle(3),                                    # n=3 < smallest bucket
        Graph(n_nodes=2, adj=np.zeros((2, 2), dtype=bool)),   # zero edges
        Graph(n_nodes=1, adj=np.zeros((1, 1), dtype=bool)),   # single vertex
        Graph(n_nodes=5, edges=np.zeros((2, 0), dtype=np.int32)),  # edge view
    ]
    auto = ChordalityEngine(backend="auto", max_batch=4)
    ref = ChordalityEngine(backend="numpy_ref", max_batch=4)
    for g in graphs:                                   # batch=1 plans
        np.testing.assert_array_equal(
            auto.run([g]).verdicts, ref.run([g]).verdicts)
    res = auto.run(graphs)
    np.testing.assert_array_equal(res.verdicts, ref.run(graphs).verdicts)
    for unit in res.plan.units:
        assert unit.backend in auto.router.candidates


def test_zero_edge_certificate_routes_to_capable_backend():
    eng = ChordalityEngine(backend="auto")
    cert = eng.certificate(np.zeros((3, 3), dtype=bool))
    assert cert.chordal and cert.n_violations == 0


def test_routing_density_uses_edge_views_without_densifying():
    # Graphs that carry only an edge list: planning must not densify them.
    g = G.sparse_erdos_renyi(512, c=6, seed=0)
    lean = Graph(n_nodes=g.n_nodes, edges=g.edges)
    eng = ChordalityEngine(backend="auto", max_batch=8)
    plan = eng.plan([lean] * 8)
    (unit,) = plan.units
    assert unit.backend == "csr"
    assert lean.adj is None               # still no dense view materialized

# ---------------------------------------------------------------------------
# Online refit (ISSUE 5 satellite): a session re-fits its router from its
# own measured unit latencies, and the refit clamps the fitted support so
# routing never extrapolates outside the n-range it actually measured.
# ---------------------------------------------------------------------------
def _run_streams(eng, ns=(64, 256), passes=3):
    for _ in range(passes):
        for n in ns:
            eng.run([_edge_graph(n, 6, s) for s in range(8)])


def test_refit_router_updates_model_and_clamps_support():
    eng = ChordalityEngine(backend="auto", max_batch=8)
    before = {k: v for k, v in eng.router.cost_model.items()}
    _run_streams(eng)
    refitted = eng.refit_router(min_samples=2)
    assert refitted                       # at least one backend re-fitted
    for name in refitted:
        assert eng.router.cost_model[name] != before[name]
    # support clamp: exactly the observed n_pad range
    assert eng.router.fit_n_range == (64, 256)


def test_refit_never_routes_outside_fitted_support():
    eng = ChordalityEngine(backend="auto", max_batch=8)
    _run_streams(eng)
    eng.refit_router(min_samples=2)
    r = eng.router
    lo, hi = r.fit_n_range
    # Any query outside the measured range routes exactly like the nearest
    # measured regime — the refitted linear forms are never evaluated on
    # unfitted features.
    for d, b in ((0.0, 1), (0.02, 8), (0.5, 4)):
        assert r.choose(1, d, b) == r.choose(lo, d, b)
        assert r.choose(10 ** 9, d, b) == r.choose(hi, d, b)
        assert r.clamp_features(hi * 16, d, b)[0] == hi


def test_refit_keeps_unmeasured_backends_at_prior_coefficients():
    eng = ChordalityEngine(backend="auto", max_batch=8)
    _run_streams(eng, ns=(64,), passes=2)
    prior_csr = eng.router.cost_model["csr"]
    eng.refit_router(min_samples=10 ** 6)   # nobody reaches the bar
    assert eng.router.cost_model["csr"] == prior_csr


def test_refit_requires_auto_engine():
    eng = ChordalityEngine(backend="jax_fast")
    with pytest.raises(ValueError, match="auto"):
        eng.refit_router()


def test_refit_with_single_n_samples_keeps_prior_model_and_support():
    # Regression (ISSUE 8 bugfix): a sample log with only one distinct n
    # cannot identify the cost model's n-slope. Pre-fix, the refit fitted
    # anyway and collapsed fit_n_range to (64, 64) — every later query
    # clamped to n=64 and, e.g., big sparse graphs misrouted to jax_fast.
    eng = ChordalityEngine(backend="auto", max_batch=8)
    _run_streams(eng, ns=(64,), passes=3)
    prior_model = dict(eng.router.cost_model)
    prior_range = eng.router.fit_n_range
    assert eng.refit_router(min_samples=2) == ()
    assert eng.router.cost_model == prior_model
    assert eng.router.fit_n_range == prior_range
    # routing for far-away n is untouched by the degenerate log
    fresh = Router()
    for d, b in ((0.005, 8), (0.3, 4)):
        assert eng.router.choose(1024, d, b) == fresh.choose(1024, d, b)
    # explicit opt-in overrides the distinct-n bar, but even then a
    # single-n fit must not collapse the support interval
    refitted = eng.refit_router(min_samples=2, min_distinct_n=1)
    assert refitted
    assert eng.router.fit_n_range == prior_range


def test_stats_surface_unit_samples():
    eng = ChordalityEngine(backend="auto", max_batch=8)
    res = eng.run([_edge_graph(64, 6, s) for s in range(8)])
    assert len(res.stats.unit_samples) == res.stats.n_units
    name, n, density, batch, device_count, us = res.stats.unit_samples[0]
    assert name in eng.router.candidates
    assert n == 64 and batch == 8
    assert 0.0 < density < 1.0 and us > 0.0
    # auto candidates are all single-device backends
    assert device_count == 1


# ---------------------------------------------------------------------------
# The calibration row's density: ``Graph.n_edges`` counts a dense
# adjacency's set entries, the same number a bool sum gives.
# ---------------------------------------------------------------------------
_POOL = G.dense_random(48, p=0.5, seed=3).adj


def _n_edges_case(case):
    """(graph, the count ``int(adj.sum())`` gives) for one storage case."""
    if case == "contiguous":
        adj = np.ascontiguousarray(_POOL[:40, :40])
    elif case == "prefix_view":
        adj = _POOL[:37, :37]               # strided, as the traffic sends
    elif case == "int8":
        adj = _POOL[:33, :33].astype(np.int8)
    elif case == "empty":
        adj = np.zeros((16, 16), dtype=bool)
    else:                                   # "edges": the edge list wins
        adj = np.zeros((8, 8), dtype=bool)
        g = Graph(n_nodes=8, adj=adj,
                  edges=np.array([[0, 1, 2], [1, 0, 3]], dtype=np.int32))
        return g, 3
    return Graph(n_nodes=adj.shape[0], adj=adj), int(adj.sum())


@pytest.mark.parametrize(
    "case", ["contiguous", "prefix_view", "int8", "empty", "edges"])
def test_n_edges_equals_bool_sum(case):
    g, want = _n_edges_case(case)
    assert g.n_edges == want
    assert isinstance(g.n_edges, int)


def test_fixed_backend_unit_sample_density_is_mean_bool_sum():
    graphs = [Graph(n_nodes=n, adj=_POOL[:n, :n])
              for n in (33, 40, 47, 48)]
    eng = ChordalityEngine(backend="numpy_ref", max_batch=4)
    res = eng.run(graphs)
    (sample,) = res.stats.unit_samples
    name, n_pad, density, batch, device_count, _ = sample
    assert (name, n_pad, batch, device_count) == ("numpy_ref", 64, 4, 1)
    want = float(np.mean([g.adj.sum() for g in graphs])) / (64 * 64)
    assert density == want


# ---------------------------------------------------------------------------
# device_count feature (PR 10): mesh-aware pricing, clamped to fitted
# support so single-device logs never extrapolate multi-device costs.
# ---------------------------------------------------------------------------
def test_us_per_graph_device_count_divides_compute_terms():
    c = BackendCost(dispatch_us=100, per_graph_us=10, sweep_us=2,
                    n_us=1, n2_us=0.5, m_us=0.25, dev_us=3, max_devices=8)
    # d=1 recovers the legacy form exactly (test_cost_formula_terms)
    assert c.us_per_graph(4, 0.5, 2, device_count=1) == pytest.approx(78.0)
    # d=4: compute terms (4 + 8 + 2) divide by 4, coordination adds 3*(4-1)
    assert c.us_per_graph(4, 0.5, 2, device_count=4) == pytest.approx(
        50 + 10 + 4 + (4 + 8 + 2) / 4 + 9)
    # past the fitted span the entry clamps to its own max_devices
    assert c.us_per_graph(4, 0.5, 2, device_count=64) == \
        c.us_per_graph(4, 0.5, 2, device_count=8)


def test_device_count_is_inert_for_single_device_entries():
    # Every committed default entry is a single-device fit
    # (max_devices=1): pricing with a mesh width must change nothing.
    for c in DEFAULT_COST_MODEL.values():
        assert c.us_per_graph(256, 0.1, 8, device_count=8) == \
            c.us_per_graph(256, 0.1, 8)


def test_clamp_features_clamps_device_count_to_fitted_support():
    # The satellite fix: a router whose model was fitted single-device
    # (the default) must clamp device_count to 1 rather than price a
    # mesh width nobody measured.
    r = Router()
    assert r.fit_device_range == (1, 1)
    assert r.clamp_features(256, 0.1, 8, 8) == (256, 0.1, 8, 1)
    # a router fitted over a real device span passes it through...
    r8 = Router(fit_device_range=(1, 8))
    assert r8.clamp_features(256, 0.1, 8, 8) == (256, 0.1, 8, 8)
    # ...and clamps past its edges
    assert r8.clamp_features(256, 0.1, 8, 64)[3] == 8
    assert r8.clamp_features(256, 0.1, 8, 0)[3] == 1
    # the 3-feature surface is unchanged (pre-PR 10 callers)
    assert r.clamp_features(256, 0.1, 8) == (256, 0.1, 8)


def test_router_rejects_invalid_fit_device_range():
    with pytest.raises(ValueError, match="fit_device_range"):
        Router(fit_device_range=(0, 8))
    with pytest.raises(ValueError, match="fit_device_range"):
        Router(fit_device_range=(8, 1))


def test_platform_overlay_prices_sharded_mesh():
    from repro.engine.router import platform_cost_model

    # The bare default model carries no sharded entry; the cpu overlay
    # does (fitted from the emulated-mesh scaling bench).
    assert "sharded" not in DEFAULT_COST_MODEL
    cpu = platform_cost_model("cpu")
    assert "sharded" in cpu and cpu["sharded"].max_devices == 8
    r = Router(platform="cpu",
               candidates=("numpy_ref", "jax_fast", "csr", "sharded"),
               fit_device_range=(1, 8))
    est = r.estimate_us_per_graph
    # more devices -> cheaper big dense units, never more expensive
    assert est("sharded", 1024, 0.3, 32, device_count=8) < \
        est("sharded", 1024, 0.3, 32, device_count=1)
    # single-device, sharded never undercuts the plain jit path it wraps
    assert est("sharded", 256, 0.1, 8, device_count=1) >= \
        est("jax_fast", 256, 0.1, 8)


def test_fit_cost_model_learns_device_terms():
    true = BackendCost(dispatch_us=120, per_graph_us=2, n_us=0.4,
                       n2_us=0.01, dev_us=15, max_devices=8)
    samples = [
        ("sharded", n, 0.1, b, d, true.us_per_graph(n, 0.1, b, d))
        for n in (64, 256, 1024) for b in (8, 32) for d in (1, 2, 4, 8)
    ]
    fitted = fit_cost_model(samples)["sharded"]
    assert fitted.max_devices == 8
    for n, b, d in ((128, 16, 1), (512, 8, 4), (1024, 32, 8)):
        assert fitted.us_per_graph(n, 0.1, b, d) == pytest.approx(
            true.us_per_graph(n, 0.1, b, d), rel=0.05)
    # legacy 5-field rows still fit (at device_count=1, max_devices=1)
    legacy = fit_cost_model(
        [("jax_fast", n, 0.1, 8, DEFAULT_COST_MODEL["jax_fast"]
          .us_per_graph(n, 0.1, 8)) for n in (64, 128, 256, 512)])
    assert legacy["jax_fast"].max_devices == 1


def test_refit_clamps_device_support_to_observed_single_device():
    # Live logs from a single-device session must narrow the device
    # support to (1, 1) — even on a router that started mesh-capable.
    eng = ChordalityEngine(
        backend="auto", max_batch=8,
        router=Router(fit_device_range=(1, 8)))
    _run_streams(eng)
    assert eng.refit_router(min_samples=2)
    assert eng.router.fit_device_range == (1, 1)
    assert eng.router.clamp_features(256, 0.1, 8, 8)[3] == 1
