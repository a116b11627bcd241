"""Cross-backend agreement: every registered backend returns bit-identical
verdicts on the generator zoo, and verdicts are invariant to the padding
bucket a request lands in."""
import numpy as np
import pytest

from repro.core import generators as G
from repro.engine import ChordalityEngine, backend_names

# The zoo: mixed sizes (hits several n_pad buckets) and mixed classes with
# known chordality — cycles non-chordal (n >= 4), the rest chordal except
# sparse_random (verdict varies; the agreement assertion is what matters).
def _zoo():
    return [
        G.random_chordal(21, k=3, subset_p=0.8, seed=0),
        G.cycle(7),
        G.sparse_random(33, avg_degree=5, seed=1),
        G.random_tree(18, seed=2),
        G.random_chordal(45, k=4, subset_p=1.0, seed=3),
        G.cycle(30),
        G.sparse_random(12, avg_degree=4, seed=4),
        G.random_tree(50, seed=5),
        G.cycle(4),
    ]


def _reference_verdicts():
    eng = ChordalityEngine(backend="jax_faithful", max_batch=4)
    return eng.run(_zoo()).verdicts


@pytest.fixture(scope="module")
def ref_verdicts():
    return _reference_verdicts()


@pytest.mark.parametrize(
    "backend", [b for b in backend_names() if b != "jax_faithful"])
def test_backend_agrees_with_faithful_on_zoo(backend, ref_verdicts):
    got = ChordalityEngine(backend=backend, max_batch=4).run(_zoo()).verdicts
    np.testing.assert_array_equal(got, ref_verdicts)


def test_zoo_known_answers(ref_verdicts):
    # Sanity-anchor the reference itself (indices per _zoo above).
    v = ref_verdicts.tolist()
    assert v[0] and v[3] and v[4] and v[7]      # chordal classes
    assert not v[1] and not v[5] and not v[8]   # cycles


@pytest.mark.parametrize("backend", ["jax_faithful", "jax_fast"])
def test_fast_orders_bit_identical(backend):
    """lexbfs_fast must produce the same PEO/witness, not just verdicts."""
    eng = ChordalityEngine(backend=backend)
    ref = ChordalityEngine(backend="jax_faithful")
    for g in (_zoo()[0], _zoo()[1], _zoo()[4]):
        a = eng.certificate(g)
        b = ref.certificate(g)
        assert a.chordal == b.chordal
        assert a.n_violations == b.n_violations
        np.testing.assert_array_equal(a.order, b.order)


# ---------------------------------------------------------------------------
# Padding invariance: same graph, different bucket grids -> same verdict.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", sorted(backend_names()))
@pytest.mark.parametrize("buckets", [(16, 32, 64, 128), (64, 128), (128,)])
def test_verdict_invariant_across_bucket_sizes(backend, buckets):
    graphs = [G.cycle(11), G.random_chordal(13, k=3, seed=7),
              G.sparse_random(24, avg_degree=5, seed=8)]
    base = ChordalityEngine(
        backend=backend, buckets=(16, 32, 64, 128)).run(graphs).verdicts
    got = ChordalityEngine(
        backend=backend, buckets=buckets).run(graphs).verdicts
    np.testing.assert_array_equal(got, base)


@pytest.mark.parametrize("backend", sorted(backend_names()))
def test_batch_padding_slots_do_not_leak(backend):
    """A unit with empty-graph padding slots must not perturb real slots."""
    graphs = [G.cycle(9), G.clique(9), G.cycle(9)]   # batch rounds 3 -> 4
    res = ChordalityEngine(backend=backend, max_batch=4).run(graphs)
    assert res.plan.units[0].n_padding_slots == 1
    assert res.verdicts.tolist() == [False, True, False]


# ---------------------------------------------------------------------------
# pallas_peo's fused runners: a realized bool unit, fresh or in the realize
# pool's buffer, goes to the device as an int8 view of its own bytes;
# anything else is converted once and counted.
# ---------------------------------------------------------------------------
_PACK_N = {"fused": 70, "fused_packed": 9, "fused_witness": 9}


@pytest.mark.parametrize("case", ["bool", "int64", "list", "noncanonical",
                                  "pooled"])
@pytest.mark.parametrize("kind", sorted(_PACK_N))
def test_fused_pack_views_bool_units(monkeypatch, kind, case):
    import jax.numpy as jnp

    from repro import obs
    from repro.core.lexbfs import lexbfs_numpy_dense
    from repro.core.peo import peo_check_numpy
    from repro.engine.backends import PallasPeoBackend, _pack
    from repro.engine.planner import RealizePool, plan_requests, realize_unit

    n = _PACK_N[kind]
    graphs = [G.cycle(n), G.random_chordal(n, k=3, seed=0)]
    (unit,) = plan_requests(graphs, max_batch=2).units
    realized = realize_unit(unit, graphs)
    pool = RealizePool()
    with pool.realized(unit, graphs) as pooled:
        (pool_buf,) = pool.leased()
    backend = PallasPeoBackend(interpret=True, pipeline="fused")
    assert kind == (backend.witness_kind(unit.n_pad) if kind == "fused_witness"
                    else backend.verdict_kind(unit.n_pad))
    run = getattr(backend, f"compile_{kind}_batch")(unit.n_pad, unit.batch)
    payload = {"bool": realized,
               "int64": realized.astype(np.int64),
               "list": realized.tolist(),
               "noncanonical": (realized.view(np.uint8) * 2).view(bool),
               "pooled": pooled}[case]

    sent = []
    real_asarray = jnp.asarray
    monkeypatch.setattr(jnp, "asarray",
                        lambda x, *a, **k: sent.append(x) or
                        real_asarray(x, *a, **k))
    copies = obs.registry.counter("repro_pack_copies_total")
    before = copies.value()
    if kind == "fused_witness":
        out = run(payload, np.array([g.n_nodes for g in graphs])).chordal
    else:
        out = run(payload)

    copied = case in ("int64", "list")
    assert copies.value() - before == int(copied)
    h2d = sent[0]                          # the first copy to the device
    assert h2d.dtype == np.int8
    if case == "bool":
        assert np.shares_memory(h2d, realized)
    if case == "pooled":
        np.testing.assert_array_equal(pooled, realized)
        assert np.shares_memory(h2d, pool_buf)
        assert all(np.shares_memory(a, pool_buf) for a in _pack(pooled))
    if case == "int64":
        assert not np.shares_memory(h2d, payload)
    want = [peo_check_numpy(a, lexbfs_numpy_dense(a)) for a in realized]
    assert want == [n <= 3, True]
    assert np.asarray(out).tolist() == want
