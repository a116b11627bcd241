"""repro.sparse: CSR container, padded packing, LexBFS/PEO parity.

The load-bearing invariants:
* CSRGraph round-trips dense <-> CSR and builds from every Graph view.
* Both CSR LexBFS implementations (device scan, host batched numpy) are
  BIT-IDENTICAL to the dense reference on padded inputs.
* CSR PEO violation counts equal the dense counts (same (v, z) pairs).
* Verdicts are invariant under nnz_pad / deg_pad growth (padded-CSR
  contract: sentinel edges and empty rows never change an answer).
"""
import numpy as np
import pytest

from repro.configs.shapes import engine_deg_bucket, engine_nnz_bucket
from repro.core import generators as G
from repro.core.lexbfs import lexbfs_numpy_dense
from repro.core.peo import peo_violations_numpy
from repro.graphs.structure import Graph
from repro.sparse import (
    CSRGraph,
    is_chordal_csr,
    lexbfs_csr,
    lexbfs_csr_numpy_batch,
    pack_csr_batch,
    pack_dense_batch,
    peo_violations_csr,
    peo_violations_csr_numpy_batch,
)


def _zoo():
    return [
        G.sparse_erdos_renyi(40, c=4, seed=0),
        G.cycle(23),
        G.long_cycle(37, n_chords=4, seed=1),
        G.random_tree(31, seed=2),
        G.k_tree(29, k=3, seed=3),
        G.gnp(26, 0.3, seed=4),
        G.clique(9),
        G.path(2),
        # Last graph with edges: its final row's last edge must count in
        # the host segment max although every row after it is empty.
        G.gnp(19, 0.179, seed=3340),
        Graph(n_nodes=3),                 # empty graph, no arrays at all
    ]


# ---------------------------------------------------------------------------
# CSRGraph container
# ---------------------------------------------------------------------------
def test_csr_roundtrip_dense():
    for g in _zoo():
        g = g.with_dense()
        c = CSRGraph.from_dense(g.adj, g.n_nodes)
        np.testing.assert_array_equal(
            c.to_dense(), g.adj[: g.n_nodes, : g.n_nodes])
        # columns sorted within each row
        for v in range(c.n_nodes):
            row = c.col_idx[c.row_ptr[v]: c.row_ptr[v + 1]]
            assert (np.diff(row) > 0).all()


def test_csr_from_graph_prefers_edge_list():
    g = G.sparse_erdos_renyi(50, c=5, seed=7)
    assert g.edges is not None
    lean = Graph(n_nodes=g.n_nodes, edges=g.edges)   # no dense view at all
    c = CSRGraph.from_graph(lean)
    c_dense = CSRGraph.from_dense(g.with_dense().adj, g.n_nodes)
    np.testing.assert_array_equal(c.row_ptr, c_dense.row_ptr)
    np.testing.assert_array_equal(c.col_idx, c_dense.col_idx)


def test_csr_from_edges_dedups_and_symmetrizes():
    edges = np.array([[0, 0, 1, 2, 2], [1, 1, 0, 2, 0]], dtype=np.int32)
    c = CSRGraph.from_edges(3, edges)     # dup 0-1 both ways, loop 2-2
    want = np.zeros((3, 3), dtype=bool)
    want[0, 1] = want[1, 0] = want[0, 2] = want[2, 0] = True
    np.testing.assert_array_equal(c.to_dense(), want)
    assert c.nnz == 4 and c.n_edges == 2


def test_csr_stats():
    c = CSRGraph.from_graph(G.cycle(10))
    s = c.stats()
    assert s["n"] == 10 and s["nnz"] == 20 and s["n_edges"] == 10
    assert s["max_degree"] == 2 and s["mean_degree"] == 2.0
    assert s["density"] == pytest.approx(0.2)
    # CSR wins memory once n outgrows the fixed row_ptr overhead:
    big = CSRGraph.from_graph(G.cycle(1000)).stats()
    assert big["csr_bytes"] < big["dense_bytes"]


def test_prepadded_graph_slices_to_logical_block():
    from repro.graphs.structure import pad_graph

    g = pad_graph(G.cycle(9), 64)
    c = CSRGraph.from_graph(g)
    assert c.n_nodes == 9 and c.nnz == 18


# ---------------------------------------------------------------------------
# Packing
# ---------------------------------------------------------------------------
def test_pack_shapes_and_sentinels():
    csrs = [CSRGraph.from_graph(g) for g in (_zoo()[:4])]
    packed = pack_csr_batch(csrs, n_pad=64, batch=6)
    assert packed.row_ptr.shape == (6, 65)
    assert packed.col_idx.shape[0] == 6
    assert packed.nnz_pad == engine_nnz_bucket(max(c.nnz for c in csrs))
    assert packed.deg_pad == engine_deg_bucket(
        max(c.max_degree for c in csrs), 64)
    for i, c in enumerate(csrs):
        assert packed.row_ptr[i, -1] == c.nnz
        assert (packed.col_idx[i, c.nnz:] == 64).all()   # sentinel tail
    assert (packed.row_ptr[4:] == 0).all()               # empty slots
    assert (packed.col_idx[4:] == 64).all()


def test_pack_rejects_undersized_pads():
    c = CSRGraph.from_graph(G.clique(8))
    with pytest.raises(ValueError, match="deg_pad"):
        pack_csr_batch([c], n_pad=16, deg_pad=4)
    with pytest.raises(ValueError, match="nnz_pad"):
        pack_csr_batch([c], n_pad=16, nnz_pad=16)
    with pytest.raises(ValueError, match="n_pad"):
        pack_csr_batch([c], n_pad=4)


def test_pack_dense_batch_matches_per_graph_csr():
    graphs = [g.with_dense() for g in _zoo()[:3]]
    n_pad = 64
    adjs = np.zeros((3, n_pad, n_pad), dtype=bool)
    for i, g in enumerate(graphs):
        n = g.n_nodes
        adjs[i, :n, :n] = g.adj[:n, :n]
    packed = pack_dense_batch(adjs)
    for i, g in enumerate(graphs):
        c = CSRGraph.from_dense(g.adj, g.n_nodes)
        assert packed.row_ptr[i, -1] == c.nnz
        np.testing.assert_array_equal(packed.col_idx[i, : c.nnz], c.col_idx)


# ---------------------------------------------------------------------------
# LexBFS parity (bit-identical orders) and PEO count parity
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def packed_zoo():
    csrs = [CSRGraph.from_graph(g) for g in _zoo()]
    return _zoo(), pack_csr_batch(csrs, n_pad=48, batch=len(csrs) + 1)


def _dense_padded(g, n_pad):
    g = g.with_dense()
    adj = np.zeros((n_pad, n_pad), dtype=bool)
    n = g.n_nodes
    adj[:n, :n] = g.adj[:n, :n]
    return adj


def test_host_lexbfs_bit_identical_to_dense_reference(packed_zoo):
    graphs, packed = packed_zoo
    orders = lexbfs_csr_numpy_batch(
        packed.row_ptr, packed.col_idx, packed.deg_pad)
    for i, g in enumerate(graphs):
        ref = lexbfs_numpy_dense(_dense_padded(g, packed.n_pad))
        np.testing.assert_array_equal(orders[i], ref)


def test_device_lexbfs_bit_identical_to_dense_reference(packed_zoo):
    import jax

    graphs, packed = packed_zoo
    rp, ci = packed.device_arrays()
    orders = jax.vmap(
        lambda a, b: lexbfs_csr(a, b, packed.deg_pad))(rp, ci)
    for i, g in enumerate(graphs):
        ref = lexbfs_numpy_dense(_dense_padded(g, packed.n_pad))
        np.testing.assert_array_equal(np.asarray(orders[i]), ref)
    # host and device agree on the padding slot too (empty graph)
    host = lexbfs_csr_numpy_batch(
        packed.row_ptr, packed.col_idx, packed.deg_pad)
    np.testing.assert_array_equal(np.asarray(orders), host)


def test_peo_violation_counts_match_dense(packed_zoo):
    import jax

    graphs, packed = packed_zoo
    orders = lexbfs_csr_numpy_batch(
        packed.row_ptr, packed.col_idx, packed.deg_pad)
    viol_host = peo_violations_csr_numpy_batch(
        packed.row_ptr, packed.col_idx, orders)
    rp, ci = packed.device_arrays()
    import jax.numpy as jnp

    viol_dev = jax.vmap(peo_violations_csr)(rp, ci, jnp.asarray(orders))
    for i, g in enumerate(graphs):
        adj = _dense_padded(g, packed.n_pad)
        ref = peo_violations_numpy(adj, orders[i])
        assert viol_host[i] == ref
        assert int(viol_dev[i]) == ref
    assert viol_host[-1] == 0             # padding slot: empty graph


@pytest.mark.parametrize("grow_nnz,grow_deg", [(2, 1), (1, 2), (4, 4)])
def test_padded_csr_invariance(grow_nnz, grow_deg):
    """Verdict and violation count unchanged under nnz_pad/deg_pad growth."""
    graphs = [G.cycle(15), G.k_tree(20, k=3, seed=0),
              G.sparse_erdos_renyi(24, c=4, seed=1)]
    csrs = [CSRGraph.from_graph(g) for g in graphs]
    base = pack_csr_batch(csrs, n_pad=32)
    grown = pack_csr_batch(
        csrs, n_pad=32, nnz_pad=base.nnz_pad * grow_nnz,
        deg_pad=min(base.deg_pad * grow_deg, 32))
    o1 = lexbfs_csr_numpy_batch(base.row_ptr, base.col_idx, base.deg_pad)
    o2 = lexbfs_csr_numpy_batch(grown.row_ptr, grown.col_idx, grown.deg_pad)
    np.testing.assert_array_equal(o1, o2)
    v1 = peo_violations_csr_numpy_batch(base.row_ptr, base.col_idx, o1)
    v2 = peo_violations_csr_numpy_batch(grown.row_ptr, grown.col_idx, o2)
    np.testing.assert_array_equal(v1, v2)


def test_is_chordal_csr_known_classes():
    cases = [
        (G.random_tree(40, seed=0), True),
        (G.k_tree(40, k=4, seed=1), True),
        (G.cycle(4), False),
        (G.long_cycle(60), False),
        (G.clique(12), True),
    ]
    for g, want in cases:
        c = CSRGraph.from_graph(g)
        assert is_chordal_csr(c, pipeline="host") is want
        assert is_chordal_csr(c, pipeline="device") is want


# ---------------------------------------------------------------------------
# Sparse generators
# ---------------------------------------------------------------------------
def test_sparse_er_density_scales_as_c_over_n():
    g = G.sparse_erdos_renyi(400, c=6, seed=0)
    c = CSRGraph.from_graph(g)
    assert 2.0 < c.stats()["mean_degree"] < 10.0
    assert g.edges is not None            # no-densify path available


def test_long_cycle_chords():
    g = G.long_cycle(50, n_chords=5, seed=0)
    c = CSRGraph.from_graph(g)
    assert c.n_edges >= 50 and c.n_edges <= 55


def test_k_tree_edge_count_and_chordality():
    n, k = 30, 3
    g = G.k_tree(n, k=k, seed=2)
    c = CSRGraph.from_graph(g)
    assert c.n_edges == k * n - k * (k + 1) // 2
    assert is_chordal_csr(c)


def test_sparse_classes_registry():
    for name, gen in G.SPARSE_CLASSES.items():
        g = gen(30)
        assert g.n_nodes == 30, name


# ---------------------------------------------------------------------------
# Acceptance: csr agrees with numpy_ref on >= 200 generated graphs
# (chordal and non-chordal, n up to 512), through the engine.
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_csr_agrees_with_numpy_ref_on_200_graphs():
    from repro.engine import ChordalityEngine

    rng = np.random.default_rng(2025)
    gens = [
        lambda n, s: G.random_tree(n, seed=s),
        lambda n, s: G.long_cycle(n, n_chords=int(n // 16), seed=s),
        lambda n, s: G.k_tree(n, k=int(rng.integers(2, 5)), seed=s),
        lambda n, s: G.sparse_erdos_renyi(n, c=float(rng.uniform(2, 8)),
                                          seed=s),
        lambda n, s: G.cycle(n),
        lambda n, s: G.gnp(n, 0.15, seed=s),
    ]
    graphs = []
    # Mostly small (fast), a tail up to n=512; few distinct buckets keep
    # the compile bill bounded.
    for s in range(200):
        if s % 25 == 0:
            n = int(rng.integers(300, 513))
        else:
            n = int(rng.integers(4, 97))
        graphs.append(gens[s % len(gens)](n, s))
    csr = ChordalityEngine(backend="csr", max_batch=32).run(graphs)
    ref = ChordalityEngine(backend="numpy_ref", max_batch=32).run(graphs)
    np.testing.assert_array_equal(csr.verdicts, ref.verdicts)
    # the stream genuinely mixes verdicts
    assert 20 < csr.verdicts.sum() < 180
