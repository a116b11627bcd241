"""The benchmark's yardstick: the plain numpy reference and the generators.

The reference decides every run's ``correct``, so it is checked on known
answers and against an independent brute-force test (repeatedly delete a
simplicial vertex). The generators make every run's inputs, so they must
be deterministic under a seed and produce the classes they name.
"""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import graphs as G  # noqa: E402
from bench import reference as R  # noqa: E402


def cycle(n):
    a = np.zeros((n, n), dtype=bool)
    i = np.arange(n)
    a[i, (i + 1) % n] = True
    return a | a.T


def brute_chordal(adj):
    """Chordal iff repeatedly deleting a simplicial vertex empties it."""
    alive = list(range(adj.shape[0]))
    while alive:
        for v in alive:
            nb = [u for u in alive if adj[v, u]]
            if all(adj[a, b] for a in nb for b in nb if a != b):
                alive.remove(v)
                break
        else:
            return False
    return True


def one(adj, **kw):
    return bool(R.chordal(adj[None], **kw)[0])


@pytest.mark.parametrize("n", [4, 5, 6, 9, 64])
def test_long_cycles_are_not_chordal(n):
    assert not one(cycle(n))


def test_triangle_and_small_graphs_are_chordal():
    assert one(cycle(3))
    assert one(np.zeros((1, 1), dtype=bool))
    assert one(np.zeros((2, 2), dtype=bool))


@pytest.mark.parametrize("seed", range(3))
def test_trees_cliques_and_k_trees_are_chordal(seed):
    rng = np.random.default_rng(seed)
    graphs = [G.random_tree(200, rng), G.clique(50), G.k_tree(120, rng, k=3),
              G.k_tree(120, rng, k=16)]
    for adj in graphs:
        assert one(adj)


def test_cycle_with_one_chord_is_not_chordal_until_triangulated():
    a = cycle(6)
    a[0, 3] = a[3, 0] = True          # two 4-cycles remain
    assert not one(a)
    a[0, 2] = a[2, 0] = a[0, 4] = a[4, 0] = True
    assert one(a)


def test_agrees_with_brute_force_on_random_graphs():
    rng = np.random.default_rng(7)
    graphs, want = [], []
    for _ in range(150):
        n = int(rng.integers(3, 14))
        adj = G.dense_random(n, rng, p=float(rng.uniform(0.2, 0.9)))
        graphs.append((n, adj))
        want.append(brute_chordal(adj))
    got = R.verdicts(graphs)
    assert got.tolist() == want
    assert 0 < sum(want) < len(want)


def test_batches_threads_and_padding_do_not_change_verdicts():
    """Prefixes of mixed sizes, in other batches, on one thread or four,
    read the same as each graph alone."""
    rng = np.random.default_rng(3)
    pool = [G.k_tree(70, rng, k=3), G.dense_random(70, rng, p=0.2)]
    graphs = [(int(n), pool[i % 2]) for i, n in
              enumerate(rng.integers(3, 71, size=40))]
    alone = [one(adj[:n, :n]) for n, adj in graphs]
    assert 0 < sum(alone) < len(alone)
    for kw in ({}, {"threads": 1}, {"batch_bytes": 1}):
        assert R.verdicts(graphs, **kw).tolist() == alone, kw


def test_control_breaks_exactness_on_chordal_graphs():
    rng = np.random.default_rng(0)
    tree = G.random_tree(300, rng)
    assert one(tree) and not one(tree, reverse=False)
    assert one(G.clique(30), reverse=False)   # every order of a clique works
    assert not one(cycle(8), reverse=False)


@pytest.mark.parametrize("name,kw", [
    ("clique", {}), ("dense_random", {"p": 0.5}),
    ("sparse_random", {"edges_per_vertex": 20}), ("random_tree", {}),
    ("k_tree", {"k": 16}),
])
def test_generators_are_deterministic_and_symmetric(name, kw):
    gen = G.CLASSES[name]
    a = gen(300, np.random.default_rng(11), **kw)
    b = gen(300, np.random.default_rng(11), **kw)
    assert a.dtype == bool and a.shape == (300, 300)
    assert np.array_equal(a, b)
    assert np.array_equal(a, a.T) and not a.diagonal().any()
    if name not in ("clique",):
        c = gen(300, np.random.default_rng(12), **kw)
        assert not np.array_equal(a, c)


def test_class_edge_counts_follow_the_thesis():
    rng = np.random.default_rng(5)
    n = 512
    assert G.clique(n).sum() == n * (n - 1)
    assert abs(G.dense_random(n, rng, p=0.5).sum() / (n * (n - 1)) - 0.5) \
        < 0.02
    m = G.sparse_random(n, rng, edges_per_vertex=20).sum() / 2
    assert 0.95 * 20 * n < m <= 20 * n
    assert G.random_tree(n, rng).sum() / 2 == n - 1
    k = 16
    assert G.k_tree(n, rng, k=k).sum() / 2 == k * n - k * (k + 1) // 2


def test_prefixes_keep_their_class():
    rng = np.random.default_rng(9)
    tree, ktree = G.random_tree(400, rng), G.k_tree(400, rng, k=8)
    for n in (50, 201, 399):
        assert one(tree[:n, :n]) and one(ktree[:n, :n])
        assert tree[:n, :n].sum() / 2 == n - 1
