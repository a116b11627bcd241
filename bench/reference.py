"""Plain numpy chordality reference: dense LexBFS, then the PEO test.

Independent of the code under test: it imports nothing from ``repro``.
A graph is chordal iff the reverse of any LexBFS order is a perfect
elimination ordering (Rose, Tarjan and Lueker 1976). The PEO test is the
classic one: with v's earlier-visited neighbours E(v) and p(v) the last
of them, reverse LexBFS is a PEO iff E(v) \\ {p(v)} ⊆ N(p(v)) for all v.

Graphs are processed in batches of equal padded size: padding vertices
are isolated, so they change no verdict.
"""
from __future__ import annotations

import concurrent.futures as cf

import numpy as np

#: Steps between rank re-compactions: ranks stay below 2**(11 + 50).
_COMPACT_EVERY = 50


def lexbfs_orders(adjs: np.ndarray) -> np.ndarray:
    """(G, N, N) bool -> (G, N) LexBFS visit orders, one per graph.

    A vertex's label is the sequence of visit steps of its visited
    neighbours; ``rank`` encodes it as an integer (one bit per step,
    earlier steps more significant) that is re-compacted every
    ``_COMPACT_EVERY`` steps. Ties go to the lowest vertex id.
    """
    g, n, _ = adjs.shape
    rank = np.zeros((g, n), dtype=np.int64)
    seen = np.zeros((g, n), dtype=bool)
    order = np.empty((g, n), dtype=np.int64)
    rows = np.arange(g)
    for step in range(n):
        v = np.argmax(np.where(seen, -1, rank), axis=1)
        order[:, step] = v
        seen[rows, v] = True
        rank = 2 * rank + adjs[rows, v]
        if step % _COMPACT_EVERY == _COMPACT_EVERY - 1:
            rank = _dense_rank(rank)
    return order


def _dense_rank(rank: np.ndarray) -> np.ndarray:
    """Replace each row's values by their dense rank within the row."""
    idx = np.argsort(rank, axis=1)
    srt = np.take_along_axis(rank, idx, axis=1)
    new = np.ones(rank.shape, dtype=np.int64)
    new[:, 1:] = srt[:, 1:] != srt[:, :-1]
    out = np.empty_like(rank)
    np.put_along_axis(out, idx, np.cumsum(new, axis=1) - 1, axis=1)
    return out


def peo_ok(adj: np.ndarray, order: np.ndarray) -> bool:
    """Whether the reverse of visit ``order`` is a PEO of ``adj``."""
    n = adj.shape[0]
    if n < 3:
        return True
    a = adj[np.ix_(order, order)]              # a[i, j]: σi ~ σj
    earlier = np.tril(a, -1)
    has = earlier.any(axis=1)
    last = n - 1 - np.argmax(earlier[:, ::-1], axis=1)
    cols = np.arange(n)
    bad = earlier & ~a[last] & (cols[None, :] != last[:, None])
    return not bad[has].any()


def chordal(adjs: np.ndarray, *, reverse: bool = True) -> np.ndarray:
    """(G, N, N) bool -> (G,) reference verdicts.

    ``reverse=False`` is the benchmark's control: the PEO test run over
    the LexBFS order itself instead of its reverse. It breaks the
    guarantee that every verdict is exact, and has to come out as not
    correct on every configuration's traffic.
    """
    adjs = np.asarray(adjs, dtype=bool)
    orders = lexbfs_orders(adjs)
    if not reverse:
        orders = orders[:, ::-1]
    return np.array([peo_ok(a, o) for a, o in zip(adjs, orders)],
                    dtype=bool)


def verdicts(graphs, *, reverse: bool = True, batch_bytes: int = 1 << 26,
             threads: int = 4) -> np.ndarray:
    """Reference verdicts for ``graphs``: a list of (n, dense adjacency
    whose ``[:n, :n]`` prefix is the graph). Graphs are grouped by
    power-of-two padded size and run in batches of at most
    ``batch_bytes`` of adjacency, on ``threads`` threads (numpy lets go
    of the interpreter lock inside each array operation)."""
    out = np.zeros(len(graphs), dtype=bool)
    by_pad: dict = {}
    for i, (n, _) in enumerate(graphs):
        by_pad.setdefault(1 << max(int(n) - 1, 0).bit_length(), []).append(i)
    chunks = []
    for n_pad, idxs in sorted(by_pad.items()):
        per = max(1, batch_bytes // (n_pad * n_pad))
        chunks += [(n_pad, idxs[lo: lo + per])
                   for lo in range(0, len(idxs), per)]

    def run(chunk):
        n_pad, idxs = chunk
        adjs = np.zeros((len(idxs), n_pad, n_pad), dtype=bool)
        for slot, i in enumerate(idxs):
            n, adj = graphs[i]
            adjs[slot, :n, :n] = adj[:n, :n]
        out[idxs] = chordal(adjs, reverse=reverse)

    with cf.ThreadPoolExecutor(threads) as pool:
        list(pool.map(run, chunks))
    return out
