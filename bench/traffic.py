"""The one general request generator: a graph source and an arrival plan.

The configuration's ``family`` says what a request is; the traffic mix
says when requests arrive. Both are data; everything is drawn from the
run's seed, so the same seed gives the same requests in the same order.

The family (``prefix_pool``): a pool of ``pool_n``-vertex graphs per
class; a request is the induced prefix ``adj[:n, :n]`` of a pool graph (a
free view). The size range is cut into ``strata`` equal sub-ranges, and
requests come in blocks of ``strata``, one size from each sub-range, in
an order drawn from the seed: every seed sends the same mix of sizes, so
the seed changes which graphs are sent and not how much work they are.
Classes take equal shares: over ``len(classes)`` blocks every (class,
sub-range) pair comes once. No (pool graph, n) pair is sent twice.

Arrival plans (traffic ``mode``):

* ``closed`` — ``outstanding`` requests in flight; each answer frees a
  slot for the next request, which is due the moment the slot frees.
* ``poisson`` — open loop at ``rate`` requests/s. The gaps are the
  exponential distribution's quantiles at evenly spaced levels, in an
  order drawn from the seed, scaled to the window: every seed offers the
  same number of requests and the same set of gaps.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import graphs as G


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), *stream])


@dataclasses.dataclass
class Source:
    """Requests in the order they are sent: ``count`` distinct ones, then
    the stream wraps (a repeat, counted by :meth:`repeat_share`)."""

    count: int
    n_nodes: np.ndarray     # (count,) vertices of each request
    adj: list               # (count,) the pool graph each request cuts
    keys: np.ndarray        # (count,) distinct (pool graph, n) keys

    def graph(self, i: int):
        """Request ``i`` as the ``repro`` Graph it is submitted as."""
        from repro.graphs.structure import Graph

        n, adj = self.payload(i)
        return Graph(n_nodes=n, adj=adj[:n, :n])

    def payload(self, i: int):
        """(n, the pool graph's dense adjacency; its prefix is the graph)."""
        k = i % self.count
        return int(self.n_nodes[k]), self.adj[k]

    def repeat_share(self, sent: int) -> float:
        """Share of the first ``sent`` requests whose adjacency equals
        that of an earlier request in the same run."""
        if sent <= 0:
            return 0.0
        keys = self.keys[np.arange(sent) % self.count]
        return 1.0 - len(np.unique(keys)) / sent


def prefix_pool(family: dict, seed: int) -> Source:
    """Thesis-style traffic: induced prefixes of per-class pool graphs."""
    n_pool = int(family["pool_n"])
    lo, hi = map(int, family["n_range"])
    k = int(family["strata"])
    edges = lo + np.rint(np.arange(k + 1) * (hi + 1 - lo) / k).astype(int)
    classes = family["classes"]
    pool, pairs = [], []                # pairs[c][s]: shuffled (graph, n)
    for c, cls in enumerate(classes):
        gen = G.CLASSES[cls["generator"]]
        variants = cls.get("variants") or [{}]
        first = len(pool)
        for j in range(int(cls["graphs"])):
            pool.append(gen(n_pool, _rng(seed, c, j),
                            **variants[j % len(variants)]))
        per = []
        for s in range(k):
            sizes = np.arange(edges[s], edges[s + 1])
            g = np.repeat(np.arange(first, len(pool)), len(sizes))
            n = np.tile(sizes, len(pool) - first)
            perm = _rng(seed, c, s, 1 << 20).permutation(len(g))
            per.append((g[perm], n[perm]))
        pairs.append(per)
    rounds = min(len(g) for per in pairs for g, _ in per)
    rng = _rng(seed, 1 << 21)
    gidx, nidx = [], []
    for r in range(rounds):             # a round: len(classes) blocks
        shift = rng.permutation(len(classes))
        for b in range(len(classes)):
            block = [((s + shift[b]) % len(classes), s) for s in range(k)]
            for j in rng.permutation(k):
                c, s = block[j]
                gidx.append(pairs[c][s][0][r])
                nidx.append(pairs[c][s][1][r])
    gidx, nidx = np.array(gidx), np.array(nidx)
    return Source(count=len(gidx), n_nodes=nidx,
                  adj=[pool[g] for g in gidx], keys=gidx * (hi + 1) + nidx)


FAMILIES = {"prefix_pool": prefix_pool}


def source(config: dict, seed: int) -> Source:
    family = config["family"]
    return FAMILIES[family["kind"]](family, seed)


def poisson_due(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from window start) of an open-loop Poisson stream:
    ``round(rate · seconds)`` requests inside ``[0, seconds)``."""
    k = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(k) + 0.5) / k) / rate
    gaps = _rng(seed, 1 << 22).permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


def sample(count: int, size: int, seed: int) -> np.ndarray:
    """Indices of the answers the reference checks, drawn from the seed."""
    if count <= size:
        return np.arange(count)
    return np.sort(_rng(seed, 1 << 23).choice(count, size, replace=False))
