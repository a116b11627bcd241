"""Execution planner — variable-size requests to fixed-shape work units.

jit'd device code wants fixed shapes; serving traffic is ragged. The planner
closes that gap with two rounds of power-of-two bucketing:

* **n_pad bucket** — every graph pads up to the smallest bucket in
  ``repro.configs.shapes.ENGINE_NPAD_BUCKETS`` that holds it (padding
  vertices are isolated and never change the verdict, see
  ``repro.graphs.structure.pad_graph``).
* **batch bucket** — requests sharing an n_pad bucket are chunked to
  ``max_batch``; a trailing partial chunk rounds its batch dimension up to
  a power of two (empty-graph padding slots, masked out of the results).

The result: for a given engine config, at most
``len(ENGINE_NPAD_BUCKETS) * (log2(max_batch) + 1)`` distinct compiled
shapes ever exist, regardless of traffic. :class:`CompileCache` holds those
executables, keyed on ``(backend, cache_scope, kind, n_pad, batch)`` —
the scope pins each program to the platform/device (or mesh slice) it
was compiled against.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.configs.shapes import engine_batch_bucket
from repro.graphs.structure import Graph, bucket_graphs

# Process-wide cache traffic, aggregated across every CompileCache
# instance (each cache also keeps its own int counters for per-engine
# stats). Steady-state serving shows hits climbing while misses stay
# flat — the compile-amortization story as a scrapeable metric.
_M_CACHE_HITS = obs.registry.counter(
    "repro_compile_cache_hits_total", "compile-cache executable reuses")
_M_CACHE_MISSES = obs.registry.counter(
    "repro_compile_cache_misses_total",
    "compile-cache misses (each builds an executable)")
# XLA compiles themselves happen at an executable's first call, not when
# the cache builds it: they are counted by repro.obs.compiles.
obs.install_compile_listener()


@dataclasses.dataclass(frozen=True)
class WorkUnit:
    """One fixed-shape batch: ``batch`` slots padded to ``n_pad`` vertices.

    ``indices`` are the request positions filled into slots ``0..len-1``;
    remaining slots (up to ``batch``) are empty-graph padding. ``backend``
    is the router's per-unit choice under ``ChordalityEngine("auto")``
    (None = use the engine's fixed backend) — it is plan metadata callers
    can inspect via ``plan.unit_of(i).backend``.
    """

    n_pad: int
    batch: int
    indices: Tuple[int, ...]
    backend: Optional[str] = None

    @property
    def n_padding_slots(self) -> int:
        return self.batch - len(self.indices)


@dataclasses.dataclass
class Plan:
    """The shape plan for one request stream."""

    units: List[WorkUnit]
    n_requests: int

    @property
    def bucket_histogram(self) -> Dict[int, int]:
        """{n_pad: number of requests} over the whole plan."""
        hist: Dict[int, int] = {}
        for u in self.units:
            hist[u.n_pad] = hist.get(u.n_pad, 0) + len(u.indices)
        return hist

    def unit_of(self, request_index: int) -> WorkUnit:
        """The work unit a given request was scheduled into."""
        for u in self.units:
            if request_index in u.indices:
                return u
        raise IndexError(f"request {request_index} not in plan")


def plan_requests(
    graphs: Sequence[Graph],
    max_batch: int = 64,
    buckets: Optional[Sequence[int]] = None,
) -> Plan:
    """Bucket + chunk a request stream into fixed-shape work units."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    units: List[WorkUnit] = []
    for n_pad, idxs in sorted(bucket_graphs(graphs, buckets).items()):
        for lo in range(0, len(idxs), max_batch):
            chunk = tuple(idxs[lo: lo + max_batch])
            units.append(WorkUnit(
                n_pad=n_pad,
                batch=engine_batch_bucket(len(chunk), max_batch),
                indices=chunk,
            ))
    return Plan(units=units, n_requests=len(graphs))


def unit_for_chunk(
    n_pad: int,
    count: int,
    max_batch: int,
    backend: Optional[str] = None,
) -> WorkUnit:
    """One work unit for ``count`` requests already grouped in an n_pad
    bucket — the admission-time entry point the async service uses.

    Unlike :func:`plan_requests` (which schedules a whole stream at once),
    the caller here has *drained a bucket*: the requests are consecutive, so
    indices are local positions ``0..count-1`` into the drained chunk. The
    batch dimension rounds up exactly like a trailing partial chunk in a
    plan, so the compile-cache keys are shared with the synchronous path.
    """
    if count < 1:
        raise ValueError(f"count must be >= 1, got {count}")
    if count > max_batch:
        raise ValueError(
            f"count {count} exceeds max_batch {max_batch}; drain earlier")
    return WorkUnit(
        n_pad=n_pad,
        batch=engine_batch_bucket(count, max_batch),
        indices=tuple(range(count)),
        backend=backend,
    )


def realize_unit(
    unit: WorkUnit, graphs: Sequence[Graph]
) -> np.ndarray:
    """Materialize a work unit's (batch, n_pad, n_pad) bool adjacency batch.

    Padding slots are all-zero adjacencies (empty graphs — trivially
    chordal); their verdicts are dropped by the session layer. Graphs whose
    stored adjacency is already padded beyond ``n_nodes`` are sliced down
    first — their padding vertices are isolated by contract, so the logical
    (n_nodes, n_nodes) block carries the whole graph.
    """
    out = np.zeros((unit.batch, unit.n_pad, unit.n_pad), dtype=bool)
    for slot, idx in enumerate(unit.indices):
        g = graphs[idx].with_dense()
        n = g.n_nodes
        out[slot, :n, :n] = g.adj[:n, :n]
    return out


def realize_unit_csr(unit: WorkUnit, graphs: Sequence[Graph]):
    """Materialize a work unit as a :class:`~repro.sparse.PackedCSRBatch`.

    The sparse twin of :func:`realize_unit`: graphs carrying edge-list or
    CSR views never touch a dense matrix, so the unit's host footprint is
    O(B·(N + M)) instead of O(B·N²) — this is what lifts the practical N
    cap for sparse traffic. Padding slots are empty graphs, padding
    vertices empty rows; both are verdict-invariant (packing contract).
    """
    from repro.sparse.format import CSRGraph
    from repro.sparse.packing import pack_csr_batch

    csrs = [CSRGraph.from_graph(graphs[i]) for i in unit.indices]
    return pack_csr_batch(csrs, n_pad=unit.n_pad, batch=unit.batch)


class CompileCache:
    """Executable cache keyed on (backend name, cache scope, kind, n_pad,
    batch).

    ``scope`` is ``backend.cache_scope()`` — the platform + device (or
    mesh slice) the executable is pinned to: ``"host"`` for host
    backends, ``"cpu:0"``-style for single-device jit backends,
    ``"cpu:mesh8"`` for mesh-sharded ones (DESIGN.md §16). Two backends
    that differ only in device placement (a 4- vs an 8-device mesh, or
    the same code on CPU vs TPU) therefore never share a compiled
    program.

    ``kind`` selects the executable family: ``"verdict"`` programs come
    from ``backend.compile_batch``, ``"fused"`` programs (the whole unit
    in one device dispatch, e.g. the single-pass LexBFS+PEO Pallas
    kernel) from ``backend.compile_fused_batch``, ``"fused_packed"``
    programs (G graphs block-diagonal per grid program for tiny buckets)
    from ``backend.compile_fused_packed_batch``, ``"witness"`` programs
    (verdict + certificate extraction in one fused pass, see
    ``repro.witness``) from ``backend.compile_witness_batch``, and
    ``"fused_witness"`` programs (the Pallas kernel emitting certificate
    raw material alongside the verdict in the same dispatch) from
    ``backend.compile_fused_witness_batch``, and ``"recognition:<p1,p2>"``
    programs (the shared-sweep multi-property executables of
    ``repro.recognition``, one cache entry per *normalized* property
    tuple) from ``backend.compile_recognition_batch``. All ride
    the same bucket grid, so enabling a family adds at most one extra
    compile per bucket shape; the session picks the verdict family per
    bucket via ``backend.verdict_kind(n_pad)`` and the witness family
    via ``backend.witness_kind(n_pad)``. A
    miss builds the executable; a device backend's XLA compile happens
    at its first call (``repro_xla_compiles_total``,
    :mod:`repro.obs.compiles`). A hit reuses the executable. The
    hit/miss counters feed the engine's stats — in steady-state serving,
    misses stay flat.
    """

    def __init__(self):
        self._fns: Dict[Tuple[str, str, str, int, int], Callable] = {}
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._fns)

    def keys(self):
        """Cached ``(backend, scope, kind, n_pad, batch)`` keys."""
        return list(self._fns)

    def get(self, backend, n_pad: int, batch: int,
            kind: str = "verdict") -> Callable:
        scope = backend.cache_scope()
        key = (backend.name, scope, kind, n_pad, batch)
        fn = self._fns.get(key)
        if fn is None:
            self.misses += 1
            _M_CACHE_MISSES.inc()
            if kind == "verdict":
                fn = backend.compile_batch(n_pad, batch)
            elif kind == "fused":
                fn = backend.compile_fused_batch(n_pad, batch)
            elif kind == "fused_packed":
                fn = backend.compile_fused_packed_batch(n_pad, batch)
            elif kind == "witness":
                fn = backend.compile_witness_batch(n_pad, batch)
            elif kind == "fused_witness":
                fn = backend.compile_fused_witness_batch(n_pad, batch)
            elif kind.startswith("recognition:"):
                props = tuple(kind[len("recognition:"):].split(","))
                fn = backend.compile_recognition_batch(n_pad, batch, props)
            else:
                raise ValueError(f"unknown executable kind {kind!r}")
            self._fns[key] = fn
        else:
            self.hits += 1
            _M_CACHE_HITS.inc()
        return fn
