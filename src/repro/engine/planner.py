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

import contextlib
import dataclasses
import threading
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

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
# Dense units realized into a pooled host buffer (RealizePool): a reuse
# fills a buffer kept from an earlier unit, an allocation makes a new one.
# Their ratio is the pool's hit share.
_M_REALIZE_REUSES = obs.registry.counter(
    "repro_realize_buffer_reuses_total",
    "dense work units realized into a host buffer kept from an earlier unit")
_M_REALIZE_ALLOCS = obs.registry.counter(
    "repro_realize_buffer_allocs_total",
    "host unit buffers allocated by the realize pool")
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


class _UnitBuffer:
    """One pooled host buffer: a C-contiguous ``(capacity, n_pad, n_pad)``
    bool array, plus per slot the extent ``p`` such that everything
    outside ``[:p, :p]`` of that slot is zero."""

    __slots__ = ("array", "extents")

    def __init__(self, n_pad: int, capacity: int):
        self.array = np.zeros((capacity, n_pad, n_pad), dtype=bool)
        self.extents = [0] * capacity

    @property
    def capacity(self) -> int:
        return self.array.shape[0]

    def fill(self, unit: WorkUnit, graphs: Sequence[Graph]) -> np.ndarray:
        """Realize ``unit`` into the leading ``unit.batch`` slots; returns
        that view, equal to :func:`realize_unit`'s array. Each slot copies
        its graph's ``[:n, :n]`` block and zeroes only what the slot's last
        graph left outside it; padding slots are zeroed over their old
        extent."""
        arr, extents = self.array, self.extents
        for slot in range(unit.batch):
            n = 0
            if slot < len(unit.indices):
                g = graphs[unit.indices[slot]].with_dense()
                n = g.n_nodes
                arr[slot, :n, :n] = g.adj[:n, :n]
            p = extents[slot]
            if p > n:
                arr[slot, :n, n:p] = False
                arr[slot, n:p, :p] = False
            extents[slot] = n
        return arr[: unit.batch]


class RealizePool:
    """Host buffers for dense work units, kept from one unit to the next.

    A fresh ``(batch, n_pad, n_pad)`` array per unit (128 MB at B=32,
    n_pad=2048) costs a page fault and a kernel zero per page before the
    copy even starts; a kept buffer is only rewritten where it must be
    (:meth:`_UnitBuffer.fill`). Buffers are keyed by ``n_pad``; each has
    the capacity of the largest batch asked for at its ``n_pad``, and a
    unit uses the leading ``[:batch]`` view, which is contiguous, so the
    fused dispatch still hands the device the buffer's own bytes.

    :meth:`realized` checks a buffer out and returns it when its block
    ends: callers keep the block open until the unit's executable has
    returned host verdicts, by which time the device has read the input.
    Checkout and return hold a lock, so executor lanes running at once
    hold different buffers, and a new buffer is made only when every
    buffer at that ``n_pad`` is out or too small: the pool never holds
    more buffers per ``n_pad`` than units were ever in flight at once
    there. A block that raises (in realize or in the executable) drops
    its buffer instead of trusting its extents.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._idle: Dict[int, List[_UnitBuffer]] = {}
        self._capacity: Dict[int, int] = {}
        self._leased: Dict[int, _UnitBuffer] = {}
        self.reuses = 0
        self.allocs = 0

    def __len__(self) -> int:
        """Buffers held, idle and checked out."""
        with self._lock:
            return (sum(len(v) for v in self._idle.values())
                    + len(self._leased))

    def leased(self) -> List[np.ndarray]:
        """The arrays of the buffers checked out now."""
        with self._lock:
            return [b.array for b in self._leased.values()]

    def _checkout(self, n_pad: int, batch: int) -> _UnitBuffer:
        with self._lock:
            cap = max(batch, self._capacity.get(n_pad, 0))
            self._capacity[n_pad] = cap
            idle = self._idle.get(n_pad)
            buf = idle.pop() if idle else None
            if buf is not None and buf.capacity >= batch:
                self.reuses += 1
                _M_REALIZE_REUSES.inc()
            else:
                buf = _UnitBuffer(n_pad, cap)
                self.allocs += 1
                _M_REALIZE_ALLOCS.inc()
            if id(buf) in self._leased:
                raise RuntimeError("realize buffer checked out twice")
            self._leased[id(buf)] = buf
            return buf

    def _return(self, buf: _UnitBuffer, keep: bool) -> None:
        with self._lock:
            if self._leased.pop(id(buf), None) is None:
                raise RuntimeError("realize buffer returned twice")
            if keep:
                self._idle.setdefault(buf.array.shape[1], []).append(buf)

    @contextlib.contextmanager
    def realized(self, unit: WorkUnit,
                 graphs: Sequence[Graph]) -> Iterator[np.ndarray]:
        """Check out a buffer, realize ``unit`` into it and yield the
        ``(batch, n_pad, n_pad)`` view; the buffer goes back to the pool
        when the block exits cleanly and is dropped when it raises."""
        buf = self._checkout(unit.n_pad, unit.batch)
        try:
            yield buf.fill(unit, graphs)
        except BaseException:
            self._return(buf, keep=False)
            raise
        self._return(buf, keep=True)


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
