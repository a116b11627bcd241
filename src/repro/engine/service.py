"""Async serving layer — ``AsyncChordalityEngine``: queue in, futures out.

The synchronous session (``ChordalityEngine.run``) needs the whole request
stream up front; a service sees requests one at a time. This module closes
that gap with the classic serving triad:

* **bounded admission queue** — ``submit`` buckets each request by n_pad
  (the planner's grid) and appends it to that bucket's pending deque;
  beyond ``max_queue`` outstanding requests it rejects (or blocks, with a
  timeout) so queue delay stays finite under overload.
* **micro-batching admission loop** (background thread) — a bucket drains
  into a work unit as soon as it *fills* (``max_batch`` requests) or its
  oldest request has waited ``max_wait_ms``; the drained chunk becomes a
  :func:`~repro.engine.planner.unit_for_chunk` work unit, routed per unit
  by the engine's router (``backend="auto"`` is the default serving path).
* **background executor lanes** — routed units land on per-lane deques
  (one executor thread per lane, ``ServiceConfig.n_lanes``; the default 1
  is the classic single-executor service). Admission dispatches each unit
  to the least-loaded lane (weighted by ``lane_weights``) and an idle
  lane steals from the most-loaded lane's tail, so a slow lane — a slow
  device, in the mesh deployment of DESIGN.md §16 — never stalls the
  admission loop or starves the other lanes. Lane ``i`` is bound to
  ``jax.local_devices()[i % n]`` (with one lane or one device, to JAX's
  default device): it runs every verdict unit it takes, stolen ones
  too, on a one-device backend pinned there, and all lanes share a
  ``sharded`` backend's one mesh. Every lane drives the session's
  single execution path (``ChordalityEngine.execute_unit``): same
  compile cache (scoped per device), same realize contract (dense or
  padded-CSR), so admission overlaps execution and the compiled-shape
  universe is identical to offline runs.

Each ``submit`` returns a ``concurrent.futures.Future`` resolving to a
:class:`ServiceResponse` (verdict, optional certificate, optional checkable
witness, queue/execution latency, and where it ran). Futures support
cancellation until their unit starts executing. ``flush`` force-drains
partial buckets and waits for an empty backlog; ``shutdown`` (also via
``with``) stops admission, optionally drains, and joins both threads.
:class:`ServiceStats` aggregates queue-delay percentiles, the
batch-occupancy histogram, and the backend mix.

Three client-surface extras on top of the triad:

* **witnesses** — ``submit(want_witness=True)`` resolves the future with a
  ``repro.witness.WitnessResult`` (clique tree / treewidth / coloring, or
  a chordless-cycle counterexample). If any request in a drained unit
  wants one, the whole unit runs the fused witness executable — same
  buckets, same compile cache (``kind="witness"``).
* **deadlines** — ``ServiceConfig.deadline_ms`` (or per-request
  ``submit(deadline_ms=...)``): requests still in the admission queue past
  their deadline are dropped, their futures cancelled,
  ``ServiceStats.n_expired`` incremented. Under overload this sheds the
  stalest work instead of serving answers nobody is waiting for anymore.
* **asyncio** — :meth:`AsyncChordalityEngine.asubmit` wraps the
  thread-based future for ``await``-style clients (coroutine servers,
  ``asyncio.gather`` fan-in); see examples/serve_chordality.py.
* **recognition** — ``submit(properties=["proper_interval", ...])``
  resolves the future with per-property verdicts plus the request's
  ``repro.recognition.RecognitionResult``. Like the witness upgrade, one
  recognizing request upgrades its whole unit: the unit runs a single
  shared-sweep recognition executable compiled for the *union* of the
  live requests' property sets (``kind="recognition:<props>"``), and each
  response is filtered back down to what its request asked for.
* **priorities** — ``submit(priority=...)`` assigns the request a class
  from ``ServiceConfig.priority_weights``; each bucket drains its
  classes in smooth weighted-fair order (:class:`_BucketQueue`), so
  high-priority traffic gets proportionally more unit slots under
  contention without starving anyone.
* **autotune** — ``ServiceConfig.autotune=AutotuneConfig(...)`` closes
  the control loops (``repro.engine.autotune``, DESIGN.md §14): the
  wait window adapts per bucket (AIMD on occupancy/queue-delay p95),
  the router re-fits continuously from live unit samples, and queued
  deadlined work projected to miss is shed lowest-priority-first
  (``ServiceStats.n_shed`` / ``shed_by_priority``).
"""
from __future__ import annotations

import dataclasses
import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Deque, Dict, List, Optional, Sequence, Tuple, Union

import collections

import numpy as np

from repro import obs
from repro.obs import clock as _clock
from repro.configs.service import ServiceConfig
from repro.engine.autotune import Autotuner, RefitPolicy
from repro.engine.planner import unit_for_chunk
from repro.engine.session import Certificate, ChordalityEngine
from repro.graphs.structure import Graph, bucket_graphs, bucket_npad


class QueueFullError(RuntimeError):
    """The service backlog is at ``max_queue``; the request was rejected."""


class ServiceClosedError(RuntimeError):
    """``submit`` after ``shutdown`` began."""


def _lane_devices(n_lanes: int) -> list:
    """The device each executor lane runs its verdict units on: lane
    ``i`` takes ``jax.local_devices()[i % n]``. With one lane or one
    local device every lane gets None, JAX's default device, where the
    single-executor service always ran."""
    if n_lanes == 1:
        return [None]
    import jax

    devices = jax.local_devices()
    if len(devices) == 1:
        return [None] * n_lanes
    return [devices[i % len(devices)] for i in range(n_lanes)]


@dataclasses.dataclass(frozen=True)
class ServiceResponse:
    """What a request's future resolves to."""

    verdict: bool
    certificate: Optional[Certificate]   # populated iff want_certificate
    witness: Optional[object] = None     # WitnessResult iff want_witness
    #: {property: verdict} over the request's normalized property set
    #: (always includes "chordal") iff it submitted ``properties=[...]``.
    properties: Optional[Dict[str, bool]] = None
    #: the request's RecognitionResult (per-property answers + the
    #: proper-interval witness when requested) iff ``properties=[...]``.
    recognition: Optional[object] = None
    queue_ms: float = 0.0  # submit -> unit execution start
    exec_ms: float = 0.0   # the unit executable call (shared across batch)
    backend: str = ""      # backend the request's unit ran on
    n_pad: int = 0         # padding bucket the request landed in
    batch: int = 0         # compiled batch dimension of its unit
    occupancy: int = 0     # real requests in the unit (rest = padding)
    priority: int = 0      # class the request was admitted under
    #: the request's closed span tree (repro.obs.Span rooted at
    #: "request") when tracing was enabled at submit time, else None.
    trace: Optional[object] = None


# eq=False: requests are identity objects — queue membership tests and
# shed-path removal must never compare payload graphs (ndarray ==).
@dataclasses.dataclass(eq=False)
class _Request:
    graph: Graph
    future: Future
    t_submit: float
    want_certificate: bool
    want_witness: bool = False
    properties: Tuple[str, ...] = ()     # normalized; empty = verdict-only
    priority: int = 0                    # index into priority_weights
    #: absolute repro.obs.clock seconds (one monotonic clock for
    #: deadlines, waits, and spans alike — see repro/obs/clock.py)
    deadline: Optional[float] = None
    # Tracing (None unless the tracer was enabled at submit): the open
    # "request" root and its "queue" child, carried across the submit ->
    # admission -> executor thread hops and closed at resolution.
    trace: Optional[object] = None
    queue_span: Optional[object] = None


@dataclasses.dataclass
class _AdmittedUnit:
    """A drained bucket: local work unit + the requests filling its slots."""

    unit: object                     # WorkUnit with indices 0..len(reqs)-1
    requests: List[_Request]
    #: admission-time "plan" span (unit formation + routing), adopted
    #: into each live request's trace at execution; None when untraced.
    plan_span: Optional[object] = None


class _BucketQueue:
    """One n_pad bucket's admission queue: a FIFO deque per priority
    class, drained in smooth weighted-fair order.

    Each :meth:`pop` credits every backlogged class its weight and
    serves the richest (ties to the higher class), so over a contended
    stretch class ``p`` receives ~``weights[p] / sum(weights of
    backlogged classes)`` of the unit slots, and no non-empty class
    starves — its credit grows every pop until it wins. A class that
    empties forfeits its accumulated credit: absence must not bank a
    burst for later.
    """

    __slots__ = ("_weights", "_dqs", "_credit", "_len")

    def __init__(self, weights: Tuple[float, ...]):
        self._weights = weights
        self._dqs: List[Deque[_Request]] = [
            collections.deque() for _ in weights]
        self._credit = [0.0] * len(weights)
        self._len = 0

    def __len__(self) -> int:
        return self._len

    def push(self, req: _Request) -> None:
        self._dqs[req.priority].append(req)
        self._len += 1

    def pop(self) -> _Request:
        """Weighted-fair pop (see class docstring)."""
        backlogged = [p for p, dq in enumerate(self._dqs) if dq]
        if not backlogged:
            raise IndexError("pop from empty bucket queue")
        total = 0.0
        for p, dq in enumerate(self._dqs):
            if dq:
                self._credit[p] += self._weights[p]
                total += self._weights[p]
            else:
                self._credit[p] = 0.0
        best = max(backlogged, key=lambda p: (self._credit[p], p))
        self._credit[best] -= total
        self._len -= 1
        return self._dqs[best].popleft()

    def remove(self, req: _Request) -> bool:
        """Drop one queued request (identity match) — the shed path."""
        try:
            self._dqs[req.priority].remove(req)
        except ValueError:
            return False
        self._len -= 1
        return True

    def remove_if(self, pred) -> List[_Request]:
        """Remove and return every queued request matching ``pred``."""
        removed: List[_Request] = []
        for p, dq in enumerate(self._dqs):
            if not any(pred(r) for r in dq):
                continue
            keep: Deque[_Request] = collections.deque()
            for r in dq:
                if pred(r):
                    removed.append(r)
                else:
                    keep.append(r)
            self._dqs[p] = keep
        self._len -= len(removed)
        return removed

    def drain_all(self) -> List[_Request]:
        """Empty the queue; returns the requests (class-ascending, FIFO)."""
        out = list(self.requests())
        for dq in self._dqs:
            dq.clear()
        self._credit = [0.0] * len(self._weights)
        self._len = 0
        return out

    def requests(self):
        """Iterate queued requests, class-ascending, FIFO within class."""
        for dq in self._dqs:
            yield from dq

    def oldest_t_submit(self) -> Optional[float]:
        """Submission time of the oldest queued request (any class)."""
        heads = [dq[0].t_submit for dq in self._dqs if dq]
        return min(heads) if heads else None


@dataclasses.dataclass
class ServiceStats:
    """Aggregate serving behavior (mutated under the service lock).

    The sample buffers (``queue_delays_ms``, ``exec_latencies_ms``) are
    bounded sliding windows: :meth:`record_queue_delay` /
    :meth:`record_exec_latency` roll the oldest samples off beyond
    ``window`` entries, so a long-lived service reports recent-window
    percentiles instead of leaking memory. The percentile properties
    are degenerate-safe — 0 samples reads 0.0, 1 sample reads that
    sample — and never mutate the buffers.
    """

    n_submitted: int = 0
    n_completed: int = 0
    n_cancelled: int = 0
    n_rejected: int = 0
    n_failed: int = 0
    n_expired: int = 0     # dropped in-queue past their deadline
    #: dropped by the deadline-pressure shedding policy (autotune only):
    #: queued deadlined work whose projected queue delay exceeded its
    #: remaining deadline — cancelled at admission, lowest class first.
    n_shed: int = 0
    #: {priority class: requests shed from it}
    shed_by_priority: Dict[int, int] = dataclasses.field(
        default_factory=dict)
    #: AIMD wait-window movements (autotune only)
    wait_adjustments: int = 0
    #: online router refits that updated at least one backend
    router_refits: int = 0
    n_units: int = 0
    #: units upgraded to the fused witness executable because at least one
    #: live request in them asked ``want_witness`` — the batching economics
    #: of certified serving (one heavier dispatch amortized over the unit).
    witness_upgraded: int = 0
    #: units upgraded to a shared-sweep recognition executable because at
    #: least one live request in them submitted ``properties=[...]`` — the
    #: unit answers the union of the live property sets in one dispatch.
    recognition_upgraded: int = 0
    queue_delays_ms: List[float] = dataclasses.field(default_factory=list)
    exec_latencies_ms: List[float] = dataclasses.field(default_factory=list)
    #: sliding-window bound on the sample buffers above
    window: int = 4096
    #: {filled slots: units executed with that occupancy}
    occupancy_histogram: Dict[int, int] = dataclasses.field(
        default_factory=dict)
    #: {backend name: requests it served}
    backend_histogram: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    #: {"full" | "timeout" | "forced": units drained for that reason}
    drain_reasons: Dict[str, int] = dataclasses.field(default_factory=dict)

    @staticmethod
    def _pct(buf: List[float], q: float) -> float:
        """Percentile over a copy of ``buf`` — well-defined for 0 samples
        (0.0) and 1 sample (that sample), and never mutates or reorders
        the buffer itself (np.percentile sorts its own copy)."""
        if not buf:
            return 0.0
        return float(np.percentile(np.asarray(buf, dtype=float), q))

    def record_queue_delay(self, ms: float) -> None:
        self.queue_delays_ms.append(ms)
        excess = len(self.queue_delays_ms) - self.window
        if excess > 0:
            del self.queue_delays_ms[:excess]

    def record_exec_latency(self, ms: float) -> None:
        self.exec_latencies_ms.append(ms)
        excess = len(self.exec_latencies_ms) - self.window
        if excess > 0:
            del self.exec_latencies_ms[:excess]

    @property
    def p50_queue_ms(self) -> float:
        return self._pct(self.queue_delays_ms, 50.0)

    @property
    def p95_queue_ms(self) -> float:
        return self._pct(self.queue_delays_ms, 95.0)

    @property
    def p50_exec_ms(self) -> float:
        return self._pct(self.exec_latencies_ms, 50.0)

    @property
    def p95_exec_ms(self) -> float:
        return self._pct(self.exec_latencies_ms, 95.0)

    @property
    def mean_occupancy(self) -> float:
        """Mean real requests per executed unit."""
        total = sum(k * v for k, v in self.occupancy_histogram.items())
        units = sum(self.occupancy_histogram.values())
        return total / units if units else 0.0


class AsyncChordalityEngine:
    """Request-at-a-time serving on top of :class:`ChordalityEngine`.

    Args:
      config: queue/batching knobs (:class:`~repro.configs.service
        .ServiceConfig`); default preset accepts 1024 outstanding requests
        and holds partial buckets up to 2 ms.
      backend: overrides ``config.backend`` (a registered name or
        ``"auto"``).
      engine: inject a pre-built session engine (must be constructed with
        the config's ``max_batch``); default builds one, so the service
        owns its compile cache.
      buckets / router: forwarded to the inner engine.

    Thread safety: ``submit`` may be called from any number of threads.
    The service runs ``1 + config.n_lanes`` daemon threads (admission +
    one executor per lane; the default config runs the classic
    admission + single-executor pair). ``shutdown(drain=True)`` — or
    leaving a ``with`` block — resolves every accepted future before
    returning.

    Lock ordering: the service lock (``self._lock``) may be taken first
    and the lane lock (``self._lane_cv``) second, never the reverse —
    lane workers release the lane lock before executing a unit (which
    takes the service lock to resolve futures).
    """

    def __init__(
        self,
        config: Optional[ServiceConfig] = None,
        backend: Optional[str] = None,
        engine: Optional[ChordalityEngine] = None,
        buckets: Optional[Sequence[int]] = None,
        router=None,
    ):
        self.config = config if config is not None else ServiceConfig()
        if engine is not None:
            if backend is not None or buckets is not None \
                    or router is not None:
                raise ValueError(
                    "pass either a pre-built engine or "
                    "backend/buckets/router, not both")
            if engine.max_batch != self.config.max_batch:
                raise ValueError(
                    f"engine.max_batch={engine.max_batch} != "
                    f"config.max_batch={self.config.max_batch}")
            self.engine = engine
        else:
            self.engine = ChordalityEngine(
                backend=backend if backend is not None
                else self.config.backend,
                max_batch=self.config.max_batch,
                buckets=buckets,
                router=router,
            )
        self.stats = ServiceStats(window=self.config.stats_window)

        self._lock = threading.Lock()
        self._work_cv = threading.Condition(self._lock)   # admission wakeups
        self._done_cv = threading.Condition(self._lock)   # backlog drains
        self._pending: Dict[int, _BucketQueue] = {}
        self._backlog = 0          # submitted, not yet resolved
        self._n_deadlined = 0      # queued requests carrying a deadline
        self._closed = False
        self._force_drain = False
        # shutdown(drain=False) structural guard: once up, the admission
        # loop may only cancel pending requests, never drain them.
        self._no_drain = False
        # Control loops (None = static knobs, the pre-autotune service).
        self._autotuner = Autotuner(self.config) \
            if self.config.autotune is not None else None
        self._refit_policy = None
        if self.config.autotune is not None \
                and self.engine.router is not None:
            self._refit_policy = RefitPolicy(
                self.config.autotune, _clock.now(),
                self.engine.router_sample_count)
        # Observability (DESIGN.md §15): the process tracer (checked per
        # request — near-free when disabled) and the registry series the
        # service publishes into. Metrics are always on.
        self._tracer = obs.get_tracer()
        _m = obs.registry
        self._m_requests = _m.counter(
            "repro_requests_total",
            "service requests by terminal outcome", labels=("outcome",))
        self._m_units = _m.counter(
            "repro_units_total", "work units executed",
            labels=("kind", "device"))
        self._m_backend = _m.counter(
            "repro_backend_requests_total",
            "requests served per backend", labels=("backend",))
        self._m_queue_ms = _m.histogram(
            "repro_queue_delay_ms", "submit -> unit execution start")
        self._m_exec_ms = _m.histogram(
            "repro_unit_exec_ms", "unit executable wall time")
        self._m_occupancy = _m.histogram(
            "repro_unit_occupancy", "live requests per executed unit",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128))
        self._m_wait_adjust = _m.counter(
            "repro_autotune_wait_adjustments_total",
            "AIMD wait-window movements")
        self._m_wait_ms = _m.gauge(
            "repro_autotune_wait_ms",
            "current adapted batching window per bucket",
            labels=("n_pad",))
        self._m_refits = _m.counter(
            "repro_router_refits_total",
            "online router refits that updated at least one backend")
        self._m_steals = _m.counter(
            "repro_lane_steals_total",
            "units an idle executor lane took from another lane's queue",
            labels=("lane",))
        # Executor lanes (PR 10, DESIGN.md §16): one deque + one daemon
        # thread per lane, all under one lane lock/condition, each lane
        # bound to a device. n_lanes=1 degenerates to the classic
        # single-executor service.
        n_lanes = self.config.n_lanes
        self._lane_devices = _lane_devices(n_lanes)
        self._lane_weights: Tuple[float, ...] = (
            self.config.lane_weights
            if self.config.lane_weights is not None
            else (1.0,) * n_lanes)
        self._lane_queues: List[Deque[_AdmittedUnit]] = [
            collections.deque() for _ in range(n_lanes)]
        self._lane_cv = threading.Condition(threading.Lock())
        self._lanes_closed = False
        self._admitter = threading.Thread(
            target=self._admission_loop, name="chordality-admission",
            daemon=True)
        self._executors = [
            threading.Thread(
                target=self._lane_loop, args=(lane,),
                name=f"chordality-executor-{lane}", daemon=True)
            for lane in range(n_lanes)]
        self._admitter.start()
        for t in self._executors:
            t.start()

    @property
    def _executor(self) -> threading.Thread:
        """Lane 0's executor thread — the single-executor service's
        thread under its pre-lane name (kept for callers/tests that
        join or liveness-check ``svc._executor``)."""
        return self._executors[0]

    # -- client surface ----------------------------------------------------
    def warmup(self, sample: Sequence[Graph],
               witness: bool = False) -> "AsyncChordalityEngine":
        """Pre-compile every shape traffic drawn like ``sample`` can hit.

        The synchronous engine warms a *plan* — full-occupancy units. A
        service additionally executes partial-occupancy batches whenever
        the wait window closes a bucket early, so this warms each
        power-of-two batch size per n_pad bucket (up to the bucket's
        request count and ``max_batch``). Call it before going live;
        otherwise the first minutes of traffic pay the jit compiles as
        queue delay. Only call while the service is idle — it drives the
        inner engine's compile cache from the caller's thread.
        ``witness=True`` additionally warms the fused witness executables
        (for traffic that will ask ``want_witness``). Lanes bound to
        devices warm their verdict executables on each device, one
        thread per device, so set-up grows by the slowest device rather
        than by their sum.
        """
        by_bucket = bucket_graphs(sample, self.engine.buckets)
        plans = []
        for _, idxs in sorted(by_bucket.items()):
            b = 1
            while True:
                chunk = [sample[i] for i in idxs[:b]]
                plans.append((self.engine.plan(chunk), chunk))
                if b >= min(len(idxs), self.config.max_batch):
                    break
                b *= 2
        devices = list(dict.fromkeys(self._lane_devices))

        def warm(device, witness: bool) -> None:
            for plan, chunk in plans:
                self.engine.warmup_plan(
                    plan, chunk, witness=witness, device=device)

        if len(devices) == 1:
            warm(devices[0], witness)
        else:
            # Witness executables run on the default device whatever the
            # lane (see _execute): the first device's pass warms them.
            with ThreadPoolExecutor(len(devices)) as pool:
                list(pool.map(warm, devices,
                              [witness] + [False] * (len(devices) - 1)))
        return self

    def submit(
        self,
        graph: Union[Graph, np.ndarray],
        want_certificate: bool = False,
        want_witness: bool = False,
        properties: Optional[Sequence[str]] = None,
        priority: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        timeout: Optional[float] = None,
    ) -> "Future[ServiceResponse]":
        """Enqueue one request; returns its future.

        ``graph`` is a :class:`Graph` or a dense bool adjacency. With the
        backlog at ``max_queue``: raises :class:`QueueFullError`
        immediately when ``timeout`` is None, else waits up to ``timeout``
        seconds for space. ``want_certificate`` attaches the detailed
        (order, violation-count) witness to the response — costs one extra
        single-graph pass on a certificate-capable backend.
        ``want_witness`` resolves the future with a checkable
        ``repro.witness.WitnessResult``; its unit then runs the fused
        witness executable (batched — no per-request extra pass).
        ``properties=[...]`` resolves the future with multi-property
        recognition answers (``ServiceResponse.properties`` /
        ``.recognition``); its unit then runs one shared-sweep recognition
        executable for the union of the unit's live property sets.
        Mutually exclusive with ``want_witness`` — recognition carries its
        own proper-interval witness.
        ``priority`` (default: the config's ``default_priority``) picks
        the request's class in ``config.priority_weights``; its bucket
        drains classes weighted-fair, so higher classes get
        proportionally more unit slots under contention.
        ``deadline_ms`` (default: the config's) drops the request if it is
        still queued this long after submission — the future is cancelled
        and ``ServiceStats.n_expired`` counts it. Deadlined requests are
        also the load-shedding candidates when autotuning (see
        ``ServiceStats.n_shed``).
        """
        props: Tuple[str, ...] = ()
        if properties is not None:
            if want_witness:
                raise ValueError(
                    "want_witness=True and properties=[...] are mutually "
                    "exclusive; recognition responses carry their own "
                    "proper-interval witnesses")
            from repro.recognition import normalize_properties

            props = normalize_properties(properties)  # validates names
        if not isinstance(graph, Graph):
            adj = np.asarray(graph, dtype=bool)
            graph = Graph(n_nodes=adj.shape[0], adj=adj)
        if deadline_ms is None:
            deadline_ms = self.config.deadline_ms
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be positive or None, got {deadline_ms}")
        if priority is None:
            priority = self.config.default_priority
        if not 0 <= priority < self.config.n_priorities:
            raise ValueError(
                f"priority {priority} outside classes "
                f"0..{self.config.n_priorities - 1}")
        t_submit = _clock.now()
        fut: Future = Future()
        req = _Request(
            graph=graph, future=fut, t_submit=t_submit,
            want_certificate=want_certificate,
            want_witness=want_witness,
            properties=props,
            priority=priority,
            deadline=None if deadline_ms is None
            else t_submit + deadline_ms / 1e3)
        if self._tracer.enabled:
            req.trace = self._tracer.start_span(
                "request", t=t_submit, n_nodes=graph.n_nodes,
                priority=priority, want_witness=want_witness,
                want_certificate=want_certificate,
                properties=list(props))
            req.queue_span = req.trace.child("queue", t=t_submit)
        # Admission-wait deadline: same obs clock as request deadlines —
        # mixing clock sources here is exactly the bug PR 9 removed.
        deadline = None if timeout is None else _clock.now() + timeout
        with self._lock:
            while True:
                if self._closed:
                    self._resolve_request_locked(req, "rejected")
                    raise ServiceClosedError("service is shut down")
                if self._backlog < self.config.max_queue:
                    break
                if deadline is None:
                    self.stats.n_rejected += 1
                    self._resolve_request_locked(req, "rejected")
                    raise QueueFullError(
                        f"backlog at max_queue={self.config.max_queue}")
                remaining = deadline - _clock.now()
                if remaining <= 0:
                    self.stats.n_rejected += 1
                    self._resolve_request_locked(req, "rejected")
                    raise QueueFullError(
                        f"backlog still full after {timeout}s")
                self._done_cv.wait(remaining)
            self._admit_locked(req)
        return fut

    def _admit_locked(self, req: _Request) -> None:
        """Book-keep one accepted request into its bucket (lock held)."""
        self._backlog += 1
        self.stats.n_submitted += 1
        if req.deadline is not None:
            self._n_deadlined += 1
        n_pad = bucket_npad(
            max(req.graph.n_nodes, 1), self.engine.buckets)
        if req.trace is not None:
            req.trace.attrs["n_pad"] = n_pad
        bq = self._pending.get(n_pad)
        if bq is None:
            bq = self._pending[n_pad] = _BucketQueue(
                self.config.priority_weights)
        bq.push(req)
        self._work_cv.notify_all()

    def submit_many(
        self,
        graphs: Sequence[Union[Graph, np.ndarray]],
        want_certificate: bool = False,
        want_witness: bool = False,
        properties: Optional[Sequence[str]] = None,
        priority: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        timeout: Optional[float] = None,
    ) -> List["Future[ServiceResponse]"]:
        """``submit`` each graph in order; returns the futures in order."""
        return [
            self.submit(g, want_certificate=want_certificate,
                        want_witness=want_witness, properties=properties,
                        priority=priority,
                        deadline_ms=deadline_ms, timeout=timeout)
            for g in graphs
        ]

    def asubmit(
        self,
        graph: Union[Graph, np.ndarray],
        want_certificate: bool = False,
        want_witness: bool = False,
        properties: Optional[Sequence[str]] = None,
        priority: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        timeout: Optional[float] = None,
    ):
        """``await``-able twin of :meth:`submit` for asyncio clients.

        A thin adapter: the request goes through the exact same admission
        queue and thread-based executor; the returned ``asyncio.Future``
        wraps the concurrent future, so resolution hops onto the calling
        event loop. Must be called with a running loop (i.e. from a
        coroutine):

            resp = await svc.asubmit(graph, want_witness=True)

        Admission control still applies *synchronously*: a full queue
        raises :class:`QueueFullError` in the caller's coroutine (use
        ``timeout`` to block the loop at most that long — prefer 0/None
        and retry at the application layer to keep the loop responsive).
        """
        import asyncio

        fut = self.submit(
            graph, want_certificate=want_certificate,
            want_witness=want_witness, properties=properties,
            priority=priority, deadline_ms=deadline_ms, timeout=timeout)
        return asyncio.wrap_future(fut)

    def flush(self, timeout: Optional[float] = None) -> None:
        """Force-drain partial buckets and wait for an empty backlog.

        Requests submitted *while* flushing are drained too (the force flag
        stays up until the pending buckets empty). Raises TimeoutError if
        the backlog has not cleared within ``timeout`` (default: the
        config's ``drain_timeout_s``).
        """
        t = self.config.drain_timeout_s if timeout is None else timeout
        deadline = _clock.now() + t
        with self._lock:
            while self._backlog > 0:
                # Re-assert every wakeup: admission clears the flag once
                # pending empties, but a submit racing in right after
                # would otherwise sit out its full batching window.
                self._force_drain = True
                self._work_cv.notify_all()
                remaining = deadline - _clock.now()
                if remaining <= 0:
                    raise TimeoutError(
                        f"backlog {self._backlog} after {t}s flush")
                self._done_cv.wait(remaining)
            # Backlog empty => pending empty: restore windowed batching.
            # (The admission loop's own reset only runs on a drain pass,
            # which never happens when the last wakeup was in-flight work
            # finishing rather than a bucket draining.)
            self._force_drain = self._closed

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop admission and join the worker threads.

        ``drain=True`` resolves every accepted future first; ``drain=False``
        cancels requests still waiting in buckets (already-admitted units
        still execute). Idempotent.
        """
        with self._lock:
            if self._closed and not self._admitter.is_alive():
                return
            self._closed = True
            if drain:
                self._force_drain = True
            else:
                # Raise the structural guard *before* cancelling: from
                # this point the admission loop can only cancel pending
                # requests, never drain them into units — whatever
                # interleaving leaves (or lands) requests in a bucket.
                self._no_drain = True
                self._cancel_pending_locked()
            self._work_cv.notify_all()
        t = self.config.drain_timeout_s if timeout is None else timeout
        self._admitter.join(t)
        for th in self._executors:
            th.join(t)
        if self._admitter.is_alive() or \
                any(th.is_alive() for th in self._executors):
            raise TimeoutError(f"service threads alive after {t}s")

    def __enter__(self) -> "AsyncChordalityEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown(drain=exc_type is None)

    @property
    def backlog(self) -> int:
        """Requests submitted but not yet resolved (queued + in flight)."""
        with self._lock:
            return self._backlog

    # -- admission loop ----------------------------------------------------
    def _resolve_request_locked(self, req: _Request, outcome: str,
                                t: Optional[float] = None) -> None:
        """Terminal observability bookkeeping for one request: count the
        outcome and, when traced, close + emit its span tree. Idempotent
        per request (the trace is detached on first resolution); safe for
        the pre-admission reject path too (nothing here needs the lock —
        the metric and sink carry their own)."""
        self._m_requests.inc(outcome=outcome)
        if req.trace is None:
            return
        tnow = _clock.now() if t is None else t
        if req.queue_span is not None and req.queue_span.t_end is None:
            req.queue_span.end(tnow)
        req.trace.attrs["outcome"] = outcome
        req.trace.end(tnow)
        self._tracer.finish(req.trace)
        req.trace = None

    def _cancel_pending_locked(self) -> None:
        """Cancel every queued request and release its backlog slot."""
        for bq in self._pending.values():
            for req in bq.drain_all():
                if req.deadline is not None:
                    self._n_deadlined -= 1
                if req.future.cancel():
                    self.stats.n_cancelled += 1
                self._resolve_request_locked(req, "cancelled")
                self._backlog -= 1
        self._done_cv.notify_all()

    def _expire_locked(self, now: float) -> Optional[float]:
        """Deadline sweep: drop queued requests past their deadline, then
        shed queued deadlined work projected to miss (autotune only).

        Returns the earliest deadline still pending (the admission loop's
        extra wakeup bound), or None when nothing is deadlined. Only
        queued requests expire — once drained into a unit, a request
        always executes (its result may simply arrive late). The
        ``_n_deadlined`` counter (maintained at submit/expire/dequeue)
        makes this a no-op for deadline-free traffic — the default
        config never pays the backlog scan.
        """
        if self._n_deadlined == 0:
            return None
        dropped = 0
        for bq in self._pending.values():
            for req in bq.remove_if(
                    lambda r: r.deadline is not None and now >= r.deadline):
                if req.future.cancelled():  # client beat the deadline
                    self.stats.n_cancelled += 1
                    self._resolve_request_locked(req, "cancelled", t=now)
                else:
                    req.future.cancel()
                    self.stats.n_expired += 1
                    self._resolve_request_locked(req, "expired", t=now)
                self._backlog -= 1
                self._n_deadlined -= 1
                dropped += 1
        dropped += self._shed_locked(now)
        earliest: Optional[float] = None
        for bq in self._pending.values():
            for req in bq.requests():
                if req.deadline is not None and (
                        earliest is None or req.deadline < earliest):
                    earliest = req.deadline
        if dropped:
            self._done_cv.notify_all()
        return earliest

    def _shed_locked(self, now: float) -> int:
        """Deadline-pressure load shedding (autotune only; DESIGN.md §14).

        For each bucket, while the tuner projects the backlog's clear
        time to exceed ``shed_headroom`` × some queued deadlined
        request's remaining deadline, cancel that request now — lowest
        priority class first, oldest first — instead of letting it hold
        a unit slot it can only expire in. Deadline-free requests are
        never shed. Returns the number of requests shed.
        """
        if self._autotuner is None or self._n_deadlined == 0:
            return 0
        headroom = self._autotuner.knobs.shed_headroom
        ready_units = self._ready_units()
        shed = 0
        for n_pad, bq in self._pending.items():
            while len(bq) and self._n_deadlined:
                proj = self._autotuner.projected_delay_ms(
                    n_pad, len(bq), ready_units)
                if proj is None:
                    break
                victim: Optional[_Request] = None
                for req in bq.requests():   # class-ascending, FIFO within
                    if req.deadline is None:
                        continue
                    if proj > headroom * (req.deadline - now) * 1e3:
                        victim = req
                        break
                if victim is None or not bq.remove(victim):
                    break
                if victim.future.cancelled():
                    self.stats.n_cancelled += 1
                    self._resolve_request_locked(victim, "cancelled", t=now)
                else:
                    victim.future.cancel()
                    self.stats.n_shed += 1
                    self.stats.shed_by_priority[victim.priority] = \
                        self.stats.shed_by_priority.get(
                            victim.priority, 0) + 1
                    self._resolve_request_locked(victim, "shed", t=now)
                self._backlog -= 1
                self._n_deadlined -= 1
                shed += 1
        return shed

    def _wait_s(self, n_pad: int) -> float:
        """This bucket's current batching window, seconds (the AIMD
        controller's adapted value when autotuning, the static config
        knob otherwise)."""
        if self._autotuner is not None:
            return self._autotuner.wait_ms(n_pad) / 1e3
        return self.config.max_wait_ms / 1e3

    def _drainable(self, now: float):
        """(bucket n_pads to drain now, seconds until the next deadline)."""
        drain, next_wait = [], None
        if self._no_drain:          # shutdown(drain=False): cancel-only
            return drain, next_wait
        for n_pad, bq in self._pending.items():
            if not bq:
                continue
            if self._force_drain or len(bq) >= self.config.max_batch:
                drain.append(n_pad)
                continue
            deadline = bq.oldest_t_submit() + self._wait_s(n_pad)
            if now >= deadline:
                drain.append(n_pad)
            else:
                remaining = deadline - now
                if next_wait is None or remaining < next_wait:
                    next_wait = remaining
        return drain, next_wait

    def _admission_loop(self) -> None:
        while True:
            admitted: List[_AdmittedUnit] = []
            with self._lock:
                while True:
                    now = _clock.now()
                    next_expiry = self._expire_locked(now)
                    drain, next_wait = self._drainable(now)
                    if drain:
                        break
                    if self._closed:
                        if self._no_drain:
                            # Defensive twin of the shutdown-side cancel:
                            # anything still (or newly) pending after a
                            # drain=False shutdown is cancelled here, so
                            # no interleaving can revive a drain.
                            self._cancel_pending_locked()
                        if not any(self._pending.values()):
                            self._close_lanes()  # lanes drain then stop
                            return
                    if next_expiry is not None:
                        expiry_wait = max(next_expiry - now, 0.0)
                        next_wait = expiry_wait if next_wait is None \
                            else min(next_wait, expiry_wait)
                    self._work_cv.wait(timeout=next_wait)
                for n_pad in drain:
                    admitted.extend(self._drain_bucket_locked(n_pad))
                if self._force_drain and not any(self._pending.values()):
                    self._force_drain = self._closed  # keep for shutdown
            for au in admitted:
                self._dispatch_unit(au)

    def _drain_bucket_locked(self, n_pad: int) -> List[_AdmittedUnit]:
        """Pop up to max_batch live requests; route; skip dead ones.

        Re-reads the clock rather than trusting the pass's sweep: an
        admission pass drains buckets one at a time, and routing an
        earlier bucket can stall long enough (slow router, lock held)
        that requests here expired since the sweep ran. A request found
        past its deadline releases its slot immediately — counted in
        ``n_expired``, never built into the unit — so a unit's batch
        only ever contains live work (regression: tests/test_service.py
        ``test_expired_requests_release_slots_at_drain``).
        """
        bq = self._pending[n_pad]
        now = _clock.now()
        out: List[_AdmittedUnit] = []
        reqs: List[_Request] = []
        while bq and len(reqs) < self.config.max_batch:
            req = bq.pop()
            if req.deadline is not None:
                self._n_deadlined -= 1     # leaves the queue either way
            if req.future.cancelled():
                self.stats.n_cancelled += 1
                self._resolve_request_locked(req, "cancelled", t=now)
                self._backlog -= 1
                self._done_cv.notify_all()
                continue
            if req.deadline is not None and now >= req.deadline:
                req.future.cancel()
                self.stats.n_expired += 1
                self._resolve_request_locked(req, "expired", t=now)
                self._backlog -= 1
                self._done_cv.notify_all()
                continue
            reqs.append(req)
        if not reqs:
            return out
        full = len(reqs) >= self.config.max_batch
        reason = ("full" if full
                  else "forced" if self._force_drain else "timeout")
        self.stats.drain_reasons[reason] = \
            self.stats.drain_reasons.get(reason, 0) + 1
        # Unit formation + routing as a "plan" span. It overlaps the
        # requests' queue stage (planning happens while they sit queued),
        # so it is adopted into each trace as its own root child rather
        # than splitting the queue span.
        plan_span = self._tracer.start_span(
            "plan", t=now, n_pad=n_pad, count=len(reqs), reason=reason) \
            if self._tracer.enabled else None
        unit = unit_for_chunk(
            n_pad, len(reqs), self.config.max_batch)
        try:
            unit = self.engine.route_unit(unit, [r.graph for r in reqs])
        except Exception as e:
            # A misconfigured router must fail these requests, not kill
            # the admission thread (which would strand the whole service).
            for r in reqs:
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(e)
                    self.stats.n_failed += 1
                    self._resolve_request_locked(r, "failed")
                else:
                    self.stats.n_cancelled += 1
                    self._resolve_request_locked(r, "cancelled")
                self._backlog -= 1
            self._done_cv.notify_all()
            return out
        if plan_span is not None:
            plan_span.attrs["backend"] = unit.backend
            plan_span.attrs["batch"] = unit.batch
            plan_span.end()
        out.append(_AdmittedUnit(
            unit=unit, requests=reqs, plan_span=plan_span))
        return out

    # -- executor lanes ----------------------------------------------------
    def _ready_units(self) -> int:
        """Units routed but not yet picked up by a lane (all lanes)."""
        with self._lane_cv:
            return sum(len(dq) for dq in self._lane_queues)

    def _dispatch_unit(self, au: _AdmittedUnit) -> None:
        """Least-loaded (weight-normalized) lane dispatch.

        The admission loop places each routed unit on the lane whose
        backlog-per-weight is smallest (ties to the lowest lane index), so
        a weight-2 lane carries ~2x the units of a weight-1 lane in steady
        state. A slow lane's queue grows, its normalized load rises, and
        new work flows around it — the admission loop itself never blocks
        on any lane.
        """
        with self._lane_cv:
            lane = min(
                range(len(self._lane_queues)),
                key=lambda i: (len(self._lane_queues[i])
                               / self._lane_weights[i], i))
            self._lane_queues[lane].append(au)
            self._lane_cv.notify_all()

    def _close_lanes(self) -> None:
        """Signal every lane to drain its remaining queue and exit."""
        with self._lane_cv:
            self._lanes_closed = True
            self._lane_cv.notify_all()

    def _take_unit_locked(self, lane: int) -> Optional[_AdmittedUnit]:
        """Next unit for ``lane`` (lane lock held): own queue first,
        else weighted steal from the most-loaded victim's tail.

        An idle lane steals up to ``max(1, round(weight))`` units in one
        grab — tail-first (the units the victim would reach last), then
        re-ordered oldest-first onto its own queue — so a fast (heavily
        weighted) lane drains a slow lane's backlog proportionally
        faster. Returns None when every queue is empty.
        """
        dq = self._lane_queues[lane]
        if dq:
            return dq.popleft()
        victims = [j for j in range(len(self._lane_queues))
                   if j != lane and self._lane_queues[j]]
        if not victims:
            return None
        victim = max(victims, key=lambda j: len(self._lane_queues[j]))
        vq = self._lane_queues[victim]
        k = min(len(vq), max(1, int(round(self._lane_weights[lane]))))
        self._m_steals.inc(k, lane=lane)
        stolen = [vq.pop() for _ in range(k)]   # tail: newest first
        stolen.reverse()                        # run oldest stolen first
        dq.extend(stolen[1:])
        return stolen[0]

    def _lane_loop(self, lane: int) -> None:
        while True:
            with self._lane_cv:
                while True:
                    au = self._take_unit_locked(lane)
                    if au is not None:
                        break
                    if self._lanes_closed:
                        return
                    self._lane_cv.wait()
            try:
                self._execute(au, lane)
            except Exception as e:                  # pragma: no cover
                # Last-resort guard: a lane death would strand every
                # outstanding future and hang all future submits, so any
                # escaped exception fails this unit's requests instead.
                self._fail_unit(au, e)

    def _fail_unit(self, au: _AdmittedUnit, exc: Exception) -> None:
        with self._lock:
            for r in au.requests:
                if r.future.cancelled():
                    self.stats.n_cancelled += 1
                    self._resolve_request_locked(r, "cancelled")
                elif r.future.done():
                    continue                        # already resolved
                else:
                    if r.future.set_running_or_notify_cancel():
                        r.future.set_exception(exc)
                        self.stats.n_failed += 1
                        self._resolve_request_locked(r, "failed")
                    else:
                        self.stats.n_cancelled += 1
                        self._resolve_request_locked(r, "cancelled")
                self._backlog -= 1
            self._done_cv.notify_all()

    def _execute(self, au: _AdmittedUnit, lane: int = 0) -> None:
        t_start = _clock.now()
        device = self._lane_devices[lane]
        live = [r.future.set_running_or_notify_cancel()
                for r in au.requests]
        graphs = [r.graph for r in au.requests]
        # The shared "exec" span: entered on this lane's executor thread
        # so the session's unit/realize/compile/dispatch spans nest inside
        # it, emit=False because it is adopted into each live request's
        # root rather than emitted standalone. The ``lane`` attribute ties
        # the span to the executor lane that ran the unit, ``device`` (set
        # once it ran) to the cache scope it ran in. Queue spans close at
        # its exact start instant so queue+exec+finalize sums to the root
        # duration.
        exec_span = self._tracer.span(
            "exec", emit=False, n_pad=au.unit.n_pad, batch=au.unit.batch,
            lane=lane)
        if self._tracer.enabled:
            exec_span.t_start = t_start
            for r in au.requests:
                if r.queue_span is not None and r.queue_span.t_end is None:
                    r.queue_span.end(t_start)
        # One witness-wanting live request upgrades the whole unit to the
        # fused witness executable: the certificates are batched, so they
        # ride the unit's single device call instead of per-request passes.
        # Recognition upgrades work the same way, over the *union* of the
        # live requests' property sets (one shared-sweep dispatch answers
        # every property any of them asked for). A unit can carry both
        # upgrades when different requests want different extras.
        unit_wits: Optional[List] = None
        unit_recs: Optional[tuple] = None   # (props, batch, results)
        try:
            with exec_span:
                prop_union = set()
                for r, ok in zip(au.requests, live):
                    if ok:
                        prop_union.update(r.properties)
                if prop_union:
                    from repro.recognition import normalize_properties

                    props = normalize_properties(sorted(prop_union))
                    rb, recs, backend_name, exec_ms = \
                        self.engine.execute_unit_recognition(
                            au.unit, graphs, props)
                    unit_recs = (props, rb, recs)
                    out = np.asarray(
                        rb.verdicts["chordal"][: len(au.requests)],
                        dtype=bool)
                if any(r.want_witness and ok
                       for r, ok in zip(au.requests, live)):
                    out, unit_wits, backend_name, wit_ms = \
                        self.engine.execute_unit_witness(au.unit, graphs)
                    exec_ms = wit_ms if unit_recs is None \
                        else exec_ms + wit_ms
                elif unit_recs is None:
                    out, backend_name, exec_ms = self.engine.execute_unit(
                        au.unit, graphs, device=device)
        except Exception as e:
            with self._lock:
                for r, ok in zip(au.requests, live):
                    if ok:
                        r.future.set_exception(e)
                        self.stats.n_failed += 1
                        self._resolve_request_locked(r, "failed")
                    else:
                        self.stats.n_cancelled += 1
                        self._resolve_request_locked(r, "cancelled")
                    self._backlog -= 1
                self._done_cv.notify_all()
            return
        # Verdict units ran on the lane's device; witness and recognition
        # units on the engine's default-device instances.
        ran_on = device if unit_wits is None and unit_recs is None else None
        try:
            scope = self.engine._resolve(backend_name, ran_on).cache_scope()
        except Exception:
            scope = "host"
        if self._tracer.enabled:
            exec_span.attrs["backend"] = backend_name
            exec_span.attrs["device"] = scope
        # Certificates are per-request extras: one failing must neither
        # fail its unit-mates nor kill the executor thread.
        certs: List[Optional[Certificate]] = []
        cert_errs: List[Optional[Exception]] = []
        for r, ok in zip(au.requests, live):
            cert, err = None, None
            if ok and r.want_certificate:
                try:
                    cert = self.engine.certificate(r.graph)
                except Exception as e:
                    err = e
            certs.append(cert)
            cert_errs.append(err)
        live_delays: List[float] = []    # this unit's queue delays
        with self._lock:
            self.stats.n_units += 1
            kinds = []
            if unit_recs is not None:
                self.stats.recognition_upgraded += 1
                kinds.append("recognition")
            if unit_wits is not None:
                self.stats.witness_upgraded += 1
                kinds.append("witness")
            self._m_units.inc(
                kind="+".join(kinds) or "verdict", device=scope)
            self.stats.record_exec_latency(exec_ms)
            self._m_exec_ms.observe(exec_ms)
            occ = sum(live)       # cancelled-after-drain slots don't count
            self.stats.occupancy_histogram[occ] = \
                self.stats.occupancy_histogram.get(occ, 0) + 1
            self._m_occupancy.observe(occ)
            for slot, (r, ok) in enumerate(zip(au.requests, live)):
                if not ok:
                    self.stats.n_cancelled += 1
                    self._resolve_request_locked(r, "cancelled")
                elif cert_errs[slot] is not None:
                    r.future.set_exception(cert_errs[slot])
                    self.stats.n_failed += 1
                    self._resolve_request_locked(r, "failed")
                else:
                    queue_ms = (t_start - r.t_submit) * 1e3
                    self.stats.record_queue_delay(queue_ms)
                    self._m_queue_ms.observe(queue_ms)
                    live_delays.append(queue_ms)
                    self.stats.backend_histogram[backend_name] = \
                        self.stats.backend_histogram.get(
                            backend_name, 0) + 1
                    self._m_backend.inc(backend=backend_name)
                    props_resp = recog_resp = None
                    if unit_recs is not None and r.properties:
                        # Filter the unit's union answers back down to
                        # this request's own normalized property set.
                        _, rb, recs = unit_recs
                        props_resp = {
                            p: bool(rb.verdicts[p][slot])
                            for p in r.properties}
                        recog_resp = dataclasses.replace(
                            recs[slot], properties=props_resp,
                            witness=recs[slot].witness
                            if "proper_interval" in r.properties
                            else None)
                    # Close the trace BEFORE resolving the future so the
                    # client-visible response carries a finished tree:
                    # adopt the shared plan/exec subtrees, then a
                    # "finalize" stage from exec end to now (covers the
                    # certificate pass and response assembly), then the
                    # root — ends stitched so the stage sum is exact.
                    trace_obj = None
                    if r.trace is not None:
                        root = r.trace
                        if au.plan_span is not None:
                            root.adopt(au.plan_span)
                        root.adopt(exec_span)
                        fin = root.child("finalize", t=exec_span.t_end)
                        fin.end()
                        root.attrs["outcome"] = "completed"
                        root.end(t=fin.t_end)
                        self._tracer.finish(root)
                        trace_obj = root
                        r.trace = None
                    self._m_requests.inc(outcome="completed")
                    r.future.set_result(ServiceResponse(
                        verdict=bool(out[slot]),
                        certificate=certs[slot],
                        witness=unit_wits[slot]
                        if unit_wits is not None and r.want_witness
                        else None,
                        properties=props_resp,
                        recognition=recog_resp,
                        queue_ms=queue_ms,
                        exec_ms=exec_ms,
                        backend=backend_name,
                        n_pad=au.unit.n_pad,
                        batch=au.unit.batch,
                        occupancy=occ,
                        priority=r.priority,
                        trace=trace_obj,
                    ))
                    self.stats.n_completed += 1
                self._backlog -= 1
            if self._autotuner is not None:
                if self._autotuner.observe_unit(
                        au.unit.n_pad, occ, live_delays, exec_ms,
                        lane=lane):
                    self.stats.wait_adjustments += 1
                    self._m_wait_adjust.inc()
                    decision = self._autotuner.last_decision
                    if decision is not None:
                        self._m_wait_ms.set(
                            decision["wait_ms"],
                            n_pad=decision["n_pad"])
                        self._tracer.event("autotune_wait", **decision)
            self._done_cv.notify_all()
        self._maybe_refit()

    def _maybe_refit(self) -> None:
        """Online router refit (executor thread, outside the service lock
        — a least-squares solve must not stall admission).

        Fires on the :class:`~repro.engine.autotune.RefitPolicy`
        triggers; the session's ``refit_router`` applies its own
        degenerate-sample guards, and the policy is marked either way so
        an unfittable log doesn't re-trigger on every unit.
        """
        if self._refit_policy is None:
            return
        now = _clock.now()
        count = self.engine.router_sample_count
        if not self._refit_policy.due(count, now):
            return
        try:
            refitted = self.engine.refit_router(
                min_samples=self.config.autotune.refit_backend_min_samples)
        except Exception:      # a bad refit must never kill the executor
            refitted = ()
        self._refit_policy.mark(count, now)
        if refitted:
            self._m_refits.inc()
            self._tracer.event(
                "router_refit", backends=list(refitted),
                sample_count=count)
            with self._lock:
                self.stats.router_refits += 1

    def autotune_snapshot(self) -> Optional[Dict[int, float]]:
        """{n_pad: adapted wait_ms} when autotuning, else None."""
        with self._lock:
            return None if self._autotuner is None \
                else self._autotuner.snapshot()

    def telemetry(self) -> dict:
        """Service-level observability snapshot (DESIGN.md §15).

        One dict a dashboard (or the serving demo) can dump directly:
        per-stage latency percentiles from the sliding stats windows,
        the backend mix and request-outcome counts, the inner engine's
        compile-cache traffic, the autotuner's adapted wait windows, and
        the process-global metrics registry snapshot.
        """
        with self._lock:
            st = self.stats
            stages = {
                "queue_ms": {"p50": st.p50_queue_ms,
                             "p95": st.p95_queue_ms},
                "exec_ms": {"p50": st.p50_exec_ms,
                            "p95": st.p95_exec_ms},
            }
            requests = {
                "submitted": st.n_submitted,
                "completed": st.n_completed,
                "cancelled": st.n_cancelled,
                "rejected": st.n_rejected,
                "failed": st.n_failed,
                "expired": st.n_expired,
                "shed": st.n_shed,
            }
            units = {
                "executed": st.n_units,
                "mean_occupancy": st.mean_occupancy,
                "witness_upgraded": st.witness_upgraded,
                "recognition_upgraded": st.recognition_upgraded,
                "drain_reasons": dict(st.drain_reasons),
            }
            backend_mix = dict(st.backend_histogram)
            autotune = None if self._autotuner is None \
                else self._autotuner.snapshot()
            lanes = {
                "n_lanes": self.config.n_lanes,
                "weights": list(self._lane_weights),
                "ready_units": self._ready_units(),
            }
            if self._autotuner is not None:
                lanes["autotune"] = self._autotuner.lane_snapshot()
        engine_tel = self.engine.telemetry()   # takes no service state
        return {
            "stages": stages,
            "requests": requests,
            "units": units,
            "backend_mix": backend_mix,
            "lanes": lanes,
            "cache": engine_tel["cache"],
            "router_samples": engine_tel["router_samples"],
            "autotune_wait_ms": autotune,
            "metrics": engine_tel["metrics"],
        }


def gather(futures: Sequence["Future[ServiceResponse]"],
           timeout: Optional[float] = None) -> List[ServiceResponse]:
    """Resolve a batch of service futures in submission order."""
    return [f.result(timeout=timeout) for f in futures]
