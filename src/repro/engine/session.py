"""Session layer — ``ChordalityEngine``: the one entry point for callers.

    from repro.engine import ChordalityEngine

    eng = ChordalityEngine(backend="auto", max_batch=64)
    result = eng.run(graphs)          # graphs: Sequence[Graph] (any sizes)
    result.verdicts                   # (len(graphs),) bool, input order
    result.stats.throughput_gps      # graphs/second over the run
    result.plan.unit_of(i).backend   # router's per-unit choice (auto mode)
    eng.certificate(graphs[i])       # (chordal, PEO-or-witness)

    result = eng.run(graphs, witness=True)   # checkable certificates
    result.witnesses[i]               # WitnessResult: clique tree /
                                      # treewidth / coloring, or a
                                      # chordless cycle (repro.witness)
    eng.witness(graphs[i])            # single-graph witness

    result = eng.run(graphs, properties=["chordal", "proper_interval"])
    result.properties["proper_interval"]   # (len(graphs),) bool planes
    result.recognitions[i].witness    # proper-interval certificate
    eng.recognize(graphs[i])          # single-graph multi-property answer

The engine owns one backend instance (or, under ``backend="auto"``, a
router plus lazily-built instances of its candidates) and one compile cache
for its lifetime, so repeated ``run`` calls amortize compilation the way a
serving process does. All shape planning goes through
``repro.engine.planner`` — callers never pad or batch by hand. Work units
whose backend carries the ``sparse`` capability are realized as padded CSR
batches (no dense matrix on that path); everything else gets the dense
host-array contract.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from repro import obs
from repro.obs import clock as _clock
from repro.engine.backends import (
    ChordalityBackend,
    make_backend,
)
from repro.engine.planner import (
    CompileCache,
    Plan,
    RealizePool,
    plan_requests,
    realize_unit,
    realize_unit_csr,
)
from repro.graphs.structure import Graph, bucket_npad


@dataclasses.dataclass
class EngineStats:
    n_requests: int = 0
    n_units: int = 0
    wall_s: float = 0.0
    unit_latencies_ms: List[float] = dataclasses.field(default_factory=list)
    compile_hits: int = 0
    compile_misses: int = 0
    bucket_histogram: Dict[int, int] = dataclasses.field(default_factory=dict)
    backend_histogram: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # Router-calibration rows, one per executed unit:
    # (backend, n_pad, density, batch, device_count, us_per_graph) — the
    # exact sample
    # format ``repro.engine.router.fit_cost_model`` consumes, so a session
    # can re-fit its router from its own measurements (refit_router).
    unit_samples: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def throughput_gps(self) -> float:
        """Graphs per second across the whole run (incl. compile time)."""
        return self.n_requests / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def p50_latency_ms(self) -> float:
        return float(np.median(self.unit_latencies_ms)) \
            if self.unit_latencies_ms else 0.0

    @property
    def p95_latency_ms(self) -> float:
        return float(np.percentile(self.unit_latencies_ms, 95)) \
            if self.unit_latencies_ms else 0.0


@dataclasses.dataclass
class EngineResult:
    """Verdicts aligned to the input request order, plus the shape plan
    that produced them (per-request metadata via ``plan.unit_of(i)``).

    ``witnesses`` is populated by witness runs (``run(..., witness=True)``)
    — one ``repro.witness.WitnessResult`` per request, same order as
    ``verdicts``; None on verdict-only runs.

    ``properties`` / ``recognitions`` are populated by recognition runs
    (``run(..., properties=[...])``): one ``(n_requests,)`` bool plane per
    normalized property (``verdicts`` stays the chordal plane), and one
    ``repro.recognition.RecognitionResult`` per request carrying the
    per-graph answers plus the proper-interval witness when requested.
    """

    verdicts: np.ndarray          # (n_requests,) bool
    plan: Plan
    stats: EngineStats
    witnesses: Optional[List] = None   # List[repro.witness.WitnessResult]
    properties: Optional[Dict[str, np.ndarray]] = None
    recognitions: Optional[List] = None  # List[RecognitionResult]

    def __len__(self) -> int:
        return len(self.verdicts)


@dataclasses.dataclass(frozen=True)
class Certificate:
    chordal: bool
    order: np.ndarray             # LexBFS order; a PEO iff chordal
    n_violations: int             # > 0 is the quantitative negative witness
    n_pad: int                    # bucket the request was padded to


class ChordalityEngine:
    """Backend-dispatched, bucket-batched chordality testing.

    Args:
      backend: registered backend name (see
        ``repro.engine.backends.backend_names()``), the string ``"auto"``
        (cost-model routing per work unit, see ``repro.engine.router``),
        or an already-built :class:`ChordalityBackend` instance.
      max_batch: work-unit batch cap; partial chunks round up to powers
        of two (bounded compile count, see planner docs).
      buckets: override the n_pad bucket grid (default
        ``configs.shapes.ENGINE_NPAD_BUCKETS``). Mainly for tests.
      router: override the router used by ``backend="auto"``.
      witness: default for ``run``'s witness flag — witness runs return
        checkable certificates (``repro.witness.WitnessResult``) alongside
        verdicts, through the same buckets and compile cache.
      backend_opts: forwarded to the backend factory (named backends only).
    """

    def __init__(
        self,
        backend: Union[str, ChordalityBackend] = "jax_fast",
        max_batch: int = 64,
        buckets: Optional[Sequence[int]] = None,
        router=None,
        witness: bool = False,
        **backend_opts,
    ):
        self.router = None
        self._instances: Dict[str, ChordalityBackend] = {}
        if isinstance(backend, str) and backend == "auto":
            if backend_opts:
                raise ValueError(
                    "backend_opts do not apply to backend='auto'; "
                    "pass a configured router instead")
            from repro.engine.router import Router

            self.backend: Optional[ChordalityBackend] = None
            self.router = router if router is not None else Router()
        elif isinstance(backend, str):
            self.backend = make_backend(backend, **backend_opts)
        else:
            if backend_opts:
                raise ValueError(
                    "backend_opts only apply when backend is given by name")
            self.backend = backend
        self.max_batch = max_batch
        self.buckets = tuple(buckets) if buckets is not None else None
        self.witness_default = witness
        self.cache = CompileCache()
        # Host buffers the verdict path realizes dense units into, kept
        # from one unit to the next (planner.RealizePool).
        self.realize_pool = RealizePool()
        # Engine-lifetime measurement log feeding refit_router(); every
        # execute_unit appends one (backend, n, density, batch,
        # device_count, us/graph) row, from sync runs and the async
        # service's executor alike.
        # Bounded: beyond the cap the oldest rows roll off, so a long-lived
        # serving process keeps a recent-window fit, not a memory leak.
        # Appends/trims are GIL-atomic list ops; readers snapshot first.
        self._router_samples: List[tuple] = []
        self._router_samples_cap = 4096
        # Monotone count of samples ever logged — unlike len() of the
        # capped list, usable as a refit trigger by long-lived services.
        self._router_samples_total = 0

    # -- backend resolution ------------------------------------------------
    def _resolve(self, name: Optional[str]) -> ChordalityBackend:
        """Unit backend name -> instance (engine-owned, built lazily)."""
        if name is None:
            if self.backend is None:
                raise RuntimeError(
                    "auto engine got an unannotated work unit; plans must "
                    "come from ChordalityEngine.plan()")
            return self.backend
        if self.backend is not None and self.backend.name == name:
            return self.backend
        inst = self._instances.get(name)
        if inst is None:
            inst = self._instances[name] = make_backend(name)
        return inst

    def _resolve_witness(self, name: Optional[str]) -> ChordalityBackend:
        """Like :meth:`_resolve` but guarantees the witness capability.

        Units routed (or engines fixed) onto a witness-less backend
        (``sharded``) fall back to ``jax_faithful`` for the witness pass —
        the same fallback :meth:`certificate` uses.
        """
        backend = self._resolve(name)
        if backend.caps.witness:
            return backend
        inst = self._instances.get("jax_faithful")
        if inst is None or not inst.caps.witness:
            inst = self._instances["jax_faithful"] = \
                make_backend("jax_faithful")
        return inst

    def _resolve_properties(self, name: Optional[str]) -> ChordalityBackend:
        """Like :meth:`_resolve` but guarantees the ``properties``
        capability: units landing on a backend without recognition
        executables fall back to ``jax_fast`` (the device twin; numpy_ref
        holds the host twin and is reachable by name or routing)."""
        backend = self._resolve(name)
        if backend.caps.properties:
            return backend
        inst = self._instances.get("jax_fast")
        if inst is None or not inst.caps.properties:
            inst = self._instances["jax_fast"] = make_backend("jax_fast")
        return inst

    @staticmethod
    def _realize(backend: ChordalityBackend, unit, graphs):
        if backend.caps.sparse:
            return realize_unit_csr(unit, graphs)
        return realize_unit(unit, graphs)

    @staticmethod
    def _unit_n_nodes(unit, graphs) -> np.ndarray:
        """(batch,) logical sizes (0 legal: empty structures come back)."""
        n_vec = np.zeros(unit.batch, dtype=np.int32)
        for slot, idx in enumerate(unit.indices):
            n_vec[slot] = graphs[idx].n_nodes
        return n_vec

    # -- planning ----------------------------------------------------------
    def plan(self, graphs: Sequence[Graph],
             witness: Optional[bool] = None,
             properties: Optional[Sequence[str]] = None) -> Plan:
        """Shape-bucketed plan; auto engines route each unit.

        ``witness`` (default: the engine's witness setting) prices the
        routing with the witness-mode cost model — certified units run
        heavier executables, so their backend crossovers differ.
        ``properties`` prices with the recognition-mode model instead
        (``DEFAULT_RECOGNITION_COST_MODEL``) and requires the
        ``properties`` capability.
        """
        witness = self.witness_default if witness is None else witness
        plan = plan_requests(
            graphs, max_batch=self.max_batch, buckets=self.buckets)
        if self.router is not None:
            mode = "recognition" if properties is not None else None
            plan = self.router.annotate(
                plan, graphs, witness=bool(witness), mode=mode)
        return plan

    def route_unit(self, unit, graphs: Sequence[Graph]):
        """Annotate one work unit with the router's per-unit choice.

        Fixed-backend engines return the unit unchanged (the engine's own
        backend applies); auto engines route it exactly like a unit inside
        a full plan. This is the admission-time twin of :meth:`plan` —
        the async service routes each drained bucket through it.
        """
        if self.router is None:
            return unit
        routed = self.router.annotate(
            Plan(units=[unit], n_requests=len(unit.indices)), graphs)
        return routed.units[0]

    def warmup(self, n_pads: Sequence[int], batch: Optional[int] = None,
               witness: Optional[bool] = None):
        """Pre-compile the given buckets at one batch size (default
        ``max_batch`` — the steady-state full-chunk shape). Requires a
        fixed backend; auto engines warm up per plan (:meth:`warmup_plan`,
        which knows the router's choices). ``witness`` (default: the
        engine's witness setting) additionally warms the fused witness
        executables for the same shapes."""
        if self.backend is None:
            raise ValueError(
                "warmup() needs a fixed backend; use warmup_plan() with "
                "an auto engine")
        witness = self.witness_default if witness is None else witness
        b = batch if batch is not None else self.max_batch
        wbackend = self._resolve_witness(self.backend.name) \
            if witness else None
        for n_pad in n_pads:
            fn = self.cache.get(
                self.backend, n_pad, b,
                kind=self.backend.verdict_kind(n_pad))
            fn(np.zeros((b, n_pad, n_pad), dtype=bool))
            if wbackend is not None:
                wfn = self.cache.get(
                    wbackend, n_pad, b,
                    kind=wbackend.witness_kind(n_pad))
                wfn(np.zeros((b, n_pad, n_pad), dtype=bool),
                    np.zeros(b, dtype=np.int32))
        return self

    def warmup_plan(self, plan: Plan, graphs: Optional[Sequence[Graph]] = None,
                    witness: Optional[bool] = None):
        """Pre-compile exactly the shapes a plan needs.

        For dense backends the (backend, n_pad, batch) key fully determines
        the compiled shape, so empty probes suffice. Sparse (CSR) work
        units additionally compile against the (nnz_pad, deg_pad) buckets
        of their *contents* — pass the plan's ``graphs`` to warm those
        exact buckets; without graphs, sparse units warm the minimum
        buckets only (best effort — real traffic may still compile once
        per new edge-count bucket).
        """
        witness = self.witness_default if witness is None else witness
        seen = set()
        for unit in plan.units:
            backend = self._resolve(unit.backend)
            key = (backend.name, unit.n_pad, unit.batch)
            fn = self.cache.get(
                backend, unit.n_pad, unit.batch,
                kind=backend.verdict_kind(unit.n_pad))
            wfn = None
            if witness:
                wbackend = self._resolve_witness(unit.backend)
                wfn = self.cache.get(
                    wbackend, unit.n_pad, unit.batch,
                    kind=wbackend.witness_kind(unit.n_pad))
            if backend.caps.sparse and graphs is not None:
                payload = realize_unit_csr(unit, graphs)
                fn(payload)
                if wfn is not None:
                    wfn(payload, self._unit_n_nodes(unit, graphs))
                continue
            if key in seen:
                continue
            seen.add(key)
            probe = np.zeros(
                (unit.batch, unit.n_pad, unit.n_pad), dtype=bool)
            fn(probe)
            if wfn is not None:
                wfn(probe, np.ones(unit.batch, dtype=np.int32))
        return self

    # -- execution ---------------------------------------------------------
    def execute_unit(self, unit, graphs: Sequence[Graph]):
        """Run one work unit: ``(verdicts, backend_name, exec_ms)``.

        The single execution path shared by :meth:`run` and the async
        service's executor thread: resolve the unit's backend, realize the
        payload (dense or padded-CSR by capability), fetch the executable
        from the compile cache, run it. ``verdicts`` align to
        ``unit.indices`` order (padding slots already dropped); ``exec_ms``
        covers the executable call only (realize/compile time is visible
        through the cache counters instead).
        """
        out, backend_name, exec_ms, _ = self._execute_unit_sampled(
            unit, graphs)
        return out, backend_name, exec_ms

    def _execute_unit_sampled(self, unit, graphs: Sequence[Graph]):
        """:meth:`execute_unit` plus the unit's router-calibration sample
        (logged engine-wide and returned, so ``run`` can attribute its own
        units' samples to its stats without racing the async executor's
        appends to the shared log)."""
        backend = self._resolve(unit.backend)
        kind = backend.verdict_kind(unit.n_pad)
        # A dense unit lives in a pooled buffer, returned when the stack
        # closes: after the executable has handed back host verdicts, so
        # the device has read its input (DESIGN.md §6).
        with obs.span("unit", n_pad=unit.n_pad, batch=unit.batch,
                      backend=backend.name, kind=kind), \
                contextlib.ExitStack() as lease:
            with obs.span("realize"):
                if backend.caps.sparse:
                    payload = realize_unit_csr(unit, graphs)
                else:
                    payload = lease.enter_context(
                        self.realize_pool.realized(unit, graphs))
            fn = self.cache.get(backend, unit.n_pad, unit.batch, kind=kind)
            t1 = _clock.now()
            with obs.span("dispatch", backend=backend.name, kind=kind):
                out = fn(payload)
            exec_ms = (_clock.now() - t1) * 1e3
        sample = (
            backend.name, unit.n_pad,
            float(np.mean([graphs[i].n_edges for i in unit.indices]))
            / float(unit.n_pad * unit.n_pad) if unit.indices else 0.0,
            unit.batch, int(getattr(backend, "device_count", 1) or 1),
            exec_ms * 1e3 / max(unit.batch, 1))
        self._router_samples.append(sample)
        self._router_samples_total += 1
        excess = len(self._router_samples) - self._router_samples_cap
        if excess > 0:
            del self._router_samples[:excess]
        return out[: len(unit.indices)], backend.name, exec_ms, sample

    def execute_unit_witness(self, unit, graphs: Sequence[Graph]):
        """Run one work unit's witness pass:
        ``(verdicts, witnesses, backend_name, exec_ms)``.

        The witness twin of :meth:`execute_unit`: one fused executable
        (cached under ``backend.witness_kind(n_pad)`` — ``"witness"`` or
        the raw-material ``"fused_witness"`` — on the same bucket key)
        produces
        verdict **and** certificate structures per slot; the padded
        :class:`~repro.witness.WitnessBatch` is cropped to per-request
        ``WitnessResult``\\ s. A non-witness backend on the unit falls
        back to ``jax_faithful`` (see :meth:`_resolve_witness`).
        """
        backend = self._resolve_witness(unit.backend)
        kind = backend.witness_kind(unit.n_pad)
        with obs.span("unit", n_pad=unit.n_pad, batch=unit.batch,
                      backend=backend.name, kind=kind):
            with obs.span("realize"):
                payload = self._realize(backend, unit, graphs)
                n_vec = self._unit_n_nodes(unit, graphs)
            fn = self.cache.get(backend, unit.n_pad, unit.batch, kind=kind)
            t1 = _clock.now()
            with obs.span("dispatch", backend=backend.name, kind=kind):
                wb = fn(payload, n_vec)
            exec_ms = (_clock.now() - t1) * 1e3
            with obs.span("finalize", kind="witness_crop"):
                witnesses = []
                for slot, idx in enumerate(unit.indices):
                    g = graphs[idx]
                    adj = None
                    if not wb.chordal[slot] and wb.cycle_len[slot] < 4:
                        adj = g.with_dense().adj  # exhaustive-fallback input
                    witnesses.append(wb.result(slot, g.n_nodes, adj=adj))
                verdicts = np.asarray(
                    wb.chordal[: len(unit.indices)], dtype=bool)
        return verdicts, witnesses, backend.name, exec_ms

    def execute_unit_recognition(
        self, unit, graphs: Sequence[Graph], properties: Sequence[str]
    ):
        """Run one work unit's multi-property recognition pass:
        ``(recognition_batch, results, backend_name, exec_ms)``.

        The recognition twin of :meth:`execute_unit`: one shared-sweep
        executable (cached under ``"recognition:<props>"`` on the same
        bucket key) answers every requested property; ``results`` are the
        per-request ``repro.recognition.RecognitionResult``\\ s in
        ``unit.indices`` order. A unit landing on a backend without the
        ``properties`` capability falls back to ``jax_fast``
        (:meth:`_resolve_properties`).
        """
        from repro.recognition import normalize_properties

        props = normalize_properties(properties)
        backend = self._resolve_properties(unit.backend)
        kind = "recognition:" + ",".join(props)
        with obs.span("unit", n_pad=unit.n_pad, batch=unit.batch,
                      backend=backend.name, kind=kind):
            with obs.span("realize"):
                payload = realize_unit(unit, graphs)  # dense contract only
                n_vec = self._unit_n_nodes(unit, graphs)
            fn = self.cache.get(backend, unit.n_pad, unit.batch, kind=kind)
            t1 = _clock.now()
            with obs.span("dispatch", backend=backend.name, kind=kind):
                rb = fn(payload, n_vec)
            exec_ms = (_clock.now() - t1) * 1e3
            with obs.span("finalize", kind="recognition_crop"):
                results = [
                    rb.result(slot, graphs[idx].n_nodes)
                    for slot, idx in enumerate(unit.indices)
                ]
        return rb, results, backend.name, exec_ms

    def run(
        self, graphs: Sequence[Graph], witness: Optional[bool] = None,
        properties: Optional[Sequence[str]] = None,
    ) -> EngineResult:
        """Test a stream of graphs; verdicts come back in request order.

        ``witness=True`` (or constructing the engine with
        ``witness=True``) additionally returns one checkable
        ``repro.witness.WitnessResult`` per request — same plan, same
        buckets, one fused witness executable per unit instead of the
        verdict-only one.

        ``properties=[...]`` switches the run to multi-property
        recognition (``repro.recognition``): every unit executes one
        shared-sweep executable answering all requested properties, the
        result carries a bool plane per normalized property
        (``result.properties``) plus per-request ``RecognitionResult``\\ s
        (``result.recognitions``); ``verdicts`` stays the chordal plane.
        Mutually exclusive with ``witness=True`` — recognition carries its
        own (proper-interval) witness structures.
        """
        witness = self.witness_default if witness is None else witness
        if properties is not None:
            if witness:
                raise ValueError(
                    "witness=True and properties=[...] are mutually "
                    "exclusive; recognition runs carry their own "
                    "proper-interval witnesses")
            return self._run_recognition(graphs, properties)
        plan = self.plan(graphs, witness=witness)
        verdicts = np.zeros(plan.n_requests, dtype=bool)
        witnesses: Optional[List] = [None] * plan.n_requests \
            if witness else None
        stats = EngineStats(
            n_requests=plan.n_requests, n_units=len(plan.units))
        hits0, misses0 = self.cache.hits, self.cache.misses
        t0 = _clock.now()
        for unit in plan.units:
            if witness:
                out, wits, backend_name, exec_ms = \
                    self.execute_unit_witness(unit, graphs)
                for idx, w in zip(unit.indices, wits):
                    witnesses[idx] = w
            else:
                out, backend_name, exec_ms, sample = \
                    self._execute_unit_sampled(unit, graphs)
                stats.unit_samples.append(sample)
            stats.unit_latencies_ms.append(exec_ms)
            verdicts[list(unit.indices)] = out
            stats.backend_histogram[backend_name] = (
                stats.backend_histogram.get(backend_name, 0)
                + len(unit.indices))
        stats.wall_s = _clock.now() - t0
        stats.compile_hits = self.cache.hits - hits0
        stats.compile_misses = self.cache.misses - misses0
        stats.bucket_histogram = plan.bucket_histogram
        return EngineResult(
            verdicts=verdicts, plan=plan, stats=stats, witnesses=witnesses)

    def _run_recognition(
        self, graphs: Sequence[Graph], properties: Sequence[str]
    ) -> EngineResult:
        """The recognition body of :meth:`run` (``properties=[...]``)."""
        from repro.recognition import normalize_properties

        props = normalize_properties(properties)
        plan = self.plan(graphs, witness=False, properties=props)
        planes = {
            p: np.zeros(plan.n_requests, dtype=bool) for p in props}
        recognitions: List = [None] * plan.n_requests
        stats = EngineStats(
            n_requests=plan.n_requests, n_units=len(plan.units))
        hits0, misses0 = self.cache.hits, self.cache.misses
        t0 = _clock.now()
        for unit in plan.units:
            rb, results, backend_name, exec_ms = \
                self.execute_unit_recognition(unit, graphs, props)
            stats.unit_latencies_ms.append(exec_ms)
            for slot, (idx, res) in enumerate(
                    zip(unit.indices, results)):
                recognitions[idx] = res
                for p in props:
                    planes[p][idx] = bool(rb.verdicts[p][slot])
            stats.backend_histogram[backend_name] = (
                stats.backend_histogram.get(backend_name, 0)
                + len(unit.indices))
        stats.wall_s = _clock.now() - t0
        stats.compile_hits = self.cache.hits - hits0
        stats.compile_misses = self.cache.misses - misses0
        stats.bucket_histogram = plan.bucket_histogram
        return EngineResult(
            verdicts=planes["chordal"].copy(), plan=plan, stats=stats,
            properties=planes, recognitions=recognitions)

    def refit_router(self, min_samples: int = 4,
                     min_distinct_n: int = 2):
        """Online re-fit of the router's cost model from this session's own
        measured unit latencies (ROADMAP PR 3 extension).

        Every executed unit leaves one ``(backend, n_pad, density, batch,
        device_count, us_per_graph)`` row in the engine's measurement log
        (surfaced per run as ``EngineStats.unit_samples``); this re-runs
        the same
        least-squares fit the offline ``--tables router`` calibration uses
        on those rows, updates the router's coefficients for every backend
        with at least ``min_samples`` measurements (others keep their
        prior coefficients), and — the safety property — **clamps the
        router's fitted support** (``fit_n_range``) to the n-range
        actually observed, so a refit can never extrapolate routing
        decisions outside the regime it was fitted on (regression-tested
        in tests/test_router.py).

        Degenerate live logs are refused, not extrapolated: a backend
        whose samples cover fewer than ``min_distinct_n`` distinct n
        values keeps its prior coefficients (a one-point fit has no
        slope — it would price every other regime off a constant), and
        ``fit_n_range`` only narrows to the observed span when that span
        is a real interval (lo < hi). Single-n traffic — the common case
        for a service warming up on one bucket — therefore leaves both
        the model and the clamping range at their priors, so unobserved
        regimes keep routing on the committed fit.

        Thread safety: the fitted coefficients are installed by swapping
        the cost-model dict wholesale, so concurrent ``route_unit``
        readers see either the old model or the new one, never a
        half-updated mix.

        Returns the tuple of backend names whose coefficients were
        refitted (empty if no backend reached ``min_samples`` /
        ``min_distinct_n``).
        """
        if self.router is None:
            raise ValueError(
                "refit_router() needs backend='auto' (no router to refit)")
        from repro.engine.router import fit_cost_model

        log = list(self._router_samples)   # snapshot vs concurrent appends
        by_backend: Dict[str, List[tuple]] = {}
        for s in log:
            by_backend.setdefault(s[0], []).append(s)
        samples = [
            s for rows in by_backend.values()
            if len(rows) >= min_samples
            and len({r[1] for r in rows}) >= min_distinct_n
            for s in rows
        ]
        if not samples:
            return ()
        fitted = fit_cost_model(samples)
        self.router.cost_model = {**self.router.cost_model, **fitted}
        ns = {s[1] for s in samples}
        lo, hi = min(ns), max(ns)
        if lo < hi:
            self.router.fit_n_range = (int(lo), int(hi))
        # Device support clamps to what the live log actually measured —
        # including *narrowing* to (1, 1) when every sample ran single-
        # device, so a refit from such logs never extrapolates mesh costs
        # (the PR 10 clamp_features satellite; tests/test_router.py).
        ds = {s[4] for s in samples}
        self.router.fit_device_range = (int(min(ds)), int(max(ds)))
        return tuple(sorted(fitted))

    @property
    def router_sample_count(self) -> int:
        """Unit samples ever logged (monotone — unaffected by the log cap).

        The async service's online-refit trigger compares this against
        the count at its last refit to decide when enough fresh evidence
        has accumulated; ``len`` of the capped log can't serve that role
        because it stops moving once the cap is reached.
        """
        return self._router_samples_total

    def telemetry(self) -> dict:
        """Session-level observability snapshot (DESIGN.md §15).

        Returns the engine's compile-cache traffic (with hit ratio), the
        router-calibration sample count, and the process-global metrics
        registry snapshot (which the cache and kernel counters publish
        into).
        """
        hits, misses = self.cache.hits, self.cache.misses
        total = hits + misses
        return {
            "cache": {
                "entries": len(self.cache),
                "hits": hits,
                "misses": misses,
                "hit_ratio": hits / total if total else 0.0,
            },
            "router_samples": self._router_samples_total,
            "metrics": obs.registry.snapshot(),
        }

    def _pad_single(self, graph_or_adj):
        """Normalize one request to its bucket: ``(padded, n, n_pad)``.

        Graphs are sliced to their logical size first (pre-existing
        padding vertices are isolated by contract), so the request lands
        in the bucket its logical size deserves.
        """
        if isinstance(graph_or_adj, Graph):
            g = graph_or_adj.with_dense()
            n = g.n_nodes
            adj = g.adj[:n, :n]
        else:
            adj = np.asarray(graph_or_adj, dtype=bool)
            n = adj.shape[0]
        n_pad = bucket_npad(max(n, 1), self.buckets)
        padded = np.zeros((n_pad, n_pad), dtype=bool)
        padded[:n, :n] = adj[:n, :n]
        return padded, n, n_pad

    def _route_single(self, padded, n_pad: int, require,
                      mode: str = "verdict") -> Optional[str]:
        """Router's pick for a padded batch=1 request (None on fixed
        engines — the caller applies its own fallback policy)."""
        if self.router is None:
            return None
        density = float(padded.sum()) / float(n_pad * n_pad)
        return self.router.choose(
            n_pad, density, batch=1, require=require, mode=mode)

    def certificate(self, graph_or_adj) -> Certificate:
        """Detailed single-graph answer through the engine's shape planning.

        Auto engines route with the certificate capability required;
        fixed engines fall back to ``jax_faithful`` when their backend
        cannot produce certificates (e.g. ``sharded``).
        """
        padded, n, n_pad = self._pad_single(graph_or_adj)
        name = self._route_single(padded, n_pad, ("certificate",))
        if name is not None:
            backend = self._resolve(name)
        else:
            backend = self.backend
            if not backend.caps.certificate:
                backend = make_backend("jax_faithful")
        ok, order, viol = backend.certificate(padded)
        return Certificate(
            chordal=bool(ok), order=np.asarray(order),
            n_violations=int(viol), n_pad=n_pad)

    def witness(self, graph_or_adj):
        """Checkable single-graph witness (``repro.witness.WitnessResult``).

        Rides the same bucket grid and compile cache as batch runs — the
        request pads to its bucket and executes a ``batch=1`` witness
        program. Auto engines route with the witness capability required;
        fixed engines fall back to ``jax_faithful`` if their backend
        cannot produce witnesses.
        """
        padded, n, n_pad = self._pad_single(graph_or_adj)
        backend = self._resolve_witness(
            self._route_single(padded, n_pad, ("witness",),
                               mode="witness"))
        fn = self.cache.get(
            backend, n_pad, 1, kind=backend.witness_kind(n_pad))
        wb = fn(padded[None], np.array([n], dtype=np.int32))
        adj_fallback = padded if (
            not wb.chordal[0] and wb.cycle_len[0] < 4) else None
        return wb.result(0, n, adj=adj_fallback)

    def recognize(self, graph_or_adj, properties: Optional[Sequence[str]]
                  = None):
        """Single-graph multi-property answer
        (``repro.recognition.RecognitionResult``).

        Defaults to the full property registry. Rides the same bucket
        grid and compile cache as batch runs — the request pads to its
        bucket and executes a ``batch=1`` recognition program whose
        sweeps are shared across all requested properties. Auto engines
        route with the ``properties`` capability required; fixed engines
        fall back to ``jax_fast`` if their backend lacks it.
        """
        from repro.recognition import normalize_properties, property_names

        props = normalize_properties(
            properties if properties is not None else property_names())
        padded, n, n_pad = self._pad_single(graph_or_adj)
        backend = self._resolve_properties(
            self._route_single(padded, n_pad, ("properties",),
                               mode="recognition"))
        fn = self.cache.get(
            backend, n_pad, 1, kind="recognition:" + ",".join(props))
        rb = fn(padded[None], np.array([n], dtype=np.int32))
        return rb.result(0, n)
