"""Chordality engine — backend dispatch + bucketed batching (DESIGN.md §6).

This subsystem is the production entry point for the paper's pipeline
(parallel LexBFS §6.1 + parallel PEO test §6.2): a backend registry over
every implementation in the repo, a planner that turns ragged request
streams into fixed-shape work units (dense or padded-CSR), a cost-model
router for adaptive backend selection, a session layer with throughput
and latency stats, and an async serving layer
(:class:`AsyncChordalityEngine`, DESIGN.md §9) that micro-batches a live
request stream onto the same planner/cache/router — with per-request
deadlines and an ``asubmit`` asyncio adapter. Witness runs
(``run(..., witness=True)``, ``submit(want_witness=True)``) attach
independently checkable certificates from ``repro.witness`` (clique
tree / treewidth / optimal coloring, or an induced chordless cycle —
DESIGN.md §10), compiled and cached per bucket exactly like verdict
programs. Recognition runs (``run(..., properties=[...])``,
``submit(properties=[...])``) answer multiple graph-class properties —
chordal, proper interval, interval, MCS/LexDFS order checks — from shared
LexBFS-family sweeps through the ``repro.recognition`` registry
(DESIGN.md §13), again compiled and cached per bucket
(``kind="recognition:<props>"``). Direct use of the ``repro.core`` multi-entry functions
is deprecated for serving/benchmark callers — go through
:class:`ChordalityEngine`.

Backend discovery: :func:`list_backends` returns every registered
:class:`BackendSpec` (name, capability flags, one-line doc);
``ChordalityEngine(backend="auto")`` lets the router pick per work unit.
"""
from repro.engine.autotune import (
    Autotuner,
    RefitPolicy,
)
from repro.engine.backends import (
    BackendCaps,
    BackendSpec,
    ChordalityBackend,
    backend_names,
    backend_spec,
    list_backends,
    make_backend,
    register_backend,
)
from repro.engine.mesh import (
    MESH_AXIS,
    build_mesh,
    host_device_count,
    make_mesh_verdict_runner,
    make_mesh_verdicts,
    mesh_device_count,
    mesh_signature,
    pad_to_shards,
)
from repro.engine.planner import (
    CompileCache,
    Plan,
    RealizePool,
    WorkUnit,
    plan_requests,
    realize_unit,
    realize_unit_csr,
    unit_for_chunk,
)
from repro.engine.router import (
    BackendCost,
    DEFAULT_COST_MODEL,
    DEFAULT_FIT_DEVICE_RANGE,
    DEFAULT_FIT_N_RANGE,
    DEFAULT_RECOGNITION_COST_MODEL,
    PLATFORM_COST_MODELS,
    Router,
    fit_cost_model,
    platform_cost_model,
)
from repro.engine.service import (
    AsyncChordalityEngine,
    QueueFullError,
    ServiceClosedError,
    ServiceResponse,
    ServiceStats,
    gather,
)
from repro.engine.session import (
    Certificate,
    ChordalityEngine,
    EngineResult,
    EngineStats,
)

__all__ = [
    "Autotuner",
    "RefitPolicy",
    "BackendCaps",
    "BackendSpec",
    "ChordalityBackend",
    "backend_names",
    "backend_spec",
    "list_backends",
    "make_backend",
    "register_backend",
    "MESH_AXIS",
    "build_mesh",
    "host_device_count",
    "make_mesh_verdict_runner",
    "make_mesh_verdicts",
    "mesh_device_count",
    "mesh_signature",
    "pad_to_shards",
    "CompileCache",
    "Plan",
    "RealizePool",
    "WorkUnit",
    "plan_requests",
    "realize_unit",
    "realize_unit_csr",
    "unit_for_chunk",
    "BackendCost",
    "DEFAULT_COST_MODEL",
    "DEFAULT_FIT_DEVICE_RANGE",
    "DEFAULT_FIT_N_RANGE",
    "DEFAULT_RECOGNITION_COST_MODEL",
    "PLATFORM_COST_MODELS",
    "Router",
    "fit_cost_model",
    "platform_cost_model",
    "AsyncChordalityEngine",
    "QueueFullError",
    "ServiceClosedError",
    "ServiceResponse",
    "ServiceStats",
    "gather",
    "Certificate",
    "ChordalityEngine",
    "EngineResult",
    "EngineStats",
]
