"""Backend registry — every chordality implementation behind one protocol.

The repo grew five divergent entry points (``is_chordal``,
``is_chordal_fast``, ``is_chordal_batch``, ``make_sharded_chordality``,
``is_chordal_host``); this module is the single seam that replaces direct
multi-entry use.  Each implementation registers a :class:`BackendSpec` with
capability flags, and exposes exactly two operations:

* ``compile_batch(n_pad, batch)`` — build the executable for one fixed
  work-unit shape ``(batch, n_pad, n_pad)``.  The planner's compile cache
  (``repro.engine.planner.CompileCache``) stores what this returns, keyed
  on ``(backend, cache_scope, kind, n_pad, batch)`` where
  ``cache_scope()`` names the platform + device (or mesh slice) the
  executable is pinned to, so jit compilation is paid once per bucket
  shape per device scope, not per request.
* ``certificate(adj)`` — the detailed single-graph answer
  ``(chordal, order, n_violations)`` for backends that can produce one.

Witness-capable backends additionally expose ``compile_witness_batch`` —
the same fixed-shape contract, but the executable returns a
``repro.witness.WitnessBatch`` (verdict + clique tree/treewidth/coloring
or chordless-cycle counterexample in one pass, see DESIGN.md §10).

Property-capable backends (``caps.properties``) additionally expose
``compile_recognition_batch`` — multi-property recognition executables
(``repro.recognition``) returning a ``RecognitionBatch`` from one shared
sweep plan; cached under ``kind="recognition:<props>"``.

Registered backends:

========== ======== ======= ============ ====== ======= ===== ====================
name       batched  device  certificate  sparse witness props implementation
========== ======== ======= ============ ====== ======= ===== ====================
numpy_ref  no       no      yes          no     yes     yes   lexbfs_numpy_dense
jax_faithful yes    yes     yes          no     yes     no    lexbfs (§6.1)
jax_fast   yes      yes     yes          no     yes     yes   lexbfs_fast (lazy)
pallas_peo no       yes     yes          no     yes     no    lexbfs + Pallas PEO
sharded    yes      yes     no           no     no      no    jit over a batch-sharded mesh
csr        yes      yes     yes          yes    yes     no    repro.sparse CSR
========== ======== ======= ============ ====== ======= ===== ====================

``sparse`` backends consume :class:`repro.sparse.packing.PackedCSRBatch`
payloads (the planner realizes those without densifying); every backend's
``compile_batch`` executable also accepts the dense host-array contract, so
warmup and generic callers stay uniform.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class BackendCaps:
    """Capability flags the planner/session/router dispatch on."""

    batched: bool       # natively executes (B, N, N) in one device program
    device: bool        # runs under jit on the accelerator
    certificate: bool   # can produce (order, n_violations) witnesses
    sparse: bool = False  # consumes PackedCSRBatch work units (O(N+M) path)
    witness: bool = False  # compiles WitnessBatch executables (repro.witness)
    fused: bool = False  # compiles one-dispatch-per-unit fused executables
    properties: bool = False  # compiles RecognitionBatch executables
    #                           (multi-property, repro.recognition)


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    name: str
    caps: BackendCaps
    factory: Callable[..., "ChordalityBackend"]
    doc: str = ""


class ChordalityBackend:
    """Protocol base class. Subclasses set ``name``/``caps`` and implement
    :meth:`compile_batch`; certificate-capable ones also implement
    :meth:`certificate`."""

    name: str = "abstract"
    caps: BackendCaps = BackendCaps(False, False, False)
    #: Devices a work unit spans on this backend — the router's
    #: ``device_count`` cost feature. Mesh backends override.
    device_count: int = 1

    def cache_scope(self) -> str:
        """Which platform/device the compiled executables are pinned to —
        the compile cache's scope key component (DESIGN.md §16).

        Host backends share one ``"host"`` scope; single-device jit
        backends are keyed per platform + default device (``"cpu:0"``);
        mesh backends override with their mesh signature
        (``"cpu:mesh8"``) so an executable compiled against one device
        slice is never served to another.
        """
        if not self.caps.device:
            return "host"
        scope = self.__dict__.get("_cache_scope")
        if scope is None:
            import jax

            scope = f"{jax.default_backend()}:0"
            self.__dict__["_cache_scope"] = scope
        return scope

    def compile_batch(
        self, n_pad: int, batch: int
    ) -> Callable[[np.ndarray], np.ndarray]:
        """Executable for the fixed shape (batch, n_pad, n_pad) -> (batch,).

        Input is a host bool array; output a host bool array of verdicts.
        Backends without native batching return a host loop here — the
        shape contract (and thus the compile-cache key) is identical.
        """
        raise NotImplementedError

    def certificate(
        self, adj: np.ndarray
    ) -> Tuple[bool, np.ndarray, int]:
        """(chordal, elimination order, violation count) for one graph."""
        raise NotImplementedError(
            f"backend {self.name!r} does not produce certificates")

    def verdict_kind(self, n_pad: int) -> str:
        """Which executable family serves this backend's verdicts at a
        bucket: ``"verdict"`` (``compile_batch``) or ``"fused"``
        (``compile_fused_batch`` — one device dispatch per work unit).
        The session/compile-cache key this per bucket, so a backend can
        serve small buckets fused and fall back past its memory budget.
        """
        return "verdict"

    def compile_fused_batch(
        self, n_pad: int, batch: int
    ) -> Callable[[np.ndarray], np.ndarray]:
        """Fused-pipeline executable: same contract as :meth:`compile_batch`
        but the whole unit must execute in one device dispatch. Backends
        carrying the ``fused`` capability implement this; the compile
        cache stores it under ``kind="fused"``."""
        raise NotImplementedError(
            f"backend {self.name!r} has no fused pipeline")

    def witness_kind(self, n_pad: int) -> str:
        """Which executable family serves certified traffic at a bucket:
        ``"witness"`` (:meth:`compile_witness_batch`) or
        ``"fused_witness"`` (:meth:`compile_fused_witness_batch` — the
        verdict kernel emits certificate raw material in the same
        dispatch). Mirrors :meth:`verdict_kind`; the session/compile
        cache key it per bucket."""
        return "witness"

    def compile_witness_batch(self, n_pad: int, batch: int):
        """Executable for the witness pass at one fixed shape.

        Contract: ``fn(payload, n_nodes) -> repro.witness.WitnessBatch``
        where ``payload`` follows the backend's batch contract (dense
        host array, or PackedCSRBatch for sparse backends) and
        ``n_nodes`` is the (batch,) vector of logical sizes. Entries may
        be 0 — padding slots are passed as 0 and must come back with
        empty structures. Backends carrying the ``witness`` capability
        must implement this; the planner's compile cache stores the
        result under ``kind="witness"``.
        """
        raise NotImplementedError(
            f"backend {self.name!r} does not produce witnesses")

    def compile_fused_witness_batch(self, n_pad: int, batch: int):
        """Same contract as :meth:`compile_witness_batch`, but the device
        work must be the backend's *one* fused dispatch (verdict +
        certificate raw material in a single kernel launch); cached under
        ``kind="fused_witness"``."""
        raise NotImplementedError(
            f"backend {self.name!r} has no fused witness pipeline")

    def compile_fused_packed_batch(
        self, n_pad: int, batch: int
    ) -> Callable[[np.ndarray], np.ndarray]:
        """Packed tiny-bucket variant of :meth:`compile_fused_batch`:
        multiple graphs per grid program (``FUSED_PACK_FACTOR``
        block-diagonal units), still one device dispatch per work unit;
        cached under ``kind="fused_packed"``."""
        raise NotImplementedError(
            f"backend {self.name!r} has no packed fused pipeline")

    def compile_recognition_batch(
        self, n_pad: int, batch: int, properties: Tuple[str, ...]
    ):
        """Executable for a multi-property recognition pass at one shape.

        Contract: ``fn(payload, n_nodes) ->
        repro.recognition.RecognitionBatch`` — the dense host-array
        payload, plus the (batch,) logical sizes (0 for padding slots,
        which come back trivially true). ``properties`` is the
        *normalized* tuple (``repro.recognition.normalize_properties``) so
        the compile-cache kind ``"recognition:<p1,p2,...>"`` is stable
        regardless of request phrasing. Backends carrying the
        ``properties`` capability must implement this.
        """
        raise NotImplementedError(
            f"backend {self.name!r} does not answer property requests")


# ---------------------------------------------------------------------------
# Implementations (thin adapters over repro.core / repro.kernels).
# ---------------------------------------------------------------------------
class NumpyRefBackend(ChordalityBackend):
    """Host reference: the dense numpy rank-refinement twin. No jit — the
    compile cache is a no-op for it, but it honors the same shape contract
    so the planner treats every backend uniformly."""

    name = "numpy_ref"
    caps = BackendCaps(batched=False, device=False, certificate=True,
                       witness=True, properties=True)

    def compile_batch(self, n_pad, batch):
        from repro.core.lexbfs import lexbfs_numpy_dense
        from repro.core.peo import peo_check_numpy

        def run(adjs: np.ndarray) -> np.ndarray:
            out = np.zeros(adjs.shape[0], dtype=bool)
            for i, adj in enumerate(adjs):
                order = lexbfs_numpy_dense(adj)
                out[i] = peo_check_numpy(adj, order)
            return out

        return run

    def certificate(self, adj):
        from repro.core.lexbfs import lexbfs_numpy_dense
        from repro.core.peo import peo_violations_numpy

        order = lexbfs_numpy_dense(np.asarray(adj, dtype=bool))
        viol = peo_violations_numpy(adj, order)
        return viol == 0, np.asarray(order), viol

    def compile_witness_batch(self, n_pad, batch):
        from repro.core.lexbfs import lexbfs_numpy_dense
        from repro.witness import witness_batch_numpy

        def run(adjs, n_nodes):
            adjs = np.asarray(adjs, dtype=bool)
            orders = np.stack([lexbfs_numpy_dense(a) for a in adjs])
            return witness_batch_numpy(adjs, orders, n_nodes)

        return run

    def compile_recognition_batch(self, n_pad, batch, properties):
        from repro.recognition import make_recognition_host

        return make_recognition_host(properties)


class _JaxBackendBase(ChordalityBackend):
    """Shared device plumbing for the jnp pipelines."""

    def _order_fn(self):
        raise NotImplementedError

    def compile_batch(self, n_pad, batch):
        import jax

        from repro.core.peo import peo_check

        order_fn = self._order_fn()

        def one(adj):
            return peo_check(adj, order_fn(adj))

        fn = jax.jit(jax.vmap(one))

        def run(adjs: np.ndarray) -> np.ndarray:
            # numpy in, numpy out: jit's implicit device_put beats an
            # explicit jnp.asarray round-trip on the small-unit hot path.
            return np.asarray(fn(adjs))

        return run

    def certificate(self, adj):
        import jax.numpy as jnp

        from repro.core.peo import peo_violations

        order = self._order_fn()(jnp.asarray(np.asarray(adj, dtype=bool)))
        viol = int(peo_violations(jnp.asarray(adj), order))
        return viol == 0, np.asarray(order), viol

    def compile_witness_batch(self, n_pad, batch):
        from repro.witness import make_witness_kernel

        return make_witness_kernel(self._order_fn())


class JaxFaithfulBackend(_JaxBackendBase):
    """Paper-faithful pipeline: per-iteration rank compaction (§6.1+§6.2,
    ``lexbfs_scan``) — the differential anchor among the device backends."""

    name = "jax_faithful"
    caps = BackendCaps(batched=True, device=True, certificate=True,
                       witness=True)

    def _order_fn(self):
        from repro.core.lexbfs import lexbfs_scan

        return lexbfs_scan


class JaxFastBackend(_JaxBackendBase):
    """Restructured batch-major LexBFS (lazy comparator compaction, PR 5).
    Bit-identical orders to jax_faithful — asserted in
    tests/test_engine_backends.py."""

    name = "jax_fast"
    caps = BackendCaps(batched=True, device=True, certificate=True,
                       witness=True, properties=True)

    def _order_fn(self):
        from repro.core.lexbfs import lexbfs_fast

        return lexbfs_fast

    def compile_witness_batch(self, n_pad, batch):
        # The batch-major fused executable: same orders (lexbfs_fast IS
        # the batch-major loop), one jit dispatch, and the clique/cycle
        # follow-ups gated at batch granularity instead of vmapped
        # select-both-branches. jax_faithful keeps the vmapped reference
        # kernel, preserving the differential pair.
        from repro.witness import make_fused_witness_kernel

        return make_fused_witness_kernel()

    def compile_recognition_batch(self, n_pad, batch, properties):
        # The shared-sweep device program: one jit dispatch answers every
        # requested property (repro.recognition.sweeps). numpy_ref holds
        # the bit-identical host twin, preserving the differential pair.
        from repro.recognition import make_recognition_kernel

        return make_recognition_kernel(properties)


class PallasPeoBackend(ChordalityBackend):
    """The Pallas kernel backend — two pipelines over one registry entry:

    * ``fused`` — the single-pass LexBFS+PEO kernel
      (``repro.kernels.lexbfs_fused``): the whole work unit is **one**
      ``pallas_call`` with the batch as the leading grid axis and the
      partition state resident in VMEM. Served through the compile
      cache's ``kind="fused"`` entries (:meth:`verdict_kind`), capped at
      ``configs.shapes.FUSED_MAX_NPAD`` by the VMEM budget.
    * ``split`` — LexBFS + the two-kernel PEO test
      (``repro.kernels.peo_check``): a host loop of two jit'd
      single-graph dispatches per slot. The fallback above the fused
      bucket cap, and the pre-PR 5 behavior.

    ``pipeline="auto"`` (default) selects ``fused`` off-interpret (a real
    accelerator) and ``split`` under interpret mode, where the fused
    kernel's sequential emulation is the slower of the two on CPU.
    ``interpret=None`` (default) resolves through
    :func:`repro.kernels.resolve_interpret` (interpreted only off-TPU) —
    the same build is correct on CPU CI and compiles via Mosaic on TPU. ``caps.batched`` stays False: it describes the *split* batch
    contract; fused units are natively batched and keyed separately.

    PR 6 adds two more compile-cache kinds (DESIGN.md §12):

    * ``fused_witness`` — the witness variant of the fused kernel emits
      per-vertex LN rows, parent pointers, and the latest violating
      triple alongside the verdict, so certified traffic is the same one
      ``pallas_call`` as verdict-only (host finalization assembles the
      WitnessBatch from the raw material). Capped at
      ``FUSED_WITNESS_MAX_NPAD`` by the LN output's VMEM footprint;
      bigger buckets fall back to the batch-major jnp executable.
    * ``fused_packed`` — tiny buckets (``n_pad <= FUSED_PACK_MAX_NPAD``)
      pack ``FUSED_PACK_FACTOR`` graphs per grid program, amortizing
      launch/pipeline overhead at high batch. Served whenever the fused
      pipeline would serve the bucket.
    """

    name = "pallas_peo"
    caps = BackendCaps(batched=False, device=True, certificate=True,
                       witness=True, fused=True)

    def __init__(self, interpret: Optional[bool] = None,
                 pipeline: str = "auto"):
        if pipeline not in ("auto", "fused", "split"):
            raise ValueError(f"unknown pallas_peo pipeline {pipeline!r}")
        from repro.kernels import resolve_interpret

        self._interpret = resolve_interpret(interpret)
        self._pipeline = pipeline

    @property
    def interpret(self) -> bool:
        """Whether this backend's kernels run in Pallas interpret mode."""
        return self._interpret

    def verdict_kind(self, n_pad: int) -> str:
        from repro.configs.shapes import FUSED_MAX_NPAD, FUSED_PACK_MAX_NPAD

        if n_pad > FUSED_MAX_NPAD:
            return "verdict"           # VMEM budget: split pipeline
        if self._pipeline == "auto":
            if self._interpret:
                return "verdict"
        elif self._pipeline != "fused":
            return "verdict"
        return ("fused_packed" if n_pad <= FUSED_PACK_MAX_NPAD
                else "fused")

    def witness_kind(self, n_pad: int) -> str:
        from repro.configs.shapes import FUSED_WITNESS_MAX_NPAD

        return ("fused_witness" if n_pad <= FUSED_WITNESS_MAX_NPAD
                else "witness")

    def compile_fused_batch(self, n_pad, batch):
        import jax.numpy as jnp

        from repro.kernels.lexbfs_fused.ops import lexbfs_peo_fused

        interpret = self._interpret

        def run(adjs: np.ndarray) -> np.ndarray:
            verdicts, _, _ = lexbfs_peo_fused(
                jnp.asarray(np.asarray(adjs, dtype=np.int8)),
                interpret=interpret)
            return np.asarray(verdicts)

        return run

    def compile_batch(self, n_pad, batch):
        import jax.numpy as jnp

        from repro.core.lexbfs import lexbfs
        from repro.kernels import dispatch_counter
        from repro.kernels.peo_check.ops import peo_check_pallas

        interpret = self._interpret

        def run(adjs: np.ndarray) -> np.ndarray:
            out = np.zeros(adjs.shape[0], dtype=bool)
            for i, adj in enumerate(adjs):
                a = jnp.asarray(adj)
                dispatch_counter.tick(2)   # LexBFS jit + PEO kernel launch
                out[i] = bool(
                    peo_check_pallas(a, lexbfs(a), interpret=interpret))
            return out

        return run

    def certificate(self, adj):
        import jax.numpy as jnp

        from repro.core.lexbfs import lexbfs
        from repro.kernels.peo_check.ops import peo_violations_count

        a = jnp.asarray(np.asarray(adj, dtype=bool))
        order = lexbfs(a)
        viol = int(peo_violations_count(a, order, interpret=self._interpret))
        return viol == 0, np.asarray(order), viol

    def compile_fused_packed_batch(self, n_pad, batch):
        import jax.numpy as jnp

        from repro.kernels.lexbfs_fused.ops import lexbfs_peo_fused_packed

        interpret = self._interpret

        def run(adjs: np.ndarray) -> np.ndarray:
            verdicts, _, _ = lexbfs_peo_fused_packed(
                jnp.asarray(np.asarray(adjs, dtype=np.int8)),
                interpret=interpret)
            return np.asarray(verdicts)

        return run

    def compile_fused_witness_batch(self, n_pad, batch):
        import jax.numpy as jnp

        from repro.kernels.lexbfs_fused.ops import lexbfs_peo_fused_witness
        from repro.witness import witness_batch_from_fused_raw

        interpret = self._interpret

        def run(adjs, n_nodes):
            adjs = np.asarray(adjs, dtype=bool)
            _, orders, viols, ln, parent, triple = lexbfs_peo_fused_witness(
                jnp.asarray(adjs.astype(np.int8)), interpret=interpret)
            return witness_batch_from_fused_raw(
                adjs, np.asarray(orders), np.asarray(viols),
                np.asarray(ln), np.asarray(parent), np.asarray(triple),
                n_nodes)

        return run

    def compile_witness_batch(self, n_pad, batch):
        # Fallback past FUSED_WITNESS_MAX_NPAD: the batch-major jnp
        # executable (same orders, one jit dispatch).
        from repro.witness import make_fused_witness_kernel

        return make_fused_witness_kernel()


class ShardedBackend(ChordalityBackend):
    """Batch tester jit'd over an explicit 1-D device mesh — the
    multi-device production path (``repro.engine.mesh``, DESIGN.md §16).

    A work unit's batch axis is split across the mesh; each shard owns
    whole graphs (adjacency tiles are replicated per shard, never split)
    and runs the unchanged ``jax_fast`` verdict pipeline, so verdicts
    are bit-identical to the single-device backends at every mesh size,
    with **one** jit dispatch per work unit driving every shard. On a
    single-device host the mesh degenerates to one device and the runner
    is the plain jit path plus a no-op pad/slice — the code path stays
    exercised everywhere.

    Honest caps: no ``certificate``, no ``witness``, no ``properties`` —
    those passes return per-graph host payloads (orders, clique trees)
    that batch-axis sharding cannot reassemble without a gather the
    engine doesn't need: certified/multi-property traffic on a sharded
    engine falls back per the session's resolve rules (witness →
    ``jax_faithful``, properties → ``jax_fast``), covered by the
    fallback regression test in ``tests/test_differential.py``.

    Compiled executables are pinned to the mesh slice:
    :meth:`cache_scope` returns the mesh signature (``"cpu:mesh8"``), so
    the compile cache never serves one mesh's program to another.
    """

    name = "sharded"
    caps = BackendCaps(batched=True, device=True, certificate=False)

    def __init__(self, mesh=None, n_devices: Optional[int] = None):
        if mesh is not None and n_devices is not None:
            raise ValueError("pass mesh or n_devices, not both")
        self._mesh = mesh
        self._n_devices = n_devices

    def _get_mesh(self):
        if self._mesh is None:
            from repro.engine.mesh import build_mesh

            self._mesh = build_mesh(self._n_devices)
        return self._mesh

    @property
    def device_count(self) -> int:
        from repro.engine.mesh import mesh_device_count

        return mesh_device_count(self._get_mesh())

    def cache_scope(self) -> str:
        from repro.engine.mesh import mesh_signature

        return mesh_signature(self._get_mesh())

    def compile_batch(self, n_pad, batch):
        from repro.engine.mesh import make_mesh_verdict_runner

        return make_mesh_verdict_runner(self._get_mesh())


class CSRBackend(ChordalityBackend):
    """Sparse CSR pipeline (repro.sparse): LexBFS + PEO over the edge
    stream — O(N + M) operands instead of the dense (N, N) matrix.

    Two pipelines, identical verdicts (orders are bit-identical to the
    dense implementations):

    * ``host`` — batch-vectorized numpy twins. The CPU fast path: the
      paper's Fig. 8 already measures sequential LexBFS winning on sparse
      graphs, and XLA:CPU scatter costs make the device formulation lose
      to it there (measured crossovers in DESIGN.md §8).
    * ``device`` — jit segment-op kernels (vmap over the packed batch),
      the accelerator path.

    ``pipeline="auto"`` (default) picks ``host`` on CPU, ``device``
    otherwise.

    Witness pass: orders come from the CSR LexBFS host twin
    (bit-identical to every other pipeline); the clique/coloring/cycle
    extraction walks the packed edge stream directly
    (``repro.witness.csr``) — the adjacency is **never** densified. The
    only square arrays built are certificate outputs (clique membership
    rows on chordal slots), which are Θ(n²) payload by contract.
    """

    name = "csr"
    caps = BackendCaps(batched=True, device=True, certificate=True,
                       sparse=True, witness=True)

    def __init__(self, pipeline: str = "auto"):
        if pipeline not in ("auto", "host", "device"):
            raise ValueError(f"unknown csr pipeline {pipeline!r}")
        self._pipeline = pipeline

    def _resolved(self) -> str:
        if self._pipeline != "auto":
            return self._pipeline
        import jax

        return "host" if jax.default_backend() == "cpu" else "device"

    def _pack(self, payload, n_pad):
        from repro.sparse.packing import PackedCSRBatch, pack_dense_batch

        if isinstance(payload, PackedCSRBatch):
            return payload
        return pack_dense_batch(np.asarray(payload, dtype=bool))

    def compile_batch(self, n_pad, batch):
        pipeline = self._resolved()

        def run(payload) -> np.ndarray:
            packed = self._pack(payload, n_pad)
            if pipeline == "host":
                from repro.sparse import (
                    lexbfs_csr_numpy_batch,
                    peo_violations_csr_numpy_batch,
                )

                orders = lexbfs_csr_numpy_batch(
                    packed.row_ptr, packed.col_idx, packed.deg_pad)
                viol = peo_violations_csr_numpy_batch(
                    packed.row_ptr, packed.col_idx, orders)
                return viol == 0
            from repro.sparse import csr_verdicts_batched

            rp, ci = packed.device_arrays()
            return np.asarray(csr_verdicts_batched(rp, ci, packed.deg_pad))

        return run

    def compile_witness_batch(self, n_pad, batch):
        from repro.sparse import lexbfs_csr_numpy_batch
        from repro.witness.csr import witness_batch_csr_numpy

        def run(payload, n_nodes):
            packed = self._pack(payload, n_pad)
            orders = lexbfs_csr_numpy_batch(
                packed.row_ptr, packed.col_idx, packed.deg_pad)
            return witness_batch_csr_numpy(
                packed.row_ptr, packed.col_idx,
                np.stack([np.asarray(o) for o in orders]), n_nodes)

        return run

    def certificate(self, adj):
        from repro.sparse import (
            CSRGraph,
            lexbfs_csr,
            lexbfs_csr_numpy,
            pack_csr_batch,
            peo_violations_csr,
            peo_violations_csr_numpy,
        )

        csr = CSRGraph.from_dense(np.asarray(adj, dtype=bool))
        packed = pack_csr_batch([csr], n_pad=csr.n_nodes)
        rp, ci = packed.row_ptr[0], packed.col_idx[0]
        if self._resolved() == "host":
            order = lexbfs_csr_numpy(rp, ci, packed.deg_pad)
            viol = peo_violations_csr_numpy(rp, ci, order)
        else:
            import jax.numpy as jnp

            rp, ci = jnp.asarray(rp), jnp.asarray(ci)
            order = lexbfs_csr(rp, ci, packed.deg_pad)
            viol = int(peo_violations_csr(rp, ci, order))
        return viol == 0, np.asarray(order), int(viol)


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, BackendSpec] = {}


def register_backend(spec: BackendSpec, overwrite: bool = False) -> None:
    if spec.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec


def backend_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def backend_spec(name: str) -> BackendSpec:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown backend {name!r}; registered: {backend_names()}")
    return _REGISTRY[name]


def make_backend(name: str, **opts) -> ChordalityBackend:
    """Instantiate a registered backend by name."""
    return backend_spec(name).factory(**opts)


def list_backends() -> Tuple[BackendSpec, ...]:
    """All registered :class:`BackendSpec`\\ s, sorted by name.

    Each spec carries the capability flags and a one-line doc; this is the
    discovery surface for callers choosing a backend (see
    ``examples/quickstart.py`` for a rendered table).
    """
    return tuple(_REGISTRY[name] for name in backend_names())


for _cls in (
    NumpyRefBackend,
    JaxFaithfulBackend,
    JaxFastBackend,
    PallasPeoBackend,
    ShardedBackend,
    CSRBackend,
):
    register_backend(BackendSpec(
        name=_cls.name, caps=_cls.caps, factory=_cls,
        doc=(_cls.__doc__ or "").strip().splitlines()[0]))
