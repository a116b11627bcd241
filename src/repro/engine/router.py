"""Adaptive backend selection — a measured cost model over (n, density, B).

The registry (``engine.backends``) says what each backend *can* do; this
module decides what it *should* do for a given work unit. The model is a
per-backend linear form in the features that dominate measured runtime:

    us_per_graph = dispatch_us/B + per_graph_us + sweep_us·n/B
                   + (n_us·n + n2_us·n² + m_us·m)/D + dev_us·(D-1)

with ``m = density·n²`` (directed edge entries at the padded size) and
``D`` the ``device_count`` feature — how many devices the unit's batch
shards across (PR 10). The terms mirror the implementations: every
LexBFS runs n sequential sweeps, whose fixed per-sweep overhead (XLA
thunk dispatch for the jit backends, numpy-call overhead for the host
ones) is shared across a unit's batch (``sweep_us·n/B``); per-graph data
cost is O(n) per sweep for the dense rank vector (``n2_us·n²``) and O(m)
one-shot for the CSR PEO (``m_us·m``). Device parallelism divides the
per-graph compute terms (each shard runs B/D graphs concurrently) and
adds a per-device coordination term; single-device backends pin
``max_devices=1`` so ``D`` degenerates to 1 and the PR 8 form is
recovered exactly. ``D`` is clamped to the router's *fitted* device
support (``fit_device_range``) — a model fitted from single-device live
logs must never extrapolate multi-device costs (clamp_features).

``DEFAULT_COST_MODEL`` and its witness / recognition siblings are
constants least-squares fitted once from offline samples on a 2-core CPU
reference host; the sampler that produced them is no longer in the repo,
so the tables are not regenerated offline. They are re-fitted online:
``ChordalityEngine.refit_router`` runs :func:`fit_cost_model` over the
engine's live unit samples, and the service's
:class:`~repro.engine.autotune.RefitPolicy` decides when. Routing only
needs the *ordering* of backends per regime, which is robust to modest
coefficient error:

* tiny graphs → ``numpy_ref`` (no dispatch, no compile);
* sparse, large n → ``csr`` (O(N+M) operands, batch-amortized sweeps);
* dense bulk → ``jax_fast`` (one fused device program per unit).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.engine.backends import backend_spec
from repro.engine.planner import Plan, WorkUnit


@dataclasses.dataclass(frozen=True)
class BackendCost:
    """Fitted per-backend coefficients (all µs; see module docstring)."""

    dispatch_us: float = 0.0     # per work unit (jit dispatch, loop setup)
    per_graph_us: float = 0.0    # fixed per graph
    sweep_us: float = 0.0        # × n, shared across the unit's batch
    n_us: float = 0.0            # × n, per graph
    n2_us: float = 0.0           # × n², per graph
    m_us: float = 0.0            # × m (directed nnz), per graph
    dev_us: float = 0.0          # × (D-1): per-device coordination cost
    max_devices: int = 1         # device span this entry was fitted over

    def us_per_graph(self, n: int, density: float, batch: int,
                     device_count: int = 1) -> float:
        b = max(batch, 1)
        # Per-entry clamp: a backend fitted single-device must not have
        # its compute terms divided by a mesh width it never ran at.
        d = max(1, min(int(device_count), self.max_devices))
        m = density * n * n
        return (self.dispatch_us / b + self.per_graph_us
                + self.sweep_us * n / b
                + (self.n_us * n + self.n2_us * n * n + self.m_us * m) / d
                + self.dev_us * (d - 1))


CostModel = Mapping[str, BackendCost]

# CPU-fitted constants (2-core reference host, warm engines, best-of-5
# sub-ms cells); fitted in the *same session* as
# DEFAULT_WITNESS_COST_MODEL, so cross-mode comparisons
# (estimate_us_per_graph mode="witness" vs verdict) are coherent. The
# offline sampler that produced them is gone; re-fit online through
# ChordalityEngine.refit_router (fit_cost_model over live unit samples)
# or the service's RefitPolicy. Measured crossovers this model encodes:
# jax_fast wins tiny through dense-bulk traffic (the PR 6 wrapper fix
# dropped its dispatch floor below numpy_ref's per-graph python cost, so
# numpy_ref no longer wins single-shot tiny requests — it remains the
# zero-compile fallback and the differential oracle); csr overtakes
# jax_fast on sparse streams around n ~ 512 at density c/n (earlier for
# lower density / bigger batches) — DESIGN.md §8.
DEFAULT_COST_MODEL: Dict[str, BackendCost] = {
    "numpy_ref": BackendCost(
        dispatch_us=0.0, per_graph_us=122.9, sweep_us=0.0,
        n_us=8.121, n2_us=0.03439, m_us=0.0),
    "jax_fast": BackendCost(
        dispatch_us=92.57, per_graph_us=0.9986, sweep_us=0.0,
        n_us=0.4237, n2_us=0.009035, m_us=0.0),
    "csr": BackendCost(
        dispatch_us=87.54, per_graph_us=36.89, sweep_us=9.128,
        n_us=0.6673, n2_us=0.002517, m_us=0.1317),
    # The fused single-dispatch Pallas pipeline (pallas_peo,
    # pipeline="fused"): one kernel launch per unit (dispatch term), then a
    # per-graph sequential n-loop whose per-step row reads and periodic
    # comparator compactions the n/n² terms absorb. Fitted on the CI
    # reference host in *interpret* mode — the only Pallas substrate a CPU
    # box has — where the emulation compiles to roughly the jnp path's
    # speed; it stays out of CPU auto-routing because it is not in
    # DEFAULT_CANDIDATES. A TPU deployment re-fits online via
    # ChordalityEngine.refit_router and opts it into the candidate list.
    "pallas_peo": BackendCost(
        dispatch_us=847.6, per_graph_us=0.0, sweep_us=0.0,
        n_us=0.3358, n2_us=0.009781, m_us=0.0),
}

# Witness-mode coefficients: what a *certified* graph costs end to end —
# LexBFS + PEO + certificate extraction (cliques, clique tree, coloring /
# chordless cycle). Same linear form, separate fit: extraction shifts
# every backend's curve differently (numpy_ref pays per-graph python
# clique loops, jax_fast pays one heavier fused batch-major program, csr
# pays segment-reduction passes over edge windows), so routing certified
# traffic off the verdict coefficients would misplace every crossover.
# Fitted on the CPU reference host in the same session as
# DEFAULT_COST_MODEL, over an offline sample grid measured with
# witness=True; re-fit online via fit_cost_model over live
# (backend, n, density, batch, us) rows.
DEFAULT_WITNESS_COST_MODEL: Dict[str, BackendCost] = {
    "numpy_ref": BackendCost(
        dispatch_us=0.0, per_graph_us=207.0, sweep_us=0.0,
        n_us=7.322, n2_us=0.04848, m_us=0.0),
    "jax_fast": BackendCost(
        dispatch_us=121.3, per_graph_us=23.93, sweep_us=0.0,
        n_us=0.0, n2_us=0.01644, m_us=0.0),
    "csr": BackendCost(
        dispatch_us=59.83, per_graph_us=117.8, sweep_us=8.96,
        n_us=0.6814, n2_us=0.002221, m_us=0.1432),
    # One pallas_call still (fused_witness kind): verdict dispatch plus the
    # LN-row stores in-loop, then host finalization per certified graph.
    "pallas_peo": BackendCost(
        dispatch_us=292.0, per_graph_us=53.56, sweep_us=0.0,
        n_us=2.447, n2_us=0.01301, m_us=0.0),
}

# Recognition-mode coefficients: what a multi-property request costs per
# graph through the shared-sweep executables (repro.recognition). Same
# linear form, separate fit: the plan runs up to 5 sweeps where the
# verdict runs 1, the LexBFS+ selection does two reductions per step, and
# numpy_ref pays python-loop sweeps per graph while jax_fast amortizes one
# bigger jit program per unit — so recognition crossovers sit elsewhere
# than verdict ones. Fitted on the CPU reference host via
# fit_cost_model over an offline sample grid (n 8–512, B 1–16)
# measured with properties=<full 5-property registry> — the conservative
# plan: pricing lighter property sets with it only overestimates both
# candidates the same way, preserving ordering. Only the
# properties-capable backends appear; choose(mode="recognition") requires
# that capability, so others never price here.
DEFAULT_RECOGNITION_COST_MODEL: Dict[str, BackendCost] = {
    "numpy_ref": BackendCost(
        dispatch_us=0.0, per_graph_us=458.5, sweep_us=0.0,
        n_us=42.05, n2_us=0.1818, m_us=0.0),
    "jax_fast": BackendCost(
        dispatch_us=155.6, per_graph_us=62.32, sweep_us=0.0,
        n_us=0.0, n2_us=0.05513, m_us=0.0),
}

#: Backends "auto" chooses among. All three carry the certificate cap;
#: specialist backends (pallas_peo, sharded) stay opt-in by name.
DEFAULT_CANDIDATES: Tuple[str, ...] = ("numpy_ref", "jax_fast", "csr")

# Per-platform coefficient overlays (PR 10). The defaults above are the
# CPU CI reference fit; a platform overlay replaces/extends entries whose
# measured coefficients differ structurally — today that is the sharded
# mesh backend, whose device_count terms only exist where a mesh was
# actually measured. The CPU entry was fitted from an 8-device
# *emulated* scaling run (serialized shards — see TESTING.md), so on CPU
# it prices sharding as batch-partitioning overhead, which is honest
# there; a TPU/GPU deployment re-fits via refit_router() on real
# hardware and gets real dev_us coefficients.
# Opt in via Router(platform="cpu", candidates=(*DEFAULT_CANDIDATES,
# "sharded"), fit_device_range=(1, 8)).
PLATFORM_COST_MODELS: Dict[str, Dict[str, BackendCost]] = {
    "cpu": {
        # Fitted via fit_cost_model over live unit samples from a
        # mesh calibration grid (n 64/128/256, B 32, D 1/2/4/8
        # emulated devices, 72 samples): a fixed per-graph cost plus an
        # n²/D compute term and a per-device partition/reassembly cost.
        "sharded": BackendCost(
            dispatch_us=2.9, per_graph_us=93.6, sweep_us=0.0,
            n_us=0.0, n2_us=0.02192, m_us=0.0,
            dev_us=4.81, max_devices=8),
    },
    "tpu": {},
    "gpu": {},
}


def platform_cost_model(platform: Optional[str] = None
                        ) -> Dict[str, BackendCost]:
    """DEFAULT_COST_MODEL overlaid with the platform's fitted entries."""
    model = dict(DEFAULT_COST_MODEL)
    if platform:
        model.update(PLATFORM_COST_MODELS.get(platform, {}))
    return model

#: n-range DEFAULT_COST_MODEL was fitted over (bench_router_samples sweeps
#: the engine's n_pad buckets, smallest 16, largest measured 8192). Outside
#: it, the linear forms have no data behind them: below the floor the csr
#: sweep term shrinks toward zero and beats numpy_ref's fixed per-graph
#: cost on paper while losing in practice, so routing must clamp rather
#: than extrapolate.
DEFAULT_FIT_N_RANGE: Tuple[int, int] = (16, 8192)

#: Device span the default model was fitted over: single device. A
#: router only prices multi-device execution after seeing multi-device
#: measurements (a platform overlay entry, or refit_router over samples
#: with device_count variation widening the range).
DEFAULT_FIT_DEVICE_RANGE: Tuple[int, int] = (1, 1)


class Router:
    """Cost-model backend selection for plans and single requests."""

    def __init__(
        self,
        cost_model: Optional[CostModel] = None,
        candidates: Sequence[str] = DEFAULT_CANDIDATES,
        fit_n_range: Tuple[int, int] = DEFAULT_FIT_N_RANGE,
        *,
        witness_cost_model: Optional[CostModel] = None,
        recognition_cost_model: Optional[CostModel] = None,
        platform: Optional[str] = None,
        fit_device_range: Tuple[int, int] = DEFAULT_FIT_DEVICE_RANGE,
    ):
        self.cost_model: Dict[str, BackendCost] = dict(
            platform_cost_model(platform) if cost_model is None
            else cost_model)
        # Witness-mode coefficients; a backend missing here falls back to
        # its verdict entry (custom verdict-only models keep working).
        self.witness_cost_model: Dict[str, BackendCost] = dict(
            DEFAULT_WITNESS_COST_MODEL if witness_cost_model is None
            else witness_cost_model)
        # Recognition-mode coefficients, same fallback discipline.
        self.recognition_cost_model: Dict[str, BackendCost] = dict(
            DEFAULT_RECOGNITION_COST_MODEL if recognition_cost_model is None
            else recognition_cost_model)
        self.candidates = tuple(candidates)
        unknown = [c for c in self.candidates if c not in self.cost_model]
        if unknown:
            raise ValueError(f"candidates without cost entries: {unknown}")
        lo, hi = fit_n_range
        if not (0 < lo <= hi):
            raise ValueError(f"invalid fit_n_range {fit_n_range}")
        self.fit_n_range = (int(lo), int(hi))
        dlo, dhi = fit_device_range
        if not (0 < dlo <= dhi):
            raise ValueError(f"invalid fit_device_range {fit_device_range}")
        self.fit_device_range = (int(dlo), int(dhi))

    def clamp_features(
        self, n: int, density: float, batch: int,
        device_count: Optional[int] = None,
    ):
        """Pull a feature point back inside the model's measured support.

        Degenerate requests (n below every bucket, zero-edge graphs whose
        density underflows, batch=1 probes) otherwise evaluate the linear
        fit where it was never sampled, and the cheapest extrapolation wins
        for the wrong reasons. Clamping keeps the *ordering* question
        inside the regime the coefficients were measured on.

        ``device_count`` gets the same treatment against
        ``fit_device_range``: a model refitted from single-device live
        logs has ``(1, 1)`` support, so pricing an 8-wide mesh with it
        must collapse to the single-device estimate rather than divide
        compute terms by a width nobody measured. Returns a 3-tuple when
        ``device_count`` is omitted (the pre-PR 10 surface), a 4-tuple
        when it is passed.
        """
        lo, hi = self.fit_n_range
        n = min(max(int(n), lo), hi)
        if not math.isfinite(density):
            density = 0.0
        density = min(max(float(density), 0.0), 1.0)
        batch = max(int(batch), 1)
        if device_count is None:
            return n, density, batch
        dlo, dhi = self.fit_device_range
        device_count = min(max(int(device_count), dlo), dhi)
        return n, density, batch, device_count

    def estimate_us_per_graph(
        self, name: str, n: int, density: float, batch: int,
        *, mode: str = "verdict", device_count: int = 1,
    ) -> float:
        if mode == "witness":
            cost = self.witness_cost_model.get(name)
            if cost is not None:
                return cost.us_per_graph(n, density, batch, device_count)
        elif mode == "recognition":
            cost = self.recognition_cost_model.get(name)
            if cost is not None:
                return cost.us_per_graph(n, density, batch, device_count)
        elif mode != "verdict":
            raise ValueError(f"unknown routing mode {mode!r}")
        return self.cost_model[name].us_per_graph(
            n, density, batch, device_count)

    def choose(
        self,
        n: int,
        density: float,
        batch: int,
        require: Iterable[str] = (),
        *,
        mode: str = "verdict",
        device_count: int = 1,
    ) -> str:
        """Cheapest candidate whose capabilities cover ``require``.

        ``require`` names :class:`~repro.engine.backends.BackendCaps`
        fields (e.g. ``("certificate",)``); a backend missing any required
        capability is excluded no matter how cheap the model says it is.
        ``mode="witness"`` prices candidates with the witness-mode
        coefficients (and implies the witness capability requirement) —
        certified traffic has different crossovers than verdict-only.
        ``mode="recognition"`` does the same with the recognition-mode
        coefficients and the ``properties`` capability. Features are
        clamped to the fitted support first (:meth:`clamp_features`), so
        degenerate inputs route like the nearest measured regime instead
        of extrapolating. ``device_count`` is the mesh width available to
        device-parallel candidates — clamped to ``fit_device_range``
        here, and again per cost entry to its own ``max_devices`` (a
        single-device backend never sees its compute terms divided).
        """
        n, density, batch, device_count = self.clamp_features(
            n, density, batch, device_count)
        req = tuple(require)
        if mode == "witness" and "witness" not in req:
            req = req + ("witness",)
        if mode == "recognition" and "properties" not in req:
            req = req + ("properties",)
        best_name, best_cost = None, math.inf
        for name in self.candidates:
            caps = backend_spec(name).caps
            if any(not getattr(caps, r) for r in req):
                continue
            cost = self.estimate_us_per_graph(
                name, n, density, batch, mode=mode,
                device_count=device_count)
            if cost < best_cost:
                best_name, best_cost = name, cost
        if best_name is None:
            raise ValueError(
                f"no candidate in {self.candidates} satisfies {req}")
        return best_name

    def annotate(
        self, plan: Plan, graphs, *, witness: bool = False,
        mode: Optional[str] = None, device_count: int = 1,
    ) -> Plan:
        """Return a plan whose units carry per-unit backend choices.

        The density feature is the unit mean of ``n_edges / n_pad²`` —
        what the padded work unit will actually look like on device.
        ``witness=True`` routes with the witness-mode coefficients (the
        plan's units will run certified executables, whose cost curves
        cross over elsewhere); ``mode`` overrides outright (the session's
        recognition path passes ``mode="recognition"``).
        ``device_count`` is the mesh width available to device-parallel
        candidates (see :meth:`choose`).
        """
        if mode is None:
            mode = "witness" if witness else "verdict"
        units: List[WorkUnit] = []
        for u in plan.units:
            m_mean = (
                float(np.mean([graphs[i].n_edges for i in u.indices]))
                if u.indices else 0.0)
            density = m_mean / float(u.n_pad * u.n_pad)
            name = self.choose(u.n_pad, density, u.batch, mode=mode,
                               device_count=device_count)
            units.append(dataclasses.replace(u, backend=name))
        return Plan(units=units, n_requests=plan.n_requests)


#: Which cost terms each backend's fit may use. A host loop has no unit
#: dispatch or batch-shared sweeps; the dense backends have no m term
#: (their cost is density-independent). Constraining the fit keeps
#: collinear features from inventing phantom terms that wreck routing at
#: the regime boundaries.
FIT_FEATURE_MASKS: Dict[str, Tuple[int, ...]] = {
    # indices into (dispatch, per_graph, sweep, n, n2, m, dev)
    "numpy_ref": (1, 3, 4),
    "jax_fast": (0, 1, 2, 3, 4),
    "csr": (0, 1, 2, 3, 4, 5),
    # One dispatch per unit; the in-kernel n-loop + comparator are pure
    # per-graph n/n² costs (density-independent: dense row reads).
    "pallas_peo": (0, 1, 3, 4),
    # jax_fast-shaped compute per shard, plus the device_count terms:
    # the per-graph n/n² features already carry the 1/D division, and
    # feature 6 (= D-1) absorbs partition/reassembly coordination.
    "sharded": (0, 1, 3, 4, 6),
}


def fit_cost_model(
    samples: Sequence[Tuple],
    feature_masks: Optional[Mapping[str, Tuple[int, ...]]] = None,
) -> Dict[str, BackendCost]:
    """Least-squares fit of per-backend coefficients from measurements.

    ``samples`` rows are ``(backend, n, density, batch, us_per_graph)``
    or, since PR 10, ``(backend, n, density, batch, device_count,
    us_per_graph)`` — the format the engine's live unit-sample log
    emits. 5-field rows fit at ``device_count=1``.
    The fit is *relative* (rows weighted by 1/µs — routing needs tiny-n
    rows as accurate as big-n rows), masked per backend
    (:data:`FIT_FEATURE_MASKS`), and clipped at 0 (a negative term has no
    physical reading and would let the router extrapolate nonsense).
    Each fitted entry's ``max_devices`` is the largest device_count that
    backend was actually measured at, so estimates never divide compute
    terms past the fitted span.
    """
    masks = dict(FIT_FEATURE_MASKS)
    if feature_masks:
        masks.update(feature_masks)
    by_backend: Dict[str, List[Tuple[int, float, int, int, float]]] = {}
    for row in samples:
        if len(row) == 5:
            name, n, density, batch, us = row
            d = 1
        else:
            name, n, density, batch, d, us = row
        by_backend.setdefault(name, []).append(
            (n, density, batch, max(int(d), 1), us))
    out: Dict[str, BackendCost] = {}
    for name, rows in by_backend.items():
        feats = np.array([
            [1.0 / b, 1.0, n * 1.0 / b, n * 1.0 / d, n * n * 1.0 / d,
             density * n * n / d, d - 1.0]
            for n, density, b, d, _ in rows])
        mask = masks.get(name, (0, 1, 2, 3, 4, 5))
        target = np.array([us for *_, us in rows])
        w = (1.0 / target)[:, None]
        coef, *_ = np.linalg.lstsq(
            feats[:, mask] * w, target * w[:, 0], rcond=None)
        full = np.zeros(7)
        full[list(mask)] = np.clip(coef, 0.0, None)
        out[name] = BackendCost(
            *[float(c) for c in full],
            max_devices=max(d for *_, d, _us in rows))
    return out
