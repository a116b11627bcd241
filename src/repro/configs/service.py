"""Serving-layer knobs — queue/batching/autotune config for the async engine.

The async service (``repro.engine.service.AsyncChordalityEngine``) trades
latency for batch occupancy with two knobs: how long the admission loop may
hold a partially-filled bucket (``max_wait_ms``) and how many requests fill
a bucket (``max_batch``).  ``max_queue`` bounds the total backlog a service
will accept — admission control, the knob that keeps queue delay finite
under overload.  Named presets capture the standard operating points.

:class:`AutotuneConfig` closes the control loops the static knobs leave
open (``repro.engine.autotune``): an AIMD controller adapts the wait
window per n_pad bucket from observed occupancy and queue-delay
percentiles, a refit policy re-fits the router's cost model continuously
from live unit latencies, and a deadline-pressure shedding policy drops
the lowest-priority queued work when its projected queue delay exceeds
its remaining deadline. ``ServiceConfig.autotune=None`` (the default)
keeps every knob static — exactly the pre-autotune service.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class AutotuneConfig:
    """Feedback-loop knobs for ``repro.engine.autotune.Autotuner``.

    Attributes:
      wait_min_ms / wait_max_ms: hard bounds on the per-bucket adapted
        wait window. The controller can never push ``max_wait_ms``
        outside ``[wait_min_ms, wait_max_ms]`` no matter what it
        observes.
      wait_increase_ms: additive increase applied when a bucket's units
        run under ``target_occupancy`` while queue delay is within
        budget (hold buckets longer -> fuller units).
      wait_decrease: multiplicative decrease factor applied when the
        bucket's observed p95 queue delay exceeds ``delay_budget_ms``
        (drain faster -> shed latency). Classic AIMD: slow to add
        latency, fast to shed it.
      target_occupancy: occupancy fraction (filled slots / max_batch)
        below which the controller considers units underfilled.
      delay_budget_ms: p95 queue-delay budget per bucket; the congestion
        signal for the multiplicative decrease.
      interval_units: controller decision cadence — one AIMD step per
        this many executed units per bucket (the observation window).
      refit_min_samples: new engine unit samples that trigger an online
        ``refit_router()`` (the sample-count trigger).
      refit_max_staleness_s: refit at least this often while any new
        samples exist (the staleness trigger). None disables.
      refit_backend_min_samples: forwarded to ``refit_router`` — a
        backend re-fits only with at least this many of its own samples
        (and 2+ distinct n values; see session docs).
      shed_headroom: shed a queued deadlined request when
        ``projected_queue_delay > shed_headroom * remaining_deadline``.
        1.0 sheds exactly the work projected to miss; < 1.0 sheds
        earlier (more headroom), > 1.0 gambles on the projection being
        pessimistic.
    """

    wait_min_ms: float = 0.0
    wait_max_ms: float = 32.0
    wait_increase_ms: float = 0.5
    wait_decrease: float = 0.5
    target_occupancy: float = 0.75
    delay_budget_ms: float = 50.0
    interval_units: int = 4
    refit_min_samples: int = 64
    refit_max_staleness_s: Optional[float] = 30.0
    refit_backend_min_samples: int = 8
    shed_headroom: float = 1.0

    def __post_init__(self):
        if not (0.0 <= self.wait_min_ms <= self.wait_max_ms):
            raise ValueError(
                f"need 0 <= wait_min_ms <= wait_max_ms, got "
                f"[{self.wait_min_ms}, {self.wait_max_ms}]")
        if self.wait_increase_ms < 0:
            raise ValueError(
                f"wait_increase_ms must be >= 0, got {self.wait_increase_ms}")
        if not (0.0 < self.wait_decrease < 1.0):
            raise ValueError(
                f"wait_decrease must be in (0, 1), got {self.wait_decrease}")
        if not (0.0 < self.target_occupancy <= 1.0):
            raise ValueError(
                f"target_occupancy must be in (0, 1], got "
                f"{self.target_occupancy}")
        if self.delay_budget_ms <= 0:
            raise ValueError(
                f"delay_budget_ms must be positive, got "
                f"{self.delay_budget_ms}")
        if self.interval_units < 1:
            raise ValueError(
                f"interval_units must be >= 1, got {self.interval_units}")
        if self.refit_min_samples < 1:
            raise ValueError(
                f"refit_min_samples must be >= 1, got "
                f"{self.refit_min_samples}")
        if self.refit_max_staleness_s is not None \
                and self.refit_max_staleness_s <= 0:
            raise ValueError(
                f"refit_max_staleness_s must be positive or None, got "
                f"{self.refit_max_staleness_s}")
        if self.refit_backend_min_samples < 1:
            raise ValueError(
                f"refit_backend_min_samples must be >= 1, got "
                f"{self.refit_backend_min_samples}")
        if self.shed_headroom <= 0:
            raise ValueError(
                f"shed_headroom must be positive, got {self.shed_headroom}")


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Queue + micro-batching knobs for ``AsyncChordalityEngine``.

    Attributes:
      max_queue: bound on the backlog (submitted but unresolved requests);
        ``submit`` rejects (or blocks, with a timeout) beyond it.
      max_batch: work-unit batch cap — a bucket drains as soon as this many
        requests of one n_pad size are pending.
      max_wait_ms: micro-batch window — a non-empty bucket drains once its
        oldest request has waited this long, full or not. 0 disables
        batching-by-time (every admission pass drains what it sees).
        With ``autotune`` set this is only the *initial* window; the
        controller then adapts it per bucket within the autotune bounds.
      backend: engine backend name; ``"auto"`` routes per drained unit.
      deadline_ms: default per-request deadline. A request still waiting
        in the admission queue this long after submission is dropped —
        its future is cancelled and ``ServiceStats.n_expired`` counts it.
        None (default) disables expiry; ``submit(deadline_ms=...)``
        overrides per request. Expiry applies only while queued: a
        request already drained into a work unit always executes.
      priority_weights: drain-share weights for the priority classes,
        indexed by priority (class ``p`` gets weight
        ``priority_weights[p]``). Buckets drain in weighted-fair order:
        a class with weight 4 gets ~4x the unit slots of a class with
        weight 1 under contention, and no non-empty class starves. The
        tuple's length defines how many classes exist.
      default_priority: class assigned when ``submit`` passes none.
      drain_timeout_s: default wait bound for ``flush``/``shutdown``.
      stats_window: bound on the ``ServiceStats`` sample buffers (queue
        delays, exec latencies). Beyond it the oldest samples roll off,
        so a long-lived service keeps recent-window percentiles instead
        of a monotonically growing list.
      n_lanes: executor lanes (PR 10). 1 (the default) is the classic
        single-executor service. With ``n_lanes > 1`` the service runs
        one executor thread per lane — one per device or mesh slice —
        with least-loaded dispatch and weighted work-stealing, so a
        slow lane never stalls the admission loop (DESIGN.md §16).
      lane_weights: optional per-lane steal weights, length ``n_lanes``.
        A lane's share of stolen work scales with its weight — weight 2
        steals twice as eagerly as weight 1 (keeps a fast device fed
        from a slow device's backlog). None = all lanes weight 1.0.
      autotune: feedback-loop knobs (:class:`AutotuneConfig`); None (the
        default) disables every control loop — static knobs only.
    """

    max_queue: int = 1024
    max_batch: int = 32
    max_wait_ms: float = 2.0
    backend: str = "auto"
    deadline_ms: Optional[float] = None
    priority_weights: Tuple[float, ...] = (1.0, 2.0, 4.0)
    default_priority: int = 1
    drain_timeout_s: float = 60.0
    stats_window: int = 4096
    n_lanes: int = 1
    lane_weights: Optional[Tuple[float, ...]] = None
    autotune: Optional[AutotuneConfig] = None

    def __post_init__(self):
        if self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_wait_ms < 0:
            raise ValueError(
                f"max_wait_ms must be >= 0, got {self.max_wait_ms}")
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be positive or None, "
                f"got {self.deadline_ms}")
        if not self.priority_weights or \
                any(w <= 0 for w in self.priority_weights):
            raise ValueError(
                f"priority_weights must be a non-empty tuple of positive "
                f"weights, got {self.priority_weights}")
        if not (0 <= self.default_priority < len(self.priority_weights)):
            raise ValueError(
                f"default_priority {self.default_priority} outside classes "
                f"0..{len(self.priority_weights) - 1}")
        if self.stats_window < 1:
            raise ValueError(
                f"stats_window must be >= 1, got {self.stats_window}")
        if self.n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {self.n_lanes}")
        if self.lane_weights is not None:
            if len(self.lane_weights) != self.n_lanes:
                raise ValueError(
                    f"lane_weights length {len(self.lane_weights)} != "
                    f"n_lanes {self.n_lanes}")
            if any(w <= 0 for w in self.lane_weights):
                raise ValueError(
                    f"lane_weights must all be positive, got "
                    f"{self.lane_weights}")

    @property
    def n_priorities(self) -> int:
        return len(self.priority_weights)


#: Standard operating points. ``throughput`` holds buckets longer for
#: fuller work units; ``latency`` drains almost immediately; ``smoke`` is
#: the tiny CI/benchmark-smoke shape; ``autotuned`` starts from the
#: default and lets the control loops move the knobs.
SERVICE_CONFIGS: Dict[str, ServiceConfig] = {
    "default": ServiceConfig(),
    "throughput": ServiceConfig(max_batch=64, max_wait_ms=8.0),
    "latency": ServiceConfig(max_batch=8, max_wait_ms=0.5),
    "smoke": ServiceConfig(max_queue=64, max_batch=8, max_wait_ms=1.0),
    "autotuned": ServiceConfig(autotune=AutotuneConfig()),
}


def service_config(name: str) -> ServiceConfig:
    if name not in SERVICE_CONFIGS:
        raise KeyError(
            f"unknown service config {name!r}; "
            f"available: {sorted(SERVICE_CONFIGS)}")
    return SERVICE_CONFIGS[name]
