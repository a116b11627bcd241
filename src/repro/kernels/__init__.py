"""The Pallas kernels (``lexbfs_fused``, ``peo_check``) and their shared
utilities.

:data:`dispatch_counter` counts host-level compiled-program launches —
each tick is one host->device dispatch (a jit call or a ``pallas_call``
invocation from Python). ``tests/test_exact_counts.py`` reads deltas
off it to hold the *measured* dispatches per work unit.

Since PR 9 the counter is an alias over the obs metrics registry
(``repro_dispatches_total`` in :data:`repro.obs.registry`) and the
increment is lock-protected — it is ticked from the async service's
background executor threads, where GIL-only atomicity is not a
guarantee for ``+=``. The legacy surface (``.count`` attribute,
``tick``/``delta``, tests assigning ``count`` directly) is preserved.

Since PR 10 the metric family carries a ``device`` label so mesh-sharded
dispatches are attributable to the device slice that ran them
(``"cpu:mesh8"`` — see ``repro.engine.mesh.mesh_signature``). Legacy
tick sites stay label-free at the call site and land in the ``"host"``
series; ``.count``/``.delta`` sum across every device series, so all
pre-existing dispatch accounting is unchanged.
"""
from __future__ import annotations

from typing import Optional

from repro.obs.metrics import Counter
from repro.obs.metrics import registry as _registry


class DispatchCounter:
    """Counts host-level device-program launches (registry-backed,
    thread-safe; see module docstring)."""

    def __init__(self, metric: Counter | None = None) -> None:
        self._metric = metric if metric is not None else _registry.counter(
            "repro_dispatches_total",
            "host-level compiled-program launches (jit / pallas_call)",
            labels=("device",))

    def tick(self, k: int = 1, device: str = "host") -> None:
        self._metric.inc(k, device=device)

    @property
    def count(self) -> int:
        # Sum across device series: dispatch accounting (bench deltas,
        # fused-unit tests) is device-agnostic by contract.
        return int(self._metric.total())

    @count.setter
    def count(self, value: int) -> None:
        # Legacy test hook: suites snapshot-and-reset the raw attribute.
        # Zero every device series first so the total equals ``value``.
        for key in list(self._metric.series()):
            self._metric.set_value(0, **dict(zip(self._metric.labels, key)))
        self._metric.set_value(int(value), device="host")

    def delta(self, since: int) -> int:
        return self.count - since


#: Process-global counter the kernel wrappers and backends tick.
dispatch_counter = DispatchCounter()


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode for a kernel call: an explicit value wins;
    ``None`` interprets only when the default backend is not a TPU, so a
    kernel never runs interpreted on a TPU unless the caller asks."""
    if interpret is not None:
        return bool(interpret)
    import jax

    return jax.default_backend() != "tpu"


__all__ = ["DispatchCounter", "dispatch_counter", "resolve_interpret"]
