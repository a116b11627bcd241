"""Mesh-sharded execution: explicit device meshes for work-unit dispatch.

The paper's thesis is one-thread-per-vertex parallelism on a single
device; the engine generalized that to batched buckets (one compiled
program per ``(n_pad, batch)`` shape). This module adds the third axis —
*many devices* — without touching the kernels: a planner work unit's
batch dimension is split across an explicit 1-D device mesh by a
batch-axis ``NamedSharding``, each shard holding whole graphs (adjacency tiles are
never split across devices), and the per-shard math is exactly the
``jax_fast`` verdict pipeline. Verdicts are therefore bit-identical to
the single-device backends at every mesh size, and one jit dispatch per
work unit drives every shard (DESIGN.md §16).

CPU CI exercises real multi-device partitioning by emulating host
devices: ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` **before
jax initializes** splits the host into 8 XLA CPU devices. Emulated
shards serialize on one core, so wall-clock there measures partitioning
overhead, not interconnect speedups — see TESTING.md for what the
emulated numbers do and do not mean.

Surface:

* :func:`build_mesh` — 1-D ``Mesh`` over the first *n* local devices.
* :func:`mesh_signature` — stable ``"platform:meshN"`` string naming the
  platform + device slice an executable is pinned to; the compile
  cache's scope component (``CompileCache`` keys are
  ``(backend, scope, kind, n_pad, batch)``).
* :func:`make_mesh_verdicts` — ``jit`` of the verdict pipeline with its
  input and output sharded along the mesh's batch axis.
* :func:`make_mesh_verdict_runner` — the host-facing numpy wrapper the
  ``sharded`` backend serves from its compile cache: pads the batch up
  to a mesh-size multiple (empty-graph slots), runs the one sharded
  dispatch, slices verdicts back.
"""
from __future__ import annotations

from typing import Callable, List, Optional

import numpy as np

#: Name of the batch axis every 1-D work-unit mesh shards over.
MESH_AXIS = "data"

__all__ = [
    "MESH_AXIS",
    "available_devices",
    "host_device_count",
    "build_mesh",
    "mesh_device_count",
    "mesh_signature",
    "pad_to_shards",
    "make_mesh_verdicts",
    "make_mesh_verdict_runner",
]


def available_devices(platform: Optional[str] = None) -> List:
    """Local jax devices, optionally filtered to one platform."""
    import jax

    return list(jax.devices(platform) if platform else jax.devices())


def host_device_count(platform: Optional[str] = None) -> int:
    """How many local devices a mesh could span (after any emulation)."""
    return len(available_devices(platform))


def build_mesh(n_devices: Optional[int] = None,
               axis_name: str = MESH_AXIS,
               platform: Optional[str] = None):
    """1-D device mesh over the first ``n_devices`` local devices.

    ``n_devices=None`` takes every visible device. The mesh is 1-D on
    purpose: work units shard only along the batch axis — adjacency
    tiles are replicated per shard, never split — so a second mesh axis
    would buy nothing the planner's bucketing doesn't already provide.
    """
    from jax.sharding import Mesh

    devs = available_devices(platform)
    if n_devices is None:
        n_devices = len(devs)
    if not 1 <= n_devices <= len(devs):
        raise ValueError(
            f"n_devices={n_devices} out of range: {len(devs)} local "
            f"device(s) visible (platform={platform or 'any'})")
    return Mesh(np.asarray(devs[:n_devices]), (axis_name,))


def mesh_device_count(mesh) -> int:
    """Total devices in the mesh (the router's ``device_count`` feature)."""
    return int(mesh.devices.size)


def mesh_signature(mesh) -> str:
    """Stable scope string for compile-cache keying: ``"cpu:0"`` for a
    single-device mesh (same scope as the plain jit backends on the
    default device), ``"cpu:mesh8"`` for a slice — executables compiled
    against one mesh must never be served to another."""
    devs = mesh.devices.ravel()
    platform = devs[0].platform
    if devs.size == 1:
        return f"{platform}:{devs[0].id}"
    return f"{platform}:mesh{devs.size}"


def pad_to_shards(batch: int, n_shards: int) -> int:
    """Smallest multiple of ``n_shards`` >= ``batch`` (the sharded axis
    must divide evenly by the mesh size)."""
    return -(-batch // n_shards) * n_shards


def make_mesh_verdicts(mesh, axis_name: Optional[str] = None) -> Callable:
    """``jit`` of the ``jax_fast`` verdict pipeline over a batch sharded
    along the mesh axis: the device-side sharded verdict program.

    The input ``(B, N, N)`` bool batch is placed split along axis 0
    across the mesh (``NamedSharding(mesh, P(axis))``); each device runs
    the unchanged ``jax_fast`` pipeline (``vmap(peo_check ∘
    lexbfs_fast)``) on its ``B/d`` graphs, and the ``(B,)`` verdict
    vector comes back sharded along the same axis. The per-graph math
    never mixes graphs, so the partitioned program holds no collective
    (asserted in ``tests/test_mesh.py``). ``B`` must be a multiple of the
    mesh size — callers pad via :func:`pad_to_shards` (the runner below
    does).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.lexbfs import lexbfs_fast
    from repro.core.peo import peo_check

    sharding = NamedSharding(mesh, P(axis_name or mesh.axis_names[0]))

    def verdicts(adjs):
        return jax.vmap(lambda a: peo_check(a, lexbfs_fast(a)))(adjs)

    return jax.jit(verdicts, in_shardings=(sharding,),
                   out_shardings=sharding)


def make_mesh_verdict_runner(mesh) -> Callable[[np.ndarray], np.ndarray]:
    """Host-facing executable for one ``(n_pad, batch)`` bucket: numpy
    in, numpy out, one dispatch per call regardless of mesh size.

    The planner's power-of-two batches know nothing about device counts,
    so the batch pads up to a mesh-size multiple here (all-zero
    adjacency slots — their verdicts are computed and discarded) and the
    verdict vector slices back to the caller's ``b``. The dispatch
    counter ticks once per call under the mesh's device scope, which is
    what ``BENCH_mesh.json`` reads to prove sharding never multiplies
    host launches.
    """
    from repro.kernels import dispatch_counter

    fn = make_mesh_verdicts(mesh)
    n_shards = mesh_device_count(mesh)
    scope = mesh_signature(mesh)

    def run(adjs: np.ndarray) -> np.ndarray:
        b = adjs.shape[0]
        b_pad = pad_to_shards(b, n_shards)
        if b_pad != b:
            adjs = np.concatenate([
                adjs,
                np.zeros((b_pad - b,) + adjs.shape[1:], dtype=adjs.dtype),
            ])
        dispatch_counter.tick(1, device=scope)
        return np.asarray(fn(adjs))[:b]

    return run
