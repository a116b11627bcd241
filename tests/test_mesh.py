"""Mesh-sharded execution (repro.engine.mesh + the sharded backend).

Two layers of coverage (DESIGN.md §16, TESTING.md):

* **in-process** — pure helpers (pad_to_shards, mesh validation) and the
  single-device degeneration: with one visible device the sharded
  backend must behave exactly like the plain jit path — same verdicts,
  same compile-cache scope (``"cpu:0"``), one dispatch per unit.
* **subprocess** — real multi-device partitioning on emulated host
  devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must
  be set before jax initializes, so it cannot run in the parent pytest
  process). One child sweeps mesh sizes 1/2/4/8 and asserts bit-identity
  of verdicts vs the numpy_ref oracle, uneven unit counts (batch not a
  multiple of the mesh size), the witness fallback, and the
  one-dispatch-per-unit invariant at every mesh size. Two more children
  (4 and 8 devices) run the fused per-shard program (the Pallas kernel
  under ``shard_map``, interpreted) against numpy_ref on the five graph
  classes, and check a traced fused unit's stage spans.

The emulated shards serialize on one core — these tests prove
*partitioning correctness*, never speedups (see TESTING.md).
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import generators as G
from repro.engine.backends import make_backend
from repro.engine.mesh import (
    build_mesh,
    host_device_count,
    make_mesh_verdict_runner,
    mesh_device_count,
    mesh_signature,
    pad_to_shards,
)
from repro.engine.session import ChordalityEngine
from repro.kernels import dispatch_counter

ROOT = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# In-process: helpers + single-device degeneration
# ---------------------------------------------------------------------------
def test_pad_to_shards():
    assert pad_to_shards(1, 1) == 1
    assert pad_to_shards(8, 4) == 8     # exact multiple: no padding
    assert pad_to_shards(5, 4) == 8
    assert pad_to_shards(1, 8) == 8
    assert pad_to_shards(17, 8) == 24


def test_build_mesh_validates_device_range():
    with pytest.raises(ValueError, match="out of range"):
        build_mesh(0)
    with pytest.raises(ValueError, match="out of range"):
        build_mesh(host_device_count() + 1)


def test_single_device_mesh_signature_matches_jit_scope():
    """A 1-device mesh compiles under the same scope as the plain jit
    backends on the default device — they may share cache entries."""
    mesh = build_mesh(1)
    assert mesh_device_count(mesh) == 1
    assert mesh_signature(mesh) == make_backend("jax_fast").cache_scope()


def test_sharded_backend_rejects_mesh_and_n_devices():
    with pytest.raises(ValueError):
        make_backend("sharded", mesh=build_mesh(1), n_devices=1)


def test_single_device_sharded_degenerates_to_existing_path():
    """With one visible device the sharded backend is the jax_fast
    pipeline behind a size-1 shard_map: verdicts bit-identical to the
    oracle, scope ``"cpu:0"``, one dispatch per unit."""
    graphs = [G.gnp(20, 0.3, seed=s) for s in range(6)]
    graphs += [G.cycle(9), G.clique(5), G.path(7)]
    oracle = ChordalityEngine(backend="numpy_ref", max_batch=8)
    want = oracle.run(graphs).verdicts

    eng = ChordalityEngine(backend="sharded", max_batch=8)
    assert eng.backend.device_count == 1
    assert eng.backend.cache_scope() == \
        make_backend("jax_fast").cache_scope()
    c0 = dispatch_counter.count
    res = eng.run(graphs)
    assert dispatch_counter.count - c0 == len(res.plan.units)
    np.testing.assert_array_equal(res.verdicts, want)
    # Compiled entries are pinned to the mesh's device scope.
    scope = eng.backend.cache_scope()
    assert all(k[1] == scope for k in eng.cache._fns)


def test_sharded_verdict_kind_follows_pallas_rule():
    """The fused per-shard kernel serves n_pad <= FUSED_MAX_NPAD only
    where Pallas compiles (a TPU); interpreted, the jax_fast program."""
    from repro.configs.shapes import FUSED_MAX_NPAD

    backend = make_backend("sharded")
    assert backend.interpret                    # a CPU host
    for n_pad in (16, 64, FUSED_MAX_NPAD, 2 * FUSED_MAX_NPAD):
        assert backend.verdict_kind(n_pad) == "verdict"
    backend._interpret = False                  # as resolved on a TPU
    for n_pad in (16, 64, FUSED_MAX_NPAD):
        assert backend.verdict_kind(n_pad) == "fused"
    assert backend.verdict_kind(2 * FUSED_MAX_NPAD) == "verdict"


def test_single_device_fused_runner_matches_reference():
    adjs = np.zeros((3, 16, 16), dtype=bool)
    for i, g in enumerate((G.cycle(9), G.clique(9), G.path(16))):
        adjs[i, :g.n_nodes, :g.n_nodes] = g.with_dense().adj
    c0 = dispatch_counter.count
    out = make_backend("sharded").compile_fused_batch(16, 3)(adjs)
    assert dispatch_counter.count - c0 == 1
    np.testing.assert_array_equal(out, [False, True, True])


def test_mesh_runner_pads_uneven_batches():
    run = make_mesh_verdict_runner(build_mesh(1))
    adjs = np.stack([g.with_dense().adj for g in
                     (G.cycle(9), G.clique(9), G.path(9))])
    out = run(adjs)                      # b=3 on any mesh size
    assert out.shape == (3,)
    np.testing.assert_array_equal(out, [False, True, True])


# ---------------------------------------------------------------------------
# Subprocess: emulated 8-device host
# ---------------------------------------------------------------------------
_CHILD = r"""
import numpy as np
import jax

assert jax.device_count() == 8, f"emulation failed: {jax.device_count()}"

from repro.core import generators as G
from repro.engine.backends import make_backend
from repro.engine.mesh import build_mesh, make_mesh_verdict_runner, \
    mesh_signature
from repro.engine.session import ChordalityEngine
from repro.kernels import dispatch_counter
from repro.witness import verify_witness

graphs = [G.gnp(24, 0.25, seed=s) for s in range(14)]
graphs += [G.cycle(9), G.clique(6), G.path(11), G.gnp(20, 0.6, seed=99)]
oracle = ChordalityEngine(backend="numpy_ref", max_batch=8)
want = oracle.run(graphs).verdicts
assert want.any() and not want.all(), "zoo must mix verdicts"

for d in (1, 2, 4, 8):
    eng = ChordalityEngine(
        backend=make_backend("sharded", n_devices=d), max_batch=8)
    assert eng.backend.device_count == d
    sig = eng.backend.cache_scope()
    assert sig == ("cpu:0" if d == 1 else f"cpu:mesh{d}"), sig
    c0 = dispatch_counter.count
    res = eng.run(graphs)
    assert dispatch_counter.count - c0 == len(res.plan.units), \
        f"d={d}: dispatches != units"
    np.testing.assert_array_equal(res.verdicts, want,
                                  err_msg=f"d={d} verdict mismatch")
    # Uneven unit count: 3 graphs -> batch bucket 4, padded to 8 shards.
    res3 = eng.run(graphs[:3])
    np.testing.assert_array_equal(res3.verdicts, want[:3],
                                  err_msg=f"d={d} uneven mismatch")
    print(f"MESH-OK d={d} scope={sig}")

# Direct runner: batch not a multiple of the mesh size pads internally.
run8 = make_mesh_verdict_runner(build_mesh(8))
adjs = np.stack([g.with_dense().adj for g in
                 (G.cycle(9), G.clique(9), G.path(9), G.gnp(9, .5, 1),
                  G.gnp(9, .5, 2))])
out = run8(adjs)                      # b=5 on 8 shards
assert out.shape == (5,)
np.testing.assert_array_equal(
    out[:3], [False, True, True])

# Witnesses on a sharded engine ride the documented jax_faithful
# fallback — still bit-identical, still independently checkable.
eng8 = ChordalityEngine(
    backend=make_backend("sharded", n_devices=8), max_batch=8)
wres = eng8.run(graphs[:6], witness=True)
np.testing.assert_array_equal(wres.verdicts, want[:6])
for g, w in zip(graphs[:6], wres.witnesses):
    n = g.n_nodes
    err = verify_witness(g.with_dense().adj[:n, :n], w)
    assert err is None, err
print("ALL-OK")
"""


def _run_emulated(script: str, n_devices: int = 8, args=()) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-c", script, *args], capture_output=True,
        text=True, env=env, cwd=str(ROOT), timeout=600)
    assert p.returncode == 0, (
        f"child failed ({p.returncode})\n"
        f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-4000:]}")
    return p.stdout


def test_sharded_bit_identity_across_emulated_mesh_sizes():
    out = _run_emulated(_CHILD)
    for d in (1, 2, 4, 8):
        assert f"MESH-OK d={d}" in out
    assert "ALL-OK" in out


# ---------------------------------------------------------------------------
# The fused per-shard program (kind "fused": the Pallas kernel under
# shard_map), interpreted on emulated meshes of 4 and 8 devices.
# ---------------------------------------------------------------------------
_FUSED_CHILD = r"""
import sys

import numpy as np
import jax

from repro import obs
from repro.core import generators as G
from repro.engine.backends import make_backend
from repro.engine.session import ChordalityEngine
from repro.kernels import dispatch_counter

d = int(sys.argv[1])
assert jax.device_count() == d, f"emulation failed: {jax.device_count()}"
backend = make_backend("sharded")
assert backend.interpret and backend.cache_scope() == f"cpu:mesh{d}"
reference = make_backend("numpy_ref").compile_batch(0, 0)
# The five graph classes of the thesis's section 7.
MAKERS = (lambda n, s: G.clique(n),
          lambda n, s: G.dense_random(n, 0.5, seed=s),
          lambda n, s: G.sparse_random(n, avg_degree=4, seed=s),
          lambda n, s: G.random_tree(n, seed=s),
          lambda n, s: G.k_tree(n, k=3, seed=s))


def unit(n_pad, b, seed):
    rng = np.random.default_rng(seed)
    adjs = np.zeros((b, n_pad, n_pad), dtype=bool)
    for i in range(b):
        n = int(rng.integers(n_pad // 2 + 1, n_pad + 1))
        adjs[i, :n, :n] = MAKERS[i % 5](n, seed + i).with_dense().adj[:n, :n]
    return adjs


# Batch 6 pads to 8: on 4 devices the last shard holds only padding.
for n_pad in (64, 128):
    for b in (8, 4, 6):
        adjs = unit(n_pad, b, 100 * n_pad + b)
        want = reference(adjs)
        assert want.any() and not want.all()
        c0 = dispatch_counter.count
        got = backend.compile_fused_batch(n_pad, b)(adjs)
        assert dispatch_counter.count - c0 == 1, f"n_pad={n_pad} b={b}"
        np.testing.assert_array_equal(
            got, want, err_msg=f"d={d} n_pad={n_pad} b={b}")
print(f"FUSED-OK d={d}")

# A traced unit through the engine: the test steers the backend onto its
# fused kind, which a CPU mesh would not choose (Pallas is interpreted).
backend.verdict_kind = lambda n_pad: "fused"
graphs = [MAKERS[i % 5](40 + i, i) for i in range(6)]
eng = ChordalityEngine(backend=backend, max_batch=8)
sink = obs.ListSink()
obs.enable_tracing(sink)
try:
    c0 = dispatch_counter.count
    res = eng.run(graphs)
    ticks = dispatch_counter.count - c0
finally:
    obs.disable_tracing()
np.testing.assert_array_equal(
    res.verdicts,
    ChordalityEngine(backend="numpy_ref").run(graphs).verdicts)
units = [s for root in sink.spans for s in root.walk() if s.name == "unit"]
assert len(units) == len(res.plan.units) == ticks == 1, (len(units), ticks)
(u,) = units
assert u.attrs["kind"] == "fused" and u.attrs["batch"] == 8
dispatch = u.find("dispatch")
assert [c.name for c in dispatch.children] == \
    ["pack", "h2d", "device", "d2h"], dispatch.children
h2d = dispatch.children[1]
assert h2d.attrs == {"bytes": 8 * 64 * 64, "devices": d}, h2d.attrs
print(f"TRACED-OK d={d}")
"""


@pytest.mark.parametrize("n_devices", [4, 8])
def test_sharded_fused_kernel_matches_reference_on_emulated_mesh(n_devices):
    out = _run_emulated(_FUSED_CHILD, n_devices, args=(str(n_devices),))
    assert f"FUSED-OK d={n_devices}" in out
    assert f"TRACED-OK d={n_devices}" in out
