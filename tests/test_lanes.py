"""Executor lanes bound to devices (DESIGN.md §16).

Lane ``i`` of ``AsyncChordalityEngine`` runs its verdict units on a
one-device backend pinned to ``jax.local_devices()[i % n]``; ``sharded``
lanes share one mesh; with one lane or one device every lane stays on
JAX's default device, under the ``<platform>:0`` cache scope.

* **in-process** — the single CPU device of the test process: pinning a
  backend, and a multi-lane service that keeps every unit on ``cpu:0``.
* **subprocess** — four emulated host devices
  (``XLA_FLAGS=--xla_force_host_platform_device_count=4`` must be set
  before jax initializes): each lane's units run on its own device, read
  from the ``exec`` spans' ``device``; the verdicts, stolen units
  included, equal ``numpy_ref``'s; after ``warmup`` serving adds no
  compile-cache key and no XLA compile. Emulated devices share the CPU:
  these tests prove placement, never speed.
"""
import os
import pathlib
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.service import ServiceConfig
from repro.core import generators as G
from repro.engine import AsyncChordalityEngine, ChordalityEngine, gather
from repro.engine.backends import make_backend
from repro.engine.service import _lane_devices

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_on_device_pins_scope_and_put():
    (dev,) = jax.local_devices()
    pinned = make_backend("jax_fast").on_device(dev)
    assert pinned.device is dev
    assert pinned.cache_scope() == f"{dev.platform}:{dev.id}" == "cpu:0"
    assert pinned._put(np.zeros(3, dtype=bool)).devices() == {dev}
    assert make_backend("jax_fast").device is None   # the copy is pinned
    for name in ("numpy_ref", "sharded"):
        backend = make_backend(name)
        assert backend.on_device(dev) is backend


def test_lanes_resolving_at_once_share_one_pinned_backend():
    """Many threads resolve the same (backend, device) at once, with the
    interpreter switching threads as often as it can: every one gets the
    same pinned instance, so its executables are built once."""
    (dev,) = jax.local_devices()
    eng = ChordalityEngine(backend="jax_fast", max_batch=4)
    got, errors = [], []
    start = threading.Barrier(16, timeout=60)

    def resolve():
        try:
            start.wait()
            for _ in range(200):
                got.append(eng._resolve("jax_fast", dev))
        except Exception as e:                  # surfaced by the assert
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=resolve) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(got) == 16 * 200
    assert len({id(b) for b in got}) == 1 and got[0].device is dev
    assert got[0] is not eng.backend and eng._resolve("jax_fast") is \
        eng.backend


def test_one_device_host_keeps_every_lane_on_the_default_device():
    assert _lane_devices(1) == [None]
    assert _lane_devices(4) == [None] * 4
    graphs = [G.cycle(9), G.clique(9), G.random_tree(20, seed=1)] * 4
    sink = obs.ListSink()
    obs.enable_tracing(sink)
    try:
        cfg = ServiceConfig(max_batch=2, max_wait_ms=0.5, n_lanes=4)
        with AsyncChordalityEngine(config=cfg, backend="jax_fast") as svc:
            svc.warmup(graphs)
            got = [r.verdict for r in
                   gather(svc.submit_many(graphs), timeout=120)]
    finally:
        obs.disable_tracing()
    assert got == [False, True, True] * 4
    assert {k[1] for k in svc.engine.cache.keys()} == {"cpu:0"}
    devices = {root.find("exec").attrs["device"] for root in sink.spans}
    assert devices == {"cpu:0"} and svc._lane_devices == [None] * 4


_CHILD = r"""
import sys
import threading

import numpy as np
import jax

from repro import obs
from repro.configs.service import ServiceConfig
from repro.core import generators as G
from repro.engine import AsyncChordalityEngine, ChordalityEngine, gather
from repro.engine.backends import make_backend
from repro.engine.service import _lane_devices

mode = sys.argv[1]
assert jax.device_count() == 4, f"emulation failed: {jax.device_count()}"
# The five graph classes of the thesis's section 7, at n 33-64 (n_pad 64).
MAKERS = (lambda n, s: G.clique(n),
          lambda n, s: G.dense_random(n, 0.5, seed=s),
          lambda n, s: G.sparse_random(n, avg_degree=4, seed=s),
          lambda n, s: G.random_tree(n, seed=s),
          lambda n, s: G.k_tree(n, k=3, seed=s))
graphs = [MAKERS[i % 5](33 + (7 * i) % 32, 1000 + i) for i in range(20)]
want = ChordalityEngine(backend="numpy_ref").run(graphs).verdicts
assert want.any() and not want.all()


def compiles():
    series = obs.registry.snapshot()["repro_xla_compiles_total"]["series"]
    return sum(s["value"] for s in series)


if mode == "one_lane":
    assert _lane_devices(1) == [None]
    with AsyncChordalityEngine(
            config=ServiceConfig(max_batch=4, max_wait_ms=1.0),
            backend="jax_fast") as svc:
        svc.warmup(graphs)
        got = [r.verdict for r in gather(svc.submit_many(graphs), 300)]
    np.testing.assert_array_equal(got, want)
    assert {k[1] for k in svc.engine.cache.keys()} == {"cpu:0"}
    print("ONE-LANE-OK")
    sys.exit(0)

devices = jax.local_devices()
assert _lane_devices(4) == devices and _lane_devices(6)[4:] == devices[:2]
opts = {"pipeline": "fused"} if mode == "pallas_peo" else {}
for d in devices:
    backend = make_backend(mode, **opts).on_device(d)
    if mode == "sharded":
        assert backend.cache_scope() == "cpu:mesh4"
    else:
        assert backend.cache_scope() == f"cpu:{d.id}"
        assert backend._put(np.zeros(3, dtype=bool)).devices() == {d}

engine = ChordalityEngine(backend=mode, max_batch=4, **opts)
svc = AsyncChordalityEngine(
    config=ServiceConfig(max_batch=4, max_wait_ms=1.0, n_lanes=4),
    engine=engine)
svc.warmup(graphs)
keys, c0 = set(engine.cache.keys()), compiles()
scopes = {k[1] for k in keys}
assert scopes == ({"cpu:mesh4"} if mode == "sharded"
                  else {f"cpu:{i}" for i in range(4)}), scopes

# Every unit lands on lane 0's queue and each lane's first unit waits
# until every lane holds one, so lanes 1-3 get their first units only by
# stealing them, and run them on their own devices.
barrier = threading.Barrier(4, timeout=120)
started, lock = set(), threading.Lock()
execute = svc._execute


def to_lane_0(au):
    with svc._lane_cv:
        svc._lane_queues[0].append(au)
        svc._lane_cv.notify_all()


def gated(au, lane=0):
    with lock:
        first = lane not in started
        started.add(lane)
    if first:
        barrier.wait()
    return execute(au, lane)


svc._dispatch_unit, svc._execute = to_lane_0, gated
sink = obs.ListSink()
obs.enable_tracing(sink)
try:
    got = [r.verdict for r in gather(svc.submit_many(graphs), 300)]
finally:
    obs.disable_tracing()
    svc.shutdown()
np.testing.assert_array_equal(got, want)
steals = [svc._m_steals.value(lane=i) for i in range(4)]
assert steals[0] == 0 and all(steals[1:]), steals
by_lane = {}
for root in sink.spans:
    ex = root.find("exec")
    by_lane.setdefault(ex.attrs["lane"], set()).add(ex.attrs["device"])
expect = {i: {"cpu:mesh4" if mode == "sharded" else f"cpu:{i}"}
          for i in range(4)}
assert by_lane == expect, by_lane
assert set(engine.cache.keys()) == keys
assert compiles() == c0, (compiles(), c0)
print(f"LANES-OK {mode}")
"""


def _run_emulated(mode: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    p = subprocess.run(
        [sys.executable, "-c", _CHILD, mode], capture_output=True,
        text=True, env=env, cwd=str(ROOT), timeout=600)
    assert p.returncode == 0, (
        f"child failed ({p.returncode})\n"
        f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-4000:]}")
    return p.stdout


@pytest.mark.parametrize("mode", ["jax_fast", "pallas_peo", "sharded"])
def test_lanes_run_units_on_their_own_devices(mode):
    assert f"LANES-OK {mode}" in _run_emulated(mode)


def test_one_lane_keeps_the_default_scope_on_four_devices():
    assert "ONE-LANE-OK" in _run_emulated("one_lane")
