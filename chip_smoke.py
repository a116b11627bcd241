#!/usr/bin/env python3
"""Smoke run of the served chordality path on a TPU.

    python chip_smoke.py              # one chip: jax_fast + pallas_peo
    python chip_smoke.py --chips 4    # the sharded backend on a 4-chip mesh

One process drives the chip through the entry points a user calls:
``AsyncChordalityEngine`` over the compile cache and a named device
backend. Seeded graphs from ``repro.core.generators`` (chordal and not)
land in fixed engine buckets; every verdict is compared with the host
``numpy_ref`` backend and every certificate is checked by
``repro.witness.verify_witness``.

Phases (one chip):

* verdict — ``jax_fast`` then ``pallas_peo`` at n_pad 64 (B=32), 256
  (B=32), 2048 (B=8, the fused kernel's cap) and 8192 (B=2, the largest
  engine bucket, served by pallas_peo's split pipeline);
* certified — ``want_witness=True`` at n_pad 256 and 1024 on both
  backends (pallas_peo's fused witness kernel).

With ``--chips 4`` only the ``sharded`` backend runs, on a mesh over four
chips at n_pad 256 (B=32) and 2048 (B=8), where it runs the fused Pallas
kernel once per chip, and 4096 (B=4), where it runs ``jax_fast``'s
program; it is compared with ``jax_fast`` on one chip and with
``numpy_ref``, and neither compiled program may hold a collective.

Lines before the last are informational (compile and wall seconds are
set-up and smoke timings, not measured speed). The last line is
``{"ok": true, "device": {...}}``; any mismatch, failed request or
unexpected path exits non-zero without it. Without a TPU, or without the
repo's ``src/`` next to this file, it exits non-zero at once.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

#: (n_pad, batch, graphs) of each phase; graph sizes are drawn inside the
#: bucket so every request pads to exactly that n_pad.
VERDICT_BUCKETS = ((64, 32), (256, 32), (2048, 8), (8192, 2))
WITNESS_BUCKETS = ((256, 8), (1024, 4))
SHARDED_BUCKETS = ((256, 32), (2048, 8), (4096, 4))
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter")


class SmokeError(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def make_graphs(n_pad: int, count: int, seed: int):
    """``count`` seeded graphs whose sizes pad to ``n_pad``, chordal
    (random_chordal, k_tree, clique) and non-chordal (cycle,
    sparse_random) in rotation — any two consecutive ones mix verdicts."""
    import numpy as np

    from repro.core import generators as G

    rng = np.random.default_rng(seed)
    makers = (
        lambda n, s: G.random_chordal(n, k=8, subset_p=0.6, seed=s),
        lambda n, s: G.cycle(n),
        lambda n, s: G.k_tree(n, k=3, seed=s),
        lambda n, s: G.sparse_random(n, avg_degree=6, seed=s),
        lambda n, s: G.clique(n),
    )
    sizes = rng.integers(n_pad // 2 + 1, n_pad + 1, size=count)
    return [makers[i % len(makers)](int(n), seed + i)
            for i, n in enumerate(sizes)]


def reference_verdicts(graphs):
    """Host ``numpy_ref`` verdicts through the sync engine."""
    import numpy as np

    from repro.engine import ChordalityEngine

    return np.asarray(
        ChordalityEngine(backend="numpy_ref").run(graphs).verdicts,
        dtype=bool)


def emit(**row) -> None:
    print(json.dumps(row, sort_keys=True), flush=True)


def serve_bucket(svc, graphs, n_pad, batch, want_witness=False):
    """Warm one (n_pad, batch) shape, serve ``graphs`` through the queue;
    returns (responses, compile_s, wall_s, units)."""
    from repro.engine import gather

    t0 = time.perf_counter()
    svc.engine.warmup([n_pad], batch=batch, witness=want_witness)
    compile_s = time.perf_counter() - t0
    units0 = svc.stats.n_units
    t0 = time.perf_counter()
    futs = svc.submit_many(graphs, want_witness=want_witness)
    svc.flush()
    resps = gather(futs)
    wall_s = time.perf_counter() - t0
    for r in resps:
        check(r.n_pad == n_pad and r.batch == batch,
              f"request landed in (n_pad={r.n_pad}, B={r.batch}), "
              f"expected ({n_pad}, {batch})")
    return resps, compile_s, wall_s, svc.stats.n_units - units0


def run_phase(name, buckets, device_kind, *, want_witness=False, seed=0):
    """Serve every bucket on backend ``name``; check verdicts against
    numpy_ref (and witnesses against verify_witness).

    Returns ``({n_pad: verdicts}, compile-cache keys, backend)`` for the
    caller's cross-backend and path checks.
    """
    import numpy as np

    from repro.configs.service import ServiceConfig
    from repro.engine import AsyncChordalityEngine
    from repro.witness import verify_witness

    batch_cap = max(b for _, b in buckets)
    cfg = ServiceConfig(max_batch=batch_cap, max_wait_ms=60_000.0,
                        max_queue=4 * batch_cap, backend=name,
                        drain_timeout_s=900.0)
    verdicts = {}
    with AsyncChordalityEngine(cfg) as svc:
        for n_pad, batch in buckets:
            graphs = make_graphs(n_pad, batch, seed=seed + n_pad)
            want = reference_verdicts(graphs)
            check(want.any() and not want.all(),
                  f"n_pad={n_pad}: the graphs do not mix verdicts")
            resps, compile_s, wall_s, units = serve_bucket(
                svc, graphs, n_pad, batch, want_witness=want_witness)
            got = np.asarray([r.verdict for r in resps], dtype=bool)
            check(np.array_equal(got, want),
                  f"{name} n_pad={n_pad}: verdicts {got.tolist()} != "
                  f"numpy_ref {want.tolist()}")
            if want_witness:
                for g, r, v in zip(graphs, resps, want):
                    w = r.witness
                    check(w is not None and bool(w.chordal) == bool(v),
                          f"{name} n_pad={n_pad}: witness missing or "
                          f"disagrees with numpy_ref")
                    n = g.n_nodes
                    err = verify_witness(g.with_dense().adj[:n, :n], w)
                    check(err is None,
                          f"{name} n_pad={n_pad}: witness rejected: {err}")
            verdicts[n_pad] = got
            mix = {}
            for r in resps:
                mix[r.backend] = mix.get(r.backend, 0) + 1
            emit(phase="certified" if want_witness else "verdict",
                 backend=name, n_pad=n_pad, batch=batch,
                 compile_s=compile_s, wall_s=wall_s, units=units,
                 occupancy=len(graphs) / max(units, 1), backend_mix=mix,
                 chordal=int(want.sum()), device_kind=device_kind)
        st = svc.stats
        check(st.n_failed == 0 and st.n_cancelled == 0
              and st.n_completed == sum(b for _, b in buckets),
              f"{name}: {st.n_failed} failed, {st.n_cancelled} cancelled, "
              f"{st.n_completed} completed")
        check(set(st.backend_histogram) == {name},
              f"{name}: backend mix {st.backend_histogram}")
        return verdicts, svc.engine.cache.keys(), svc.engine.backend


def check_pallas_paths(keys, backend) -> None:
    """pallas_peo on the chip: Mosaic-compiled, fused kernels up to the
    fused cap, the fused witness kernel for certified buckets."""
    from repro.configs.shapes import FUSED_MAX_NPAD, FUSED_WITNESS_MAX_NPAD

    check(backend.interpret is False,
          "pallas_peo resolved interpret=True on a TPU")
    kinds = {(k[2], k[3]) for k in keys}
    for n_pad, _ in VERDICT_BUCKETS:
        kind = backend.verdict_kind(n_pad)
        if n_pad <= FUSED_MAX_NPAD:
            check(kind in ("fused", "fused_packed"),
                  f"pallas_peo n_pad={n_pad} served by {kind!r}")
        check((kind, n_pad) in kinds, f"no {kind!r} executable at {n_pad}")
    for n_pad, _ in WITNESS_BUCKETS:
        if n_pad <= FUSED_WITNESS_MAX_NPAD:
            check(backend.witness_kind(n_pad) == "fused_witness",
                  f"pallas_peo certified n_pad={n_pad} not fused_witness")
            check(("fused_witness", n_pad) in kinds,
                  f"no fused_witness executable at {n_pad}")


def sharded_phase(device_kind, buckets=SHARDED_BUCKETS):
    """The sharded backend on a mesh over every chip vs jax_fast on one
    chip vs numpy_ref; the program each bucket's kind compiles holds no
    collective. Returns the sharded phase's compile-cache keys and
    backend for :func:`check_sharded_paths`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.engine.backends import make_backend
    from repro.engine.mesh import (
        batch_sharding,
        build_mesh,
        make_mesh_fused,
        make_mesh_verdicts,
    )

    mesh = build_mesh()
    probe = make_backend("sharded", mesh=mesh)
    for n_pad, batch in buckets:
        if probe.verdict_kind(n_pad) == "fused":
            fn, dtype = make_mesh_fused(mesh, probe.interpret), jnp.int8
        else:
            fn, dtype = make_mesh_verdicts(mesh), jnp.bool_
        hlo = fn.lower(jax.ShapeDtypeStruct(
            (batch, n_pad, n_pad), dtype,
            sharding=batch_sharding(mesh))).compile().as_text()
        found = [c for c in COLLECTIVES if c in hlo]
        check(not found, f"sharded n_pad={n_pad}: collectives {found}")
    sharded, keys, backend = run_phase("sharded", buckets, device_kind)
    check(backend.device_count == len(jax.devices()),
          f"sharded mesh spans {backend.device_count} devices")
    one_chip, _, _ = run_phase("jax_fast", buckets, device_kind)
    for n_pad, _ in buckets:
        check(np.array_equal(sharded[n_pad], one_chip[n_pad]),
              f"sharded vs jax_fast (1 chip) n_pad={n_pad}: "
              f"{sharded[n_pad].tolist()} != {one_chip[n_pad].tolist()}")
    return keys, backend


def check_sharded_paths(keys, backend, buckets=SHARDED_BUCKETS) -> None:
    """sharded on the chip: the Mosaic-compiled fused kernel per shard up
    to the fused cap, the jax_fast program above it."""
    from repro.configs.shapes import FUSED_MAX_NPAD

    check(backend.interpret is False,
          "sharded resolved interpret=True on a TPU")
    kinds = {(k[2], k[3]) for k in keys}
    for n_pad, _ in buckets:
        want = "fused" if n_pad <= FUSED_MAX_NPAD else "verdict"
        kind = backend.verdict_kind(n_pad)
        check(kind == want, f"sharded n_pad={n_pad} served by {kind!r}")
        check((kind, n_pad) in kinds, f"no {kind!r} executable at {n_pad}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a 4-chip mesh")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); refusing to run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: the repro package is not under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.engine.persistent_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    kind = devices[0].device_kind
    emit(phase="start", device_kind=kind, devices=len(devices),
         jax=jax.__version__, compile_cache=cache_dir)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            check_sharded_paths(*sharded_phase(kind))
        else:
            for name in ("jax_fast", "pallas_peo"):
                _, keys, _ = run_phase(name, VERDICT_BUCKETS, kind)
                _, wkeys, backend = run_phase(name, WITNESS_BUCKETS, kind,
                                              want_witness=True)
                if name == "pallas_peo":
                    check_pallas_paths(keys + wkeys, backend)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit(phase="done", total_s=time.perf_counter() - t0, device_kind=kind)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
