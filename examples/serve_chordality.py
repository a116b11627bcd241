"""Chordality-testing service: an async engine under open-loop load —
the serving-shaped example application.

    PYTHONPATH=src python examples/serve_chordality.py \
        [--requests 64] [--rate 200] [--max-wait-ms 2.0] [--backend auto]

A synthetic load generator submits requests (graphs of varying size and
class) at an offered rate with exponential inter-arrival gaps — open loop:
arrivals don't wait for completions, exactly the traffic a service sees.
Each ``submit`` returns immediately with a future; the service's admission
loop micro-batches same-bucket requests into fixed-shape work units
(collect up to ``--max-wait-ms`` or until ``--batch`` fills), routes every
drained unit through the cost model (``--backend auto``), and a background
executor drives the compile cache. The report shows the serving tradeoff:
queue-delay percentiles vs batch occupancy vs backend mix (DESIGN.md §9).
"""
import argparse
import time

import numpy as np

from repro.core import generators as G
from repro.configs.service import ServiceConfig
from repro.engine import AsyncChordalityEngine, backend_names, gather
from repro.engine.persistent_cache import enable_persistent_cache

REQUEST_KINDS = ("random_chordal", "sparse_random", "cycle", "random_tree")


def synth_request(i: int, n_max: int, rng):
    """One synthetic request; returns (Graph, kind) — the kind is the
    request metadata a real service would carry alongside the payload."""
    kind = REQUEST_KINDS[i % 4]
    n = int(rng.integers(n_max // 2, n_max))
    if kind == "random_chordal":
        return G.random_chordal(n, k=4, subset_p=0.8, seed=i), kind
    if kind == "sparse_random":
        return G.sparse_random(n, avg_degree=6, seed=i), kind
    if kind == "cycle":
        return G.cycle(n), kind
    return G.random_tree(n, seed=i), kind


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16,
                    help="bucket fill target (work-unit batch cap)")
    ap.add_argument("--n-max", type=int, default=96)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="offered load, graphs/s (0 = back-to-back)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="micro-batch window before a partial bucket drains")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="admission bound on outstanding requests")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", *backend_names()],
                    help="registered backend, or 'auto' for cost-model "
                         "routing per drained work unit")
    args = ap.parse_args()
    enable_persistent_cache()

    rng = np.random.default_rng(0)
    pairs = [synth_request(i, args.n_max, rng)
             for i in range(args.requests)]
    requests = [g for g, _ in pairs]
    kinds = [k for _, k in pairs]

    cfg = ServiceConfig(
        max_queue=args.max_queue, max_batch=args.batch,
        max_wait_ms=args.max_wait_ms, backend=args.backend)
    print(f"async service: {args.requests} requests at "
          f"{'max speed' if args.rate <= 0 else f'{args.rate:g}/s offered'}"
          f" (backend={args.backend}, max_batch={args.batch}, "
          f"max_wait={args.max_wait_ms:g}ms)")

    with AsyncChordalityEngine(config=cfg) as svc:
        # Warm the compile cache on every shape this traffic can hit —
        # including partial-occupancy batches the wait window produces —
        # so the measured pass shows serving behavior, not jit compiles.
        svc.warmup(requests)

        t0 = time.perf_counter()
        futures = []
        for i, g in enumerate(requests):
            if args.rate > 0:
                # Exponential gaps = Poisson arrivals (open loop).
                time.sleep(float(rng.exponential(1.0 / args.rate)))
            futures.append(svc.submit(g, timeout=30))
        t_submitted = time.perf_counter() - t0
        responses = gather(futures, timeout=300)
        wall = time.perf_counter() - t0

        n_chordal = sum(r.verdict for r in responses)
        s = svc.stats
        print(f"  -> {n_chordal}/{len(responses)} chordal")
        print(f"  admission: {s.n_submitted} submitted in "
              f"{t_submitted:.2f}s, {s.n_units} work units "
              f"(drains: {s.drain_reasons}), mean occupancy "
              f"{s.mean_occupancy:.1f}/{args.batch}")
        print(f"  queue delay p50 {s.p50_queue_ms:.2f}ms / "
              f"p95 {s.p95_queue_ms:.2f}ms, unit exec p50 "
              f"{s.p50_exec_ms:.2f}ms")
        print(f"  backend mix: {s.backend_histogram}")
        print(f"  completed {s.n_completed} in {wall:.2f}s -> "
              f"{s.n_completed / wall:.0f} graphs/s")

        # One detailed answer with certificate, fetched through the same
        # (still warm) service — want_certificate attaches the witness
        # to the future.
        idx = next(
            (i for i, r in enumerate(responses) if not r.verdict), None)
        if idx is not None:
            resp = svc.submit(
                requests[idx], want_certificate=True).result(timeout=120)
            cert = resp.certificate
            print(f"  example certificate: request #{idx} "
                  f"({kinds[idx]}, n={requests[idx].n_nodes}, "
                  f"bucket n_pad={resp.n_pad}, ran on {resp.backend}): "
                  f"chordal={cert.chordal} violations={cert.n_violations}")
        else:
            print("  (all requests chordal — "
                  "no negative certificate to show)")

        # Checkable witnesses through the asyncio adapter: asubmit wraps
        # the thread-based future onto an event loop, and want_witness
        # resolves it with a full repro.witness.WitnessResult that the
        # independent checkers can validate without trusting the engine.
        asyncio_witness_demo(svc, requests, kinds)

        # The scrape surface a dashboard would poll (DESIGN.md §15):
        # stage percentiles, outcome counts, backend mix, cache traffic.
        t = svc.telemetry()
        q, e = t["stages"]["queue_ms"], t["stages"]["exec_ms"]
        print("  telemetry:")
        print(f"    stages: queue p50 {q['p50']:.2f}ms / p95 "
              f"{q['p95']:.2f}ms, exec p50 {e['p50']:.2f}ms / p95 "
              f"{e['p95']:.2f}ms")
        print(f"    requests: {t['requests']}")
        print(f"    backend mix: {t['backend_mix']}, cache hit ratio "
              f"{t['cache']['hit_ratio']:.2f} "
              f"({t['cache']['hits']} hits / {t['cache']['misses']} "
              f"misses, {t['cache']['entries']} executables)")


def asyncio_witness_demo(svc, requests, kinds, k=4):
    """await-style clients: deadline-bounded witness requests."""
    import asyncio

    from repro.witness import verify_witness

    picks = list(range(0, len(requests), max(1, len(requests) // k)))[:k]

    async def fetch():
        futs = [svc.asubmit(requests[i], want_witness=True,
                            deadline_ms=30_000.0) for i in picks]
        return await asyncio.gather(*futs)

    print("  asyncio clients (asubmit + want_witness):")
    for i, resp in zip(picks, asyncio.run(fetch())):
        g = requests[i]
        n = g.n_nodes
        w = resp.witness
        adj = g.with_dense().adj[:n, :n]
        checked = "verified" if verify_witness(adj, w) is None else "BAD"
        if w.chordal:
            detail = (f"treewidth={w.treewidth} colors={w.n_colors} "
                      f"cliques={len(w.cliques)}")
        else:
            detail = f"chordless cycle len={len(w.cycle)}"
        print(f"    #{i} {kinds[i]:>14s} n={n:3d}: "
              f"chordal={w.chordal} {detail} [{checked}]")


if __name__ == "__main__":
    main()
