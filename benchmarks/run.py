"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table (Figures 6–10) + kernel micro-benches +
engine serving tables (backend comparison, sparse-regime CSR vs dense,
compile-time amortization, router-calibration samples).
Prints ``name,us_per_call,derived`` CSV rows (assignment format); the
derived column carries the parallel-vs-sequential speedup — the paper's
headline metric — or graphs/s for the engine tables.

Flags: --quick shrinks sizes (local iteration); --smoke shrinks harder
(the CI smoke step runs ``--tables engine --smoke``); --tables selects
sections. The ``mesh`` table is opt-in only (never part of ``all``): it
forces 8 emulated host devices via XLA_FLAGS *before jax initializes*,
which would contaminate every other table's single-device timings.
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal sizes for CI smoke (implies --quick)")
    ap.add_argument("--tables", default="all",
                    help="comma list: cliques,dense,sparse,trees,chordal,"
                         "kernels,lexbfs,engine,router,service,witness,"
                         "recognition,saturation,obs,mesh (mesh is opt-in"
                         " only; it is excluded from 'all')")
    ap.add_argument("--mesh-devices", type=int, default=8,
                    help="emulated host device count for --tables mesh")
    args = ap.parse_args(argv)
    if args.smoke:
        args.quick = True

    which = (
        ["cliques", "dense", "sparse", "trees", "chordal", "kernels",
         "lexbfs", "engine", "router", "service", "witness", "recognition",
         "saturation", "obs"]
        if args.tables == "all" else args.tables.split(",")
    )

    if "mesh" in which:
        # Must happen before anything imports jax: the device count is
        # frozen at backend init. A jax already imported (e.g. via a
        # caller's site hook) would silently pin device_count=1, so the
        # mesh table refuses to run in that case.
        if "jax" in sys.modules:
            print("error: --tables mesh needs XLA_FLAGS set before jax "
                  "imports; run benchmarks.run as a fresh process",
                  file=sys.stderr)
            return 2
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.mesh_devices}").strip()
        import jax

        platform = jax.devices()[0].platform
        if platform != "cpu":
            print(f"error: --tables mesh is a CPU emulation of "
                  f"{args.mesh_devices} host devices and refuses to run "
                  f"where JAX sees a {platform!r} device; chip_smoke.py "
                  f"--chips 4 drives the sharded path on chips",
                  file=sys.stderr)
            return 2
        print(f"# mesh table: CPU emulation of {args.mesh_devices} host "
              f"devices - partitioning overhead, not chip speed",
              file=sys.stderr)

    from repro.engine.persistent_cache import enable_persistent_cache

    enable_persistent_cache()

    from benchmarks import kernel_bench, paper_tables

    print("name,us_per_call,derived")

    def emit(rows):
        for r in rows:
            if "us_per_call" in r:  # kernel rows are preformatted
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
                continue
            par = r["parallel_jax_ms"]
            seq = r.get("seq_habib_ms", float("nan"))
            seq_np = r.get("seq_numpy_ms", float("nan"))
            speedup = seq / par if par and seq == seq else float("nan")
            speedup_np = (
                seq_np / par if par and seq_np == seq_np else float("nan"))
            print(
                f"{r['name']},{par * 1e3:.1f},"
                f"speedup_vs_habib={speedup:.2f};"
                f"speedup_vs_numpy={speedup_np:.2f};"
                f"n={r['n']};m={r['m_undirected']}")
            sys.stdout.flush()

    sizes = dict(
        cliques=(256, 512, 1024) if args.quick else (256, 512, 1024, 2048),
        dense_n=768 if args.quick else 1536,
        sparse_n=1024 if args.quick else 4096,
        trees_n=1024 if args.quick else 4096,
        chordal_n=768 if args.quick else 1536,
        n_tests=2 if args.quick else 3,
    )

    if "cliques" in which:
        print("# paper Fig.6 - cliques", file=sys.stderr)
        emit(paper_tables.table_cliques(sizes["cliques"]))
    if "dense" in which:
        print("# paper Fig.7 - dense random", file=sys.stderr)
        emit(paper_tables.table_dense(sizes["dense_n"], sizes["n_tests"]))
    if "sparse" in which:
        print("# paper Fig.8 - sparse random (M=20N)", file=sys.stderr)
        emit(paper_tables.table_sparse(sizes["sparse_n"], sizes["n_tests"]))
    if "trees" in which:
        print("# paper Fig.9 - trees", file=sys.stderr)
        emit(paper_tables.table_trees(sizes["trees_n"], sizes["n_tests"]))
    if "chordal" in which:
        print("# paper Fig.10 - random chordal", file=sys.stderr)
        emit(paper_tables.table_chordal(
            sizes["chordal_n"], 3 if args.quick else 4))
    if "kernels" in which:
        print("# kernel micro-bench - peo paths", file=sys.stderr)
        if not args.smoke:
            emit(kernel_bench.bench_peo_paths(n=1024 if args.quick else 2048))
        print("# kernel micro-bench - fused pipeline + batched lexbfs "
              "(-> BENCH_kernels.json)", file=sys.stderr)
        if args.smoke:
            rows, artifact = kernel_bench.bench_kernels_fused(
                ns=(64, 256), batch=4, repeats=2,
                dispatch_n=64, dispatch_batch=4)
        elif args.quick:
            rows, artifact = kernel_bench.bench_kernels_fused(
                ns=(64, 128, 256), batch=8, repeats=2)
        else:
            rows, artifact = kernel_bench.bench_kernels_fused(
                ns=(64, 128, 256, 512, 1024), batch=8)
        emit(rows)
        import json

        with open("BENCH_kernels.json", "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print("# wrote BENCH_kernels.json", file=sys.stderr)
    if "lexbfs" in which:
        print("# kernel micro-bench - lexbfs/mcs", file=sys.stderr)
        emit(kernel_bench.bench_lexbfs(n=1024 if args.quick else 2048))
    if "engine" in which:
        print("# engine serving bench - backends via repro.engine",
              file=sys.stderr)
        emit(kernel_bench.bench_engine_backends(
            n_max=64 if args.smoke else (128 if args.quick else 256),
            requests=8 if args.smoke else (16 if args.quick else 32),
            backends=("jax_faithful", "jax_fast", "numpy_ref", "csr",
                      "auto")))
        print("# engine serving bench - sparse regime (csr vs dense)",
              file=sys.stderr)
        if args.smoke:
            emit(kernel_bench.bench_engine_sparse(
                n=256, c=8.0, requests=8, max_batch=8, repeats=1))
        elif args.quick:
            emit(kernel_bench.bench_engine_sparse(
                n=512, c=10.0, requests=16, max_batch=16))
        else:
            emit(kernel_bench.bench_engine_sparse(
                n=1024, c=10.0, requests=32, max_batch=32))
        print("# engine serving bench - compile-time amortization",
              file=sys.stderr)
        emit(kernel_bench.bench_engine_amortization(
            n=64 if args.smoke else (128 if args.quick else 256),
            stream_lens=(1, 8) if args.smoke else (1, 4, 16, 64),
            max_batch=8 if args.smoke else 32))
    if "service" in which:
        print("# async serving bench - throughput vs offered load and "
              "max_wait_ms", file=sys.stderr)
        if args.smoke:
            emit(kernel_bench.bench_service(
                n=64, requests=12, max_batch=4, waits_ms=(0.0, 4.0),
                offered_gps=(0,)))
        elif args.quick:
            emit(kernel_bench.bench_service(
                n=128, requests=32, max_batch=8, waits_ms=(0.0, 4.0),
                offered_gps=(0, 200)))
        else:
            emit(kernel_bench.bench_service(
                n=256, requests=96, max_batch=32,
                waits_ms=(0.0, 2.0, 8.0), offered_gps=(0, 200)))
    if "witness" in which:
        print("# witness bench - verdict-only vs +certificate overhead "
              "(-> BENCH_witness.json)", file=sys.stderr)
        if args.smoke:
            # density 0.05 so the n64_d5_B1 cell shares a key with the
            # committed full-run artifact — overlap is what the perf
            # gate's overhead ceiling actually compares.
            rows, artifact = kernel_bench.bench_witness(
                ns=(64,), densities=(0.05,), batches=(1, 8),
                requests=8, repeats=1, dispatch_n=32, dispatch_batch=4)
        elif args.quick:
            rows, artifact = kernel_bench.bench_witness(
                ns=(64, 128), densities=(0.05, 0.3), batches=(1, 8),
                requests=12)
        else:
            rows, artifact = kernel_bench.bench_witness()
        emit(rows)
        import json

        with open("BENCH_witness.json", "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print("# wrote BENCH_witness.json", file=sys.stderr)
    if "recognition" in which:
        print("# recognition bench - multi-property vs verdict-only "
              "(-> BENCH_recognition.json)", file=sys.stderr)
        if args.smoke:
            # n=64, B=1 cells share keys with the committed full-run
            # artifact — overlap is what the perf gate's overhead ceiling
            # and sweeps-per-unit equality actually compare.
            rows, artifact = kernel_bench.bench_recognition(
                ns=(64,), batches=(1,), requests=8, repeats=1,
                sweep_n=64, sweep_batch=4)
        elif args.quick:
            rows, artifact = kernel_bench.bench_recognition(
                ns=(64, 128), batches=(1, 8), requests=12, repeats=3)
        else:
            rows, artifact = kernel_bench.bench_recognition()
        emit(rows)
        import json

        with open("BENCH_recognition.json", "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print("# wrote BENCH_recognition.json", file=sys.stderr)
    if "saturation" in which:
        print("# saturation bench - static waits vs autotuned under "
              "bimodal-n load (-> BENCH_saturation.json)", file=sys.stderr)
        # The stream must be long enough that the saturation burst blows
        # the autotuned delay budget (the controller's collapse signal)
        # and that per-pass scheduler jitter amortizes; below ~300
        # requests the end-of-stream window tax dominates and the knee
        # measures the tail, not the serving discipline.
        if args.smoke:
            rows, artifact = kernel_bench.bench_saturation(
                requests=320, max_batch=16, waits_ms=(0.0, 2.0),
                offered_gps=(1000, 0), repeats=2, burst_repeats=9)
        elif args.quick:
            rows, artifact = kernel_bench.bench_saturation(
                requests=512, max_batch=16, waits_ms=(0.0, 2.0, 8.0),
                offered_gps=(1000, 4000, 0), repeats=3, burst_repeats=15)
        else:
            rows, artifact = kernel_bench.bench_saturation()
        emit(rows)
        import json

        with open("BENCH_saturation.json", "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print("# wrote BENCH_saturation.json", file=sys.stderr)
    if "obs" in which:
        print("# obs bench - tracing overhead enabled vs disabled "
              "(-> BENCH_obs.json)", file=sys.stderr)
        # All tiers keep n=256/B=32 so the smoke cell shares its key
        # with the committed full-run artifact — the perf gate's
        # overhead ceiling reads exactly that cell.
        if args.smoke:
            rows, artifact = kernel_bench.bench_obs(
                n=256, batch=32, requests=32, repeats=3)
        elif args.quick:
            rows, artifact = kernel_bench.bench_obs(
                n=256, batch=32, requests=64, repeats=5)
        else:
            rows, artifact = kernel_bench.bench_obs()
        emit(rows)
        import json

        with open("BENCH_obs.json", "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print("# wrote BENCH_obs.json", file=sys.stderr)
    if "mesh" in which:
        print("# mesh bench - sharded scaling over emulated devices "
              "(-> BENCH_mesh.json)", file=sys.stderr)
        # All tiers keep n=256/B=32/d=1..8 so the smoke cells share
        # their keys with the committed full-run artifact — the perf
        # gate's efficiency/parity floors read exactly those cells.
        # Smoke floor: requests must give >= 2 work units per timed run
        # (64/B32) — a single-unit run can't amortize per-run overhead
        # and the d=1 parity cell flakes under the 0.9 gate floor.
        if args.smoke:
            rows, artifact = kernel_bench.bench_mesh(
                n=256, batch=32, requests=64, repeats=3)
        elif args.quick:
            rows, artifact = kernel_bench.bench_mesh(
                n=256, batch=32, requests=64, repeats=3)
        else:
            rows, artifact = kernel_bench.bench_mesh()
        emit(rows)
        import json

        with open("BENCH_mesh.json", "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
        print("# wrote BENCH_mesh.json", file=sys.stderr)
    if "router" in which:
        print("# router cost-model calibration samples", file=sys.stderr)
        emit(kernel_bench.bench_router_samples(quick=args.quick))
    return 0


if __name__ == "__main__":
    sys.exit(main())
