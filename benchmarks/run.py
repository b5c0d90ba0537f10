"""Benchmark driver: one harness per paper table/figure + the roofline table.

  PYTHONPATH=src python -m benchmarks.run               # quick CPU pass
  PYTHONPATH=src python -m benchmarks.run --full        # full layer sweeps
  PYTHONPATH=src python -m benchmarks.run --json BENCH_ci.json
      # emit the perf-trajectory artifact: per-layer steady-state ms +
      # HBM bytes moved for the streamed vs pre-streaming Pallas Winograd
      # paths on the VGG-style config (CI uploads this; BENCH_PR2.json in
      # the repo root is the committed run for that config)
  PYTHONPATH=src python -m benchmarks.run --json BENCH_PR4.json \
      --config mobilenet
      # same artifact on the MobileNet ladders: fused separable streamed
      # kernel vs the unfused two-kernel pipeline, the stride-2 Winograd
      # (transform-domain phase decomposition) vs im2row A/B on the
      # reduction-block ladder, and the fused-vs-composed MobileNet-v2
      # inverted-residual A/B (BENCH_PR3.json / BENCH_PR4.json in the repo
      # root are the committed runs; CI runs the quick variant per PR)
  PYTHONPATH=src python -m benchmarks.run --json BENCH_PR5.json \
      --config compile
      # whole-network startup A/B through the graph compiler: cold
      # compile() vs warm NetworkPlan.load() artifact, artifact size, a
      # fresh-process bitwise parity gate, and planned-vs-im2row
      # steady-state (BENCH_PR5.json is the committed run)
  PYTHONPATH=src python -m benchmarks.run --json BENCH_PR6.json \
      --config crossover
      # the N-way measured auto_tuned race (im2row / F(2,3) / F(4,3) /
      # F(6,3) / FFT) over the filter-size x resolution x channel
      # crossover grid plus the VGG and MobileNet-v2 ladders, with the
      # per-contender plan-time evidence and the end-to-end time of the
      # chosen policy per layer (BENCH_PR6.json is the committed run)
  PYTHONPATH=src python -m benchmarks.run --json BENCH_PR7.json \
      --config serving
      # the serving runtime under Poisson arrivals: p50/p99 latency +
      # throughput per arrival rate over the bucketed batch plans, then
      # one drill per injected fault class (executor raise, latency
      # spike, corrupt artifact, queue overload) gated on zero dropped
      # requests and zero incorrect responses vs the im2row oracle
      # (BENCH_PR7.json is the committed run)
  PYTHONPATH=src python -m benchmarks.run --json BENCH_PR9.json \
      --config scaling
      # the 1 -> 8 device scaling curve for sharded NetworkPlan execution
      # (data-parallel batch sharding + spatial halo partitioning), each
      # device count in a fresh forced-host-device subprocess, gated on
      # parity vs the unsharded oracle, strictly increasing normalized
      # throughput, >= 3x aggregate at 8 devices, and the version-5
      # artifact restoring the recorded partition on warm start
      # (BENCH_PR9.json is the committed run)
  PYTHONPATH=src python -m benchmarks.run --json BENCH_PR8.json \
      --config precision
      # the mixed-precision A/B: per-layer fp32/bf16/int8 plans over the
      # deep VGG + MobileNet ladders (measured times, analytic HBM bytes
      # with reduced filter payloads, per-layer accuracy), the unpinned
      # auto_tuned race evidence, and the MobileNet-v2 whole-network
      # policy A/B gated on int8 logits top-1 agreement vs fp32
      # (BENCH_PR8.json is the committed run)
  PYTHONPATH=src python -m benchmarks.run --json BENCH_PR10.json \
      --config observe
      # the observability A/B: MobileNet-v2 served with the profiler
      # disabled vs enabled (interleaved rounds, machine-relative
      # overhead %), the per-request span decomposition audited against
      # measured latency, and the chrome://tracing + metrics-snapshot
      # exports written next to the JSON (BENCH_PR10.json is the
      # committed run; benchmarks/regress.py gates CI against it)

Every emitted BENCH_*.json is stamped with jax version, backend/device
kind, git SHA and a UTC timestamp (benchmarks.common.bench_metadata), so
artifacts from different runs/machines are comparable.

Quick mode trims iteration counts and caps per-network layer counts so the
whole suite finishes in minutes on one CPU core; --full runs every unique
layer at paper resolution.
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip", nargs="*", default=[],
                    choices=["per_layer", "whole_network", "fast_fraction",
                             "amortization", "roofline"])
    ap.add_argument("--plan-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="A/B switch for the amortization benchmark: each row "
                         "records a cold plan build plus a rebuild that hits "
                         "the spec cache (--plan-cache, default) or starts "
                         "cold again (--no-plan-cache), next to per-call and "
                         "planned steady-state times")
    ap.add_argument("--json", default=None, metavar="BENCH_<tag>.json",
                    help="run ONLY the benchmark of the chosen --config "
                         "(quick variant unless --full) and write its "
                         "artifact, stamped with jax/backend/git-SHA "
                         "metadata, to this path")
    ap.add_argument("--config", default="vgg_style",
                    choices=["vgg_style", "mobilenet", "compile",
                             "crossover", "serving", "precision",
                             "scaling", "observe"],
                    help="which --json benchmark to run: vgg_style "
                         "(streamed vs materialized dense Winograd), "
                         "mobilenet (fused vs unfused separable blocks), "
                         "compile (whole-network cold-compile vs "
                         "warm-artifact startup + fresh-process parity "
                         "via the graph compiler), crossover (the "
                         "N-way measured auto_tuned race over the "
                         "filter x resolution x channel grid + VGG/MBv2 "
                         "ladders -- BENCH_PR6.json), or serving (the "
                         "fault-tolerant batched serving runtime under "
                         "Poisson arrivals + per-fault-class drills -- "
                         "BENCH_PR7.json), or precision (the per-layer "
                         "and whole-network fp32/bf16/int8 A/B with the "
                         "int8 top-1 accuracy gate -- BENCH_PR8.json), "
                         "or observe (the observability overhead A/B + "
                         "span decomposition audit -- BENCH_PR10.json)")
    args = ap.parse_args(argv)

    from repro.runtime.cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (amortization, fast_fraction, per_layer, roofline,
                            serving, startup, whole_network)

    t0 = time.time()

    if args.json:
        if args.config == "serving":
            serving.main(["--out", args.json]
                         + ([] if args.full else ["--smoke"]))
        elif args.config == "precision":
            from benchmarks import precision
            precision.main(["--out", args.json]
                           + ([] if args.full else ["--quick"]))
        elif args.config == "scaling":
            from benchmarks import scaling
            scaling.main(["--out", args.json]
                         + ([] if args.full else ["--quick"]))
        elif args.config == "observe":
            from benchmarks import observe
            observe.main(["--out", args.json]
                         + ([] if args.full else ["--quick"]))
        elif args.config == "compile":
            res = "224" if args.full else "96"
            iters = "3" if args.full else "2"
            startup.main(["--res", res, "--iters", iters, "--warmup", "1",
                          "--out", args.json])
        else:
            cfg = args.config if args.full else f"{args.config}_quick"
            iters = "3" if args.full else "2"
            per_layer.main(["--config", cfg, "--iters", iters,
                            "--warmup", "1", "--out", args.json])
        print(f"\nwrote {args.json} in {time.time() - t0:.0f}s")
        return

    quick_nets = ["vgg16", "googlenet", "inception_v3", "squeezenet"]

    if "per_layer" not in args.skip:
        print("\n#### benchmarks.per_layer (paper Table 2) ####", flush=True)
        pl_args = ["--iters", "3"] if args.full else \
            ["--iters", "2", "--warmup", "1", "--max-layers-per-net", "6",
             "--networks", *quick_nets]
        per_layer.main(pl_args + ["--out", "results/bench_per_layer.json"])

    if "whole_network" not in args.skip:
        print("\n#### benchmarks.whole_network (paper Table 1) ####",
              flush=True)
        wn_args = [] if args.full else \
            ["--iters", "2", "--networks", *quick_nets]
        whole_network.main(wn_args + ["--out",
                                      "results/bench_whole_network.json"])

    if "fast_fraction" not in args.skip:
        print("\n#### benchmarks.fast_fraction (paper Fig 3) ####", flush=True)
        ff_args = [] if args.full else \
            ["--iters", "1", "--warmup", "1", "--networks", "squeezenet",
             "googlenet"]
        fast_fraction.main(ff_args + ["--out",
                                      "results/bench_fast_fraction.json"])

    if "amortization" not in args.skip:
        print("\n#### benchmarks.amortization (paper section 4) ####",
              flush=True)
        am_args = [] if args.full else ["--iters", "3",
                                        "--m-sweep", "16", "64", "256"]
        am_args += ["--plan-cache" if args.plan_cache else "--no-plan-cache"]
        amortization.main(am_args + ["--out",
                                     "results/bench_amortization.json"])

    if "roofline" not in args.skip:
        print("\n#### benchmarks.roofline (dry-run artifacts) ####",
              flush=True)
        roofline.main(["--out", "results/bench_roofline.json"])
        print("\n#### roofline, optimized phase (EXPERIMENTS.md "
              "section Perf hillclimb cells) ####", flush=True)
        roofline.main(["--phase", "optimized",
                       "--out", "results/bench_roofline_optimized.json"])

    print(f"\nbenchmarks done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
