"""Fault-tolerant conv serving example: MobileNet-v2 behind the batched
serving runtime, warm-started from per-bucket NetworkPlan artifacts, with
a live fault drill against the supervisor's degrade ladder.

First run compiles one plan per batch bucket and saves the artifacts
(cold); re-running warm-starts every bucket from disk with zero filter
transforms. The drill then injects a permanent executor failure into one
layer mid-traffic and shows the ladder re-place it onto the im2row
fallback without dropping a single in-flight request.

  PYTHONPATH=src python examples/serve_conv.py                 # res 96
  PYTHONPATH=src python examples/serve_conv.py --res 224       # paper res
  PYTHONPATH=src python examples/serve_conv.py --artifacts DIR # warm demo
"""

import argparse
import tempfile

import numpy as np

import jax

from repro.models import cnn
from repro.runtime import inject
from repro.runtime.cache import enable_compile_cache
from repro.runtime.serve import ServeConfig, Server


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="mobilenet_v2",
                    choices=sorted(cnn.NETWORKS))
    ap.add_argument("--res", type=int, default=96)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--artifacts", default=None,
                    help="artifact dir (default: a temp dir -- pass a real "
                         "path and re-run to see the warm start)")
    args = ap.parse_args()
    enable_compile_cache()

    specs_fn, _ = cnn.NETWORKS[args.net]
    specs = specs_fn()
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=args.res)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((args.res, args.res, 3)).astype(np.float32)
          for _ in range(8)]

    art = args.artifacts or tempfile.mkdtemp(prefix="serve_conv_")
    cfg = ServeConfig(buckets=(1, 2, 4), queue_capacity=32, verbose=True)
    srv = Server(params, specs, res=args.res, algorithm="auto", config=cfg,
                 artifact_dir=art)
    s = srv.stats
    print(f"[serve_conv] {args.net}@{args.res}: "
          f"{s.artifact_warm_starts} warm / {s.artifact_cold_starts} cold "
          f"bucket plans from {art}")

    with srv:
        tickets = [srv.submit(xs[i % len(xs)], deadline_s=30.0)
                   for i in range(args.requests)]
        ys = [t.result(timeout=300) for t in tickets]
        lat = sorted(t.latency_s for t in tickets)
        print(f"[serve_conv] clean: {len(ys)} served, "
              f"p50 {lat[len(lat) // 2] * 1e3:.1f} ms, "
              f"buckets {srv.stats.bucket_batches}")

        # fault drill: a permanently failing executor in one mid layer.
        victim = sorted(srv.nets[1].plans)[len(srv.nets[1].plans) // 2]
        print(f"[serve_conv] injecting permanent executor failure into "
              f"layer {victim!r} ...")
        inject.install_on_server(srv, inject.ExecutorRaise(victim))
        tickets = [srv.submit(xs[i % len(xs)]) for i in range(args.requests)]
        ys2 = [t.result(timeout=300) for t in tickets]

    s = srv.stats.snapshot()
    print(f"[serve_conv] drill: {len(ys2)} served through the fault -- "
          f"retries={s['retries']}, replacements={s['replacements']}, "
          f"failed={s['failed']}, dropped={s['in_flight']}")
    err = max(float(np.max(np.abs(ys2[i] - ys[i]))
                    / (np.max(np.abs(ys[i])) + 1e-9))
              for i in range(len(ys2)))
    print(f"[serve_conv] parity vs pre-fault outputs: "
          f"max rel err {err:.2e}")
    assert s["in_flight"] == 0 and s["failed"] == 0 and err < 2e-3


if __name__ == "__main__":
    main()
