"""Multi-device scaling curve for sharded NetworkPlan execution.

Measures compiled-plan apply() at 1/2/4/8 devices -- each device count in
a FRESH subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=N
(the way tests/test_multidevice.py runs) -- over two partitionings:

  * batch-sharded (partition="data"): a small VGG + MobileNet-v2 style
    ladder (dense conv, separable block, inverted residual, stride-2
    reduction) at a FIXED global batch -- strong scaling; weights
    replicate, the batch dim splits across the mesh.
  * halo-sharded (partition="spatial"): a stride-1 conv ladder at high
    resolution, H split across the mesh with ppermute halo exchange,
    gated on <= 1e-5 relative error against the unsharded oracle.

Normalization -- read this before comparing numbers: forced host devices
on a single physical core execute the shard_map program's per-shard work
SERIALLY, so wall-clock alone cannot show a speedup on this box. The
curve therefore reports raw wall seconds per apply AND the
serialized-forced-host-devices normalized throughput
    throughput(N) = N * global_batch / wall_N
which models N physical devices each doing its measured per-shard slice
concurrently. On real multi-core/multi-chip hardware wall_N itself drops;
here the signal is that per-shard partitioned work + collectives do not
blow up wall_N as N grows. The gates (strictly increasing throughput,
>= 3x aggregate at 8 devices) bound exactly that overhead:
speedup(8) >= 3 iff wall_8 <= (8/3) * wall_1.

The 8-device worker also round-trips the version-5 artifact: a warm
compile(artifact=, mesh=) must restore the recorded partition without
re-deciding (one artifact hit, zero misses, identical partition record).

  PYTHONPATH=src:. python -m benchmarks.scaling --out BENCH_PR9.json
  PYTHONPATH=src:. python -m benchmarks.scaling --quick --out ...   # CI
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_MARK = "SCALING_JSON "
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _batch_ladder():
    from repro.models import cnn
    return [cnn.Conv("c1", 3, 3, 16),
            cnn.SeparableConv("sep1", 3, 24),
            cnn.InvertedResidual("ir1", 24, expand=2),
            cnn.Conv("c2", 3, 3, 32, stride=2),
            cnn.GlobalAvgPool(),
            cnn.Dense("fc", 10, relu=False)]


def _halo_ladder():
    from repro.models import cnn
    return [cnn.Conv("h1", 3, 3, 16),
            cnn.Conv("h2", 5, 5, 16),
            cnn.Conv("h3", 3, 3, 32),
            cnn.GlobalAvgPool(),
            cnn.Dense("fc", 10, relu=False)]


# ---------------------------------------------------------------------------
# worker: one device count, fresh process
# ---------------------------------------------------------------------------

def _time_apply(fn, x, *, warmup: int, iters: int) -> float:
    from benchmarks.common import time_jitted
    return time_jitted(fn, x, warmup=warmup, iters=iters)


def _worker(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import compile as C
    from repro.core.plan import clear_plan_cache, plan_cache_info
    from repro.launch.mesh import make_data_mesh
    from repro.models import cnn

    n = args.devices
    assert jax.device_count() >= n, (jax.device_count(), n)
    mesh = make_data_mesh(n)
    out: dict = {"devices": n}

    def sharded_callable(net):
        return net.apply if net.is_sharded() else jax.jit(net.apply)

    # -- batch-sharded, fixed global batch (strong scaling) -----------------
    g = args.global_batch
    specs = _batch_ladder()
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=args.res)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (g, args.res, args.res, 3)).astype(np.float32))
    ref = np.asarray(jax.jit(
        C.compile(params, specs, res=args.res, batch=g).apply)(x))
    net = C.compile(params, specs, res=args.res, batch=g, mesh=mesh)
    fn = sharded_callable(net)
    y = np.asarray(fn(x))
    rel = float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))
    wall = _time_apply(fn, x, warmup=args.warmup, iters=args.iters)
    out["batch_sharded"] = {
        "num_shards": net.partition["num_shards"],
        "degraded": net.partition["degraded"],
        "global_batch": g, "res": args.res,
        "wall_s": wall, "rel_err": rel,
        "throughput_img_s": n * g / wall}

    # -- halo-sharded, high-resolution stride-1 ladder ----------------------
    hspecs = _halo_ladder()
    hparams = cnn.init_cnn(jax.random.key(1), hspecs, 3, res=args.halo_res)
    hx = jnp.asarray(np.random.default_rng(1).standard_normal(
        (args.halo_batch, args.halo_res, args.halo_res, 3))
        .astype(np.float32))
    href = np.asarray(jax.jit(
        C.compile(hparams, hspecs, res=args.halo_res,
                  batch=args.halo_batch).apply)(hx))
    hnet = C.compile(hparams, hspecs, res=args.halo_res,
                     batch=args.halo_batch, mesh=mesh, partition="spatial")
    hfn = sharded_callable(hnet)
    hy = np.asarray(hfn(hx))
    hrel = float(np.max(np.abs(hy - href)) / np.max(np.abs(href)))
    hwall = _time_apply(hfn, hx, warmup=args.warmup, iters=args.iters)
    out["halo_sharded"] = {
        "num_shards": hnet.partition["num_shards"],
        "degraded": hnet.partition["degraded"],
        "modes": hnet.partition.get("modes"),
        "batch": args.halo_batch, "res": args.halo_res,
        "wall_s": hwall, "rel_err": hrel,
        "throughput_img_s": n * args.halo_batch / hwall}

    # -- artifact round-trip: warm start restores the partition -------------
    if args.artifact:
        clear_plan_cache()
        cold = C.compile(params, specs, res=args.res, batch=g, mesh=mesh,
                         artifact=args.artifact)
        cold_info = plan_cache_info()
        clear_plan_cache()
        warm = C.compile(params, specs, res=args.res, batch=g, mesh=mesh,
                         artifact=args.artifact)
        info = plan_cache_info()
        wy = np.asarray(sharded_callable(warm)(x))
        out["warm_restore"] = {
            "cold_misses": cold_info["artifact_misses"],
            "warm_hits": info["artifact_hits"],
            "warm_misses": info["artifact_misses"],
            "partition_match": warm.partition == cold.partition,
            "rel_err": float(np.max(np.abs(wy - ref)) / np.max(np.abs(ref))),
        }
    return out


# ---------------------------------------------------------------------------
# parent: spawn one worker per device count, gate, emit the artifact
# ---------------------------------------------------------------------------

def _spawn(n: int, args, artifact: str | None) -> dict:
    env = dict(os.environ)
    # forced host devices, not chips: pin the CPU backend so a worker never
    # reaches for (or waits on) an accelerator the parent's host may hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.pathsep.join(
        [_ROOT, os.path.join(_ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    cmd = [sys.executable, "-m", "benchmarks.scaling", "--worker",
           "--devices", str(n), "--global-batch", str(args.global_batch),
           "--res", str(args.res), "--halo-batch", str(args.halo_batch),
           "--halo-res", str(args.halo_res), "--iters", str(args.iters),
           "--warmup", str(args.warmup)]
    if artifact:
        cmd += ["--artifact", artifact]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"scaling worker (devices={n}) failed:\n"
                           f"{out.stderr[-3000:]}")
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith(_MARK))
    return json.loads(line[len(_MARK):])


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_PR9.json")
    ap.add_argument("--quick", action="store_true",
                    help="CI variant: fewer timing iters, smaller halo "
                         "resolution; the device counts and gates are "
                         "identical")
    ap.add_argument("--device-counts", type=int, nargs="*",
                    default=[1, 2, 4, 8])
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--res", type=int, default=32)
    ap.add_argument("--halo-batch", type=int, default=2)
    ap.add_argument("--halo-res", type=int, default=None,
                    help="default 64 (32 with --quick)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--artifact", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.halo_res is None:
        args.halo_res = 32 if args.quick else 64
    if args.iters is None:
        args.iters = 2 if args.quick else 5
    if args.warmup is None:
        args.warmup = 1 if args.quick else 2

    if args.worker:
        print(_MARK + json.dumps(_worker(args)), flush=True)
        return

    from benchmarks.common import bench_metadata

    t0 = time.time()
    art_dir = os.path.join(os.path.dirname(os.path.abspath(args.out)) or ".",
                           "results")
    os.makedirs(art_dir, exist_ok=True)
    art = os.path.join(art_dir, "scaling_plan_b8.npz")
    curve = []
    for n in args.device_counts:
        row = _spawn(n, args, art if n == max(args.device_counts) else None)
        b, h = row["batch_sharded"], row["halo_sharded"]
        print(f"devices={n}: batch wall {b['wall_s'] * 1e3:7.2f} ms  "
              f"thr {b['throughput_img_s']:8.1f} img/s  "
              f"rel {b['rel_err']:.2e} | halo wall "
              f"{h['wall_s'] * 1e3:7.2f} ms  rel {h['rel_err']:.2e}",
              flush=True)
        curve.append(row)

    thr = [r["batch_sharded"]["throughput_img_s"] for r in curve]
    warm = next((r["warm_restore"] for r in curve
                 if "warm_restore" in r), {})
    gates = {
        "batch_parity_1e5": all(
            r["batch_sharded"]["rel_err"] <= 1e-5 for r in curve),
        "halo_parity_1e5": all(
            r["halo_sharded"]["rel_err"] <= 1e-5 for r in curve),
        "throughput_strictly_increasing": all(
            b > a for a, b in zip(thr, thr[1:])),
        "speedup_max_dev_ge_3x": thr[-1] >= 3 * thr[0],
        "warm_restores_partition": bool(
            warm and warm["warm_hits"] == 1 and warm["warm_misses"] == 0
            and warm["partition_match"] and warm["rel_err"] <= 1e-5),
    }
    gates["all_pass"] = all(gates.values())
    report = {
        "benchmark": "sharded NetworkPlan scaling curve (PR 9)",
        "meta": bench_metadata(),
        "normalization": (
            "forced host devices on one physical core run shard_map "
            "per-shard work serially; throughput_img_s = devices * "
            "global_batch / wall_s models N physical devices running "
            "their measured per-shard slice concurrently. Raw wall_s is "
            "reported unmodified; the gates bound partitioning + "
            "collective overhead (speedup(8) >= 3x iff wall_8 <= 8/3 * "
            "wall_1), not physical parallel speedup on this box."),
        "config": {"device_counts": args.device_counts,
                   "global_batch": args.global_batch, "res": args.res,
                   "halo_batch": args.halo_batch,
                   "halo_res": args.halo_res, "iters": args.iters,
                   "warmup": args.warmup, "quick": args.quick},
        "curve": curve,
        "speedup_vs_1dev": [t / thr[0] for t in thr],
        "gates": gates,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    status = "PASS" if gates["all_pass"] else "FAIL"
    print(f"\n[{status}] gates: {gates}")
    print(f"wrote {args.out} in {time.time() - t0:.0f}s")
    if not gates["all_pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
