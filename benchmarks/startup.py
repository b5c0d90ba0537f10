"""Cold-plan vs warm-artifact startup: the paper's section-4 deployment
story measured end to end through the graph compiler.

For each network the harness measures
  * cold start -- compile(params, specs): lowering + fusion rewrites +
    placement + every filter transform;
  * save -- NetworkPlan.save(path) artifact emission (and the artifact
    size on disk);
  * warm start -- NetworkPlan.load(path) in this process with the plan
    caches cleared: no re-planning, no re-measuring, no filter-transform
    ops (the ship-transformed-weights path);
  * a FRESH-PROCESS reload: a subprocess loads the artifact, runs the same
    deterministic input, and must produce byte-identical output (the CI
    parity gate);
  * steady-state latency of the compiled plan vs the im2row baseline.

One process holds a chip at a time, so the parent never imports JAX: each
network's compile/save/load/timing half runs in one child, and the
fresh-process reload in a second child after the first has exited.

  PYTHONPATH=src python -m benchmarks.startup --out BENCH_PR5.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

NETWORKS = ["mobilenet_v2", "vgg16"]
_MARK = "STARTUP_JSON "
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The subprocess half of the fresh-process parity gate: load the artifact,
# run the deterministic input, print the output digest. No access to specs
# or raw params -- everything comes from the artifact.
_CHILD = r"""
import hashlib, sys
import jax.numpy as jnp
import numpy as np
from repro.core.compile import NetworkPlan
from repro.core.plan import plan_cache_info

path, res = sys.argv[1], int(sys.argv[2])
net = NetworkPlan.load(path)
x = jnp.asarray(np.random.default_rng(0).standard_normal(
    (1, res, res, 3)), jnp.float32)
y = np.asarray(net.apply(x))
info = plan_cache_info()
assert info["artifact_hits"] == 1, info
print(hashlib.sha256(y.tobytes()).hexdigest())
"""


def _child(args: list[str]) -> str:
    """Run a Python child from the repo root; its stdout."""
    env = {**os.environ, "PYTHONPATH": os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"), _ROOT,
                    os.environ.get("PYTHONPATH")) if p)}
    out = subprocess.run([sys.executable, *args], capture_output=True,
                         text=True, env=env, cwd=_ROOT)
    assert out.returncode == 0, out.stderr
    return out.stdout


def bench_startup(net: str, res: int, iters: int, warmup: int,
                  artifact_dir: str) -> dict:
    """The compile half, run in a child: cold compile, save, warm load,
    in-process parity, and steady-state timing."""
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import bench_metadata, time_jitted
    from repro.core.compile import NetworkPlan, compile as compile_network
    from repro.core.plan import clear_plan_cache, plan_cache_info
    from repro.models import cnn

    def _digest(y) -> str:
        return hashlib.sha256(np.asarray(y).tobytes()).hexdigest()

    specs = cnn.NETWORKS[net][0]()
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=res)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, res, res, 3)), jnp.float32)
    path = os.path.join(artifact_dir, f"{net}_{res}.npz")

    clear_plan_cache()
    t0 = time.perf_counter()
    plan = compile_network(params, specs, res=res, algorithm="auto")
    jax.block_until_ready(plan.weight_arrays())
    cold_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    plan.save(path)
    save_s = time.perf_counter() - t0
    artifact_bytes = os.path.getsize(path)

    clear_plan_cache()
    t0 = time.perf_counter()
    loaded = NetworkPlan.load(path)
    jax.block_until_ready(loaded.weight_arrays())
    warm_s = time.perf_counter() - t0
    assert plan_cache_info()["artifact_hits"] == 1

    # in-process parity must be bitwise; the parent checks fresh-process
    # parity against output_sha256.
    y_cold = plan.apply(x)
    y_warm = loaded.apply(x)
    assert np.array_equal(np.asarray(y_cold), np.asarray(y_warm)), \
        "save/load round-trip is not bitwise identical"

    fn_planned = jax.jit(loaded.apply)
    fn_base = jax.jit(lambda x: cnn.cnn_forward(params, x, specs,
                                                algorithm="im2col"))
    t_planned = time_jitted(fn_planned, x, warmup=warmup, iters=iters)
    t_base = time_jitted(fn_base, x, warmup=warmup, iters=iters)

    return {"network": net, "res": res,
            "cold_compile_s": cold_s, "save_s": save_s,
            "warm_load_s": warm_s, "artifact_bytes": artifact_bytes,
            "startup_speedup": cold_s / warm_s,
            "t_planned_s": t_planned, "t_im2row_s": t_base,
            "artifact": path, "output_sha256": _digest(y_cold),
            "meta": bench_metadata()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--networks", nargs="*", default=NETWORKS)
    ap.add_argument("--res", type=int, default=96,
                    help="input resolution (96 keeps the CI run in seconds; "
                         "use 224 for the paper setting)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--worker", default=None, metavar="DIR",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.worker:
        for net in args.networks:
            print(_MARK + json.dumps(bench_startup(
                net, args.res, args.iters, args.warmup, args.worker)),
                flush=True)
        return None

    rows, meta = [], None
    print("== cold compile vs warm artifact startup (compile/save/load) ==")
    print(f"{'Network':14s} {'cold(ms)':>9s} {'save(ms)':>9s} "
          f"{'warm(ms)':>9s} {'x-faster':>8s} {'MB':>6s} "
          f"{'planned(ms)':>12s} {'im2row(ms)':>11s}")
    with tempfile.TemporaryDirectory() as tmp:
        for net in args.networks:
            out = _child(["-m", "benchmarks.startup", "--worker", tmp,
                          "--networks", net, "--res", str(args.res),
                          "--iters", str(args.iters),
                          "--warmup", str(args.warmup)])
            r = json.loads(next(ln for ln in out.splitlines()
                                if ln.startswith(_MARK))[len(_MARK):])
            fresh = _child(["-c", _CHILD, r.pop("artifact"),
                            str(args.res)]).strip().splitlines()[-1]
            assert fresh == r["output_sha256"], (fresh, r["output_sha256"])
            r["fresh_process_parity"] = True
            meta = r.pop("meta")
            rows.append(r)
            print(f"{r['network']:14s} {r['cold_compile_s']*1e3:9.1f} "
                  f"{r['save_s']*1e3:9.1f} {r['warm_load_s']*1e3:9.1f} "
                  f"{r['startup_speedup']:7.1f}x "
                  f"{r['artifact_bytes']/2**20:6.1f} "
                  f"{r['t_planned_s']*1e3:12.1f} "
                  f"{r['t_im2row_s']*1e3:11.1f}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"config": "startup", "meta": meta,
                       "res": args.res, "rows": rows}, f, indent=1)
    return rows


if __name__ == "__main__":
    main()
