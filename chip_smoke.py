"""Smoke test of the served convolution path on a TPU, in one process.

    python chip_smoke.py [--seed 0]      # one chip: the phases below
    python chip_smoke.py --chips 4       # four chips: the mesh path only

One chip, with random weights drawn from --seed:

  served_auto     Server over MobileNet-v2 at 224 (algorithm="auto",
                  buckets 1 and 8, jitted dispatch), 16 requests;
  served_pallas   the same server with algorithm="pallas_winograd": the
                  region-wise Pallas kernels, whose per-layer placement must
                  survive warmup and traffic unchanged;
  dense_vgg16     VGG-16 at 224, batch 1, compile(algorithm=
                  "pallas_winograd") under jax.jit.

Four chips (--chips 4), MobileNet-v2 over make_data_mesh(4):

  mesh_data       partition="data" at batch 8;
  mesh_spatial    partition="spatial" at batch 1, resolution 512;
  mesh_server     Server(mesh=) with every bucket sharded;

each against the same network's unsharded one-chip plan.

Every output is compared with the plain float32 reference,
`cnn.cnn_forward(..., algorithm="im2col")` at "highest" matmul precision on
the same device: the largest |y - ref| over the largest |ref| of a phase's
logits must stay within REL_ERR_BOUND. A served phase also requires zero
failures, replacements and jit fallbacks, nothing in flight, and jitted
dispatches. Any failed check or exception exits non-zero. One line per
phase goes to stdout; the last line is the JSON result.

With no TPU (e.g. JAX_PLATFORMS=cpu), or without the repository's `src/`
next to this file, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from collections import Counter

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: Bound on max|y - ref| / max|ref| over a phase's logits, against the
#: float32 im2row reference at "highest" matmul precision.
REL_ERR_BOUND = 5e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(chips: int):
    """The device list, or SmokeFailure when JAX finds no TPU (or too few
    chips, or compiled Pallas is not the default)."""
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX found only {devs[0].platform} devices; this smoke "
          f"test runs on a TPU")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} TPU chips, JAX found {len(devs)}")
    try:
        from repro.kernels.runtime import default_interpret
    except ImportError as e:
        raise SmokeFailure(f"the repro package is not importable from "
                           f"{ROOT}/src ({e})") from e
    check(not default_interpret(),
          "Pallas would run in interpret mode on this TPU backend")
    return devs


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------

def network(name: str, res: int, seed: int):
    import jax
    from repro.models import cnn
    specs = cnn.NETWORKS[name][0]()
    params = cnn.init_cnn(jax.random.key(seed), specs, 3, res=res)
    return specs, params


def inputs(seed: int, n: int, res: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, res, res, 3)).astype(np.float32)


def reference(params, specs, x) -> np.ndarray:
    """Plain float32 im2row forward at "highest" matmul precision."""
    import jax
    from repro.models import cnn
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, v: cnn.cnn_forward(p, v, specs,
                                                  algorithm="im2col"))
        return np.asarray(fn(params, x))


def rel_err(y, ref) -> float:
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    check(y.shape == ref.shape, f"output shape {y.shape} != {ref.shape}")
    check(bool(np.all(np.isfinite(y))), "non-finite output")
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


def within_bound(phase: str, err: float) -> None:
    check(err <= REL_ERR_BOUND,
          f"{phase}: max relative logit error {err!r} exceeds "
          f"{REL_ERR_BOUND}")


def is_pallas(executor: str) -> bool:
    """Whether a plan's executor (composite blocks join theirs with '+')
    runs a Pallas kernel."""
    return any(e.startswith("pallas_") or e.endswith("_streamed")
               for e in executor.split("+"))


def executors(net) -> dict[str, str]:
    return {nid: p.describe()["executor"] for nid, p in net.plans.items()}


def census(execs: dict[str, str]) -> dict[str, int]:
    return dict(sorted(Counter(execs.values()).items()))


def halo_eligible(node) -> bool:
    """A stride-1 k > 1 conv-family node without a residual add: the
    layers a spatial partition keeps row-local with a halo exchange."""
    a = node.attrs
    return (node.op in ("conv2d", "separable", "inverted_residual")
            and a.get("kh", a.get("k", 1)) > 1
            and tuple(a["stride"]) == (1, 1) and not a.get("residual"))


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def served_phase(phase: str, algorithm: str, *, seed: int, res: int = 224,
                 n_requests: int = 16, buckets=(1, 8), mesh=None,
                 partition: str | None = None) -> None:
    """Serve MobileNet-v2: a burst of half the requests (the big bucket),
    then the rest one at a time (the small bucket)."""
    from repro.runtime.serve import ServeConfig, Server
    specs, params = network("mobilenet_v2", res, seed)
    xs = inputs(seed + 1, n_requests, res)
    ref = reference(params, specs, xs)

    t0 = time.perf_counter()
    srv = Server(params, specs, res=res, algorithm=algorithm,
                 config=ServeConfig(buckets=buckets, batch_wait_s=0.05,
                                    verbose=False),
                 artifact_dir=None, mesh=mesh, partition=partition)
    placed = {b: executors(srv.nets[b]) for b in srv.buckets}
    srv.start()                                       # warmup included
    setup_s = time.perf_counter() - t0
    half = n_requests // 2
    tickets = [srv.submit(x) for x in xs[:half]]
    ys = [t.result(timeout=600) for t in tickets]
    ys += [srv.submit(x).result(timeout=600) for x in xs[half:]]
    srv.stop()
    st = srv.stats.snapshot()
    served = {b: executors(srv.nets[b]) for b in srv.buckets}

    for key in ("failed", "in_flight", "replacements", "jit_fallbacks"):
        check(st[key] == 0, f"{phase}: stats.{key} = {st[key]}")
    check(st["jit_dispatches"] > 0, f"{phase}: no jitted dispatch")
    check(st["completed"] == n_requests,
          f"{phase}: {st['completed']} of {n_requests} requests completed")
    check(sorted(map(int, st["bucket_batches"])) == sorted(srv.buckets),
          f"{phase}: buckets served {st['bucket_batches']}, want "
          f"{srv.buckets}")
    for b in srv.buckets:
        moved = {n: (placed[b][n], served[b][n]) for n in placed[b]
                 if is_pallas(placed[b][n]) and served[b][n] != placed[b][n]}
        check(not moved, f"{phase}: bucket {b} lost its Pallas executors "
                         f"{moved}")
    if mesh is not None:
        check(len(st["sharded_buckets"]) == len(srv.buckets),
              f"{phase}: sharded buckets {st['sharded_buckets']} of "
              f"{srv.buckets}")
    err = rel_err(np.stack(ys), ref)
    execs = served[srv.buckets[0]]
    report(phase, network="mobilenet_v2", res=res, algorithm=algorithm,
           buckets=list(srv.buckets), requests=n_requests,
           compile_and_warmup_s=setup_s,
           bucket_batches=st["bucket_batches"],
           jit_dispatches=st["jit_dispatches"],
           sharded_buckets=st["sharded_buckets"],
           pallas_layers=sum(map(is_pallas, execs.values())),
           executors=census(execs), max_rel_err=err)
    within_bound(phase, err)
    if algorithm == "pallas_winograd":
        check(any(map(is_pallas, execs.values())),
              f"{phase}: no layer runs a Pallas kernel")


def dense_phase(phase: str, *, seed: int, res: int = 224) -> None:
    """VGG-16 through compile(algorithm="pallas_winograd") and jax.jit."""
    import jax
    import jax.numpy as jnp
    from repro.core.compile import compile as compile_network
    specs, params = network("vgg16", res, seed)
    x = jnp.asarray(inputs(seed + 2, 1, res))
    ref = reference(params, specs, x)
    t0 = time.perf_counter()
    net = compile_network(params, specs, res=res, batch=1,
                          algorithm="pallas_winograd")
    plan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fn = jax.jit(net.apply).lower(x).compile()
    compile_s = time.perf_counter() - t0
    y = fn(x)
    execs = executors(net)
    err = rel_err(y, ref)
    report(phase, network="vgg16", res=res, algorithm="pallas_winograd",
           plan_s=plan_s, compile_s=compile_s,
           pallas_layers=sum(map(is_pallas, execs.values())),
           executors=census(execs), max_rel_err=err)
    within_bound(phase, err)
    check(all(map(is_pallas, execs.values())),
          f"{phase}: a 3x3 layer left the Pallas kernel: {census(execs)}")


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------

def mesh_phase(phase: str, partition: str, *, seed: int, res: int,
               batch: int, mesh) -> None:
    """One partitioned MobileNet-v2 plan against its unsharded one-chip
    plan and the reference."""
    import jax
    import jax.numpy as jnp
    from repro.core.compile import compile as compile_network
    specs, params = network("mobilenet_v2", res, seed)
    x = jnp.asarray(inputs(seed + 3, batch, res))
    ref = reference(params, specs, x)
    one = compile_network(params, specs, res=res, batch=batch)
    y_one = np.asarray(jax.jit(one.apply)(x))
    t0 = time.perf_counter()
    net = compile_network(params, specs, res=res, batch=batch, mesh=mesh,
                          partition=partition)
    rec = net.partition
    check(rec["degraded"] is None, f"{phase}: partition degraded: "
                                   f"{rec['degraded']}")
    check(rec["num_shards"] == mesh.size,
          f"{phase}: {rec['num_shards']} shards on a {mesh.size}-chip mesh")
    y = net.apply(x)
    jax.block_until_ready(y)
    setup_s = time.perf_counter() - t0
    spans = len(y.sharding.device_set)
    check(spans == mesh.size,
          f"{phase}: output spans {spans} devices, want {mesh.size}")
    modes = rec.get("modes", {})
    if partition == "spatial":
        eligible = [n.id for n in net.graph if halo_eligible(n)]
        missing = [n for n in eligible if modes.get(n) != "halo"]
        check(eligible and not missing,
              f"{phase}: stride-1 layers without a halo: {missing}")
    err_one = rel_err(y, y_one)
    err_ref = rel_err(y, ref)
    report(phase, network="mobilenet_v2", res=res, batch=batch,
           partition=partition, num_shards=rec["num_shards"],
           degraded=rec["degraded"], output_devices=spans,
           halo_layers=sum(m == "halo" for m in modes.values()),
           compile_and_first_run_s=setup_s,
           max_rel_err_vs_one_chip=err_one, max_rel_err=err_ref)
    within_bound(phase, err_one)
    within_bound(phase, err_ref)


# ---------------------------------------------------------------------------

def run(chips: int, seed: int) -> list:
    devs = require_tpu(chips)
    from repro.runtime.cache import enable_compile_cache
    enable_compile_cache()
    if chips == 1:
        served_phase("served_auto", "auto", seed=seed)
        gc.collect()
        served_phase("served_pallas", "pallas_winograd", seed=seed)
        gc.collect()
        dense_phase("dense_vgg16", seed=seed)
    else:
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(chips)
        mesh_phase("mesh_data", "data", seed=seed, res=224, batch=8,
                   mesh=mesh)
        gc.collect()
        mesh_phase("mesh_spatial", "spatial", seed=seed, res=512, batch=1,
                   mesh=mesh)
        gc.collect()
        served_phase("mesh_server", "auto", seed=seed, n_requests=8,
                     buckets=(4, 8), mesh=mesh, partition="data")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path on four chips")
    args = ap.parse_args(argv)
    try:
        devs = run(args.chips, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
