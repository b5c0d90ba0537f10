"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json`) names a configuration and a traffic mix, each a
file of its own. One run, in one process:

  1. finds the TPU, or exits 1 with no result (there is no CPU fallback);
  2. turns on JAX's persistent compile cache in the checkout;
  3. draws the weights on the device from the seed, in one jitted call,
     and the pool of input images on the host;
  4. builds the program's `Server` with the traffic's buckets and starts
     it as users do (plan, compile, warm-up);
  5. drives the traffic: a warm-up share, then the measured window;
  6. frees the server and checks every answer against the plain float32
     reference (`bench/check.py`);
  7. prints the metrics of the cell: the end-to-end ones, or with
     `--trace 1` the per-layer ones read from a profiler trace of the
     window, which is then at most `TRACE_WINDOW_S` long, after a
     `bench nodes` line (`trace_reduce.summary`: host phases, and device
     time and roofline share per program node). The numbers compared go
     last on stderr, and the result is the last line on stdout.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import check, flops, load, manifest, trace_reduce  # noqa: E402

#: images per block of the reference
REF_BLOCK = 8
#: the input images' grid of colours (see `images`)
IMAGE_GRID = 4
#: the persistent compile cache's size where the environment sets none
CACHE_MAX_BYTES = 1 << 30
#: the longest traced window: under the profiler the runtime's layout of
#: each input batch takes about 70 ms, and stopping and reading the trace
#: take three to four seconds a traced second (v5e host), so a traced
#: window of 51 s made a run of 310 s
TRACE_WINDOW_S = 10.0


class BenchError(Exception):
    """A run that cannot produce a result: no chip, no program, bad files."""


def note(what: str, **fields) -> None:
    """One earlier line of the run's story on stdout."""
    print(f"bench {what} " + json.dumps(fields, default=float), flush=True)


def ms(seconds: np.ndarray, q: float) -> float | None:
    """The q-th percentile of `seconds`, in ms; None when empty."""
    return float(np.percentile(seconds, q)) * 1e3 if len(seconds) else None


def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    try:
        peak = flops.peaks(devs[0].device_kind)
    except KeyError as e:
        raise BenchError(str(e)) from None
    return devs, peak


def import_program():
    try:
        return SimpleNamespace(
            cnn=importlib.import_module("repro.models.cnn"),
            serve=importlib.import_module("repro.runtime.serve"),
            cache=importlib.import_module("repro.runtime.cache"))
    except ImportError as e:
        raise BenchError(f"the program is not importable from "
                         f"{ROOT}/src ({e})") from None


def seed_key(seed: int):
    """A JAX key from a seed of any size."""
    import jax
    word = np.random.SeedSequence(seed).generate_state(1)[0]
    return jax.random.key(int(word) & 0x7FFFFFFF)


class CompileLog:
    """Backend compiles and persistent-cache hits, with their times."""

    def __init__(self):
        import jax
        self.compiles: list[tuple[float, float]] = []
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), secs))

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t, _ in self.compiles)


class Marks:
    """What the generator calls as the window opens and closes."""

    def __init__(self, server, annotate):
        self.server, self.annotate = server, annotate
        self.t_open = self.t_close = None
        self.stats_open = self.stats_close = None
        self._ann = None

    def open(self) -> None:
        self.t_open = time.perf_counter()
        self.stats_open = self.server.stats.snapshot()
        self._ann = self.annotate("bench.window")
        self._ann.__enter__()

    def close(self) -> None:
        self._ann.__exit__(None, None, None)
        self.t_close = time.perf_counter()
        self.stats_close = self.server.stats.snapshot()


def batches_between(a: dict, b: dict) -> dict[int, int]:
    """Batches dispatched per bucket between two stats snapshots."""
    return {int(k): v - a["bucket_batches"].get(k, 0)
            for k, v in b["bucket_batches"].items()
            if v - a["bucket_batches"].get(k, 0)}


def reference_logits(ref_mod, cfg, params, pool, images,
                     round_to=None) -> dict:
    """Reference logits of the pool images that were answered (`round_to`:
    the control's lower precision)."""
    import jax
    fwd = jax.jit(lambda p, x: ref_mod.forward(p, x, cfg, round_to))
    images = sorted(images)
    out = {}
    for i in range(0, len(images), REF_BLOCK):
        idx = images[i:i + REF_BLOCK]
        block = pool[idx]
        if len(idx) < REF_BLOCK:          # one compiled block size
            block = np.concatenate(
                [block, np.zeros((REF_BLOCK - len(idx),) + block.shape[1:],
                                 block.dtype)])
        y = np.asarray(fwd(params, block))
        out.update(zip(idx, y[:len(idx)]))
    return out


def draw(ref_mod, cfg: dict, traffic: dict, seed: int):
    """The weights, on the device in one jitted call, the pool of input
    images and the order requests take them in, all from the seed."""
    import jax
    params = jax.jit(lambda k: ref_mod.init(k, cfg))(seed_key(seed))
    jax.block_until_ready(params)
    rng = np.random.default_rng(seed)
    pool = images(rng, traffic["pool"], cfg["res"], cfg["c_in"])
    return params, pool, rng.permutation(traffic["pool"])


def images(rng, n: int, res: int, c: int) -> np.ndarray:
    """`n` images that differ where a network looks: a 4 x 4 grid of
    random colours (unit normal) plus half as much pixel noise. Pixel noise
    alone averages out through the pools, and every image then gives
    nearly the same logits, so an answer handed to the wrong request would
    pass the check."""
    g = IMAGE_GRID
    block = -(-res // g)
    base = rng.standard_normal((n, g, g, c), dtype=np.float32)
    up = np.repeat(np.repeat(base, block, axis=1), block, axis=2)
    return up[:, :res, :res] + 0.5 * rng.standard_normal(
        (n, res, res, c), dtype=np.float32)


def setup(cfg: dict, traffic: dict, seed: int) -> SimpleNamespace:
    """Weights, input pool and a started server for one configuration
    under one traffic mix's buckets. The chip has been found."""
    prog = import_program()
    import jax

    prog.cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if jax.config.jax_compilation_cache_max_size < 0:
        # an executable with the weights baked in is written anew for
        # every seed; the cap keeps VGG-16's (1.2 GB) off the disk
        jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    clog = CompileLog()
    ref_mod = importlib.import_module(f"bench.reference.{cfg['reference']}")

    t = time.perf_counter()
    params, pool, order = draw(ref_mod, cfg, traffic, seed)
    specs = prog.cnn.NETWORKS[cfg["network"]][0]()
    want = jax.eval_shape(lambda: prog.cnn.init_cnn(
        jax.random.key(0), specs, cfg["c_in"], res=cfg["res"]))
    if (jax.tree.structure(want) != jax.tree.structure(params)
            or any(a.shape != b.shape for a, b in
                   zip(jax.tree.leaves(want), jax.tree.leaves(params)))):
        raise BenchError(f"the program's {cfg['network']!r} does not have "
                         f"the sizes of configuration {cfg['name']!r}")
    weights_s = time.perf_counter() - t

    t = time.perf_counter()
    srv = prog.serve.Server(
        params, specs, res=cfg["res"], c_in=cfg["c_in"],
        algorithm=cfg["algorithm"], compute_dtype=cfg["compute_dtype"],
        config=prog.serve.ServeConfig(buckets=tuple(traffic["buckets"])),
        artifact_dir=None)
    plan_s = time.perf_counter() - t
    t = time.perf_counter()
    n0, c0 = len(clog.compiles), clog.hits
    srv.start()
    start_s = time.perf_counter() - t
    note("setup", weights_and_pool_s=weights_s, server_plan_s=plan_s,
         server_start_s=start_s,
         start_backend_compiles=len(clog.compiles) - n0,
         start_backend_compile_s=sum(s for _, s in clog.compiles[n0:]),
         start_cache_hits=clog.hits - c0)
    return SimpleNamespace(prog=prog, srv=srv, params=params, pool=pool,
                           order=order, ref_mod=ref_mod, clog=clog)


def run(workload: str, seed: int, seconds: float, trace: bool,
        cell_files=None) -> tuple[dict, dict]:
    """One run; returns (result line, checks). `cell_files` (config,
    traffic) stands in for the files `BENCHMARK.json` names."""
    man = manifest.load()
    cell = manifest.workload(man, workload) if cell_files is None else \
        {"name": workload, "chips": 1}
    cfg, traffic = cell_files or (manifest.config(cell["config"]),
                                  manifest.traffic(cell["traffic"]))
    devs, peak = require_chips(cell["chips"])
    import jax
    s = setup(cfg, traffic, seed)
    prog, srv, params, pool, ref_mod, clog = (
        s.prog, s.srv, s.params, s.pool, s.ref_mod, s.clog)

    tdir = None
    annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        # level 1 keeps annotations and drops the runtime's per-chunk
        # events (a million host-to-device transpose chunks a second at
        # level 2, which slowed the served path tenfold)
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation

    marks = Marks(srv, annotate)
    submit = load.Submitter(srv, pool, s.order, prog.serve.QueueFullError,
                            annotate)
    # what set-up made lives to the end of the run: out of the collector's
    # reach, a full collection no longer walks it and stalls the served
    # path for some 75 ms (v5e host) at a time
    gc.collect()
    gc.freeze()
    t_traffic = time.perf_counter()
    window = load.GENERATORS[traffic["kind"]](
        submit, traffic, seconds, np.random.default_rng([seed, 1]),
        annotate, marks)
    gc.unfreeze()
    setup_s = marks.t_open - T_PROCESS
    dev_mem = [d.memory_stats() or {} for d in devs]
    bytes_in_use = max(m.get("bytes_in_use", 0) for m in dev_mem)
    peak_bytes = max(m.get("peak_bytes_in_use", 0) for m in dev_mem)

    reduction = None
    if trace:
        t = time.perf_counter()
        jax.profiler.stop_trace()
        stop_s = time.perf_counter() - t
        reduction = trace_reduce.reduce(trace_reduce.load(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        note("trace", stop_s=stop_s,
             read_s=time.perf_counter() - t - stop_s)
    srv.stop()
    stats = srv.stats.snapshot()
    del srv
    gc.collect()

    in_window = window.due_in_window()
    done_in_window = [r for r in window.requests
                      if r.ticket is not None and r.ticket.status == "ok"
                      and window.t0 <= r.ticket.finished_at <= window.t1]
    lateness = np.array([r.submitted - r.due for r in in_window])
    latencies = np.array([r.ticket.finished_at - r.due for r in in_window
                          if r.ticket is not None and r.ticket.done()
                          and r.ticket.status == "ok"])
    note("window", seconds=window.t1 - window.t0,
         traffic_warmup_s=window.t0 - t_traffic,
         requests_due=len(in_window), answered_in_window=len(done_in_window),
         compiles_in_window=clog.between(marks.t_open, marks.t_close),
         generator_late_ms_p50=ms(lateness, 50),
         generator_late_ms_p99=ms(lateness, 99),
         generator_late_ms_max=ms(lateness, 100),
         batches=batches_between(marks.stats_open, marks.stats_close),
         latency_ms_p50=ms(latencies, 50), latency_ms_p99=ms(latencies, 99),
         bytes_in_use=bytes_in_use, peak_bytes_in_use=peak_bytes,
         memory_stats=dev_mem[0])
    note("server", **{k: stats[k] for k in (
        "admitted", "rejected", "completed", "failed", "in_flight",
        "jit_dispatches", "dispatched_ahead", "jit_fallbacks",
        "replacements", "retries", "bucket_batches")})

    t = time.perf_counter()
    answered = {r.image for r in window.requests
                if r.ticket is not None and r.ticket.done()}
    ref = reference_logits(ref_mod, cfg, params, pool, answered)
    nums = check.numbers(window.requests, ref)
    correct, checks = check.verdict(nums, cfg["limits"])
    note("reference", seconds=time.perf_counter() - t, images=len(ref))

    ctx = SimpleNamespace(
        config=cfg, traffic=traffic, peak=peak,
        layers=ref_mod.layers(cfg), setup_s=setup_s,
        window_s=window.t1 - window.t0, completed=len(done_in_window),
        latencies_s=latencies, bytes_in_use=bytes_in_use,
        trace=reduction,
        batches=batches_between(marks.stats_open, marks.stats_close))
    if reduction is not None:
        note("nodes", **trace_reduce.summary(
            reduction, flops.least_time_by_node(ctx.layers, ctx.batches,
                                                peak)))
    metrics = {}
    for m in manifest.metrics(man, workload, trace):
        v = manifest.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    failed = sum(r.refused or (r.ticket.done() and r.ticket.status != "ok")
                 for r in in_window)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(in_window),
              "failed": int(failed), "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["checks"] = checks
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, checks = run(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except BenchError as e:
        print(f"bench: no result: {e}", file=sys.stderr, flush=True)
        return 1
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
