"""Observability overhead + decomposition benchmark (BENCH_PR10).

  PYTHONPATH=src python -m benchmarks.observe --quick --out BENCH_PR10.json

One MobileNet-v2 server (eager supervised dispatch, so per-layer hooks
fire) serves the same request stream twice per round, interleaved:
profiler DISABLED then ENABLED. Interleaving makes the A/B
machine-relative -- thermal drift and background noise hit both arms --
so the emitted metrics (overhead in PERCENT, decomposition residual in
percent, boolean gates) compare across machines, and CI can gate a fresh
run against the committed baseline (benchmarks/regress.py).

The enabled arm's trace is then audited: for every request, the four
intervals (queue_wait -> batch_formation -> its batch's dispatch ->
respond) must tile [submit, finish], so their sum is checked against the
independently measured ticket latency (max residual gated < 1%). The
chrome://tracing export and the process metrics snapshot are written
next to the JSON for CI artifact upload.

Artifact format "repro.observe/v1":
    p50_disabled_ms / p50_enabled_ms / overhead_pct
    decomposition: {max_residual_pct, per_request: [...]}
    span_table: named spans of one request (EXPERIMENTS.md table)
    trace_events: event count of the chrome export
    gates: {overhead_lt_10pct, decomposition_residual_lt_1pct,
            valid_chrome_trace, phase_spans_present}
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks.common import bench_metadata
from repro.models import cnn
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import trace as obs_trace
from repro.runtime.serve import ServeConfig, Server


def _serve_round(srv, inputs, rng, n):
    """n sequential submit/wait requests; returns latencies (s)."""
    lat = []
    for _ in range(n):
        x = inputs[int(rng.integers(len(inputs)))]
        t = srv.submit(x)
        t.result(timeout=120)
        lat.append(t.latency_s)
    return lat


def _request_decomposition(tracer):
    """Per-request [queue_wait, batch_formation, dispatch, respond]
    reconstruction from the enabled arm's spans; returns rows with the
    residual vs the span-implied latency."""
    by_rid: dict[int, dict[str, tuple[float, float]]] = {}
    for s in tracer.spans():
        rid = s.args.get("rid")
        if rid is None or s.name not in ("serve.queue_wait",
                                         "serve.batch_formation",
                                         "serve.respond"):
            continue
        by_rid.setdefault(rid, {})[s.name] = (s.t0, s.t1)
    rows = []
    for rid, parts in sorted(by_rid.items()):
        if len(parts) != 3:
            continue
        qw = parts["serve.queue_wait"]
        bf = parts["serve.batch_formation"]
        rp = parts["serve.respond"]
        latency = rp[1] - qw[0]            # finish - submit
        pieces = {"queue_wait_ms": (qw[1] - qw[0]) * 1e3,
                  "batch_formation_ms": (bf[1] - bf[0]) * 1e3,
                  "dispatch_ms": (rp[0] - bf[1]) * 1e3,
                  "respond_ms": (rp[1] - rp[0]) * 1e3}
        total = sum(pieces.values())
        resid = abs(total - latency * 1e3) / max(latency * 1e3, 1e-9) * 100
        rows.append({"rid": rid,
                     **{k: round(v, 4) for k, v in pieces.items()},
                     "latency_ms": round(latency * 1e3, 4),
                     "residual_pct": round(resid, 4)})
    return rows


def _span_table(tracer, rid):
    """The named spans of one request, plus the live phase spans of its
    batch between batch selection and the request's finish -- the
    EXPERIMENTS.md table."""
    spans = tracer.spans()
    mine = [s for s in spans if s.args.get("rid") == rid]
    if not mine:
        return []
    rows = [{"span": s.name, "ms": round((s.t1 - s.t0) * 1e3, 4)}
            for s in sorted(mine, key=lambda s: s.t0)]
    t1 = max(s.t1 for s in mine)
    bf = next((s for s in mine if s.name == "serve.batch_formation"), None)
    if bf is not None:
        rows += [{"span": s.name, "ms": round((s.t1 - s.t0) * 1e3, 4)}
                 for s in spans
                 if s.name.startswith("serve.") and "rid" not in s.args
                 and s.name != "serve.batch"
                 and bf.t0 <= s.t0 and s.t1 <= t1]
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small resolution / fewer rounds (CI)")
    ap.add_argument("--res", type=int, default=None)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--per-round", type=int, default=4)
    ap.add_argument("--out", default="BENCH_PR10.json")
    ap.add_argument("--trace-out", default=None,
                    help="chrome://tracing JSON path "
                         "(default: <out>.trace.json)")
    ap.add_argument("--metrics-out", default=None,
                    help="metrics snapshot path "
                         "(default: <out>.metrics.json)")
    args = ap.parse_args(argv)
    res = args.res or (32 if args.quick else 64)
    rounds = args.rounds or (5 if args.quick else 10)
    trace_out = args.trace_out or f"{args.out}.trace.json"
    metrics_out = args.metrics_out or f"{args.out}.metrics.json"

    print(f"[observe] MobileNet-v2 res={res}, {rounds} interleaved rounds "
          f"x {args.per_round} req/arm", flush=True)
    specs = cnn.NETWORKS["mobilenet_v2"][0]()
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=res)
    rng = np.random.default_rng(7)
    inputs = [rng.standard_normal((res, res, 3)).astype(np.float32)
              for _ in range(4)]

    obs_profile.disable()
    cfg = ServeConfig(buckets=(1, 2), jit_dispatch=False, verbose=False)
    lat_dis, lat_en = [], []
    t_start = time.time()
    with Server(params, specs, res=res, algorithm="auto",
                config=cfg) as srv:
        # warmup both arms' code paths before measuring
        _serve_round(srv, inputs, rng, 2)
        obs_profile.enable()
        _serve_round(srv, inputs, rng, 2)
        obs_profile.disable()
        for r in range(rounds):
            lat_dis += _serve_round(srv, inputs, rng, args.per_round)
            obs_profile.enable()
            lat_en += _serve_round(srv, inputs, rng, args.per_round)
            obs_profile.disable(tracing=False)   # keep spans for audit
        tracer = obs_trace.get()
        decomp = _request_decomposition(tracer)
        table_rid = decomp[-1]["rid"] if decomp else None
        span_table = _span_table(tracer, table_rid) if decomp else []
        chrome = tracer.export_chrome(trace_out)
        stats_snapshot = srv.stats.snapshot()
    obs_trace.disable()

    with open(metrics_out, "w") as f:
        json.dump(obs_metrics.snapshot_all(), f, indent=1, sort_keys=True)

    p50_dis = float(np.percentile(lat_dis, 50)) * 1e3
    p50_en = float(np.percentile(lat_en, 50)) * 1e3
    overhead = (p50_en - p50_dis) / p50_dis * 100
    max_resid = max((r["residual_pct"] for r in decomp), default=1e9)
    n_phase_spans = sum(1 for r in span_table
                        if r["span"] in ("serve.eager", "serve.await"))
    valid = (isinstance(chrome.get("traceEvents"), list)
             and len(chrome["traceEvents"]) > 0
             and all("ph" in e for e in chrome["traceEvents"]))

    doc = {
        "format": "repro.observe/v1",
        "meta": bench_metadata(),
        "network": "mobilenet_v2", "res": res,
        "rounds": rounds, "requests_per_arm": rounds * args.per_round,
        "p50_disabled_ms": round(p50_dis, 4),
        "p50_enabled_ms": round(p50_en, 4),
        "overhead_pct": round(overhead, 3),
        "decomposition": {
            "max_residual_pct": round(max_resid, 4),
            "per_request": decomp[:16],
        },
        "span_table": span_table,
        "trace_events": len(chrome["traceEvents"]),
        "trace_dropped": chrome["otherData"]["dropped_spans"],
        "serve_stats": {k: v for k, v in stats_snapshot.items()
                        if isinstance(v, int)},
        "gates": {
            "overhead_lt_10pct": overhead < 10.0,
            "decomposition_residual_lt_1pct": max_resid < 1.0,
            "valid_chrome_trace": bool(valid),
            "phase_spans_present": n_phase_spans > 0,
        },
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"[observe] p50 disabled {p50_dis:.3f} ms, enabled "
          f"{p50_en:.3f} ms -> overhead {overhead:+.2f}%", flush=True)
    print(f"[observe] decomposition max residual {max_resid:.4f}% over "
          f"{len(decomp)} requests; {len(chrome['traceEvents'])} trace "
          f"events -> {trace_out}", flush=True)
    print(f"[observe] gates: {doc['gates']}", flush=True)
    print(f"[observe] wrote {args.out} (+ {metrics_out}) in "
          f"{time.time() - t_start:.0f}s", flush=True)
    if not all(doc["gates"].values()):
        raise SystemExit(f"observe gates failed: {doc['gates']}")


if __name__ == "__main__":
    main()
