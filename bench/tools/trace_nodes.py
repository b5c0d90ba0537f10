"""A traced run of one cell that also reads the program's own spans and
node names (bench/trace_spans.py): the host phases of each batch and the
device time of each network node, on the chip.

    python bench/tools/trace_nodes.py --workload vgg16.offline \
        --seed 11 --seconds 10 [--keep trace.json]

It is `bench/run.py --trace 1` with `trace_spans.load` and `.reduce` in
place of the trace reduction's own, and with the per-layer metrics that
read them (`host_ms`, `h2d_ms`, `kernel_roofline`, in bench/metrics/)
beside the cell's. Before the result line it prints the host phases'
medians, the share of device time that named nodes claim, the ten nodes
with the most device time and the ops outside every node. `--keep`
writes the trace of the window's first batches as JSON, the form the
tests keep.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import manifest, trace_reduce, trace_spans  # noqa: E402
from bench import run as bench_run  # noqa: E402

#: the metrics that read the program's spans and node names
METRICS = [{"name": "host_ms", "unit": "ms"},
           {"name": "h2d_ms", "unit": "ms"},
           {"name": "kernel_roofline", "unit": "%"}]
#: batches of the window that `--keep` writes
KEEP_BATCHES = 3


@contextlib.contextmanager
def reading_spans(captured: dict):
    """bench/run.py, for the duration, reading the program's spans: the
    trace as `trace_spans.load` gives it and both reductions of it (kept
    in `captured`), and METRICS beside the cell's per-layer metrics."""
    load, reduce, metrics = (trace_reduce.load, trace_reduce.reduce,
                             manifest.metrics)

    def load_all(tdir):
        captured["trace"] = trace_spans.load(tdir)
        return captured["trace"]

    def reduce_all(trace):
        captured["reduction"] = {**reduce(trace), **trace_spans.reduce(trace)}
        return captured["reduction"]

    def metrics_all(man, workload, trace):
        return metrics(man, workload, trace) + (METRICS if trace else [])

    trace_reduce.load, trace_reduce.reduce = load_all, reduce_all
    manifest.metrics = metrics_all
    try:
        yield
    finally:
        trace_reduce.load, trace_reduce.reduce = load, reduce
        manifest.metrics = metrics


def first_batches(trace: dict, n: int) -> dict:
    """The trace cut to its window's first `n` batches, with the window
    annotation moved to cover just them."""
    w0, w1 = next((a, b) for name, a, b in trace["host"]
                  if name == trace_reduce.WINDOW)
    spans = sorted((s for s in trace["spans"] if s[0] == trace_spans.BATCH
                    and w0 <= s[1] and s[2] <= w1), key=lambda s: s[1])[:n]
    if not spans:
        raise ValueError("no serve.batch inside the window")
    t0, t1 = spans[0][1], spans[-1][2]

    def cut(events):
        return [e for e in events if e[2] > t0 and e[1] < t1]

    host = [e for e in cut(trace["host"]) if e[0] != trace_reduce.WINDOW]
    return {"devices": [{"ops": cut(d["ops"]), "op_nodes": d["op_nodes"]}
                        for d in trace["devices"]],
            "host": host + [[trace_reduce.WINDOW, t0, t1]],
            "spans": cut(trace["spans"])}


def summary(reduction: dict) -> dict:
    """The host phases' medians (ms), the share of device time that the
    named nodes claim, the ten nodes with the most device time and the ops
    outside every node (seconds)."""
    rows = reduction["serve_batches"]
    phases = sorted({k for r in rows for k in r})
    nodes = reduction["node_device_s"]
    claimed = sum(nodes.values())
    return {
        "batches": len(rows),
        "phase_ms_p50": {k: 1e3 * statistics.median(r.get(k, 0.0)
                                                    for r in rows)
                         for k in phases} if rows else {},
        "node_share": (100.0 * claimed / reduction["busy_s"]
                       if reduction["busy_s"] else None),
        "nodes": sorted(nodes.items(), key=lambda kv: -kv[1])[:10],
        "unattributed_ops": reduction["unattributed_ops"]}


def run(workload: str, seed: int, seconds: float, cell_files=None,
        keep: str | None = None) -> tuple[dict, dict]:
    """One traced run of the cell; returns (result line, summary)."""
    captured: dict = {}
    with reading_spans(captured):
        result, _ = bench_run.run(workload, seed, seconds, True,
                                  cell_files=cell_files)
    if keep:
        with open(keep, "w") as f:
            json.dump(first_batches(captured["trace"], KEEP_BATCHES), f,
                      separators=(",", ":"))
    return result, summary(captured["reduction"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="write the window's first batches' trace here")
    args = ap.parse_args(argv)
    try:
        result, nodes = run(args.workload, args.seed, args.seconds,
                            keep=args.keep)
    except bench_run.BenchError as e:
        print(f"bench: no result: {e}", file=sys.stderr, flush=True)
        return 1
    bench_run.note("nodes", **nodes)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
