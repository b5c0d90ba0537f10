"""Reduce a profiler trace of one window to device metrics, and read the
program's own spans and node names from it.

The program names what it does in the trace (PERF.md, "Naming contract"):

  - the served path's phases are host annotations `serve.*` on the
    scheduler thread: one `serve.batch` per batch, tiled by its children
    `serve.select`, `serve.assemble`, `serve.h2d`, `serve.enqueue`,
    `serve.await` (or `serve.eager`), `serve.d2h`, `serve.respond`;
  - each network node runs under the named scope "<op>:<id>", which every
    HLO op of the node carries in its `op_name`, and each Pallas kernel of
    a node is named "<family>__<op>__<label>", so the device op is
    `%<family>__<op>__<label>.<n>`; the label is the node id, or the ids
    of the nodes whose kernels are alike joined by "-", whose ops then
    share their time equally.

`load()` reads the `.xplane.pb` file that `jax.profiler` wrote and keeps,
as [name, start_ns, end_ns]: the operations that ran on each TPU (line
"XLA Ops" of each "/device:TPU:<n>" plane) with "op_nodes", the node
("<op>:<label>") of each op name that one claims; the host's own events
(every line of the "/host:CPU" plane: the benchmark's annotations and the
runtime's); and "spans", the program's `serve.*` spans as [name, start_ns,
end_ns, line], `line` being the host line (thread) they ran on. The result
is plain JSON, which is how the tests keep a recorded trace.

`reduce()` clips everything to the benchmark's "bench.window" annotation
and gives:

  window_s          the window's length;
  busy_s            the union of the op intervals, averaged over the chips;
  device_ops        the ten op names with the most device time (seconds,
                    summed over the chips);
  idle_gaps         the idle time between ops, by what the host was doing:
                    each gap goes to the host event that overlaps it most,
                    the runtime's before the benchmark's own (a generator
                    asleep, or waiting on an answer), and "unattributed"
                    where no host event overlaps it. Ten names at most;
  serve_batches     one dict per dispatched `serve.batch` inside the
                    window: "batch" and each child phase, in seconds;
  node_device_s     device seconds per node ("<op>:<id>"), averaged over
                    the chips (a kernel labelled with several nodes split
                    equally);
  unattributed_ops  the ten op names outside every node, with their
                    device seconds averaged over the chips.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
SPAN_PREFIX = "serve."
BATCH = "serve.batch"
TOP = 10
#: a node's named scope as one element of an op_name path
_SCOPE = re.compile(r"^([a-z][a-z0-9_]*):([A-Za-z0-9_.\-]+)$")
#: XLA's unique suffix on an instruction name
_SUFFIX = re.compile(r"\.\d+$")


def node_of(op: str, stats: dict | None = None) -> str | None:
    """The node ("<op>:<label>") that a device op belongs to: from its
    kernel name, else from the named scope in its op_name path among
    `stats`."""
    parts = _SUFFIX.sub("", op.lstrip("%")).split("__")
    if len(parts) == 3:
        return f"{parts[1]}:{parts[2]}"
    for v in (stats or {}).values():
        if isinstance(v, str) and v.startswith("jit("):
            for el in v.split("/"):
                if _SCOPE.match(el):
                    return el
    return None


def load(trace_dir: str) -> dict:
    """{"devices": [{"ops": [...], "op_nodes": {...}}, ...], "host": [...],
    "spans": [...]} from the one .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"want one .xplane.pb under {trace_dir}, found "
                         f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, host, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops, nodes = [], {}
            line = next((l for l in plane.lines if l.name == OPS_LINE), None)
            for e in (line.events if line is not None else ()):
                name = short(e.name)
                ops.append([name, e.start_ns, e.end_ns])
                if name not in nodes:
                    nodes[name] = node_of(name, dict(e.stats))
            devices.append({"ops": ops, "op_nodes": {
                k: v for k, v in nodes.items() if v is not None}})
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    host.append([e.name, e.start_ns, e.end_ns])
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.end_ns, i])
    return {"devices": devices, "host": host, "spans": spans}


def short(name: str) -> str:
    """An op's name in the trace without its HLO text: `%fusion.3 = f32[..]
    fusion(..)` -> `%fusion.3`."""
    return name.split(" = ", 1)[0]


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted [start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events, w0, w1):
    return [(n, max(a, w0), min(b, w1)) for n, a, b in events
            if b > w0 and a < w1]


def gaps(busy, w0, w1) -> list[tuple[float, float]]:
    """The parts of [w0, w1) that `busy` (merged) leaves free."""
    out, t = [], w0
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < w1:
        out.append((t, w1))
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def attribute(gap_list, host) -> dict[str, float]:
    """Idle ns per host event name (see the module docstring)."""
    host = sorted((a, b, n) for n, a, b in host if n != WINDOW)
    ends: list = []
    out: dict[str, float] = {}
    j = 0
    for g0, g1 in gap_list:
        while j < len(host) and host[j][0] < g1:
            ends.append(host[j])
            j += 1
        ends = [e for e in ends if e[1] > g0]
        best, score = "unattributed", (False, 0.0)
        for a, b, n in ends:
            ov = _overlap(a, b, g0, g1)
            s = (not n.startswith("bench."), ov)
            if ov > 0 and s > score:
                best, score = n, s
        out[best] = out.get(best, 0.0) + (g1 - g0)
    return out


def batches(spans, w0, w1) -> list[dict]:
    """Each `serve.batch` inside [w0, w1] that dispatched (one whose
    selection found every request expired has no `serve.h2d`), with its
    children's seconds."""
    by_line: dict[int, list] = {}
    for s in spans:
        by_line.setdefault(s[3], []).append(s)
    out = []
    for line in by_line.values():
        row, end = None, None
        # a parent sorts before the children that start with it
        for name, a, b, _ in sorted(line, key=lambda s: (s[1], -s[2])):
            if name == BATCH:
                row, end = None, None
                if w0 <= a and b <= w1:
                    row, end = {"batch": (b - a) * 1e-9}, b
                    out.append(row)
            elif row is not None and b <= end:
                key = name[len(SPAN_PREFIX):]
                row[key] = row.get(key, 0.0) + (b - a) * 1e-9
    return [r for r in out if "h2d" in r]


def _top(d: dict[str, float]) -> list:
    return [[k, v * 1e-9] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce(trace: dict) -> dict:
    wins = [(a, b) for n, a, b in trace["host"] if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"want one {WINDOW!r} event, found {len(wins)}")
    w0, w1 = wins[0]
    busy_ns, op_ns = [], {}
    idle: dict[str, float] = {}
    node_ns: dict[str, float] = {}
    other_ns: dict[str, float] = {}
    for dev in trace["devices"]:
        ops = _clip(dev["ops"], w0, w1)
        busy = union((a, b) for _, a, b in ops)
        busy_ns.append(sum(b - a for a, b in busy))
        nodes = dev.get("op_nodes", {})
        for n, a, b in ops:
            op_ns[n] = op_ns.get(n, 0.0) + (b - a)
            node = nodes.get(n)
            if node is None:
                other_ns[n] = other_ns.get(n, 0.0) + (b - a)
                continue
            op, label = node.split(":", 1)
            ids = label.split("-")
            for nid in ids:
                key = f"{op}:{nid}"
                node_ns[key] = node_ns.get(key, 0.0) + (b - a) / len(ids)
        for k, v in attribute(gaps(busy, w0, w1), trace["host"]).items():
            idle[k] = idle.get(k, 0.0) + v
    n = max(1, len(trace["devices"]))
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy_ns) / n * 1e-9,
            "device_ops": _top(op_ns),
            "idle_gaps": _top({k: v / n for k, v in idle.items()}),
            "serve_batches": batches(trace.get("spans", []), w0, w1),
            "node_device_s": {k: v * 1e-9 / n for k, v in node_ns.items()},
            "unattributed_ops": [[k, s / n] for k, s in _top(other_ns)]}


def node_id(node: str) -> str:
    """A node's id: "inverted_residual:ir3" -> "ir3"."""
    return node.split(":", 1)[1]


def summary(reduction: dict, least_s: dict[str, float]) -> dict:
    """The host phases' medians (ms), the share of busy time that named
    nodes hold, the ten nodes with the most device time, each as [node,
    seconds, roofline %] with its layers' least seconds (`least_s`, by
    node id) over its device time (None where it has no layers), and the
    ops outside every node (seconds)."""
    rows = reduction["serve_batches"]
    phases = sorted({k for r in rows for k in r})
    nodes = reduction["node_device_s"]
    top = sorted(nodes.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "batches": len(rows),
        "phase_ms_p50": {k: 1e3 * statistics.median(r.get(k, 0.0)
                                                    for r in rows)
                         for k in phases},
        "node_share": (100.0 * sum(nodes.values()) / reduction["busy_s"]
                       if reduction["busy_s"] else None),
        "nodes": [[k, s, 100.0 * least_s[node_id(k)] / s
                   if s and node_id(k) in least_s else None]
                  for k, s in top],
        "unattributed_ops": reduction["unattributed_ops"]}


def first_batches(trace: dict, n: int) -> dict:
    """The trace cut to its window's first `n` batches, with the window
    annotation moved to cover just them: how the recorded spans trace of
    the tests (`bench/tests/data/`) was cut from a chip run's."""
    w0, w1 = next((a, b) for name, a, b in trace["host"] if name == WINDOW)
    spans = sorted((s for s in trace["spans"] if s[0] == BATCH
                    and w0 <= s[1] and s[2] <= w1), key=lambda s: s[1])[:n]
    if not spans:
        raise ValueError("no serve.batch inside the window")
    t0, t1 = spans[0][1], spans[-1][2]

    def cut(events):
        return [e for e in events if e[2] > t0 and e[1] < t1]

    host = [e for e in cut(trace["host"]) if e[0] != WINDOW]
    return {"devices": [{"ops": cut(d["ops"]), "op_nodes": d["op_nodes"]}
                        for d in trace["devices"]],
            "host": host + [[WINDOW, t0, t1]],
            "spans": cut(trace["spans"])}
