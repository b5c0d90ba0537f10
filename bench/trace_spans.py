"""Read the program's own spans and node names from a profiler trace.

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

`load()` reads what `trace_reduce.load()` reads, by the same rules, and
adds, for each chip, "op_nodes": the node ("<op>:<id>") of each op name
that one claims, and "spans": the program's `serve.*` spans as [name,
start_ns, end_ns, line], `line` being the host line (thread) they ran on.
`trace_reduce.reduce()` reads the result as it reads its own.

`reduce()` clips to the "bench.window" annotation and gives:

  serve_batches      one dict per dispatched `serve.batch` inside the
                     window: "batch" and each child phase, in seconds;
  node_device_s      device seconds per node, averaged over the chips
                     (a kernel labelled with several nodes split equally);
  unattributed_ops   the ten op names outside every node, with their
                     device seconds averaged over the chips.
"""

from __future__ import annotations

import glob
import os
import re

from bench import trace_reduce as tr

SPAN_PREFIX = "serve."
BATCH = "serve.batch"
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
    """`trace_reduce.load()`'s trace of the one .xplane.pb under
    `trace_dir`, with each chip's "op_nodes" and the host "spans"."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"want one .xplane.pb under {trace_dir}, found "
                         f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, host, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith(tr.DEVICE_PLANE):
            ops, nodes = [], {}
            for line in plane.lines:
                if line.name != tr.OPS_LINE:
                    continue
                for e in line.events:
                    name = tr.short(e.name)
                    ops.append([name, e.start_ns, e.end_ns])
                    if name not in nodes:
                        nodes[name] = node_of(name, dict(e.stats))
                break
            devices.append({"ops": ops, "op_nodes": {
                k: v for k, v in nodes.items() if v is not None}})
        elif plane.name == tr.HOST_PLANE:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    host.append([e.name, e.start_ns, e.end_ns])
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, e.start_ns, e.end_ns, i])
    return {"devices": devices, "host": host, "spans": spans}


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


def reduce(trace: dict) -> dict:
    wins = [(a, b) for n, a, b in trace["host"] if n == tr.WINDOW]
    if len(wins) != 1:
        raise ValueError(f"want one {tr.WINDOW!r} event, found {len(wins)}")
    w0, w1 = wins[0]
    node_ns: dict[str, float] = {}
    other_ns: dict[str, float] = {}
    for dev in trace["devices"]:
        nodes = dev.get("op_nodes", {})
        for n, a, b in tr._clip(dev["ops"], w0, w1):
            node = nodes.get(n)
            if node is None:
                other_ns[n] = other_ns.get(n, 0.0) + (b - a)
                continue
            op, label = node.split(":", 1)
            ids = label.split("-")
            for nid in ids:
                key = f"{op}:{nid}"
                node_ns[key] = node_ns.get(key, 0.0) + (b - a) / len(ids)
    chips = max(1, len(trace["devices"]))
    return {"serve_batches": batches(trace.get("spans", []), w0, w1),
            "node_device_s": {n: v * 1e-9 / chips
                              for n, v in node_ns.items()},
            "unattributed_ops": [[n, s / chips]
                                 for n, s in tr._top(other_ns)]}
