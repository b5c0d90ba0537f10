"""Reduce a profiler trace of one window to device metrics.

`load()` reads the `.xplane.pb` file that `jax.profiler` wrote and keeps
two kinds of events, as [name, start_ns, end_ns]: the operations that ran
on each TPU (line "XLA Ops" of each "/device:TPU:<n>" plane) and the
host's own events (every line of the "/host:CPU" plane: the benchmark's
annotations and the runtime's). The
result is plain JSON, which is how the tests keep a recorded trace.

`reduce()` clips everything to the benchmark's "bench.window" annotation
and gives:

  window_s        the window's length;
  busy_s          the union of the op intervals, averaged over the chips;
  device_ops      the ten op names with the most device time (seconds,
                  summed over the chips);
  idle_gaps       the idle time between ops, by what the host was doing:
                  each gap goes to the host event that overlaps it most,
                  the runtime's before the benchmark's own (a generator
                  asleep, or waiting on an answer), and "unattributed"
                  where no host event overlaps it. Ten names at most.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
TOP = 10


def load(trace_dir: str) -> dict:
    """{"devices": [{"ops": [...]}, ...], "host": [...]}
    from the one .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"want one .xplane.pb under {trace_dir}, found "
                         f"{len(paths)}")
    data = ProfileData.from_file(paths[0])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = next((l.events for l in plane.lines
                        if l.name == OPS_LINE), ())
            devices.append({"ops": [[short(e.name), e.start_ns, e.end_ns]
                                    for e in ops]})
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.end_ns] for e in line.events]
    return {"devices": devices, "host": host}


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
    for dev in trace["devices"]:
        ops = _clip(dev["ops"], w0, w1)
        busy = union((a, b) for _, a, b in ops)
        busy_ns.append(sum(b - a for a, b in busy))
        for n, a, b in ops:
            op_ns[n] = op_ns.get(n, 0.0) + (b - a)
        for k, v in attribute(gaps(busy, w0, w1), trace["host"]).items():
            idle[k] = idle.get(k, 0.0) + v
    n = max(1, len(trace["devices"]))
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy_ns) / n * 1e-9,
            "device_ops": _top(op_ns),
            "idle_gaps": _top({k: v / n for k, v in idle.items()})}
