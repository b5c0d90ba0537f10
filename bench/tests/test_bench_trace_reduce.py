"""bench/trace_reduce.py on a hand-made trace with known answers, on a
trace recorded on a TPU v5e (bench/tests/data/), and on a trace this CPU
records."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace_reduce as tr  # noqa: E402

MS = 1_000_000


def _hand_made():
    """Window [0, 100) ms. Ops: [10, 30) and [20, 40) overlap, [60, 70);
    Host: the runtime busy over [45, 55), the
    benchmark asleep over [75, 95)."""
    return {"devices": [{
        "ops": [["conv", 10 * MS, 30 * MS], ["conv", 20 * MS, 40 * MS],
                ["fusion", 60 * MS, 70 * MS], ["late", 99 * MS, 120 * MS]]}],
        "host": [["bench.window", 0, 100 * MS],
                 ["TransferToDevice", 45 * MS, 55 * MS],
                 ["bench.sleep", 75 * MS, 95 * MS]]}


def test_union_and_gaps():
    assert tr.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tr.gaps([(1, 4), (5, 7)], 0, 10) == [(0, 1), (4, 5), (7, 10)]


def test_hand_made_trace():
    r = tr.reduce(_hand_made())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.041)       # 30 + 10 + 1 ms
    assert r["device_ops"][0] == ["conv", pytest.approx(0.040)]
    gaps = dict(r["idle_gaps"])
    # a gap goes whole to the event that overlaps it most: [40, 60) to
    # the runtime, [70, 99) to the sleep; nothing overlaps [0, 10)
    assert gaps == {"TransferToDevice": pytest.approx(0.020),
                    "bench.sleep": pytest.approx(0.029),
                    "unattributed": pytest.approx(0.010)}
    assert sum(gaps.values()) == pytest.approx(0.1 - r["busy_s"])


def test_runtime_event_comes_before_the_benchmarks_own():
    t = _hand_made()
    t["host"].append(["bench.wait", 0, 100 * MS])
    gaps = dict(tr.reduce(t)["idle_gaps"])
    assert gaps["TransferToDevice"] == pytest.approx(0.020)
    assert gaps["bench.wait"] == pytest.approx(0.039)


def test_recorded_tpu_trace():
    with open(os.path.join(HERE, "data", "v5e_mbv2_trace.json")) as f:
        t = json.load(f)
    r = tr.reduce(t)
    w0, w1 = next((a, b) for n, a, b in t["host"] if n == tr.WINDOW)
    # brute force: the busy share on a 1 us grid
    grid = np.zeros(int((w1 - w0) // 1000) + 1, bool)
    for _, a, b in t["devices"][0]["ops"]:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            grid[int((a - w0) // 1000):int(-(-(b - w0) // 1000))] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    assert 0 < r["busy_s"] < r["window_s"]
    assert len(r["device_ops"]) <= tr.TOP and len(r["idle_gaps"]) <= tr.TOP
    secs = [s for _, s in r["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r[
        "busy_s"] + 1e-9


def test_load_reads_the_window_annotation(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert [n for n, _, _ in t["host"]].count(tr.WINDOW) == 1
    assert json.loads(json.dumps(t)) == t
