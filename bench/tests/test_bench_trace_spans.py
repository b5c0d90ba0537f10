"""The program's spans and node names in bench/trace_reduce.py, and the
metrics that read them (host_ms, h2d_ms, kernel_roofline): on hand-made
traces with known answers, on a trace of the program recorded on a TPU v5e
(bench/tests/data/), and through whole traced runs of bench/run.py on this
CPU."""

import json
import os
import statistics
import sys
from types import SimpleNamespace

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import flops, manifest  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from bench.reference import ops  # noqa: E402

MS = 1_000_000
#: a window of the mbv2.offline cell's first three batches (bucket 8),
#: cut by `trace_reduce.first_batches` from a traced run on a TPU v5e; the
#: runtime's per-chunk "Transpose" host events are left out for size
SPANS_TRACE = os.path.join(HERE, "data", "v5e_mbv2_spans_trace.json")
#: the four keys of each recorded trace's reduction as the reduction gave
#: them before it read spans and node names
OLD_KEYS = ("window_s", "busy_s", "device_ops", "idle_gaps")
OLD_READINGS = os.path.join(HERE, "data", "v5e_mbv2_reductions.json")
JIT_PHASES = ["select", "assemble", "h2d", "enqueue", "await", "d2h",
              "respond"]


def _recorded():
    with open(SPANS_TRACE) as f:
        return json.load(f)


def _hand_made():
    """Window [0, 100) ms. Device ops: a named kernel of conv2d:c1 [10,
    30), a pad [5, 10), a kernel of pool:p [40, 45), the head [50, 55),
    and two runs of one kernel labelled for c2 and c3 [80, 88).
    Host: one batch [2, 60) on line 0 tiled by select, h2d, await,
    respond; one at [70, 71) that dispatched nothing; a batch on line 1
    that ends after the window."""
    return {
        "devices": [{"ops": [["%pad.0", 5 * MS, 10 * MS],
                             ["%winograd_streamed__conv2d__c1.3",
                              10 * MS, 30 * MS],
                             ["%maxpool__pool__p.1", 40 * MS, 45 * MS],
                             ["%fusion.2", 50 * MS, 55 * MS],
                             ["%matmul__conv2d__c2-c3.1", 80 * MS, 84 * MS],
                             ["%matmul__conv2d__c2-c3.2", 84 * MS, 88 * MS]],
                     "op_nodes": {
                         "%winograd_streamed__conv2d__c1.3": "conv2d:c1",
                         "%maxpool__pool__p.1": "pool:p",
                         "%matmul__conv2d__c2-c3.1": "conv2d:c2-c3",
                         "%matmul__conv2d__c2-c3.2": "conv2d:c2-c3"}}],
        "host": [["bench.window", 0, 100 * MS]],
        "spans": [["serve.batch", 2 * MS, 60 * MS, 0],
                  ["serve.select", 2 * MS, 3 * MS, 0],
                  ["serve.h2d", 3 * MS, 9 * MS, 0],
                  ["serve.await", 9 * MS, 56 * MS, 0],
                  ["serve.respond", 56 * MS, 60 * MS, 0],
                  ["serve.idle", 60 * MS, 70 * MS, 0],
                  ["serve.batch", 70 * MS, 71 * MS, 0],
                  ["serve.select", 70 * MS, 71 * MS, 0],
                  ["serve.batch", 90 * MS, 120 * MS, 1],
                  ["serve.h2d", 91 * MS, 92 * MS, 1]]}


@pytest.mark.parametrize("op,stats,node", [
    ("%winograd_streamed__conv2d__conv1_1.14", None, "conv2d:conv1_1"),
    ("%matmul__inverted_residual__ir2.1", None, "inverted_residual:ir2"),
    ("%matmul__conv2d__ir3.dw.2", None, "conv2d:ir3.dw"),
    ("%separable_streamed__inverted_residual__ir8-ir9-ir10.4", None,
     "inverted_residual:ir8-ir9-ir10"),
    ("%pad.0", {"tf_op": "jit(apply)/conv2d:c1/jit(_pad)/pad"},
     "conv2d:c1"),
    ("%pad.0", {"long_name": "%pad.0 = f32[8]{0:T(8)} pad(..)"}, None),
    ("%copy.26", None, None),
])
def test_node_of(op, stats, node):
    assert tr.node_of(op, stats) == node


def test_hand_made_trace():
    t = _hand_made()
    r = tr.reduce(t)
    assert r["serve_batches"] == [{
        "batch": pytest.approx(0.058), "select": pytest.approx(0.001),
        "h2d": pytest.approx(0.006), "await": pytest.approx(0.047),
        "respond": pytest.approx(0.004)}]
    assert r["node_device_s"] == {"conv2d:c1": pytest.approx(0.020),
                                  "pool:p": pytest.approx(0.005),
                                  "conv2d:c2": pytest.approx(0.004),
                                  "conv2d:c3": pytest.approx(0.004)}
    assert dict(r["unattributed_ops"]) == {"%pad.0": pytest.approx(0.005),
                                           "%fusion.2": pytest.approx(0.005)}
    assert r["busy_s"] == pytest.approx(0.043)


def test_recorded_trace_keeps_the_old_reduction():
    """Reading spans and node names leaves the device reduction as it
    was: on both recorded traces, with and without spans and node names,
    equal to the last digit to what it read before it read them."""
    with open(OLD_READINGS) as f:
        before = json.load(f)
    t = _recorded()
    bare = {"devices": [{"ops": d["ops"]} for d in t["devices"]],
            "host": t["host"]}
    for trace in (t, bare):
        r = tr.reduce(trace)
        assert {k: r[k] for k in OLD_KEYS} == \
            before["v5e_mbv2_spans_trace.json"]
    with open(os.path.join(HERE, "data", "v5e_mbv2_trace.json")) as f:
        old = tr.reduce(json.load(f))
    assert {k: old[k] for k in OLD_KEYS} == before["v5e_mbv2_trace.json"]
    assert old["window_s"] == pytest.approx(0.03)
    assert old["busy_s"] == pytest.approx(0.004736851)
    assert old["device_ops"][0] == ["%winograd_strided_streamed.1",
                                    pytest.approx(0.000980282)]
    assert old["idle_gaps"][0] == ["XlaLinearize",
                                   pytest.approx(0.025262764)]


def test_recorded_trace_batches_and_nodes():
    t = _recorded()
    r = tr.reduce(t)
    assert len(r["serve_batches"]) == 3
    for row in r["serve_batches"]:
        assert [k for k in row if k != "batch"] == JIT_PHASES
        phases = sum(v for k, v in row.items() if k != "batch")
        assert 0.99 * row["batch"] <= phases <= row["batch"]
    nodes = r["node_device_s"]
    assert set(nodes) == set(t["devices"][0]["op_nodes"].values())
    assert len(nodes) == 18                  # conv1 and ir1 .. ir17
    # every op is a node's or listed outside, once
    busy = r["busy_s"]
    outside = sum(s for _, s in r["unattributed_ops"])
    assert sum(nodes.values()) + outside <= busy * 1.001
    assert sum(nodes.values()) > 0.5 * busy
    assert r["unattributed_ops"][0][0] == "%pad.0"


def _ctx(reduction, batches):
    cfg = manifest.config("mbv2_224")
    ref = __import__(f"bench.reference.{cfg['reference']}",
                     fromlist=["layers"])
    return SimpleNamespace(trace=reduction, batches=batches,
                           layers=ref.layers(cfg),
                           peak=flops.peaks("TPU v5 lite"))


def test_readers_on_recorded_trace():
    reduction = tr.reduce(_recorded())
    run = _ctx(reduction, {8: 3})
    rows = reduction["serve_batches"]
    host = manifest.reader("host_ms")(run)
    assert host == pytest.approx(1e3 * statistics.median(
        r["batch"] - r["await"] - r["h2d"] for r in rows))
    assert 0 < host < 10
    assert manifest.reader("h2d_ms")(run) == pytest.approx(
        1e3 * statistics.median(r["h2d"] for r in rows))
    kernel = manifest.reader("kernel_roofline")(run)
    assert 0 < kernel < 100
    # conv_head and fc run on XLA's own ops, which carry no node: their
    # least time leaves the numerator as their device time is not in the
    # denominator
    device = {tr.node_id(n): s
              for n, s in reduction["node_device_s"].items()}
    assert set(device) == {"conv1", *(f"ir{i}" for i in range(1, 18))}
    least = flops.least_time_by_node(run.layers, run.batches, run.peak)
    assert kernel == pytest.approx(
        100 * sum(least[n] for n in device) / sum(device.values()))


def test_readers_read_nothing_without_the_new_keys():
    """A trace without the program's spans and node names."""
    t = _recorded()
    bare = {"devices": [{"ops": d["ops"]} for d in t["devices"]],
            "host": t["host"]}
    run = _ctx(tr.reduce(bare), {8: 3})
    for name in ("host_ms", "h2d_ms", "kernel_roofline"):
        assert manifest.reader(name)(run) is None


def test_node_without_attributed_ops_leaves_both_sides():
    """Two alike convs; only `a` runs in a named kernel, `b` on ops that
    carry no node. `b`'s least time stays out of the numerator as its
    device time is out of the denominator, so the reading is `a`'s own
    share (80%), where counting `b`'s least time would read 160%."""
    peak = flops.peaks("TPU v5 lite")
    layers = [ops.conv_layer(n, 56, 56, 64, 64, 3, 3) for n in "ab"]
    least = flops.layer_time_s(layers[0], 8, peak)
    dur = round(1.25 * least * 1e9)
    kernel = "%winograd_streamed__conv2d__a.1"
    trace = {"devices": [{"ops": [[kernel, 0, dur], ["%fusion.1", dur,
                                                     2 * dur]],
                          "op_nodes": {kernel: "conv2d:a"}}],
             "host": [["bench.window", 0, 3 * dur]]}
    reduction = tr.reduce(trace)
    assert reduction["node_device_s"] == {"conv2d:a": pytest.approx(
        dur * 1e-9)}
    run = SimpleNamespace(trace=reduction, batches={8: 1}, layers=layers,
                          peak=peak)
    reading = manifest.reader("kernel_roofline")(run)
    assert reading == pytest.approx(100 * least / (dur * 1e-9))
    assert 79.9 < reading <= 100
    nodes = tr.summary(reduction, flops.least_time_by_node(
        layers, {8: 1}, peak))["nodes"]
    assert nodes == [["conv2d:a", pytest.approx(dur * 1e-9),
                      pytest.approx(reading)]]


def test_first_batches_cuts_the_window():
    t = _recorded()
    cut = tr.first_batches(t, 2)
    r = tr.reduce(cut)
    assert len(r["serve_batches"]) == 2
    assert r["serve_batches"] == tr.reduce(t)["serve_batches"][:2]
    assert r["window_s"] < tr.reduce(t)["window_s"]


@pytest.mark.parametrize("source", ["cpu_trace", "recorded_trace"])
def test_traced_run_on_cpu(monkeypatch, tmp_path, capsys, source):
    """A whole traced run of bench/run.py at a small size on this CPU,
    with the look for a chip skipped, reading mbv2.offline's per-layer
    metrics. On the CPU's own trace the host
    metrics read the program's spans, and kernel_roofline reads nothing (a
    CPU trace has no device plane). With the recorded TPU trace in its
    place, every per-layer metric reads, and the `bench nodes` line names
    the recorded nodes with their roofline shares."""
    from bench import run as bench_run
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(bench_run, "require_chips", lambda chips: (
        jax.devices(), flops.peaks("TPU v5 lite")))
    if source == "recorded_trace":
        monkeypatch.setattr(tr, "load", lambda tdir: _recorded())
    knobs = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_compilation_cache_max_size")}
    cfg = dict(manifest.config("mbv2_224"), res=32, algorithm="auto")
    traffic = {"kind": "closed", "in_flight": 4, "pool": 8, "buckets": [2],
               "warm_requests": 4}
    try:
        res, _ = bench_run.run("mbv2.offline", 2**31 + 7, 0.5, True,
                               cell_files=(cfg, traffic))
    finally:
        for k, v in knobs.items():
            jax.config.update(k, v)
    lines = capsys.readouterr().out.splitlines()
    nodes = [json.loads(line[len("bench nodes "):]) for line in lines
             if line.startswith("bench nodes ")]
    assert len(nodes) == 1
    nodes = nodes[0]
    assert res["correct"]
    m = res["metrics"]
    assert set(m) >= {"host_ms", "h2d_ms", "idle_share"}
    if source == "cpu_trace":
        assert m["host_ms"]["value"] > 0 and m["h2d_ms"]["value"] > 0
        assert "kernel_roofline" not in m
        assert nodes["batches"] > 0 and nodes["nodes"] == []
        assert set(nodes["phase_ms_p50"]) == {"batch", *JIT_PHASES}
        return
    recorded = tr.reduce(_recorded())
    assert res["device"]["busy_s"] == recorded["busy_s"]
    assert m["host_ms"]["value"] == manifest.reader("host_ms")(
        SimpleNamespace(trace=recorded))
    assert 0 < m["kernel_roofline"]["value"] <= 100
    assert nodes["batches"] == 3
    assert nodes["node_share"] == pytest.approx(
        100 * sum(recorded["node_device_s"].values()) / recorded["busy_s"])
    assert [n[0] for n in nodes["nodes"]][:2] == ["conv2d:conv1",
                                                  "inverted_residual:ir1"]
    assert all(0 < share <= 100 for _, _, share in nodes["nodes"])
    assert nodes["unattributed_ops"][0][0] == "%pad.0"
