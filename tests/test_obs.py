"""Observability subsystem (repro.obs): span tracing + chrome export,
atomic metrics, the serve-path profiler's per-request decomposition,
provably-zero disabled overhead, the artifact-audit CLI, the BENCH
regression gate, and the fleet tuning database that lets a fresh process
adopt measured auto_tuned placements without re-measuring."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import compile as C
from repro.core import plan
from repro.models import cnn
from repro.obs import metrics, profile, regress, trace, tuningdb
from repro.runtime import inject
from repro.runtime import serve as serve_mod
from repro.runtime.serve import ServeConfig, Server

RES = 16
SPECS = [cnn.Conv("c1", 3, 3, 8), cnn.Conv("c2", 3, 3, 8, relu=False)]


@pytest.fixture(autouse=True)
def _obs_clean_slate():
    """Global observability state (tracer, profiler, default metrics,
    tuning DB) must not leak between tests."""
    profile.disable()
    metrics.reset()
    tuningdb.clear()
    yield
    profile.disable()
    metrics.reset()
    tuningdb.clear()


@pytest.fixture
def params():
    return cnn.init_cnn(jax.random.key(0), SPECS, 3, res=RES)


@pytest.fixture
def xs(rng):
    return [rng.standard_normal((RES, RES, 3)).astype(np.float32)
            for _ in range(4)]


def make_cfg(**kw):
    base = dict(buckets=(1, 2), queue_capacity=16, verbose=False,
                jit_dispatch=False, backoff_base_s=0.002,
                backoff_cap_s=0.01)
    base.update(kw)
    return ServeConfig(**base)


def serve_n(srv, xs, n):
    tickets = []
    for i in range(n):
        t = srv.submit(xs[i % len(xs)])
        t.result(timeout=60)
        tickets.append(t)
    return tickets


# ---------------------------------------------------------------------------
# trace: ring buffer, nesting, chrome export
# ---------------------------------------------------------------------------

def test_tracer_ring_capacity_and_dropped():
    tr = trace.Tracer(capacity=4)
    for i in range(10):
        tr.add_span(f"s{i}", float(i), float(i) + 0.5)
    assert len(tr) == 4
    assert tr.dropped == 6
    # oldest dropped first: only s6..s9 survive
    assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_span_nesting_depth_and_error_capture():
    tr = trace.Tracer()
    with tr.span("outer"):
        with tr.span("inner") as sp:
            sp.set(detail=7)
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    by_name = {s.name: s for s in tr.spans()}
    assert by_name["outer"].depth == 0
    assert by_name["inner"].depth == 1
    assert by_name["inner"].args["detail"] == 7
    assert "ValueError" in by_name["boom"].args["error"]
    # depth unwound: a fresh span is top-level again
    with tr.span("later"):
        pass
    assert {s.name: s.depth for s in tr.spans()}["later"] == 0


def test_chrome_export_is_valid_and_rebased(tmp_path):
    tr = trace.Tracer()
    with tr.span("a"):
        time.sleep(0.001)
    tr.instant("mark", k=1)
    path = str(tmp_path / "trace.json")
    doc = tr.export_chrome(path)
    with open(path) as f:
        assert json.load(f) == doc          # file round-trips
    events = doc["traceEvents"]
    assert events[0]["ph"] == "M"           # process-name metadata
    xs = [e for e in events if e["ph"] == "X"]
    ins = [e for e in events if e["ph"] == "i"]
    assert len(xs) == 1 and len(ins) == 1
    assert xs[0]["dur"] > 0
    assert all(e["ts"] >= 0 for e in xs + ins)   # rebased to first span
    assert min(e["ts"] for e in xs + ins) == 0
    assert doc["otherData"]["dropped_spans"] == 0


def test_disabled_module_api_is_noop():
    trace.disable()
    assert trace.span("x") is trace.NULL_SPAN
    trace.add_span("x", 0.0, 1.0)            # no-ops, no error
    trace.instant("x")
    assert trace.get() is None and not trace.is_enabled()
    with pytest.raises(RuntimeError, match="disabled"):
        trace.export_chrome()
    tr = trace.enable(capacity=8)
    assert trace.enable() is tr              # enable() reuses the tracer
    trace.disable()


# ---------------------------------------------------------------------------
# metrics: histogram semantics + atomic snapshots
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_bucket_bound():
    reg = metrics.MetricsRegistry("t")
    h = reg.histogram("lat")
    samples = [0.001 * (i + 1) for i in range(100)]
    for s in samples:
        h.record(s)
    true_p50 = float(np.percentile(samples, 50))
    assert true_p50 <= h.percentile(0.5) <= 2 * true_p50
    assert h.percentile(0.99) <= h.max
    st = h.state()
    assert st["count"] == 100
    assert st["min"] == samples[0] and st["max"] == samples[-1]
    assert sum(st["buckets"].values()) == 100
    h.record(0.0)                            # underflow bucket
    assert h.state()["buckets"]["underflow"] == 1


def test_metrics_snapshot_is_atomic_under_hammer():
    """Two counters incremented together under the registry lock must
    never be observed torn by snapshot()."""
    reg = metrics.MetricsRegistry("t")
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            with reg.lock:
                reg.count("a")
                reg.count("b")

    threads = [threading.Thread(target=writer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(300):
            snap = reg.snapshot()["counters"]
            assert snap.get("a", 0) == snap.get("b", 0), snap
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_snapshot_all_merges_live_server_registries(params):
    srv = Server(params, SPECS, res=RES, config=make_cfg())
    try:
        merged = metrics.snapshot_all()
        assert "default" in merged
        serve_regs = [k for k in merged if k.startswith("serve")]
        assert serve_regs, merged.keys()
        assert "serve.admitted" in merged[serve_regs[0]]["counters"]
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# ServerStats: atomic snapshot under concurrent traffic (satellite 1)
# ---------------------------------------------------------------------------

def test_stats_snapshot_race_stress(params, xs):
    """Hammer snapshot()/in_flight from reader threads while traffic runs:
    no RuntimeError (dict resized during iteration), and every cut is
    internally consistent (in_flight identity holds, never negative)."""
    errors: list[BaseException] = []
    snaps: list[dict] = []
    stop = threading.Event()

    with Server(params, SPECS, res=RES, config=make_cfg()) as srv:
        def reader():
            try:
                while not stop.is_set():
                    s = srv.stats.snapshot()
                    assert s["in_flight"] == (
                        s["admitted"] - s["completed"] - s["timed_out"]
                        - s["cancelled"] - s["failed"])
                    assert s["in_flight"] >= 0, s
                    assert srv.stats.in_flight >= 0
                    snaps.append(s)
            except BaseException as e:      # noqa: BLE001 - reraised below
                errors.append(e)

        readers = [threading.Thread(target=reader) for _ in range(3)]
        for t in readers:
            t.start()
        try:
            serve_n(srv, xs, 24)
        finally:
            stop.set()
            for t in readers:
                t.join()
    assert not errors, errors[0]
    assert len(snaps) > 50
    final = srv.stats.snapshot()
    assert final["completed"] == 24 and final["in_flight"] == 0
    # the attribute views and the snapshot tell one story
    assert srv.stats.completed == 24
    assert sum(final["bucket_batches"].values()) == final["batches"]


# ---------------------------------------------------------------------------
# profiler: disabled-path zero overhead (satellite 4)
# ---------------------------------------------------------------------------

def test_serve_disabled_emits_zero_spans(params, xs, monkeypatch):
    """No tracer and no profiler session: the serve path makes no span
    object and formats no span args (every span() is the shared no-op).
    The same traffic with the tracer on records the phase spans, so the
    check is not vacuous."""
    made = []

    class Counting(trace._SpanCtx):
        __slots__ = ()

        def __init__(self, *a, **kw):
            made.append(a[1])
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace, "_SpanCtx", Counting)
    assert not trace.profiling()
    with Server(params, SPECS, res=RES, config=make_cfg()) as srv:
        serve_n(srv, xs, 6)
        assert made == []
        tr = trace.enable()
        serve_n(srv, xs, 2)
        assert "serve.batch" in made
        assert tr.spans("serve.batch")
    trace.disable()


def _profiled(path, fn):
    """Run fn() under a CPU profiler session (host annotations only, as
    the benchmark records) and return the host plane's lines (one per
    thread) as [[(event name, start_ns, end_ns, stats), ...], ...]."""
    import glob

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    [pb] = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                     recursive=True)
    data = jax.profiler.ProfileData.from_file(pb)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    return [[(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for e in line.events] for line in host.lines]


def test_span_lands_in_profiler_trace_and_recorder(tmp_path):
    """trace.span feeds both sinks: the recorder, when enabled, and a
    recording profiler session, with its args and nesting."""
    tr = trace.enable()

    def body():
        assert trace.profiling()
        with trace.span("outer", k=1) as sp:
            sp.set(late=2)
            with trace.span("inner"):
                pass

    lines = _profiled(tmp_path, body)
    assert not trace.profiling()
    events = {n: (a, b, st) for evs in lines for n, a, b, st in evs}
    assert events["outer"][2] == {"k": 1, "late": 2}
    assert events["outer"][0] <= events["inner"][0] <= \
        events["inner"][1] <= events["outer"][1]
    assert [s.name for s in tr.spans()] == ["outer", "inner"]
    assert tr.spans("outer")[0].args == {"k": 1, "late": 2}
    trace.disable()


#: the children of one jitted batch, in order
JIT_PHASES = ["serve.select", "serve.assemble", "serve.h2d", "serve.enqueue",
              "serve.await", "serve.d2h", "serve.respond"]


def test_profiler_trace_holds_serve_phase_spans(params, xs, tmp_path):
    """Under a CPU profiler session every batch is one serve.batch span on
    the scheduler thread (bucket, rows, padded rows as args) whose
    children tile it in order; the scheduler's waits are serve.idle.
    Between two children runs a statement or two, which a loaded host can
    stretch, so the cover asked of a batch of a millisecond or two is
    half; bench/tests reads 99% on a trace recorded on a TPU host."""
    with Server(params, SPECS, res=RES,
                config=make_cfg(jit_dispatch=True)) as srv:
        serve_n(srv, xs, 2)

        def six_batches():
            serve_n(srv, xs, 6)
            srv.stop()          # the last batch's span closes in the trace

        lines = _profiled(tmp_path, six_batches)
        n = srv.stats.batches - 2
    [sched] = [evs for evs in lines
               if any(e[0] == "serve.batch" for e in evs)]
    batches = [e for e in sched if e[0] == "serve.batch"]
    assert len(batches) == n == 6
    assert any(e[0] == "serve.idle" for e in sched)
    for _, a, b, st in batches:
        assert st == {"bucket": 1, "rows": 1, "padded": 1}
        kids = sorted((e for e in sched if e[0].startswith("serve.")
                       and e[0] != "serve.batch"
                       and a <= e[1] and e[2] <= b), key=lambda e: e[1])
        assert [k[0] for k in kids] == JIT_PHASES
        for k0, k1 in zip(kids, kids[1:]):
            assert k0[2] <= k1[1]                  # no overlap
        assert sum(k[2] - k[1] for k in kids) >= 0.5 * (b - a)


def test_lone_requests_bypass_dispatching_ahead(params, xs):
    """One request at a time leaves nothing queued behind a batch: no
    batch is enqueued ahead, each batch is awaited in the serve.batch that
    enqueued it, and the scheduler never waits for work (serve.idle) while
    a batch is in flight."""
    with Server(params, SPECS, res=RES,
                config=make_cfg(jit_dispatch=True)) as srv:
        serve_n(srv, xs, 2)
        tr = trace.enable()
        serve_n(srv, xs, 6)
        srv.stop()
    trace.disable()
    assert srv.stats.dispatched_ahead == 0
    assert srv.stats.jit_dispatches == 8
    spans = tr.spans()
    batches = [s for s in spans if s.name == "serve.batch"]
    enqueued = [s for s in spans if s.name == "serve.enqueue"]
    awaited = [s for s in spans if s.name == "serve.await"]
    idle = [s for s in spans if s.name == "serve.idle"]
    assert len(batches) == len(enqueued) == len(awaited) == 6
    assert idle
    for b, e, a in zip(batches, enqueued, awaited):
        assert b.t0 <= e.t0 <= e.t1 <= a.t0 <= a.t1 <= b.t1
        assert "ahead" not in b.args
        assert not any(i.t0 < a.t1 and e.t0 < i.t1 for i in idle)


def test_jit_builds_count_rebuilt_programs(params, xs):
    """jit_builds counts each bucket's jitted callable once, and again
    when a re-placed layer forces a rebuild."""
    with Server(params, SPECS, res=RES,
                config=make_cfg(jit_dispatch=True)) as srv:
        assert srv.stats.jit_builds == len(srv.buckets)
        serve_n(srv, xs, 3)
        assert srv.stats.jit_builds == len(srv.buckets)
        assert srv._replace_layer("c1", reason="test")
        serve_n(srv, xs, 1)
        assert srv.stats.jit_builds == len(srv.buckets) + 1
        assert srv.stats.snapshot()["jit_builds"] == srv.stats.jit_builds


def test_profiler_leaves_jitted_computation_unchanged(params):
    """jaxpr-level proof: enabling the profiler does not change what the
    jitted network computes -- instrumentation lives outside the trace."""
    import re

    def jaxpr_of(fn, x):
        # object reprs embed memory addresses that differ between any two
        # traces; strip them so the compare is structural
        return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(x)))

    net = C.compile(params, SPECS, res=RES, batch=1, algorithm="winograd")
    x = jnp.zeros((1, RES, RES, 3), jnp.float32)
    before = jaxpr_of(net.apply, x)
    profile.enable()
    after = jaxpr_of(net.apply, x)
    profile.disable()
    assert before == after


def test_serve_overhead_p50_under_5pct(params, xs):
    """Enabled-profiler p50 latency inflation < 5% on a serving smoke,
    measured interleaved so drift hits both arms."""
    lat = {"off": [], "on": []}
    with Server(params, SPECS, res=RES, config=make_cfg()) as srv:
        serve_n(srv, xs, 6)                  # warm both paths
        profile.enable()
        serve_n(srv, xs, 2)
        profile.disable()
        for _ in range(8):
            lat["off"] += [t.latency_s for t in serve_n(srv, xs, 3)]
            profile.enable()
            lat["on"] += [t.latency_s for t in serve_n(srv, xs, 3)]
            profile.disable()
    p50_off = float(np.percentile(lat["off"], 50))
    p50_on = float(np.percentile(lat["on"], 50))
    assert p50_on < p50_off * 1.05, (p50_off, p50_on)


# ---------------------------------------------------------------------------
# profiler: per-request decomposition + per-layer attribution
# ---------------------------------------------------------------------------

def _spans_by_rid(tracer):
    out: dict[int, dict[str, trace.Span]] = {}
    for s in tracer.spans():
        rid = s.args.get("rid")
        if rid is not None:
            out.setdefault(rid, {})[s.name] = s
    return out


def test_decomposition_sums_to_measured_latency(params, xs):
    """queue_wait + batch_formation + dispatch + respond tile
    [submit, finish]: per request the spans sum to the independently
    measured ticket latency. The dispatch interval is the batch's live
    serve.h2d .. serve.eager spans, which lie inside it and fill it up to
    the few microseconds between two spans."""
    with Server(params, SPECS, res=RES, config=make_cfg()) as srv:
        serve_n(srv, xs, 2)
        profile.enable()
        tickets = serve_n(srv, xs, 6)
        tr = trace.get()
        by_rid = _spans_by_rid(tr)
        live = [s for s in tr.spans()
                if s.name in ("serve.h2d", "serve.eager")]
    for t in tickets:
        parts = by_rid[t.rid]
        qw = parts["serve.queue_wait"]
        bf = parts["serve.batch_formation"]
        rp = parts["serve.respond"]
        d0, d1 = bf.t1, rp.t0                       # its batch's dispatch
        total = (qw.duration_s + bf.duration_s + (d1 - d0)
                 + rp.duration_s)
        assert abs(total - t.latency_s) <= 1e-6 + 1e-3 * t.latency_s, \
            (total, t.latency_s)
        # the boundaries are shared stamps, not re-measured
        assert qw.t0 == t.submitted_at and rp.t1 == t.finished_at
        assert qw.t1 == bf.t0
        phases = [s for s in live if d0 <= s.t0 and s.t1 <= d1]
        assert [s.name for s in phases] == ["serve.h2d", "serve.eager"]
        assert sum(s.duration_s for s in phases) >= 0.9 * (d1 - d0)
    profile.disable()


def _pallas_kernel_names(jaxpr) -> list[str]:
    """Names of the pallas_calls in a (closed) jaxpr, in program order,
    through nested jits."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _pallas_kernel_names(sub)
    return out


def _node_scopes(jaxpr) -> list[str]:
    """The node scope ("<op>:<id>") of each top-level equation, in order,
    without repeats."""
    return list(dict.fromkeys(
        str(e.source_info.name_stack).split("/")[0] for e in jaxpr.eqns))


def test_layer_spans_match_plan_node_ids_mbv2():
    """Satellite 3: on MobileNet-v2 the served jitted program evaluates
    every graph node under its named scope "<op>:<id>", in execution
    order, and names each Pallas kernel "<family>__<op>__<id>" after the
    node and its executor -- and after replace_layer the rebuilt program
    follows the new executor (the im2col fallback runs no Pallas kernel)."""
    res = 32
    specs = cnn.NETWORKS["mobilenet_v2"][0]()
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=res)
    x = np.zeros((1, res, res, 3), np.float32)
    srv = Server(params, specs, res=res, algorithm="pallas_winograd",
                 config=make_cfg(buckets=(1,)))
    if True:
        net = srv.nets[1]
        table = net.describe()
        nodes = [f"{n.op}:{n.id}" for n in net.graph[1:]]

        def program():
            jaxpr = jax.make_jaxpr(net.apply)(x).jaxpr
            return (_node_scopes(jaxpr),
                    [k.split("__") for k in _pallas_kernel_names(jaxpr)])

        scopes, kernels = program()
        assert scopes == nodes
        ops = {n.id: n.op for n in net.graph}
        pallas = [nid for nid, p in net.plans.items()
                  if any(e.startswith("pallas") or e.endswith("streamed")
                         for e in p.describe()["executor"].split("+"))]
        # a label names one node, or the nodes whose kernels are alike
        assert list(dict.fromkeys(nid for _, _, label in kernels
                                  for nid in label.split("-"))) == pallas
        for family, op, label in kernels:
            assert all(nid in table and op == ops[nid]
                       for nid in label.split("-"))
        assert [f for f, _, label in kernels if label == "conv1"] == \
            ["winograd_strided_streamed"]

        # evict the stem conv onto the fallback; the program must follow
        assert srv._replace_layer("conv1", reason="test")
        assert net.plans["conv1"].describe()["executor"] == "im2col"
        scopes, after = program()
        assert scopes == nodes
        assert [k for k in after if k[2] == "conv1"] == []
        assert after == [k for k in kernels if k[2] != "conv1"]


def test_kernel_name_follows_node_scope():
    """A kernel is named after the node scope it is called in (or the
    label the node gives), the same eagerly and traced (so the jitted walk
    reuses the eager warm-up's kernel traces), and after its family alone
    outside any node."""
    from repro.kernels.runtime import kernel_name, node_scope
    assert kernel_name("matmul") == "matmul"
    with node_scope("conv2d", "c1"):
        assert kernel_name("matmul") == "matmul__conv2d__c1"
        names = []
        jax.make_jaxpr(lambda v: names.append(kernel_name("matmul")) or v)(
            jnp.ones(3))
        assert names == ["matmul__conv2d__c1"]
        with node_scope("inverted_residual", "ir5", "ir5-ir6"):
            assert kernel_name("separable_streamed") == \
                "separable_streamed__inverted_residual__ir5-ir6"
    assert kernel_name("matmul") == "matmul"


def test_hlo_carries_plan_node_ids_in_order():
    """The lowered program of a small MobileNet-v2 names every plan node,
    in execution order, in its ops' locations (the HLO op_name metadata a
    device trace carries)."""
    import re
    res = 32
    specs = cnn.NETWORKS["mobilenet_v2"][0]()
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=res)
    net = C.compile(params, specs, res=res, batch=1, algorithm="auto")
    x = jax.ShapeDtypeStruct((1, res, res, 3), jnp.float32)
    module = jax.jit(net.apply).lower(x).compiler_ir("stablehlo")
    main = next(op for op in module.body.operations
                if str(op.attributes["sym_name"]) == '"main"')
    seen = []
    for op in main.regions[0].blocks[0].operations:
        m = re.search(r"jit\(apply\)/([^/\"]+)", str(op.location))
        if m and m.group(1) not in seen:
            seen.append(m.group(1))
    assert seen == [f"{n.op}:{n.id}" for n in net.graph[1:]]
    assert [s.split(":")[1] for s in seen if s.split(":")[1]
            in net.plans] == list(net.plans)


def test_compile_and_autotune_spans(params):
    """compile() phases and the measured autotune race land in the trace."""
    trace.enable()
    trace.get().clear()
    C.compile(params, SPECS, res=RES, batch=1, algorithm="auto_tuned")
    names = {s.name for s in trace.get().spans()}
    for phase in ("compile.lower", "compile.fuse", "compile.infer_shapes",
                  "compile.place", "compile.bind"):
        assert phase in names, names
    races = trace.get().spans("plan.autotune.race")
    assert races and "winner" in races[0].args
    trace.disable()


# ---------------------------------------------------------------------------
# verify-artifacts CLI (satellite 2)
# ---------------------------------------------------------------------------

def test_verify_artifacts_cli(params, tmp_path, capsys):
    adir = str(tmp_path / "artifacts")
    with Server(params, SPECS, res=RES, config=make_cfg(),
                artifact_dir=adir):
        pass
    names = sorted(os.listdir(adir))
    assert names == ["plan_b1.npz", "plan_b2.npz"], names

    assert serve_mod.main(["verify-artifacts", adir]) == 0
    out = capsys.readouterr().out
    assert "plan_b1.npz: OK" in out and "all digests verified" in out

    inject.flip_bit(os.path.join(adir, "plan_b2.npz"))
    assert serve_mod.main(["verify-artifacts", adir]) == 1
    out = capsys.readouterr().out
    assert "plan_b2.npz: CORRUPT" in out
    assert "plan_b1.npz: OK" in out
    assert "[CORRUPT" in out                 # the per-array status line

    assert serve_mod.main(["verify-artifacts",
                           str(tmp_path / "nope")]) == 2


# ---------------------------------------------------------------------------
# regression gate (benchmarks/regress.py over repro.obs.regress)
# ---------------------------------------------------------------------------

def _serving_doc(p50=10.0, dropped=0):
    return {"clean": [{"rate_rps": 20, "p50_ms": p50, "p99_ms": 3 * p50,
                       "mean_ms": p50, "throughput_rps": 19.0,
                       "dropped": dropped, "incorrect": 0}],
            "faults": [], "zero_dropped": dropped == 0,
            "zero_incorrect": True, "fault_survived": True}


def test_regress_cli_fails_on_2x_slowdown(tmp_path):
    import benchmarks.regress as cli
    base = tmp_path / "base.json"
    cur = tmp_path / "cur.json"
    base.write_text(json.dumps(_serving_doc(p50=10.0)))
    cur.write_text(json.dumps(_serving_doc(p50=10.5)))
    assert cli.main([str(base), str(cur)]) == 0      # within threshold
    cur.write_text(json.dumps(_serving_doc(p50=20.0)))
    assert cli.main([str(base), str(cur)]) == 1      # injected 2x
    assert cli.main([str(base), str(cur), "--warn-only"]) == 0
    assert cli.main([str(base), str(cur), "--threshold", "3.0"]) == 0


def test_regress_count_and_bool_gates_zero_tolerance(tmp_path):
    import benchmarks.regress as cli
    base = tmp_path / "base.json"
    cur = tmp_path / "cur.json"
    base.write_text(json.dumps(_serving_doc(dropped=0)))
    cur.write_text(json.dumps(_serving_doc(dropped=1)))
    assert cli.main([str(base), str(cur)]) == 1      # any drop regresses


def test_regress_observe_format_machine_relative():
    ob = {"format": "repro.observe/v1", "overhead_pct": 1.0,
          "p50_disabled_ms": 100.0,
          "decomposition": {"max_residual_pct": 0.1},
          "gates": {"valid_chrome_trace": True}}
    worse = dict(ob, overhead_pct=9.0, p50_disabled_ms=900.0)
    findings = {f.metric: f for f in regress.compare(ob, worse)}
    assert findings["observe.overhead_pct"].regressed        # +8 points
    # absolute latency is informational: 9x slower machine, no gate
    assert not findings["observe.p50_disabled_ms"].regressed
    ok = dict(ob, overhead_pct=3.0)
    assert not any(f.regressed for f in regress.compare(ob, ok))
    broken = dict(ob, gates={"valid_chrome_trace": False})
    fs = {f.metric: f for f in regress.compare(ob, broken)}
    assert fs["observe.gate.valid_chrome_trace"].regressed


def test_regress_trajectory_pairs_committed_with_ci(tmp_path):
    import benchmarks.regress as cli
    root = tmp_path / "root"
    ci = tmp_path / "ci"
    root.mkdir(), ci.mkdir()
    (root / "BENCH_PR7.json").write_text(json.dumps(_serving_doc(10.0)))
    (ci / "BENCH_PR7_ci_x.json").write_text(
        json.dumps(_serving_doc(40.0)))
    # absolute serving metrics across machines: warn-only -> exit 0
    assert cli.main(["--trajectory", str(ci), "--root", str(root)]) == 0
    # --strict gates them
    assert cli.main(["--trajectory", str(ci), "--root", str(root),
                     "--strict"]) == 1
    # an observe-format pair gates hard without --strict
    ob = {"format": "repro.observe/v1", "overhead_pct": 1.0,
          "gates": {"g": True}, "decomposition": {"max_residual_pct": 0.1}}
    (root / "BENCH_PR10.json").write_text(json.dumps(ob))
    (ci / "BENCH_PR10_ci_y.json").write_text(
        json.dumps(dict(ob, gates={"g": False})))
    assert cli.main(["--trajectory", str(ci), "--root", str(root)]) == 1


# ---------------------------------------------------------------------------
# fleet tuning DB: export -> install -> zero-measurement adoption
# ---------------------------------------------------------------------------

def test_tuningdb_roundtrip_skips_measurement(params):
    net = C.compile(params, SPECS, res=RES, batch=1,
                    algorithm="auto_tuned")
    assert plan.plan_cache_info()["measured"] > 0
    db = tuningdb.export([net])
    assert db["format"] == "repro.tuning_db"
    assert len(db["entries"]) == 2

    plan.clear_plan_cache()
    assert tuningdb.install(db) == 2
    net2 = C.compile(params, SPECS, res=RES, batch=1,
                     algorithm="auto_tuned")
    info = plan.plan_cache_info()
    assert info["measured"] == 0, info       # zero autotune measurements
    assert info["tuningdb_hits"] == 2, info
    for nid in net.plans:
        assert net.plans[nid].describe()["executor"] == \
            net2.plans[nid].describe()["executor"]
    x = jnp.zeros((1, RES, RES, 3), jnp.float32)
    np.testing.assert_allclose(np.asarray(net.apply(x)),
                               np.asarray(net2.apply(x)), atol=1e-5)
    # adopted decisions carry provenance + stay artifact-durable
    meta = net2.plans[next(iter(net2.plans))].describe()
    assert meta["decision"] != "static"


def test_tuningdb_merge_prefers_faster_evidence(params):
    net = C.compile(params, SPECS, res=RES, batch=1,
                    algorithm="auto_tuned")
    db = tuningdb.export([net])
    k, entry = next(iter(db["entries"].items()))
    slower = json.loads(json.dumps(db))
    slower["entries"][k]["winner_time_s"] = entry["winner_time_s"] * 10
    slower["entries"][k]["winner_label"] = "slow_variant"
    merged = tuningdb.merge(db, slower)
    assert merged["entries"][k]["winner_label"] == entry["winner_label"]
    merged2 = tuningdb.merge(slower, db)
    assert merged2["entries"][k]["winner_label"] == entry["winner_label"]


def test_tuningdb_fresh_process_zero_measurements(params, tmp_path):
    """Acceptance: a FRESH process compiling under REPRO_TUNING_DB adopts
    the exported placements with zero autotune measurements."""
    net = C.compile(params, SPECS, res=RES, batch=1,
                    algorithm="auto_tuned")
    db_path = str(tmp_path / "fleet_db.json")
    tuningdb.save(tuningdb.export([net]), db_path)
    placement = {nid: net.plans[nid].describe()["executor"]
                 for nid in net.plans}

    prog = (
        "import json, jax\n"
        "from repro.core import compile as C, plan\n"
        "from repro.models import cnn\n"
        "specs = [cnn.Conv('c1', 3, 3, 8),"
        " cnn.Conv('c2', 3, 3, 8, relu=False)]\n"
        f"params = cnn.init_cnn(jax.random.key(0), specs, 3, res={RES})\n"
        f"net = C.compile(params, specs, res={RES}, batch=1,"
        " algorithm='auto_tuned')\n"
        "info = plan.plan_cache_info()\n"
        "print(json.dumps({'measured': info['measured'],"
        " 'tuningdb_hits': info['tuningdb_hits'],"
        " 'placement': {n: net.plans[n].describe()['executor']"
        " for n in net.plans}}))\n")
    env = dict(os.environ, REPRO_TUNING_DB=db_path,
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", prog], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["measured"] == 0, got
    assert got["tuningdb_hits"] == 2, got
    assert got["placement"] == placement


def test_tuningdb_rejects_unknown_and_foreign_entries(params):
    """DB entries that don't validate against the live registry fall back
    to a local race instead of poisoning the plan."""
    net = C.compile(params, SPECS, res=RES, batch=1,
                    algorithm="auto_tuned")
    db = tuningdb.export([net])
    for entry in db["entries"].values():
        entry["winner"] = "no_such_executor"
    plan.clear_plan_cache()
    tuningdb.install(db)
    C.compile(params, SPECS, res=RES, batch=1, algorithm="auto_tuned")
    info = plan.plan_cache_info()
    assert info["tuningdb_hits"] == 0
    assert info["measured"] > 0              # raced locally instead
