"""Fault-tolerant serving runtime: admission/backpressure, bucketed
batching, deadlines, and the supervisor's degrade ladder (retry ->
registry re-placement -> recompile-in-place), each driven by the
deterministic fault injectors in repro.runtime.inject, plus the per-array
artifact checksum gate in repro.core.compile."""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import compile as C
from repro.core.compile import (ArtifactMismatchError, LayerExecutionError,
                                NetworkPlan, verify_artifact)
from repro.core.plan import plan_cache_info
from repro.models import cnn
from repro.runtime import inject
from repro.runtime.serve import QueueFullError, ServeConfig, Server

RES = 16
SPECS = [cnn.Conv("c1", 3, 3, 8), cnn.Conv("c2", 3, 3, 8, relu=False)]


@pytest.fixture
def params():
    return cnn.init_cnn(jax.random.key(0), SPECS, 3, res=RES)


@pytest.fixture
def xs(rng):
    return [rng.standard_normal((RES, RES, 3)).astype(np.float32)
            for _ in range(6)]


def make_cfg(**kw):
    base = dict(buckets=(1, 2, 4), queue_capacity=8, verbose=False,
                backoff_base_s=0.002, backoff_cap_s=0.01)
    base.update(kw)
    return ServeConfig(**base)


def oracle_outputs(params, xs):
    net = C.compile(params, SPECS, res=RES, batch=1, algorithm="im2col")
    return [np.asarray(net.apply(jnp.asarray(x[None])))[0] for x in xs]


def assert_close(y, ref, tol=2e-3):
    err = np.max(np.abs(y - ref)) / (np.max(np.abs(ref)) + 1e-9)
    assert err < tol, err


# ---------------------------------------------------------------------------
# per-array artifact checksums (satellite: save/load integrity)
# ---------------------------------------------------------------------------

def test_artifact_checksums_roundtrip(params, tmp_path):
    path = str(tmp_path / "net.npz")
    net = C.compile(params, SPECS, res=RES, algorithm="winograd")
    net.save(path)
    assert verify_artifact(path) == []
    loaded = NetworkPlan.load(path)
    x = jnp.zeros((1, RES, RES, 3), jnp.float32)
    assert np.array_equal(np.asarray(net.apply(x)),
                          np.asarray(loaded.apply(x)))


def test_bitflip_fails_integrity_digest(params, tmp_path):
    path = str(tmp_path / "net.npz")
    C.compile(params, SPECS, res=RES, algorithm="winograd").save(path)
    bad = inject.flip_bit(path)
    assert [bad] == verify_artifact(path)
    with pytest.raises(ArtifactMismatchError,
                       match="integrity digest.*recompile"):
        NetworkPlan.load(path)


def test_corrupt_artifact_recompiles_and_repairs(params, tmp_path):
    """The satellite's corrupt-artifact -> recompile-and-repair contract:
    compile(artifact=) over a bit-flipped file must cold-compile (one
    artifact miss), produce correct outputs, and leave a repaired artifact
    behind."""
    path = str(tmp_path / "net.npz")
    ref = C.compile(params, SPECS, res=RES, algorithm="winograd",
                    artifact=path)
    x = jnp.zeros((1, RES, RES, 3), jnp.float32)
    y_ref = np.asarray(ref.apply(x))
    inject.flip_bit(path)
    before = plan_cache_info()
    net = C.compile(params, SPECS, res=RES, algorithm="winograd",
                    artifact=path)
    after = plan_cache_info()
    assert after["artifact_misses"] == before["artifact_misses"] + 1
    assert np.array_equal(np.asarray(net.apply(x)), y_ref)
    assert verify_artifact(path) == []          # repaired on disk
    NetworkPlan.load(path)                       # and loadable again


# ---------------------------------------------------------------------------
# re-placement hook (core side of the degrade ladder)
# ---------------------------------------------------------------------------

def test_replace_layer_parity(params, xs):
    net = C.compile(params, SPECS, res=RES, batch=1, algorithm="winograd")
    x = jnp.asarray(xs[0][None])
    y_before = np.asarray(net.apply(x))
    assert net.plans["c1"].spec.algorithm != "im2col"
    net.replace_layer("c1", params, algorithm="im2col")
    assert net.plans["c1"].spec.algorithm == "im2col"
    assert_close(np.asarray(net.apply(x)), y_before)


def test_replace_layer_rejects_unknown_node_and_foreign_params(
        params, tmp_path):
    path = str(tmp_path / "net.npz")
    net = C.compile(params, SPECS, res=RES, algorithm="winograd",
                    artifact=path)
    with pytest.raises(ValueError, match="not a plan-bearing node"):
        net.replace_layer("nope", params)
    other = cnn.init_cnn(jax.random.key(1), SPECS, 3, res=RES)
    with pytest.raises(ValueError, match="params_digest mismatch"):
        net.replace_layer("c1", other)


def test_apply_annotates_layer_errors(params):
    net = C.compile(params, SPECS, res=RES, algorithm="winograd")
    inject.install(net, inject.ExecutorRaise("c2"))
    x = jnp.zeros((1, RES, RES, 3), jnp.float32)
    with pytest.raises(inject.InjectedExecutorError):
        net.apply(x)                             # default: raw error
    with pytest.raises(LayerExecutionError) as ei:
        net.apply(x, annotate_errors=True)
    assert ei.value.node_id == "c2"
    assert isinstance(ei.value.__cause__, inject.InjectedExecutorError)


# ---------------------------------------------------------------------------
# serving: the degrade ladder under injected faults
# ---------------------------------------------------------------------------

def test_executor_raise_replacement_parity(params, xs):
    """Permanent executor failure: retries burn out, the supervisor
    re-places the failing layer onto im2row across every bucket, and every
    in-flight request is answered with outputs matching the im2row
    oracle -- zero drops, zero incorrect responses."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg())
    srv.start()
    inject.install_on_server(srv, inject.ExecutorRaise("c1"))
    tickets = [srv.submit(x) for x in xs]
    ys = [t.result(timeout=120) for t in tickets]
    srv.stop()
    s = srv.stats
    assert s.replacements >= 1 and s.executor_failures >= 1
    assert s.failed == 0 and s.in_flight == 0
    for b in srv.buckets:
        assert srv.nets[b].plans["c1"].spec.algorithm == "im2col"
    for y, ref in zip(ys, oracle_outputs(params, xs)):
        assert_close(y, ref)


def test_transient_executor_raise_survived_by_retry(params, xs):
    """A fault that clears within the retry budget never escalates."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg())
    srv.start()
    inject.install_on_server(srv, inject.ExecutorRaise("c1", times=1))
    ys = [t.result(timeout=120) for t in [srv.submit(x) for x in xs]]
    srv.stop()
    assert srv.stats.retries >= 1 and srv.stats.replacements == 0
    assert srv.stats.failed == 0 and srv.stats.in_flight == 0
    for y, ref in zip(ys, oracle_outputs(params, xs)):
        assert_close(y, ref)


def test_recompile_rung_when_replacement_cannot_cure(params, xs,
                                                     monkeypatch):
    """When re-placement is unavailable the ladder's last rung recompiles
    every bucket plan from raw params -- which drops the fault proxies --
    and the batch still completes."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg())
    srv.start()
    monkeypatch.setattr(srv, "_replace_layer",
                        lambda *a, **k: False)
    inject.install_on_server(srv, inject.ExecutorRaise("c1"))
    ys = [t.result(timeout=120) for t in [srv.submit(x) for x in xs]]
    srv.stop()
    assert srv.stats.recompiles == 1
    assert srv.stats.failed == 0 and srv.stats.in_flight == 0
    for y, ref in zip(ys, oracle_outputs(params, xs)):
        assert_close(y, ref)


def test_queue_overload_bounded_rejection(params, xs):
    """Satellite: overload degrades into bounded rejection with a
    retry-after hint; every ADMITTED request is still served (zero
    drops)."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg(queue_capacity=4))
    accepted, rejected = [], 0
    for i in range(11):
        try:
            accepted.append(srv.submit(xs[i % len(xs)]))
        except QueueFullError as e:
            rejected += 1
            assert e.retry_after_s > 0 and e.capacity == 4
    assert len(accepted) == 4 and rejected == 7
    assert srv.stats.rejected == 7
    srv.start()
    ys = [t.result(timeout=120) for t in accepted]
    srv.stop()
    assert srv.stats.completed == 4 and srv.stats.in_flight == 0
    refs = oracle_outputs(params, [t.x for t in accepted])
    for y, ref in zip(ys, refs):
        assert_close(y, ref)


def test_straggler_eviction_counter(params, xs):
    """Satellite: an injected latency spike on one layer is flagged by the
    per-bucket StepTimer, attributed via per-layer times, and the layer is
    evicted onto the fallback executor after the configured count.
    Straggler attribution needs the eager supervised path's per-layer
    timing hooks, so the jitted dispatch fast path is disabled."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg(buckets=(2,), queue_capacity=64,
                                 jit_dispatch=False,
                                 straggler_window=16,
                                 straggler_min_baseline=5,
                                 straggler_evict_after=2, batch_wait_s=0.0))
    srv.start()
    for _ in range(8):                           # build the baseline
        [t.result(timeout=60) for t in [srv.submit(x) for x in xs[:2]]]
    inject.install_on_server(srv, inject.LatencySpike("c2", delay_s=0.3))
    for _ in range(6):
        [t.result(timeout=60) for t in [srv.submit(x) for x in xs[:2]]]
    srv.stop()
    s = srv.stats
    assert s.stragglers >= 2 and s.evictions >= 1
    assert srv.nets[2].plans["c2"].spec.algorithm == "im2col"
    assert s.failed == 0 and s.in_flight == 0


def test_deadline_timeout_cancellation(params, xs):
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg())
    expired = srv.submit(xs[0], deadline_s=0.0)   # dead before dispatch
    live = srv.submit(xs[1], deadline_s=60.0)
    srv.start()
    with pytest.raises(TimeoutError, match="deadline expired"):
        expired.result(timeout=60)
    assert_close(live.result(timeout=60), oracle_outputs(params, [xs[1]])[0])
    srv.stop()
    assert expired.status == "timeout" and srv.stats.timed_out == 1
    assert srv.stats.completed == 1 and srv.stats.in_flight == 0


def test_corrupt_bucket_artifact_repaired_at_startup(params, xs, tmp_path):
    """A bit-flipped bucket artifact is detected by the per-array checksums
    at server startup, recompiled in place, and serving proceeds with
    correct outputs; the repaired artifact warm-starts the next server."""
    art = str(tmp_path)
    cfg = make_cfg()
    srv = Server(params, SPECS, res=RES, algorithm="winograd", config=cfg,
                 artifact_dir=art)
    assert srv.stats.artifact_cold_starts == len(srv.buckets)
    del srv
    inject.flip_bit(os.path.join(art, "plan_b2.npz"))
    srv2 = Server(params, SPECS, res=RES, algorithm="winograd", config=cfg,
                  artifact_dir=art)
    assert srv2.stats.corrupt_artifacts == 1
    assert srv2.stats.corrupt_arrays >= 1
    assert srv2.stats.artifact_cold_starts == 1     # only the corrupt bucket
    assert srv2.stats.artifact_warm_starts == len(srv2.buckets) - 1
    assert verify_artifact(os.path.join(art, "plan_b2.npz")) == []
    srv2.start()
    ys = [t.result(timeout=120) for t in [srv2.submit(x) for x in xs]]
    srv2.stop()
    for y, ref in zip(ys, oracle_outputs(params, xs)):
        assert_close(y, ref)
    srv3 = Server(params, SPECS, res=RES, algorithm="winograd", config=cfg,
                  artifact_dir=art)
    assert srv3.stats.artifact_warm_starts == len(srv3.buckets)


def test_jit_dispatch_happy_path_counters(params, xs):
    """Satellite: fault-free traffic is served entirely by the jitted
    happy path (stats.jit_dispatches), no bucket ever falls back, and
    outputs match the eager oracle."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg())
    srv.start()
    ys = [t.result(timeout=120) for t in [srv.submit(x) for x in xs]]
    srv.stop()
    assert srv.stats.jit_dispatches >= 1
    assert srv.stats.jit_fallbacks == 0 and srv.stats.retries == 0
    for y, ref in zip(ys, oracle_outputs(params, xs)):
        assert_close(y, ref)


def test_probation_promotes_layer_back(params, xs):
    """Satellite: continuous re-placement. A permanent executor fault
    breaks the bucket's jitted path (counted in jit_fallbacks), the
    supervisor evicts the layer onto im2col, and after the probation
    window of clean batches a re-probe promotes it back onto winograd."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg(probation_batches=2))
    srv.start()
    inject.install_on_server(srv, inject.ExecutorRaise("c1"))
    [t.result(timeout=120) for t in [srv.submit(x) for x in xs]]
    assert srv.stats.replacements >= 1 and srv.stats.jit_fallbacks >= 1
    # serve clean singles until the probation window fills
    ys = []
    for _ in range(4):
        ys.append(srv.submit(xs[0]).result(timeout=120))
    srv.stop()
    s = srv.stats
    assert s.probation_reprobes >= 1 and s.probation_promotions == 1
    for b in srv.buckets:
        assert srv.nets[b].plans["c1"].spec.algorithm == "winograd"
    ref = oracle_outputs(params, [xs[0]])[0]
    for y in ys:
        assert_close(y, ref)
    assert s.failed == 0 and s.in_flight == 0


def test_probation_window_doubles_on_failed_probe(params, xs, monkeypatch):
    """A failed probation re-probe keeps the layer on the fallback and
    doubles its window instead of flapping."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg(probation_batches=1))
    srv.start()
    inject.install_on_server(srv, inject.ExecutorRaise("c1"))
    [t.result(timeout=120) for t in [srv.submit(x) for x in xs]]
    assert srv.stats.replacements >= 1

    def boom(*a, **k):
        raise RuntimeError("probe refused")
    monkeypatch.setattr(srv, "_fresh_plan", boom)
    for _ in range(2):
        srv.submit(xs[0]).result(timeout=120)
    srv.stop()
    s = srv.stats
    assert s.probation_reprobes >= 1 and s.probation_promotions == 0
    assert srv._probation["c1"]["need"] >= 2
    for b in srv.buckets:
        assert srv.nets[b].plans["c1"].spec.algorithm == "im2col"


def test_batches_form_across_buckets(params, xs):
    """Dynamic batch formation picks the smallest covering bucket; a
    pre-loaded queue of 6 forms a 4-batch plus a 2-batch."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg())
    tickets = [srv.submit(x) for x in xs]
    srv.start()
    [t.result(timeout=120) for t in tickets]
    srv.stop()
    assert srv.stats.bucket_batches == {4: 1, 2: 1}
    assert srv.stats.completed == 6 and srv.stats.in_flight == 0


# ---------------------------------------------------------------------------
# dispatching ahead: one jitted batch in flight while the next is prepared
# ---------------------------------------------------------------------------

def burst(rng, n=12):
    """`n` distinct inputs: three batches of the largest bucket (4)."""
    return [rng.standard_normal((RES, RES, 3)).astype(np.float32)
            for _ in range(n)]


def serve_burst(srv, xs, *, warmup=True):
    """Admit every input before the scheduler starts, so the batches form
    back to back, then answer them all."""
    tickets = [srv.submit(x) for x in xs]
    srv.start(warmup=warmup)
    ys = [t.result(timeout=120) for t in tickets]
    srv.stop()
    return tickets, ys


def test_dispatch_ahead_answers_each_ticket_its_own_rows(params, rng):
    """A burst of three largest-bucket batches runs one batch ahead on the
    jitted path, and every ticket still gets the answer of its own input,
    as the unpipelined eager path gives it."""
    xs = burst(rng)
    cfg = make_cfg(queue_capacity=16)
    ref = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg(queue_capacity=16, jit_dispatch=False))
    _, want = serve_burst(ref, xs)
    srv = Server(params, SPECS, res=RES, algorithm="winograd", config=cfg)
    tickets, ys = serve_burst(srv, xs)
    s = srv.stats
    # the second and third batches were enqueued behind an un-awaited one
    assert s.dispatched_ahead == 2 and s.jit_dispatches == 3
    assert s.bucket_batches == {4: 3}
    assert s.in_flight == 0 and s.failed == 0
    assert all(t.status == "ok" for t in tickets)
    for y, w in zip(ys, want):
        assert_close(y, w, tol=1e-5)


@pytest.mark.parametrize("where", ["enqueue", "await"])
def test_jit_fault_with_a_batch_ahead_loses_no_request(params, rng,
                                                       monkeypatch, where):
    """A jitted fault while a batch is ahead -- raised as the second batch
    is enqueued, or as the first is awaited -- breaks the bucket once, and
    both batches (and the third, behind them) are answered through the
    eager supervised path: no request fails or stays pending."""
    xs = burst(rng)
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg(queue_capacity=16))
    srv.warmup()
    eager_rows = []
    supervised = srv._supervised_apply

    def recording(bucket, X):
        eager_rows.extend(np.asarray(X))
        return supervised(bucket, X)
    monkeypatch.setattr(srv, "_supervised_apply", recording)
    if where == "enqueue":
        token, fn = srv._jit[4]
        calls = []

        def apply(X):
            calls.append(None)
            if len(calls) == 2:
                raise RuntimeError("injected fault at enqueue")
            return fn(X)
        srv._jit[4] = (token, apply)
    else:
        block = jax.block_until_ready
        fired = []

        def await_once(y):
            if not fired:
                fired.append(None)
                raise RuntimeError("injected fault at await")
            return block(y)
        monkeypatch.setattr(jax, "block_until_ready", await_once)
    tickets, ys = serve_burst(srv, xs, warmup=False)
    s = srv.stats
    assert s.jit_fallbacks == 1 and s.failed == 0 and s.in_flight == 0
    assert s.jit_dispatches == 0 and s.completed == len(xs)
    assert s.dispatched_ahead == (1 if where == "await" else 0)
    assert all(t.done() and t.status == "ok" for t in tickets)
    # every batch ran eagerly: each input went through the eager path once
    assert len(eager_rows) == len(xs)
    assert sorted(next(i for i, x in enumerate(xs) if np.array_equal(r, x))
                  for r in eager_rows) == list(range(len(xs)))
    for y, want in zip(ys, oracle_outputs(params, xs)):
        assert_close(y, want)


def test_service_time_excludes_the_batch_ahead(params, rng, monkeypatch):
    """A batch enqueued behind another is timed from the other's result,
    not from its own enqueue, so straggler detection and the service
    EWMA see one batch's device time, not two. Each await is made to take
    0.1 s, standing in for the device."""
    srv = Server(params, SPECS, res=RES, algorithm="winograd",
                 config=make_cfg(queue_capacity=16))
    srv.warmup()
    block = jax.block_until_ready

    def slow_device(y):
        time.sleep(0.1)
        return block(y)
    monkeypatch.setattr(jax, "block_until_ready", slow_device)
    times = []
    observe = srv._observe_stragglers

    def recording(bucket, dt, layer_times):
        times.append(dt)
        return observe(bucket, dt, layer_times)
    monkeypatch.setattr(srv, "_observe_stragglers", recording)
    serve_burst(srv, burst(rng), warmup=False)
    assert srv.stats.dispatched_ahead == 2 and len(times) == 3
    # two batches' device time would read 0.2 s
    assert all(0.1 <= dt < 0.17 for dt in times), times
