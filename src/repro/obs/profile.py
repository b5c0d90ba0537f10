"""The Profiler: the per-request view of the serving hot path.

`enable()` installs the global tracer (repro.obs.trace) and a `Profiler`
that `runtime/serve.py` consults via `active()` -- one global read per
batch, None when profiling is off. compile() pass phases and plan-cache /
autotune-race events in core/plan.py and core/compile.py report through
the same global tracer directly, so enabling the profiler lights up the
whole stack: plan -> compile -> serve in one trace.

The server's own phase spans (`serve.batch` and its children, see
runtime/serve.py) are live `trace.span`s: they record here while the
tracer is enabled and land in a JAX profiler trace while one records.
What only the profiler adds is the per-request decomposition
(`serve_batch`): the server hands over the batch's boundary stamps --
submit (per ticket), batch selection, dispatch start/end, finish (per
ticket) -- and the profiler turns them into spans per request:

    serve.queue_wait        submit -> batch selection
    serve.batch_formation   selection -> dispatch start
    serve.respond           dispatch end -> ticket finish

With the batch's dispatch interval (its live `serve.h2d` through
`serve.await` spans) between them, they tile [submit, finish] (same
perf_counter clock, shared stamps), so per request they sum to the
measured latency -- the contract tests/test_obs.py asserts. These spans
are recorder-only: they are made after the batch, so a profiler trace
does not hold them.

Latency and queue-wait histograms go to the default metrics registry
under `serve.*`.
"""

from __future__ import annotations

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

__all__ = ["Profiler", "enable", "disable", "active", "is_enabled"]


class Profiler:
    """Span + histogram emission for one process; see module docstring."""

    def __init__(self, tracer: _trace.Tracer,
                 registry: _metrics.MetricsRegistry | None = None):
        self.tracer = tracer
        self.registry = registry or _metrics.registry()

    # ---- the serve hot path ----------------------------------------------

    def serve_batch(self, *, bucket: int, batch: list, t_select: float,
                    t0: float, t1: float) -> None:
        """Record one dispatched batch's requests. `batch` is the ticket
        list (rid / submitted_at / finished_at), `t_select` the
        batch-selection stamp from the scheduler loop, [t0, t1] the
        dispatch interval."""
        tr, reg = self.tracer, self.registry
        for t in batch:
            rid = t.rid
            tr.add_span("serve.queue_wait", t.submitted_at, t_select,
                        rid=rid, bucket=bucket)
            tr.add_span("serve.batch_formation", t_select, t0,
                        rid=rid, bucket=bucket)
            reg.observe("serve.queue_wait_s", t_select - t.submitted_at)
            fin = t.finished_at
            if fin is not None:
                tr.add_span("serve.respond", t1, fin, rid=rid,
                            bucket=bucket)
                reg.observe("serve.latency_s", fin - t.submitted_at)

    def serve_batch_error(self, *, bucket: int, batch: list,
                          error: BaseException) -> None:
        self.tracer.instant("serve.batch_error", bucket=bucket,
                            batch=len(batch), error=repr(error))
        self.registry.count("serve.batch_errors")


# ---------------------------------------------------------------------------
# Global profiler: disabled (None) by default
# ---------------------------------------------------------------------------

_PROFILER: Profiler | None = None


def enable(capacity: int = _trace.DEFAULT_CAPACITY,
           registry: _metrics.MetricsRegistry | None = None) -> Profiler:
    """Turn on profiling: installs the global tracer (lighting up the
    compile/plan spans too) and the serve-path profiler."""
    global _PROFILER
    tracer = _trace.enable(capacity)
    if _PROFILER is None or _PROFILER.tracer is not tracer:
        _PROFILER = Profiler(tracer, registry)
    return _PROFILER


def disable(tracing: bool = True) -> None:
    """Turn the profiler off; `tracing=False` keeps the tracer (and its
    recorded spans) alive for inspection/export."""
    global _PROFILER
    _PROFILER = None
    if tracing:
        _trace.disable()


def active() -> Profiler | None:
    """The serve path's single disabled-check: None when profiling is off."""
    return _PROFILER


def is_enabled() -> bool:
    return _PROFILER is not None
