"""Plan/execute split for convolution: decide once, run many.

The paper's deployment insight (section 4) is that the fast Winograd /
Cook-Toom scheme only pays off once the GEMM phase amortizes the transform
phases -- and that the *filter* transform should never be on the inference
path at all: weights are transformed once, offline, and reused every step.

This module is that insight as an architecture:

  * `plan_conv2d(x_shape, w, ...)` makes every per-layer decision exactly
    once -- algorithm choice, CookToom pair, output tile, padding amounts,
    tile counts, Pallas block sizes -- and pre-transforms the filter into the
    execution domain (Winograd domain for the fast scheme, the flattened
    GEMM matrix for im2row).
  * `ConvPlan.apply(x)` executes with zero per-call filter or geometry work.
  * A process-level spec cache keyed on (shapes, dtype, stride, padding,
    algorithm, output tile) means repeated planning of the same layer shape
    is a dict hit; the cached spec carries the algorithm decision, so a
    measured `auto_tuned` choice is made once per shape per process.
  * `algorithm="auto_tuned"` is *plan-time measured autotuning*: both
    schemes are timed on the real layer shape and the winner is cached.
    The static amortization constants remain only as the fallback policy
    when measurement is impossible (planning inside a jit trace).
  * Which executor may run which layer is declared by the executors
    themselves in the capability registry (repro.core.registry); every
    algorithm choice and coverage error message here is a registry query.

`core.dispatch.conv2d` / `conv1d` stay as thin per-call wrappers over this
module for backward compatibility; model code (models/cnn.py, models/audio.py)
builds plans at init/weight-load time and executes them.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import logging
import os
import time
import typing
from typing import Any, Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fft as _fft
# observability: stdlib-only tracing/metrics (repro.obs.trace/metrics import
# nothing from repro.core, so this dependency edge is acyclic and free --
# every hook's disabled path is one global None check / one dict lookup).
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.core import im2col as _im2col
from repro.core import registry
from repro.core import winograd as _wg
from repro.core.registry import LayerQuery
from repro.core.transforms import (DEFAULT_OUTPUT_TILE, CookToom, cook_toom,
                                   scaled_cook_toom)
# Shared epilogue vocabulary, dependency-free (the heavy Pallas kernels in
# repro.kernels stay optional, imported locally where needed).
# EPILOGUE_ACTIVATIONS: the activations plan.apply(..., activation=) accepts
# (kernels/runtime.py is the single source of truth): the Pallas executors
# fuse these into the kernel store, the pure-JAX executors apply them as one
# XLA op (_epilogue_jnp).
from repro.kernels.runtime import ACTIVATIONS as EPILOGUE_ACTIVATIONS
from repro.kernels.runtime import epilogue_jnp as _epilogue_jnp

_LOG = logging.getLogger(__name__)

Algorithm = Literal["auto", "auto_tuned", "winograd", "winograd_f63", "fft",
                    "im2col", "pallas_winograd",
                    "pallas_winograd_materialized", "pallas_im2col"]
#: The requestable algorithm names, derived from the Literal so the type,
#: the resolver checks, and every unknown-algorithm error message agree.
ALGORITHMS: tuple[str, ...] = typing.get_args(Algorithm)
Padding = _wg.Padding

#: Filter sizes the paper's fast scheme covers (2D NxN and 1D 1xN / Nx1).
#: Declared by the executor registry; re-exported for compatibility.
WINOGRAD_FILTER_SIZES = registry.WINOGRAD_FILTER_SIZES

#: auto_tuned *fallback* crossover, used only when plan-time measurement is
#: impossible (planning under an active jit trace, or REPRO_PLAN_NO_MEASURE
#: set): winograd wins when the per-point GEMMs are large enough to amortize
#: the transform passes -- which needs BOTH enough regions (output pixels)
#: and enough channel depth (the GEMM's contraction dim). Calibrated on the
#: measured per-layer sweep (results/bench_per_layer.json; EXPERIMENTS.md
#: section Perf). The primary auto_tuned policy is the measured one below
#: (_measure_autotune): time both schemes on the real shape, cache the winner.
AMORTIZE_MIN_OUT_PIXELS = 1156            # 34 x 34
AMORTIZE_MIN_C_IN = 64


def spatial_halo(k: int) -> int:
    """Rows of neighbor overlap a stride-1 SAME kxk conv needs on each side
    of a contiguous H strip to produce that strip's output rows exactly --
    the cross-device analogue of the halo-strip overlap stream_geometry
    derives per tile. Spatial partitioning (core/partition.py) exchanges
    this many rows between mesh neighbors and binds the local plan VALID."""
    return (k - 1) // 2


def winograd_suitable(kh: int, kw: int, stride) -> bool:
    """Whether some winograd-family executor covers this filter/stride
    combination (a registry query; kept as the historical entry point).
    Since the stride-2 phase-decomposition executors registered, suitable
    no longer means stride (1, 1)."""
    q = registry.as_query(kh, kw, stride)
    return registry.best_fast(q) is not None


def winograd_amortizes(h: int, w: int, kh: int, kw: int, c_in: int,
                       padding: str = "SAME", groups: int = 1,
                       stride=1) -> bool:
    """The paper's section-4 amortization insight as a static predicate --
    the auto_tuned fallback when plan-time measurement is unavailable.

    For grouped convs the GEMM contraction depth is the per-group channel
    count C/G, so that is what must clear the channel threshold. Depthwise
    (G == C) has no channel GEMM to amortize at all -- it is memory-bound
    (Zhang et al. 2020) and the transform passes pay for themselves on
    spatial extent alone, so only the output-pixel threshold applies."""
    sh, sw = (stride, stride) if isinstance(stride, int) else tuple(stride)
    out_h = -(-h // sh) if padding == "SAME" else (h - kh) // sh + 1
    out_w = -(-w // sw) if padding == "SAME" else (w - kw) // sw + 1
    if out_h * out_w < AMORTIZE_MIN_OUT_PIXELS:
        return False
    if groups > 1 and groups == c_in:     # depthwise
        return True
    return c_in // groups >= AMORTIZE_MIN_C_IN


def algorithm_supported(algorithm: str, kh: int, kw: int, stride,
                        *, groups: int = 1, c_in: int | None = None,
                        c_out: int | None = None,
                        layout: str = "NHWC") -> bool:
    """Whether plan_conv2d would accept this (algorithm, layer) combination
    without raising -- a registry query over the capabilities the executors
    declare. Model-level fallback policies (models/cnn.py:_layer_algorithm)
    consult this instead of duplicating the constraint list."""
    q = registry.as_query(kh, kw, stride, groups=groups, c_in=c_in,
                          c_out=c_out, layout=layout)
    return registry.supported(algorithm, q)


# ---------------------------------------------------------------------------
# Specs: the cacheable, weight-free part of a plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Everything about a planned conv layer except the weights: the resolved
    algorithm, transform variant, geometry, and kernel blocking. Hashable and
    shape-keyed, so it lives in the process-level plan cache."""

    x_shape: tuple[int, ...]          # (N, H, W, C) the plan was built for
                                      # (always NHWC internally; see layout)
    w_shape: tuple[int, ...]          # (kh, kw, C/groups, M)
    dtype: str
    stride: tuple[int, int]
    padding: str
    requested: str                    # the algorithm= the caller asked for
    algorithm: str                    # resolved executor (a registry
                                      # Capability.executor name): winograd |
                                      # winograd_1d | winograd_depthwise |
                                      # winograd_grouped | winograd_strided |
                                      # winograd_f63 | fft | im2col |
                                      # pallas_winograd | pallas_depthwise |
                                      # pallas_winograd_strided |
                                      # pallas_depthwise_strided |
                                      # pallas_winograd_materialized |
                                      # pallas_im2col
    groups: int = 1                   # feature_group_count (1 = dense,
                                      # C = depthwise)
    layout: str = "NHWC"              # caller-facing data format; "NCHW"
                                      # plans transpose weights once at plan
                                      # time and apply() transposes x/y at
                                      # the boundary
    compute_dtype: str = "float32"    # transform-domain GEMM/Hadamard dtype
                                      # (registry.COMPUTE_DTYPES). Input and
                                      # inverse transforms always run fp32;
                                      # bf16/int8 only change the cached
                                      # filter operand -- int8 carries
                                      # per-output-channel scales folded
                                      # into the epilogue (ConvPlan.scale)
    output_tile: tuple[int, int] | None = None
    ct_h: CookToom | None = None
    ct_w: CookToom | None = None      # also the single CT of the 1D variant
    geometry: Any = None              # Conv2DGeometry | Axis1DGeometry |
                                      # Im2RowGeometry
    axis: int | None = None           # 1xN / Nx1: the non-unit spatial axis
    blocks: tuple[int, ...] | None = None        # Pallas block sizes
    stream: Any = None                # StreamGeometry (halo blocking) of the
                                      # streaming pallas_winograd executor
    fft: Any = None                   # fft.FFTGeometry of the rfft2 executor
                                      # (re-derived from output_tile on
                                      # artifact reload)
    autotune: tuple | None = None     # (("t_winograd_s", ...), ...) measured
                                      # evidence behind an auto_tuned choice

    @property
    def autotune_report(self) -> dict | None:
        return dict(self.autotune) if self.autotune is not None else None


# ---------------------------------------------------------------------------
# Process-level spec cache
# ---------------------------------------------------------------------------

_SPEC_CACHE: dict[tuple, ConvSpec] = {}
_CACHE_HITS = 0
_CACHE_MISSES = 0
# Serialized-plan (NetworkPlan artifact) load counters: a hit is a
# successful NetworkPlan.load / compile(..., artifact=) warm start, a miss
# is a load that had to fall back to a cold compile (file absent, header
# mismatch). Maintained by repro.core.compile via record_artifact_load.
_ARTIFACT_HITS = 0
_ARTIFACT_MISSES = 0
# auto_tuned resolution accounting: 'measured' counts decisions backed by a
# plan-time N-way timing race, 'fallback' counts auto_tuned resolutions made
# WITHOUT measurement (heuristic under a jit trace / REPRO_PLAN_NO_MEASURE,
# or the sole-candidate im2col case). Plans rebuilt from a NetworkPlan
# artifact increment neither -- the zero-re-measurement contract of warm
# loads is asserted against these counters in tests.
_MEASURED = 0
_FALLBACK = 0
# Plan-time weight-quantization accounting: one count per int8
# _bind_weights pass (bf16 casts are free and not counted). Warm artifact
# loads take the quantized payload verbatim, so the zero-re-quantization
# contract of NetworkPlan.load is asserted against this counter in tests.
_QUANTIZED = 0
# Fleet tuning-database accounting: auto_tuned layers resolved from an
# installed tuning database (repro.obs.tuningdb) -- adopted measured
# evidence, zero local measurements. Such a resolution counts neither
# 'measured' nor 'fallback'.
_TUNINGDB_HITS = 0


def plan_cache_info() -> dict:
    """{'hits', 'misses', 'size'} of the process-level spec cache, plus
    {'artifact_hits', 'artifact_misses'} of serialized-plan loads
    (repro.core.compile.NetworkPlan.save/load warm starts),
    {'measured', 'fallback'} auto_tuned resolution counts (measured timing
    race vs the no-measurement fallback path), {'tuningdb_hits'} auto_tuned
    resolutions adopted from an installed fleet tuning database
    (repro.obs.tuningdb -- zero local measurements), and {'quantized'}
    plan-time int8 weight-quantization passes."""
    return {"hits": _CACHE_HITS, "misses": _CACHE_MISSES,
            "size": len(_SPEC_CACHE),
            "artifact_hits": _ARTIFACT_HITS,
            "artifact_misses": _ARTIFACT_MISSES,
            "measured": _MEASURED, "fallback": _FALLBACK,
            "tuningdb_hits": _TUNINGDB_HITS,
            "quantized": _QUANTIZED}


def _record_autotune_resolution(measured: bool) -> None:
    global _MEASURED, _FALLBACK
    if measured:
        _MEASURED += 1
        _obs_metrics.count("plan.autotune.measured")
    else:
        _FALLBACK += 1
        _obs_metrics.count("plan.autotune.fallback")


def record_artifact_load(hit: bool) -> None:
    """Count one serialized-plan load attempt (see plan_cache_info)."""
    global _ARTIFACT_HITS, _ARTIFACT_MISSES
    if hit:
        _ARTIFACT_HITS += 1
        _obs_metrics.count("plan.artifact.hit")
    else:
        _ARTIFACT_MISSES += 1
        _obs_metrics.count("plan.artifact.miss")


def clear_plan_cache() -> None:
    global _CACHE_HITS, _CACHE_MISSES, _ARTIFACT_HITS, _ARTIFACT_MISSES, \
        _MEASURED, _FALLBACK, _QUANTIZED, _TUNINGDB_HITS
    _SPEC_CACHE.clear()
    _CACHE_HITS = 0
    _CACHE_MISSES = 0
    _ARTIFACT_HITS = 0
    _ARTIFACT_MISSES = 0
    _MEASURED = 0
    _FALLBACK = 0
    _QUANTIZED = 0
    _TUNINGDB_HITS = 0


def _cache_enabled() -> bool:
    return not os.environ.get("REPRO_PLAN_NO_CACHE")


def _count_cache(hit: bool) -> None:
    """Spec-cache accounting, mirrored into the default metrics registry
    (plan.cache.hit / plan.cache.miss) so the observability snapshot and
    plan_cache_info() tell one story."""
    global _CACHE_HITS, _CACHE_MISSES
    if hit:
        _CACHE_HITS += 1
        _obs_metrics.count("plan.cache.hit")
    else:
        _CACHE_MISSES += 1
        _obs_metrics.count("plan.cache.miss")


def _measure_allowed(w) -> bool:
    """Measured autotuning needs concrete execution: it is disabled when the
    filter being planned is a tracer (planning inside a jit trace) and via
    REPRO_PLAN_NO_MEASURE."""
    if os.environ.get("REPRO_PLAN_NO_MEASURE"):
        return False
    return not isinstance(w, jax.core.Tracer)


# ---------------------------------------------------------------------------
# Fleet tuning database: adopt measured auto_tuned evidence without racing
# ---------------------------------------------------------------------------

#: installed database entries ({tuning_db_key: entry}) -- see
#: repro.obs.tuningdb for the export/merge/install pipeline. None means
#: "no database": plan_conv2d measures (or falls back) as always.
_TUNING_DB: dict[str, dict] | None = None
#: last REPRO_TUNING_DB path auto-loaded, so a bad/changed path is only
#: attempted once per value.
_TUNING_DB_ENV_PATH: str | None = None


def tuning_db_key(x_shape, w_shape, dtype: str, stride, padding: str,
                  groups: int, layout: str, compute_request: str,
                  output_tile=None) -> str:
    """The canonical database key: every plan_conv2d input that decides an
    auto_tuned race. `compute_request` is the caller's compute_dtype
    REQUEST ("auto" when reduced-precision contenders were fielded), not
    the resolved winner dtype; `output_tile` the requested (not tuned)
    tile."""
    if output_tile is None:
        ot = None
    elif isinstance(output_tile, (tuple, list)):
        ot = [int(v) for v in output_tile]
    else:
        ot = [int(output_tile), int(output_tile)]
    return json.dumps(
        [list(x_shape), list(w_shape), str(dtype),
         list(stride) if isinstance(stride, (tuple, list))
         else [stride, stride],
         str(padding), int(groups), str(layout), str(compute_request), ot],
        separators=(",", ":"))


def set_tuning_db(entries: dict | None) -> None:
    """Install (or with None remove) tuning-database entries. Entries stay
    installed across clear_plan_cache() -- the database is configuration,
    not cache state."""
    global _TUNING_DB
    _TUNING_DB = dict(entries) if entries is not None else None


def tuning_db() -> dict | None:
    _maybe_load_env_tuning_db()
    return _TUNING_DB


def _maybe_load_env_tuning_db() -> None:
    global _TUNING_DB, _TUNING_DB_ENV_PATH
    path = os.environ.get("REPRO_TUNING_DB")
    if _TUNING_DB is not None or not path or path == _TUNING_DB_ENV_PATH:
        return
    _TUNING_DB_ENV_PATH = path
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("format") == "repro.tuning_db":
            _TUNING_DB = dict(doc.get("entries") or {})
    except (OSError, ValueError):
        pass                     # unreadable database == no database


def _tuningdb_lookup(x_shape, w_shape, dtype: str, stride, padding: str,
                     groups: int, layout: str, compute_request: str,
                     output_tile) -> tuple | None:
    """A validated database resolution shaped exactly like
    _measure_autotune's return -- (winner, winner_tile, winner_dtype,
    evidence) -- or None (no database / no entry / entry names an
    executor or dtype this registry no longer covers)."""
    global _TUNINGDB_HITS
    _maybe_load_env_tuning_db()
    if _TUNING_DB is None:
        return None
    entry = _TUNING_DB.get(tuning_db_key(
        x_shape, w_shape, dtype, stride, padding, groups, layout,
        compute_request, output_tile))
    if not entry:
        return None
    winner = entry.get("winner")
    winner_dtype = str(entry.get("winner_dtype", "float32"))
    known = {cap.executor for cap in registry.CAPABILITIES}
    if winner not in known or \
            winner_dtype not in registry.compute_dtypes_for(winner):
        return None               # stale fleet evidence: race locally
    if compute_request not in ("auto", "float32") and \
            compute_request not in registry.compute_dtypes_for(winner):
        return None               # winner can't serve the pinned dtype
    tile = entry.get("winner_tile")
    evidence = tuple(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in (entry.get("evidence") or []) if k != "source")
    evidence += (("source", "tuning_db"),)
    _TUNINGDB_HITS += 1
    _obs_metrics.count("plan.autotune.tuningdb_hit")
    _obs_trace.instant("plan.autotune.tuningdb_hit", winner=winner,
                       layer=f"{tuple(x_shape)}x{tuple(w_shape)}")
    return winner, tuple(tile) if tile else None, winner_dtype, evidence


# ---------------------------------------------------------------------------
# Spec construction (all per-layer decisions happen here, once)
# ---------------------------------------------------------------------------

def _resolve_output_tile(kh: int, kw: int, output_tile) -> tuple[int, int]:
    if output_tile is None:
        mt = DEFAULT_OUTPUT_TILE.get(max(kh, kw), 2)
        return (mt, mt)
    if isinstance(output_tile, int):
        return (output_tile, output_tile)
    return tuple(output_tile)


#: Shape thresholds below/above which the stride-2 executors default to the
#: F(2, r_ph) tile set instead of F(4, r_ph). The larger tile cuts the
#: per-output multiply count (4 * t^2/m^2 phase points per output) but its
#: four t=5 phase banks quadruple the transformed-input cache, so on small
#: output grids the point-GEMMs are too thin to amortize the transforms and
#: on deep layers the VMEM budget forces tiny region blocks. Calibrated on
#: the stride-2 reduction-block ladder (BENCH_PR4.json; EXPERIMENTS.md
#: section Perf): F(4, .) wins only on large-spatial shallow layers.
STRIDED_TILE4_MIN_OUT = 24
STRIDED_TILE4_MAX_C = 64


def _resolve_strided_tile(h: int, w: int, kh: int, kw: int, padding,
                          output_tile, c_in: int) -> tuple[int, int]:
    """Output tile of the stride-2 phase algorithm (per-axis F(m, r_ph),
    r_ph = (k+1)//2): explicit request wins; the default is shape-aware --
    F(4, .) on large-spatial shallow layers, F(2, .) everywhere else."""
    if output_tile is not None:
        if isinstance(output_tile, int):
            return (output_tile, output_tile)
        return tuple(output_tile)
    out_h = _wg.strided_out_size(h, kh, padding)
    out_w = _wg.strided_out_size(w, kw, padding)
    mt = 4 if (min(out_h, out_w) >= STRIDED_TILE4_MIN_OUT
               and c_in <= STRIDED_TILE4_MAX_C) else 2
    return (mt, mt)


def _build_spec(x_shape, w_shape, dtype, stride, padding, requested,
                resolved, output_tile, groups: int = 1,
                layout: str = "NHWC",
                compute_dtype: str = "float32") -> ConvSpec:
    """Materialize geometry/transform/blocking decisions for one resolved
    algorithm."""
    n, h, w, c = x_shape
    kh, kw, _, mout = w_shape
    base = dict(x_shape=tuple(x_shape), w_shape=tuple(w_shape), dtype=dtype,
                stride=stride, padding=padding, requested=requested,
                groups=groups, layout=layout, compute_dtype=compute_dtype)

    if (compute_dtype != "float32" and output_tile is None
            and resolved not in ("winograd_f63", "fft", "im2col",
                                 "pallas_im2col")):
        # Low-precision grids pair with the small tile: the transform-domain
        # dynamic range grows with tile size, and F(4,3)'s inverse transform
        # amplifies the bf16/int8 quantization grid past any useful budget
        # (measured ~1.4 rel max-abs err for int8 at F(4,3) vs ~0.02 at
        # F(2,3)). An explicit output_tile still wins.
        output_tile = 2

    if resolved in ("winograd_strided", "pallas_winograd_strided",
                    "pallas_depthwise_strided"):
        # shared stride-2 derivation: phase tile set F(m, (k+1)/2) and the
        # full-resolution phase geometry; only the halo blocking differs
        # per executor.
        mh, mw = _resolve_strided_tile(h, w, kh, kw, padding, output_tile, c)
        ct_h = cook_toom(mh, (kh + 1) // 2)
        ct_w = cook_toom(mw, (kw + 1) // 2)
        geom = _wg.conv2d_strided_geometry(h, w, kh, kw, mh, mw, padding)
        strided = dict(algorithm=resolved, output_tile=(mh, mw), ct_h=ct_h,
                       ct_w=ct_w, geometry=geom, **base)
        if resolved == "winograd_strided":
            return ConvSpec(**strided)
        chooser = (_wg.stream_geometry_depthwise
                   if resolved == "pallas_depthwise_strided" else
                   functools.partial(_wg.stream_geometry, mout=mout))
        try:
            stream = chooser(geom.n_h, geom.n_w, c, ct_h=ct_h, ct_w=ct_w,
                             phases=4, input_stride=2)
        except ValueError:
            # the default F(4, .) tile's four phase banks can outgrow VMEM
            # (a 3-channel stem pads to a 128-lane filter block); the
            # default then steps down to F(2, .). A pinned tile raises.
            if output_tile is not None or (mh, mw) == (2, 2):
                raise
            return _build_spec(x_shape, w_shape, dtype, stride, padding,
                               requested, resolved, 2, groups, layout,
                               compute_dtype)
        blocks = (stream.bh * stream.bw, stream.block_c)
        if resolved == "pallas_winograd_strided":
            blocks += (stream.block_m,)
        return ConvSpec(stream=stream, blocks=blocks, **strided)

    if resolved in ("winograd_depthwise", "winograd_grouped"):
        mh, mw = _resolve_output_tile(kh, kw, output_tile)
        ct_h, ct_w = cook_toom(mh, kh), cook_toom(mw, kw)
        geom = _wg.conv2d_geometry(h, w, kh, kw, mh, mw, padding)
        return ConvSpec(algorithm=resolved, output_tile=(mh, mw),
                        ct_h=ct_h, ct_w=ct_w, geometry=geom, **base)

    if resolved == "pallas_depthwise":
        # Streamed depthwise: same halo blocking machinery as the dense
        # streaming kernel, channel axes collapsed (no M sweep, no C
        # reduction). A channel multiplier > 1 rides as a trailing taps
        # axis; the chooser widens its VMEM estimate accordingly.
        mh, mw = _resolve_output_tile(kh, kw, output_tile)
        ct_h, ct_w = cook_toom(mh, kh), cook_toom(mw, kw)
        geom = _wg.conv2d_geometry(h, w, kh, kw, mh, mw, padding)
        stream = _wg.stream_geometry_depthwise(geom.n_h, geom.n_w, c,
                                               ct_h, ct_w, mult=mout // c)
        return ConvSpec(algorithm="pallas_depthwise", output_tile=(mh, mw),
                        ct_h=ct_h, ct_w=ct_w, geometry=geom, stream=stream,
                        blocks=(stream.bh * stream.bw, stream.block_c),
                        **base)

    if resolved == "winograd_1d":
        # 1xN / Nx1: single-axis Cook-Toom (the Pallas families also declare
        # this executor -- its GEMM is one matmul XLA already maps to the
        # MXU).
        axis = 1 if kh > 1 else 2
        k = max(kh, kw)
        mh, mw = _resolve_output_tile(kh, kw, output_tile)
        m = (mh, mw)[axis - 1]
        ct = cook_toom(m, k)
        geom = _wg.conv1d_axis_geometry(x_shape[axis], axis, k, m, padding)
        return ConvSpec(algorithm="winograd_1d", output_tile=(m, m),
                        ct_w=ct, geometry=geom, axis=axis, **base)

    if resolved == "winograd":
        mh, mw = _resolve_output_tile(kh, kw, output_tile)
        ct_h, ct_w = cook_toom(mh, kh), cook_toom(mw, kw)
        geom = _wg.conv2d_geometry(h, w, kh, kw, mh, mw, padding)
        return ConvSpec(algorithm="winograd", output_tile=(mh, mw),
                        ct_h=ct_h, ct_w=ct_w, geometry=geom, **base)

    if resolved == "winograd_f63":
        # Large-tile F(6x6, 3x3): same executor as "winograd" with the
        # row-scaled transform set (transforms.scaled_cook_toom) that holds
        # the fp32 error budget at t = 8.
        ct_h, ct_w = scaled_cook_toom(6, kh), scaled_cook_toom(6, kw)
        geom = _wg.conv2d_geometry(h, w, kh, kw, 6, 6, padding)
        return ConvSpec(algorithm="winograd_f63", output_tile=(6, 6),
                        ct_h=ct_h, ct_w=ct_w, geometry=geom, **base)

    if resolved == "fft":
        # rfft2 overlap-tiled executor: the transform lengths are the one
        # plan-time decision; output_tile persists them (fft = m + k - 1),
        # so artifact reloads rebuild the identical FFTGeometry.
        fftg = _fft.choose_fft_geometry(
            h, w, kh, kw,
            output_tile=(tuple(output_tile)
                         if isinstance(output_tile, (tuple, list))
                         else ((output_tile, output_tile)
                               if output_tile else None)))
        geom = _wg.conv2d_fft_geometry(h, w, kh, kw, fftg.fft_h, fftg.fft_w,
                                       padding)
        return ConvSpec(algorithm="fft", output_tile=(fftg.m_h, fftg.m_w),
                        geometry=geom, fft=fftg, **base)

    if resolved == "pallas_winograd":
        # Streaming executor: halo-blocking geometry (strip origins,
        # edge-block padding, VMEM budget -> block sizes) derived here, once.
        mh, mw = _resolve_output_tile(kh, kw, output_tile)
        ct_h, ct_w = cook_toom(mh, kh), cook_toom(mw, kw)
        geom = _wg.conv2d_geometry(h, w, kh, kw, mh, mw, padding)
        stream = _wg.stream_geometry(geom.n_h, geom.n_w, c, mout, ct_h, ct_w)
        return ConvSpec(algorithm="pallas_winograd", output_tile=(mh, mw),
                        ct_h=ct_h, ct_w=ct_w, geometry=geom, stream=stream,
                        blocks=(stream.bh * stream.bw, stream.block_c,
                                stream.block_m), **base)

    if resolved == "pallas_winograd_materialized":
        from repro.kernels import ops  # local import: kernels are optional
        mh, mw = _resolve_output_tile(kh, kw, output_tile)
        ct_h, ct_w = cook_toom(mh, kh), cook_toom(mw, kw)
        geom = _wg.conv2d_geometry(h, w, kh, kw, mh, mw, padding)
        blocks = ops.winograd_blocks(n * geom.n_h * geom.n_w, c, mout)
        return ConvSpec(algorithm="pallas_winograd_materialized",
                        output_tile=(mh, mw), ct_h=ct_h, ct_w=ct_w,
                        geometry=geom, blocks=blocks, **base)

    if resolved == "im2col":
        geom = _im2col.im2row_geometry(h, w, kh, kw, stride, padding)
        return ConvSpec(algorithm="im2col", geometry=geom, **base)

    if resolved == "pallas_im2col":
        from repro.kernels import ops
        geom = _im2col.im2row_geometry(h, w, kh, kw, stride, padding)
        blocks = ops.im2col_blocks(n * geom.oh * geom.ow, kh * kw * c, mout)
        return ConvSpec(algorithm="pallas_im2col", geometry=geom,
                        blocks=blocks, **base)

    raise ValueError(f"unknown algorithm {resolved!r}")


def _depthwise_domain_taps(w: jax.Array, ct_h: CookToom, ct_w: CookToom,
                           c_in: int, c_pad: int) -> jax.Array:
    """(kh, kw, 1, C) depthwise filter -> (P, Cp) Winograd-domain taps,
    channel-padded to the kernel block grid. The one recipe shared by the
    pallas_depthwise plan binding and the fused separable-block binding."""
    u = _wg.transform_filter_2d(w, ct_h, ct_w)            # (th, tw, 1, C)
    u = u.reshape(ct_h.t * ct_w.t, c_in)                  # (P, C)
    return jnp.pad(u, ((0, 0), (0, c_pad - c_in)))


def _domain_filter(spec: ConvSpec, w: jax.Array) -> jax.Array:
    """Transform the filter into the spec's execution domain (fp32). This is
    the once-per-plan weight work; ConvPlan.apply never touches it again."""
    kh, kw, c, mout = spec.w_shape     # c = C/groups (HWIO grouped filter)
    if spec.algorithm in ("winograd", "winograd_f63"):
        return _wg.transform_filter_2d(w, spec.ct_h, spec.ct_w)
    if spec.algorithm == "fft":
        return _fft.fft_transform_filter(w, spec.fft.fft_h, spec.fft.fft_w)
    if spec.algorithm == "winograd_1d":
        return _wg.transform_filter_1d(w.reshape(max(kh, kw), c, mout),
                                       spec.ct_w)
    if spec.algorithm == "winograd_depthwise":
        c_in = spec.x_shape[3]
        u = _wg.transform_filter_2d(w, spec.ct_h, spec.ct_w)  # (th, tw, 1, M)
        return u.reshape(spec.ct_h.t, spec.ct_w.t, c_in, mout // c_in)
    if spec.algorithm == "winograd_grouped":
        return _wg.transform_filter_2d(w, spec.ct_h, spec.ct_w)
    if spec.algorithm == "winograd_strided":
        u = _wg.strided_phase_filters(w, spec.ct_h, spec.ct_w)
        if spec.groups > 1 and spec.groups == spec.x_shape[3]:
            # depthwise: make the channel axis explicit, (2,2,th,tw,C,mult)
            c_in = spec.x_shape[3]
            return u.reshape(*u.shape[:4], c_in, mout // c_in)
        return u                                     # (2, 2, th, tw, Cg, M)
    if spec.algorithm == "pallas_winograd_strided":
        from repro.kernels import ops
        u = _wg.strided_phase_filters(w, spec.ct_h, spec.ct_w)
        u = u.reshape(4 * spec.ct_h.t * spec.ct_w.t, c, mout)  # phase-major
        return ops.pad_winograd_filter(u, spec.blocks[1], spec.blocks[2])
    if spec.algorithm == "pallas_depthwise_strided":
        c_in = spec.x_shape[3]
        u = _wg.strided_phase_filters(w, spec.ct_h, spec.ct_w)
        u = u.reshape(4 * spec.ct_h.t * spec.ct_w.t, c_in)     # (4P, C)
        return jnp.pad(u, ((0, 0), (0, spec.stream.c_pad - c_in)))
    if spec.algorithm == "pallas_depthwise":
        # (kh, kw, 1, C*mult) -> (P, Cp, mult): the last HWIO axis is
        # o = c*mult + j (lax ordering), so the reshape peels the
        # multiplier off as a trailing taps axis.
        c_in = spec.x_shape[3]
        u = _wg.transform_filter_2d(w, spec.ct_h, spec.ct_w)
        u = u.reshape(spec.ct_h.t * spec.ct_w.t, c_in, mout // c_in)
        return jnp.pad(u, ((0, 0), (0, spec.stream.c_pad - c_in), (0, 0)))
    if spec.algorithm in ("pallas_winograd", "pallas_winograd_materialized"):
        from repro.kernels import ops
        u = _wg.transform_filter_2d(w, spec.ct_h, spec.ct_w)
        u = u.reshape(spec.ct_h.t * spec.ct_w.t, c, mout)
        return ops.pad_winograd_filter(u, spec.blocks[1], spec.blocks[2])
    if spec.algorithm == "im2col":
        if spec.groups > 1:
            return _im2col.grouped_filter_matrix(w, spec.groups)
        return w.reshape(kh * kw * c, mout)
    if spec.algorithm == "pallas_im2col":
        from repro.kernels import ops
        return ops.pad_im2col_filter(w.reshape(kh * kw * c, mout),
                                     spec.blocks[1], spec.blocks[2])
    raise ValueError(spec.algorithm)


def _quantize_axes(spec: ConvSpec) -> tuple[tuple[int, ...], str]:
    """(channel_axes, scale_form) of the int8 per-output-channel quantizer
    for one executor's execution-domain filter layout. `channel_axes` are
    the axes that together enumerate output channels (depthwise layouts
    split them into (C, mult)); scale_form says how ConvPlan.scale is
    shaped for the executor's epilogue -- 'flat' (pure-JAX: one f32 per
    NHWC output channel, broadcast in _dequantize) or 'row' (Pallas: a
    (1, M_padded) operand mirroring the bias blockspec)."""
    alg = spec.algorithm
    depthwise = spec.groups > 1 and spec.groups == spec.x_shape[3]
    if alg in ("winograd", "winograd_1d", "winograd_grouped"):
        return (-1,), "flat"
    if alg == "winograd_depthwise":
        return (-2, -1), "flat"
    if alg == "winograd_strided":
        return ((-2, -1) if depthwise else (-1,)), "flat"
    if alg == "im2col":
        return ((0, 2) if spec.groups > 1 else (-1,)), "flat"
    if alg in ("pallas_winograd", "pallas_winograd_materialized",
               "pallas_winograd_strided", "pallas_im2col",
               "pallas_depthwise_strided"):
        return (-1,), "row"
    if alg == "pallas_depthwise":
        return (-2, -1), "row"
    raise ValueError(
        f"executor {alg!r} has no int8 transform-domain path")


def _bind_weights(spec: ConvSpec,
                  w: jax.Array) -> tuple[jax.Array, jax.Array | None]:
    """Filter -> (execution-domain filter, dequantization scale). fp32
    plans get (fp32 u, None); bf16 plans downcast the transformed filter
    (dequantization is implicit -- bf16 is a truncated fp32); int8 plans
    quantize per output channel AFTER the transform and padding, so
    `u_int8 * scale` reproduces the fp32 transformed filter up to rounding
    and the hot path dequantizes with ONE per-channel multiply folded into
    the bias+activation epilogue. All of this is once-per-plan weight work;
    warm artifact loads bypass it entirely."""
    global _QUANTIZED
    u = _domain_filter(spec, w)
    cd = spec.compute_dtype
    if cd == "float32":
        return u, None
    if cd == "bfloat16":
        return u.astype(jnp.bfloat16), None
    if cd == "int8":
        from repro.optim import compression as _comp
        axes, form = _quantize_axes(spec)
        q, scale = _comp.quantize_channelwise(u, channel_axes=axes)
        _QUANTIZED += 1
        scale = (scale.reshape(1, -1) if form == "row"
                 else scale.reshape(-1))
        return q, scale
    raise ValueError(f"unknown compute_dtype {cd!r}; expected one of "
                     f"{registry.COMPUTE_DTYPES}")


# ---------------------------------------------------------------------------
# ConvPlan: spec + weights in the execution domain
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """A fully-decided, weight-bound convolution. apply(x) does only input
    work: pad, tile, transform the input, GEMM against the cached filter,
    inverse-transform. No filter transform, no geometry derivation.

    apply(x, bias=..., activation=...) runs the layer epilogue too: on the
    Pallas executors (streaming Winograd, im2col GEMM) the bias add and
    activation are fused into the kernel's store step, so the conv output
    never round-trips HBM before the elementwise work; pure-JAX executors
    apply the same contract as one XLA op."""

    spec: ConvSpec
    u: jax.Array                       # filter in the execution domain
                                       # (fp32 / bf16 / int8 per
                                       # spec.compute_dtype)
    build_time_s: float = 0.0
    precision: Any = None
    scale: jax.Array | None = None     # int8 per-output-channel dequant
                                       # scales (None for fp32/bf16): flat
                                       # (M,) on pure-JAX executors, (1, Mp)
                                       # on Pallas executors (a kernel
                                       # operand mirroring the bias)

    def __call__(self, x: jax.Array, **kwargs) -> jax.Array:
        return self.apply(x, **kwargs)

    def apply(self, x: jax.Array, bias: jax.Array | None = None,
              activation: str = "none") -> jax.Array:
        spec = self.spec
        if spec.layout == "NCHW":
            # NCHW ingest: one boundary transpose per call (the weights were
            # transposed once, at plan time); executors always run NHWC.
            want = (spec.x_shape[3],) + spec.x_shape[1:3]
            if x.shape[1:] != want:
                raise ValueError(
                    f"plan built for NCHW input (N, {want[0]}, {want[1]}, "
                    f"{want[2]}) got {x.shape} (batch may differ; C/H/W "
                    f"must match)")
            y = self._apply_nhwc(jnp.transpose(x, (0, 2, 3, 1)), bias,
                                 activation)
            return jnp.transpose(y, (0, 3, 1, 2))
        return self._apply_nhwc(x, bias, activation)

    def _dequantize(self, y: jax.Array) -> jax.Array:
        """Fold the int8 per-output-channel scales back in (pure-JAX
        executors only -- the Pallas kernels take `scale` as an operand and
        multiply in the store epilogue). One elementwise multiply, fused by
        XLA into the bias/activation epilogue that follows."""
        if self.scale is None:
            return y
        return y * self.scale.reshape(-1).astype(y.dtype)

    def _apply_nhwc(self, x: jax.Array, bias: jax.Array | None,
                    activation: str) -> jax.Array:
        spec = self.spec
        if x.shape[1:] != spec.x_shape[1:]:
            raise ValueError(
                f"plan built for input {spec.x_shape} got {x.shape} "
                f"(batch may differ; H/W/C must match)")
        if activation not in EPILOGUE_ACTIVATIONS:
            raise ValueError(f"unknown activation {activation!r}; "
                             f"expected one of {EPILOGUE_ACTIVATIONS}")
        alg = spec.algorithm
        if alg in ("winograd", "winograd_f63"):
            y = _wg.winograd_conv2d_pretransformed(
                x, self.u, spec.ct_h, spec.ct_w, padding=spec.padding,
                geometry=spec.geometry, precision=self.precision)
            return _epilogue_jnp(self._dequantize(y), bias, activation)
        if alg == "fft":
            y = _fft.fft_conv2d_pretransformed(
                x, self.u, spec.fft, padding=spec.padding,
                geometry=spec.geometry, precision=self.precision)
            return _epilogue_jnp(y, bias, activation)
        if alg == "winograd_1d":
            y = _wg.winograd_conv1d_axis_pretransformed(
                x, self.u, spec.ct_w, spec.geometry, precision=self.precision)
            return _epilogue_jnp(self._dequantize(y), bias, activation)
        if alg == "winograd_depthwise":
            y = _wg.winograd_depthwise_conv2d_pretransformed(
                x, self.u, spec.ct_h, spec.ct_w, padding=spec.padding,
                geometry=spec.geometry)
            return _epilogue_jnp(self._dequantize(y), bias, activation)
        if alg == "winograd_grouped":
            y = _wg.winograd_grouped_conv2d_pretransformed(
                x, self.u, spec.ct_h, spec.ct_w, spec.groups,
                padding=spec.padding, geometry=spec.geometry,
                precision=self.precision)
            return _epilogue_jnp(self._dequantize(y), bias, activation)
        if alg == "winograd_strided":
            y = _wg.winograd_strided_conv2d_pretransformed(
                x, self.u, spec.ct_h, spec.ct_w, groups=spec.groups,
                geometry=spec.geometry, precision=self.precision)
            return _epilogue_jnp(self._dequantize(y), bias, activation)
        if alg == "pallas_winograd_strided":
            from repro.kernels import ops
            return ops.winograd_strided_conv2d_planned(
                x, self.u, ct_h=spec.ct_h, ct_w=spec.ct_w,
                geometry=spec.geometry, stream=spec.stream,
                c_out=spec.w_shape[3], bias=bias, activation=activation,
                scale=self.scale)
        if alg == "pallas_depthwise_strided":
            from repro.kernels import ops
            return ops.depthwise_strided_conv2d_planned(
                x, self.u, ct_h=spec.ct_h, ct_w=spec.ct_w,
                geometry=spec.geometry, stream=spec.stream,
                c_out=spec.w_shape[3], bias=bias, activation=activation,
                scale=self.scale)
        if alg == "im2col":
            geom = spec.geometry
            kh, kw, _, mout = spec.w_shape
            b = self.u
            if b.dtype == jnp.bfloat16:
                cast = lambda a: a.astype(jnp.bfloat16)   # noqa: E731
            elif b.dtype != x.dtype:
                b, cast = b.astype(x.dtype), (lambda a: a)  # int8 -> f32
            else:
                cast = lambda a: a                        # noqa: E731
            if spec.groups > 1:
                a, _ = _im2col.grouped_im2row(x, kh, kw, spec.stride,
                                              spec.padding, spec.groups, geom)
                y = jnp.einsum("rgk,gkm->rgm", cast(a), b,
                               precision=self.precision,
                               preferred_element_type=jnp.float32)
            else:
                a, _ = _im2col.im2row(x, kh, kw, spec.stride, spec.padding,
                                      geom)
                y = jnp.matmul(cast(a), b, precision=self.precision,
                               preferred_element_type=jnp.float32)
            y = y.reshape(x.shape[0], geom.oh, geom.ow, mout).astype(x.dtype)
            return _epilogue_jnp(self._dequantize(y), bias, activation)
        if alg == "pallas_depthwise":
            from repro.kernels import ops
            return ops.depthwise_conv2d_planned(
                x, self.u, ct_h=spec.ct_h, ct_w=spec.ct_w,
                geometry=spec.geometry, stream=spec.stream,
                c_out=spec.w_shape[3], bias=bias, activation=activation,
                scale=self.scale)
        if alg == "pallas_winograd":
            from repro.kernels import ops
            return ops.winograd_conv2d_planned(
                x, self.u, ct_h=spec.ct_h, ct_w=spec.ct_w,
                geometry=spec.geometry, stream=spec.stream,
                c_out=spec.w_shape[3], bias=bias, activation=activation,
                scale=self.scale)
        if alg == "pallas_winograd_materialized":
            from repro.kernels import ops
            _, _, c, mout = spec.w_shape
            y = ops.winograd_conv2d_planned_materialized(
                x, self.u, ct_h=spec.ct_h, ct_w=spec.ct_w,
                geometry=spec.geometry, blocks=spec.blocks, c_in=c,
                c_out=mout)
            return _epilogue_jnp(y, bias, activation)
        if alg == "pallas_im2col":
            from repro.kernels import ops
            kh, kw, _, mout = spec.w_shape
            return ops.im2col_conv2d_planned(
                x, self.u, kh=kh, kw=kw, stride=spec.stride,
                padding=spec.padding, geometry=spec.geometry,
                blocks=spec.blocks, c_out=mout, bias=bias,
                activation=activation, scale=self.scale)
        raise ValueError(alg)

    @property
    def algorithm(self) -> str:
        return self.spec.algorithm

    @property
    def out_shape(self) -> tuple[int, ...]:
        spec, g = self.spec, self.spec.geometry
        mout = spec.w_shape[-1]
        n = spec.x_shape[0]
        if spec.algorithm in ("winograd", "winograd_f63", "fft",
                              "winograd_depthwise",
                              "winograd_grouped", "winograd_strided",
                              "pallas_winograd", "pallas_depthwise",
                              "pallas_winograd_strided",
                              "pallas_depthwise_strided",
                              "pallas_winograd_materialized"):
            shape = (n, g.out_h, g.out_w, mout)
        elif spec.algorithm == "winograd_1d":
            h, w = spec.x_shape[1:3]
            shape = ((n, g.out_size, w, mout) if g.axis == 1
                     else (n, h, g.out_size, mout))
        else:
            shape = (n, g.oh, g.ow, mout)
        if spec.layout == "NCHW":
            return (shape[0], shape[3], shape[1], shape[2])
        return shape

    # ---- LayerPlan protocol: describe + artifact (de)serialization -------

    def describe(self) -> dict:
        spec = self.spec
        kh, kw = spec.w_shape[:2]
        if spec.requested == "auto_tuned":
            # an auto_tuned plan says HOW it was decided: "measured" carries
            # the timing-race evidence (spec.autotune_report), "heuristic"
            # means the static fallback decided (planning inside a jit
            # trace, REPRO_PLAN_NO_MEASURE, or a sole-candidate layer).
            decision = "measured" if spec.autotune is not None else \
                "heuristic"
        else:
            decision = "static"
        return {"kind": "conv2d", "executor": spec.algorithm,
                "requested": spec.requested, "filter": f"{kh}x{kw}",
                "stride": f"{spec.stride[0]}x{spec.stride[1]}",
                "groups": spec.groups,
                "tile": ("x".join(map(str, spec.output_tile))
                         if spec.output_tile else "-"),
                "decision": decision,
                "compute_dtype": spec.compute_dtype}

    def to_artifact(self) -> tuple[dict, dict]:
        """(meta, arrays): `meta` is the JSON-safe spec record from which
        _build_spec deterministically re-derives all geometry; `arrays` is
        the execution-domain filter. Loading re-runs neither the algorithm
        decision nor the filter transform."""
        spec = self.spec
        meta = {"kind": "conv2d", "x_shape": list(spec.x_shape),
                "w_shape": list(spec.w_shape), "dtype": spec.dtype,
                "stride": list(spec.stride), "padding": spec.padding,
                "requested": spec.requested, "algorithm": spec.algorithm,
                "groups": spec.groups, "layout": spec.layout,
                "compute_dtype": spec.compute_dtype,
                "output_tile": (list(spec.output_tile)
                                if spec.output_tile else None),
                "autotune": ([list(kv) for kv in spec.autotune]
                             if spec.autotune else None)}
        arrays = {"u": np.asarray(self.u)}
        if self.scale is not None:
            arrays["scale"] = np.asarray(self.scale)
        return meta, arrays

    @classmethod
    def from_artifact(cls, meta: dict, arrays: dict) -> "ConvPlan":
        """Rebuild the plan from a saved artifact: the spec geometry is
        re-derived from the *saved* resolved algorithm (deterministic, no
        measurement), and the execution-domain filter is taken verbatim --
        _bind_weights never runs, so no filter-transform op executes."""
        ot = meta["output_tile"]
        spec = _build_spec(tuple(meta["x_shape"]), tuple(meta["w_shape"]),
                           meta["dtype"], tuple(meta["stride"]),
                           meta["padding"], meta["requested"],
                           meta["algorithm"], tuple(ot) if ot else None,
                           meta["groups"], meta["layout"],
                           meta.get("compute_dtype", "float32"))
        if meta.get("autotune"):
            spec = dataclasses.replace(
                spec, autotune=tuple(
                    (k, tuple(v) if isinstance(v, list) else v)
                    for k, v in meta["autotune"]))
        scale = (jnp.asarray(arrays["scale"]) if "scale" in arrays
                 else None)
        return cls(spec=spec, u=jnp.asarray(arrays["u"]), scale=scale)


# ---------------------------------------------------------------------------
# Plan-time measured autotuning (algorithm="auto_tuned")
# ---------------------------------------------------------------------------

def _time_apply(plan: ConvPlan, x, warmup: int = 1, iters: int = 3) -> float:
    fn = jax.jit(plan.apply)
    for _ in range(warmup):
        jax.block_until_ready(fn(x))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        best = min(best, time.perf_counter() - t0)
    return best


#: Accuracy budgets of the auto_tuned dtype race: a reduced-precision
#: contender may only win when its relative max-abs error vs the fp32
#: reference output stays under budget. bf16 has ~3 decimal digits of
#: mantissa; int8's budget also absorbs the per-channel quantization grid.
AUTOTUNE_ACCURACY_BUDGET = {"bfloat16": 3e-2, "int8": 6e-2}

_DTYPE_LABEL = {"bfloat16": "bf16", "int8": "int8"}


def _autotune_contenders(x_shape, w_shape, stride, groups,
                         output_tile, fast: str,
                         pin_dtype: str = "float32",
                         dtype_race: bool = False) -> list[tuple]:
    """(label, executor, output_tile, compute_dtype) contenders of the
    N-way auto_tuned race: the registry-matched winograd-family executor at
    its default tile (F(4,3) for dense 3x3), its small-tile F(2,3) variant,
    the large-tile F(6,3) executor, the rfft2 executor, the im2row
    baseline, and the fast executor's reduced-precision (bf16/int8)
    transform-domain variants where its Capability declares them -- each
    only where the record covers the layer. Labels key the persisted
    evidence (t_<label>_s; dtype contenders also persist err_<label>)."""
    kh, kw = w_shape[:2]
    q = LayerQuery(kh=kh, kw=kw, stride=stride, groups=groups,
                   c_in=x_shape[3], c_out=w_shape[3])
    entries = [("winograd", fast, output_tile, "float32")]
    if fast == "winograd" and output_tile is None and (kh, kw) == (3, 3):
        entries.append(("winograd_f2", "winograd", 2, "float32"))
    if registry.supported("winograd_f63", q):
        entries.append(("f63", "winograd_f63", None, "float32"))
    if registry.supported("fft", q):
        entries.append(("fft", "fft", None, "float32"))
    entries.append(("im2col", "im2col", None, "float32"))
    if dtype_race or pin_dtype != "float32":
        # Reduced-precision contenders are strictly opt-in: the default
        # fp32 race must keep fp32 numerics (a crowned int8 winner would
        # silently change auto_tuned outputs by up to its accuracy
        # budget). compute_dtype="auto" opts the unpinned race in; a
        # pinned reduced dtype fields its own variant so the race times
        # what the pinned build will actually run.
        fast_dts = registry.compute_dtypes_for(fast)
        for dt in ("bfloat16", "int8"):
            if dt in fast_dts:
                entries.append((f"winograd_{_DTYPE_LABEL[dt]}", fast,
                                output_tile, dt))
    if pin_dtype != "float32":
        # A pinned reduced dtype drops contenders whose executor cannot run
        # it -- the race must not crown an fp32-only executor (fft, f63)
        # that the pinned build would then refuse.
        entries = [e for e in entries
                   if pin_dtype in registry.compute_dtypes_for(e[1])]
    return entries


def _measure_autotune(x_shape, w_shape, dtype, stride, padding,
                      output_tile, groups: int = 1,
                      fast: str = "winograd",
                      pin_dtype: str = "float32",
                      dtype_race: bool = False
                      ) -> tuple[str, Any, str, tuple]:
    """Time every registry-eligible contender on the real layer shape;
    return (winner executor, winner output_tile, winner compute_dtype,
    evidence). Runs once per shape per process (the spec cache holds the
    result) and the evidence tuple is persisted into NetworkPlan artifacts,
    so warm loads never re-measure. `fast` is the winograd-family executor
    the registry matched for this layer (grouped/depthwise/strided variants
    included); the legacy evidence keys t_winograd_s / t_im2col_s name that
    contender and the (grouped) im2row baseline.

    Reduced-precision contenders (winograd_bf16 / winograd_int8) enter the
    race only when the caller opted in (compute_dtype="auto" sets
    `dtype_race`, or a pinned reduced dtype fields its own variant) and
    are gated on accuracy BEFORE they may win: each is compared against the fp32 fast
    contender's output and dropped from the race (its err_<label> evidence
    still persisted) when its relative max-abs error exceeds
    AUTOTUNE_ACCURACY_BUDGET -- a quantized executor never wins on speed at
    the cost of a busted output."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(x_shape), dtype)
    w = jnp.asarray(rng.standard_normal(w_shape)
                    / (w_shape[0] * w_shape[1]), dtype)
    times: dict[str, tuple[float, str, Any, str]] = {}
    errs: list[tuple[str, float]] = []
    y_ref = None   # fp32 fast-contender output, the dtype-gate oracle
    for label, alg, ot, cd in _autotune_contenders(x_shape, w_shape, stride,
                                                   groups, output_tile,
                                                   fast, pin_dtype,
                                                   dtype_race):
        try:
            spec = _build_spec(x_shape, w_shape, str(jnp.dtype(dtype)),
                               stride, padding, alg, alg, ot, groups,
                               compute_dtype=cd)
            u, scale = _bind_weights(spec, w)
            plan = ConvPlan(spec=spec, u=u, scale=scale)
            if cd != "float32":
                if y_ref is None:
                    continue   # no fp32 oracle -> no gated contender
                y = np.asarray(jax.jit(plan.apply)(x), np.float32)
                err = float(np.max(np.abs(y - y_ref))
                            / (np.max(np.abs(y_ref)) or 1.0))
                errs.append((f"err_{label}", err))
                if err > AUTOTUNE_ACCURACY_BUDGET[cd]:
                    continue   # accuracy gate: may not win the race
            t = _time_apply(plan, x)
            if label == "winograd":
                y_ref = np.asarray(jax.jit(plan.apply)(x), np.float32)
        except Exception:
            if label in ("winograd", "im2col"):
                raise  # the two contenders every eligible layer must have
            # a dropped contender is a fault to see, not a silent skip
            _obs_metrics.count("plan.autotune.contender_failed")
            _LOG.warning("autotune contender %s (%s) failed on %sx%s; "
                         "dropped from the race", label, alg, x_shape,
                         w_shape, exc_info=True)
            continue
        times[label] = (t, spec.algorithm, spec.output_tile, cd)
    win = min(times, key=lambda k: times[k][0])
    _, winner, winner_tile, winner_dtype = times[win]
    evidence = [(f"t_{label}_s", times[label][0]) for label in times]
    evidence.extend(errs)
    # winner: resolved executor; winner_label: the contender that won the
    # race (the two differ when e.g. the F(2,3) tile variant of the same
    # winograd executor wins, or a reduced-precision variant of it does).
    evidence.append(("winner_label", win))
    evidence.append(("winner", winner))
    evidence.append(("winner_dtype", winner_dtype))
    if winner_tile is not None:
        evidence.append(("winner_tile", tuple(winner_tile)))
    # race identity, so repro.obs.tuningdb can reconstruct the exact
    # planning request (the dtype pin vs the "auto" race, the requested
    # tile) when lifting this evidence out of an artifact.
    evidence.append(("pin_dtype", pin_dtype))
    evidence.append(("dtype_race", bool(dtype_race)))
    if output_tile is not None:
        evidence.append(("req_tile", tuple(output_tile)
                         if isinstance(output_tile, (tuple, list))
                         else (output_tile, output_tile)))
    return winner, winner_tile, winner_dtype, tuple(evidence)


# ---------------------------------------------------------------------------
# plan_conv2d: the public entry point
# ---------------------------------------------------------------------------

def plan_conv2d(
    x_shape: tuple[int, ...],
    w: jax.Array,
    *,
    stride: int | tuple[int, int] = 1,
    padding: Padding = "SAME",
    algorithm: Algorithm = "auto",
    groups: int = 1,
    output_tile: int | tuple[int, int] | None = None,
    precision=None,
    dtype=None,
    data_format: str = "NHWC",
    compute_dtype: str = "float32",
) -> ConvPlan:
    """Build a ConvPlan for a (N, H, W, C) x (kh, kw, C/groups, M) conv.

    All per-layer decisions (algorithm, transform variant, padding/tiling
    geometry, Pallas blocking) are made here, once; the filter is transformed
    into the execution domain, once. Decisions are cached process-wide keyed
    on (shapes, dtype, stride, padding, algorithm, groups, output_tile,
    data_format), so repeated planning of the same layer shape -- including
    a measured auto_tuned choice -- is a dict lookup plus one filter
    transform.

    Algorithm resolution is a query against the capability-declaring
    executor registry (repro.core.registry): the concrete families resolve
    to the matching declared executor or raise an error enumerating the
    executors that do cover the layer; "auto" is the paper's mixed policy
    (cheapest matching fast-scheme capability, else im2row). Stride-2
    layers with odd filters resolve to the transform-domain
    phase-decomposition executors (winograd_strided / the strided Pallas
    kernels).

    `groups` is jax.lax's feature_group_count: 1 is the dense conv, C is a
    depthwise conv ((kh, kw, 1, C*mult) filter), anything between is a
    grouped conv.

    `data_format="NCHW"` ingests NCHW inputs with an OIHW (M, C/groups, kh,
    kw) filter -- checkpoint compatibility: the filter is transposed to HWIO
    once, here, and apply() transposes x/y at the call boundary.

    `compute_dtype` selects the transform-domain GEMM/Hadamard dtype:
    "float32" (default), "bfloat16" (filter cast once at bind time), or
    "int8" (per-output-channel symmetric weight quantization at bind time;
    dequantization folds into the bias+activation epilogue). The input and
    inverse transforms always run fp32. An explicit reduced dtype pins the
    choice. `compute_dtype="auto"` (requires `algorithm="auto_tuned"`)
    additionally fields bf16/int8 contenders in the measured race, gated
    by AUTOTUNE_ACCURACY_BUDGET, and adopts the winner's dtype; the
    default "float32" race never lowers precision, so plain auto_tuned
    keeps fp32 numerics.
    """
    global _CACHE_HITS, _CACHE_MISSES
    t0 = time.perf_counter()
    x_shape = tuple(x_shape)
    if algorithm not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {algorithm!r}; expected one of "
                         f"{ALGORITHMS}")
    if data_format not in registry.LAYOUTS:
        raise ValueError(f"unknown data_format {data_format!r}; expected one "
                         f"of {registry.LAYOUTS}")
    if len(x_shape) != 4 or len(w.shape) != 4:
        raise ValueError(f"expected 4D input x 4D filter, got {x_shape} x "
                         f"{tuple(w.shape)}")
    if data_format == "NCHW":
        # One plan-time normalization: NCHW/OIHW -> NHWC/HWIO. The weight
        # transpose happens once per plan; the spec cache key carries the
        # layout so NCHW and NHWC plans of the same shape stay distinct.
        x_shape = (x_shape[0], x_shape[2], x_shape[3], x_shape[1])
        w = jnp.transpose(w, (2, 3, 1, 0))
    w_shape = tuple(w.shape)
    if groups < 1 or x_shape[3] % groups or w_shape[3] % groups:
        raise ValueError(
            f"groups={groups} must divide both C_in={x_shape[3]} and "
            f"C_out={w_shape[3]}")
    if x_shape[3] != w_shape[2] * groups:
        raise ValueError(
            f"channel mismatch: input {x_shape} (NHWC) filter {w_shape} "
            f"(HWIO) groups={groups} (grouped filters carry C_in/groups "
            f"input channels)")
    stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
    dtype = dtype or w.dtype
    dtype_str = str(jnp.dtype(dtype))
    dtype_race = compute_dtype == "auto"
    if dtype_race:
        if algorithm != "auto_tuned":
            raise ValueError(
                "compute_dtype='auto' races bf16/int8 against fp32 and "
                "needs measured evidence -- it requires "
                "algorithm='auto_tuned' (got algorithm="
                f"{algorithm!r}); pin a concrete dtype otherwise")
        compute_dtype = "float32"   # race baseline; winner may lower it
    else:
        compute_dtype = str(jnp.dtype(compute_dtype))
    if compute_dtype not in registry.COMPUTE_DTYPES:
        raise ValueError(
            f"unknown compute_dtype {compute_dtype!r}; expected one of "
            f"{registry.COMPUTE_DTYPES}")
    kh, kw = w_shape[:2]
    n, h, wdt, c = x_shape
    query = LayerQuery(kh=kh, kw=kw, stride=stride, groups=groups, c_in=c,
                       c_out=w_shape[3], layout=data_format)

    key = (x_shape, w_shape, dtype_str, stride, padding, algorithm,
           output_tile if not isinstance(output_tile, list) else
           tuple(output_tile), precision, groups, data_format,
           "auto" if dtype_race else compute_dtype)
    spec = _SPEC_CACHE.get(key) if _cache_enabled() else None
    if spec is not None:
        _count_cache(True)
    else:
        _count_cache(False)
        fast = registry.best_fast(query)
        autotune = None
        build_tile = output_tile
        build_dtype = compute_dtype
        if algorithm == "auto":
            resolved = registry.select_auto(query).executor
        elif algorithm == "auto_tuned":
            if fast is None:
                resolved = "im2col"
                _record_autotune_resolution(measured=False)
            elif (tuned := _tuningdb_lookup(
                    x_shape, w_shape, dtype_str, stride, padding, groups,
                    data_format, "auto" if dtype_race else compute_dtype,
                    output_tile)) is not None or _measure_allowed(w):
                if tuned is not None:
                    # fleet tuning database: adopt the recorded winner,
                    # tile, dtype, and evidence -- zero local
                    # measurements (plan_cache_info()["tuningdb_hits"]).
                    resolved, tuned_tile, tuned_dtype, autotune = tuned
                else:
                    t_race = time.perf_counter()
                    resolved, tuned_tile, tuned_dtype, autotune = \
                        _measure_autotune(
                            x_shape, w_shape, dtype_str, stride, padding,
                            output_tile, groups, fast=fast.executor,
                            pin_dtype=compute_dtype,
                            dtype_race=dtype_race)
                    _obs_trace.add_span(
                        "plan.autotune.race", t_race, time.perf_counter(),
                        winner=resolved, contenders=len(
                            [k for k, _ in autotune
                             if k.startswith("t_")]),
                        layer=f"{x_shape}x{w_shape}")
                    _record_autotune_resolution(measured=True)
                if tuned_tile is not None:
                    build_tile = tuned_tile
                # Only compute_dtype="auto" fields reduced contenders, so
                # an un-opted race always returns tuned_dtype="float32"
                # and default numerics are untouched; an explicit reduced
                # dtype pins the choice (the race still picked the
                # executor). A pinned reduced dtype must not inherit an
                # fp32 winner's tile -- the low-precision grid needs the
                # small-tile default.
                if compute_dtype == "float32":
                    build_dtype = tuned_dtype
                elif tuned_dtype != compute_dtype:
                    build_tile = output_tile
            else:
                resolved = fast.executor if winograd_amortizes(
                    h, wdt, kh, kw, c, padding, groups, stride) else "im2col"
                _record_autotune_resolution(measured=False)
        else:
            # concrete algorithm families: the registry either yields the
            # declared executor or raises the capability-enumerating error.
            resolved = registry.resolve(algorithm, query).executor
        if build_dtype != "float32":
            supported = registry.compute_dtypes_for(resolved)
            if build_dtype not in supported:
                supporting = sorted({
                    cap.executor for cap in registry.CAPABILITIES
                    if build_dtype in cap.compute_dtypes})
                raise ValueError(
                    f"executor {resolved!r} does not support "
                    f"compute_dtype={build_dtype!r} (it supports "
                    f"{'/'.join(supported)}); executors with a "
                    f"{build_dtype} transform-domain path: {supporting}")
        spec = _build_spec(x_shape, w_shape, dtype_str, stride, padding,
                           algorithm, resolved, build_tile, groups,
                           data_format, compute_dtype=build_dtype)
        if autotune is not None:
            spec = dataclasses.replace(spec, autotune=autotune)
        # An auto_tuned decision made via the heuristic fallback (planning
        # under a jit trace) must not be cached: a later eager plan of the
        # same shape should still get to measure. Only measured decisions
        # (and the deterministic unsuitable->im2col case) are durable.
        durable = (algorithm != "auto_tuned" or autotune is not None
                   or fast is None)
        if _cache_enabled() and durable:
            _SPEC_CACHE[key] = spec

    u, scale = _bind_weights(spec, w)
    return ConvPlan(spec=spec, u=u, scale=scale, precision=precision,
                    build_time_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Separable blocks: depthwise kxk -> pointwise 1x1 planned as one fused unit
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SeparableSpec:
    """Cacheable decisions of a planned separable (depthwise kxk +
    pointwise 1x1) block. mode 'fused_pallas' runs both convs and both
    epilogues in ONE streamed kernel (kernels/depthwise.py:
    separable_streamed -- the intermediate never touches HBM); mode
    'composed' chains two ConvPlans (each with its own fused-epilogue
    path), covering strided / multiplier>1 / non-Pallas configurations."""

    x_shape: tuple[int, ...]          # (N, H, W, C)
    w_dw_shape: tuple[int, ...]       # (kh, kw, 1, C*mult)
    w_pw_shape: tuple[int, ...]       # (1, 1, C*mult, M)
    dtype: str
    stride: tuple[int, int]
    padding: str
    requested: str
    mode: str                         # "fused_pallas" | "composed"
    output_tile: tuple[int, int] | None = None
    ct_h: CookToom | None = None
    ct_w: CookToom | None = None
    geometry: Any = None              # Conv2DGeometry (fused mode)
    stream: Any = None                # StreamGeometry (fused mode)


@dataclasses.dataclass(frozen=True)
class SeparableBlockPlan:
    """A planned MobileNet-style separable block with a single epilogue
    contract: apply(x, bias_dw=, bias_pw=, inner_activation=, activation=)
    runs depthwise conv -> bias+activation -> pointwise conv ->
    bias+activation. In fused mode all of it happens inside one Pallas
    kernel; in composed mode each conv rides its own plan's epilogue."""

    spec: SeparableSpec
    u_dw: jax.Array | None = None      # (P, Cp) fused-mode depthwise taps
    u_pw: jax.Array | None = None      # (Cp, Mp) fused-mode pointwise matrix
    dw: ConvPlan | None = None         # composed-mode sub-plans
    pw: ConvPlan | None = None
    build_time_s: float = 0.0

    def __call__(self, x: jax.Array, **kwargs) -> jax.Array:
        return self.apply(x, **kwargs)

    def apply(self, x: jax.Array, bias_dw: jax.Array | None = None,
              bias_pw: jax.Array | None = None,
              inner_activation: str = "relu",
              activation: str = "relu") -> jax.Array:
        spec = self.spec
        if x.shape[1:] != spec.x_shape[1:]:
            raise ValueError(
                f"plan built for input {spec.x_shape} got {x.shape} "
                f"(batch may differ; H/W/C must match)")
        for act in (inner_activation, activation):
            if act not in EPILOGUE_ACTIVATIONS:
                raise ValueError(f"unknown activation {act!r}; expected one "
                                 f"of {EPILOGUE_ACTIVATIONS}")
        if spec.mode == "fused_pallas":
            from repro.kernels import ops
            return ops.separable_conv2d_planned(
                x, self.u_dw, self.u_pw, ct_h=spec.ct_h, ct_w=spec.ct_w,
                geometry=spec.geometry, stream=spec.stream,
                c_out=spec.w_pw_shape[3], bias_dw=bias_dw, bias_pw=bias_pw,
                inner_activation=inner_activation, activation=activation)
        h = self.dw.apply(x, bias=bias_dw, activation=inner_activation)
        return self.pw.apply(h, bias=bias_pw, activation=activation)

    @property
    def mode(self) -> str:
        return self.spec.mode

    @property
    def out_shape(self) -> tuple[int, ...]:
        if self.spec.mode == "fused_pallas":
            g = self.spec.geometry
            return (self.spec.x_shape[0], g.out_h, g.out_w,
                    self.spec.w_pw_shape[3])
        return self.pw.out_shape

    # ---- LayerPlan protocol: describe + artifact (de)serialization -------

    def describe(self) -> dict:
        spec = self.spec
        if spec.mode == "fused_pallas":
            executor = "separable_streamed"
            cd = "float32"
        else:
            executor = f"{self.dw.algorithm}+{self.pw.algorithm}"
            cds = [self.dw.spec.compute_dtype, self.pw.spec.compute_dtype]
            cd = cds[0] if cds[0] == cds[1] else "+".join(cds)
        return {"kind": "separable", "executor": executor,
                "compute_dtype": cd,
                "requested": spec.requested, "mode": spec.mode,
                "filter": f"{spec.w_dw_shape[0]}x{spec.w_dw_shape[1]}+1x1",
                "stride": f"{spec.stride[0]}x{spec.stride[1]}",
                "groups": spec.x_shape[3],
                "tile": ("x".join(map(str, spec.output_tile))
                         if spec.output_tile else "-")}

    def to_artifact(self) -> tuple[dict, dict]:
        spec = self.spec
        meta = {"kind": "separable", "mode": spec.mode,
                "x_shape": list(spec.x_shape),
                "w_dw_shape": list(spec.w_dw_shape),
                "w_pw_shape": list(spec.w_pw_shape), "dtype": spec.dtype,
                "stride": list(spec.stride), "padding": spec.padding,
                "requested": spec.requested,
                "output_tile": (list(spec.output_tile)
                                if spec.output_tile else None)}
        if spec.mode == "fused_pallas":
            return meta, {"u_dw": np.asarray(self.u_dw),
                          "u_pw": np.asarray(self.u_pw)}
        meta["dw"], dw_arrays = self.dw.to_artifact()
        meta["pw"], pw_arrays = self.pw.to_artifact()
        arrays = {f"dw.{k}": v for k, v in dw_arrays.items()}
        arrays.update({f"pw.{k}": v for k, v in pw_arrays.items()})
        return meta, arrays

    @classmethod
    def from_artifact(cls, meta: dict, arrays: dict) -> "SeparableBlockPlan":
        ot = meta["output_tile"]
        if meta["mode"] == "fused_pallas":
            spec = _build_separable_fused_spec(
                tuple(meta["x_shape"]), tuple(meta["w_dw_shape"]),
                tuple(meta["w_pw_shape"]), meta["dtype"],
                tuple(meta["stride"]), meta["padding"], meta["requested"],
                tuple(ot) if ot else None)
            return cls(spec=spec, u_dw=jnp.asarray(arrays["u_dw"]),
                       u_pw=jnp.asarray(arrays["u_pw"]))
        spec = SeparableSpec(
            x_shape=tuple(meta["x_shape"]),
            w_dw_shape=tuple(meta["w_dw_shape"]),
            w_pw_shape=tuple(meta["w_pw_shape"]), dtype=meta["dtype"],
            stride=tuple(meta["stride"]), padding=meta["padding"],
            requested=meta["requested"], mode="composed",
            output_tile=tuple(ot) if ot else None)
        return cls(spec=spec,
                   dw=ConvPlan.from_artifact(meta["dw"],
                                             _sub_arrays(arrays, "dw.")),
                   pw=ConvPlan.from_artifact(meta["pw"],
                                             _sub_arrays(arrays, "pw.")))


def _sub_arrays(arrays: dict, prefix: str) -> dict:
    """Select the `prefix`-namespaced entries of a nested artifact's array
    dict, prefix stripped."""
    return {k[len(prefix):]: v for k, v in arrays.items()
            if k.startswith(prefix)}


def _build_separable_fused_spec(x_shape, dw_shape, pw_shape, dtype_str,
                                stride, padding, requested,
                                output_tile) -> SeparableSpec:
    """Derive the fused-mode SeparableSpec (transform set, conv geometry,
    halo blocking) -- shared by plan_separable_block and artifact reload."""
    n, h, wdt, c = x_shape
    kh, kw = dw_shape[:2]
    mh, mw = _resolve_output_tile(kh, kw, output_tile)
    ct_h, ct_w = cook_toom(mh, kh), cook_toom(mw, kw)
    geom = _wg.conv2d_geometry(h, wdt, kh, kw, mh, mw, padding)
    stream = _wg.stream_geometry(geom.n_h, geom.n_w, c, pw_shape[3],
                                 ct_h, ct_w)
    return SeparableSpec(
        x_shape=x_shape, w_dw_shape=dw_shape, w_pw_shape=pw_shape,
        dtype=dtype_str, stride=stride, padding=padding,
        requested=requested, mode="fused_pallas", output_tile=(mh, mw),
        ct_h=ct_h, ct_w=ct_w, geometry=geom, stream=stream)


def plan_separable_block(
    x_shape: tuple[int, ...],
    w_dw: jax.Array,
    w_pw: jax.Array,
    *,
    stride: int | tuple[int, int] = 1,
    padding: Padding = "SAME",
    algorithm: Algorithm = "auto",
    output_tile: int | tuple[int, int] | None = None,
    dtype=None,
    compute_dtype: str = "float32",
) -> SeparableBlockPlan:
    """Plan a depthwise kxk conv and its following 1x1 pointwise conv as one
    unit (the MobileNet separable block).

    With a Pallas algorithm on a fusable configuration (stride 1, suitable
    filter size, channel multiplier 1) the block is planned onto the fused
    streamed kernel: the depthwise output stays in VMEM and feeds the
    pointwise GEMM directly, with both bias+activation epilogues applied
    in-kernel. Every other configuration composes two ConvPlans (the
    depthwise one falling back per the usual suitability rules), so this
    entry point never rejects a block shape.

    A reduced `compute_dtype` (bfloat16 / int8) always composes: the fused
    separable kernel is fp32-only, and the composed sub-plans each carry
    their own quantized transform-domain filter + epilogue scales.
    """
    global _CACHE_HITS, _CACHE_MISSES
    t0 = time.perf_counter()
    x_shape = tuple(x_shape)
    dw_shape, pw_shape = tuple(w_dw.shape), tuple(w_pw.shape)
    if algorithm not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {algorithm!r}; expected one of "
                         f"{ALGORITHMS}")
    if len(x_shape) != 4 or len(dw_shape) != 4 or len(pw_shape) != 4:
        raise ValueError(f"expected NHWC x HWIO x HWIO, got {x_shape} x "
                         f"{dw_shape} x {pw_shape}")
    n, h, wdt, c = x_shape
    kh, kw = dw_shape[:2]
    if dw_shape[2] != 1 or dw_shape[3] % c:
        raise ValueError(f"depthwise filter must be (kh, kw, 1, C*mult) for "
                         f"C={c}, got {dw_shape}")
    if pw_shape[:2] != (1, 1) or pw_shape[2] != dw_shape[3]:
        raise ValueError(f"pointwise filter must be (1, 1, {dw_shape[3]}, M), "
                         f"got {pw_shape}")
    stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
    dtype = dtype or w_dw.dtype
    dtype_str = str(jnp.dtype(dtype))
    mult = dw_shape[3] // c
    pallas = algorithm in ("pallas_winograd", "pallas_winograd_materialized",
                           "pallas_im2col")
    dw_query = registry.as_query(kh, kw, stride, groups=c, c_in=c,
                                 c_out=dw_shape[3])
    # Only the streamed-kernel request fuses; the Pallas *baseline*
    # algorithms must never be silently substituted with the fast path
    # (their whole point is to be the other arm of an A/B). The fused
    # separable kernel itself is stride-1 only -- stride-2 blocks compose a
    # strided depthwise plan with a pointwise plan below.
    fusable = (algorithm == "pallas_winograd" and mult == 1
               and stride == (1, 1)
               and str(jnp.dtype(compute_dtype)) == "float32"
               and registry.supported("pallas_winograd", dw_query))

    if fusable:
        key = ("sepblock", x_shape, dw_shape, pw_shape, dtype_str, stride,
               padding, algorithm, output_tile)
        spec = _SPEC_CACHE.get(key) if _cache_enabled() else None
        if spec is not None:
            _count_cache(True)
        else:
            _count_cache(False)
            spec = _build_separable_fused_spec(
                x_shape, dw_shape, pw_shape, dtype_str, stride, padding,
                algorithm, output_tile)
            if _cache_enabled():
                _SPEC_CACHE[key] = spec
        u_dw = _depthwise_domain_taps(w_dw, spec.ct_h, spec.ct_w, c,
                                      spec.stream.c_pad)
        u_pw = jnp.pad(w_pw.reshape(c, pw_shape[3]),
                       ((0, spec.stream.c_pad - c),
                        (0, spec.stream.m_pad - pw_shape[3])))
        return SeparableBlockPlan(spec=spec, u_dw=u_dw, u_pw=u_pw,
                                  build_time_s=time.perf_counter() - t0)

    # composed fallback: two plans, each on its best available executor.
    if pallas:
        # reached when the block cannot fuse (stride > 1, unsuitable k,
        # mult > 1) or a Pallas baseline was requested. The streamed-kernel
        # family keeps its own depthwise executors where one is declared
        # (e.g. the stride-2 streamed depthwise kernel); the Pallas
        # *baselines* have no depthwise executor and run grouped im2row.
        if algorithm == "pallas_winograd" and registry.supported(algorithm,
                                                                 dw_query):
            dw_alg = "pallas_winograd"
        else:
            dw_alg = "im2col"
        pw_alg = "pallas_im2col"
    else:
        dw_alg = algorithm
        if algorithm == "winograd" and not registry.supported("winograd",
                                                              dw_query):
            dw_alg = "im2col"
        pw_alg = "im2col" if algorithm == "im2col" else "auto"
    dw = plan_conv2d(x_shape, w_dw, stride=stride, padding=padding,
                     algorithm=dw_alg, groups=c, output_tile=output_tile,
                     dtype=dtype, compute_dtype=compute_dtype)
    pw = plan_conv2d(dw.out_shape, w_pw, stride=1, padding="SAME",
                     algorithm=pw_alg, dtype=dtype,
                     compute_dtype=compute_dtype)
    spec = SeparableSpec(x_shape=x_shape, w_dw_shape=dw_shape,
                         w_pw_shape=pw_shape, dtype=dtype_str, stride=stride,
                         padding=padding, requested=algorithm,
                         mode="composed")
    return SeparableBlockPlan(spec=spec, dw=dw, pw=pw,
                              build_time_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Inverted residual blocks (MobileNet-v2): expand -> depthwise -> project
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InvertedResidualPlan:
    """A planned MobileNet-v2 inverted residual unit: 1x1 expand (+bias,
    activation) -> kxk depthwise (+bias, activation) -> 1x1 linear project
    (+bias, NO activation) -> residual add when stride 1 and C_in == C_out.

    Built on the separable-block machinery: the depthwise+project pair is
    ONE SeparableBlockPlan, so on the Pallas path (stride 1, suitable k,
    multiplier 1) it runs as a single streamed kernel with the intermediate
    in VMEM; the expand conv is a pure channel GEMM XLA maps to the MXU
    directly. Stride-2 blocks compose, with the depthwise half on the
    strided transform-domain executors."""

    x_shape: tuple[int, ...]
    stride: tuple[int, int]
    residual: bool
    expand: ConvPlan | None            # None when expansion factor is 1
    sep: SeparableBlockPlan
    build_time_s: float = 0.0

    def __call__(self, x: jax.Array, **kwargs) -> jax.Array:
        return self.apply(x, **kwargs)

    def apply(self, x: jax.Array, bias_exp: jax.Array | None = None,
              bias_dw: jax.Array | None = None,
              bias_pw: jax.Array | None = None,
              activation: str = "relu6") -> jax.Array:
        h = x
        if self.expand is not None:
            h = self.expand.apply(h, bias=bias_exp, activation=activation)
        y = self.sep.apply(h, bias_dw=bias_dw, bias_pw=bias_pw,
                           inner_activation=activation,
                           activation="none")        # linear bottleneck
        return x + y if self.residual else y

    @property
    def mode(self) -> str:
        return self.sep.mode

    @property
    def out_shape(self) -> tuple[int, ...]:
        return self.sep.out_shape

    # ---- LayerPlan protocol: describe + artifact (de)serialization -------

    def describe(self) -> dict:
        d = self.sep.describe()
        executor = d["executor"]
        cd = d.get("compute_dtype", "float32")
        if self.expand is not None:
            executor = f"{self.expand.algorithm}+{executor}"
            exp_cd = self.expand.spec.compute_dtype
            if exp_cd != cd:
                cd = f"{exp_cd}+{cd}"
        return {"kind": "inverted_residual", "executor": executor,
                "compute_dtype": cd,
                "requested": d["requested"], "mode": self.mode,
                "filter": ("1x1+" if self.expand is not None else "")
                + d["filter"],
                "stride": f"{self.stride[0]}x{self.stride[1]}",
                "groups": self.sep.spec.x_shape[3],
                "tile": d["tile"],
                "residual": self.residual}

    def to_artifact(self) -> tuple[dict, dict]:
        meta = {"kind": "inverted_residual", "x_shape": list(self.x_shape),
                "stride": list(self.stride), "residual": self.residual,
                "expand": None}
        arrays = {}
        if self.expand is not None:
            meta["expand"], exp_arrays = self.expand.to_artifact()
            arrays.update({f"exp.{k}": v for k, v in exp_arrays.items()})
        meta["sep"], sep_arrays = self.sep.to_artifact()
        arrays.update({f"sep.{k}": v for k, v in sep_arrays.items()})
        return meta, arrays

    @classmethod
    def from_artifact(cls, meta: dict,
                      arrays: dict) -> "InvertedResidualPlan":
        expand = None
        if meta["expand"] is not None:
            expand = ConvPlan.from_artifact(meta["expand"],
                                            _sub_arrays(arrays, "exp."))
        sep = SeparableBlockPlan.from_artifact(meta["sep"],
                                               _sub_arrays(arrays, "sep."))
        return cls(x_shape=tuple(meta["x_shape"]),
                   stride=tuple(meta["stride"]), residual=meta["residual"],
                   expand=expand, sep=sep)


def plan_inverted_residual(
    x_shape: tuple[int, ...],
    w_exp: jax.Array | None,
    w_dw: jax.Array,
    w_pw: jax.Array,
    *,
    stride: int | tuple[int, int] = 1,
    padding: Padding = "SAME",
    algorithm: Algorithm = "auto",
    output_tile: int | tuple[int, int] | None = None,
    dtype=None,
    compute_dtype: str = "float32",
) -> InvertedResidualPlan:
    """Plan a MobileNet-v2 inverted residual block as one unit.

    `w_exp` is the (1, 1, C, C*t) expansion filter (None for expand factor
    1), `w_dw` the (k, k, 1, C*t) depthwise filter, `w_pw` the
    (1, 1, C*t, M) linear projection. The depthwise+project pair rides
    plan_separable_block (fused streamed kernel where it applies); the
    residual connection is planned in when stride is 1 and M == C."""
    t0 = time.perf_counter()
    x_shape = tuple(x_shape)
    stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
    expand = None
    inner_shape = x_shape
    if w_exp is not None:
        # 1x1 expand: a pure channel GEMM -- "auto" resolves it to the
        # im2row executor, which for 1x1 is exactly one XLA matmul.
        expand = plan_conv2d(x_shape, w_exp, stride=1, padding="SAME",
                             algorithm="auto", dtype=dtype,
                             compute_dtype=compute_dtype)
        inner_shape = expand.out_shape
    sep = plan_separable_block(inner_shape, w_dw, w_pw, stride=stride,
                               padding=padding, algorithm=algorithm,
                               output_tile=output_tile, dtype=dtype,
                               compute_dtype=compute_dtype)
    residual = stride == (1, 1) and x_shape[3] == tuple(w_pw.shape)[3]
    return InvertedResidualPlan(
        x_shape=x_shape, stride=stride, residual=residual, expand=expand,
        sep=sep, build_time_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# conv1d plans (sequence convolutions, incl. polyphase stride > 1)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Conv1DPlan:
    """Planned (B, L, C) x (k, C, M) -> (B, L', M) sequence convolution.

    mode "as2d": stride-1, executed through a 2D plan on (B, L, 1, C).
    mode "polyphase": stride > 1 decomposed into stride-1 Cook-Toom
      sub-convolutions (sub-filter w[p::s] over sub-sequence x[p::s]), each
      planned independently; geometry (padding, output length) precomputed.
    mode "im2col": strided baseline through a 2D im2col plan.
    """

    x_shape: tuple[int, ...]
    w_shape: tuple[int, ...]
    stride: int
    padding: str
    requested: str
    mode: str
    inner: ConvPlan | None = None
    subplans: tuple[ConvPlan, ...] = ()
    pad: tuple[int, int] = (0, 0)
    out_len: int = 0
    build_time_s: float = 0.0

    def __call__(self, x: jax.Array, **kwargs) -> jax.Array:
        return self.apply(x, **kwargs)

    def apply(self, x: jax.Array, bias: jax.Array | None = None,
              activation: str = "none") -> jax.Array:
        if self.mode in ("as2d", "im2col"):
            return self.inner.apply(x[:, :, None, :], bias=bias,
                                    activation=activation)[:, :, 0, :]
        # polyphase: y[i] = sum_p (w[p::s] (*) x[p::s])[i]. The epilogue can
        # only run after the cross-phase sum, so it stays an XLA op here.
        s = self.stride
        x = jnp.pad(x, ((0, 0), self.pad, (0, 0)))
        acc = None
        for p, sub in enumerate(self.subplans):
            sub_x = x[:, p::s, None, :]
            y = sub.apply(sub_x)[:, :self.out_len, 0, :]
            acc = y if acc is None else acc + y
        return _epilogue_jnp(acc, bias, activation)

    # ---- LayerPlan protocol: describe + artifact (de)serialization -------

    def describe(self) -> dict:
        if self.mode == "polyphase":
            executor = (f"polyphase[{'+'.join(s.algorithm for s in self.subplans)}]")
        else:
            executor = self.inner.algorithm
        return {"kind": "conv1d", "executor": executor,
                "requested": self.requested, "mode": self.mode,
                "filter": f"k={self.w_shape[0]}", "stride": str(self.stride),
                "groups": 1, "tile": "-"}

    def to_artifact(self) -> tuple[dict, dict]:
        meta = {"kind": "conv1d", "mode": self.mode,
                "x_shape": list(self.x_shape), "w_shape": list(self.w_shape),
                "stride": self.stride, "padding": self.padding,
                "requested": self.requested, "pad": list(self.pad),
                "out_len": self.out_len}
        arrays = {}
        if self.mode in ("as2d", "im2col"):
            meta["inner"], inner_arrays = self.inner.to_artifact()
            arrays.update({f"inner.{k}": v for k, v in inner_arrays.items()})
        else:
            subs = []
            for i, sub in enumerate(self.subplans):
                sm, sa = sub.to_artifact()
                subs.append(sm)
                arrays.update({f"sub{i}.{k}": v for k, v in sa.items()})
            meta["subplans"] = subs
        return meta, arrays

    @classmethod
    def from_artifact(cls, meta: dict, arrays: dict) -> "Conv1DPlan":
        base = dict(x_shape=tuple(meta["x_shape"]),
                    w_shape=tuple(meta["w_shape"]), stride=meta["stride"],
                    padding=meta["padding"], requested=meta["requested"],
                    mode=meta["mode"], pad=tuple(meta["pad"]),
                    out_len=meta["out_len"])
        if meta["mode"] in ("as2d", "im2col"):
            inner = ConvPlan.from_artifact(meta["inner"],
                                           _sub_arrays(arrays, "inner."))
            return cls(inner=inner, **base)
        subplans = tuple(
            ConvPlan.from_artifact(sm, _sub_arrays(arrays, f"sub{i}."))
            for i, sm in enumerate(meta["subplans"]))
        return cls(subplans=subplans, **base)


def plan_conv1d(
    x_shape: tuple[int, ...],
    w: jax.Array,
    *,
    stride: int = 1,
    padding: Padding = "SAME",
    algorithm: Algorithm = "auto",
    output_tile: int | None = None,
) -> Conv1DPlan:
    """Plan a (B, L, C) x (k, C, M) sequence convolution (see Conv1DPlan)."""
    t0 = time.perf_counter()
    x_shape = tuple(x_shape)
    b, length, c = x_shape
    k, _, m = w.shape
    base = dict(x_shape=x_shape, w_shape=tuple(w.shape), stride=stride,
                padding=padding, requested=algorithm)
    if stride == 1:
        inner = plan_conv2d((b, length, 1, c), w[:, None, :, :], stride=1,
                            padding=padding, algorithm=algorithm,
                            output_tile=output_tile)
        return Conv1DPlan(mode="as2d", inner=inner,
                          build_time_s=time.perf_counter() - t0, **base)

    if algorithm in ("winograd", "auto") and k > stride:
        if padding == "SAME":
            out = -(-length // stride)
            total = max((out - 1) * stride + k - length, 0)
            pad = (total // 2, total - total // 2)
        else:
            out = (length - k) // stride + 1
            pad = (0, 0)
        padded = length + pad[0] + pad[1]
        subplans = []
        for p in range(stride):
            sub_w = w[p::stride]                    # (ceil((k-p)/s), C, M)
            sub_len = -(-(padded - p) // stride)
            subplans.append(plan_conv2d(
                (b, sub_len, 1, c), sub_w[:, None, :, :], stride=1,
                padding="VALID", algorithm="auto", output_tile=output_tile))
        return Conv1DPlan(mode="polyphase", subplans=tuple(subplans),
                          pad=pad, out_len=out,
                          build_time_s=time.perf_counter() - t0, **base)

    inner = plan_conv2d((b, length, 1, c), w[:, None, :, :],
                        stride=(stride, 1), padding=padding,
                        algorithm="im2col")
    return Conv1DPlan(mode="im2col", inner=inner,
                      build_time_s=time.perf_counter() - t0, **base)


# ---------------------------------------------------------------------------
# Depthwise causal Cook-Toom conv1d plans (Mamba's short conv)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DepthwiseConv1DSpec:
    """Cacheable decisions of a planned (B, L, C) x (r, C) causal depthwise
    Cook-Toom convolution: the F(m, r) transform set, tile count, padding and
    kernel blocking -- everything the unplanned path re-derived per call."""

    x_shape: tuple[int, ...]          # (B, L, C) the plan was built for
    w_shape: tuple[int, ...]          # (r, C)
    dtype: str
    output_tile: int
    backend: str                      # "jnp" | "pallas"
    ct: CookToom = None
    n_tiles: int = 0
    pad_hi: int = 0                   # right pad so tiles cover n_tiles * m
    blocks: tuple[int, int] | None = None   # (block_s, block_c), pallas only


@dataclasses.dataclass(frozen=True)
class DepthwiseConv1DPlan:
    """Spec + taps in the Cook-Toom domain. apply(x) performs no cook_toom
    construction, tile-count or padding derivation -- only the input work."""

    spec: DepthwiseConv1DSpec
    u: jax.Array                      # (t, C) (jnp) / (t, Cp) (pallas) taps
    build_time_s: float = 0.0

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.apply(x)

    def apply(self, x: jax.Array) -> jax.Array:
        spec = self.spec
        if x.shape[1:] != spec.x_shape[1:]:
            raise ValueError(
                f"plan built for input {spec.x_shape} got {x.shape} "
                f"(batch may differ; L/C must match)")
        if spec.backend == "pallas":
            from repro.kernels import ops
            return ops.ct_depthwise_causal_conv1d_planned(
                x, self.u, ct=spec.ct, n_tiles=spec.n_tiles,
                pad_hi=spec.pad_hi, blocks=spec.blocks,
                c_in=spec.w_shape[1])
        return _wg.ct_depthwise_causal_conv1d_pretransformed(
            x, self.u, spec.ct, n_tiles=spec.n_tiles, pad_hi=spec.pad_hi)

    # ---- LayerPlan protocol: describe + artifact (de)serialization -------

    def describe(self) -> dict:
        spec = self.spec
        return {"kind": "conv1d_depthwise",
                "executor": f"ct_causal_{spec.backend}",
                "requested": spec.backend, "filter": f"k={spec.w_shape[0]}",
                "stride": "1", "groups": spec.w_shape[1],
                "tile": str(spec.output_tile)}

    def to_artifact(self) -> tuple[dict, dict]:
        spec = self.spec
        meta = {"kind": "conv1d_depthwise", "x_shape": list(spec.x_shape),
                "w_shape": list(spec.w_shape), "dtype": spec.dtype,
                "output_tile": spec.output_tile, "backend": spec.backend}
        return meta, {"u": np.asarray(self.u)}

    @classmethod
    def from_artifact(cls, meta: dict,
                      arrays: dict) -> "DepthwiseConv1DPlan":
        r = meta["w_shape"][0]
        length = meta["x_shape"][1]
        ct = cook_toom(meta["output_tile"], r)
        nt = -(-length // ct.m)
        blocks = None
        if meta["backend"] == "pallas":
            from repro.kernels import ops
            blocks = ops.conv1d_ct_blocks(nt, meta["w_shape"][1])
        spec = DepthwiseConv1DSpec(
            x_shape=tuple(meta["x_shape"]), w_shape=tuple(meta["w_shape"]),
            dtype=meta["dtype"], output_tile=meta["output_tile"],
            backend=meta["backend"], ct=ct, n_tiles=nt,
            pad_hi=nt * ct.m - length, blocks=blocks)
        return cls(spec=spec, u=jnp.asarray(arrays["u"]))


def plan_depthwise_conv1d(
    x_shape: tuple[int, ...],
    w: jax.Array,
    *,
    output_tile: int = 4,
    backend: str = "jnp",
    dtype=None,
) -> DepthwiseConv1DPlan:
    """Plan a causal depthwise Cook-Toom conv (B, L, C) x (r, C) -> (B, L, C).

    Decisions (cook_toom transform set, tile count, padding, Pallas blocking)
    are made once and cached process-wide keyed on (shape, dtype, output
    tile, backend); the taps are transformed into the Cook-Toom domain here.
    models/mamba.py routes its short conv through this, so the hot path does
    only input work per call.
    """
    global _CACHE_HITS, _CACHE_MISSES
    t0 = time.perf_counter()
    x_shape = tuple(x_shape)
    if len(x_shape) != 3 or len(w.shape) != 2 or x_shape[2] != w.shape[1]:
        raise ValueError(f"expected (B, L, C) x (r, C), got "
                         f"{x_shape} x {tuple(w.shape)}")
    r, c = w.shape
    length = x_shape[1]
    dtype_str = str(jnp.dtype(dtype or w.dtype))
    key = ("dwconv1d", x_shape, tuple(w.shape), dtype_str, output_tile,
           backend)
    spec = _SPEC_CACHE.get(key) if _cache_enabled() else None
    if spec is not None:
        _count_cache(True)
    else:
        _count_cache(False)
        ct = cook_toom(output_tile, r)
        nt = -(-length // ct.m)
        blocks = None
        if backend == "pallas":
            from repro.kernels import ops
            blocks = ops.conv1d_ct_blocks(nt, c)
        elif backend != "jnp":
            raise ValueError(f"unknown backend {backend!r}")
        spec = DepthwiseConv1DSpec(
            x_shape=x_shape, w_shape=tuple(w.shape), dtype=dtype_str,
            output_tile=output_tile, backend=backend, ct=ct, n_tiles=nt,
            pad_hi=nt * ct.m - length, blocks=blocks)
        if _cache_enabled():
            _SPEC_CACHE[key] = spec

    u = jnp.einsum("ij,jc->ic", jnp.asarray(spec.ct.G, w.dtype), w)  # (t, C)
    if spec.backend == "pallas":
        bc = spec.blocks[1]
        pad_c = -(-c // bc) * bc - c
        if pad_c:
            u = jnp.pad(u, ((0, 0), (0, pad_c)))
    return DepthwiseConv1DPlan(spec=spec, u=u,
                               build_time_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# LayerPlan protocol dispatcher (artifact reload)
# ---------------------------------------------------------------------------

#: kind tag (to_artifact meta["kind"]) -> plan class. Every class conforms
#: to the LayerPlan protocol: apply(x, ...), describe(), to_artifact(),
#: from_artifact(meta, arrays).
PLAN_KINDS = {
    "conv2d": ConvPlan,
    "separable": SeparableBlockPlan,
    "inverted_residual": InvertedResidualPlan,
    "conv1d": Conv1DPlan,
    "conv1d_depthwise": DepthwiseConv1DPlan,
}


def plan_from_artifact(meta: dict, arrays: dict):
    """Rebuild any LayerPlan from its (meta, arrays) artifact pair. The
    inverse of .to_artifact(): geometry is re-derived deterministically from
    the saved decisions; the execution-domain weights are taken verbatim
    (no filter transform runs)."""
    kind = meta.get("kind")
    if kind not in PLAN_KINDS:
        raise ValueError(f"unknown plan artifact kind {kind!r}; expected one "
                         f"of {sorted(PLAN_KINDS)}")
    return PLAN_KINDS[kind].from_artifact(meta, arrays)
