"""Shared benchmark utilities: wall-clock timing of jitted callables and the
layer-shape inventories of the paper's five networks."""

from __future__ import annotations

import math
import subprocess
import time
from typing import Callable

import jax
import numpy as np


def bench_metadata() -> dict:
    """Environment stamp for emitted BENCH_*.json artifacts: jax version,
    backend/device kind, git SHA and a timestamp, so the perf trajectory is
    comparable across runs and machines."""
    try:
        sha = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    dev = jax.devices()[0]
    return {"jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_kind": getattr(dev, "device_kind", str(dev)),
            "device_count": jax.device_count(),
            "git_sha": sha,
            "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime())}


def time_jitted(fn: Callable, *args, warmup: int = 2, iters: int = 5,
                inner: int = 1) -> float:
    """Median wall-time (seconds) of fn(*args) after jit warmup."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / inner)
    return float(np.median(times))


def pairwise_min_times(fa: Callable, fb: Callable, x, warmup: int = 2,
                       iters: int = 5) -> tuple[float, float]:
    """Interleaved best-of timing of two callables on the same input.

    Interleaving cancels slow drift (thermal / co-tenant noise) that makes
    back-to-back medians unreliable; min is the steady-state floor."""
    for _ in range(warmup):
        jax.block_until_ready(fa(x))
        jax.block_until_ready(fb(x))
    ta, tb = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fa(x))
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fb(x))
        tb.append(time.perf_counter() - t0)
    return min(ta), min(tb)


#: storage bytes per element of each transform-domain compute dtype --
#: feeds the filter_elem_bytes parameter of the HBM-bytes models below, so
#: the paper's figure of merit (bytes moved on a bandwidth-bound mobile
#: CPU) reflects bf16/int8 filter payloads.
COMPUTE_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def dtype_bytes(compute_dtype: str) -> int:
    """Storage bytes per element of a transform-domain compute dtype."""
    return COMPUTE_DTYPE_BYTES[str(compute_dtype)]


def streamed_hbm_bytes(spec, batch: int = 1, elem_bytes: int = 4,
                       filter_elem_bytes: int | None = None) -> int:
    """Analytic HBM bytes moved per call by the streaming Winograd executor
    (kernels.winograd.winograd_streamed): halo strip reads (each strip is
    DMA'd once per (M sweep, C block) because the input block index carries
    the channel slice, and adjacent strips re-read their k-1 halo rows/cols)
    + filter block reads (re-fetched per strip) + NHWC output write. No tile
    tensor, no separate epilogue round trips. `elem_bytes` is the
    activation element size (fp32 default); `filter_elem_bytes` the
    transform-domain filter element size (defaults to elem_bytes; pass
    dtype_bytes(compute_dtype) for bf16/int8 plans -- their O(M) dequant
    scale rows are ignored, O(P*C*M) filter traffic dominates). The full
    derivation is in EXPERIMENTS.md section Perf."""
    if filter_elem_bytes is None:
        filter_elem_bytes = elem_bytes
    s = spec.stream
    th, tw = spec.ct_h.t, spec.ct_w.t
    mh, mw = spec.ct_h.m, spec.ct_w.m
    p = th * tw
    hs = s.bh * mh + th - mh
    ws = s.bw * mw + tw - mw
    n_strips = batch * s.n_hb
    n_mb = s.m_pad // s.block_m
    read_x = n_strips * hs * ws * s.c_pad * n_mb * elem_bytes
    read_u = n_strips * p * s.c_pad * s.m_pad * filter_elem_bytes
    write_y = batch * (s.n_hb * s.bh * mh) * (s.bw * mw) \
        * s.m_pad * elem_bytes
    return read_x + read_u + write_y


def materialized_hbm_bytes(spec, batch: int = 1, elem_bytes: int = 4,
                           filter_elem_bytes: int | None = None) -> int:
    """Analytic HBM bytes moved per call by the pre-streaming executor
    (ops.winograd_conv2d_planned_materialized + XLA epilogue): padded input
    read, (R, th, tw, C) tile tensor write + per-M-block re-read, filter
    reads, kernel output write, un-tiling read+write, and the bias+relu
    round trips. Element sizes as in streamed_hbm_bytes; see EXPERIMENTS.md
    section Perf."""
    if filter_elem_bytes is None:
        filter_elem_bytes = elem_bytes
    g = spec.geometry
    br, bc, bm = spec.blocks
    th, tw = spec.ct_h.t, spec.ct_w.t
    mh, mw = spec.ct_h.m, spec.ct_w.m
    p = th * tw
    c_in, c_out = spec.w_shape[2], spec.w_shape[3]
    r = batch * g.n_h * g.n_w
    r_pad = -(-r // br) * br
    c_pad = -(-c_in // bc) * bc
    m_pad = -(-c_out // bm) * bm
    n_mb, n_cb = m_pad // bm, c_pad // bc
    read_x = batch * (g.n_h * mh + th - mh) * (g.n_w * mw + tw - mw) \
        * c_in * elem_bytes
    tiles = r_pad * p * c_pad * elem_bytes
    write_tiles = tiles
    read_tiles = tiles * n_mb                 # re-read per M block
    read_u = (r_pad // br) * n_mb * n_cb * p * bc * bm * filter_elem_bytes
    write_kernel_out = r_pad * mh * mw * m_pad * elem_bytes
    out_nhwc = batch * g.out_h * g.out_w * c_out * elem_bytes
    untile = write_kernel_out + out_nhwc      # transpose/reshape pass
    epilogue = 4 * out_nhwc                   # bias add + relu, each r+w
    return (read_x + write_tiles + read_tiles + read_u + write_kernel_out
            + untile + epilogue)


def separable_fused_hbm_bytes(spec, batch: int = 1, elem_bytes: int = 4,
                              filter_elem_bytes: int | None = None) -> int:
    """Analytic HBM bytes per call of the FUSED separable-block kernel
    (kernels.depthwise.separable_streamed, spec a plan.SeparableSpec): halo
    strip reads (the input block index carries the channel slice and recurs
    per pointwise M block), depthwise-tap and pointwise-filter block reads,
    and the NHWC output write. The depthwise -> pointwise intermediate
    moves ZERO bytes -- it lives in the kernel's VMEM z-cache. Element
    sizes as in streamed_hbm_bytes."""
    if filter_elem_bytes is None:
        filter_elem_bytes = elem_bytes
    s = spec.stream
    th, tw = spec.ct_h.t, spec.ct_w.t
    mh, mw = spec.ct_h.m, spec.ct_w.m
    p = th * tw
    hs = s.bh * mh + th - mh
    ws = s.bw * mw + tw - mw
    n_strips = batch * s.n_hb
    n_mb = s.m_pad // s.block_m
    read_x = n_strips * hs * ws * s.c_pad * n_mb * elem_bytes
    read_u_dw = n_strips * p * s.c_pad * n_mb * filter_elem_bytes
    read_u_pw = n_strips * s.c_pad * s.m_pad * filter_elem_bytes
    write_y = batch * (s.n_hb * s.bh * mh) * (s.bw * mw) \
        * s.m_pad * elem_bytes
    return read_x + read_u_dw + read_u_pw + write_y


def separable_unfused_hbm_bytes(dw_spec, pw_mm: int, pw_k: int, pw_n: int,
                                blocks: tuple[int, int, int],
                                batch: int = 1, elem_bytes: int = 4,
                                filter_elem_bytes: int | None = None) -> int:
    """Analytic HBM bytes per call of the UNFUSED Pallas separable pipeline:
    the streamed depthwise kernel (one C sweep of halo strips + taps +
    intermediate write), then the pointwise GEMM kernel re-reading the
    intermediate once per output-channel block plus its filter blocks and
    output write. `dw_spec` is the pallas_depthwise ConvSpec; (pw_mm, pw_k,
    pw_n) the pointwise GEMM dims; `blocks` its (bm, bk, bn). Element sizes
    as in streamed_hbm_bytes."""
    if filter_elem_bytes is None:
        filter_elem_bytes = elem_bytes
    s = dw_spec.stream
    th, tw = dw_spec.ct_h.t, dw_spec.ct_w.t
    mh, mw = dw_spec.ct_h.m, dw_spec.ct_w.m
    p = th * tw
    hs = s.bh * mh + th - mh
    ws = s.bw * mw + tw - mw
    n_strips = batch * s.n_hb
    read_x = n_strips * hs * ws * s.c_pad * elem_bytes
    read_u_dw = n_strips * p * s.c_pad * filter_elem_bytes
    write_z = batch * (s.n_hb * s.bh * mh) * (s.bw * mw) \
        * s.c_pad * elem_bytes
    bm_, bk_, bn_ = blocks
    mm_pad = -(-pw_mm // bm_) * bm_
    k_pad = -(-pw_k // bk_) * bk_
    n_pad = -(-pw_n // bn_) * bn_
    n_nb = n_pad // bn_
    read_z = mm_pad * k_pad * n_nb * elem_bytes  # A re-read per N block
    read_u_pw = (mm_pad // bm_) * k_pad * n_pad * filter_elem_bytes
    write_y = mm_pad * n_pad * elem_bytes
    return read_x + read_u_dw + write_z + read_z + read_u_pw + write_y


def strided_streamed_hbm_bytes(spec, batch: int = 1, elem_bytes: int = 4,
                               filter_elem_bytes: int | None = None) -> int:
    """Analytic HBM bytes per call of the stride-2 streaming Winograd kernel
    (kernels.winograd.winograd_strided_streamed): full-resolution halo strip
    reads (2x extent per axis, re-DMA'd per (M sweep, C block)), phase-major
    filter block reads (4P points), and the stride-2 NHWC output write. The
    four phase tile tensors never exist in HBM -- they are gathered in VMEM
    from the one strip."""
    if filter_elem_bytes is None:
        filter_elem_bytes = elem_bytes
    s = spec.stream
    th, tw = spec.ct_h.t, spec.ct_w.t
    mh, mw = spec.ct_h.m, spec.ct_w.m
    p4 = 4 * th * tw
    hs = 2 * (s.bh * mh + th - mh)
    ws = 2 * (s.bw * mw + tw - mw)
    n_strips = batch * s.n_hb
    n_mb = s.m_pad // s.block_m
    read_x = n_strips * hs * ws * s.c_pad * n_mb * elem_bytes
    read_u = n_strips * p4 * s.c_pad * s.m_pad * filter_elem_bytes
    write_y = batch * (s.n_hb * s.bh * mh) * (s.bw * mw) \
        * s.m_pad * elem_bytes
    return read_x + read_u + write_y


def pallas_im2row_hbm_bytes(spec, batch: int = 1, elem_bytes: int = 4,
                            filter_elem_bytes: int | None = None) -> int:
    """Analytic HBM bytes per call of the planned Pallas im2row baseline
    (ops.im2col_conv2d_planned): input read, patch-matrix write (the
    kh*kw/(sh*sw) read-amplified copy of the input at stride (sh, sw)),
    per-N-block patch re-reads by the GEMM kernel, filter block reads, and
    the output write (epilogue fused in-kernel)."""
    if filter_elem_bytes is None:
        filter_elem_bytes = elem_bytes
    g = spec.geometry
    bm_, bk_, bn_ = spec.blocks
    kh, kw, cg, c_out = spec.w_shape
    c_in = cg * spec.groups
    mm = batch * g.oh * g.ow
    mm_pad = -(-mm // bm_) * bm_
    k_pad = -(-(kh * kw * c_in) // bk_) * bk_
    n_pad = -(-c_out // bn_) * bn_
    h_in, w_in = spec.x_shape[1:3]
    read_x = batch * (h_in + sum(g.ph)) * (w_in + sum(g.pw)) * c_in \
        * elem_bytes
    patches = mm_pad * k_pad * elem_bytes
    read_patches = patches * (n_pad // bn_)       # A re-read per N block
    read_u = (mm_pad // bm_) * k_pad * n_pad * filter_elem_bytes
    write_y = mm_pad * n_pad * elem_bytes
    return read_x + patches + read_patches + read_u + write_y


def fft_hbm_bytes(spec, batch: int = 1, elem_bytes: int = 4,
                  filter_elem_bytes: int | None = None) -> int:
    """Analytic HBM bytes per call of the rfft2 executor (core.fft, spec a
    plan.ConvSpec with algorithm='fft'): padded input read, real tile tensor
    write + re-read by rfft2, forward spectrum write + re-read by the
    complex pointwise GEMM (complex64 = 8 B), conjugated filter-spectrum
    read, product spectrum write + re-read by irfft2, real inverse write,
    and the cropped NHWC output write. XLA fuses some of these round trips;
    the model is the fusion-free dataflow upper bound, the analogue of
    materialized_hbm_bytes for the Winograd baseline. Spectra are complex
    (2 * elem_bytes per point); the filter spectrum uses filter_elem_bytes
    per real component (the executor itself is fp32-only today, but the
    model stays parametric for symmetry with the Winograd models)."""
    if filter_elem_bytes is None:
        filter_elem_bytes = elem_bytes
    g, f = spec.geometry, spec.fft
    c_in, c_out = spec.w_shape[2], spec.w_shape[3]
    n_tiles = batch * g.n_h * g.n_w
    half_w = f.fft_w // 2 + 1
    read_x = batch * (g.n_h * f.m_h + f.fft_h - f.m_h) \
        * (g.n_w * f.m_w + f.fft_w - f.m_w) * c_in * elem_bytes
    tiles = n_tiles * f.fft_h * f.fft_w * c_in * elem_bytes
    spec_in = n_tiles * f.fft_h * half_w * c_in * 2 * elem_bytes
    read_u = f.fft_h * half_w * c_in * c_out * 2 * filter_elem_bytes
    spec_out = n_tiles * f.fft_h * half_w * c_out * 2 * elem_bytes
    inverse = n_tiles * f.fft_h * f.fft_w * c_out * elem_bytes
    write_y = batch * g.out_h * g.out_w * c_out * elem_bytes
    return (read_x + 2 * tiles + 2 * spec_in + read_u + 2 * spec_out
            + inverse + write_y)


def fft_flops(spec, batch: int = 1) -> int:
    """Analytic real FLOPs per call of the rfft2 executor: forward rfft2
    per input channel + inverse per output channel (split-radix estimate
    2.5 * N * log2(N) for a real transform of N points) plus the complex
    pointwise channel GEMM (8 real flops per complex MAC). The transform
    term is independent of the filter size -- the reason FFT wins on large
    filters."""
    g, f = spec.geometry, spec.fft
    c_in, c_out = spec.w_shape[2], spec.w_shape[3]
    n_tiles = batch * g.n_h * g.n_w
    nf = f.fft_h * f.fft_w
    transform = 2.5 * nf * math.log2(nf)
    gemm = n_tiles * f.fft_h * (f.fft_w // 2 + 1) * c_in * c_out * 8
    return int(n_tiles * (c_in + c_out) * transform + gemm)


def winograd_domain_hbm_bytes(spec, batch: int = 1, elem_bytes: int = 4,
                              filter_elem_bytes: int | None = None) -> int:
    """Analytic HBM bytes per call of a pure-JAX Winograd-domain executor
    (spec a plan.ConvSpec with algorithm='winograd'/'winograd_f63'),
    parameterized by the plan's tile size t = spec.ct_h.t so one model
    covers F(2,3)/F(4,3)/F(6,3): padded input read, (t, t) tile tensor
    write + re-read by the input transform, transformed-tile write +
    re-read by the pointwise GEMM, Winograd-domain filter read, point
    product write + re-read by the output transform, inverse write, and
    the cropped NHWC output write (fusion-free dataflow upper bound)."""
    if filter_elem_bytes is None:
        filter_elem_bytes = elem_bytes
    g = spec.geometry
    th, tw = spec.ct_h.t, spec.ct_w.t
    mh, mw = spec.ct_h.m, spec.ct_w.m
    c_in, c_out = spec.w_shape[2], spec.w_shape[3]
    n_tiles = batch * g.n_h * g.n_w
    read_x = batch * (g.n_h * mh + th - mh) * (g.n_w * mw + tw - mw) \
        * c_in * elem_bytes
    tiles = n_tiles * th * tw * c_in * elem_bytes
    transformed = n_tiles * th * tw * c_in * elem_bytes
    read_u = th * tw * c_in * c_out * filter_elem_bytes
    product = n_tiles * th * tw * c_out * elem_bytes
    inverse = n_tiles * mh * mw * c_out * elem_bytes
    write_y = batch * g.out_h * g.out_w * c_out * elem_bytes
    return (read_x + 2 * tiles + 2 * transformed + read_u + 2 * product
            + inverse + write_y)


def winograd_domain_flops(spec, batch: int = 1) -> int:
    """Analytic real FLOPs per call of a pure-JAX Winograd-domain executor:
    the two-sided input transform (B^T d B) per input channel, the (t*t)
    pointwise channel GEMMs, and the two-sided output transform (A^T z A)
    per output channel. With t = spec.ct_h.t this exposes the F(6,3) vs
    F(4,3) trade: 2.25x fewer GEMM flops per output, more transform flops
    per tile."""
    g = spec.geometry
    th, tw = spec.ct_h.t, spec.ct_w.t
    mh, mw = spec.ct_h.m, spec.ct_w.m
    c_in, c_out = spec.w_shape[2], spec.w_shape[3]
    n_tiles = batch * g.n_h * g.n_w
    in_tr = 2 * (th * th * tw + th * tw * tw)          # B^T d, then (.) B
    out_tr = 2 * (mh * th * tw + mh * mw * tw)         # A^T z, then (.) A
    gemm = n_tiles * th * tw * c_in * c_out * 2
    return int(n_tiles * (c_in * in_tr + c_out * out_tr) + gemm)


def conv_layer_inventory(network: str) -> list[dict]:
    """Every conv layer of a paper network as {name, kh, kw, c_in, c_out,
    h, w, stride, suitable}, collected by tracing the spec interpreter."""
    import jax.numpy as jnp

    from repro.models import cnn

    specs_fn, res = cnn.NETWORKS[network]
    specs = specs_fn()
    params = cnn.init_cnn(jax.random.key(0), specs, 3, res=res)
    layers: dict = {}
    x = jnp.zeros((1, res, res, 3), jnp.float32)
    jax.eval_shape(lambda x: cnn.cnn_forward(params, x, specs,
                                             algorithm="im2col",
                                             layer_times=layers), x)
    return [dict(name=k, **v) for k, v in layers.items()]
