"""Public jit'd wrappers around the Pallas kernels.

These mirror the pure-JAX entry points in repro.core (same signatures, same
semantics) and handle all padding/blocking so callers never see alignment
constraints. `interpret` defaults to the shared rule in
repro.kernels.runtime: compiled kernels on a TPU backend, interpret mode
elsewhere.

The planned Winograd path streams regions end-to-end inside the kernel
(winograd_conv2d_planned -> kernels.winograd.winograd_streamed): the only
per-call HBM tensors are the padded NHWC input and the NHWC output, with the
bias+activation epilogue fused into the kernel's store step. The pre-streaming
executor that materialized the (R, th, tw, C) overlapping-tile tensor and
un-tiled the output with a separate transpose pass is kept as
winograd_conv2d_planned_materialized -- the A/B baseline for
benchmarks/per_layer.py and BENCH_PR2.json.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import im2col as _im2col
from repro.core import winograd as _wg
from repro.core.transforms import DEFAULT_OUTPUT_TILE, cook_toom
from repro.kernels import conv1d_ct as _k_conv1d
from repro.kernels import matmul as _k_matmul
from repro.kernels import winograd as _k_winograd
from repro.kernels.runtime import default_interpret as _default_interpret
from repro.kernels.runtime import epilogue_jnp as _epilogue_jnp
from repro.kernels.runtime import kernel_name as _kernel_name
from repro.kernels.runtime import pick_block as _block
from repro.kernels.runtime import resolve_interpret as _resolve_interpret


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, pad) if pad[axis][1] else x


def _pad_bias(bias: jax.Array | None, m_pad: int) -> jax.Array | None:
    """(M,) epilogue bias -> (1, Mp) fp32 for the kernel's bias BlockSpec."""
    if bias is None:
        return None
    return _pad_axis(bias.astype(jnp.float32).reshape(1, -1), 1, m_pad)


# ---------------------------------------------------------------------------
# Winograd conv2d -- halo-streaming planned path
# ---------------------------------------------------------------------------

def winograd_conv2d_planned(
    x: jax.Array,
    u: jax.Array,
    *,
    ct_h,
    ct_w,
    geometry: _wg.Conv2DGeometry,
    stream: _wg.StreamGeometry,
    c_out: int,
    bias: jax.Array | None = None,
    scale: jax.Array | None = None,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Execute a planned streaming Pallas Winograd conv.

    `u` is the pre-transformed, pre-padded (P, Cp, Mp) filter (fp32, or a
    bf16/int8 reduced-precision copy -- the kernel widens at the dot);
    `scale` is the plan's (1, Mp) int8 dequantization row or None. All
    geometry (conv padding, halo strip origins, edge-block padding,
    VMEM-budgeted block sizes) was derived once at plan time. The per-call
    work is one NHWC pad, the kernel, and one crop -- no tile
    materialization, no post-kernel un-tiling, no separate bias/activation
    passes.
    """
    c = x.shape[3]
    xp = jnp.pad(x, ((0, 0),
                     (geometry.lo_h, geometry.hi_h + stream.pad_h),
                     (geometry.lo_w, geometry.hi_w),
                     (0, stream.c_pad - c)))
    y = _k_winograd.winograd_streamed(
        xp, u, _pad_bias(bias, stream.m_pad), scale, ct_h=ct_h, ct_w=ct_w,
        bh=stream.bh, bw=stream.bw, block_c=stream.block_c,
        block_m=stream.block_m, activation=activation, interpret=interpret,
        name=_kernel_name("winograd_streamed"))
    return y[:, :geometry.out_h, :geometry.out_w, :c_out]


def winograd_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    output_tile: int | None = None,
    padding: _wg.Padding = "SAME",
    bias: jax.Array | None = None,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas-backed F(m x m, k x k) convolution, NHWC x HWIO -> NHWC.

    Unplanned compatibility path: derives the filter transform, geometry and
    halo blocking inline, then runs the streaming planned executor. Plan once
    with repro.core.plan.plan_conv2d to skip the derivation on every call.
    """
    n, h, wdt, c = x.shape
    kh, kw, _, mout = w.shape
    if kh == 1 or kw == 1:
        # 1xN / Nx1 / 1x1 layers route through the pure-JAX 1D path (its GEMM
        # is a single matmul XLA already maps to the MXU).
        mt = output_tile or DEFAULT_OUTPUT_TILE.get(max(kh, kw), 2)
        y = _wg.winograd_conv2d(x, w, output_tile=mt, padding=padding)
        return _epilogue_jnp(y, bias, activation)
    mt = output_tile or DEFAULT_OUTPUT_TILE.get(max(kh, kw), 2)
    ct_h, ct_w = cook_toom(mt, kh), cook_toom(mt, kw)
    u = _wg.transform_filter_2d(w, ct_h, ct_w)           # (th, tw, C, M)
    u = u.reshape(ct_h.t * ct_w.t, c, mout)

    geometry = _wg.conv2d_geometry(h, wdt, kh, kw, ct_h.m, ct_w.m, padding)
    stream = _wg.stream_geometry(geometry.n_h, geometry.n_w, c, mout,
                                 ct_h, ct_w)
    u = pad_winograd_filter(u, stream.block_c, stream.block_m)
    return winograd_conv2d_planned(
        x, u, ct_h=ct_h, ct_w=ct_w, geometry=geometry, stream=stream,
        c_out=mout, bias=bias, activation=activation, interpret=interpret)


def winograd_strided_conv2d_planned(
    x: jax.Array,
    u: jax.Array,
    *,
    ct_h,
    ct_w,
    geometry: _wg.Conv2DGeometry,
    stream: _wg.StreamGeometry,
    c_out: int,
    bias: jax.Array | None = None,
    scale: jax.Array | None = None,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Execute a planned stride-2 streaming Pallas Winograd conv (transform-
    domain phase decomposition). `u` is the pre-transformed (4P, Cp, Mp)
    phase-major filter (fp32/bf16/int8); `scale` the (1, Mp) int8 dequant
    row or None; the halo geometry is in full-resolution input units, so
    the edge-block padding is 2x the plan's output-tile surplus."""
    c = x.shape[3]
    xp = jnp.pad(x, ((0, 0),
                     (geometry.lo_h, geometry.hi_h + 2 * stream.pad_h),
                     (geometry.lo_w, geometry.hi_w),
                     (0, stream.c_pad - c)))
    y = _k_winograd.winograd_strided_streamed(
        xp, u, _pad_bias(bias, stream.m_pad), scale, ct_h=ct_h, ct_w=ct_w,
        bh=stream.bh, bw=stream.bw, block_c=stream.block_c,
        block_m=stream.block_m, activation=activation, interpret=interpret,
        name=_kernel_name("winograd_strided_streamed"))
    return y[:, :geometry.out_h, :geometry.out_w, :c_out]


def depthwise_strided_conv2d_planned(
    x: jax.Array,
    u: jax.Array,
    *,
    ct_h,
    ct_w,
    geometry: _wg.Conv2DGeometry,
    stream: _wg.StreamGeometry,
    c_out: int,
    bias: jax.Array | None = None,
    scale: jax.Array | None = None,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Execute a planned stride-2 streamed Pallas depthwise conv: `u` is the
    (4P, Cp) phase-major taps (fp32/bf16/int8); `scale` the (1, Cp) int8
    dequant row or None; halo blocking comes from the plan."""
    from repro.kernels import depthwise as _k_depthwise
    c = x.shape[3]
    xp = jnp.pad(x, ((0, 0),
                     (geometry.lo_h, geometry.hi_h + 2 * stream.pad_h),
                     (geometry.lo_w, geometry.hi_w),
                     (0, stream.c_pad - c)))
    y = _k_depthwise.depthwise_strided_streamed(
        xp, u, _pad_bias(bias, stream.c_pad), scale, ct_h=ct_h, ct_w=ct_w,
        bh=stream.bh, bw=stream.bw, block_c=stream.block_c,
        activation=activation, interpret=interpret,
        name=_kernel_name("depthwise_strided_streamed"))
    return y[:, :geometry.out_h, :geometry.out_w, :c_out]


# ---------------------------------------------------------------------------
# Winograd conv2d -- pre-streaming (materialized-tiles) baseline
# ---------------------------------------------------------------------------

def winograd_blocks(r_tot: int, c: int, mout: int, *, block_r: int = 128,
                    block_c: int = 128, block_m: int = 128
                    ) -> tuple[int, int, int]:
    """(block_r, block_c, block_m) for the materialized-tiles kernel."""
    return _block(r_tot, block_r), _block(c, block_c), _block(mout, block_m)


def pad_winograd_filter(u: jax.Array, block_c: int, block_m: int) -> jax.Array:
    """Pad a (P, C, M) Winograd-domain filter to the kernel's block grid.
    Done once at plan time so apply() never touches the weights."""
    p, c, mout = u.shape
    return _pad_axis(_pad_axis(u, 1, _round_up(c, block_c)),
                     2, _round_up(mout, block_m))


def winograd_conv2d_planned_materialized(
    x: jax.Array,
    u: jax.Array,
    *,
    ct_h,
    ct_w,
    geometry: _wg.Conv2DGeometry,
    blocks: tuple[int, int, int],
    c_in: int,
    c_out: int,
    interpret: bool | None = None,
) -> jax.Array:
    """The pre-streaming planned executor, kept as the A/B baseline: extracts
    the (R, th, tw, C) overlapping-tile tensor in HBM, runs the tiles-domain
    kernel, then un-tiles the output with a transpose/reshape pass. Every
    step the streaming path removes is visible here."""
    interpret = _resolve_interpret(interpret)
    n, h, wdt, c = x.shape
    br, bc, bm = blocks
    nh, nw = geometry.n_h, geometry.n_w
    xp = jnp.pad(x, ((0, 0), (geometry.lo_h, geometry.hi_h),
                     (geometry.lo_w, geometry.hi_w), (0, 0)))
    tiles = _wg._extract_tiles_1d(xp, 1, ct_h.t, ct_h.m, nh)
    tiles = _wg._extract_tiles_1d(tiles, 3, ct_w.t, ct_w.m, nw)
    tiles = tiles.transpose(0, 1, 3, 2, 4, 5).reshape(
        n * nh * nw, ct_h.t, ct_w.t, c)                  # (R, th, tw, C)

    r_tot = tiles.shape[0]
    tiles = _pad_axis(tiles, 0, _round_up(r_tot, br))
    tiles = _pad_axis(tiles, 3, _round_up(c_in, bc))

    y = _k_winograd.winograd_fused(
        tiles, u, ct_h=ct_h, ct_w=ct_w, block_r=br, block_c=bc, block_m=bm,
        interpret=interpret,
        name=_kernel_name("winograd_fused"))      # (Rp, mh, mw, Mp)
    y = y[:r_tot, :, :, :c_out].reshape(n, nh, nw, ct_h.m, ct_w.m, c_out)
    y = y.transpose(0, 1, 3, 2, 4, 5).reshape(
        n, nh * ct_h.m, nw * ct_w.m, c_out)
    return y[:, :geometry.out_h, :geometry.out_w]


# ---------------------------------------------------------------------------
# Depthwise / fused separable streamed paths
# ---------------------------------------------------------------------------

def depthwise_conv2d_planned(
    x: jax.Array,
    u: jax.Array,
    *,
    ct_h,
    ct_w,
    geometry: _wg.Conv2DGeometry,
    stream: _wg.StreamGeometry,
    c_out: int,
    bias: jax.Array | None = None,
    scale: jax.Array | None = None,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Execute a planned streaming Pallas depthwise conv: `u` is the
    pre-transformed, pre-padded (P, Cp, mult) taps (fp32/bf16/int8; mult =
    channel multiplier; output channel o = c*mult + j, the lax ordering);
    `scale` the (1, Cp*mult) int8 dequant row or None; conv padding, halo
    blocking and channel blocks come from the plan. Per-call work is one
    NHWC pad, the kernel, one crop."""
    from repro.kernels import depthwise as _k_depthwise
    c = x.shape[3]
    mult = u.shape[2]
    xp = jnp.pad(x, ((0, 0),
                     (geometry.lo_h, geometry.hi_h + stream.pad_h),
                     (geometry.lo_w, geometry.hi_w),
                     (0, stream.c_pad - c)))
    y = _k_depthwise.depthwise_streamed(
        xp, u, _pad_bias(bias, stream.c_pad * mult), scale, ct_h=ct_h,
        ct_w=ct_w, bh=stream.bh, bw=stream.bw, block_c=stream.block_c,
        activation=activation, interpret=interpret,
        name=_kernel_name("depthwise_streamed"))
    return y[:, :geometry.out_h, :geometry.out_w, :c_out]


def separable_conv2d_planned(
    x: jax.Array,
    u_dw: jax.Array,
    u_pw: jax.Array,
    *,
    ct_h,
    ct_w,
    geometry: _wg.Conv2DGeometry,
    stream: _wg.StreamGeometry,
    c_out: int,
    bias_dw: jax.Array | None = None,
    bias_pw: jax.Array | None = None,
    inner_activation: str = "none",
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Execute a planned fused separable block (depthwise Winograd +
    epilogue + pointwise 1x1 + epilogue in one streamed kernel; the
    intermediate never touches HBM). `u_dw` is the (P, Cp) depthwise taps,
    `u_pw` the (Cp, Mp) pointwise matrix, both pre-padded at plan time."""
    from repro.kernels import depthwise as _k_depthwise
    c = x.shape[3]
    xp = jnp.pad(x, ((0, 0),
                     (geometry.lo_h, geometry.hi_h + stream.pad_h),
                     (geometry.lo_w, geometry.hi_w),
                     (0, stream.c_pad - c)))
    y = _k_depthwise.separable_streamed(
        xp, u_dw, u_pw, _pad_bias(bias_dw, stream.c_pad),
        _pad_bias(bias_pw, stream.m_pad), ct_h=ct_h, ct_w=ct_w,
        bh=stream.bh, bw=stream.bw, block_c=stream.block_c,
        block_m=stream.block_m, inner_activation=inner_activation,
        activation=activation, interpret=interpret,
        name=_kernel_name("separable_streamed"))
    return y[:, :geometry.out_h, :geometry.out_w, :c_out]


# ---------------------------------------------------------------------------
# im2col conv2d (baseline)
# ---------------------------------------------------------------------------

def im2col_blocks(mm: int, kk: int, mout: int, *, block: int = 128
                  ) -> tuple[int, int, int]:
    """(bm, bk, bn) for the blocked GEMM -- plan-time."""
    return _block(mm, block), _block(kk, block), _block(mout, block)


def pad_im2col_filter(b: jax.Array, bk: int, bn: int) -> jax.Array:
    """Pad the (khkwC, M) filter matrix to the GEMM block grid -- plan-time."""
    kk, mout = b.shape
    return _pad_axis(_pad_axis(b, 0, _round_up(kk, bk)),
                     1, _round_up(mout, bn))


def im2col_conv2d_planned(
    x: jax.Array,
    b: jax.Array,
    *,
    kh: int,
    kw: int,
    stride: tuple[int, int],
    padding: _wg.Padding,
    geometry: _im2col.Im2RowGeometry,
    blocks: tuple[int, int, int],
    c_out: int,
    bias: jax.Array | None = None,
    scale: jax.Array | None = None,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Execute a planned Pallas im2row conv: `b` is the pre-reshaped,
    pre-padded (Kp, Np) filter matrix (fp32/bf16/int8); `scale` the (1, Np)
    int8 dequant row or None; geometry and block sizes come from the plan.
    The bias+activation epilogue (and the dequant multiply) is fused into
    the GEMM kernel's store step."""
    interpret = _resolve_interpret(interpret)
    n = x.shape[0]
    bm_, bk_, bn_ = blocks
    a, (oh, ow) = _im2col.im2row(x, kh, kw, stride, padding, geometry)
    mm, kk = a.shape
    a = _pad_axis(_pad_axis(a, 0, _round_up(mm, bm_)), 1, _round_up(kk, bk_))
    y = _k_matmul.matmul(a, b, bm=bm_, bn=bn_, bk=bk_,
                         bias=_pad_bias(bias, b.shape[1]), scale=scale,
                         activation=activation, interpret=interpret,
                         name=_kernel_name("matmul"))
    return y[:mm, :c_out].reshape(n, oh, ow, c_out).astype(x.dtype)


def im2col_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int | tuple[int, int] = 1,
    padding: _wg.Padding = "SAME",
    block: int = 128,
    bias: jax.Array | None = None,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Pallas-backed im2row + GEMM baseline (unplanned compatibility path)."""
    n, h, wdt, c = x.shape
    kh, kw, _, mout = w.shape
    stride = (stride, stride) if isinstance(stride, int) else stride
    geometry = _im2col.im2row_geometry(h, wdt, kh, kw, stride, padding)
    mm = n * geometry.oh * geometry.ow
    blocks = im2col_blocks(mm, kh * kw * c, mout, block=block)
    b = pad_im2col_filter(w.reshape(kh * kw * c, mout), blocks[1], blocks[2])
    return im2col_conv2d_planned(
        x, b, kh=kh, kw=kw, stride=stride, padding=padding, geometry=geometry,
        blocks=blocks, c_out=mout, bias=bias, activation=activation,
        interpret=interpret)


# ---------------------------------------------------------------------------
# Transform-domain contenders of the measured auto_tuned race
# ---------------------------------------------------------------------------

def fft_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    padding: _wg.Padding = "SAME",
    bias: jax.Array | None = None,
    activation: str = "none",
) -> jax.Array:
    """Overlap-tiled rfft2 convolution (unplanned compatibility path).

    Derives the tile geometry and the conjugated filter spectrum inline,
    then runs the planned executor (core.fft.fft_conv2d_pretransformed).
    Plan once with plan_conv2d(algorithm="fft") to pre-transform the filter
    and skip the derivation on every call.
    """
    from repro.core import fft as _fft
    n, h, wdt, c = x.shape
    kh, kw = w.shape[0], w.shape[1]
    fftg = _fft.choose_fft_geometry(h, wdt, kh, kw)
    u = _fft.fft_transform_filter(w, fftg.fft_h, fftg.fft_w)
    y = _fft.fft_conv2d_pretransformed(x, u, fftg, padding=padding)
    return _epilogue_jnp(y, bias, activation)


def winograd_f63_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    padding: _wg.Padding = "SAME",
    bias: jax.Array | None = None,
    activation: str = "none",
) -> jax.Array:
    """Large-tile F(6x6, 3x3) convolution with the power-of-two row-scaled
    transforms (unplanned compatibility path; 3x3 stride-1 only). Plan once
    with plan_conv2d(algorithm="winograd_f63") to pre-transform the filter.
    """
    from repro.core.transforms import scaled_cook_toom
    kh, kw = w.shape[0], w.shape[1]
    if (kh, kw) != (3, 3):
        raise ValueError(f"winograd_f63 covers 3x3 filters only, got "
                         f"{kh}x{kw}")
    ct_h, ct_w = scaled_cook_toom(6, 3), scaled_cook_toom(6, 3)
    u = _wg.transform_filter_2d(w, ct_h, ct_w)
    y = _wg.winograd_conv2d_pretransformed(x, u, ct_h, ct_w, padding=padding)
    return _epilogue_jnp(y, bias, activation)


# ---------------------------------------------------------------------------
# Depthwise causal Cook-Toom conv1d (Mamba short conv)
# ---------------------------------------------------------------------------

def conv1d_ct_blocks(n_tiles: int, c: int, *, block_s: int = 256,
                     block_c: int = 128) -> tuple[int, int]:
    """(block_s, block_c) for the depthwise conv1d kernel -- plan-time."""
    return _block(n_tiles, block_s), _block(c, block_c)


def ct_depthwise_causal_conv1d_planned(
    x: jax.Array,
    u: jax.Array,
    *,
    ct,
    n_tiles: int,
    pad_hi: int,
    blocks: tuple[int, int],
    c_in: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Planned executor: `u` is the pre-transformed, pre-padded (t, Cp)
    Cook-Toom-domain taps; tile count, padding and block sizes come from the
    plan (core.plan.plan_depthwise_conv1d)."""
    interpret = _resolve_interpret(interpret)
    b, length, c = x.shape
    bs, bc = blocks
    xp = jnp.pad(x, ((0, 0), (ct.r - 1, pad_hi), (0, 0)))
    tiles = _wg._extract_tiles_1d(xp, 1, ct.t, ct.m, n_tiles)  # (B, nt, t, C)
    tiles = _pad_axis(tiles, 1, _round_up(n_tiles, bs))
    tiles = _pad_axis(tiles, 3, _round_up(c_in, bc))
    y = _k_conv1d.conv1d_ct_fused(tiles, u, ct=ct, block_s=bs, block_c=bc,
                                  interpret=interpret)
    y = y[:, :n_tiles, :, :c_in].reshape(b, n_tiles * ct.m, c_in)
    return y[:, :length]


def ct_depthwise_causal_conv1d(
    x: jax.Array,
    w: jax.Array,
    *,
    output_tile: int = 4,
    block_s: int = 256,
    block_c: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """(B, L, C) x (r, C) -> (B, L, C), causal.

    Unplanned compatibility path: derives cook_toom, tile counts, padding
    and blocking inline, then runs the planned executor. Hold a
    repro.core.plan.plan_depthwise_conv1d plan to make these decisions once.
    """
    r, c = w.shape
    b, length, _ = x.shape
    ct = cook_toom(output_tile, r)
    nt = -(-length // ct.m)
    u = jnp.einsum("ij,jc->ic", jnp.asarray(ct.G, w.dtype), w)
    blocks = conv1d_ct_blocks(nt, c, block_s=block_s, block_c=block_c)
    u = _pad_axis(u, 1, _round_up(c, blocks[1]))
    return ct_depthwise_causal_conv1d_planned(
        x, u, ct=ct, n_tiles=nt, pad_hi=nt * ct.m - length, blocks=blocks,
        c_in=c, interpret=interpret)


def matmul(a: jax.Array, b: jax.Array, *, block: int = 128,
           bias: jax.Array | None = None, activation: str = "none",
           interpret: bool | None = None) -> jax.Array:
    """Padding-tolerant blocked matmul with optional fused epilogue."""
    interpret = _resolve_interpret(interpret)
    m, k = a.shape
    _, n = b.shape
    bm_, bk_, bn_ = _block(m, block), _block(k, block), _block(n, block)
    ap = _pad_axis(_pad_axis(a, 0, _round_up(m, bm_)), 1, _round_up(k, bk_))
    bp = _pad_axis(_pad_axis(b, 0, _round_up(k, bk_)), 1, _round_up(n, bn_))
    return _k_matmul.matmul(ap, bp, bm=bm_, bn=bn_, bk=bk_,
                            bias=_pad_bias(bias, bp.shape[1]),
                            activation=activation,
                            interpret=interpret)[:m, :n]
