"""Region-wise multi-channel Winograd / Cook-Toom convolution (pure JAX).

This is the paper's core contribution expressed as a composable JAX module.
The three phases map 1:1 onto the paper's scheme (Fig. 2):

  1. *Input transform*: tile the NHWC input into overlapping t x t regions,
     apply B^T x B per region, and scatter the t^2 Winograd-domain points into
     a (P, R, C) tensor -- P = t^2 Winograd points, R = regions, C = channels.
     (The paper's "array of A matrices".)
  2. *GEMM*: P batched matmuls (P, R, C) x (P, C, M) -> (P, R, M). The
     channel-wise sum of Hadamard products becomes a matrix multiply over C --
     on TPU this feeds the MXU; the Pallas kernel in kernels/winograd.py is the
     hand-tiled version of exactly this einsum.
  3. *Output transform*: gather each region's P points, apply A^T (.) A, and
     write the m x m spatial outputs back into NHWC.

Layout note (paper section 2.1): NHWC keeps C innermost, so the transform
arithmetic -- which is a fixed pattern of adds/subs across the *tile* axes --
vectorizes over channels. On TPU the channel axis maps onto the 128-wide lane
dimension; all einsums below keep C/M innermost for that reason.

Stride-1 convolutions map onto the Winograd domain directly; stride-2
layers decompose into four stride-1 phase sub-convolutions whose sum also
happens in the transform domain (winograd_strided_conv2d_pretransformed
below -- the 2D analogue of the polyphase conv1d path). Anything else
falls back to im2row per the executor registry (core/registry.py), exactly
as the paper restricts the fast scheme to "suitable" layers.
"""

from __future__ import annotations

import functools
from typing import Literal, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.transforms import CookToom, cook_toom
# dependency-free shared blocking-granularity rule (repro.kernels stays an
# optional package; runtime.py imports nothing heavy)
from repro.kernels.runtime import pick_block as _stream_block

Padding = Literal["SAME", "VALID"]


# ---------------------------------------------------------------------------
# Filter transforms (done once per layer; weights are kept in the Winograd
# domain between steps, mirroring the paper's pre-transformed 'B' matrices).
# ---------------------------------------------------------------------------

def transform_filter_2d(w: jax.Array, ct_h: CookToom, ct_w: CookToom) -> jax.Array:
    """(kh, kw, C, M) -> (th, tw, C, M): G_h w G_w^T over the spatial axes."""
    g_h = jnp.asarray(ct_h.G, w.dtype)
    g_w = jnp.asarray(ct_w.G, w.dtype)
    return jnp.einsum("ij,jkcm,lk->ilcm", g_h, w, g_w)


def transform_filter_1d(w: jax.Array, ct: CookToom) -> jax.Array:
    """(k, C, M) -> (t, C, M)."""
    return jnp.einsum("ij,jcm->icm", jnp.asarray(ct.G, w.dtype), w)


# ---------------------------------------------------------------------------
# Tiling helpers
# ---------------------------------------------------------------------------

def _pad_amounts(size: int, k: int, m: int, padding: Padding) -> tuple[int, int, int]:
    """Return (lo, hi, n_tiles) padding for one spatial axis.

    The axis is padded so that (padded - k + 1) is a positive multiple of the
    output tile m; surplus outputs are cropped after the inverse transform.
    """
    if padding == "SAME":
        out = size
        lo = (k - 1) // 2
    else:
        out = size - k + 1
        lo = 0
    if out <= 0:
        raise ValueError(f"axis of size {size} too small for filter {k} ({padding})")
    n_tiles = -(-out // m)                      # ceil
    padded = n_tiles * m + k - 1
    hi = padded - size - lo
    return lo, hi, n_tiles


class Conv2DGeometry(NamedTuple):
    """Static tiling geometry of one (H, W) conv shape.

    Derived once at plan time (core/plan.py) and threaded through every
    execution so the hot path never re-derives padding or tile counts.
    """

    lo_h: int
    hi_h: int
    n_h: int          # tile count along H
    lo_w: int
    hi_w: int
    n_w: int          # tile count along W
    out_h: int
    out_w: int


def conv2d_geometry(h: int, w: int, kh: int, kw: int, mh: int, mw: int,
                    padding: Padding) -> Conv2DGeometry:
    """All padding/tiling decisions for an (H, W) layer, computed once."""
    lo_h, hi_h, nh = _pad_amounts(h, kh, mh, padding)
    lo_w, hi_w, nw = _pad_amounts(w, kw, mw, padding)
    out_h = h if padding == "SAME" else h - kh + 1
    out_w = w if padding == "SAME" else w - kw + 1
    return Conv2DGeometry(lo_h, hi_h, nh, lo_w, hi_w, nw, out_h, out_w)


def conv2d_fft_geometry(h: int, w: int, kh: int, kw: int, fft_h: int,
                        fft_w: int, padding: Padding) -> Conv2DGeometry:
    """Tiling geometry for the FFT executor (core/fft.py).

    The overlap tiling of the FFT path is the *same* scheme as Winograd's:
    a transform length t yields m = t - k + 1 valid outputs per tile, and
    consecutive tile origins advance by m. So the FFT geometry is exactly
    conv2d_geometry with the output tile set to fft - k + 1 per axis; the
    padded extent n_tiles * m + k - 1 matches the last tile's fft window and
    the surplus outputs are cropped after the inverse transform, identically
    to the Winograd path."""
    return conv2d_geometry(h, w, kh, kw, fft_h - kh + 1, fft_w - kw + 1,
                           padding)


def strided_out_size(size: int, k: int, padding: Padding) -> int:
    """Output extent of one stride-2 axis (lax conventions) -- the ONE place
    this formula lives; the strided geometry and the plan-time tile chooser
    (core/plan.py:_resolve_strided_tile) both consult it."""
    return -(-size // 2) if padding == "SAME" else (size - k) // 2 + 1


def _pad_amounts_strided(size: int, k: int, m: int,
                         padding: Padding) -> tuple[int, int, int, int]:
    """(lo, hi, n_tiles, out) padding for one stride-2 phase-decomposed axis.

    The axis is padded to 2*n_tiles*m + k - 1 elements so every phase
    sub-grid x[p::2] (p in {0, 1}) holds exactly n_tiles*m + r_ph - 1
    elements, r_ph = (k+1)//2 -- the length the stride-1 phase tiling needs
    to cover n_tiles*m outputs. lo follows lax's SAME convention for
    stride 2; surplus outputs are cropped after the inverse transform."""
    out = strided_out_size(size, k, padding)
    if padding == "SAME":
        total = max((out - 1) * 2 + k - size, 0)
        lo = total // 2
    else:
        lo = 0
    if out <= 0:
        raise ValueError(
            f"axis of size {size} too small for filter {k} stride 2 "
            f"({padding})")
    n_tiles = -(-out // m)
    padded = 2 * n_tiles * m + k - 1
    return lo, padded - size - lo, n_tiles, out


def conv2d_strided_geometry(h: int, w: int, kh: int, kw: int, mh: int,
                            mw: int, padding: Padding) -> Conv2DGeometry:
    """Padding/tiling decisions for a stride-2 phase-decomposed layer: same
    shape of record as the stride-1 geometry (tile counts n_h/n_w describe
    the phase sub-grids; lo/hi pad the full-resolution input)."""
    lo_h, hi_h, nh, out_h = _pad_amounts_strided(h, kh, mh, padding)
    lo_w, hi_w, nw, out_w = _pad_amounts_strided(w, kw, mw, padding)
    return Conv2DGeometry(lo_h, hi_h, nh, lo_w, hi_w, nw, out_h, out_w)


class StreamGeometry(NamedTuple):
    """Halo-blocking geometry for the region-streaming Pallas kernels
    (kernels/winograd.py, kernels/depthwise.py), derived once at plan time.

    The kernel's grid walks n_hb strips of (bh, bw) output tiles, where a
    strip spans the whole width (bw = n_w); each grid cell reads one
    overlapping halo strip of the padded input (origin stride bh*mh rows,
    extent k-1 rows larger) and writes one non-overlapping (bh*mh, bw*mw)
    NHWC output block. Edge strips are covered by padding the input up to
    n_hb*bh whole tile rows (`pad_h` extra rows beyond the convolution
    padding); the surplus outputs are cropped after the kernel.
    """

    bh: int           # output-tile rows per grid cell
    bw: int           # output-tile cols per grid cell (the whole width)
    n_hb: int         # grid extent along H  (= ceil(n_h / bh))
    pad_h: int        # extra rows of input padding for edge blocks
    block_c: int      # Pallas channel block
    block_m: int      # Pallas output-channel block
    c_pad: int        # C rounded up to block_c
    m_pad: int        # M rounded up to block_m


#: Per-grid-step fixed cost in tile-equivalents for the strip score: each
#: grid step pays DMA setup / loop overhead on top of its per-tile compute,
#: so blockings that shatter the image into many small strips lose to
#: slightly-wasteful large strips.
_STRIP_OVERHEAD_TILES = 16


def _sub(d: int) -> int:
    """Round up to the 8-row sublane tile of an fp32 VMEM buffer."""
    return -(-d // 8) * 8


def _lane(d: int) -> int:
    """Round up to the 128-lane tile of an fp32 VMEM buffer."""
    return -(-d // 128) * 128


def _strip_blocking(n_h: int, n_w: int, ct_h: CookToom, ct_w: CookToom,
                    input_stride: int, steps_per_strip: int, vmem_of,
                    vmem_budget_bytes: int, what: str) -> tuple[int, int]:
    """(bh, n_hb): the strip height of a full-width halo-strip grid.

    Strips span the whole padded width (bw = n_w, one strip column): the
    TPU compiler needs a block's second-minor extent to be a multiple of 8
    or the whole axis, and a halo strip of width bw*mw + k - 1 can only be
    the latter. Candidate heights are scored by padded tile count
    (edge-block compute waste) plus a fixed per-grid-step overhead (many
    tiny strips lose), tie-broken toward larger region blocks (bigger
    point-GEMMs); `vmem_of(bh, hs, ws)` is the kernel's VMEM working set in
    bytes, and candidates over the budget are discarded."""
    th, tw, mh, mw = ct_h.t, ct_w.t, ct_h.m, ct_w.m
    bw = max(n_w, 1)
    ws = input_stride * (bw * mw + tw - mw)
    best = None
    for bh in sorted({b for b in (1, 2, 4, 8, 16) if b <= max(n_h, 1)}
                     | {b for b in range(1, 17) if n_h % b == 0}):
        if bh * bw > 256:
            continue
        hs = input_stride * (bh * mh + th - mh)
        if vmem_of(bh, hs, ws) > vmem_budget_bytes:
            continue
        n_hb = -(-n_h // bh)
        score = (n_hb * bh * bw + _STRIP_OVERHEAD_TILES * n_hb
                 * steps_per_strip, -bh)
        if best is None or score < best[0]:
            best = (score, (bh, n_hb))
    if best is None:
        raise ValueError(
            f"no halo blocking of the ({n_h}, {n_w})-tile grid ({what}, "
            f"t=({th}, {tw})) fits the {vmem_budget_bytes >> 20} MiB VMEM "
            f"budget with full-width strips; use a smaller output_tile")
    return best[1]


def stream_geometry(n_h: int, n_w: int, c: int, mout: int,
                    ct_h: CookToom, ct_w: CookToom, *,
                    phases: int = 1, input_stride: int = 1,
                    vmem_budget_bytes: int = 15 * 2 ** 20) -> StreamGeometry:
    """Choose the halo blocking of the dense streamed kernels (and the fused
    separable block), once, at plan time: full-width strips of a height
    chosen by `_strip_blocking`.

    The kernels move strips and output blocks with strided loads and
    stores, which the TPU compiler takes only on refs at most one 128-lane
    vreg wide, and it cannot window a strip narrower than 128 lanes at all.
    So the input channel block is exactly 128 (C pads up to it) and the
    output channel block is at most 128.

    The VMEM estimate counts what the kernel allocates, every block rounded
    to the (8, 128) fp32 tile: the halo strip, filter block and output
    block double-buffered (a filter of one block, single-buffered), the
    fp32 accumulator, the transformed-input cache, and the transform's live
    slabs. It is held to 15 MiB against the compiler's 16 MiB scoped-VMEM
    default.

    `phases`/`input_stride` describe the stride-2 phase-decomposition
    kernels: the halo strip spans `input_stride`x more input per axis and
    the Winograd-domain tensors (filter blocks, transformed-input cache)
    carry `phases` phase copies, so both scale the VMEM estimate.
    """
    th, tw, mh, mw = ct_h.t, ct_w.t, ct_h.m, ct_w.m
    p = th * tw
    pp = p * phases     # Winograd points across all phases
    bc, bm = 128, _stream_block(mout, 128)
    c_pad, m_pad = _lane(c), -(-mout // bm) * bm
    n_cb = c_pad // bc
    bw = max(n_w, 1)
    # a filter of one block is single-buffered by the kernel
    filter_bufs = 1 if (c_pad, m_pad) == (bc, bm) else 2

    def vmem_of(bh, hs, ws):
        br = _sub(bh * bw)
        return 4 * (2 * hs * _sub(ws) * bc                # halo strip
                    + filter_bufs * pp * bc * _lane(bm)   # filter block
                    + p * br * _lane(bm)                  # accumulator
                    + n_cb * pp * br * bc                 # v cache
                    + 2 * bh * mh * _sub(bw * mw) * _lane(bm)  # output
                    + (th + 2) * br * bc)                 # live slabs

    bh, n_hb = _strip_blocking(n_h, n_w, ct_h, ct_w, input_stride,
                               n_cb * (m_pad // bm), vmem_of,
                               vmem_budget_bytes, f"C={c}, M={mout}, "
                               f"phases={phases}")
    return StreamGeometry(bh=bh, bw=bw, n_hb=n_hb,
                          pad_h=(n_hb * bh - n_h) * mh,
                          block_c=bc, block_m=bm, c_pad=c_pad, m_pad=m_pad)


def stream_geometry_depthwise(n_h: int, n_w: int, c: int,
                              ct_h: CookToom, ct_w: CookToom, *,
                              phases: int = 1, input_stride: int = 1,
                              mult: int = 1,
                              vmem_budget_bytes: int = 15 * 2 ** 20
                              ) -> StreamGeometry:
    """Halo blocking of the streamed depthwise kernels: the same full-width
    strips and 128-lane channel block as `stream_geometry`, with one
    channel axis (block_m = block_c). The VMEM estimate is the depthwise
    kernel's own: the halo strip, the (points, mult, 128) taps and the
    `mult` output planes double-buffered, the per-point Hadamard products'
    scratch, and the transform's live slabs."""
    th, tw, mh, mw = ct_h.t, ct_w.t, ct_h.m, ct_w.m
    p = th * tw
    c_pad = _lane(c)
    bw = max(n_w, 1)

    def vmem_of(bh, hs, ws):
        br = _sub(bh * bw)
        return 4 * 128 * (2 * hs * _sub(ws)                  # halo strip
                          + 2 * p * phases * _sub(mult)      # taps
                          + 2 * mult * bh * mh * _sub(bw * mw)   # output
                          + p * br                           # products
                          + (th + 2) * br)                   # live slabs

    bh, n_hb = _strip_blocking(n_h, n_w, ct_h, ct_w, input_stride,
                               c_pad // 128, vmem_of, vmem_budget_bytes,
                               f"C={c}, mult={mult}, phases={phases}")
    return StreamGeometry(bh=bh, bw=bw, n_hb=n_hb,
                          pad_h=(n_hb * bh - n_h) * mh,
                          block_c=128, block_m=128, c_pad=c_pad,
                          m_pad=c_pad)


class Axis1DGeometry(NamedTuple):
    """Static tiling geometry for the 1xN / Nx1 (single-axis) algorithm."""

    axis: int         # spatial axis the filter runs along (1 = H, 2 = W)
    lo: int
    hi: int
    n_t: int          # tile count along the axis
    out_size: int


def conv1d_axis_geometry(size: int, axis: int, k: int, m: int,
                         padding: Padding) -> Axis1DGeometry:
    lo, hi, nt = _pad_amounts(size, k, m, padding)
    out = size if padding == "SAME" else size - k + 1
    return Axis1DGeometry(axis, lo, hi, nt, out)


def _extract_tiles_1d(x: jax.Array, axis: int, t: int, m: int, n: int) -> jax.Array:
    """Slice axis of length n*m + t - m into n overlapping windows of length t.

    Output: the axis is replaced by two axes (n, t). Uses a gather with a
    static index map (cheap under jit; the Pallas kernel replaces this with a
    BlockSpec index_map so no materialized gather happens on TPU).
    """
    idx = (np.arange(n)[:, None] * m + np.arange(t)[None, :]).reshape(-1)
    out = jnp.take(x, jnp.asarray(idx), axis=axis)
    new_shape = x.shape[:axis] + (n, t) + x.shape[axis + 1:]
    return out.reshape(new_shape)


# ---------------------------------------------------------------------------
# 2D region-wise multi-channel convolution
# ---------------------------------------------------------------------------

def winograd_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    output_tile: int | tuple[int, int] = 4,
    padding: Padding = "SAME",
    precision=None,
    preferred_element_type=jnp.float32,
) -> jax.Array:
    """F(m x m, kh x kw) region-wise multi-channel convolution.

    Args:
      x: (N, H, W, C) input, NHWC.
      w: (kh, kw, C, M) filter, HWIO. kh/kw may be 1 (degenerates to the 1D
         row/column algorithm, the paper's 1xN / Nx1 case).
      output_tile: m (outputs per tile per axis). Axes with k == 1 use m = 1
         implicitly via F(m, 1) = identity-free passthrough handled by the 1D
         path below.
      padding: SAME or VALID; stride is always 1 (dispatcher enforces).

    Returns:
      (N, H', W', M) output in the same spatial convention as
      jax.lax.conv_general_dilated with the given padding.
    """
    kh, kw, c, mout = w.shape
    if kh == 1 or kw == 1:
        return _winograd_conv2d_1d_kernel(
            x, w, output_tile=output_tile, padding=padding,
            precision=precision, preferred_element_type=preferred_element_type)

    mh, mw = (output_tile, output_tile) if isinstance(output_tile, int) else output_tile
    ct_h, ct_w = cook_toom(mh, kh), cook_toom(mw, kw)
    u = transform_filter_2d(w, ct_h, ct_w)              # (th, tw, C, M)
    return winograd_conv2d_pretransformed(
        x, u, ct_h, ct_w, padding=padding, precision=precision,
        preferred_element_type=preferred_element_type)


def winograd_conv2d_pretransformed(
    x: jax.Array,
    u: jax.Array,
    ct_h: CookToom,
    ct_w: CookToom,
    *,
    padding: Padding = "SAME",
    geometry: Conv2DGeometry | None = None,
    precision=None,
    preferred_element_type=jnp.float32,
) -> jax.Array:
    """Same as winograd_conv2d but with the filter already in the Winograd
    domain -- the deployment path (weights transformed once, reused per step).
    Pass `geometry` (built once by conv2d_geometry / core.plan) to skip the
    per-call padding/tiling derivation entirely.
    """
    n, h, wdt, c = x.shape
    th, tw, _, mout = u.shape
    mh, mw, kh, kw = ct_h.m, ct_w.m, ct_h.r, ct_w.r

    if geometry is None:
        geometry = conv2d_geometry(h, wdt, kh, kw, mh, mw, padding)
    lo_h, hi_h, nh = geometry.lo_h, geometry.hi_h, geometry.n_h
    lo_w, hi_w, nw = geometry.lo_w, geometry.hi_w, geometry.n_w
    xp = jnp.pad(x, ((0, 0), (lo_h, hi_h), (lo_w, hi_w), (0, 0)))

    # --- phase 1: tile + input transform + scatter -------------------------
    tiles = _extract_tiles_1d(xp, 1, th, mh, nh)        # (N, nh, th, Wp, C)
    tiles = _extract_tiles_1d(tiles, 3, tw, mw, nw)     # (N, nh, th, nw, tw, C)
    bt_h = jnp.asarray(ct_h.BT, x.dtype)
    bt_w = jnp.asarray(ct_w.BT, x.dtype)
    # B^T d B, vectorized over (N, nh, nw, C) -- channels innermost (NHWC).
    v = jnp.einsum("it,nhtwuc,ju->nhwijc", bt_h, tiles, bt_w)
    # scatter: (P, R, C) with P = th*tw Winograd points, R = N*nh*nw regions.
    v = v.reshape(n * nh * nw, th * tw, c).transpose(1, 0, 2)

    # --- phase 2: P batched GEMMs [R x C] x [C x M] ------------------------
    uu = u.reshape(th * tw, c, mout)
    y = jnp.einsum("prc,pcm->prm", v, uu, precision=precision,
                   preferred_element_type=preferred_element_type)

    # --- phase 3: gather + output transform --------------------------------
    y = y.transpose(1, 0, 2).reshape(n, nh, nw, th, tw, mout)
    at_h = jnp.asarray(ct_h.AT, y.dtype)
    at_w = jnp.asarray(ct_w.AT, y.dtype)
    out = jnp.einsum("it,nhwtum,ju->nhiwjm", at_h, y, at_w)
    out = out.reshape(n, nh * mh, nw * mw, mout)
    return out[:, :geometry.out_h, :geometry.out_w, :].astype(x.dtype)


def winograd_depthwise_conv2d_pretransformed(
    x: jax.Array,
    u: jax.Array,
    ct_h: CookToom,
    ct_w: CookToom,
    *,
    padding: Padding = "SAME",
    geometry: Conv2DGeometry | None = None,
) -> jax.Array:
    """Depthwise 2D Winograd executor: the transform-domain channel GEMM of
    the dense scheme degenerates to an *elementwise* multiply batched over
    channels -- each channel convolves with its own filter, so phase 2 is a
    Hadamard product over the (P, R, C) Winograd points instead of a GEMM
    over C. Phases 1 and 3 (tiling, B^T (.) B, A^T (.) A) are identical to
    the dense path and reuse its geometry.

    Args:
      x: (N, H, W, C) input, NHWC.
      u: (th, tw, C, mult) pre-transformed depthwise filter -- the HWIO
         (kh, kw, 1, C*mult) filter transformed by G_h (.) G_w^T and
         regrouped so the channel axis is explicit (mult = channel
         multiplier; the common MobileNet case is mult = 1).

    Returns:
      (N, H', W', C*mult), matching jax.lax.conv_general_dilated with
      feature_group_count = C (output channel o = c * mult + j).
    """
    n, h, wdt, c = x.shape
    th, tw, _, mult = u.shape
    mh, mw, kh, kw = ct_h.m, ct_w.m, ct_h.r, ct_w.r
    if geometry is None:
        geometry = conv2d_geometry(h, wdt, kh, kw, mh, mw, padding)
    nh, nw = geometry.n_h, geometry.n_w
    xp = jnp.pad(x, ((0, 0), (geometry.lo_h, geometry.hi_h),
                     (geometry.lo_w, geometry.hi_w), (0, 0)))

    tiles = _extract_tiles_1d(xp, 1, th, mh, nh)
    tiles = _extract_tiles_1d(tiles, 3, tw, mw, nw)     # (N, nh, th, nw, tw, C)
    bt_h = jnp.asarray(ct_h.BT, jnp.float32)
    bt_w = jnp.asarray(ct_w.BT, jnp.float32)
    v = jnp.einsum("it,nhtwuc,ju->nhwijc", bt_h,
                   tiles.astype(jnp.float32), bt_w)     # (N, nh, nw, th, tw, C)
    # phase 2, depthwise: Hadamard over channels (batched over mult). The
    # repeated c axis makes this an elementwise product, not a contraction.
    y = jnp.einsum("nhwijc,ijcm->nhwijcm", v, u.astype(jnp.float32))
    at_h = jnp.asarray(ct_h.AT, jnp.float32)
    at_w = jnp.asarray(ct_w.AT, jnp.float32)
    out = jnp.einsum("it,nhwtucm,ju->nhiwjcm", at_h, y, at_w)
    out = out.reshape(n, nh * mh, nw * mw, c * mult)
    return out[:, :geometry.out_h, :geometry.out_w, :].astype(x.dtype)


def winograd_grouped_conv2d_pretransformed(
    x: jax.Array,
    u: jax.Array,
    ct_h: CookToom,
    ct_w: CookToom,
    groups: int,
    *,
    padding: Padding = "SAME",
    geometry: Conv2DGeometry | None = None,
    precision=None,
    preferred_element_type=jnp.float32,
) -> jax.Array:
    """Grouped dense Winograd executor: the full channel reduction becomes a
    block-diagonal reduction -- one (R x Cg) x (Cg x Mg) GEMM per group per
    Winograd point, expressed as a single batched einsum so the per-group
    GEMMs stay fused. Phases 1 and 3 are the dense path's.

    Args:
      x: (N, H, W, C) input; C = groups * Cg.
      u: (th, tw, Cg, M) pre-transformed grouped filter; M = groups * Mg,
         group-major on the output axis (matching feature_group_count).
    """
    n, h, wdt, c = x.shape
    th, tw, cg, mout = u.shape
    mg = mout // groups
    mh, mw, kh, kw = ct_h.m, ct_w.m, ct_h.r, ct_w.r
    if geometry is None:
        geometry = conv2d_geometry(h, wdt, kh, kw, mh, mw, padding)
    nh, nw = geometry.n_h, geometry.n_w
    xp = jnp.pad(x, ((0, 0), (geometry.lo_h, geometry.hi_h),
                     (geometry.lo_w, geometry.hi_w), (0, 0)))

    tiles = _extract_tiles_1d(xp, 1, th, mh, nh)
    tiles = _extract_tiles_1d(tiles, 3, tw, mw, nw)     # (N, nh, th, nw, tw, C)
    bt_h = jnp.asarray(ct_h.BT, x.dtype)
    bt_w = jnp.asarray(ct_w.BT, x.dtype)
    v = jnp.einsum("it,nhtwuc,ju->nhwijc", bt_h, tiles, bt_w)
    # scatter with the channel axis split (P, R, G, Cg)
    v = v.reshape(n * nh * nw, th * tw, groups, cg).transpose(1, 0, 2, 3)

    # phase 2: block-diagonal reduction -- P x G batched (R, Cg) x (Cg, Mg)
    uu = u.reshape(th * tw, cg, groups, mg)
    y = jnp.einsum("prgc,pcgm->prgm", v, uu, precision=precision,
                   preferred_element_type=preferred_element_type)
    y = y.reshape(th * tw, n * nh * nw, mout)           # group-major M

    y = y.transpose(1, 0, 2).reshape(n, nh, nw, th, tw, mout)
    at_h = jnp.asarray(ct_h.AT, y.dtype)
    at_w = jnp.asarray(ct_w.AT, y.dtype)
    out = jnp.einsum("it,nhwtum,ju->nhiwjm", at_h, y, at_w)
    out = out.reshape(n, nh * mh, nw * mw, mout)
    return out[:, :geometry.out_h, :geometry.out_w, :].astype(x.dtype)


def strided_phase_filters(w: jax.Array, ct_h: CookToom,
                          ct_w: CookToom) -> jax.Array:
    """(kh, kw, Cg, M) filter -> (2, 2, th, tw, Cg, M) Winograd-domain phase
    sub-filters for the stride-2 decomposition.

    The filter is zero-padded to even size (kh+1, kw+1) so all four phase
    sub-filters w[p::2, q::2] share one size r_ph = (k+1)//2 -- and hence one
    F(m, r_ph) transform set, which is what lets the phase sum happen in the
    transform domain (before the single inverse transform). Done once per
    plan."""
    kh, kw = w.shape[:2]
    wp = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    return jnp.stack([
        jnp.stack([transform_filter_2d(wp[p::2, q::2], ct_h, ct_w)
                   for q in (0, 1)], 0)
        for p in (0, 1)], 0)


def winograd_strided_conv2d_pretransformed(
    x: jax.Array,
    u: jax.Array,
    ct_h: CookToom,
    ct_w: CookToom,
    *,
    groups: int = 1,
    geometry: Conv2DGeometry,
    precision=None,
    preferred_element_type=jnp.float32,
) -> jax.Array:
    """Stride-2 convolution via transform-domain phase decomposition -- the
    2D analogue of the polyphase conv1d path, with the cross-phase sum moved
    *into* the Winograd domain.

    A stride-2 conv splits into four stride-1 sub-convolutions over the four
    input phases x[p::2, q::2] with phase sub-filters w[p::2, q::2] (the
    filter zero-padded to even size so all phases share one F(m, r_ph)
    transform set, r_ph = (k+1)//2). Because every phase uses the same A^T,
    the phase outputs are summed in the transform domain: one accumulated
    (P, R, .) tensor, ONE inverse transform, one output scatter -- the four
    phases cost four input transforms and four GEMM banks, not four full
    pipelines.

    Args:
      x: (N, H, W, C) input, NHWC.
      u: (2, 2, th, tw, Cg, M') pre-transformed phase filters
         (strided_phase_filters); for depthwise Cg = C and M' = the channel
         multiplier, for grouped Cg = C/groups and M' = M (group-major).
      groups: feature_group_count; selects the phase-2 contraction (dense
         GEMM / depthwise Hadamard / grouped block-diagonal), mirroring the
         stride-1 executor family.
      geometry: conv2d_strided_geometry record (built once at plan time).

    Returns:
      (N, H', W', M), matching lax.conv_general_dilated with stride (2, 2).
    """
    n, h, wdt, c = x.shape
    th, tw = ct_h.t, ct_w.t
    mh, mw = ct_h.m, ct_w.m
    nh, nw = geometry.n_h, geometry.n_w
    depthwise = groups > 1 and groups == c
    xp = jnp.pad(x, ((0, 0), (geometry.lo_h, geometry.hi_h),
                     (geometry.lo_w, geometry.hi_w), (0, 0)))
    len_h = nh * mh + ct_h.r - 1          # phase sub-grid extents
    len_w = nw * mw + ct_w.r - 1
    dt = jnp.float32 if depthwise else x.dtype
    bt_h = jnp.asarray(ct_h.BT, dt)
    bt_w = jnp.asarray(ct_w.BT, dt)

    pp = th * tw
    r_tot = n * nh * nw

    # phase 1: per-phase tiling + input transform, scattered into ONE
    # (4P, R, C) tensor (phase-major points) so phase 2 stays a single
    # batched contraction over all phases and regions -- the strided
    # analogue of the dense scheme's (P, R, C) scatter.
    vs = []
    for p in (0, 1):
        for q in (0, 1):
            ph = xp[:, p::2, q::2, :][:, :len_h, :len_w, :]
            tiles = _extract_tiles_1d(ph, 1, th, mh, nh)
            tiles = _extract_tiles_1d(tiles, 3, tw, mw, nw)
            v = jnp.einsum("it,nhtwuc,ju->nhwijc", bt_h, tiles.astype(dt),
                           bt_w)                    # (N, nh, nw, th, tw, C)
            vs.append(v.reshape(r_tot, pp, c).transpose(1, 0, 2))
    v4 = jnp.concatenate(vs, 0)                     # (4P, R, C)
    u4 = u.astype(dt).reshape(4 * pp, *u.shape[4:])  # (4P, Cg, M')

    # phase 2: 4P batched contractions; the cross-phase sum then happens in
    # the transform domain (every phase shares A^T), so ONE inverse follows.
    if groups == 1:
        y = jnp.einsum("prc,pcm->prm", v4, u4, precision=precision,
                       preferred_element_type=preferred_element_type)
        mout = y.shape[-1]
    elif depthwise:
        # Hadamard phase 2, batched over the channel multiplier.
        y = jnp.einsum("prc,pcm->prcm", v4, u4)
        mout = c * u4.shape[-1]
        y = y.reshape(4 * pp, r_tot, mout)
    else:
        cg = c // groups
        mg = u4.shape[-1] // groups
        vg = v4.reshape(4 * pp, r_tot, groups, cg)
        ug = u4.reshape(4 * pp, cg, groups, mg)
        y = jnp.einsum("prgc,pcgm->prgm", vg, ug, precision=precision,
                       preferred_element_type=preferred_element_type)
        mout = groups * mg
        y = y.reshape(4 * pp, r_tot, mout)
    y = y.reshape(4, pp, r_tot, mout).sum(0)        # transform-domain sum

    # phase 3: one gather + inverse transform + NHWC scatter, as in the
    # stride-1 scheme.
    y = y.transpose(1, 0, 2).reshape(n, nh, nw, th, tw, mout)
    at_h = jnp.asarray(ct_h.AT, y.dtype)
    at_w = jnp.asarray(ct_w.AT, y.dtype)
    out = jnp.einsum("it,nhwtum,ju->nhiwjm", at_h, y, at_w)
    out = out.reshape(n, nh * mh, nw * mw, mout)
    return out[:, :geometry.out_h, :geometry.out_w, :].astype(x.dtype)


def pointwise_conv2d(x: jax.Array, u: jax.Array, *, precision=None,
                     preferred_element_type=jnp.float32) -> jax.Array:
    """1x1 convolution: a pure channel GEMM.  u: (C, M)."""
    return jnp.einsum("nhwc,cm->nhwm", x, u, precision=precision,
                      preferred_element_type=preferred_element_type
                      ).astype(x.dtype)


def winograd_conv1d_axis_pretransformed(
    x: jax.Array,
    u: jax.Array,
    ct: CookToom,
    geometry: Axis1DGeometry,
    *,
    precision=None,
    preferred_element_type=jnp.float32,
) -> jax.Array:
    """1xN / Nx1 executor over a pre-transformed (t, C, M) filter and a
    precomputed axis geometry: 1D Cook-Toom along geometry.axis, plain
    channel GEMM along the unit axis."""
    n, h, wdt, _ = x.shape
    axis, lo, hi, nt = geometry.axis, geometry.lo, geometry.hi, geometry.n_t
    m, mout = ct.m, u.shape[-1]
    pad = [(0, 0)] * 4
    pad[axis] = (lo, hi)
    xp = jnp.pad(x, pad)
    tiles = _extract_tiles_1d(xp, axis, ct.t, m, nt)     # axis -> (nt, t)
    bt = jnp.asarray(ct.BT, x.dtype)
    at = jnp.asarray(ct.AT, x.dtype)
    if axis == 1:
        v = jnp.einsum("it,nstwc->nsiwc", bt, tiles)     # (N, nt, t, W, C)
        y = jnp.einsum("nsiwc,icm->nsiwm", v, u, precision=precision,
                       preferred_element_type=preferred_element_type)
        out = jnp.einsum("ot,nstwm->nsowm", at.astype(y.dtype), y)
        out = out.reshape(n, nt * m, wdt, mout)
        return out[:, :geometry.out_size].astype(x.dtype)
    else:
        v = jnp.einsum("it,nhstc->nhsic", bt, tiles)     # (N, H, nt, t, C)
        y = jnp.einsum("nhsic,icm->nhsim", v, u, precision=precision,
                       preferred_element_type=preferred_element_type)
        out = jnp.einsum("ot,nhstm->nhsom", at.astype(y.dtype), y)
        out = out.reshape(n, h, nt * m, mout)
        return out[:, :, :geometry.out_size].astype(x.dtype)


def _winograd_conv2d_1d_kernel(
    x: jax.Array, w: jax.Array, *, output_tile, padding: Padding,
    precision, preferred_element_type,
) -> jax.Array:
    """1xN / Nx1 layers (paper's Inception-v3 case): derive the filter
    transform and geometry, then run the pretransformed executor."""
    kh, kw, c, mout = w.shape
    axis = 1 if kh > 1 else 2          # spatial axis the filter runs along
    k = max(kh, kw)
    if k == 1:                          # 1x1: pure channel GEMM (pointwise)
        return pointwise_conv2d(x, w[0, 0], precision=precision,
                                preferred_element_type=preferred_element_type)
    m = output_tile if isinstance(output_tile, int) else output_tile[axis - 1]
    ct = cook_toom(m, k)
    u = transform_filter_1d(w.reshape(k, c, mout), ct)   # (t, C, M)
    geometry = conv1d_axis_geometry(x.shape[axis], axis, k, m, padding)
    return winograd_conv1d_axis_pretransformed(
        x, u, ct, geometry, precision=precision,
        preferred_element_type=preferred_element_type)


# ---------------------------------------------------------------------------
# 1D depthwise causal Cook-Toom convolution (Mamba's short conv). This is the
# paper's 1D algorithm specialized to depthwise form: the per-point GEMM over
# channels degenerates to an elementwise product, but the multiplication
# reduction (m*r/t) still applies per channel.
# ---------------------------------------------------------------------------

def ct_depthwise_causal_conv1d(
    x: jax.Array, w: jax.Array, *, output_tile: int = 4,
) -> jax.Array:
    """Causal depthwise conv: y[b, l, c] = sum_k w[k, c] * x[b, l - (r-1) + k, c].

    Args:
      x: (B, L, C).
      w: (r, C) depthwise taps.
    Returns:
      (B, L, C), same length (causal left pad of r - 1).
    """
    r, c = w.shape
    b, length, _ = x.shape
    ct = cook_toom(output_tile, r)
    nt = -(-length // ct.m)
    u = jnp.einsum("ij,jc->ic", jnp.asarray(ct.G, w.dtype), w)   # (t, C)
    return ct_depthwise_causal_conv1d_pretransformed(
        x, u, ct, n_tiles=nt, pad_hi=nt * ct.m - length)


def ct_depthwise_causal_conv1d_pretransformed(
    x: jax.Array, u: jax.Array, ct: CookToom, *, n_tiles: int, pad_hi: int,
) -> jax.Array:
    """Planned executor for the depthwise causal Cook-Toom conv: `u` is the
    pre-transformed (t, C) taps and the tile count / padding come from the
    plan (core.plan.plan_depthwise_conv1d) -- no per-call cook_toom or
    geometry derivation."""
    b, length, c = x.shape
    r = ct.r
    # causal pad left r-1; pad right so tiles cover n_tiles * m outputs.
    xp = jnp.pad(x, ((0, 0), (r - 1, pad_hi), (0, 0)))
    tiles = _extract_tiles_1d(xp, 1, ct.t, ct.m, n_tiles)   # (B, nt, t, C)
    bt = jnp.asarray(ct.BT, x.dtype)
    at = jnp.asarray(ct.AT, x.dtype)
    v = jnp.einsum("it,bstc->bsic", bt, tiles)
    y = v * u.astype(x.dtype)[None, None]                 # Hadamard, per channel
    out = jnp.einsum("ot,bstc->bsoc", at, y).reshape(b, n_tiles * ct.m, c)
    return out[:, :length].astype(x.dtype)
