"""Fused region-wise multi-channel Winograd convolution Pallas kernels.

TPU-native adaptation of the paper's three-phase scheme. The paper stages
(input transform -> scatter to matrices in memory -> GEMMs -> gather -> output
transform) through L1/L2; on TPU we instead *fuse* all three phases in VMEM.

Three kernels live here:

`winograd_streamed` -- the halo-aware region-streaming kernel (the planned
hot path). Nothing but the NHWC input and the NHWC output ever touches HBM:

  grid = (N,  nHb,  M / bM,  C / bC)     # C innermost: accumulation

  per step:
    1. the input BlockSpec reads an *overlapping* full-width halo strip of
       the padded NHWC input directly from HBM (`pl.Element` offsets: strip
       i starts at row i * bh * mh and extends k - 1 rows past the next
       strip's origin). Each in-tile offset (t, u) of every region of the
       strip is then one strided `pl.ds` load: a (bR, bC) slab with the
       regions on sublanes and channels on the 128 lanes (the paper's
       region-wise multi-channel layout, 128 lanes wide instead of 4). The
       ~(t/m)^2 read-amplified tile tensor never exists;
    2. apply B^T (.) B as trace-time-unrolled linear combinations of slabs
       (the transform matrices are small constants), then the paper's
       "array of GEMMs": one 2-D (bR, bC) x (bC, bM) MXU dot per Winograd
       point, accumulated into a (P, bR, bM) fp32 VMEM scratch;
    3. on the last C step, apply A^T (.) A slab-wise, run the fused
       epilogue (bias add + activation), and scatter each output offset's
       slab into the NHWC block with one strided store -- no post-kernel
       un-tiling transpose/reshape pass.

`winograd_strided_streamed` -- the stride-2 variant via transform-domain
phase decomposition: the halo strip covers the full-resolution input (origin
stride and extent doubled), the strided loads read FOUR phase sub-grids
(x[p::2, q::2]), each is transformed with the shared F(m, (k+1)/2) B^T (the
filter was zero-padded to even size at plan time), and the four phase GEMM
banks accumulate into ONE (P, bR, bM) accumulator -- the cross-phase sum
happens in the transform domain, so there is a single inverse transform
and NHWC store with the fused epilogue.

`winograd_fused` -- the pre-streaming kernel over pre-extracted tiles
(grid (R/bR, M/bM, C/bC)), kept as the A/B baseline the benchmarks measure
the streaming win against (benchmarks/per_layer.py, BENCH_PR2.json) and for
callers that already hold a tile tensor.

The Winograd-domain tensors (the paper's scattered 'A'/'C' matrices) never
touch HBM in either kernel; the streaming kernel additionally keeps the
overlapping-tile tensor and the separate bias/activation round trips out of
HBM. The HBM-bytes accounting is in EXPERIMENTS.md section Perf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.transforms import CookToom
from repro.kernels.runtime import apply_activation, resolve_interpret


def _apply_pair(mat_h, mat_w, x):
    """einsum('it,brtuc,ju->bricj'-free): y[b,i,j,c] = sum_tu H[i,t] W[j,u] x[b,t,u,c].

    x: (bR, th, tw, bC). Contractions kept as dots on the small tile axes so
    the (bR, bC) payload axes ride along untouched (lane dim = channels).
    """
    # contract th: (i,t) x (b,t,u,c) -> (b,i,u,c)
    y = jnp.tensordot(mat_h, x, axes=(1, 1)).transpose(1, 0, 2, 3)
    # contract tw: (j,u) x (b,i,u,c) -> (b,i,j,c)
    y = jnp.tensordot(mat_w, y, axes=(1, 2)).transpose(1, 2, 0, 3)
    return y


# ---------------------------------------------------------------------------
# Region-wise slab algebra shared by the streamed kernels
# ---------------------------------------------------------------------------
#
# A "slab" is one in-tile offset of every region in a block, as a 2-D
# (bR, lanes) value with the bR = bh*bw regions on sublanes and channels on
# lanes: the paper's region-wise multi-channel layout. Strided ref loads
# read a slab straight out of the halo strip, the Cook-Toom transforms are
# linear combinations of slabs with the (small, constant) transform
# coefficients unrolled at trace time, the point GEMMs are 2-D MXU dots of
# slabs, and strided ref stores scatter the output slabs into the NHWC
# block. Nothing needs a gather, a transpose or a >2-D contraction.

def phase_offsets(stride: int) -> tuple[tuple[int, int], ...]:
    """(row, col) phase origins of a stride-`stride` phase decomposition:
    ((0, 0),) at stride 1, the four x[p::2, q::2] sub-grids at stride 2."""
    return tuple((ph, qh) for ph in range(stride) for qh in range(stride))


def load_slab(x_ref, row: int, col: int, bh: int, bw: int, row_step: int,
              col_step: int) -> jax.Array:
    """The (bh*bw, bC) slab of the strip element at (row + a*row_step,
    col + b*col_step) for every region (a, b) of the block: one strided
    load from the (Hs, Ws, bC) halo-strip ref."""
    v = x_ref[pl.ds(row, bh, row_step), pl.ds(col, bw, col_step), :]
    return v.astype(jnp.float32).reshape(bh * bw, v.shape[-1])


def store_slab(o_ref, lead: tuple, v: jax.Array, a: int, b: int, bh: int,
               bw: int, mh: int, mw: int) -> None:
    """Scatter the (bh*bw, lanes) output slab of in-tile offset (a, b) into
    the NHWC block ref at rows a::mh, cols b::mw (one strided store)."""
    o_ref[lead + (pl.ds(a, bh, mh), pl.ds(b, bw, mw), slice(None))] = (
        v.reshape(bh, bw, v.shape[-1]).astype(o_ref.dtype))


def lincomb(coeffs, slabs):
    """sum_k coeffs[k] * slabs[k] with trace-time constant coefficients.

    `slabs` holds values or zero-argument loaders (called only for a
    non-zero coefficient); zero terms vanish and +-1 need no multiply."""
    acc = None
    for c, s in zip(coeffs, slabs):
        c = float(c)
        if c == 0.0:
            continue
        s = s() if callable(s) else s
        if acc is None:
            acc = s if c == 1.0 else (-s if c == -1.0 else c * s)
        elif c == 1.0:
            acc = acc + s
        elif c == -1.0:
            acc = acc - s
        else:
            acc = acc + c * s
    if acc is None:
        s = slabs[0]
        acc = jnp.zeros_like(s() if callable(s) else s)
    return acc


def input_transform(load, ct_h: CookToom, ct_w: CookToom, emit) -> None:
    """V = B^T X B per region, slab-wise: X's (t, u) slab is load(t, u);
    emit(i * tw + j, v) receives each transformed slab. W is contracted
    first, so only th partial slabs are live at a time."""
    bt_h, bt_w = ct_h.BT, ct_w.BT
    th, tw = ct_h.t, ct_w.t
    for j in range(tw):
        part = [lincomb(bt_w[j], [functools.partial(load, t, u)
                                  for u in range(tw)]) for t in range(th)]
        for i in range(th):
            emit(i * tw + j, lincomb(bt_h[i], part))


def output_transform(point, ct_h: CookToom, ct_w: CookToom, emit) -> None:
    """Y = A^T M A per region, slab-wise: M's Winograd point q is point(q);
    emit(a, b, y) receives output offset (a, b)'s slab."""
    at_h, at_w = ct_h.AT, ct_w.AT
    th, tw, mh, mw = ct_h.t, ct_w.t, ct_h.m, ct_w.m
    for b in range(mw):
        part = [lincomb(at_w[b], [functools.partial(point, i * tw + j)
                                  for j in range(tw)]) for i in range(th)]
        for a in range(mh):
            emit(a, b, lincomb(at_h[a], part))


def epilogue(y: jax.Array, bias, scale, activation: str) -> jax.Array:
    """Fused store-step epilogue on an fp32 slab: int8 dequantization
    (per-output-channel scale, commutes with the inverse transform), bias,
    activation. `bias`/`scale` are (1, lanes) rows or None."""
    if scale is not None:
        y = y * scale
    if bias is not None:
        y = y + bias
    return apply_activation(y, activation)


# ---------------------------------------------------------------------------
# Halo-aware region-streaming kernel (stride 1 and stride 2)
# ---------------------------------------------------------------------------

def _streamed_kernel(x_ref, u_ref, bias_ref, scale_ref, o_ref, acc_ref, v_ref,
                     *, ct_h: CookToom, ct_w: CookToom, stride: int, n_c: int,
                     bh: int, bw: int, activation: str, has_bias: bool,
                     has_scale: bool):
    m_step = pl.program_id(2)
    c_step = pl.program_id(3)
    th, tw, mh, mw = ct_h.t, ct_w.t, ct_h.m, ct_w.m
    p = th * tw
    phases = phase_offsets(stride)

    @pl.when(c_step == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The strip's block index carries the channel slice, so the halo DMA
    # recurs per (M sweep, C block); the transform below runs only once per
    # (strip, C block) -- the first M step fills the v cache, later M steps
    # reuse it. At stride 2 each phase (ph, qh) is the sub-grid
    # strip[ph::2, qh::2], transformed with the shared B^T (the filter was
    # zero-padded to even size at plan time).
    @pl.when(m_step == 0)
    def _transform():
        for k, (ph, qh) in enumerate(phases):
            def load(t, u, ph=ph, qh=qh):
                return load_slab(x_ref, stride * t + ph, stride * u + qh, bh,
                                 bw, stride * mh, stride * mw)

            def emit(q, v, k=k):
                v_ref[c_step, k * p + q] = v
            input_transform(load, ct_h, ct_w, emit)

    # point GEMMs: the paper's t^2 GEMMs as 2-D (bR, bC) x (bC, bM) MXU
    # dots; at stride 2 the four phase banks sum into one accumulator (the
    # phase sum happens in the transform domain, before the one inverse).
    def point_gemm(q, carry):
        acc = acc_ref[q]
        for k in range(len(phases)):
            acc = acc + jnp.dot(v_ref[c_step, k * p + q],
                                u_ref[k * p + q].astype(jnp.float32),
                                preferred_element_type=jnp.float32)
        acc_ref[q] = acc
        return carry
    jax.lax.fori_loop(0, p, point_gemm, 0)

    @pl.when(c_step == n_c - 1)
    def _store():
        bias = bias_ref[...] if has_bias else None
        scale = scale_ref[...] if has_scale else None

        def emit(a, b, y):
            store_slab(o_ref, (0,), epilogue(y, bias, scale, activation),
                       a, b, bh, bw, mh, mw)
        output_transform(lambda q: acc_ref[q], ct_h, ct_w, emit)


def _streamed_call(xp, u, bias, scale, *, ct_h, ct_w, stride, bh, bw,
                   block_c, block_m, activation, interpret):
    """pallas_call shared by the stride-1 and stride-2 streamed kernels."""
    interpret = resolve_interpret(interpret)
    n, hp, wp, c = xp.shape
    pp, c2, m = u.shape
    th, tw, mh, mw = ct_h.t, ct_w.t, ct_h.m, ct_w.m
    sh, sw = bh * mh, bw * mw                        # output block extent
    hs = stride * (sh + th - mh)                     # input halo strip extent
    ws = stride * (sw + tw - mw)
    assert pp == stride * stride * th * tw and c == c2, (xp.shape, u.shape)
    assert c % block_c == 0 and m % block_m == 0, (xp.shape, u.shape,
                                                   (block_c, block_m))
    n_hb, rh = divmod(hp - stride * (th - mh), stride * sh)
    assert rh == 0 and wp == ws, (xp.shape, (bh, bw), (mh, mw))
    n_c = c // block_c
    grid = (n, n_hb, m // block_m, n_c)

    has_bias = bias is not None
    if bias is None:
        bias = jnp.zeros((1, m), jnp.float32)
    has_scale = scale is not None
    if scale is None:
        scale = jnp.ones((1, m), jnp.float32)
    return pl.pallas_call(
        functools.partial(_streamed_kernel, ct_h=ct_h, ct_w=ct_w,
                          stride=stride, n_c=n_c, bh=bh, bw=bw,
                          activation=activation, has_bias=has_bias,
                          has_scale=has_scale),
        grid=grid,
        in_specs=[
            # overlapping full-width halo strips in element offsets (Mosaic
            # takes element offsets on all dims or none): strip i starts at
            # row i * stride * sh and extends k - 1 rows past the next
            # strip's origin; the channel slice starts at cb * block_c.
            pl.BlockSpec((None, pl.Element(hs), pl.Element(ws),
                          pl.Element(block_c)),
                         lambda n_, i, mb, cb: (n_, i * stride * sh, 0,
                                                cb * block_c)),
            # a filter that is one block never changes block index: one
            # buffer, not two (stream_geometry counts it the same way).
            pl.BlockSpec((pp, block_c, block_m),
                         lambda n_, i, mb, cb: (0, cb, mb),
                         pipeline_mode=(pl.Buffered(1)
                                        if (c, m) == (block_c, block_m)
                                        else None)),
            pl.BlockSpec((1, block_m), lambda n_, i, mb, cb: (0, mb)),
            pl.BlockSpec((1, block_m), lambda n_, i, mb, cb: (0, mb)),
        ],
        out_specs=pl.BlockSpec((1, sh, sw, block_m),
                               lambda n_, i, mb, cb: (n_, i, 0, mb)),
        out_shape=jax.ShapeDtypeStruct((n, n_hb * sh, sw, m), xp.dtype),
        scratch_shapes=[pltpu.VMEM((th * tw, bh * bw, block_m), jnp.float32),
                        # transformed-input cache: filled on the first M
                        # step of each strip, reused by the rest of the
                        # (M, C) sweep.
                        pltpu.VMEM((n_c, pp, bh * bw, block_c), jnp.float32)],
        interpret=interpret,
    )(xp, u, bias, scale)


@functools.partial(jax.jit, static_argnames=(
    "ct_h", "ct_w", "bh", "bw", "block_c", "block_m", "activation",
    "interpret"))
def winograd_streamed(
    xp: jax.Array,           # (N, Hp, Wp, Cp) halo-padded NHWC input
    u: jax.Array,            # (P, Cp, Mp) Winograd-domain filter (P = th*tw)
    bias: jax.Array | None,  # (1, Mp) fp32 epilogue bias, or None
    scale: jax.Array | None = None,  # (1, Mp) fp32 int8-dequant scale, or None
    *,
    ct_h: CookToom,
    ct_w: CookToom,
    bh: int,
    bw: int,
    block_c: int = 128,
    block_m: int = 128,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Halo-streaming transform+GEMM+inverse+epilogue over the padded input.

    `xp` must be padded so Hp = nHb*bh*mh + (th - mh) for an integer strip
    count nHb and Wp = bw*mw + (tw - mw) (ops.py pads from the plan's
    StreamGeometry). Returns (N, nHb*bh*mh, bw*mw, Mp) NHWC output; the
    caller crops the geometry surplus.
    """
    return _streamed_call(xp, u, bias, scale, ct_h=ct_h, ct_w=ct_w, stride=1,
                          bh=bh, bw=bw, block_c=block_c, block_m=block_m,
                          activation=activation, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "ct_h", "ct_w", "bh", "bw", "block_c", "block_m", "activation",
    "interpret"))
def winograd_strided_streamed(
    xp: jax.Array,           # (N, Hp, Wp, Cp) halo-padded full-res input
    u: jax.Array,            # (4P, Cp, Mp) phase-major Winograd-domain filter
    bias: jax.Array | None,  # (1, Mp) fp32 epilogue bias, or None
    scale: jax.Array | None = None,  # (1, Mp) fp32 int8-dequant scale, or None
    *,
    ct_h: CookToom,
    ct_w: CookToom,
    bh: int,
    bw: int,
    block_c: int = 128,
    block_m: int = 128,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Stride-2 halo-streaming Winograd conv via transform-domain phase
    decomposition: four phase input-transforms + GEMM banks per strip, one
    accumulator, one inverse transform, one NHWC store with fused epilogue.

    `xp` must be padded so Hp = nHb*2*bh*mh + 2*(th - mh) and
    Wp = 2*bw*mw + 2*(tw - mw) (ops.py pads from the plan's StreamGeometry;
    2*(th - mh) = k - 1 is the stride-2 halo). Returns the
    (N, nHb*bh*mh, bw*mw, Mp) stride-2 output grid; the caller crops the
    geometry surplus.
    """
    return _streamed_call(xp, u, bias, scale, ct_h=ct_h, ct_w=ct_w, stride=2,
                          bh=bh, bw=bw, block_c=block_c, block_m=block_m,
                          activation=activation, interpret=interpret)


# ---------------------------------------------------------------------------
# Pre-extracted-tiles kernel (A/B baseline for the streaming path)
# ---------------------------------------------------------------------------

def _winograd_kernel(bt_h_ref, bt_w_ref, at_h_ref, at_w_ref, x_ref, u_ref,
                     o_ref, acc_ref, *, n_c: int):
    c_step = pl.program_id(2)

    @pl.when(c_step == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # (bR, th, tw, bC)
    br, th, tw, bc = x.shape
    v = _apply_pair(bt_h_ref[...], bt_w_ref[...],
                    x.astype(jnp.float32))           # (bR, th, tw, bC)
    v = v.transpose(1, 2, 0, 3).reshape(th * tw, br, bc)  # (P, bR, bC)

    u = u_ref[...]                                   # (P, bC, bM)
    # batched point-GEMM: the paper's x^2 GEMMs as one dot_general.
    acc_ref[...] += jax.lax.dot_general(
        v, u.astype(jnp.float32),
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)          # (P, bR, bM)

    @pl.when(c_step == n_c - 1)
    def _store():
        bm_ = acc_ref.shape[-1]
        y = acc_ref[...].reshape(th, tw, br, bm_).transpose(2, 0, 1, 3)
        out = _apply_pair(at_h_ref[...], at_w_ref[...], y)  # (bR, mh, mw, bM)
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ct_h", "ct_w", "block_r",
                                             "block_c", "block_m", "interpret"))
def winograd_fused(
    tiles: jax.Array,        # (R, th, tw, C) pre-extracted input tiles
    u: jax.Array,            # (P, C, M) Winograd-domain filter (P = th*tw)
    *,
    ct_h: CookToom,
    ct_w: CookToom,
    block_r: int = 128,
    block_c: int = 128,
    block_m: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused transform+GEMM+inverse over pre-extracted tiles.

    Returns (R, mh, mw, M) spatial output tiles. R, C, M must be multiples of
    the block sizes (ops.py pads). `interpret=None` resolves via the shared
    rule in kernels.runtime, so direct callers compile on TPU just like the
    ops.py wrappers.
    """
    interpret = resolve_interpret(interpret)
    r_, th, tw, c = tiles.shape
    p, c2, m = u.shape
    assert (th, tw) == (ct_h.t, ct_w.t) and p == th * tw and c == c2
    assert r_ % block_r == 0 and c % block_c == 0 and m % block_m == 0, (
        tiles.shape, u.shape, (block_r, block_c, block_m))
    n_c = c // block_c
    grid = (r_ // block_r, m // block_m, n_c)
    bt_h = jnp.asarray(ct_h.BT, jnp.float32)
    bt_w = jnp.asarray(ct_w.BT, jnp.float32)
    at_h = jnp.asarray(ct_h.AT, jnp.float32)
    at_w = jnp.asarray(ct_w.AT, jnp.float32)
    whole = lambda arr: pl.BlockSpec(arr.shape, lambda i, j, k: (0,) * arr.ndim)
    return pl.pallas_call(
        functools.partial(_winograd_kernel, n_c=n_c),
        grid=grid,
        in_specs=[
            whole(bt_h), whole(bt_w), whole(at_h), whole(at_w),
            pl.BlockSpec((block_r, th, tw, block_c),
                         lambda i, j, k: (i, 0, 0, k)),
            pl.BlockSpec((p, block_c, block_m),
                         lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((block_r, ct_h.m, ct_w.m, block_m),
                               lambda i, j, k: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((r_, ct_h.m, ct_w.m, m), tiles.dtype),
        scratch_shapes=[pltpu.VMEM((p, block_r, block_m), jnp.float32)],
        interpret=interpret,
    )(bt_h, bt_w, at_h, at_w, tiles, u)
