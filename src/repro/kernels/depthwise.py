"""Streamed Pallas depthwise and fused separable (depthwise -> pointwise)
convolution kernels.

Depthwise layers are memory-bound (Zhang et al. 2020; Hao et al. 2022): the
per-channel transform-domain work is a Hadamard product, so the win lives
entirely in layout and fusion -- exactly what the halo-streaming machinery
from kernels/winograd.py provides. Both kernels reuse its structure: the
input BlockSpec reads overlapping full-width halo strips of the padded NHWC
input (`pl.Element` offsets), strided loads read each in-tile offset as one
(regions, channels) slab, the transforms are slab-wise linear combinations,
and strided stores scatter the output slabs into the NHWC block. Halo
blocking comes from the plan-time chooser
(core/winograd.py:stream_geometry_depthwise).

`depthwise_streamed` -- grid (N, nHb, C/bC). One pass, no reduction axis:
per step, transform the halo strip (B^T (.) B), multiply elementwise by the
(P, bC) Winograd-domain taps, inverse-transform (A^T (.) A), run the fused
bias+activation epilogue, and scatter the NHWC block. The only HBM tensors
are the padded input and the output.

`depthwise_strided_streamed` -- the stride-2 depthwise kernel (MobileNet
reduction blocks): same structure with the halo strip covering the
full-resolution input and four phase sub-grids read by the strided loads;
the phase Hadamard products accumulate in the transform domain (shared
A^T), one inverse transform, one store.

`separable_streamed` -- the fused MobileNet block: depthwise k x k ->
bias+activation -> pointwise 1x1 -> bias+activation, in ONE kernel. Grid
(N, nHb, M/bM, C/bC) with C innermost, mirroring the dense streaming
kernel's (M, C) sweep: on the first M step of each strip the depthwise
output block for channel slice cb is computed in VMEM and cached (the
z-cache below, the analogue of the dense kernel's transformed-input cache);
every step then runs one (S, bC) x (bC, bM) pointwise GEMM into the fp32
accumulator; the last C step applies the pointwise epilogue and stores the
NHWC block. The depthwise -> pointwise intermediate NEVER touches HBM --
that round trip (write + re-read per pointwise M block + separate epilogue
passes) is precisely what the unfused baseline pays.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.transforms import CookToom
from repro.kernels.runtime import resolve_interpret
from repro.kernels.winograd import (epilogue, input_transform, load_slab,
                                    output_transform, phase_offsets,
                                    store_slab)


def _depthwise_outputs(x_ref, tap, y_ref, *, ct_h: CookToom,
                       ct_w: CookToom, stride: int, bh: int, bw: int,
                       emit) -> None:
    """Shared depthwise compute over one halo-strip ref: per phase, the
    slab-wise input transform, then the Hadamard product with the
    Winograd-domain taps into the (P, bR, bC) VMEM scratch `y_ref` -- the
    dense kernel's channel GEMM degenerates to an elementwise multiply per
    Winograd point, and at stride 2 the four phase products sum in the
    transform domain -- then one slab-wise inverse transform. `tap(q)` is
    the (1, bC) row of phase-major point q; emit(a, b, y) receives each
    fp32 output slab before any epilogue."""
    th, tw, mh, mw = ct_h.t, ct_w.t, ct_h.m, ct_w.m
    p = th * tw
    for k, (ph, qh) in enumerate(phase_offsets(stride)):
        def load(t, u, ph=ph, qh=qh):
            return load_slab(x_ref, stride * t + ph, stride * u + qh, bh, bw,
                             stride * mh, stride * mw)

        def mul(q, v, k=k):
            y = v * tap(k * p + q)
            y_ref[q] = y if k == 0 else y_ref[q] + y
        input_transform(load, ct_h, ct_w, mul)
    output_transform(lambda q: y_ref[q], ct_h, ct_w, emit)


def _depthwise_kernel(x_ref, u_ref, bias_ref, scale_ref, o_ref, y_ref, *,
                      ct_h: CookToom, ct_w: CookToom, stride: int, bh: int,
                      bw: int, activation: str, has_bias: bool,
                      has_scale: bool):
    mh, mw = ct_h.m, ct_w.m
    # channel multiplier j fans input channel c out to output c*mult + j;
    # each j is its own lane-aligned output plane (the wrapper interleaves).
    for j in range(u_ref.shape[1]):
        bias = bias_ref[pl.ds(j, 1), :] if has_bias else None
        scale = scale_ref[pl.ds(j, 1), :] if has_scale else None

        def tap(q, j=j):
            return u_ref[q, pl.ds(j, 1), :].astype(jnp.float32)

        def emit(a, b, y, j=j, bias=bias, scale=scale):
            store_slab(o_ref, (j, 0), epilogue(y, bias, scale, activation),
                       a, b, bh, bw, mh, mw)
        _depthwise_outputs(x_ref, tap, y_ref, ct_h=ct_h, ct_w=ct_w,
                           stride=stride, bh=bh, bw=bw, emit=emit)


def _depthwise_call(xp, u, bias, scale, *, ct_h, ct_w, stride, bh, bw,
                    block_c, activation, interpret):
    """pallas_call shared by the stride-1 and stride-2 depthwise kernels.
    `u` is (phases*P, Cp, mult); bias/scale are (1, Cp*mult) rows in the
    o = c*mult + j order. Returns (N, Ho, Wo, Cp*mult)."""
    interpret = resolve_interpret(interpret)
    n, hp, wp, c = xp.shape
    pp, c2, mult = u.shape
    th, tw, mh, mw = ct_h.t, ct_w.t, ct_h.m, ct_w.m
    sh, sw = bh * mh, bw * mw
    hs = stride * (sh + th - mh)
    ws = stride * (sw + tw - mw)
    assert pp == stride * stride * th * tw and c == c2, (xp.shape, u.shape)
    assert c % block_c == 0, (xp.shape, block_c)
    n_hb, rh = divmod(hp - stride * (th - mh), stride * sh)
    assert rh == 0 and wp == ws, (xp.shape, (bh, bw), (mh, mw))
    grid = (n, n_hb, c // block_c)

    def planes(row):          # (1, Cp*mult) c-major -> (mult, Cp) planes
        return row.reshape(c, mult).T

    has_bias = bias is not None
    has_scale = scale is not None
    bias = planes(bias) if has_bias else jnp.zeros((mult, c), jnp.float32)
    scale = planes(scale) if has_scale else jnp.ones((mult, c), jnp.float32)
    y = pl.pallas_call(
        functools.partial(_depthwise_kernel, ct_h=ct_h, ct_w=ct_w,
                          stride=stride, bh=bh, bw=bw, activation=activation,
                          has_bias=has_bias, has_scale=has_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, pl.Element(hs), pl.Element(ws),
                          pl.Element(block_c)),
                         lambda n_, i, cb: (n_, i * stride * sh, 0,
                                            cb * block_c)),
            pl.BlockSpec((pp, mult, block_c), lambda n_, i, cb: (0, 0, cb)),
            pl.BlockSpec((mult, block_c), lambda n_, i, cb: (0, cb)),
            pl.BlockSpec((mult, block_c), lambda n_, i, cb: (0, cb)),
        ],
        out_specs=pl.BlockSpec((mult, 1, sh, sw, block_c),
                               lambda n_, i, cb: (0, n_, i, 0, cb)),
        out_shape=jax.ShapeDtypeStruct((mult, n, n_hb * sh, sw, c),
                                       xp.dtype),
        scratch_shapes=[pltpu.VMEM((th * tw, bh * bw, block_c),
                                   jnp.float32)],
        interpret=interpret,
    )(xp, u.transpose(0, 2, 1), bias, scale)
    if mult == 1:
        return y[0]
    return y.transpose(1, 2, 3, 4, 0).reshape(y.shape[1:4] + (c * mult,))


@functools.partial(jax.jit, static_argnames=(
    "ct_h", "ct_w", "bh", "bw", "block_c", "activation", "interpret"))
def depthwise_streamed(
    xp: jax.Array,           # (N, Hp, Wp, Cp) halo-padded NHWC input
    u: jax.Array,            # (P, Cp, mult) Winograd-domain depthwise taps
    bias: jax.Array | None,  # (1, Cp*mult) fp32 epilogue bias, or None
    scale: jax.Array | None = None,  # (1, Cp*mult) int8-dequant scale, or None
    *,
    ct_h: CookToom,
    ct_w: CookToom,
    bh: int,
    bw: int,
    block_c: int = 128,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Halo-streaming depthwise transform+Hadamard+inverse+epilogue.

    `xp` must be padded so Hp = nHb*bh*mh + (th - mh) for an integer strip
    count nHb and Wp = bw*mw + (tw - mw) (ops.py pads from the plan's
    StreamGeometry). The taps carry the channel multiplier as a trailing
    axis; output channel o = c*mult + j (the lax feature_group_count
    ordering). Returns (N, nHb*bh*mh, bw*mw, Cp*mult); the caller crops the
    geometry surplus.
    """
    return _depthwise_call(xp, u, bias, scale, ct_h=ct_h, ct_w=ct_w,
                           stride=1, bh=bh, bw=bw, block_c=block_c,
                           activation=activation, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "ct_h", "ct_w", "bh", "bw", "block_c", "activation", "interpret"))
def depthwise_strided_streamed(
    xp: jax.Array,           # (N, Hp, Wp, Cp) halo-padded full-res input
    u: jax.Array,            # (4P, Cp) phase-major Winograd-domain taps
    bias: jax.Array | None,  # (1, Cp) fp32 epilogue bias, or None
    scale: jax.Array | None = None,  # (1, Cp) fp32 int8-dequant scale, or None
    *,
    ct_h: CookToom,
    ct_w: CookToom,
    bh: int,
    bw: int,
    block_c: int = 128,
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Stride-2 streamed depthwise conv via transform-domain phase
    decomposition: the MobileNet reduction-block depthwise layer as one
    halo-streaming kernel (fused epilogue, no phase tensors in HBM).

    `xp` must be padded so Hp = nHb*2*bh*mh + 2*(th - mh) and
    Wp = 2*bw*mw + 2*(tw - mw) (ops.py pads from the plan's
    StreamGeometry). Returns the stride-2 output grid
    (N, nHb*bh*mh, bw*mw, Cp); the caller crops.
    """
    return _depthwise_call(xp, u[:, :, None], bias, scale, ct_h=ct_h,
                           ct_w=ct_w, stride=2, bh=bh, bw=bw,
                           block_c=block_c, activation=activation,
                           interpret=interpret)


# ---------------------------------------------------------------------------
# Fused separable block: depthwise -> epilogue -> pointwise -> epilogue
# ---------------------------------------------------------------------------

def _separable_kernel(x_ref, udw_ref, upw_ref, bdw_ref, bpw_ref, o_ref,
                      acc_ref, z_ref, y_ref, *, ct_h: CookToom, ct_w: CookToom,
                      n_c: int, bh: int, bw: int, inner_activation: str,
                      activation: str, has_bias_dw: bool, has_bias_pw: bool):
    m_step = pl.program_id(2)
    c_step = pl.program_id(3)
    mh, mw = ct_h.m, ct_w.m

    @pl.when(c_step == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Depthwise stage runs once per (strip, C block) -- the first M step
    # fills the z cache with the post-epilogue depthwise output slabs (one
    # per output offset), later M steps reuse it (the analogue of the dense
    # kernel's transformed-input cache). The intermediate lives only in
    # this VMEM scratch.
    @pl.when(m_step == 0)
    def _dw():
        bias = bdw_ref[...] if has_bias_dw else None

        def tap(q):
            return udw_ref[pl.ds(q, 1), :].astype(jnp.float32)

        def emit(a, b, y):
            z_ref[c_step, a * mw + b] = epilogue(y, bias, None,
                                                 inner_activation)
        _depthwise_outputs(x_ref, tap, y_ref, ct_h=ct_h, ct_w=ct_w,
                           stride=1, bh=bh, bw=bw, emit=emit)

    # pointwise stage: one (bR, bC) x (bC, bM) GEMM per output offset.
    def offset_gemm(s, carry):
        acc_ref[s] += jnp.dot(z_ref[c_step, s],
                              upw_ref[...].astype(jnp.float32),
                              preferred_element_type=jnp.float32)
        return carry
    jax.lax.fori_loop(0, mh * mw, offset_gemm, 0)

    @pl.when(c_step == n_c - 1)
    def _store():
        bias = bpw_ref[...] if has_bias_pw else None
        for a in range(mh):
            for b in range(mw):
                y = epilogue(acc_ref[a * mw + b], bias, None, activation)
                store_slab(o_ref, (0,), y, a, b, bh, bw, mh, mw)


@functools.partial(jax.jit, static_argnames=(
    "ct_h", "ct_w", "bh", "bw", "block_c", "block_m", "inner_activation",
    "activation", "interpret"))
def separable_streamed(
    xp: jax.Array,            # (N, Hp, Wp, Cp) halo-padded NHWC input
    u_dw: jax.Array,          # (P, Cp) Winograd-domain depthwise taps
    u_pw: jax.Array,          # (Cp, Mp) pointwise filter matrix
    bias_dw: jax.Array | None,   # (1, Cp) fp32 depthwise bias, or None
    bias_pw: jax.Array | None,   # (1, Mp) fp32 pointwise bias, or None
    *,
    ct_h: CookToom,
    ct_w: CookToom,
    bh: int,
    bw: int,
    block_c: int = 128,
    block_m: int = 128,
    inner_activation: str = "none",
    activation: str = "none",
    interpret: bool | None = None,
) -> jax.Array:
    """Fused separable block over the halo-padded input: depthwise Winograd
    + bias/activation + pointwise 1x1 + bias/activation in one kernel; the
    depthwise -> pointwise intermediate never leaves VMEM. Returns
    (N, nHb*bh*mh, bw*mw, Mp); the caller crops the geometry surplus.
    """
    interpret = resolve_interpret(interpret)
    n, hp, wp, c = xp.shape
    p, c2 = u_dw.shape
    c3, m = u_pw.shape
    th, tw, mh, mw = ct_h.t, ct_w.t, ct_h.m, ct_w.m
    sh, sw = bh * mh, bw * mw
    hs, ws = sh + th - mh, sw + tw - mw
    assert p == th * tw and c == c2 == c3, (xp.shape, u_dw.shape, u_pw.shape)
    assert c % block_c == 0 and m % block_m == 0, (xp.shape, u_pw.shape,
                                                   (block_c, block_m))
    n_hb, rh = divmod(hp - (th - mh), sh)
    assert rh == 0 and wp == ws, (xp.shape, (bh, bw), (mh, mw))
    n_c = c // block_c
    grid = (n, n_hb, m // block_m, n_c)

    has_bias_dw = bias_dw is not None
    has_bias_pw = bias_pw is not None
    if bias_dw is None:
        bias_dw = jnp.zeros((1, c), jnp.float32)
    if bias_pw is None:
        bias_pw = jnp.zeros((1, m), jnp.float32)
    return pl.pallas_call(
        functools.partial(_separable_kernel, ct_h=ct_h, ct_w=ct_w, n_c=n_c,
                          bh=bh, bw=bw, inner_activation=inner_activation,
                          activation=activation, has_bias_dw=has_bias_dw,
                          has_bias_pw=has_bias_pw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, pl.Element(hs), pl.Element(ws),
                          pl.Element(block_c)),
                         lambda n_, i, mb, cb: (n_, i * sh, 0, cb * block_c)),
            pl.BlockSpec((p, block_c), lambda n_, i, mb, cb: (0, cb)),
            pl.BlockSpec((block_c, block_m), lambda n_, i, mb, cb: (cb, mb)),
            pl.BlockSpec((1, block_c), lambda n_, i, mb, cb: (0, cb)),
            pl.BlockSpec((1, block_m), lambda n_, i, mb, cb: (0, mb)),
        ],
        out_specs=pl.BlockSpec((1, sh, sw, block_m),
                               lambda n_, i, mb, cb: (n_, i, 0, mb)),
        out_shape=jax.ShapeDtypeStruct((n, n_hb * sh, sw, m), xp.dtype),
        scratch_shapes=[pltpu.VMEM((mh * mw, bh * bw, block_m), jnp.float32),
                        # depthwise-output cache: filled on the first M step
                        # of each strip, reused by the rest of the (M, C)
                        # sweep -- the fused block's only "intermediate".
                        pltpu.VMEM((n_c, mh * mw, bh * bw, block_c),
                                   jnp.float32),
                        # per-point depthwise products
                        pltpu.VMEM((p, bh * bw, block_c), jnp.float32)],
        interpret=interpret,
    )(xp, u_dw, u_pw, bias_dw, bias_pw)
