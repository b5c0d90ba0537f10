"""Blocked MXU matmul Pallas kernel with fused bias+activation epilogue.

The GEMM that the paper's im2row baseline (and the unfused Winograd GEMM
phase) bottoms out in. Grid = (M/bm, N/bn, K/bk) with the K axis innermost so
the fp32 VMEM accumulator carries across K steps; A/B panels are staged
HBM->VMEM by BlockSpec, C is written once on the final K step -- with the
optional bias add + activation applied to the fp32 accumulator in that same
store, so conv layers using the im2col path never round-trip the output
through HBM for their elementwise epilogue.

The B panel may arrive in a reduced storage dtype (bf16 cast or int8
per-output-column quantized weights): the dot widens it to fp32 in VMEM, and
the int8 dequantization is one (1, N) `scale` row multiplied into the
accumulator in the same store step as the bias -- the low-precision panel is
what travels HBM->VMEM.

Block defaults are MXU-aligned (128) on the matmul dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import apply_activation, resolve_interpret


def _matmul_kernel(a_ref, b_ref, bias_ref, scale_ref, o_ref, acc_ref, *,
                   n_k: int, activation: str, has_bias: bool,
                   has_scale: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        y = acc_ref[...]
        if has_scale:
            y = y * scale_ref[...]                   # (1, bn) dequant row
        if has_bias:
            y = y + bias_ref[...]                    # (1, bn) broadcast
        o_ref[...] = apply_activation(y, activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "activation",
                                             "interpret", "name"))
def matmul(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
           bk: int = 128, bias: jax.Array | None = None,
           scale: jax.Array | None = None,
           activation: str = "none",
           interpret: bool | None = None,
           name: str | None = None) -> jax.Array:
    """C[M, N] = act(scale * (A[M, K] @ B[K, N]) + bias), fp32 accumulation.

    M, K, N must be multiples of the block sizes (ops.py pads). `bias` is a
    (1, N) fp32 row or None; `scale` a (1, N) fp32 per-output-column
    dequantization row (int8 B panels) or None; `activation` is
    none/relu/gelu, applied to the accumulator in the kernel's store step.
    B may be fp32, bf16, or int8 -- the dot widens it to fp32.
    """
    interpret = resolve_interpret(interpret)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape)
    has_bias = bias is not None
    if bias is None:
        bias = jnp.zeros((1, n), jnp.float32)
    assert bias.shape == (1, n), (bias.shape, b.shape)
    has_scale = scale is not None
    if scale is None:
        scale = jnp.ones((1, n), jnp.float32)
    assert scale.shape == (1, n), (scale.shape, b.shape)
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k, activation=activation,
                          has_bias=has_bias, has_scale=has_scale),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name=name,
    )(a, b, bias, scale)
