"""Fused Mamba-1 selective-scan Pallas kernel.

The XLA lowering of the chunked selective scan materializes every
associative-scan level as an HBM round-trip of the (B, chunk, d_in, N) state
transient -- measured 78 TB/device on the falcon train_4k cell even after the
in-chunk discretization restructure (EXPERIMENTS.md section Perf). The state
expansion (N=16) times the log2(chunk) scan levels is inherent to expressing
the recurrence in XLA ops.

This kernel is the structural fix, and the TPU analogue of the paper's core
move (keep the transformed domain in registers/VMEM, never touch memory in
the expanded domain):

  grid = (B, D / bD, L / chunk)   -- L innermost, sequential

  per step: load dt/xs (1, chunk, bD) and B/C (1, chunk, N) tiles, carry the
  (bD, N) fp32 state in a VMEM scratch across L steps, run the within-chunk
  associative scan entirely in VMEM, write back only y (1, chunk, bD).

HBM traffic therefore = inputs + outputs = B*L*(2 bD + 2N)*bytes per D-block,
i.e. the N-fold state expansion and the log-levels never leave VMEM. At
falcon train shapes that is ~130 GB/device/step vs 78 TB -- a ~600x cut on
the scan's share (the roofline accounting for the TPU path is derived
analytically in EXPERIMENTS.md; the CPU tests validate the kernel in
interpret mode).

Channels ride the 128-lane axis (bD a multiple of 128), N on sublanes --
the paper's channels-innermost argument, applied to the SSM state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32


def _kernel(a_ref, dt_ref, xs_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
            n_l: int):
    l_step = pl.program_id(2)

    @pl.when(l_step == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a_mat = a_ref[...]                                # (bD, N) fp32
    dt = dt_ref[0].astype(_F32)                       # (chunk, bD)
    xs = xs_ref[0].astype(_F32)                       # (chunk, bD)
    bmat = b_ref[0].astype(_F32)                      # (chunk, N)
    cmat = c_ref[0].astype(_F32)                      # (chunk, N)

    a_c = jnp.exp(dt[:, :, None] * a_mat[None])       # (chunk, bD, N)
    bx = (dt * xs)[:, :, None] * bmat[:, None, :]     # (chunk, bD, N)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    a_acc, b_acc = jax.lax.associative_scan(combine, (a_c, bx), axis=0)
    h_all = a_acc * h_ref[...][None] + b_acc          # (chunk, bD, N)
    y_ref[0] = jnp.einsum("lds,ls->ld", h_all, cmat).astype(y_ref.dtype)
    h_ref[...] = h_all[-1]

    @pl.when(l_step == n_l - 1)
    def _final():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def selective_scan(
    dt: jax.Array,        # (B, L, D) fp32/bf16
    xs: jax.Array,        # (B, L, D)
    bmat: jax.Array,      # (B, L, N)
    cmat: jax.Array,      # (B, L, N)
    a_mat: jax.Array,     # (D, N) fp32 (A = -exp(a_log))
    *,
    chunk: int = 256,
    block_d: int = 512,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B, L, D) fp32, h_last (B, D, N) fp32).

    L % chunk == 0 and D % block_d == 0 (ops.py pads).
    """
    b, l, d = dt.shape
    n = a_mat.shape[-1]
    assert l % chunk == 0 and d % block_d == 0, (dt.shape, chunk, block_d)
    n_l = l // chunk
    grid = (b, d // block_d, n_l)
    y, h_last = pl.pallas_call(
        functools.partial(_kernel, n_l=n_l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_d, n), lambda i, j, k: (j, 0)),
            pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, block_d, n), lambda i, j, k: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, d), _F32),
            jax.ShapeDtypeStruct((b, d, n), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), _F32)],
        interpret=interpret,
    )(a_mat.astype(_F32), dt, xs, bmat, cmat)
    return y, h_last
