"""Depthwise causal Cook-Toom conv1d Pallas kernel (Mamba short conv).

The paper's 1D algorithm specialized to depthwise form. The per-point channel
GEMM degenerates to a lane-wise multiply (no channel reduction), so the whole
algorithm is VPU work: transform (adds/subs over the tile axis), one Hadamard
multiply per Winograd point, inverse transform. Multiplication count drops by
m*r/t per channel -- e.g. F(4,4): 16 -> 7 multiplies per 4 outputs (2.29x).

grid = (B, S / bS, C / bC) over pre-extracted tiles (B, S, t, C); everything
is elementwise over (bS, bC) so channels sit on the 128-lane axis (NHWC
argument again) and the sublane axis carries tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.transforms import CookToom
from repro.kernels.runtime import resolve_interpret


def _kernel(bt_ref, at_ref, x_ref, u_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)                     # (bS, t, C)
    v = jnp.tensordot(bt_ref[...], x, axes=(1, 1)).transpose(1, 0, 2)
    y = v * u_ref[...][None]                             # Hadamard per channel
    out = jnp.tensordot(at_ref[...], y, axes=(1, 1)).transpose(1, 0, 2)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("ct", "block_s", "block_c",
                                             "interpret"))
def conv1d_ct_fused(
    tiles: jax.Array,      # (B, S, t, C) pre-extracted causal tiles
    u: jax.Array,          # (t, C) Cook-Toom-domain depthwise taps
    *,
    ct: CookToom,
    block_s: int = 256,
    block_c: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns (B, S, m, C) output tiles. S % block_s == 0, C % block_c == 0.
    `interpret=None` resolves via the shared rule (kernels.runtime)."""
    interpret = resolve_interpret(interpret)
    b, s, t, c = tiles.shape
    assert t == ct.t and u.shape == (t, c)
    assert s % block_s == 0 and c % block_c == 0, (tiles.shape, block_s, block_c)
    bt = jnp.asarray(ct.BT, jnp.float32)
    at = jnp.asarray(ct.AT, jnp.float32)
    return pl.pallas_call(
        _kernel,
        grid=(b, s // block_s, c // block_c),
        in_specs=[
            pl.BlockSpec(bt.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec(at.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, block_s, t, block_c),
                         lambda i, j, k: (i, j, 0, k)),
            pl.BlockSpec((t, block_c), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, block_s, ct.m, block_c),
                               lambda i, j, k: (i, j, 0, k)),
        out_shape=jax.ShapeDtypeStruct((b, s, ct.m, c), tiles.dtype),
        interpret=interpret,
    )(bt, at, tiles, u)
