"""Shared kernel-runtime policy: interpret-mode resolution and the fused
epilogue vocabulary.

Every Pallas entry point (kernels/*.py and the ops.py wrappers) resolves its
`interpret` argument through `resolve_interpret`, so direct kernel callers and
the wrapped paths follow the same rule: compiled kernels on a TPU backend,
interpret mode on any other (the CPU test runs).

`apply_activation` is the epilogue vocabulary shared by the Winograd and GEMM
kernels (bias add + none/relu/relu6/gelu) and by the pure-JAX executors, so
every conv backend exposes the same fused-epilogue contract.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

#: Epilogue activations the fused kernels support (static compile-time
#: choice). relu6 is the MobileNet-v2 nonlinearity (clipped ReLU).
ACTIVATIONS = ("none", "relu", "relu6", "gelu")


def pick_block(dim: int, target: int, quantum: int = 8) -> int:
    """Block size <= target; tiny dims round up to the VPU quantum. The one
    blocking-granularity rule shared by the kernel wrappers (ops.py) and the
    plan-time geometry choosers (core/winograd.py)."""
    return target if dim >= target else -(-dim // quantum) * quantum


def default_interpret() -> bool:
    """Pallas interpret-mode default: False on TPU or when
    REPRO_PALLAS_COMPILE is set, True elsewhere (CPU/GPU hosts)."""
    if os.environ.get("REPRO_PALLAS_COMPILE"):
        return False
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def apply_activation(y: jax.Array, activation: str) -> jax.Array:
    """Elementwise epilogue activation; `y` is the fp32 accumulator."""
    if activation == "none":
        return y
    if activation == "relu":
        return jax.nn.relu(y)
    if activation == "relu6":
        return jnp.minimum(jax.nn.relu(y), 6.0)
    if activation == "gelu":
        return jax.nn.gelu(y)
    raise ValueError(
        f"unknown epilogue activation {activation!r}; expected {ACTIVATIONS}")


def epilogue_jnp(y: jax.Array, bias: jax.Array | None,
                 activation: str) -> jax.Array:
    """XLA-side bias+activation for executors without a fused kernel
    epilogue (XLA fuses this into the producing op's consumers). Same
    contract as the in-kernel epilogues: fp32 math, output in y's dtype."""
    if bias is None and activation == "none":
        return y
    out = y.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return apply_activation(out, activation).astype(y.dtype)
