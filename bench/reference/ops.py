"""Plain float32 building blocks of the reference networks.

Every matrix product runs at `Precision.HIGHEST`, so on a TPU the reference
is float32 and not one bf16 pass. Nothing here imports the system under
test: the reference shares only the parameter layout with it (HWIO conv
filters with a bias, `{"w"}` dense weights), so that one set of weights
drawn from the seed feeds both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def out_size(size: int, stride: int) -> int:
    """Output size of a "SAME"-padded window."""
    return -(-size // stride)


#: scale of the random conv biases. A zero bias would leave the bias
#: epilogue unchecked; a large one swamps the input: through a dozen random
#: ReLU layers the biases' common part grows until the logits of any two
#: images differ by about as much as float32-with-bf16-products rounds
#: (0.1 gave 1-2% between images, 0.01 gives 5-25%).
BIAS_SCALE = 0.01


def conv_init(key, k: int, c_in: int, c_out: int, groups: int = 1) -> dict:
    """He-normal HWIO filter and a small random bias."""
    kw, kb = jax.random.split(key)
    cg = c_in // groups
    return {"w": jax.random.normal(kw, (k, k, cg, c_out), jnp.float32)
            * (k * k * cg) ** -0.5,
            "b": BIAS_SCALE * jax.random.normal(kb, (c_out,), jnp.float32)}


def dense_init(key, n_in: int, n_out: int) -> dict:
    return {"w": jax.random.normal(key, (n_in, n_out), jnp.float32)
            * n_in ** -0.5}


def rounded(a, to=None):
    """`a` rounded to the dtype `to` (and back to float32); the control
    computes the reference with every product's operands so rounded."""
    return a if to is None else a.astype(to).astype(jnp.float32)


def conv(p: dict, x, stride: int = 1, groups: int = 1, act: str = "relu",
         round_to=None):
    """NHWC "SAME" convolution + bias + activation."""
    y = jax.lax.conv_general_dilated(
        rounded(x, round_to), rounded(p["w"], round_to), (stride, stride),
        "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST) + p["b"]
    if act == "relu":
        return jax.nn.relu(y)
    if act == "relu6":
        return jnp.clip(y, 0.0, 6.0)
    return y


def dense(p: dict, x, relu: bool, round_to=None):
    y = jnp.dot(rounded(x.reshape(x.shape[0], -1), round_to),
                rounded(p["w"], round_to), precision=HIGHEST)
    return jax.nn.relu(y) if relu else y


def max_pool(x, k: int, stride: int):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                 (1, stride, stride, 1), "VALID")


def conv_layer(node: str, h: int, w: int, c_in: int, c_out: int, kh: int,
               kw: int, stride: int = 1, groups: int = 1,
               padding: str = "SAME") -> dict:
    """One entry of a network's layer inventory: a kh x kw filter over an
    h x w input, run inside the program node `node` (its id in
    `NetworkPlan.describe()`)."""
    return {"op": "conv", "node": node, "h": h, "w": w, "c_in": c_in,
            "c_out": c_out, "kh": kh, "kw": kw, "stride": stride,
            "groups": groups, "padding": padding}


def dense_layer(node: str, n_in: int, n_out: int) -> dict:
    return {"op": "dense", "node": node, "n_in": n_in, "n_out": n_out}
