"""Compile the served Pallas kernels for a described TPU v5e.

Every other kernel test runs Pallas in interpret mode, which checks the
numerics but not what the TPU compiler (Mosaic) accepts: block shapes, the
element-offset halo strips, strided loads and stores, and the scoped VMEM
that `core/winograd.py:stream_geometry` budgets. Here each kernel of the
served path is planned at a real layer width on the CPU and compiled for one
chip of a described v5e:2x2 topology; nothing runs.

The topology is described only inside the `topo` fixture, never while a
module is imported: one process at a time may load the TPU library, and a
fixture keeps the other test workers off it.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.plan import plan_conv2d, plan_separable_block
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _weights(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) / np.prod(shape[:3]),
                       jnp.float32)


def _compile_for_chip(fn, arrays, one_chip) -> str:
    """Lower `fn(*arrays)` for the described chip (shapes only) and return
    the compiled HLO text."""
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in arrays]
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _bias(m):
    return jnp.zeros((m,), jnp.float32)


def _conv_call(plan, activation="relu"):
    """(fn(x, u, bias), executor) running the plan's Pallas executor with
    interpret=False, so the CPU-side planner yields the chip's kernel."""
    spec = plan.spec
    kw = dict(ct_h=spec.ct_h, ct_w=spec.ct_w, geometry=spec.geometry,
              stream=spec.stream, c_out=spec.w_shape[3],
              activation=activation, interpret=False)
    run = {"pallas_winograd": ops.winograd_conv2d_planned,
           "pallas_winograd_strided": ops.winograd_strided_conv2d_planned,
           "pallas_depthwise": ops.depthwise_conv2d_planned,
           "pallas_depthwise_strided": ops.depthwise_strided_conv2d_planned,
           }[spec.algorithm]
    return lambda x, u, b: run(x, u, bias=b, **kw)


# (id, input NHWC, filter HWIO, stride, groups, output tile, executor)
CONV_CASES = [
    ("vgg16_conv3_f43", (1, 56, 56, 256), (3, 3, 256, 256), 1, 1, 4,
     "pallas_winograd"),
    ("vgg16_conv3_f23", (1, 56, 56, 256), (3, 3, 256, 256), 1, 1, 2,
     "pallas_winograd"),
    ("mobilenet_v2_stem_s2", (1, 224, 224, 3), (3, 3, 3, 32), 2, 1, None,
     "pallas_winograd_strided"),
    ("squeezenet_stem_7x7_s2", (1, 224, 224, 3), (7, 7, 3, 96), 2, 1, None,
     "pallas_winograd_strided"),
    ("mobilenet_v2_dw_s1", (1, 56, 56, 144), (3, 3, 1, 144), 1, 144, None,
     "pallas_depthwise"),
    ("mobilenet_v2_dw_s2", (1, 112, 112, 96), (3, 3, 1, 96), 2, 96, None,
     "pallas_depthwise_strided"),
]


@pytest.mark.parametrize("case", CONV_CASES, ids=[c[0] for c in CONV_CASES])
def test_streamed_conv_kernel_compiles(case, one_chip):
    _, x_shape, w_shape, stride, groups, tile, executor = case
    plan = plan_conv2d(x_shape, _weights(w_shape), stride=stride,
                       groups=groups, output_tile=tile,
                       algorithm="pallas_winograd")
    assert plan.algorithm == executor
    x = jnp.zeros(x_shape, jnp.float32)
    hlo = _compile_for_chip(_conv_call(plan), (x, plan.u, _bias(w_shape[3])),
                            one_chip)
    assert "tpu_custom_call" in hlo


# (id, input NHWC, depthwise channels, pointwise outputs): MobileNet-v2's
# expand-1 block and a 144-wide inverted-residual depthwise+project pair.
SEPARABLE_CASES = [
    ("mobilenet_v2_block1", (1, 112, 112, 32), 16),
    ("mobilenet_v2_block3", (1, 56, 56, 144), 24),
]


@pytest.mark.parametrize("case", SEPARABLE_CASES,
                         ids=[c[0] for c in SEPARABLE_CASES])
def test_fused_separable_kernel_compiles(case, one_chip):
    _, x_shape, m = case
    c = x_shape[3]
    plan = plan_separable_block(x_shape, _weights((3, 3, 1, c)),
                                _weights((1, 1, c, m), seed=1),
                                algorithm="pallas_winograd")
    assert plan.mode == "fused_pallas"
    spec = plan.spec

    def fn(x, u_dw, u_pw, b_dw, b_pw):
        return ops.separable_conv2d_planned(
            x, u_dw, u_pw, ct_h=spec.ct_h, ct_w=spec.ct_w,
            geometry=spec.geometry, stream=spec.stream, c_out=m,
            bias_dw=b_dw, bias_pw=b_pw, inner_activation="relu6",
            activation="none", interpret=False)

    x = jnp.zeros(x_shape, jnp.float32)
    hlo = _compile_for_chip(fn, (x, plan.u_dw, plan.u_pw, _bias(c), _bias(m)),
                            one_chip)
    assert "tpu_custom_call" in hlo


def test_im2col_matmul_kernel_compiles(one_chip):
    """The blocked GEMM under pallas_im2col, at a VGG-16 3x3 layer."""
    x_shape, w_shape = (1, 56, 56, 256), (3, 3, 256, 256)
    plan = plan_conv2d(x_shape, _weights(w_shape), algorithm="pallas_im2col")
    spec = plan.spec

    def fn(x, b, bias):
        return ops.im2col_conv2d_planned(
            x, b, kh=3, kw=3, stride=spec.stride, padding=spec.padding,
            geometry=spec.geometry, blocks=spec.blocks, c_out=w_shape[3],
            bias=bias, activation="relu", interpret=False)

    x = jnp.zeros(x_shape, jnp.float32)
    hlo = _compile_for_chip(fn, (x, plan.u, _bias(w_shape[3])), one_chip)
    assert "tpu_custom_call" in hlo
