"""Plain MobileNet-v2 reference (Sandler et al., arXiv:1801.04381, Table 2).

The configuration file gives the stem width, `stages` as [t, c, n, s]
(expansion, output channels, repeats, first stride), the 1x1 head width
and the classes. Stem and head are conv + ReLU6; each inverted residual is
1x1 expand (skipped at t = 1) + ReLU6, 3x3 depthwise at stride s + ReLU6,
1x1 linear projection, plus the input where the stride is 1 and the
channels match. Then global average pooling and a dense classifier without
bias. Parameter names follow the served network: `conv1`, `ir<i>`
({"exp", "dw", "pw"}), `conv_head`, `fc`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import ops


def _blocks(cfg):
    c_in, i = cfg["stem"], 0
    for t, c, n, s in cfg["stages"]:
        for j in range(n):
            i += 1
            yield f"ir{i}", c_in, c, t, s if j == 0 else 1
            c_in = c


def init(key, cfg) -> dict:
    key, k = jax.random.split(key)
    params = {"conv1": ops.conv_init(k, 3, cfg["c_in"], cfg["stem"])}
    c_last = cfg["stem"]
    for name, c_in, c_out, t, _ in _blocks(cfg):
        key, k1, k2, k3 = jax.random.split(key, 4)
        ce = c_in * t
        p = {"dw": ops.conv_init(k2, 3, ce, ce, groups=ce),
             "pw": ops.conv_init(k3, 1, ce, c_out)}
        if t != 1:
            p["exp"] = ops.conv_init(k1, 1, c_in, ce)
        params[name] = p
        c_last = c_out
    key, k1, k2 = jax.random.split(key, 3)
    params["conv_head"] = ops.conv_init(k1, 1, c_last, cfg["head"])
    params["fc"] = ops.dense_init(k2, cfg["head"], cfg["classes"])
    return params


def forward(params, x, cfg, round_to=None):
    r = round_to
    x = ops.conv(params["conv1"], x, stride=2, act="relu6", round_to=r)
    for name, c_in, c_out, t, s in _blocks(cfg):
        p = params[name]
        h = ops.conv(p["exp"], x, act="relu6", round_to=r) if t != 1 else x
        h = ops.conv(p["dw"], h, stride=s, groups=h.shape[-1], act="relu6",
                     round_to=r)
        h = ops.conv(p["pw"], h, act="none", round_to=r)
        x = x + h if s == 1 and c_in == c_out else h
    x = ops.conv(params["conv_head"], x, act="relu6", round_to=r)
    return ops.dense(params["fc"], jnp.mean(x, axis=(1, 2)), relu=False,
                     round_to=r)


def layers(cfg) -> list[dict]:
    """Conv and dense layers of one image, with their input sizes and
    the program node each runs in: an inverted residual's expand, depthwise
    and projection all run in its fused node `ir<i>`."""
    side = cfg["res"]
    out = [ops.conv_layer("conv1", side, side, cfg["c_in"], cfg["stem"], 3,
                          3, 2)]
    side = ops.out_size(side, 2)
    c_last = cfg["stem"]
    for name, c_in, c_out, t, s in _blocks(cfg):
        ce = c_in * t
        if t != 1:
            out.append(ops.conv_layer(name, side, side, c_in, ce, 1, 1))
        out.append(ops.conv_layer(name, side, side, ce, ce, 3, 3, s,
                                  groups=ce))
        side = ops.out_size(side, s)
        out.append(ops.conv_layer(name, side, side, ce, c_out, 1, 1))
        c_last = c_out
    out.append(ops.conv_layer("conv_head", side, side, c_last, cfg["head"],
                              1, 1))
    out.append(ops.dense_layer("fc", cfg["head"], cfg["classes"]))
    return out
