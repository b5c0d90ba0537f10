"""Plain VGG reference (Simonyan & Zisserman, arXiv:1409.1556, Table 1).

The configuration file gives `blocks` as [convs, channels] per stage (each
stage ends in a 2x2 stride-2 max pool) and `fc` as the widths of the
classifier; every conv is 3x3, stride 1, "SAME", with ReLU. Parameter
names follow the served network: `conv<stage>_<i>`, `fc6`..`fc8`.
"""

from __future__ import annotations

import jax

from bench.reference import ops


def _convs(cfg):
    c_in = cfg["c_in"]
    for s, (n, c) in enumerate(cfg["blocks"]):
        for i in range(n):
            yield f"conv{s + 1}_{i}", c_in, c, i == n - 1
            c_in = c


def _fcs(cfg):
    first = 6
    return [f"fc{first + i}" for i in range(len(cfg["fc"]))]


def init(key, cfg) -> dict:
    params = {}
    for name, c_in, c_out, _ in _convs(cfg):
        key, k = jax.random.split(key)
        params[name] = ops.conv_init(k, 3, c_in, c_out)
    side = cfg["res"] // 2 ** len(cfg["blocks"])
    n_in = side * side * cfg["blocks"][-1][1]
    for name, n_out in zip(_fcs(cfg), cfg["fc"]):
        key, k = jax.random.split(key)
        params[name] = ops.dense_init(k, n_in, n_out)
        n_in = n_out
    return params


def forward(params, x, cfg, round_to=None):
    for name, _, _, last in _convs(cfg):
        x = ops.conv(params[name], x, round_to=round_to)
        if last:
            x = ops.max_pool(x, 2, 2)
    names = _fcs(cfg)
    for i, name in enumerate(names):
        x = ops.dense(params[name], x, relu=i < len(names) - 1,
                      round_to=round_to)
    return x


def layers(cfg) -> list[dict]:
    """Conv and dense layers of one image, with their input sizes and
    the program node each runs in (named as the parameters are)."""
    out, side = [], cfg["res"]
    for name, c_in, c_out, last in _convs(cfg):
        out.append(ops.conv_layer(name, side, side, c_in, c_out, 3, 3))
        if last:
            side //= 2
    n_in = side * side * cfg["blocks"][-1][1]
    for name, n_out in zip(_fcs(cfg), cfg["fc"]):
        out.append(ops.dense_layer(name, n_in, n_out))
        n_in = n_out
    return out
