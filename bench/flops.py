"""Least work of each layer, and the chip peaks it is divided by.

A conv layer of the inventory (`bench/reference/*.layers`) has a filter of
kh x kw taps, a stride and a padding ("SAME" or "VALID"). Its output is
ceil(h/s) wide for SAME and (h - kh)//s + 1 for VALID. `flops_min` is the
fewest multiply-adds (x 2) of any algorithm modelled here, for the layer's
shape alone, whatever executor the program picks:

  direct            kh*kw*(c_in/groups)*c_out per output pixel;
  Winograd F(m, r)  (m+r-1)^2/m^2 * (c_in/groups)*c_out per output pixel,
                    at stride 1 with kh = kw = r;
  Cook-Toom F(m, r) (m+r-1)/m * (c_in/groups)*c_out per output pixel, at
                    stride 1 for a 1 x r or r x 1 filter;
  stride-2 phases   a stride-2 3x3 conv splits into phase convs of 2x2,
                    2x1, 1x2 and 1x1 taps; the 2-tap axes run F(m, 2) at
                    (m+1)/m multiplies per output, so ((m+1)/m + 1)^2 per
                    output pixel.

Both Winograd forms take m from TILES with m + r - 1 <= MAX_POINTS, the
largest transform the system runs (F(6,3)): F(2..6,3), F(2..4,5), F(2,7).
Tiles are counted fractionally (h_out*w_out/m^2), so no tiling can count
fewer. Dense layers and 1x1 convs are direct. `bytes_min` reads the input
and the filter and writes the output once, in float32. A layer's least time
is the larger of flops_min over the bf16 peak and bytes_min over the HBM
bandwidth: float32 products run as several bf16 passes, so no
implementation beats it, and a share of it cannot pass 100%. A program
node that fuses several layers (`node_time_s`) moves only its own input,
output and filters, so that is what its least time counts.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
TILES = (2, 4, 6)
MAX_POINTS = 8
ELEM_BYTES = 4


def peaks(device_kind: str) -> dict:
    """The peaks of one chip, by `device_kind`; KeyError when the table
    lacks it (there is no default)."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def out_size(size: int, k: int, stride: int, padding: str) -> int:
    """Output size of one axis of a window under `padding`."""
    if padding == "SAME":
        return -(-size // stride)
    if padding == "VALID":
        return (size - k) // stride + 1
    raise ValueError(f"unknown padding {padding!r}")


def _pixels(layer: dict) -> int:
    s, pad = layer["stride"], layer["padding"]
    return (out_size(layer["h"], layer["kh"], s, pad)
            * out_size(layer["w"], layer["kw"], s, pad))


def conv_counts(layer: dict) -> dict[str, float]:
    """Multiply-adds of one image under each modelled algorithm."""
    kh, kw, s = layer["kh"], layer["kw"], layer["stride"]
    pixels = _pixels(layer)
    pairs = layer["c_in"] // layer["groups"] * layer["c_out"]
    counts = {"direct": float(pixels * kh * kw * pairs)}
    r, one_d = max(kh, kw), min(kh, kw) == 1
    if s == 1 and r > 1 and (kh == kw or one_d):
        for m in TILES:
            if m + r - 1 > MAX_POINTS:
                continue
            if one_d:
                counts[f"F({m},{r}) 1-D"] = (pixels * (m + r - 1) / m
                                             * pairs)
            else:
                counts[f"F({m},{r})"] = (pixels * (m + r - 1) ** 2 / m ** 2
                                         * pairs)
    if kh == kw == 3 and s == 2:
        for m in TILES:
            counts[f"phase F({m},2)"] = (pixels * ((m + 1) / m + 1) ** 2
                                         * pairs)
    return counts


def layer_flops_min(layer: dict) -> float:
    """Fewest FLOPs (2 per multiply-add) of one image."""
    if layer["op"] == "dense":
        return 2.0 * layer["n_in"] * layer["n_out"]
    return 2.0 * min(conv_counts(layer).values())


def _elems(layer: dict) -> tuple[int, int, int]:
    """Elements of one image's input and output, and of the filter."""
    if layer["op"] == "dense":
        return layer["n_in"], layer["n_out"], layer["n_in"] * layer["n_out"]
    x = layer["h"] * layer["w"] * layer["c_in"]
    y = _pixels(layer) * layer["c_out"]
    w = (layer["kh"] * layer["kw"] * layer["c_in"] // layer["groups"]
         * layer["c_out"])
    return x, y, w


def layer_bytes_min(layer: dict, batch: int) -> float:
    """Input and output of `batch` images and the filter, once each."""
    x, y, w = _elems(layer)
    return ELEM_BYTES * (batch * (x + y) + w)


def layer_time_s(layer: dict, batch: int, peak: dict) -> float:
    """Least time of one layer for one batch of `batch` images."""
    return max(batch * layer_flops_min(layer) / peak["bf16_flops"],
               layer_bytes_min(layer, batch) / peak["hbm_bytes_per_s"])


def node_time_s(layers: list[dict], batch: int, peak: dict) -> float:
    """Least time of one program node that runs `layers`, in order, for
    one batch: all their FLOPs at the bf16 peak, or the node's input and
    output and every filter once at the HBM bandwidth. What passes between
    the layers of a fused node may stay in the chip's fast memory, so it is
    not counted; for a node of one layer this is `layer_time_s`."""
    elems = [_elems(l) for l in layers]
    nbytes = ELEM_BYTES * (batch * (elems[0][0] + elems[-1][1])
                           + sum(w for _, _, w in elems))
    return max(batch * sum(layer_flops_min(l) for l in layers)
               / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def least_time_s(layers: list[dict], batch: int, peak: dict,
                 ops: tuple[str, ...] = ("conv",)) -> float:
    """Sum over the layers of kind `ops` of each one's least time for one
    batch of `batch` images."""
    return sum(layer_time_s(l, batch, peak) for l in layers if l["op"] in ops)


def least_time_by_node(layers: list[dict], batches: dict[int, int],
                       peak: dict) -> dict[str, float]:
    """Least seconds of each program node (`node_time_s` of its conv and
    dense layers) over `batches`, {batch size: batches run}."""
    by_node: dict[str, list[dict]] = {}
    for l in layers:
        by_node.setdefault(l["node"], []).append(l)
    return {node: sum(n * node_time_s(ls, b, peak)
                      for b, n in batches.items())
            for node, ls in by_node.items()}


def image_flops_min(layers: list[dict]) -> float:
    """Least FLOPs of one image through every conv and dense layer."""
    return sum(layer_flops_min(l) for l in layers)
