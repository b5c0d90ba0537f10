"""Least work of each layer, and the chip peaks it is divided by.

`flops_min` of a conv layer is the fewest multiply-adds (x 2) of any
algorithm modelled here, for the layer's shape alone, whatever executor
the program picks:

  direct            k*k*(c_in/groups)*c_out per output pixel;
  Winograd F(m, 3)  (m+2)^2/m^2 * (c_in/groups)*c_out per output pixel, at
                    stride 1 with k = 3, for m in 2, 4, 6;
  stride-2 phases   a stride-2 3x3 conv splits into phase convs of 2x2,
                    2x1, 1x2 and 1x1 taps; the 2-tap axes run F(m, 2) at
                    (m+1)/m multiplies per output, so ((m+1)/m + 1)^2 per
                    output pixel, for m in 2, 4, 6.

Tiles are counted fractionally (h_out*w_out/m^2), so no tiling can count
fewer. Dense layers and 1x1 convs are direct. `bytes_min` reads the input
and the filter and writes the output once, in float32. A layer's least time
is the larger of flops_min over the bf16 peak and bytes_min over the HBM
bandwidth: float32 products run as several bf16 passes, so no
implementation beats it, and a share of it cannot pass 100%.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
TILES = (2, 4, 6)
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


def _out(size: int, stride: int) -> int:
    return -(-size // stride)


def conv_counts(layer: dict) -> dict[str, float]:
    """Multiply-adds of one image under each modelled algorithm."""
    k, s = layer["k"], layer["stride"]
    pixels = _out(layer["h"], s) * _out(layer["w"], s)
    pairs = layer["c_in"] // layer["groups"] * layer["c_out"]
    counts = {"direct": float(pixels * k * k * pairs)}
    if k == 3 and s == 1:
        for m in TILES:
            counts[f"F({m},3)"] = pixels * (m + 2) ** 2 / m ** 2 * pairs
    if k == 3 and s == 2:
        for m in TILES:
            counts[f"phase F({m},2)"] = (pixels * ((m + 1) / m + 1) ** 2
                                         * pairs)
    return counts


def layer_flops_min(layer: dict) -> float:
    """Fewest FLOPs (2 per multiply-add) of one image."""
    if layer["op"] == "dense":
        return 2.0 * layer["n_in"] * layer["n_out"]
    return 2.0 * min(conv_counts(layer).values())


def layer_bytes_min(layer: dict, batch: int) -> float:
    """Input and output of `batch` images and the filter, once each."""
    if layer["op"] == "dense":
        return ELEM_BYTES * (batch * (layer["n_in"] + layer["n_out"])
                             + layer["n_in"] * layer["n_out"])
    s = layer["stride"]
    x = layer["h"] * layer["w"] * layer["c_in"]
    y = _out(layer["h"], s) * _out(layer["w"], s) * layer["c_out"]
    w = layer["k"] ** 2 * layer["c_in"] // layer["groups"] * layer["c_out"]
    return ELEM_BYTES * (batch * (x + y) + w)


def least_time_s(layers: list[dict], batch: int, peak: dict,
                 ops: tuple[str, ...] = ("conv",)) -> float:
    """Sum over the layers of kind `ops` of each one's least time for one
    batch of `batch` images."""
    return sum(max(batch * layer_flops_min(l) / peak["bf16_flops"],
                   layer_bytes_min(l, batch) / peak["hbm_bytes_per_s"])
               for l in layers if l["op"] in ops)


def image_flops_min(layers: list[dict]) -> float:
    """Least FLOPs of one image through every conv and dense layer."""
    return sum(layer_flops_min(l) for l in layers)
