"""bench/flops.py: the least work of a layer is a lower bound on every
algorithm the benchmark models, for every filter shape and padding, and
peaks come only from the table."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import flops, manifest  # noqa: E402
from bench.reference import mobilenet_v2, ops, vgg  # noqa: E402

NETS = {"vgg16_224": vgg, "mbv2_224": mobilenet_v2}

#: every distinct conv shape of Inception-v3 at 299 (Szegedy et al.,
#: arXiv:1512.00567, Table 1 and Figures 5-7), counted by hand from the
#: network: (input side, c_in, c_out, kh, kw, stride, padding, layers)
INCEPTION_V3_299 = [
    (299, 3, 32, 3, 3, 2, "VALID", 1), (149, 32, 32, 3, 3, 1, "VALID", 1),
    (147, 32, 64, 3, 3, 1, "SAME", 1), (73, 64, 80, 1, 1, 1, "VALID", 1),
    (73, 80, 192, 3, 3, 1, "VALID", 1),
    # three 35 x 35 blocks (5x5 branches), then reduction A
    (35, 192, 64, 1, 1, 1, "SAME", 2), (35, 64, 96, 3, 3, 1, "SAME", 4),
    (35, 96, 96, 3, 3, 1, "SAME", 3), (35, 192, 48, 1, 1, 1, "SAME", 1),
    (35, 48, 64, 5, 5, 1, "SAME", 3), (35, 192, 32, 1, 1, 1, "SAME", 1),
    (35, 256, 64, 1, 1, 1, "SAME", 3), (35, 256, 48, 1, 1, 1, "SAME", 1),
    (35, 288, 64, 1, 1, 1, "SAME", 4), (35, 288, 48, 1, 1, 1, "SAME", 1),
    (35, 288, 384, 3, 3, 2, "VALID", 1), (35, 96, 96, 3, 3, 2, "VALID", 1),
    # four 17 x 17 blocks (1x7 and 7x1 factorised), then reduction B
    (17, 768, 192, 1, 1, 1, "SAME", 12), (17, 128, 128, 1, 7, 1, "SAME", 2),
    (17, 128, 192, 1, 7, 1, "SAME", 1), (17, 768, 128, 1, 1, 1, "SAME", 2),
    (17, 128, 192, 7, 1, 1, "SAME", 1), (17, 128, 128, 7, 1, 1, "SAME", 2),
    (17, 160, 160, 1, 7, 1, "SAME", 4), (17, 160, 192, 1, 7, 1, "SAME", 2),
    (17, 768, 160, 1, 1, 1, "SAME", 4), (17, 160, 192, 7, 1, 1, "SAME", 2),
    (17, 160, 160, 7, 1, 1, "SAME", 4), (17, 192, 192, 1, 7, 1, "SAME", 4),
    (17, 192, 192, 7, 1, 1, "SAME", 4), (17, 192, 320, 3, 3, 2, "VALID", 1),
    (17, 192, 192, 3, 3, 2, "VALID", 1),
    # two 8 x 8 blocks (1x3 and 3x1 expanded)
    (8, 1280, 320, 1, 1, 1, "SAME", 1), (8, 384, 384, 1, 3, 1, "SAME", 4),
    (8, 1280, 384, 1, 1, 1, "SAME", 1), (8, 384, 384, 3, 1, 1, "SAME", 4),
    (8, 448, 384, 3, 3, 1, "SAME", 2), (8, 1280, 448, 1, 1, 1, "SAME", 1),
    (8, 1280, 192, 1, 1, 1, "SAME", 1), (8, 2048, 320, 1, 1, 1, "SAME", 1),
    (8, 2048, 384, 1, 1, 1, "SAME", 1), (8, 2048, 448, 1, 1, 1, "SAME", 1),
    (8, 2048, 192, 1, 1, 1, "SAME", 1),
]
#: algorithms modelled per (kh, kw, stride): direct, the Winograd or
#: Cook-Toom tiles with at most 8 points, the stride-2 phases
FORMS = {(1, 1, 1): 1, (3, 3, 1): 4, (3, 3, 2): 4, (5, 5, 1): 3,
         (1, 7, 1): 2, (7, 1, 1): 2, (1, 3, 1): 4, (3, 1, 1): 4}


def inception_v3_layers() -> list[dict]:
    return [ops.conv_layer(f"s{i}", h, h, ci, co, kh, kw, s,
                           padding=pad)
            for i, (h, ci, co, kh, kw, s, pad, _) in
            enumerate(INCEPTION_V3_299)] + [ops.dense_layer("fc", 2048,
                                                            1000)]


LAYERS = {"vgg16_224": lambda: vgg.layers(manifest.config("vgg16_224")),
          "mbv2_224": lambda: mobilenet_v2.layers(
              manifest.config("mbv2_224")),
          "inception_v3_299": inception_v3_layers}


@pytest.mark.parametrize("config", sorted(LAYERS))
def test_flops_min_bounds_every_modelled_algorithm(config):
    layers = LAYERS[config]()
    convs = [l for l in layers if l["op"] == "conv"]
    assert convs
    for layer in convs:
        counts = flops.conv_counts(layer)
        assert len(counts) == FORMS[layer["kh"], layer["kw"],
                                    layer["stride"]], layer
        for name, macs in counts.items():
            assert flops.layer_flops_min(layer) <= 2 * macs + 1e-6, name
        assert flops.layer_bytes_min(layer, 8) > flops.layer_bytes_min(
            layer, 1) > 0


def test_inception_v3_shapes_are_the_published_network():
    """94 convs, 5.71 G multiply-adds direct per image (published: 5.7 G),
    every one counted."""
    assert sum(n for *_, n in INCEPTION_V3_299) == 94
    direct = sum(n * flops.conv_counts(l)["direct"] for l, (*_, n) in
                 zip(inception_v3_layers(), INCEPTION_V3_299))
    assert direct == 5_711_168_096
    least = flops.image_flops_min(inception_v3_layers())
    assert 0 < least < 2 * direct


@pytest.mark.parametrize("layer,counts,bytes1", [
    # 1x7 at 17 x 17 (mixed 6a-e): 7 taps direct, 8/2 by F(2,7) 1-D
    (ops.conv_layer("m4_1x7a", 17, 17, 128, 128, 1, 7),
     {"direct": 289 * 7 * 16384, "F(2,7) 1-D": 289 * 4 * 16384},
     4 * (2 * 289 * 128 + 7 * 128 * 128)),
    # 7x1 at 17 x 17
    (ops.conv_layer("m4_7x1a", 17, 17, 128, 192, 7, 1),
     {"direct": 289 * 7 * 24576, "F(2,7) 1-D": 289 * 4 * 24576},
     4 * (289 * (128 + 192) + 7 * 128 * 192)),
    # 1x3 at 8 x 8 (mixed 7b-c): F(2,3), F(4,3), F(6,3) 1-D
    (ops.conv_layer("m8_1x3a", 8, 8, 384, 384, 1, 3),
     {"direct": 64 * 3 * 147456, "F(2,3) 1-D": 64 * 2 * 147456,
      "F(4,3) 1-D": 64 * 1.5 * 147456, "F(6,3) 1-D": 64 * 8 / 6 * 147456},
     4 * (2 * 64 * 384 + 3 * 384 * 384)),
    # 5x5 at 35 x 35 (mixed 5b-d): F(2,5) and F(4,5); F(6,5) has 10 points
    (ops.conv_layer("m1_5x5", 35, 35, 48, 64, 5, 5),
     {"direct": 1225 * 25 * 3072, "F(2,5)": 1225 * 9 * 3072,
      "F(4,5)": 1225 * 4 * 3072},
     4 * (1225 * (48 + 64) + 25 * 48 * 64)),
    # 3x3 stride 2 VALID, the stem: 299 -> 149
    (ops.conv_layer("conv1", 299, 299, 3, 32, 3, 3, 2, padding="VALID"),
     {"direct": 22201 * 9 * 96, "phase F(2,2)": 22201 * 2.5 ** 2 * 96,
      "phase F(4,2)": 22201 * 2.25 ** 2 * 96,
      "phase F(6,2)": 22201 * (13 / 6) ** 2 * 96},
     4 * (299 * 299 * 3 + 22201 * 32 + 9 * 3 * 32)),
    # 3x3 stride 1 VALID: 149 -> 147
    (ops.conv_layer("conv2", 149, 149, 32, 32, 3, 3, padding="VALID"),
     {"direct": 21609 * 9 * 1024, "F(2,3)": 21609 * 4 * 1024,
      "F(4,3)": 21609 * 36 / 16 * 1024, "F(6,3)": 21609 * 64 / 36 * 1024},
     4 * (149 * 149 * 32 + 21609 * 32 + 9 * 32 * 32)),
], ids=["1x7", "7x1", "1x3", "5x5", "3x3_s2_valid", "3x3_valid"])
def test_hand_counted_layers_at_299(layer, counts, bytes1):
    got = flops.conv_counts(layer)
    assert got == {k: pytest.approx(v, rel=1e-12) for k, v in counts.items()}
    assert flops.layer_flops_min(layer) == pytest.approx(
        2 * min(counts.values()), rel=1e-12)
    assert flops.layer_bytes_min(layer, 1) == bytes1


@pytest.mark.parametrize("config,image_flops,least_8", [
    ("vgg16_224", 6310133760.0, 0.0009560218412698413),
    ("mbv2_224", 560729148.4444444, 0.0005359805421245421),
])
def test_existing_configs_count_as_before(config, image_flops, least_8):
    """The counts behind conv_roofline and mfu, to the last digit, as the
    square-filter counts gave them."""
    layers = LAYERS[config]()
    assert flops.image_flops_min(layers) == image_flops
    assert flops.least_time_s(layers, 8, flops.peaks("TPU v5 lite")) == \
        least_8


def test_vgg16_direct_work_matches_the_published_count():
    """VGG-16 at 224 needs 15.5 G multiply-adds direct; the least count
    is F(6,3)'s on the 3x3 convs, 64/36 instead of 9 per output."""
    layers = vgg.layers(manifest.config("vgg16_224"))
    direct = sum(flops.conv_counts(l)["direct"] for l in layers
                 if l["op"] == "conv")
    dense = sum(l["n_in"] * l["n_out"] for l in layers if l["op"] == "dense")
    assert 15.3e9 < direct + dense < 15.6e9
    least = flops.image_flops_min(layers)
    assert least == pytest.approx(2 * (direct * 64 / 36 / 9 + dense))


def test_least_time_is_bounded_by_compute_or_bandwidth():
    layer = vgg.layers(manifest.config("vgg16_224"))[0]
    peak = flops.peaks("TPU v5 lite")
    t = flops.least_time_s([layer], 8, peak)
    assert t == max(8 * flops.layer_flops_min(layer) / peak["bf16_flops"],
                    flops.layer_bytes_min(layer, 8)
                    / peak["hbm_bytes_per_s"])


def test_node_least_time_leaves_out_what_passes_inside_a_fused_node():
    """A node of one layer takes that layer's least time; MobileNet-v2's
    fused inverted residual `ir3` counts its input, output and filters once
    and every FLOP of its three layers, and no more than the layers apart."""
    peak = flops.peaks("TPU v5 lite")
    layers = mobilenet_v2.layers(manifest.config("mbv2_224"))
    by_node = flops.least_time_by_node(layers, {8: 3, 2: 1}, peak)
    assert set(by_node) == {l["node"] for l in layers}
    conv1 = layers[0]
    assert by_node["conv1"] == 3 * flops.layer_time_s(conv1, 8, peak) + \
        flops.layer_time_s(conv1, 2, peak)
    ir3 = [l for l in layers if l["node"] == "ir3"]
    assert len(ir3) == 3
    x, y = ir3[0]["h"] ** 2 * ir3[0]["c_in"], ir3[2]["h"] ** 2 * 24
    w = sum(l["kh"] * l["kw"] * l["c_in"] // l["groups"] * l["c_out"]
            for l in ir3)
    want = max(8 * sum(flops.layer_flops_min(l) for l in ir3)
               / peak["bf16_flops"],
               4 * (8 * (x + y) + w) / peak["hbm_bytes_per_s"])
    assert flops.node_time_s(ir3, 8, peak) == pytest.approx(want, rel=1e-12)
    assert want < sum(flops.layer_time_s(l, 8, peak) for l in ir3)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
