"""bench/flops.py: the least work of a layer is a lower bound on every
algorithm the benchmark models, and peaks come only from the table."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import flops, manifest  # noqa: E402
from bench.reference import mobilenet_v2, vgg  # noqa: E402

NETS = {"vgg16_224": vgg, "mbv2_224": mobilenet_v2}


@pytest.mark.parametrize("config", sorted(NETS))
def test_flops_min_bounds_every_modelled_algorithm(config):
    layers = NETS[config].layers(manifest.config(config))
    convs = [l for l in layers if l["op"] == "conv"]
    assert convs
    for layer in convs:
        counts = flops.conv_counts(layer)
        assert layer["k"] != 3 or len(counts) == 4, layer
        for name, macs in counts.items():
            assert flops.layer_flops_min(layer) <= 2 * macs + 1e-6, name
        assert flops.layer_bytes_min(layer, 8) > flops.layer_bytes_min(
            layer, 1) > 0


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


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
