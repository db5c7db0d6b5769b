"""Dense-equivalent work of the benchmark's configurations, from shapes."""
from __future__ import annotations

import pytest

from chipbench import cnn, harness, peaks

REPO = harness.ROOT


def config(name):
    return cnn.load_config(REPO / f"chipbench/configs/{name}.json")


# (conv MACs, dense MACs, parameters) per image, worked out by hand:
# VGG-19 at 96x96: 64*3*9*96^2 + 339,738,624 x 8 + 169,869,312 x 3
#   + 84,934,656 x 4 conv; 4608*4096 + 4096*4096 + 4096*1000 head.
# AlexNet at 224x224: 64*3*121*55^2 + 192*64*25*27^2 + 384*192*9*13^2
#   + 256*384*9*13^2 + 256*256*9*13^2 conv; 9216*4096 + 4096^2 + 4096*1000.
WORK = {
    "vgg19_96": (3_583_180_800, 39_747_584, 59_766_464),
    "alexnet_224": (655_566_528, 58_621_952, 61_090_496),
}


@pytest.mark.parametrize("name", sorted(WORK))
def test_macs_and_params(name):
    cfg = config(name)
    conv, dense, n_params = WORK[name]
    assert cnn.macs_per_image(cfg, "conv") == conv
    assert cnn.macs_per_image(cfg, "dense") == dense
    assert cnn.macs_per_image(cfg) == conv + dense
    assert cnn.n_params(cfg) == n_params


def test_rounded_work_per_image():
    # about 7.2 and 1.4 GFLOP per image, 240 and 244 MB of f32 weights
    vgg, alex = config("vgg19_96"), config("alexnet_224")
    assert round(2 * cnn.macs_per_image(vgg) / 1e9, 1) == 7.2
    assert round(2 * cnn.macs_per_image(alex) / 1e9, 1) == 1.4
    assert round(4 * cnn.n_params(vgg) / 1e6) == 239
    assert round(4 * cnn.n_params(alex) / 1e6) == 244


def test_bytes_count_input_weights_and_unit_output():
    layers = cnn.layer_shapes(config("vgg19_96"))
    conv1, conv2 = layers[0], layers[1]
    # conv1: 3x96x96 in, 64x96x96 out, 64x3x3x3 weights, batch 8
    assert conv1.bytes(8) == 4 * (8 * (3 * 96 * 96 + 64 * 96 * 96) + 64 * 3 * 9)
    # conv2 ends its stage: what leaves the unit is the pooled 64x48x48
    assert conv2.out_shape == (64, 48, 48)
    assert conv2.bytes(1) == 4 * (64 * 96 * 96 + 64 * 48 * 48 + 64 * 64 * 9)
    head = [lyr for lyr in layers if lyr.op == "dense"]
    assert [lyr.weight_shape for lyr in head] == [(4608, 4096), (4096, 4096),
                                                  (4096, 1000)]


def test_roofline_is_the_larger_bound_per_layer():
    cfg = config("alexnet_224")
    pk = peaks.peaks("TPU v5 lite")
    want = sum(max(2 * lyr.macs * 8 / 197e12, lyr.bytes(8) / 819e9)
               for lyr in cnn.layer_shapes(cfg) if lyr.op == "conv")
    assert cnn.roofline_s(cfg, 8, pk) == pytest.approx(want, rel=1e-12)
    assert cnn.roofline_s(cfg, 8, pk) < cnn.roofline_s(cfg, 16, pk)


def test_peaks_table_refuses_an_unknown_kind():
    assert peaks.peaks("TPU v5 lite") == {"flops": 197e12,
                                          "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_configurations_build_the_systems_graphs():
    from repro.configs.alexnet import ALEXNET
    from repro.configs.vgg19_sparse import CNNConfig, vgg19_graph

    alex = harness.layer_graph(config("alexnet_224"))
    assert alex.signature() == ALEXNET.signature()
    vgg = harness.layer_graph(config("vgg19_96"))
    ref = vgg19_graph(CNNConfig(img_size=96))
    assert [u.conv for u in vgg.units()] == [u.conv for u in ref.units()]
    assert [u.out_shape for u in vgg.units()] == [u.out_shape for u in ref.units()]
    assert [d.d_out for d in vgg.head()] == [4096, 4096, 1000]
    assert vgg.flat_dim() == 4608


@pytest.mark.parametrize("bad", [
    [{"op": "conv", "out": 4, "k": 3}, {"op": "pool", "p": 3, "stride": 2}],
    [{"op": "conv", "out": 4, "k": 3}, {"op": "dense", "out": 2}],
    [{"op": "relu"}],
])
def test_layer_lists_that_do_not_describe_a_cnn_are_refused(bad):
    cfg = {"in_channels": 1, "image_size": 8,
           "layers": bad + [{"op": "flatten"}, {"op": "dense", "out": 2}]}
    with pytest.raises(ValueError):
        cnn.layer_shapes(cfg)
