"""Required FLOPs against hand counts, written out layer by layer."""
import json
import os

import pytest

from benchmarks import layer_costs
from benchmarks.reference import layers_net

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def plan(name):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    return layers_net.plan(cfg["layers"], cfg["input_sample_shape"])


#: multiply-adds of one forward pass: out_y * out_x * kernels * ky*kx*C
CIFAR_MACS = [
    32 * 32 * 32 * (5 * 5 * 3),      # conv1  2,457,600
    16 * 16 * 32 * (5 * 5 * 32),     # conv2  6,553,600
    8 * 8 * 64 * (5 * 5 * 32),       # conv3  3,276,800
    10 * (4 * 4 * 64),               # softmax   10,240
]
#: (dense multiply-adds, share zero_filter keeps)
ALEXNET_MACS = [
    (55 * 55 * 96 * (11 * 11 * 3), 1.0),      # conv1 105,415,200
    (27 * 27 * 256 * (5 * 5 * 96), 0.5),      # conv2 447,897,600
    (13 * 13 * 384 * (3 * 3 * 256), 0.5),     # conv3 149,520,384
    (13 * 13 * 384 * (3 * 3 * 384), 1.0),     # conv4 224,280,576
    (13 * 13 * 256 * (3 * 3 * 384), 0.5),     # conv5 149,520,384
    (4096 * (6 * 6 * 256), 0.5),              # fc6    37,748,736
    (4096 * 4096, 1.0),                       # fc7    16,777,216
    (1000 * 4096, 1.0),                       # fc8     4,096,000
]


def test_cifar_caffe_forward_macs():
    assert sum(CIFAR_MACS) == 12298240
    assert layer_costs.forward_macs_per_image(plan("cifar_caffe")) == \
        pytest.approx(12298240)


def test_alexnet_forward_macs_dense_and_masked():
    dense = sum(m for m, _ in ALEXNET_MACS)
    masked = sum(m * s for m, s in ALEXNET_MACS)
    assert dense == 1135256096
    assert masked == 742912544
    net = plan("alexnet")
    assert layer_costs.forward_macs_per_image(net, masked=False) == \
        pytest.approx(dense)
    assert layer_costs.forward_macs_per_image(net) == pytest.approx(masked)


@pytest.mark.parametrize("name,macs", [
    ("cifar_caffe", [float(m) for m in CIFAR_MACS]),
    ("alexnet", [m * s for m, s in ALEXNET_MACS])])
def test_train_flops_leave_out_the_first_layers_input_gradient(name, macs):
    # forward + dW for every layer, dX for every layer but the first
    want = 2 * (3 * sum(macs) - macs[0])
    assert layer_costs.train_flops_per_image(plan(name)) == \
        pytest.approx(want)


def test_alexnet_widths_are_the_sources():
    net = plan("alexnet")
    assert net[0]["in_shape"] == (227, 227, 3)
    assert net[-1]["out_shape"] == (1000,)
    assert [e["w_shape"][0] for e in net if e["kind"] in ("conv", "fc")] \
        == [96, 256, 384, 384, 256, 4096, 4096, 1000]


def test_least_seconds_bound_by_bytes_for_small_channels():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    total, by_bytes = layer_costs.least_seconds(plan("cifar_caffe"), 16384,
                                                peaks)
    assert total > 0 and by_bytes > 0.5
    total_a, by_bytes_a = layer_costs.least_seconds(plan("alexnet"), 1024,
                                                    peaks)
    assert by_bytes_a < by_bytes


def test_unknown_layer_kind_is_an_error():
    with pytest.raises(KeyError):
        layer_costs.module_for("attention")
