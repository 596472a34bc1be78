"""FLOP counts from shapes, against hand counts."""

import importlib.util
import os

from conftest import BENCH_DIR


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH_DIR, "flops", name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_one_conv_by_hand():
    # 3x3 conv, 64 -> 128 channels, stride 2 on 32x32: 16*16 outputs,
    # each 3*3*64 multiply-adds per output channel.
    assert _load("resnet_gn").conv_macs(32, 32, 3, 3, 64, 128, 2) == (
        16 * 16 * 9 * 64 * 128
    )


def test_one_dense_by_hand():
    assert _load("resnet_gn").dense_macs(512, 10) == 5120


def test_resnet18_and_34_cifar():
    flops = _load("resnet_gn")
    model = {"width": 64, "stage_sizes": [2, 2, 2, 2], "num_classes": 10}
    # Stem 1.77 M; stage 1: 4 convs x 37.75 M; stages 2-4: a stride-2
    # entry (3x3 + 1x1 shortcut) and three more 3x3, 9.44 M x (2+.111+..)
    by_hand = (
        32 * 32 * 27 * 64
        + 4 * 32 * 32 * 9 * 64 * 64
        + sum(
            hw * hw * 9 * c // 2 * c + hw * hw * c // 2 * c
            + 3 * hw * hw * 9 * c * c
            for hw, c in ((16, 128), (8, 256), (4, 512))
        )
        + 5120
    )
    assert flops.forward_macs(model, (32, 32, 3)) == by_hand == 555_422_720
    assert flops.train_flops_per_sample(model, (32, 32, 3)) == 6 * by_hand
    model34 = dict(model, stage_sizes=[3, 4, 6, 3])
    assert flops.forward_macs(model34, (32, 32, 3)) == 1_159_402_496


def test_cnn_tpu():
    flops = _load("cnn_tpu")
    by_hand = 8 * 8 * 48 * 128 + 8 * 8 * 9 * 128 * 128 + 4 * 4 * 9 * 128 * 256 + 2560
    assert flops.forward_macs({"width": 128, "num_classes": 10}, (32, 32, 3)) == by_hand
    assert by_hand == 14_551_552
