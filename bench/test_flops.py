"""Operation counts from shapes, and the table of peaks."""

import numpy as np
import pytest

from bench import flops


def _fc(k, n):
    return dict(kind="fc", weights=np.zeros((k, n), np.float32))


def _conv(hw, cin, cout, stride=2, k=3):
    return dict(kind="conv", weights=np.zeros((k, k, cin, cout), np.float32),
                in_hw=hw, stride=stride)


def test_fc_flops_by_hand():
    # 2 ops (multiply, add) x 4 steps x 3 inputs x 5 neurons
    assert flops.request_flops([_fc(3, 5)], steps=4) == 120
    assert flops.request_flops([_fc(3, 5), _fc(5, 2)], steps=1) == 30 + 20


def test_conv_flops_by_hand():
    # an 8x8 frame at stride 2 gives 4x4 outputs; each of the 4*4*6 outputs
    # takes 3*3*2 multiply-adds, at 2 ops each, for each of 3 steps
    assert flops.request_flops([_conv((8, 8), 2, 6)], steps=3) == \
        2 * 3 * (4 * 4 * 6) * (3 * 3 * 2)


def test_s5_request_flops():
    layers = [_fc(512, 1536), _fc(1536, 1536), _fc(1536, 1536),
              _fc(1536, 512)]
    assert flops.request_flops(layers, steps=128) == 2 * 128 * 6291456


def test_peak_of_v5e():
    assert flops.peak("TPU v5 lite") == 197e12
    assert flops.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        flops.peak("cpu")
