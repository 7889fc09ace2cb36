import json
import os

import pytest

from bench import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def spec(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_stablelm_model_flops_per_trained_token():
    """stablelm-3b at S=512, frozen backbone: 4 FLOPs per matmul weight
    (2 forward, 2 for the activation gradient) over 2.667 B weights
    (32 layers x 79.3 M: q, k, v, o 4 x 2560^2 = 26.2 M and gate, up,
    down 3 x 2560 x 6912 = 53.1 M; plus the 2560 x 50304 = 0.129 B head)
    = 10.67 GFLOP; causal attention 3 x 4 x 2560 x 256.5 x 32 layers =
    0.25 GFLOP; about 10.9 GFLOP in all. A rank-64 adapter adds
    6 x 64 x 48,896 x 32 = 0.60 GFLOP (the seven projections' d_in + d_out
    sum to 48,896)."""
    s = spec("stablelm-3b")
    assert flops.matmul_params(s) == 32 * 79_298_560 + 2560 * 50304
    backbone = flops.backbone_train_flops_per_token(s, 512)
    assert backbone == pytest.approx(10.92e9, rel=0.01)
    assert flops.lora_widths(s) == 48_896
    assert flops.lora_train_flops_per_token(s, 64) == pytest.approx(
        0.601e9, rel=0.01)
    assert flops.train_flops_per_token(s, 512, 64) == pytest.approx(
        11.5e9, rel=0.01)


def test_granite_stage_counts_gqa_widths():
    s = spec("granite-8b-l12")
    per_layer = 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 3 * 4096 * 14336
    assert flops.matmul_params(s) == 12 * per_layer + 4096 * 49152


def test_peak_is_keyed_by_device_kind():
    assert flops.peak("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")
