"""The FLOP functions reproduce PERF.md's figures from shapes: 547
MFLOP/token for the BERT-base train step and 24.5 GFLOP/image for the
ResNet-50 train step (both within 4% of XLA's cost_analysis)."""
import json
import os

import pytest

from benchmark.harness import cells
from benchmark.harness.datasets import field_shapes


def _sizes(name):
    with open(os.path.join(cells.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_flops_per_token():
    cfg = cells.load_module("configs", "bert-base")
    shapes = field_shapes(cells.load_json("traffic", "mlm-s128-b256"))
    per_token = cfg.flops_per_sample(_sizes("bert-base"), shapes) / 128
    assert per_token == pytest.approx(547e6, rel=0.05)


def test_resnet_flops_per_image():
    cfg = cells.load_module("configs", "resnet50")
    shapes = field_shapes(cells.load_json("traffic", "imagenet-b256"))
    sizes = _sizes("resnet50")
    assert cfg.flops_per_sample(sizes, shapes) == pytest.approx(24.5e9,
                                                                rel=0.05)
    # torchvision publishes 4.09 GMAC for the forward pass
    assert cfg.forward_macs(sizes, 224) == pytest.approx(4.09e9, rel=0.01)
