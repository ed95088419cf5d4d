"""MobileNetV1 (width 1.0, 224 px) — the paper's folded-mode network.  By
Table 2 of the MobileNets paper, the 1x1 convs take 94.86% of the
multiply-adds (the parameterized-kernel workhorse) and the 3x3 depthwise
convs 3.06%.  [arXiv:1704.04861]"""
import dataclasses

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="mobilenetv1", family="cnn", n_layers=14, d_model=1024, d_ff=1024,
    vocab_size=1000, image_size=224, image_channels=3,
)

SMOKE = dataclasses.replace(CONFIG, image_size=64, vocab_size=16)
