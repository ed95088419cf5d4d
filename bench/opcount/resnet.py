"""The work counter of a ResNet configuration file (its ``opcount`` key):
the convolutions of one image, layer by layer, and the model's FLOPs per
image, as the CNN runner asks of every CNN configuration's counter."""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.opcount.conv2d import conv2d
from bench.opcount.matmul import matmul


def convs(config: Dict) -> List[Tuple[int, int, int, int, int, int]]:
    """``(h, w, cin, cout, k, stride)`` of every convolution of one image,
    in order: the stem, then each basic block's projection (where the
    shape changes) and two 3x3 convolutions."""
    st = config["stem"]
    s = config["image_size"]
    out = [(s, s, config["image_channels"], st["channels"], st["conv"],
            st["stride"])]
    s = -(-s // st["stride"])
    s = -(-s // st["maxpool_stride"])
    cin = st["channels"]
    for stage, (reps, cout) in enumerate(zip(config["stage_blocks"],
                                             config["stage_channels"])):
        for r in range(reps):
            stride = 2 if (r == 0 and stage > 0) else 1
            if stride != 1 or cin != cout:
                out.append((s, s, cin, cout, 1, stride))
            out.append((s, s, cin, cout, 3, stride))
            s = -(-s // stride)
            out.append((s, s, cout, cout, 3, 1))
            cin = cout
    return out


def head_matmul(config: Dict) -> Tuple[int, int]:
    return config["stage_channels"][-1], config["num_classes"]


def flops_per_image(config: Dict) -> float:
    f = sum(conv2d(1, *c)[0] for c in convs(config))
    return f + matmul(1, *head_matmul(config))[0]
