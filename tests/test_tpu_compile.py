"""Compile rehearsal: the main path's Pallas kernels compiled for a
described TPU v5e chip at real widths (llama3.2-1b: H=32, KV=8, D=64,
block 16; ResNet-34 at 224 px, the MobileNetV1 stem and LeNet-5's conv1,
batch 8; bf16).

Nothing runs: each test lowers one kernel through the TPU compiler
(Mosaic) with ``interpret=False`` and checks that a TPU custom call came
out, which is what catches block shapes and loads the chip would refuse.
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops

B, H, KV, D, BS = 8, 32, 8, 64, 16          # llama3.2-1b serving widths
NBLK = 1024 // BS                           # block table of a 1024-token slot
NB = 1 + B * NBLK                           # full pool + trash block
BF = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sq", [1, 8])
def test_paged_decode_attention(one_chip, sq):
    """The engine's decode tick (Sq=1) and chunked catch-up cell (Sq=8)."""
    _compile(one_chip,
             lambda q, k, v, bt, ln, qp: kops.paged_decode_attention(
                 q, k, v, bt, ln, qpos=qp),
             ((B, sq, H, D), BF), ((NB, BS, KV, D), BF), ((NB, BS, KV, D), BF),
             ((B, NBLK), I32), ((B,), I32), ((B, sq), I32))


@pytest.mark.parametrize("tile", [(8, 1024), (256, 512)])
def test_flash_attention_with_positions(one_chip, tile):
    """The engine's left-padded bucketed prefill."""
    S = 512
    _compile(one_chip,
             lambda q, k, v, p: kops.flash_attention(q, k, v, p, tile=tile),
             ((B, S, H, D), BF), ((B, S, KV, D), BF), ((B, S, KV, D), BF),
             ((B, S), I32))


def test_rolling_decode_attention(one_chip):
    """``CompiledModel.generate``'s rolling-cache decode."""
    C = 1024
    _compile(one_chip,
             lambda q, k, v, p, qp: kops.decode_attention(q, k, v, p, qp),
             ((B, 1, H, D), BF), ((B, C, KV, D), BF), ((B, C, KV, D), BF),
             ((B, C), I32), ((B, 1), I32))


@pytest.mark.parametrize("m,tile", [(8, (8, 2048, 512)),
                                    (4096, (256, 512, 256))])
@pytest.mark.parametrize("glu", [False, True])
def test_matmul_fused(one_chip, m, tile, glu):
    d, ff = 2048, 8192
    if glu:
        _compile(one_chip,
                 lambda x, w, w2: kops.matmul_fused(x, w, w2=w2, act="silu",
                                                    tile=tile),
                 ((m, d), BF), ((d, ff), BF), ((d, ff), BF))
    else:
        _compile(one_chip, lambda x, w: kops.matmul_fused(x, w, tile=tile),
                 ((m, d), BF), ((d, d), BF))


def test_copy_block(one_chip):
    """The prefix cache's copy-on-write fork over a folded (16-layer) pool."""
    _compile(one_chip, lambda p, s, d: kops.copy_block(p, s, d),
             ((16, NB, BS, KV, D), BF), ((), I32), ((), I32))


@pytest.mark.parametrize("hw,ci,co,k,stride", [
    (224, 3, 64, 7, 2),       # stem: 3 lane-sparse channels, 7x7 stride 2
    (56, 64, 64, 3, 1),       # stage 1
    (56, 64, 128, 3, 2),      # stage 2 entry
    (56, 64, 128, 1, 2),      # stage 2 projection shortcut
    (7, 512, 512, 3, 1),      # stage 4: 7 output columns
    (224, 3, 32, 3, 2),       # MobileNetV1 stem: folded, K = 27
    (32, 1, 6, 5, 1),         # LeNet-5 conv1 (VALID): folded, K = 25
])
def test_conv2d_resnet34(one_chip, hw, ci, co, k, stride):
    f32 = jnp.float32
    padding = "VALID" if (hw, ci) == (32, 1) else "SAME"

    def conv(x, w, *bn):
        return kops.conv2d_fused(x, w, stride=stride, padding=padding, bn=bn,
                                 act="relu", tile=(8, 128))
    _compile(one_chip, conv, ((B, hw, hw, ci), BF), ((k, k, ci, co), BF),
             ((co,), f32), ((co,), f32), ((co,), f32), ((co,), f32))
