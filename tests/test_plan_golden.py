"""Golden plan snapshots: the pass pipeline's output for the paper's three
CNNs plus an LM config, base vs optimized flows.  These pin the plan-level
behaviour of the whole pipeline (units, tiles, mode) — any pass change that
shifts them must update the goldens deliberately."""
import pytest

from repro.configs import get_config, get_smoke
from repro.configs.base import FlowConfig, ShapeConfig
from repro.core.plan import build_plan

SERVE = ShapeConfig("bench", "prefill", 64, 8)
SMOKE_TRAIN = ShapeConfig("smoke", "train", 16, 2)

BASE_TILES = ("{'matmul': (128, 128, 128), 'attention': (128, 128), "
              "'decode_attention': 512, 'conv2d': (8, 128), "
              "'depthwise_conv2d': (8, 128), 'wkv_chunk': 16, "
              "'ce_chunk': 256}")

# KernelRegistry resolution on the CPU test platform: "auto" resolves every
# accelerable op to the reference backend (Pallas is chosen on TPU only)
KERNELS = ("  kernels: backend=auto attention=ref conv2d=ref "
           "copy_block=ref decode_attention=ref depthwise_conv2d=ref "
           "glu_matmul=ref matmul=ref paged_decode_attention=ref rg_lru=ref")

GOLDEN = {
    ("lenet5", "opt"): f"""\
plan[lenet5 x bench] mode=pipelined
  passes: fuse=True fold=True tiles=True cw=True prec=bf16
  units: 3 (0 folded: )
  tiles: {{'matmul': (64, 120, 84), 'conv2d': (8, 128), 'depthwise_conv2d': (128, 512), 'wkv_chunk': 32, 'ce_chunk': 256}}
{KERNELS}""",
    ("lenet5", "base"): f"""\
plan[lenet5 x bench] mode=folded
  passes: fuse=False fold=False tiles=False cw=False prec=fp32
  units: 3 (0 folded: )
  tiles: {BASE_TILES}
{KERNELS}""",
    ("mobilenetv1", "opt"): f"""\
plan[mobilenetv1 x bench] mode=pipelined
  passes: fuse=True fold=True tiles=True cw=True prec=bf16
  units: 15 (0 folded: )
  tiles: {{'matmul': (64, 1024, 512), 'conv2d': (8, 128), 'depthwise_conv2d': (128, 512), 'wkv_chunk': 32, 'ce_chunk': 256}}
{KERNELS}""",
    ("mobilenetv1", "base"): f"""\
plan[mobilenetv1 x bench] mode=folded
  passes: fuse=False fold=False tiles=False cw=False prec=fp32
  units: 15 (0 folded: )
  tiles: {BASE_TILES}
{KERNELS}""",
    ("resnet34", "opt"): f"""\
plan[resnet34 x bench] mode=pipelined
  passes: fuse=True fold=True tiles=True cw=True prec=bf16
  units: 18 (0 folded: )
  tiles: {{'matmul': (64, 512, 512), 'conv2d': (8, 128), 'depthwise_conv2d': (128, 512), 'wkv_chunk': 32, 'ce_chunk': 256}}
{KERNELS}""",
    ("resnet34", "base"): f"""\
plan[resnet34 x bench] mode=folded
  passes: fuse=False fold=False tiles=False cw=False prec=fp32
  units: 18 (0 folded: )
  tiles: {BASE_TILES}
{KERNELS}""",
}


@pytest.mark.parametrize("arch,variant", sorted(GOLDEN))
def test_cnn_plan_golden(arch, variant):
    flow = FlowConfig(mode="auto") if variant == "opt" else FlowConfig().base()
    plan = build_plan(get_config(arch), flow, SERVE)
    assert plan.describe() == GOLDEN[(arch, variant)]


def test_lm_plan_golden():
    plan = build_plan(get_smoke("llama3.2-1b"), FlowConfig(mode="folded"),
                      SMOKE_TRAIN)
    assert plan.describe() == f"""\
plan[llama3.2-1b x smoke] mode=folded
  passes: fuse=True fold=True tiles=True cw=True prec=bf16
  units: 3 (1 folded: 3x1)
  tiles: {{'matmul': (16, 64, 192), 'attention': (16, 16), 'decode_attention': 512, 'conv2d': (8, 128), 'depthwise_conv2d': (128, 512), 'wkv_chunk': 32, 'ce_chunk': 256}}
{KERNELS}"""


def test_lm_plan_golden_sharded():
    """The ShardingPass's decisions are part of the plan snapshot: the mesh
    factorization, axis roles, and param-spec census appear as the plan's
    sharding line."""
    plan = build_plan(
        get_smoke("llama3.2-1b"),
        FlowConfig(mode="folded", mesh_split=(("data", 2), ("model", 2))),
        SMOKE_TRAIN)
    assert plan.describe() == f"""\
plan[llama3.2-1b x smoke] mode=folded
  passes: fuse=True fold=True tiles=True cw=True prec=bf16
  units: 3 (1 folded: 3x1)
  tiles: {{'matmul': (16, 64, 192), 'attention': (16, 16), 'decode_attention': 512, 'conv2d': (8, 128), 'depthwise_conv2d': (128, 512), 'wkv_chunk': 32, 'ce_chunk': 256}}
  sharding: mesh={{data:2,model:2}} dp=data:2 tp=model:2 pp=- params[tp=7 fsdp=4 repl=0]
{KERNELS}"""


def test_describe_is_deterministic():
    args = (get_config("resnet34"), FlowConfig(mode="auto"), SERVE)
    assert build_plan(*args).describe(stats=True) == \
        build_plan(*args).describe(stats=True)


@pytest.mark.parametrize("arch,variant", sorted(GOLDEN))
def test_old_and_new_entry_points_identical(arch, variant):
    """Byte-identical plans through the deprecated build_plan shim and the
    repro.flow.compile facade (same golden snapshot)."""
    from repro import flow as rflow
    fl = FlowConfig(mode="auto") if variant == "opt" else FlowConfig().base()
    old = build_plan(get_config(arch), fl, SERVE)
    new = rflow.compile(get_config(arch), SERVE, fl)
    assert old.describe(stats=True) == new.plan.describe(stats=True)
    assert new.plan.describe() == GOLDEN[(arch, variant)]
