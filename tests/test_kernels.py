"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from conftest import relerr

R = np.random.RandomState(7)


def _tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("shape", [(16, 64, 32), (100, 130, 70),
                                   (256, 256, 256), (8, 512, 128)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_matmul_bias_act(shape, dt):
    M, K, N = shape
    x = jnp.asarray(R.randn(M, K), dt)
    w = jnp.asarray(R.randn(K, N), dt)
    b = jnp.asarray(R.randn(N), dt)
    y = ops.matmul_fused(x, w, bias=b, act="gelu", tile=(32, 64, 32),
                         interpret=True)
    r = ref.matmul_fused_ref(x, w, bias=b, act="gelu")
    assert relerr(y, r) < _tol(dt)


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_matmul_glu(dt):
    x = jnp.asarray(R.randn(64, 96), dt)
    w = jnp.asarray(R.randn(96, 48), dt)
    w2 = jnp.asarray(R.randn(96, 48), dt)
    y = ops.matmul_fused(x, w, w2=w2, act="silu", tile=(32, 32, 32),
                         interpret=True)
    r = ref.matmul_fused_ref(x, w, w2=w2, act="silu")
    assert relerr(y, r) < _tol(dt)


def test_matmul_base_no_cached_writes():
    """CW off: accumulate through the output block — still correct (fp32)."""
    x = jnp.asarray(R.randn(64, 256), jnp.float32)
    w = jnp.asarray(R.randn(256, 64), jnp.float32)
    y = ops.matmul_fused(x, w, tile=(32, 64, 32), vmem_accum=False,
                         interpret=True)
    assert relerr(y, ref.matmul_fused_ref(x, w)) < 1e-5


def test_matmul_leading_dims():
    x = jnp.asarray(R.randn(2, 10, 48), jnp.float32)
    w = jnp.asarray(R.randn(48, 32), jnp.float32)
    y = ops.matmul_fused(x, w, tile=(8, 16, 32), interpret=True)
    assert y.shape == (2, 10, 32)
    assert relerr(y, ref.matmul_fused_ref(x, w)) < 1e-5


@pytest.mark.parametrize("spec", [
    (2, 64, 64, 4, 4, 32, True, None, 0),
    (1, 48, 48, 4, 2, 16, True, 16, 0),
    (2, 32, 96, 6, 2, 32, True, None, 64),     # CP shard: q offset
    (1, 100, 100, 2, 1, 64, False, None, 0),   # bidirectional, ragged len
    (2, 128, 128, 8, 8, 64, True, 32, 0),
])
def test_flash_attention(spec):
    B, Sq, Skv, H, KV, D, causal, win, off = spec
    q = jnp.asarray(R.randn(B, Sq, H, D), jnp.float32)
    k = jnp.asarray(R.randn(B, Skv, KV, D), jnp.float32)
    v = jnp.asarray(R.randn(B, Skv, KV, D), jnp.float32)
    y = ops.flash_attention(q, k, v, causal=causal, window=win, q_offset=off,
                            tile=(32, 32), interpret=True)
    r = ref.flash_attention_ref(q, k, v, causal=causal, window=win,
                                q_offset=off)
    assert relerr(y, r) < 1e-5


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dt):
    q = jnp.asarray(R.randn(2, 64, 4, 32), dt)
    k = jnp.asarray(R.randn(2, 64, 2, 32), dt)
    v = jnp.asarray(R.randn(2, 64, 2, 32), dt)
    y = ops.flash_attention(q, k, v, tile=(32, 32), interpret=True)
    assert relerr(y, ref.flash_attention_ref(q, k, v)) < _tol(dt)


@pytest.mark.parametrize("spec", [(2, 64, 4, 2, 32, None),
                                  (1, 96, 8, 1, 64, 32),
                                  (3, 40, 4, 4, 16, None)])
def test_decode_attention_rolling(spec):
    B, C, H, KV, D, win = spec
    fill = C // 2
    kc = jnp.asarray(R.randn(B, C, KV, D), jnp.float32)
    vc = jnp.asarray(R.randn(B, C, KV, D), jnp.float32)
    pos = jnp.where(jnp.arange(C)[None] < fill, jnp.arange(C)[None], -1)
    pos = jnp.broadcast_to(pos, (B, C)).astype(jnp.int32)
    q = jnp.asarray(R.randn(B, 1, H, D), jnp.float32)
    qpos = jnp.full((B, 1), fill, jnp.int32)
    y = ops.decode_attention(q, kc, vc, pos, qpos, window=win, tile=32,
                             interpret=True)
    r = ref.decode_attention_ref(q, kc, vc, pos, qpos, window=win)
    assert relerr(y, r) < 1e-5


@pytest.mark.parametrize("spec", [(2, 4, 2, 32, 4, 5, None),
                                  (3, 8, 4, 16, 8, 3, 12),
                                  (1, 4, 1, 64, 16, 2, None),
                                  # llama3.2-1b head geometry: 8 KV heads
                                  # as lane slices of a (16, 512) block
                                  (2, 32, 8, 64, 16, 2, None)])
def test_paged_decode_attention(spec):
    """The serving subsystem's block-table gather kernel (scalar-prefetch
    index_map) vs the registered ref fallback, heterogeneous row lengths."""
    B, H, KV, D, bs, nblk, win = spec
    NB = 1 + B * nblk
    q = jnp.asarray(R.randn(B, 1, H, D), jnp.float32)
    kp = jnp.asarray(R.randn(NB, bs, KV, D), jnp.float32)
    vp = jnp.asarray(R.randn(NB, bs, KV, D), jnp.float32)
    bt = jnp.asarray(1 + R.permutation(B * nblk).reshape(B, nblk), jnp.int32)
    lens = jnp.asarray([(7 * (b + 1)) % (nblk * bs) for b in range(B)],
                       jnp.int32)
    y = ops.paged_decode_attention(q, kp, vp, bt, lens, window=win,
                                   interpret=True)
    r = ref.paged_decode_attention_ref(q, kp, vp, bt, lens, window=win,
                                       compute_dtype=jnp.float32)
    assert relerr(y, r) < 1e-5


@pytest.mark.parametrize("lead", [None, 3])
def test_copy_block_matches_ref(lead):
    """The prefix-cache COW fork: pallas (scalar-prefetch index_map, pool
    aliased in place) vs the ref fallback, flat and folded pool layouts —
    only the destination block changes, byte-for-byte."""
    NB, bs, KV, D = 6, 4, 2, 16
    shape = (NB, bs, KV, D) if lead is None else (lead, NB, bs, KV, D)
    pool = jnp.asarray(R.randn(*shape), jnp.float32)
    src, dst = 2, 5
    y = ops.copy_block(pool, src, dst, interpret=True)
    r = ref.copy_block_ref(pool, src, dst)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(r))
    got = np.asarray(y)
    want = np.asarray(pool).copy()
    want[..., dst, :, :, :] = want[..., src, :, :, :]
    np.testing.assert_array_equal(got, want)
    # dynamic (traced) indices under jit: the ledger calls it both ways
    yj = jax.jit(lambda p, s, d: ref.copy_block_ref(p, s, d))(
        pool, jnp.int32(src), jnp.int32(dst))
    np.testing.assert_array_equal(np.asarray(yj), want)


@pytest.mark.parametrize("spec", [(2, 16, 64), (1, 33, 130), (3, 8, 256)])
def test_lru_scan(spec):
    from repro.kernels.lru_scan import lru_scan, lru_scan_ref
    B, S, W = spec
    a = jnp.asarray(R.rand(B, S, W) * 0.9, jnp.float32)
    b = jnp.asarray(R.randn(B, S, W), jnp.float32)
    y = lru_scan(a, b, block_w=128, interpret=True)
    assert relerr(y, lru_scan_ref(a, b)) < 1e-5


@pytest.mark.parametrize("tile", [(4, 8), (3, 128), (16, 4), (5, 8)])
@pytest.mark.parametrize("stride,pad", [(1, "SAME"), (2, "SAME"),
                                        (2, "VALID")])
def test_conv2d_tile_tuple_regression(tile, stride, pad):
    """Regression for the dropped tile component: the wrapper used to keep
    only tile[1] (channel block) and discard tile[0] (row block).  Both
    components must now reach the kernel and stay correct for any pair,
    including row blocks that don't divide H_out (divisor fallback)."""
    N, H, W, CI, CO = 2, 12, 12, 6, 16
    x = jnp.asarray(R.randn(N, H, W, CI), jnp.float32)
    w = jnp.asarray(R.randn(3, 3, CI, CO), jnp.float32)
    y = ops.conv2d_fused(x, w, stride=stride, padding=pad, act="relu",
                         tile=tile, interpret=True)
    r = ref.conv2d_fused_ref(x, w, stride=stride, padding=pad, act="relu")
    assert relerr(y, r) < 1e-5


def test_conv2d_tile_tuple_forwards_both_components(monkeypatch):
    """The ops-layer wrapper must consume the full (block_h, block_c) tuple
    the tiling pass selected, not just the channel half."""
    from repro.kernels import conv2d as _cv
    captured = {}
    orig = _cv.conv2d_fused

    def spy(x, w, **kw):
        captured.update(kw)
        return orig(x, w, **kw)

    monkeypatch.setattr(_cv, "conv2d_fused", spy)
    x = jnp.asarray(R.randn(1, 8, 8, 4), jnp.float32)
    w = jnp.asarray(R.randn(3, 3, 4, 8), jnp.float32)
    ops.conv2d_fused(x, w, tile=(4, 8), interpret=True)
    assert captured["block_h"] == 4 and captured["block_c"] == 8
    ops.conv2d_fused(x, w, tile=64, interpret=True)     # bare int: block_c
    assert captured["block_h"] is None and captured["block_c"] == 64


# (N, H, W, CI, CO, k, stride, padding, bn[, tile]); with these small maps
# the lane-sparse cases take the folded path (``conv2d.folds``)
CONV_SPECS = [
    (2, 16, 16, 3, 8, 3, 1, "SAME", True),
    (1, 17, 17, 4, 16, 5, 2, "SAME", False),
    (2, 12, 12, 8, 8, 1, 1, "VALID", True),    # the MobileNet 1x1 workhorse
    (1, 16, 16, 3, 6, 3, 2, "VALID", False),
    (1, 23, 23, 3, 16, 7, 2, "SAME", True),     # ResNet stem: 7x7/2, CI=3
    (2, 7, 7, 16, 8, 3, 1, "SAME", True),      # 7 output columns (stage 4)
    (1, 14, 14, 8, 16, 1, 2, "SAME", True),    # strided 1x1 projection
    (2, 32, 32, 3, 32, 3, 2, "SAME", True),    # MobileNetV1 stem: 3x3/2, CI 3
    (1, 32, 32, 1, 6, 5, 1, "VALID", False),   # LeNet-5 conv1: 5x5/1, CI 1
    (1, 40, 40, 3, 16, 7, 2, "SAME", True, (6, 128)),  # stem, block_h 6 ∤ 20
]


def _conv_case(spec):
    N, H, W, CI, CO, k, s, pad, bn, *tile = spec
    x = jnp.asarray(R.randn(N, H, W, CI), jnp.float32)
    w = jnp.asarray(R.randn(k, k, CI, CO), jnp.float32)
    bnp = tuple(jnp.asarray(R.rand(CO) + 0.5, jnp.float32)
                for _ in range(4)) if bn else None
    y = ops.conv2d_fused(x, w, stride=s, padding=pad, bn=bnp, act="relu",
                         tile=tile[0] if tile else None, interpret=True)
    r = ref.conv2d_fused_ref(x, w, stride=s, padding=pad, bn=bnp, act="relu")
    return y, r


@pytest.mark.parametrize("spec", CONV_SPECS)
def test_conv2d(spec):
    y, r = _conv_case(spec)
    assert relerr(y, r) < 1e-5


@pytest.mark.parametrize("spec", CONV_SPECS)
def test_conv2d_tap_path(spec, monkeypatch):
    """Every shape through the tap path too, where the fold rule would
    send the lane-sparse ones to the folded path."""
    from repro.kernels import conv2d as _cv
    from repro.obs import METRICS
    monkeypatch.setattr(_cv, "folds", lambda *a: False)
    folded = METRICS.counter("kernels.conv2d.folded").value
    y, r = _conv_case(spec)
    assert relerr(y, r) < 1e-5
    assert METRICS.counter("kernels.conv2d.folded").value == folded


# the distinct convolutions of ResNet-34 at 224 px (He et al. 2015, Table 1;
# SAME): (H, CI, CO, k, stride), the stem first
RESNET34_CONVS = [
    (224, 3, 64, 7, 2),
    (56, 64, 64, 3, 1), (56, 64, 128, 3, 2), (56, 64, 128, 1, 2),
    (28, 128, 128, 3, 1), (28, 128, 256, 3, 2), (28, 128, 256, 1, 2),
    (14, 256, 256, 3, 1), (14, 256, 512, 3, 2), (14, 256, 512, 1, 2),
    (7, 512, 512, 3, 1),
]


def _folds(h, ci, k, stride, padding="SAME"):
    from repro.kernels.conv2d import folds
    ho = -(-h // stride) if padding == "SAME" else (h - k) // stride + 1
    return folds(k, k, ci, stride, ho, -(-ho // 8) * 8)


def test_conv2d_fold_rule():
    """Shapes alone decide the path: of ResNet-34's convs only the stem
    folds; the MobileNetV1 stem and LeNet-5's conv1 fold; 1x1 never."""
    assert [_folds(h, ci, k, s) for h, ci, _, k, s in RESNET34_CONVS] \
        == [True] + [False] * (len(RESNET34_CONVS) - 1)
    assert _folds(224, 3, 3, 2)                        # MobileNetV1 stem
    assert _folds(32, 1, 5, 1, "VALID")                # LeNet-5 conv1
    for h, ci, s in [(224, 3, 1), (224, 3, 2), (28, 1, 1), (7, 1024, 1)]:
        assert not _folds(h, ci, 1, s)


def test_conv2d_fold_counter():
    """``kernels.conv2d.folded`` counts folded call sites at trace time:
    one a traced folded conv, none a tap-path conv, and one for the whole
    ResNet-34 program (its stem), beside its 18 dispatched conv sites."""
    from repro import flow
    from repro.configs.base import FlowConfig, ShapeConfig
    from repro.configs.resnet34 import CONFIG
    from repro.obs import METRICS
    folded = METRICS.counter("kernels.conv2d.folded")
    dispatched = METRICS.counter("kernels.dispatch.pallas_interpret.conv2d")
    f32 = jnp.float32

    def traced(x, w, stride):
        before = folded.value
        jax.eval_shape(lambda a, b: ops.conv2d_fused(
            a, b, stride=stride, interpret=True),
            jax.ShapeDtypeStruct(x, f32), jax.ShapeDtypeStruct(w, f32))
        return folded.value - before

    assert traced((2, 64, 64, 3), (7, 7, 3, 64), 2) == 1
    assert traced((2, 14, 14, 64), (3, 3, 64, 64), 1) == 0
    assert traced((2, 14, 14, 3), (1, 1, 3, 64), 1) == 0
    cm = flow.compile(CONFIG, ShapeConfig("fold", "prefill", 1, 1),
                      FlowConfig(mode="folded"), backend="pallas_interpret")
    f0, d0 = folded.value, dispatched.value
    out = jax.eval_shape(
        lambda p, x: cm.apply(p, {"images": x}, mode="prefill")[0],
        cm.param_shapes(), jax.ShapeDtypeStruct((1, 224, 224, 3), f32))
    assert out.shape == (1, CONFIG.vocab_size)
    assert (folded.value - f0, dispatched.value - d0) == (1, 18)
