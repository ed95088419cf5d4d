"""Split-KV flash-decoding Pallas kernel.

One query token attends over a long (rolling) KV cache.  The cache length is
split into blocks along the grid's innermost axis; each block contributes to
an online-softmax accumulator in VMEM scratch (the distributed form — shards
of the cache on different chips — combines the same (m, l, acc) triples with
a psum at the lowering layer).  Masking is position-based: cache slots hold
absolute positions (-1 = empty), so full, rolling and sliding-window caches
all use one kernel.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, pos_ref, qpos_ref, o_ref,
            m_ref, l_ref, acc_ref, *, nk: int,
            window: Optional[int], softcap: Optional[float], scale: float):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)   # (G, bk)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    kpos = pos_ref[0]                                    # (1, bk)
    qpos = qpos_ref[0]                                   # (1, 1)
    valid = (kpos >= 0) & (kpos <= qpos)
    if window:
        valid &= kpos > qpos - window
    s = jnp.where(valid, s, NEG)
    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0, 0].astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(kb == nk - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q: jax.Array, kc: jax.Array, vc: jax.Array,
                     pos: jax.Array, qpos: jax.Array, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     block_k: int = 2048,
                     interpret: bool = False) -> jax.Array:
    """q: (B, 1, H, D); kc/vc: (B, C, KV, D); pos: (B, C) absolute positions
    (-1 empty); qpos: (B, 1).  Returns (B, 1, H, D)."""
    B, _, H, D = q.shape
    C, KV = kc.shape[1], kc.shape[2]
    G = H // KV
    bk = min(block_k, _rup(C, 128))
    Cp = _rup(C, bk)
    kt = jnp.pad(kc, ((0, 0), (0, Cp - C), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(vc, ((0, 0), (0, Cp - C), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    # positions carry a unit second-minor dim so their blocks meet the TPU
    # block-shape rule (last two dims divisible by (8, 128) or full)
    pp = jnp.pad(pos, ((0, 0), (0, Cp - C)), constant_values=-1)[:, None, :]
    qp = qpos.reshape(B, 1, 1).astype(jnp.int32)
    qt = q.reshape(B, KV, G, D)                          # group per kv head
    nk = Cp // bk
    grid = (B, KV, nk)

    kern = functools.partial(_kernel, nk=nk, window=window, softcap=softcap,
                             scale=D ** -0.5)
    out = pl.pallas_call(
        kern, grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, kb: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, kb: (b, h, kb, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, kb: (b, h, kb, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, kb: (b, 0, kb)),
            pl.BlockSpec((1, 1, 1), lambda b, h, kb: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, kb: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, 1), jnp.float32),
                        pltpu.VMEM((G, D), jnp.float32)],
        interpret=interpret)(qt, kt, vt, pp, qp)
    return out.reshape(B, 1, H, D)


def _rup(n, m):
    return (n + m - 1) // m * m


# ---------------------------------------------------------------------------
# Paged decode attention: gather over block tables (the serving subsystem's
# KV-pool lookup path)
# ---------------------------------------------------------------------------

def _paged_kernel(tbl_ref, lens_ref, q_ref, k_ref, v_ref, qp_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, bs: int, nblk: int, kv: int,
                  d: int, window: Optional[int], softcap: Optional[float],
                  scale: float):
    jb = pl.program_id(1)

    @pl.when(jb == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # block j of the table holds token positions [j*bs, (j+1)*bs); the pool
    # block it maps to was selected by the BlockSpec index_map (scalar
    # prefetch), so masking is purely positional.  Query positions arrive
    # pre-expanded to one row per (chunk token, group) pair; rows < 0 are
    # padding (fully masked → zero output, discarded by the caller).
    kpos = jb * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    qrow = qp_ref[0]                                     # (Sq*G, 1)
    valid = (qrow >= 0) & (kpos <= qrow)
    if window:
        valid &= kpos > qrow - window
    # one grid step holds one pool block for every KV head: head h is the
    # lane slice [h*d, (h+1)*d) of the flattened (bs, KV*d) block
    for h in range(kv):
        q = q_ref[0, h].astype(jnp.float32) * scale      # (Sq*G, d)
        k = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)   # (bs, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (Sq*G, bs)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG)
        m_prev = m_ref[h]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
            p, v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_cur

    @pl.when(jb == nblk - 1)
    def _():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _copy_block_kernel(idx_ref, pool_ref, out_ref):
    out_ref[...] = pool_ref[...]


def copy_block(pool: jax.Array, src, dst, *,
               interpret: bool = False) -> jax.Array:
    """Copy pool block ``src`` over pool block ``dst`` — the serving
    subsystem's copy-on-write fork.  ``pool`` is ``(NB, bs, KV, D)`` or the
    folded ``(reps, NB, bs, KV, D)``; returns the pool with row ``dst``
    replaced.

    The block ids ride the scalar-prefetch channel so the BlockSpec
    ``index_map`` aims one DMA per grid step straight at the source block,
    and the pool operand is aliased to the output: only block ``dst`` moves,
    not the pool."""
    lead = pool.ndim == 5
    p5 = pool if lead else pool[None]
    R, NB, bs, KV, D = p5.shape
    idx = jnp.stack([jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[pl.BlockSpec((1, 1, bs, KV, D),
                               lambda r, idx: (r, idx[0], 0, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, bs, KV, D),
                               lambda r, idx: (r, idx[1], 0, 0, 0)),
    )
    out = pl.pallas_call(
        _copy_block_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(p5.shape, p5.dtype),
        input_output_aliases={1: 0},     # pool buffer updated in place
        interpret=interpret)(idx, p5)
    return out if lead else out[0]


def paged_decode_attention(q: jax.Array, kp: jax.Array, vp: jax.Array,
                           bt: jax.Array, lens: jax.Array, *,
                           qpos: Optional[jax.Array] = None,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """Decode / chunked catch-up attention over a paged KV pool.

    q: (B, Sq, H, D); kp/vp: (NB, bs, KV, D) device-resident block pools;
    bt: (B, nblk) int32 block table (pool block id per logical block);
    lens: (B,) int32 current decode position per row (token ``lens[b]`` has
    just been written at logical offset ``lens[b]``).  Returns (B, Sq, H, D).

    ``qpos`` — optional (B, Sq) int32 absolute positions of the query
    tokens, required when Sq > 1 (chunked prefill catch-up: row b scores a
    whole chunk of ``Sq = k`` freshly written tokens against its pool
    blocks in one pass).  Entries < 0 mark padding rows whose output is
    zero and discarded.  Defaults to ``lens[:, None]`` — the classic
    single-token decode, bit-identical to the pre-chunk kernel.

    Block tables and lengths ride the scalar-prefetch channel
    (:class:`pltpu.PrefetchScalarGridSpec`): the BlockSpec ``index_map``
    reads ``bt[b, j]`` to aim each grid step's DMA at the right pool block —
    the gather never materializes a contiguous per-request cache.
    """
    B, Sq, H, D = q.shape
    NB, bs, KV = kp.shape[0], kp.shape[1], kp.shape[2]
    nblk = bt.shape[1]
    G = H // KV
    if qpos is None:
        qpos = lens.reshape(B, 1).astype(jnp.int32)
    # rows ordered (chunk token, group): row r ↔ token r // G, group r % G
    qt = (q.reshape(B, Sq, KV, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, KV, Sq * G, D))
    # expand positions to one (Sq*G, 1) column per row (host-side repeat
    # keeps the kernel body free of gathers/reshapes Mosaic dislikes)
    qpe = jnp.repeat(qpos.astype(jnp.int32), G, axis=1)[:, :, None]
    # the pool's (KV, D) minor dims merge into one lane dim (a free
    # reshape): each grid step DMAs one whole (bs, KV*D) pool block
    kf = kp.reshape(NB, bs, KV * D)
    vf = vp.reshape(NB, bs, KV * D)
    kern = functools.partial(_paged_kernel, bs=bs, nblk=nblk, kv=KV, d=D,
                             window=window, softcap=softcap,
                             scale=D ** -0.5)
    R = Sq * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec((1, KV, R, D), lambda b, j, tbl, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, KV * D),
                         lambda b, j, tbl, ln: (tbl[b, j], 0, 0)),
            pl.BlockSpec((1, bs, KV * D),
                         lambda b, j, tbl, ln: (tbl[b, j], 0, 0)),
            pl.BlockSpec((1, R, 1), lambda b, j, tbl, ln: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, R, D),
                               lambda b, j, tbl, ln: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((KV, R, 1), jnp.float32),
                        pltpu.VMEM((KV, R, 1), jnp.float32),
                        pltpu.VMEM((KV, R, D), jnp.float32)],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, D), q.dtype),
        interpret=interpret)(
        bt.astype(jnp.int32), lens.astype(jnp.int32), qt, kf, vf, qpe)
    return (out.reshape(B, KV, Sq, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, Sq, H, D))
