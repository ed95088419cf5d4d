"""Flash-attention Pallas kernel (causal / sliding-window, GQA).

Tiling (block_q × block_k) is chosen by the tiling pass so q/k/v tiles, the
fp32 score block, and the fp32 output accumulator fit VMEM — the HBM-side S²
score matrix of the reference path never exists (the paper's loop-fusion +
cached-writes story applied to attention).  Online softmax state (running
max / sum / output) lives in VMEM scratch across the K grid axis.

Sliding windows skip K blocks wholly outside [q_lo - window, q_hi]; causal
masking skips blocks above the diagonal (the analogue of not generating
hardware for loop iterations that are statically dead).

Masking is positional: per-row position arrays for queries and keys ride
into the kernel as a (bq, 1) column / (1, bk) row, with padded entries carrying
-1 (masked as keys, garbage-and-discarded as queries).  Callers that pass no
``positions`` get broadcast aranges — bit-identical to index-space masking —
while the serving engine's left-padded bucketed prefill passes per-row
shifted aranges (``arange(S) - pad``), making bucketed prefill exact on the
Pallas path.  The static block-skip tests stay in index space, which is
valid precisely because each row's q and k positions share one shift: the
positions contract is *per-row monotone shifted arange*, not arbitrary
per-token positions.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref, m_ref, l_ref,
            acc_ref, *, nk: int, bq: int, bk: int, causal: bool,
            window: Optional[int], softcap: Optional[float], scale: float,
            q_offset: int):
    i = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # static block skips run in index space: with per-row shifted-arange
    # positions, kpos <= qpos iff k_idx <= q_idx (the shift cancels), so a
    # block dead under the index-space test is dead under the positional
    # mask too
    q_lo = i * bq + q_offset
    k_lo = kb * bk
    run = jnp.asarray(True)
    if causal:
        run = jnp.logical_and(run, k_lo <= q_lo + bq - 1)
    if window:
        run = jnp.logical_and(run, k_lo + bk - 1 >= q_lo - window + 1)

    @pl.when(run)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = qp_ref[0]                               # (bq, 1)
        kpos = kp_ref[0]                               # (1, bk)
        valid = kpos >= 0                              # pad keys masked
        if causal:
            valid &= kpos <= qpos
        if window:
            valid &= kpos > qpos - window
        s = jnp.where(valid, s, NEG)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(kb == nk - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    positions: Optional[jax.Array] = None,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    tile: Tuple[int, int] = (256, 512),
                    q_offset: int = 0,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with H = KV * G.
    Returns (B, Sq, H, D).  ``q_offset`` is the absolute position of q[0]
    (used when queries are a sequence-parallel shard).

    ``positions`` — optional (B, Sq) per-row absolute token positions used
    for BOTH queries and keys (self-attention over one token stream; requires
    Skv == Sq and q_offset == 0).  Entries < 0 mark padding: such keys are
    masked everywhere and such query rows produce garbage the caller
    discards.  Contract: valid entries per row must form a contiguous
    shifted arange (left-padded bucketed prefill), which keeps the kernel's
    index-space block skipping exact.  ``None`` keeps the classic broadcast
    arange and is bit-identical to the pre-positional kernel."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    if positions is None:
        qp = jnp.broadcast_to(
            jnp.arange(Sq, dtype=jnp.int32) + q_offset, (B, Sq))
        kp = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32), (B, Skv))
    else:
        if positions.shape != (B, Sq):
            raise ValueError(
                f"positions must be (B, Sq)=({B}, {Sq}); "
                f"got {positions.shape}")
        if Skv != Sq:
            raise ValueError(
                "per-row positions require self-attention shapes "
                f"(Skv == Sq); got Sq={Sq}, Skv={Skv}")
        if q_offset:
            raise ValueError("positions and q_offset are mutually exclusive "
                             "(positions are absolute)")
        qp = kp = positions.astype(jnp.int32)
    bq, bk = tile
    bq = min(bq, _rup(Sq, 8))
    bk = min(bk, _rup(Skv, 128))
    Sqp, Skp = _rup(Sq, bq), _rup(Skv, bk)
    qt = jnp.pad(q, ((0, 0), (0, Sqp - Sq), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, Skp - Skv), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, Skp - Skv), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    # pad positions with -1: the padded tail is masked positionally (the
    # pre-positional kernel's kv_len test, folded into the arrays)
    # query positions ride as a (bq, 1) column and key positions as a
    # (1, bk) row: the unit dims satisfy the TPU block-shape rule (last two
    # block dims divisible by (8, 128) or equal to the array's)
    qpp = jnp.pad(qp, ((0, 0), (0, Sqp - Sq)),
                  constant_values=-1)[:, :, None]
    kpp = jnp.pad(kp, ((0, 0), (0, Skp - Skv)),
                  constant_values=-1)[:, None, :]
    nq, nk = Sqp // bq, Skp // bk
    grid = (B, H, nq, nk)

    kern = functools.partial(
        _kernel, nk=nk, bq=bq, bk=bk, causal=causal, window=window,
        softcap=softcap, scale=D ** -0.5, q_offset=q_offset)
    out = pl.pallas_call(
        kern, grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, kb: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, kb, G=G: (b, h // G, kb, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, kb, G=G: (b, h // G, kb, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, h, i, kb: (b, i, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, kb: (b, 0, kb)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, kb: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret)(qt, kt, vt, qpp, kpp)
    return out.transpose(0, 2, 1, 3)[:, :Sq]


def _rup(n, m):
    return (n + m - 1) // m * m
