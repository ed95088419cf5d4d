"""Reference (pure-XLA) implementations of every micro-op.

Each op is a function ``fn(ctx, op, p, *args)`` where ``p`` maps param name →
array (names are the *last path component* of the ParamSpec name).  ``ctx``
carries execution mode, decode state, sharding-constraint hooks and the
compilation plan.  The fused ops produced by the fusion pass (``glu_matmul``,
epilogue attrs on ``matmul``/``conv2d``) are implemented here too; the
matmul/attention/conv/recurrence entry points dispatch through the
:mod:`repro.kernels.registry` using the per-op backend table the
``kernels`` pass recorded on the plan (``plan.kernels``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.registry import plan_kernel


# ---------------------------------------------------------------------------
# Execution context
# ---------------------------------------------------------------------------

@dataclass
class Ctx:
    mode: str                        # train | prefill | decode
    plan: Any                        # ExecutionPlan
    state_in: Dict[str, Any] = field(default_factory=dict)
    state_out: Dict[str, Any] = field(default_factory=dict)
    cache_index: Optional[jax.Array] = None   # decode position (scalar int32)
    aux: Dict[str, Any] = field(default_factory=dict)

    # sharding-constraint hook, set by the lowering when a mesh is active.
    constrain: Callable[[jax.Array, tuple], jax.Array] = lambda x, roles: x

    @property
    def compute_dtype(self):
        return jnp.bfloat16 if self.plan.flow.precision == "bf16" else jnp.float32

    def cst(self, x, *roles):
        if len(roles) == 1 and isinstance(roles[0], (tuple, list)):
            roles = tuple(roles[0])
        return self.constrain(x, roles)

    def add_aux(self, name: str, value):
        self.aux[name] = self.aux.get(name, 0.0) + value


_CPU_SAFE_DOTS: Optional[bool] = None


def set_cpu_safe_dots(v: Optional[bool]):
    """The CPU interpreter backend lacks a few fused bf16xbf16->f32 dot
    layouts (hit by the MoE expert einsums under grad).  When executing on
    CPU we upcast those operands to f32; the dry-run disables this so the
    compiled TPU-target program keeps bf16 MXU dots."""
    global _CPU_SAFE_DOTS
    _CPU_SAFE_DOTS = v


def _cpu_safe_dots() -> bool:
    global _CPU_SAFE_DOTS
    if _CPU_SAFE_DOTS is None:
        _CPU_SAFE_DOTS = jax.default_backend() == "cpu"
    return _CPU_SAFE_DOTS


def _moe_dot(spec, a, b, dt):
    if _cpu_safe_dots():
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32))
    return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                      preferred_element_type=jnp.float32)


def _act(x, kind: str):
    return {
        "gelu": lambda v: jax.nn.gelu(v, approximate=True),
        "silu": jax.nn.silu,
        "relu": jax.nn.relu,
        "relu2": lambda v: jnp.square(jax.nn.relu(v)),
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "identity": lambda v: v,
    }[kind](x)


# ---------------------------------------------------------------------------
# Dense / elementwise ops
# ---------------------------------------------------------------------------

def _matmul_backend(ctx: Ctx, x, w, *, bias=None, act=None, w2=None):
    """Single entry point for all (possibly fused) matmuls; routes to the
    Pallas kernel when the plan's backend table selects it."""
    kern = plan_kernel(ctx.plan, "glu_matmul" if w2 is not None else "matmul",
                       x=x, w=w)
    if kern is not None:
        fn, interpret = kern
        return fn(x, w, bias=bias, act=act, w2=w2, interpret=interpret,
                  tile=ctx.plan.tiles.get("matmul"),
                  out_dtype=ctx.compute_dtype)
    dt = ctx.compute_dtype
    y = jnp.matmul(x.astype(dt), w.astype(dt),
                   preferred_element_type=jnp.float32)
    if w2 is not None:  # fused GLU pair: act(x@w) * (x@w2)
        y2 = jnp.matmul(x.astype(dt), w2.astype(dt),
                        preferred_element_type=jnp.float32)
        y = _act(y, act or "silu") * y2
        act = None
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if act is not None:
        y = _act(y, act)
    return y.astype(dt)


def op_matmul(ctx: Ctx, op, p, x, *extra):
    vals = list(p.values())
    w = vals[0]
    bias = vals[1] if op.attrs.get("bias") else None
    y = _matmul_backend(ctx, x, w, bias=bias, act=op.attrs.get("act"))
    if op.attrs.get("residual"):
        y = (y.astype(jnp.float32) + extra[0].astype(jnp.float32)).astype(y.dtype)
    return y


def op_glu_matmul(ctx: Ctx, op, p, x):
    vals = list(p.values())
    return _matmul_backend(ctx, x, vals[0], w2=vals[1],
                           act=op.attrs.get("act", "silu"))


def op_bias_add(ctx: Ctx, op, p, x):
    (b,) = p.values()
    return (x.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def op_act(ctx: Ctx, op, p, x):
    return _act(x, op.attrs["kind"])


def op_mul(ctx: Ctx, op, p, a, b):
    return a * b


def op_add(ctx: Ctx, op, p, a, b):
    return (a.astype(jnp.float32) + b.astype(jnp.float32)).astype(
        ctx.compute_dtype)


def op_identity(ctx: Ctx, op, p, x):
    return x


def op_norm(ctx: Ctx, op, p, x):
    eps = op.attrs.get("eps", 1e-6)
    xf = x.astype(jnp.float32)
    scale = next(v for k, v in p.items() if k.endswith("scale")).astype(jnp.float32)
    if op.attrs["kind"] == "rmsnorm":
        y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
        y = y * scale
    else:
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps) * scale
        b = next((v for k, v in p.items() if k.endswith("bias")), None)
        if b is not None:
            y = y + b.astype(jnp.float32)
    return y.astype(ctx.compute_dtype)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def op_embed(ctx: Ctx, op, p, tokens):
    table = p["table"]
    y = jnp.take(table, tokens, axis=0).astype(ctx.compute_dtype)
    if op.attrs.get("scale_by_sqrt_d"):
        y = y * jnp.asarray(math.sqrt(table.shape[1]), y.dtype)
    if op.attrs.get("sinusoid_pos"):
        B, S, d = y.shape
        if ctx.mode == "decode" and ctx.cache_index is not None:
            pos = jnp.full((B, S), 0, jnp.int32) + ctx.cache_index
        else:
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        y = y + _sinusoid(pos, d).astype(y.dtype)
    return ctx.cst(y, ("batch", "seq", "none"))


def _sinusoid(pos, d):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / max(half - 1, 1))
    ang = pos.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def op_unembed(ctx: Ctx, op, p, x, *tied):
    table = tied[0] if tied else p["lm_head"]
    dt = ctx.compute_dtype
    logits = jnp.matmul(x.astype(dt), table.astype(dt).T,
                        preferred_element_type=jnp.float32)
    vocab = op.attrs.get("true_vocab")
    if vocab is not None and vocab < table.shape[0]:
        mask = (jnp.arange(table.shape[0]) < vocab)
        logits = jnp.where(mask, logits, -1e9)
    return ctx.cst(logits, ("batch", "seq", "vocab"))


# ---------------------------------------------------------------------------
# Rotary embedding
# ---------------------------------------------------------------------------

def op_rope(ctx: Ctx, op, p, x, positions):
    # x: (B, S, H, Dh); positions: (B, S) absolute token positions.
    rd = op.attrs["rot_dim"]
    base = op.attrs.get("base", 10000.0)
    half = rd // 2
    inv = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv  # (B,S,1,half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rd].astype(jnp.float32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rd:]], -1)


def op_split_heads(ctx: Ctx, op, p, x):
    B, S, _ = x.shape
    return x.reshape(B, S, op.attrs["n"], op.attrs["dh"])


def op_merge_heads(ctx: Ctx, op, p, x):
    B, S, H, Dh = x.shape
    return x.reshape(B, S, H * Dh)


# ---------------------------------------------------------------------------
# Attention (full / causal / sliding-window / cross), GQA, with KV cache
# ---------------------------------------------------------------------------

def _sdpa(ctx: Ctx, q, k, v, qpos, kpos, *, causal, window, softcap,
          chunk=512):
    """Masked scaled-dot-product attention, query-chunked to bound the score
    intermediate (reference analogue of the flash kernel's tiling)."""
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = Dh ** -0.5
    qf = (q * scale).astype(ctx.compute_dtype)
    kf = k.astype(ctx.compute_dtype)
    vf = v.astype(ctx.compute_dtype)

    def block(qc, qpc):
        # qc: (B, C, H, Dh) -> scores (B, KV, G, C, Skv) in fp32
        qg = qc.reshape(B, qc.shape[1], KV, G, Dh)
        s = jnp.einsum("bckgd,bskd->bkgcs", qg, kf,
                       preferred_element_type=jnp.float32)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        valid = kpos[:, None, None, None, :] >= 0
        if causal:
            valid &= kpos[:, None, None, None, :] <= qpc[:, None, None, :, None]
        if window:
            valid &= kpos[:, None, None, None, :] > (
                qpc[:, None, None, :, None] - window)
        s = jnp.where(valid, s, -1e30)
        pr = jax.nn.softmax(s, axis=-1).astype(ctx.compute_dtype)
        o = jnp.einsum("bkgcs,bskd->bckgd", pr, vf,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, qc.shape[1], H, Dh).astype(ctx.compute_dtype)

    if Sq <= chunk:
        return block(qf, qpos)
    while Sq % chunk:
        chunk -= 1                       # largest divisor of Sq (whisper 1500)
    nc = Sq // chunk
    qs = qf.reshape(B, nc, chunk, H, Dh).swapaxes(0, 1)
    ps = qpos.reshape(B, nc, chunk).swapaxes(0, 1)
    # remat per chunk: the fp32 score block is recomputed in backward, never
    # saved — the reference-path analogue of the flash kernel's tiling.
    fn = jax.checkpoint(lambda t: block(*t), prevent_cse=False) \
        if ctx.mode == "train" else (lambda t: block(*t))
    out = lax.map(fn, (qs, ps))
    return out.swapaxes(0, 1).reshape(B, Sq, H, Dh)


def op_attention(ctx: Ctx, op, p, q, k, v, positions):
    attrs = op.attrs
    cross = attrs.get("cross", False)
    skey = attrs["state_key"]
    causal = attrs.get("causal", True)
    window = attrs.get("window")
    softcap = attrs.get("softcap")
    B, Sq, H, Dh = q.shape

    if ctx.mode in ("train", "prefill") and not cross:
        q = ctx.cst(q, ("batch", "seq_cp", "none", "none"))
        k = ctx.cst(k, ("batch", "gather", "none", "none"))
        v = ctx.cst(v, ("batch", "gather", "none", "none"))
        kern = plan_kernel(ctx.plan, "attention", window=window, cross=cross)
        if kern is not None:
            fn, interpret = kern
            out = fn(q, k, v, positions, causal=causal, window=window,
                     softcap=softcap, interpret=interpret,
                     tile=ctx.plan.tiles.get("attention"))
        else:
            out = _sdpa(ctx, q, k, v, positions, positions, causal=causal,
                        window=window, softcap=softcap)
        out = ctx.cst(out, ("batch", "seq_cp", "none", "none"))
        if ctx.mode == "prefill" and skey is not None:
            C = ctx.plan.cache_len
            if Sq >= C:
                kc, vc = k[:, Sq - C:], v[:, Sq - C:]
                pc = positions[:, Sq - C:]
            else:
                pad = C - Sq
                kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                pc = jnp.pad(positions, ((0, 0), (0, pad)),
                             constant_values=-1)
            ctx.state_out[skey] = {"k": ctx.cst(kc, ("batch", "kv_len", "none", "none")),
                                   "v": ctx.cst(vc, ("batch", "kv_len", "none", "none")),
                                   "pos": pc}
        return out

    if cross:
        if ctx.mode == "decode":
            st = ctx.state_in[skey]
            kc, vc = st["k"], st["v"]
            ctx.state_out[skey] = st
        else:
            kc, vc = k, v
            if ctx.mode == "prefill":   # cache encoder K/V once
                ctx.state_out[skey] = {"k": k, "v": v}
        Skv = kc.shape[1]
        kpos = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32),
                                (B, Skv))
        return _sdpa(ctx, q, kc, vc, positions, kpos, causal=False,
                     window=None, softcap=softcap)

    # -- decode: paged (block-table) or rolling cache --------------------
    st = ctx.state_in[skey]
    if "kp" in st:
        # paged KV pool (serving subsystem): per-row block tables + lengths
        # instead of a dense per-request cache.  ``len[b]`` is the position
        # of the token being decoded; the new K/V land at logical offset
        # ``len[b]`` of row b's block chain, then attention runs over the
        # pool through the block table (Pallas gather on TPU, the registered
        # ref fallback elsewhere).  Free slots park on trash block 0: their
        # writes are garbage into a block no live request owns.
        kp, vp, bt, ln = st["kp"], st["vp"], st["bt"], st["len"]
        bs = kp.shape[1]
        nblk = bt.shape[1]
        if Sq > 1:
            # paged multi-query (chunked catch-up): row b scores a chunk of
            # Sq = k freshly written tokens at absolute positions
            # ``positions[b]`` against its pool blocks.  Entries < 0 are
            # padding (decode rows advancing one token, drained tails):
            # their K/V writes are aimed at pool block 0 — the trash block
            # no live request owns — and their attention rows are masked to
            # zero and discarded by the engine.
            pos = positions.astype(jnp.int32)            # (B, Sq)
            act = pos >= 0
            safe = jnp.where(act, pos, 0)
            rows = jnp.arange(B)
            blk = jnp.where(act, bt[rows[:, None], (safe // bs) % nblk], 0)
            off = jnp.where(act, safe % bs, 0)
            kp = kp.at[blk, off].set(k.astype(kp.dtype))
            vp = vp.at[blk, off].set(v.astype(vp.dtype))
            ctx.state_out[skey] = {"kp": kp, "vp": vp, "bt": bt,
                                   "len": ln + act.sum(1).astype(jnp.int32)}
            kern = plan_kernel(ctx.plan, "paged_decode_attention")
            if kern is not None:
                fn, interpret = kern
                return fn(q, kp, vp, bt, ln, qpos=pos, window=window,
                          softcap=softcap, interpret=interpret)
            from repro.kernels.registry import REGISTRY
            ref = REGISTRY.get("paged_decode_attention", "ref").fn
            return ref(q, kp, vp, bt, ln, qpos=pos, window=window,
                       softcap=softcap, compute_dtype=ctx.compute_dtype)
        rows = jnp.arange(B)
        blk = bt[rows, (ln // bs) % nblk]            # (B,) pool block ids
        off = ln % bs
        kp = kp.at[blk, off].set(k[:, 0].astype(kp.dtype))
        vp = vp.at[blk, off].set(v[:, 0].astype(vp.dtype))
        ctx.state_out[skey] = {"kp": kp, "vp": vp, "bt": bt,
                               "len": ln + jnp.int32(1)}
        kern = plan_kernel(ctx.plan, "paged_decode_attention")
        if kern is not None:
            fn, interpret = kern
            return fn(q, kp, vp, bt, ln, window=window, softcap=softcap,
                      interpret=interpret)
        from repro.kernels.registry import REGISTRY
        ref = REGISTRY.get("paged_decode_attention", "ref").fn
        return ref(q, kp, vp, bt, ln, window=window, softcap=softcap,
                   compute_dtype=ctx.compute_dtype)

    # rolling cache path
    kc, vc, pc = st["k"], st["v"], st["pos"]
    C = kc.shape[1]
    idx = ctx.cache_index % C
    kc = lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, idx, 0, 0))
    vc = lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, idx, 0, 0))
    pc = lax.dynamic_update_slice(
        pc, jnp.broadcast_to(ctx.cache_index, (B, 1)).astype(pc.dtype),
        (0, idx))
    kc = ctx.cst(kc, ("batch", "kv_len", "none", "none"))
    vc = ctx.cst(vc, ("batch", "kv_len", "none", "none"))
    ctx.state_out[skey] = {"k": kc, "v": vc, "pos": pc}
    qpos = jnp.broadcast_to(ctx.cache_index, (B, 1)).astype(jnp.int32)
    kern = plan_kernel(ctx.plan, "decode_attention")
    if kern is not None:
        fn, interpret = kern
        return fn(q, kc, vc, pc, qpos, window=window, softcap=softcap,
                  interpret=interpret,
                  tile=ctx.plan.tiles.get("decode_attention"))
    return _sdpa(ctx, q, kc, vc, qpos, pc, causal=True, window=window,
                 softcap=softcap)


# ---------------------------------------------------------------------------
# Temporal conv + RG-LRU (Griffin)
# ---------------------------------------------------------------------------

def op_conv1d_causal(ctx: Ctx, op, p, x):
    W = p[[k for k in p if k.endswith("_w")][0]].astype(jnp.float32)
    b = p[[k for k in p if k.endswith("_b")][0]].astype(jnp.float32)
    kw = op.attrs["width"]
    skey = op.attrs["state_key"]
    xf = x.astype(jnp.float32)
    if ctx.mode == "decode":
        st = ctx.state_in[skey]          # (B, kw-1, w) previous inputs
        seq = jnp.concatenate([st.astype(jnp.float32), xf], axis=1)
        y = jnp.einsum("bkw,kw->bw", seq, W)[:, None, :] + b
        ctx.state_out[skey] = seq[:, 1:].astype(x.dtype)
        return y.astype(ctx.compute_dtype)
    pad = jnp.pad(xf, ((0, 0), (kw - 1, 0), (0, 0)))
    y = sum(pad[:, i:i + x.shape[1], :] * W[i] for i in range(kw)) + b
    if ctx.mode == "prefill":
        S = x.shape[1]
        tail = xf[:, max(0, S - (kw - 1)):, :]
        if S < kw - 1:
            tail = jnp.pad(tail, ((0, 0), (kw - 1 - S, 0), (0, 0)))
        ctx.state_out[skey] = tail.astype(x.dtype)
    return y.astype(ctx.compute_dtype)


def _block_diag_linear(x, W, b):
    # x: (B, S, w); W: (nb, w/nb, w/nb)
    B, S, w = x.shape
    nb = W.shape[0]
    xr = x.reshape(B, S, nb, w // nb)
    y = jnp.einsum("bsnk,nkj->bsnj", xr.astype(jnp.float32),
                   W.astype(jnp.float32))
    return y.reshape(B, S, w) + b.astype(jnp.float32)


def op_rg_lru(ctx: Ctx, op, p, x):
    c = op.attrs.get("c", 8.0)
    skey = op.attrs["state_key"]
    nb = op.attrs["n_blocks"]
    lam = p["lru_lambda"].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(_block_diag_linear(x, p["lru_wa"], p["lru_ba"]))
    i = jax.nn.sigmoid(_block_diag_linear(x, p["lru_wx"], p["lru_bx"]))
    log_a = -c * r * jax.nn.softplus(-lam)          # log of recurrence gate
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * xf)
    if ctx.mode == "decode":
        h0 = ctx.state_in[skey].astype(jnp.float32)
        h = a[:, 0] * h0 + gated[:, 0]
        ctx.state_out[skey] = h.astype(x.dtype)
        return h[:, None, :].astype(ctx.compute_dtype)
    # linear recurrence over the sequence: Pallas scan kernel (state resident
    # in VMEM) on the kernel backends, associative scan on the reference path
    kern = plan_kernel(ctx.plan, "rg_lru")
    if kern is not None:
        fn, interpret = kern
        h = fn(a, gated, interpret=interpret).astype(jnp.float32)
    else:
        def comb(u, w_):
            (a1, b1), (a2, b2) = u, w_
            return a2 * a1, a2 * b1 + b2
        _, h = lax.associative_scan(comb, (a, gated), axis=1)  # h_0 = 0
    if ctx.mode == "prefill":
        ctx.state_out[skey] = h[:, -1].astype(x.dtype)
    return h.astype(ctx.compute_dtype)


# ---------------------------------------------------------------------------
# RWKV6 time-mix / channel-mix
# ---------------------------------------------------------------------------

def _token_shift(ctx, x, skey):
    """Returns x_{t-1} (zeros / cached state at t=0) and stores new state."""
    if ctx.mode == "decode":
        prev = ctx.state_in[skey].astype(x.dtype)[:, None, :]
        ctx.state_out[skey] = x[:, -1]
    else:
        prev = jnp.pad(x[:, :-1], ((0, 0), (1, 0), (0, 0)))
        if ctx.mode == "prefill":
            ctx.state_out[skey] = x[:, -1]
    return prev


def _wkv_chunked(r, k, v, w, u, chunk, parallel: bool = True,
                 boundary_dt=jnp.float32):
    """RWKV6 linear recurrence, chunked.

    ``parallel=True`` (inference): inter-chunk associative scan over chunk
    summaries + one intra-chunk scan vectorized across all chunks — maximal
    parallelism, but its backward would store every per-step state
    (O(B·S·H·dk·dv), probed at 59 GiB/device for rwkv6-7b train_4k).

    ``parallel=False`` (training): nested scans — outer over chunks (carries
    only the (B,H,dk,dv) boundary state), inner over the chunk's steps, with
    the chunk body rematerialized.  Backward stores nc boundary states plus
    one chunk's steps: O(B·(S/C + C)·H·dk·dv).  This is the fla-style
    chunk-recompute schedule; a fused Pallas linear-scan kernel is the
    hardware answer on TPU.

    Shapes: r,k,w (B,S,H,dk); v (B,S,H,dv); u (H,dk). Returns (B,S,H,dv)."""
    B, S, Hh, dk = r.shape
    dv = v.shape[-1]
    C = min(chunk, S)
    while S % C:
        C //= 2
    nc = S // C

    if not parallel:
        xs = tuple(t.reshape(B, nc, C, Hh, -1).transpose(1, 2, 0, 3, 4)
                   for t in (r, k, v, jnp.exp(w)))   # (nc, C, B, H, d)

        def step(Sst, inp):
            rt, kt, vt, wt = inp
            bonus = jnp.einsum("bhk,bhk,bhv->bhv", rt, u * kt, vt)
            yt = jnp.einsum("bhk,bhkv->bhv", rt, Sst) + bonus
            Sst = wt[..., None] * Sst + kt[..., None] * vt[..., None, :]
            return Sst, yt

        @jax.checkpoint
        def chunk_body(S0, data):
            # boundary state crosses chunks in `boundary_dt` (bf16 in bf16
            # training: the saved (nc,B,H,dk,dv) stack halves — §Perf); the
            # in-chunk recurrence recomputes in f32.
            S1, ys = lax.scan(step, S0.astype(jnp.float32), data)
            return S1.astype(boundary_dt), ys

        S0 = jnp.zeros((B, Hh, dk, dv), boundary_dt)
        Sfin, ys = lax.scan(chunk_body, S0, xs)       # ys: (nc, C, B, H, dv)
        y = ys.transpose(2, 0, 1, 3, 4).reshape(B, S, Hh, dv)
        return y, Sfin.astype(jnp.float32)

    rs, ks, vs, logw = (t.reshape(B, nc, C, Hh, -1) for t in (r, k, v, w))
    Lc = jnp.cumsum(logw, axis=2)                       # (B,nc,C,H,dk)
    chunk_decay = jnp.exp(Lc[:, :, -1])                 # (B,nc,H,dk)
    # sum_s exp(L_C - L_s) k_s v_s^T  (safe: exponent <= 0)
    kd = ks * jnp.exp(Lc[:, :, -1:, :, :] - Lc)
    chunk_kv = jnp.einsum("bnchk,bnchv->bnhkv", kd, vs)
    # associative scan over chunks: S_{c} = D_c * S_{c-1} + M_c
    def comb(p1, p2):
        (d1, m1), (d2, m2) = p1, p2
        return d1 * d2, d2[..., None] * m1 + m2
    Dacc, Macc = lax.associative_scan(comb, (chunk_decay, chunk_kv), axis=1)
    # state entering chunk n (exclusive): shift right
    S_in = jnp.pad(Macc[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    # intra-chunk: sequential over C, vectorized over (B, nc, H)
    xs = tuple(t.transpose(2, 0, 1, 3, 4)
               for t in (rs, ks, vs, jnp.exp(logw)))
    def step(Sst, inp):
        rt, kt, vt, wt = inp
        bonus = jnp.einsum("bnhk,bnhk,bnhv->bnhv", rt, u * kt, vt)
        yt = jnp.einsum("bnhk,bnhkv->bnhv", rt, Sst) + bonus
        Sst = wt[..., None] * Sst + kt[..., None] * vt[..., None, :]
        return Sst, yt
    _, ys = lax.scan(step, S_in, xs)
    y = jnp.moveaxis(ys, 0, 2)                          # (B,nc,C,H,dv)
    final = Macc[:, -1]            # state after the full sequence (S_0 = 0)
    return y.reshape(B, S, Hh, dv), final


def op_rwkv6_timemix(ctx: Ctx, op, p, x):
    Hh, dh = op.attrs["n_heads"], op.attrs["head_dim"]
    rank = op.attrs["lora_rank"]
    skey = op.attrs["state_key"]
    B, S, d = x.shape
    dt = ctx.compute_dtype
    # token-shift lerps in compute dtype (fp32 copies of (B,S,d) x5 were the
    # rwkv6 train memory hog — §Perf iteration); LoRA math stays fp32.
    xf = x.astype(dt)
    prev = _token_shift(ctx, xf, skey + "_shift")
    dx = prev - xf
    # data-dependent token-shift mixes (5 targets: r,k,v,w,g)
    lo = jnp.tanh(jnp.einsum("bsd,dr->bsr", xf.astype(jnp.float32),
                             p["mu_lora_a"].astype(jnp.float32)))
    lo = lo.reshape(B, S, 5, rank)
    delta = jnp.einsum("bsnr,nrd->nbsd", lo, p["mu_lora_b"].astype(jnp.float32))
    mix = p["mu_base"].astype(jnp.float32)[:, None, None, :] + delta  # (5,B,S,d)
    xr, xk, xv, xw, xg = (xf + dx * mix[j].astype(dt) for j in range(5))
    proj = lambda z, w_: jnp.einsum("bsd,de->bse", z.astype(dt), w_.astype(dt),
                                    preferred_element_type=jnp.float32)
    r = proj(xr, p["w_r"]).reshape(B, S, Hh, dh)
    k = proj(xk, p["w_k"]).reshape(B, S, Hh, dh)
    v = proj(xv, p["w_v"]).reshape(B, S, Hh, dh)
    g = proj(xg, p["w_g"])
    wraw = (p["decay_base"].astype(jnp.float32) +
            jnp.tanh(jnp.einsum("bsd,dr->bsr", xw.astype(jnp.float32),
                                p["decay_lora_a"].astype(jnp.float32)))
            @ p["decay_lora_b"].astype(jnp.float32))
    logw = -jnp.exp(jnp.clip(wraw, -20.0, 4.0)).reshape(B, S, Hh, dh)
    u = p["bonus"].astype(jnp.float32).reshape(Hh, dh)
    if ctx.mode == "decode":
        St = ctx.state_in[skey + "_s"].astype(jnp.float32)  # (B,H,dk,dv)
        rt, kt, vt = r[:, 0], k[:, 0], v[:, 0]
        bonus = jnp.einsum("bhk,bhk,bhv->bhv", rt, u * kt, vt)
        yt = jnp.einsum("bhk,bhkv->bhv", rt, St) + bonus
        St = jnp.exp(logw[:, 0])[..., None] * St + \
            kt[..., None] * vt[..., None, :]
        ctx.state_out[skey + "_s"] = St.astype(x.dtype)
        y = yt[:, None]
    else:
        y, Sfin = _wkv_chunked(r, k, v, logw, u,
                               ctx.plan.tiles.get("wkv_chunk", 32),
                               parallel=ctx.mode != "train",
                               boundary_dt=dt)
        if ctx.mode == "prefill":
            ctx.state_out[skey + "_s"] = Sfin.astype(x.dtype)
    # per-head group norm, gate, output proj
    y = y.reshape(B, S, Hh, dh)
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.var(y, -1, keepdims=True)
    y = (y - mu) * lax.rsqrt(var + 64e-5)
    y = y.reshape(B, S, Hh * dh) * p["ln_x_scale"].astype(jnp.float32) + \
        p["ln_x_bias"].astype(jnp.float32)
    y = y * jax.nn.silu(g)
    out = jnp.einsum("bse,ed->bsd", y.astype(dt), p["w_o"].astype(dt),
                     preferred_element_type=jnp.float32)
    return out.astype(dt)


def op_rwkv6_channelmix(ctx: Ctx, op, p, x):
    skey = op.attrs["state_key"]
    dt = ctx.compute_dtype
    xf = x.astype(dt)
    prev = _token_shift(ctx, xf, skey + "_shift")
    dx = prev - xf
    mu = p["cm_mu"].astype(dt)
    xr = xf + dx * mu[0]
    xk = xf + dx * mu[1]
    mm = lambda z, w_: jnp.matmul(z.astype(dt), w_.astype(dt),
                                  preferred_element_type=jnp.float32)
    r = jax.nn.sigmoid(mm(xr, p["cw_r"]))
    k = jnp.square(jax.nn.relu(mm(xk, p["cw_k"]))).astype(dt)
    return (r * mm(k, p["cw_v"])).astype(dt)


# ---------------------------------------------------------------------------
# Mixture-of-Experts FFN (capacity-based dispatch; EP- or TP-sharded)
# ---------------------------------------------------------------------------

def _moe_core(ctx: Ctx, attrs, x, router, wg, wu, wd, shared,
              eid0=0, e_local=None, tp_shards=1):
    """Dispatch → expert FFN → combine on one model shard.

    ``eid0``/``e_local``: the expert range owned by this shard (EP); with
    expert-TP every shard owns all experts on a d_ff slice.  Routing and
    dispatch bookkeeping are replicated across model shards (cheap, integer
    work); only this shard's experts contribute to the returned *partial*
    output, which the caller psums.
    """
    E, topk = attrs["num_experts"], attrs["top_k"]
    cf = attrs.get("capacity_factor", 1.25)
    B, S, d = x.shape
    dt = ctx.compute_dtype
    E_loc = e_local or E
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)
    gate, idx = lax.top_k(probs, topk)                      # (B,S,k)
    gate = gate / jnp.clip(jnp.sum(gate, -1, keepdims=True), 1e-9)
    aux = jnp.zeros((), jnp.float32)
    if ctx.mode == "train":
        me = jnp.mean(probs, axis=(0, 1))
        ce = jnp.mean(jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32),
                      axis=(0, 1))
        aux = attrs.get("aux_weight", 0.01) * E * jnp.sum(me * ce)

    cap = max(math.ceil(S * topk / E * cf), 1)
    fe = idx.reshape(B, S * topk)
    fg = gate.reshape(B, S * topk).astype(jnp.float32)

    def pos_in_expert(e_row):
        Tk = e_row.shape[0]
        order = jnp.argsort(e_row, stable=True)
        sorted_e = e_row[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(E))
        pos_sorted = jnp.arange(Tk) - starts[sorted_e]
        return jnp.zeros((Tk,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32))
    mypos = jax.vmap(pos_in_expert)(fe)
    keep = (mypos < cap).astype(jnp.float32)
    mypos = jnp.minimum(mypos, cap - 1)
    # restrict to this shard's experts (EP); no-op for expert-TP
    fe_loc = fe - eid0
    mine = ((fe_loc >= 0) & (fe_loc < E_loc)).astype(jnp.float32)
    keep_l = keep * mine
    fe_loc = jnp.clip(fe_loc, 0, E_loc - 1)

    xr = jnp.repeat(x, topk, axis=1) if topk > 1 else x
    contrib = (xr.astype(jnp.float32) * keep_l[..., None]).astype(dt)
    scatter = jax.vmap(
        lambda e_, p_, c_: jnp.zeros((E_loc, cap, d), dt).at[e_, p_].add(c_))
    buf = scatter(fe_loc, mypos, contrib)                   # (B,E_loc,cap,d)
    hg = _moe_dot("becd,edf->becf", buf, wg, dt)
    hu = _moe_dot("becd,edf->becf", buf, wu, dt)
    hmid = (_act(hg, attrs.get("act", "silu")) * hu).astype(dt)
    out_buf = _moe_dot("becf,efd->becd", hmid, wd, dt).astype(dt)
    gather = jax.vmap(lambda ob, e_, p_: ob[e_, p_])
    y = gather(out_buf, fe_loc, mypos) * (fg * keep_l)[..., None].astype(dt)
    y = y.reshape(B, S, topk, d).sum(2) if topk > 1 else y.reshape(B, S, d)
    if shared is not None:
        ws_g, ws_u, ws_d = shared
        sg = _moe_dot("bsd,df->bsf", x, ws_g, dt)
        su = _moe_dot("bsd,df->bsf", x, ws_u, dt)
        sh = (_act(sg, "silu") * su).astype(dt)
        y = y + _moe_dot("bsf,fd->bsd", sh, ws_d, dt).astype(dt)
    return y.astype(dt), aux


def _moe_shard_map(ctx: Ctx, op, p, x):
    """Fully-manual MoE region: every collective explicit.

    Layout inside the region: batch local per dp shard; expert weights
    sharded over the model axis (EP when E divides it, expert-TP on d_ff
    otherwise) and *gathered over the dp axes at the region boundary* (the
    FSDP gather, inserted as boundary resharding); one explicit psum of the
    combined (B_loc, S, d) output over the model axis.  This replaces
    GSPMD's choice of fp32 buffer-granularity all-reduces (measured
    710 GiB/device/step on mixtral train_4k — EXPERIMENTS.md §Perf it.1).

    NB: a bf16 psum inside shard_map hits an XLA partitioner CHECK
    ("Invalid binary instruction opcode copy") on this CPU build — the
    activation crosses the boundary and reduces in f32.  On a TPU toolchain
    the psum would be bf16 (half the ICI bytes; noted in the roofline).
    """
    from jax.sharding import PartitionSpec as P
    rules = ctx.plan.rules
    attrs = op.attrs
    E = attrs["num_experts"]
    tp, tpn = rules.tp_size, rules.tp
    ep = E % tp == 0
    E_loc = E // tp if ep else E
    dp_ent = rules.dp if len(rules.dp) > 1 else rules.dp[0]
    B = x.shape[0]
    if B % rules.dp_size:
        dp_ent = None                      # long_500k: batch unshardable

    def wspec(ndim: int, ffn_dim: int):
        ent = [None] * ndim
        if ep:
            ent[0] = tpn
        else:
            ent[ffn_dim] = tpn
        return P(*ent)

    has_shared = attrs.get("num_shared")

    def body(x_, router, wg, wu, wd, *shared_w):
        x_ = x_.astype(ctx.compute_dtype)
        ax = jax.lax.axis_index(tpn)
        eid0 = ax * E_loc if ep else 0
        y, aux = _moe_core(ctx, attrs, x_, router, wg, wu, wd,
                           tuple(shared_w) if shared_w else None,
                           eid0=eid0, e_local=E_loc, tp_shards=tp)
        y = jax.lax.psum(y.astype(jnp.float32), tpn)
        if ctx.mode == "train" and dp_ent is not None:
            aux = jax.lax.pmean(aux, rules.dp if len(rules.dp) > 1
                                else rules.dp[0])
        return y, aux

    operands = [x.astype(jnp.float32), p["router"], p["we_gate"],
                p["we_up"], p["we_down"]]
    in_specs = [P(dp_ent, None, None), P(), wspec(3, 2), wspec(3, 2),
                wspec(3, 1)]
    if has_shared:
        operands += [p["ws_gate"], p["ws_up"], p["ws_down"]]
        in_specs += [P(None, tpn), P(None, tpn), P(tpn, None)]
    from repro.core.compat import shard_map
    f = shard_map(body, rules.mesh, tuple(in_specs),
                  (P(dp_ent, None, None), P()),
                  axis_names=set(rules.mesh.axis_names))
    y, aux = f(*operands)
    if ctx.mode == "train":
        ctx.add_aux("moe_aux", aux)
    return y.astype(ctx.compute_dtype)


def op_moe_ffn(ctx: Ctx, op, p, x):
    """Per-sequence, causal capacity dispatch:

    Token positions within an expert are assigned by a cumsum *within each
    sequence*, so (a) dispatch shards cleanly over the batch (no cross-shard
    cumsum), (b) a sequence's routing is independent of the rest of the batch
    (a serving invariant), and (c) prefill→decode is consistent (appending a
    token never changes earlier tokens' slots).  Decode steps (S=1, ≤1 token
    per expert per sequence) are dropless by construction.

    With an active mesh the expert compute runs in a manual shard_map over
    the model axis (EP or expert-TP) with one explicit psum — see
    :func:`_moe_shard_map`.
    """
    if ctx.plan.rules is not None and ctx.plan.rules.tp:
        return _moe_shard_map(ctx, op, p, x)
    shared = ((p["ws_gate"], p["ws_up"], p["ws_down"])
              if op.attrs.get("num_shared") else None)
    y, aux = _moe_core(ctx, op.attrs, x, p["router"], p["we_gate"],
                       p["we_up"], p["we_down"], shared)
    if ctx.mode == "train":
        ctx.add_aux("moe_aux", aux)
    return y

# ---------------------------------------------------------------------------
# Multimodal / audio stubs
# ---------------------------------------------------------------------------

def op_patch_proj(ctx: Ctx, op, p, h):
    """Replace the first n_patches positions of the token-embedded sequence
    with projected (precomputed, stubbed) vision-patch embeddings."""
    patches = ctx.aux["__inputs__"]["patches"]          # (B, P, d_vision)
    dt = ctx.compute_dtype
    z = jnp.matmul(patches.astype(dt), p["mm_w1"].astype(dt),
                   preferred_element_type=jnp.float32) + p["mm_b1"]
    z = jax.nn.gelu(z, approximate=True)
    z = jnp.matmul(z.astype(dt), p["mm_w2"].astype(dt),
                   preferred_element_type=jnp.float32) + p["mm_b2"]
    z = z.astype(dt)
    P_ = op.attrs["n_patches"]
    return jnp.concatenate([z, h[:, P_:, :]], axis=1)


def op_frames_in(ctx: Ctx, op, p, h):
    """Whisper frontend stub: input already contains frame embeddings."""
    frames = ctx.aux["__inputs__"]["frames"]            # (B, enc_seq, d)
    B, S, d = frames.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return (frames.astype(jnp.float32) +
            _sinusoid(pos, d)).astype(ctx.compute_dtype)


def op_image_in(ctx: Ctx, op, p, h):
    return h.astype(ctx.compute_dtype)


# ---------------------------------------------------------------------------
# CNN ops
# ---------------------------------------------------------------------------

def _conv_backend(ctx: Ctx, kernel: str, x, w, *, stride, padding,
                  groups=1, bn=None, act=None):
    """``kernel`` (``conv2d`` or ``depthwise_conv2d``) where the plan's
    backend table and the call site allow it, else the reference conv."""
    kern = plan_kernel(ctx.plan, kernel, groups=groups, x=x, w=w,
                       stride=stride, padding=padding)
    if kern is not None:
        fn, interpret = kern
        return fn(x, w, stride=stride, padding=padding, bn=bn, act=act,
                  interpret=interpret, tile=ctx.plan.tiles.get(kernel))
    dt = ctx.compute_dtype
    # mixed-precision conv transpose rules reject bf16 operands with an f32
    # preferred type; the reference path upcasts instead (the Pallas kernel
    # is the optimized path and accumulates fp32 natively).
    cdt = jnp.float32 if dt == jnp.bfloat16 else dt
    y = lax.conv_general_dilated(
        x.astype(cdt), w.astype(cdt), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
        preferred_element_type=jnp.float32)
    if bn is not None:
        scale, bias, mean, var = bn
        inv = lax.rsqrt(var.astype(jnp.float32) + 1e-5)
        y = (y - mean) * (inv * scale) + bias
    if act:
        y = _act(y, act)
    return y.astype(dt)


def _bn_params(p, prefix=""):
    g = lambda suf: next(v for k, v in p.items() if k.endswith(suf))
    return (g("_scale"), g("_bias"), g("_mean"), g("_var"))


def op_conv2d(ctx: Ctx, op, p, x):
    w = next(v for k, v in p.items() if k.endswith("_w"))
    bn = _bn_params(p) if op.attrs.get("bn") else None
    return _conv_backend(ctx, "conv2d", x, w,
                         stride=op.attrs.get("stride", 1),
                         padding=op.attrs.get("padding", "SAME"),
                         bn=bn, act=op.attrs.get("act"))


def op_depthwise_conv2d(ctx: Ctx, op, p, x):
    """One ``(kh, kw)`` filter per channel; the weight is ``(kh, kw, 1, C)``,
    the HWIO filter of a conv with ``feature_group_count=C``."""
    w = next(v for k, v in p.items() if k.endswith("_w"))
    bn = _bn_params(p) if op.attrs.get("bn") else None
    return _conv_backend(ctx, "depthwise_conv2d", x, w,
                         stride=op.attrs.get("stride", 1),
                         padding=op.attrs.get("padding", "SAME"),
                         groups=x.shape[-1], bn=bn, act=op.attrs.get("act"))


def op_batchnorm(ctx: Ctx, op, p, x):
    scale, bias, mean, var = _bn_params(p)
    if ctx.mode == "train":
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.var(xf, axis=(0, 1, 2))
    inv = lax.rsqrt(var.astype(jnp.float32) + op.attrs.get("eps", 1e-5))
    y = (x.astype(jnp.float32) - mean) * (inv * scale.astype(jnp.float32)) \
        + bias.astype(jnp.float32)
    return y.astype(ctx.compute_dtype)


def _pool(x, window, stride, kind):
    init = -jnp.inf if kind == "max" else 0.0
    op_ = lax.max if kind == "max" else lax.add
    y = lax.reduce_window(x.astype(jnp.float32), init, op_,
                          (1, window, window, 1), (1, stride, stride, 1),
                          "SAME")
    if kind == "avg":
        y = y / (window * window)
    return y


def op_maxpool2d(ctx: Ctx, op, p, x):
    return _pool(x, op.attrs["window"], op.attrs["stride"], "max").astype(x.dtype)


def op_avgpool2d(ctx: Ctx, op, p, x):
    return _pool(x, op.attrs["window"], op.attrs["stride"], "avg").astype(x.dtype)


def op_global_avgpool(ctx: Ctx, op, p, x):
    return jnp.mean(x.astype(jnp.float32), axis=(1, 2)).astype(x.dtype)


def op_flatten(ctx: Ctx, op, p, x):
    return x.reshape(x.shape[0], -1)


OPS: Dict[str, Callable] = {
    "matmul": op_matmul, "glu_matmul": op_glu_matmul, "bias_add": op_bias_add,
    "act": op_act, "mul": op_mul, "add": op_add, "identity": op_identity,
    "norm": op_norm, "embed": op_embed, "unembed": op_unembed,
    "rope": op_rope, "split_heads": op_split_heads,
    "merge_heads": op_merge_heads, "attention": op_attention,
    "conv1d_causal": op_conv1d_causal, "rg_lru": op_rg_lru,
    "rwkv6_timemix": op_rwkv6_timemix, "rwkv6_channelmix": op_rwkv6_channelmix,
    "moe_ffn": op_moe_ffn, "patch_proj": op_patch_proj,
    "frames_in": op_frames_in, "image_in": op_image_in,
    "conv2d": op_conv2d, "depthwise_conv2d": op_depthwise_conv2d,
    "batchnorm": op_batchnorm, "maxpool2d": op_maxpool2d,
    "avgpool2d": op_avgpool2d, "global_avgpool": op_global_avgpool,
    "flatten": op_flatten,
}
