"""Plain decoder-only transformer forward (Phi-3 / Phi-4-mini family), from
the configuration file's published keys and the seeded weights of
``bench.weights``, in float32 at the highest matmul precision, one layer at
a time so that it fits beside nothing else on the chip.

Per layer: RMSNorm, Q/K/V projections without bias, rotary embedding on the
first ``partial_rotary_factor`` of each head (rotate-half convention,
``rope_theta`` base), causal grouped-query attention, output projection and
residual add; RMSNorm, SiLU-gated MLP and residual add.  Final RMSNorm and
logits against the tied embedding table.  The long-context rotary scaling
of the published model is not part of the configuration as run (see its
``reduced`` keys), so it is not here either.

A further precision, ``"int8"`` or ``"fp8"`` (e4m3), is the control: every
projection and the logits take weights quantised per output channel and
activations per token, with absmax scales, and compute on the dequantised
values.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.weights import leaf

HIGHEST = lax.Precision.HIGHEST
LAYER_PARAMS = ("attn_norm_scale", "wq", "wk", "wv", "wo", "ffn_norm_scale",
                "w_gate", "w_up", "w_down")


def _q8(x, axis):
    """int8 quantise-dequantise along ``axis`` (absmax scale)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _qf8(x, axis):
    """float8 (e4m3) quantise-dequantise along ``axis`` (absmax scale)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


QUANT = {"int8": _q8, "fp8": _qf8}


def _mm(x, w, quant):
    if quant:
        x, w = QUANT[quant](x, -1), QUANT[quant](w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _shapes(c: Dict) -> Dict[str, tuple]:
    d, f = c["hidden_size"], c["intermediate_size"]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    Dh = d // H
    return {"attn_norm_scale": (d,), "wq": (d, H * Dh), "wk": (d, KV * Dh),
            "wv": (d, KV * Dh), "wo": (H * Dh, d), "ffn_norm_scale": (d,),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


@functools.partial(jax.jit, static_argnames=("H", "KV", "rd", "theta",
                                             "eps", "quant"))
def _layer(h, ws, *, H, KV, rd, theta, eps, quant):
    n, L, d = h.shape
    Dh = d // H
    G = H // KV
    an = _rms(h, ws["attn_norm_scale"], eps)
    q = _mm(an, ws["wq"], quant).reshape(n, L, H, Dh)
    k = _mm(an, ws["wk"], quant).reshape(n, L, KV, Dh)
    v = _mm(an, ws["wv"], quant).reshape(n, L, KV, Dh)
    half = rd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv      # (L, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]

    def rope(x):
        x1, x2 = x[..., :half], x[..., half:rd]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                                x[..., rd:]], -1)

    q, k = rope(q), rope(k)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def attend(args):
        qi, ki, vi = args                          # (L, H|KV, Dh)
        qg = qi.reshape(L, KV, G, Dh)
        s = jnp.einsum("qkgd,skd->kgqs", qg, ki,
                       precision=HIGHEST) * Dh ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, vi, precision=HIGHEST)
        return o.reshape(L, H * Dh)

    o = lax.map(attend, (q, k, v))
    h = h + _mm(o, ws["wo"], quant)
    fn = _rms(h, ws["ffn_norm_scale"], eps)
    g = jax.nn.silu(_mm(fn, ws["w_gate"], quant)) * _mm(fn, ws["w_up"], quant)
    return h + _mm(g, ws["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "quant"))
def _logits(h, scale, table, *, vocab, eps, quant):
    hn = _rms(h, scale, eps)
    lg = _mm(hn, table.T, quant)
    return jnp.where(jnp.arange(table.shape[0]) < vocab, lg, -jnp.inf)


SEQ_MULTIPLE = 512


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def padded_vocab(vocab: int, multiple: int = 32) -> int:
    return _round_up(vocab, multiple)


def served_gaps(config: Dict, seed: int, prompts: Sequence[np.ndarray],
                served: Sequence[Sequence[int]],
                precisions: Sequence[str] = ("f32",)) -> Dict[str, List]:
    """For each request, the float32 reference's logits at every position
    that produced a served token.  Returns, per request, the gap by which
    each served token's reference logit lies below the reference's best
    (``"served"``), and for every further precision in ``precisions``, the
    gap of the token that precision puts first (``"<precision>"``)."""
    c = config
    dt = jnp.dtype(c["torch_dtype"])
    d, V = c["hidden_size"], c["vocab_size"]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    Dh = d // H
    rd = int(Dh * c["partial_rotary_factor"])
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    shapes = _shapes(c)

    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(s[:-1], np.int32)])
            for p, s in zip(prompts, served)]
    # fixed shapes, so the reference compiles once per size class
    L = _round_up(max(len(s) for s in seqs), SEQ_MULTIPLE)
    toks = np.zeros((len(seqs), L), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s                 # right padding: causal, unseen
    table = leaf(seed, "embed/table", (padded_vocab(V), d), dt)
    h0 = jnp.take(table, jnp.asarray(toks), axis=0)
    hs = {p: h0 for p in precisions}
    for li in range(c["num_hidden_layers"]):
        ws = {n: leaf(seed, f"layer{li}/{n}", shapes[n], dt)
              for n in LAYER_PARAMS}
        for p in precisions:
            hs[p] = _layer(hs[p], ws, H=H, KV=KV, rd=rd, theta=theta,
                           eps=eps, quant=QUANT.get(p) and p)
        del ws
    scale = leaf(seed, "head/final_norm_scale", (d,), dt)
    out: Dict[str, List] = {"served": []}
    for p in precisions[1:]:
        out[p] = []
    for i, (pr, sv) in enumerate(zip(prompts, served)):
        G = len(sv)
        rows = np.arange(len(pr) - 1, len(pr) - 1 + _round_up(G, 128))
        rows = np.minimum(rows, L - 1)          # padding rows, dropped below
        ref = _logits(hs[precisions[0]][i, rows], scale, table, vocab=V,
                      eps=eps, quant=None)
        best = jnp.max(ref, -1)
        tok = np.zeros(len(rows), np.int32)
        tok[:G] = np.asarray(sv, np.int32)
        gap = best - jnp.take_along_axis(ref, jnp.asarray(tok)[:, None],
                                         -1)[:, 0]
        out["served"].append(np.asarray(gap)[:G])
        for p in precisions[1:]:
            lg = _logits(hs[p][i, rows], scale, table, vocab=V, eps=eps,
                         quant=p)
            top = jnp.argmax(lg, -1)
            gap = best - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
            out[p].append(np.asarray(gap)[:G])
    return out
