"""AdamW in pure JAX, sharding-transparent (moments mirror param shardings).

Includes optional int8 gradient compression with error feedback — the
distributed-optimization trick applied inside the gradient-accumulation loop
(the quantization the compressed all-reduce would introduce, with the error
carried forward so the sequence of updates stays unbiased).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array
    mu: Any
    nu: Any
    err: Optional[Any] = None      # error-feedback buffers (compression)


@dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    compress: Optional[str] = None           # None | "int8_ef"
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1

    def init(self, params) -> AdamWState:
        # zeros_like keeps each parameter's sharding: moments mirror params
        z = lambda p: jax.tree.map(
            lambda x: jnp.zeros_like(x, dtype=jnp.float32), p)
        err = z(params) if self.compress else None
        return AdamWState(jnp.zeros((), jnp.int32), z(params), z(params), err)

    def schedule(self, step):
        warm = jnp.minimum(step / max(self.warmup_steps, 1), 1.0)
        t = jnp.clip((step - self.warmup_steps) /
                     max(self.total_steps - self.warmup_steps, 1), 0.0, 1.0)
        cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
        frac = self.min_lr_frac + (1 - self.min_lr_frac) * cos
        return self.lr * warm * frac

    def compress_grads(self, grads, err):
        """int8 quantize (per-tensor scale) with error feedback."""
        def one(g, e):
            gf = g.astype(jnp.float32) + e
            scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
            q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
            deq = q.astype(jnp.float32) * scale
            return deq, gf - deq
        flat = jax.tree.map(one, grads, err)
        deq = jax.tree.map(lambda t: t[0], flat,
                           is_leaf=lambda t: isinstance(t, tuple))
        new_err = jax.tree.map(lambda t: t[1], flat,
                               is_leaf=lambda t: isinstance(t, tuple))
        return deq, new_err

    def update(self, grads, state: AdamWState, params
               ) -> Tuple[Any, AdamWState, Dict[str, jax.Array]]:
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        err = state.err
        if self.compress == "int8_ef":
            grads, err = self.compress_grads(grads, err)
        gsq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
        gnorm = jnp.sqrt(gsq)
        scale = jnp.minimum(1.0, self.grad_clip / (gnorm + 1e-9)) \
            if self.grad_clip else 1.0
        step = state.step + 1
        b1c = 1 - self.b1 ** step.astype(jnp.float32)
        b2c = 1 - self.b2 ** step.astype(jnp.float32)
        lr = self.schedule(state.step.astype(jnp.float32))

        def upd(p, g, m, v):
            g = g * scale
            m = self.b1 * m + (1 - self.b1) * g
            v = self.b2 * v + (1 - self.b2) * jnp.square(g)
            mh = m / b1c
            vh = v / b2c
            delta = mh / (jnp.sqrt(vh) + self.eps)
            if self.weight_decay and p.ndim >= 2:
                delta = delta + self.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

        out = jax.tree.map(upd, params, grads, state.mu, state.nu)
        new_p = jax.tree.map(lambda t: t[0], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        new_m = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        new_v = jax.tree.map(lambda t: t[2], out,
                             is_leaf=lambda t: isinstance(t, tuple))
        return new_p, AdamWState(step, new_m, new_v, err), \
            {"grad_norm": gnorm, "lr": lr}
