"""Training loop: sharded train_step, microbatched gradient accumulation,
checkpoint/restart, failure injection + automatic recovery, straggler-aware
data loading.  The step itself is a single donated jit program — the paper's
autorun analogue (no host round-trips inside a step)."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import lowering
from repro.core.plan import ExecutionPlan
from repro.optim.adamw import AdamW, AdamWState
from repro.train import checkpoint as ckpt_lib


@dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    async_ckpt: bool = False
    log_every: int = 10
    # fault-tolerance test hooks
    fail_at_step: Optional[int] = None        # inject a failure once
    max_restarts: int = 2


def make_train_step(plan: ExecutionPlan, opt: AdamW, microbatches: int = 1):
    loss_fn = lowering.make_loss_fn(plan)

    def train_step(params, opt_state: AdamWState, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        else:
            def mb_slice(i, b):
                return jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // microbatches),
                        x.shape[0] // microbatches), b)

            def one(i, carry):
                gacc, lacc = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb_slice(i, batch))
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gacc, g)
                return gacc, lacc + l
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, lsum = jax.lax.fori_loop(0, microbatches, one, (g0, 0.0))
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = lsum / microbatches
            metrics = {}
        params, opt_state, om = opt.update(grads, opt_state, params)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


class Trainer:
    def __init__(self, plan: ExecutionPlan, opt: AdamW,
                 tcfg: TrainerConfig, mesh=None, rules=None):
        # the launch layer hands us a repro.flow.CompiledModel; plan-based
        # construction stays for core-level tests and the legacy shims
        from repro.flow import CompiledModel
        if isinstance(plan, CompiledModel):
            mesh = mesh if mesh is not None else plan.mesh
            rules = rules if rules is not None else plan.rules
            plan = plan.plan
        self.plan, self.opt, self.tcfg = plan, opt, tcfg
        self.mesh, self.rules = mesh, rules
        self.step_fn = None
        self._restarts = 0

    # -- setup ----------------------------------------------------------------
    def init(self, rng) -> tuple:
        # a mesh plan's params are born on their shards, and the optimizer
        # moments inherit those shardings
        params = lowering.init_params(self.plan, rng)
        return params, self.opt.init(params)

    def compile_step(self, microbatches: int = 1):
        fn = make_train_step(self.plan, self.opt, microbatches)
        donate = (0, 1)
        if self.mesh is not None:
            with self.mesh:
                self.step_fn = jax.jit(fn, donate_argnums=donate)
        else:
            self.step_fn = jax.jit(fn, donate_argnums=donate)
        return self.step_fn

    # -- main loop with restart-on-failure -------------------------------------
    def fit(self, data, rng, hooks: Dict[str, Callable] = ()):
        tcfg = self.tcfg
        params, opt_state = self.init(rng)
        start = 0
        if tcfg.ckpt_dir:
            last = ckpt_lib.latest_step(tcfg.ckpt_dir)
            if last is not None:
                params, opt_state = self.restore(last, params, opt_state)
                start = last
        if self.step_fn is None:
            self.compile_step(max(self.plan.flow.microbatches, 1))
        history = []
        step = start
        while step < tcfg.steps:
            try:
                batch = {k: jnp.asarray(v) for k, v in data.get(step).items()}
                if (tcfg.fail_at_step is not None and step == tcfg.fail_at_step
                        and self._restarts == 0):
                    self._restarts += 1
                    raise RuntimeError("injected node failure")
                params, opt_state, metrics = self.step_fn(
                    params, opt_state, batch)
                if step % tcfg.log_every == 0:
                    history.append((step, float(metrics["loss"])))
                if tcfg.ckpt_dir and (step + 1) % tcfg.ckpt_every == 0:
                    ckpt_lib.save(tcfg.ckpt_dir, step + 1,
                                  {"params": params, "opt": opt_state},
                                  wait=not tcfg.async_ckpt)
                step += 1
            except RuntimeError as e:
                # node failure: restore from the last checkpoint and continue
                if self._restarts > tcfg.max_restarts or not tcfg.ckpt_dir:
                    raise
                last = ckpt_lib.latest_step(tcfg.ckpt_dir)
                if last is None:
                    params, opt_state = self.init(rng)
                    step = 0
                else:
                    params, opt_state = self.restore(last, params, opt_state)
                    step = last
        return params, opt_state, history

    def restore(self, step, params_like, opt_like):
        shardings = None
        if self.rules is not None:
            psh = self.rules.params_shardings(self.plan)
            shardings = {"params": psh, "opt": AdamWState(
                None, psh, psh, None if opt_like.err is None else psh)}
        tree = ckpt_lib.restore(self.tcfg.ckpt_dir, step,
                                {"params": params_like, "opt": opt_like},
                                shardings)
        return tree["params"], tree["opt"]
