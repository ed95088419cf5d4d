"""Lowering: ExecutionPlan × Graph → executable JAX functions.

* ``init_params``  — parameter pytree (folded groups pre-stacked for scan)
* ``init_state``   — serving state (KV caches / recurrence states), stacked
* ``make_apply``   — apply(params, batch, state, cache_index, mode)
* ``make_loss_fn`` — training loss with sequence-chunked cross-entropy (the
  LM-head analogue of the paper's loop fusion: logits never materialize)

Folded units (the paper's parameterized kernels) lower to ``lax.scan`` over
stacked per-layer parameters and state; unfolded units lower to straight-line
code (the pipelined mode's one-section-per-layer).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.graph import Block, Graph, MicroOp, ParamSpec
from repro.core.ops_impl import OPS, Ctx
from repro.core.plan import ExecutionPlan
from repro.core.passes.folding import Unit

AUX_KEYS = ("moe_aux",)


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------

def _init_one(key, spec: ParamSpec, dtype):
    shape = spec.shape
    if spec.init == "zeros":
        return jnp.zeros(shape, dtype)
    if spec.init == "ones":
        return jnp.ones(shape, dtype)
    if spec.init == "lru_lambda":
        u = jax.random.uniform(key, shape, jnp.float32, 0.9, 0.999)
        r = u ** (1.0 / 8.0)
        return jnp.log(r / (1 - r)).astype(dtype)
    if spec.init == "rwkv_mix":
        return jax.random.uniform(key, shape, jnp.float32).astype(dtype)
    if spec.init == "rwkv_decay":
        n = shape[-1]
        base = -6.0 + 5.0 * (jnp.arange(n) / max(n - 1, 1)) ** 0.9
        return jnp.broadcast_to(base, shape).astype(dtype)
    if spec.init == "embed":
        scale = spec.init_scale or shape[-1] ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
    # default: normal with 1/sqrt(fan_in)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = spec.init_scale or fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _fold_key(graph: Graph, unit: Unit) -> str:
    return f"fold_{graph.blocks[unit.indices[0]].name}"


def unit_key(graph: Graph, unit: Unit) -> str:
    if unit.folded:
        return _fold_key(graph, unit)
    return graph.blocks[unit.indices[0]].name


def init_params(plan: ExecutionPlan, rng) -> Dict[str, Any]:
    """Random parameters for ``plan``.  On a mesh plan every leaf is built
    directly on its shards (one jitted init with the plan's parameter
    shardings as outputs), so no device ever holds the whole model."""
    if plan.rules is None:
        return _init_params(plan, rng)
    return jax.jit(functools.partial(_init_params, plan),
                   out_shardings=plan.rules.params_shardings(plan))(rng)


def _init_params(plan: ExecutionPlan, rng) -> Dict[str, Any]:
    graph, dtype = plan.graph, plan.prec.param_dtype
    params: Dict[str, Any] = {}
    for unit in plan.units:
        if not unit.folded:
            b = graph.blocks[unit.indices[0]]
            bp = {}
            for spec in b.param_specs():
                k = jax.random.fold_in(rng, _stable_hash(b.name + spec.name))
                bp[spec.name] = _init_one(k, spec, dtype)
            if bp:
                params[b.name] = bp
        else:
            period, reps = unit.period, unit.reps
            gp: Dict[str, Any] = {}
            for j in range(period):
                proto = graph.blocks[unit.indices[j]]
                for spec in proto.param_specs():
                    slices = []
                    for r in range(reps):
                        blk = graph.blocks[unit.indices[r * period + j]]
                        k = jax.random.fold_in(
                            rng, _stable_hash(blk.name + spec.name))
                        slices.append(_init_one(k, spec, dtype))
                    gp[f"{j}:{spec.name}"] = jnp.stack(slices)
            params[_fold_key(graph, unit)] = gp
    return params


def _stable_hash(s: str) -> int:
    h = 2166136261
    for ch in s.encode():
        h = (h ^ ch) * 16777619 % (1 << 31)
    return h


def param_shapes(plan: ExecutionPlan) -> Dict[str, Any]:
    """ShapeDtypeStructs of the parameter pytree (no allocation) — used by
    the dry-run and the sharding solver."""
    graph, dtype = plan.graph, plan.prec.param_dtype
    out: Dict[str, Any] = {}
    for unit in plan.units:
        if not unit.folded:
            b = graph.blocks[unit.indices[0]]
            bp = {s.name: jax.ShapeDtypeStruct(s.shape, dtype)
                  for s in b.param_specs()}
            if bp:
                out[b.name] = bp
        else:
            gp = {}
            for j in range(unit.period):
                proto = graph.blocks[unit.indices[j]]
                for s in proto.param_specs():
                    gp[f"{j}:{s.name}"] = jax.ShapeDtypeStruct(
                        (unit.reps,) + s.shape, dtype)
            out[_fold_key(graph, unit)] = gp
    return out


def param_specs_tree(plan: ExecutionPlan) -> Dict[str, Any]:
    """Same structure as params, holding (ParamSpec, stacked: bool)."""
    graph = plan.graph
    out: Dict[str, Any] = {}
    for unit in plan.units:
        if not unit.folded:
            b = graph.blocks[unit.indices[0]]
            bp = {s.name: (s, False) for s in b.param_specs()}
            if bp:
                out[b.name] = bp
        else:
            gp = {}
            for j in range(unit.period):
                proto = graph.blocks[unit.indices[j]]
                for s in proto.param_specs():
                    gp[f"{j}:{s.name}"] = (s, True)
            out[_fold_key(graph, unit)] = gp
    return out


# ---------------------------------------------------------------------------
# Serving state
# ---------------------------------------------------------------------------

def _op_state_shapes(op: MicroOp, cfg, B: int, C: int, dtype):
    """Returns {suffix: (shape, dtype, roles)} for one stateful op.  The
    roles drive the sharding solver (KV length over tp; batch over dp;
    recurrence heads/width over tp)."""
    a = op.attrs
    if op.op == "attention":
        att = cfg.attention
        KV, Dh = att.n_kv_heads, att.head_dim
        if a.get("cross"):
            S = cfg.encoder_seq
            r = ("batch", "none", "none", "none")
            return {"k": ((B, S, KV, Dh), dtype, r),
                    "v": ((B, S, KV, Dh), dtype, r)}
        r = ("batch", "kv_len", "none", "none")
        return {"k": ((B, C, KV, Dh), dtype, r),
                "v": ((B, C, KV, Dh), dtype, r),
                "pos": ((B, C), jnp.int32, ("batch", "kv_len"))}
    if op.op == "conv1d_causal":
        kw, w = op.params[0].shape
        return {"": ((B, kw - 1, w), dtype, ("batch", "none", "d_ff"))}
    if op.op == "rg_lru":
        w = op.params[0].shape[0]
        return {"": ((B, w), dtype, ("batch", "d_ff"))}
    if op.op == "rwkv6_timemix":
        d = [s for s in op.params if s.name.endswith("w_r")][0].shape[0]
        H, dh = a["n_heads"], a["head_dim"]
        return {"_shift": ((B, d), dtype, ("batch", "none")),
                "_s": ((B, H, dh, dh), dtype,
                       ("batch", "heads", "none", "none"))}
    if op.op == "rwkv6_channelmix":
        d = [s for s in op.params if s.name.endswith("cw_r")][0].shape[0]
        return {"_shift": ((B, d), dtype, ("batch", "none"))}
    return {}


def _mk_state(shapes: Dict[str, tuple], lead: Tuple[int, ...] = (),
              abstract: bool = False, roles: bool = False):
    out = {}
    for suf, (shp, dt, rl) in shapes.items():
        full = lead + shp
        if roles:
            out[suf] = ("layers",) * len(lead) + rl
        elif abstract:
            out[suf] = jax.ShapeDtypeStruct(full, dt)
        elif dt == jnp.int32:
            out[suf] = jnp.full(full, -1, dt)
        else:
            out[suf] = jnp.zeros(full, dt)
    return out


def init_state(plan: ExecutionPlan, batch_size: int, abstract: bool = False,
               roles: bool = False):
    """Serving state pytree, stacked to match the folded units.  With
    ``roles=True`` returns the matching tree of per-dim role tuples (for the
    sharding solver)."""
    graph, cfg = plan.graph, plan.cfg
    dtype = plan.prec.compute_dtype
    C = plan.cache_len
    state: Dict[str, Any] = {}
    for unit in plan.units:
        ukey = unit_key(graph, unit)
        ust: Dict[str, Any] = {}
        def add(op, lead):
            shapes = _op_state_shapes(op, cfg, batch_size, C, dtype)
            made = _mk_state(shapes, lead, abstract, roles)
            key = op.attrs["state_key"]
            if op.op == "attention":      # attention state is a nested dict
                ust[key] = made
            else:
                for suf, v in made.items():
                    ust[key + suf] = v

        if not unit.folded:
            for op in graph.blocks[unit.indices[0]].stateful_ops():
                add(op, ())
        else:
            for j in range(unit.period):
                for op in graph.blocks[unit.indices[j]].stateful_ops():
                    add(op, (unit.reps,))
        if ust:
            state[ukey] = ust
    return state


def state_shardings(plan: ExecutionPlan, batch_size: int, rules):
    """NamedSharding tree for the serving state (role-driven)."""
    import jax.sharding as js
    abs_tree = init_state(plan, batch_size, abstract=True)
    role_tree = init_state(plan, batch_size, roles=True)
    def one(a, r):
        return js.NamedSharding(rules.mesh, rules.act_pspec(r, a.shape))
    return jax.tree.map(one, abs_tree, role_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# ---------------------------------------------------------------------------
# Block interpretation (with per-mode dead-code elimination)
# ---------------------------------------------------------------------------

def _used_ins(op: MicroOp, mode: str) -> Tuple[str, ...]:
    if op.op == "attention" and op.attrs.get("cross") and mode == "decode":
        return (op.ins[0], op.ins[3])       # q, positions (K/V come from cache)
    return op.ins


def live_ops(block: Block, mode: str) -> List[MicroOp]:
    keep = [False] * len(block.ops)
    live = {"h"}
    for i in range(len(block.ops) - 1, -1, -1):
        op = block.ops[i]
        needed = op.out in live
        if op.attrs.get("state_key") and mode in ("prefill", "decode"):
            needed = True
        if needed:
            keep[i] = True
            live.discard(op.out)
            live.update(_used_ins(op, mode))
    return [op for i, op in enumerate(block.ops) if keep[i]]


def _param_slice(op: MicroOp, bparams: Dict[str, Any], j: Optional[int]):
    """Dict param-name → array for one op (handles folded 'j:' prefixes)."""
    out = {}
    for spec in op.params:
        key = spec.name if j is None else f"{j}:{spec.name}"
        out[spec.name] = bparams[key]
    return out


def _run_block(ctx: Ctx, block: Block, bparams, env: Dict[str, Any],
               mode: str, j: Optional[int] = None,
               tied_tables: Optional[Dict[str, Any]] = None):
    for op in live_ops(block, mode):
        args = [env[i] for i in _used_ins(op, mode)]
        if op.op == "attention" and len(args) == 2:    # decode cross-attn
            q, pos = args
            args = [q, q, q, pos]                       # K/V placeholders
        p = _param_slice(op, bparams, j)
        if op.op == "unembed" and op.attrs.get("tied"):
            args.append(tied_tables[op.attrs["tied"]])
        env[op.out] = OPS[op.op](ctx, op, p, *args)
    return env["h"]


# ---------------------------------------------------------------------------
# apply()
# ---------------------------------------------------------------------------

def make_apply(plan: ExecutionPlan, head: bool = True):
    """Deprecated shim over :func:`_make_apply` — reach the apply function
    through :func:`repro.flow.compile` (``CompiledModel.apply``) instead."""
    from repro.core.plan import _warn_deprecated
    _warn_deprecated("repro.core.lowering.make_apply")
    return _make_apply(plan, head=head)


def _make_apply(plan: ExecutionPlan, head: bool = True):
    """Returns apply(params, batch, state, cache_index, mode) ->
    (out, new_state, aux).  ``head=False`` stops before the unembed (training
    uses the chunked-CE loss instead)."""
    graph, cfg = plan.graph, plan.cfg
    units = plan.units
    rules = plan.rules

    def constrain(x, roles):
        if rules is None:
            return x
        return rules.constrain_act(x, roles)

    def apply(params, batch, state=None, cache_index=None, mode="train"):
        ctx = Ctx(mode=mode, plan=plan, cache_index=cache_index)
        ctx.constrain = constrain
        ctx.aux["__inputs__"] = batch
        new_state: Dict[str, Any] = {}

        if "tokens" in batch:
            h = batch["tokens"]
        else:
            h = batch["images"]
        B = h.shape[0]

        def pos_for(x, encoder=False):
            # positions for the *current* chain (encoder/decoder lengths differ)
            if x.ndim == 4:                    # images
                return None
            S = x.shape[1]
            # explicit per-row positions (serving: left-padded bucketed
            # prefill, heterogeneous decode positions with the paged cache).
            # Decoder chains only — encoder chains always keep their arange.
            p = None if encoder else batch.get("positions")
            if p is not None and p.ndim == 2 and p.shape[1] == S:
                return p.astype(jnp.int32)
            if mode == "decode":
                return jnp.broadcast_to(cache_index, (B, S)).astype(jnp.int32)
            return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

        tied_tables = {}
        for unit in units:
            b0 = graph.blocks[unit.indices[0]]
            for spec in b0.param_specs():
                if spec.name == "table":
                    tied_tables[f"{b0.name}/table"] = params[b0.name]["table"]

        def cst_h(x):
            return ctx.cst(x, ("batch",) + ("none",) * (x.ndim - 1))

        cross = None
        h = cst_h(h)
        for unit in units:
            ukey = unit_key(graph, unit)
            b0 = graph.blocks[unit.indices[0]]
            if mode == "decode" and (b0.kind.startswith("enc")
                                     or b0.kind == "mm"):
                continue   # prompt-only blocks: patches/frames live in caches
            if b0.kind == "dec_embed":
                h = batch["tokens"]
            if b0.kind == "head":
                if not head:
                    break
                if mode == "prefill":
                    h = h[:, -1:]
            env = {"h": h, "cross": cross,
                   "positions": pos_for(
                       h, encoder=b0.kind.startswith("enc"))}
            if not unit.folded:
                ctx.state_in = (state or {}).get(ukey, {})
                ctx.state_out = {}
                h = _run_block(ctx, b0, params.get(ukey, {}), env, mode,
                               tied_tables=tied_tables)
                if ctx.state_out:
                    new_state[ukey] = ctx.state_out
            else:
                h, st = _run_folded(ctx, plan, unit, params[ukey],
                                    (state or {}).get(ukey), env, mode)
                if st:
                    new_state[ukey] = st
            if b0.attrs.get("captures_cross"):
                cross = h
            h = cst_h(h)
        aux = {k: v for k, v in ctx.aux.items() if k != "__inputs__"}
        return h, new_state, aux

    return apply


def _run_folded(ctx: Ctx, plan: ExecutionPlan, unit: Unit, gparams,
                gstate, env, mode: str):
    graph = plan.graph
    period = unit.period
    protos = [graph.blocks[unit.indices[j]] for j in range(period)]
    positions, cross = env["positions"], env["cross"]
    outer = ctx

    def body(carry, xs):
        h, aux = carry
        step_params, step_state = xs
        c = Ctx(mode=mode, plan=plan, cache_index=outer.cache_index)
        c.constrain = outer.constrain
        c.aux = dict(outer.aux)
        c.aux.update(aux)
        c.state_in = step_state or {}
        c.state_out = {}
        e = {"h": h, "positions": positions, "cross": cross}
        for j, blk in enumerate(protos):
            e["h"] = _run_block(c, blk, step_params, e, mode, j=j)
            e["h"] = c.cst(e["h"], ("batch",) + ("none",) * (e["h"].ndim - 1))
        aux2 = {k: jnp.asarray(c.aux.get(k, 0.0), jnp.float32)
                for k in AUX_KEYS}
        return (e["h"], aux2), c.state_out

    aux0 = {k: jnp.asarray(outer.aux.get(k, 0.0), jnp.float32)
            for k in AUX_KEYS}
    reps = unit.reps

    if mode == "train" and plan.flow.remat == "nested" and reps >= 4:
        # two-level activation checkpointing (paper-CW analogue for HBM):
        # save the layer-boundary h only every k layers; the backward pass
        # recomputes within a group.  Peak saved activations:
        # O(reps/k + k) layer inputs instead of O(reps).
        k = max(int(reps ** 0.5), 1)
        while reps % k:
            k -= 1
        inner_body = jax.checkpoint(body, prevent_cse=False)
        def group(carry, xs_g):
            return lax.scan(inner_body, carry, xs_g)
        group = jax.checkpoint(group, prevent_cse=False)
        xs_resh = jax.tree.map(
            lambda a: a.reshape((reps // k, k) + a.shape[1:]),
            (gparams, gstate))
        (h, aux), ys = lax.scan(group, (env["h"], aux0), xs_resh,
                                length=reps // k)
        ys = jax.tree.map(
            lambda a: a.reshape((reps,) + a.shape[2:]), ys)
    else:
        if mode == "train" and plan.flow.remat in ("block", "nested"):
            body = jax.checkpoint(body, prevent_cse=False)
        (h, aux), ys = lax.scan(body, (env["h"], aux0),
                                (gparams, gstate),
                                length=reps,
                                unroll=plan.flow.scan_unroll)
    for k2 in AUX_KEYS:
        outer.aux[k2] = aux[k2]
    return h, ys


# ---------------------------------------------------------------------------
# Loss (sequence-chunked cross-entropy — logits never fully materialize)
# ---------------------------------------------------------------------------

def make_loss_fn(plan: ExecutionPlan):
    cfg = plan.cfg
    apply = _make_apply(plan, head=cfg.family == "cnn")
    graph = plan.graph
    head_block = graph.blocks[-1]
    assert head_block.kind in ("head", "cnn_head")

    def loss_fn(params, batch):
        if cfg.family == "cnn":
            logits, _, aux = apply(params, batch, mode="train")
            labels = batch["labels"]
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            ll = jnp.take_along_axis(lp, labels[:, None], -1)[:, 0]
            loss = -jnp.mean(ll)
            return loss, {"loss": loss}

        h, _, aux = apply(params, batch, mode="train")
        # run the final norm from the head block
        ctx = Ctx(mode="train", plan=plan)
        if plan.rules is not None:
            ctx.constrain = plan.rules.constrain_act
        env = {"h": h}
        hp = params.get("head", {})
        ops = head_block.ops
        for op in ops:
            if op.op == "unembed":
                break
            args = [env[i] for i in op.ins]
            env[op.out] = OPS[op.op](ctx, op,
                                     _param_slice(op, hp, None), *args)
        hn = env[ops[-1].ins[0]] if ops[-1].op == "unembed" else env["h"]
        un = ops[-1]
        table = (params[un.attrs["tied"].split("/")[0]]["table"]
                 if un.attrs.get("tied") else hp["lm_head"])
        labels = batch["labels"]
        loss, acc = _chunked_ce(ctx, hn, table, labels, cfg.vocab_size,
                                plan.tiles.get("ce_chunk", 256))
        total = loss + sum(aux.get(k, 0.0) for k in AUX_KEYS)
        return total, {"loss": loss, "acc": acc,
                       **{k: aux[k] for k in aux}}

    return loss_fn


def _chunked_ce(ctx, h, table, labels, true_vocab, chunk):
    B, S, d = h.shape
    Vp = table.shape[0]
    dt = ctx.compute_dtype
    while S % chunk:
        chunk //= 2
    chunk = max(chunk, 1)
    nc = S // chunk
    hs = h.reshape(B, nc, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(B, nc, chunk).swapaxes(0, 1)
    vmask = (jnp.arange(Vp) < true_vocab)

    def one(args):
        hc, lc = args
        logits = jnp.einsum("bcd,vd->bcv", hc.astype(dt), table.astype(dt),
                            preferred_element_type=jnp.float32)
        logits = jnp.where(vmask, logits, -1e9)
        logits = ctx.cst(logits, ("batch", "none", "vocab"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        oh = jax.nn.one_hot(lc, Vp, dtype=logits.dtype)
        ll = jnp.einsum("bcv,bcv->bc", logits, oh)
        valid = (lc >= 0).astype(jnp.float32)
        nll = (lse - ll) * valid
        correct = (jnp.argmax(logits, -1) == lc).astype(jnp.float32) * valid
        return (jnp.sum(nll), jnp.sum(valid), jnp.sum(correct))

    # remat per chunk: the (B, chunk, V) logits block is recomputed in the
    # backward pass instead of being saved — full logits never exist in HBM.
    nll, cnt, cor = lax.map(jax.checkpoint(one, prevent_cse=False), (hs, ls))
    denom = jnp.maximum(jnp.sum(cnt), 1.0)
    return jnp.sum(nll) / denom, jnp.sum(cor) / denom
