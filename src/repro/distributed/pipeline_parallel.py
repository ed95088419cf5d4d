"""Cross-pod pipeline parallelism — the paper's channelization (CH) on TPU.

In pipelined execution the paper keeps every layer's kernel resident and
streams activations through OpenCL channels.  Across pods, the analogue is
GPipe: the folded layer stack is sharded over the ``pod`` axis (each pod owns
a contiguous run of layers), and microbatch activations stream pod→pod via
``jax.lax.ppermute`` — the ICI link is the channel, the number of in-flight
microbatches is the channel depth.  Inside the shard_map only ``pod`` is
manual; ``data``/``model`` sharding stays automatic (GSPMD), so FSDP/TP
compose with the pipeline.

Applies to plans whose layers fold into a single scan group with
``reps % n_stages == 0`` (true for all ten assigned archs on a 2-pod mesh).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import lowering
from repro.core.graph import Graph
from repro.core.ops_impl import OPS, Ctx
from repro.core.plan import ExecutionPlan


def _single_fold_unit(plan: ExecutionPlan):
    folded = [u for u in plan.units if u.folded]
    assert len(folded) == 1, (
        "pipeline mode requires a single folded layer group; got "
        f"{len(folded)} (use folded execution instead)")
    return folded[0]


def make_pipeline_loss(plan: ExecutionPlan, mesh, n_microbatches: int,
                       pp_axis: Optional[str] = None):
    """Returns loss(params, batch) running a GPipe schedule over ``pp_axis``.

    params uses the standard lowering layout; the folded group's stacked
    params are sharded over ``pp_axis`` on their layer dim.  The stage
    assignment comes from the plan's recorded ShardingPlan when present
    (``plan.sharding`` — the ShardingPass's decision); ``pp_axis`` and the
    stage count then must agree with the runtime mesh.
    """
    graph = plan.graph
    unit = _single_fold_unit(plan)
    ukey = lowering.unit_key(graph, unit)
    sp = plan.sharding
    if pp_axis is None:
        pp_axis = sp.pp_axis if sp is not None and sp.pp_axis else "pod"
    n_stages = mesh.shape[pp_axis]
    if sp is not None and sp.pp_axis == pp_axis and sp.n_stages > 1:
        assert sp.n_stages == n_stages, (
            f"plan assigned {sp.n_stages} pipeline stages but mesh axis "
            f"{pp_axis!r} has size {n_stages}")
        assert len(sp.stage_of_layer) == unit.reps, (sp.stage_of_layer,
                                                     unit.reps)
        # the GPipe layout below shards the stacked layer dim evenly over
        # pp_axis — exactly the contiguous equal runs the pass assigns
        per = unit.reps // n_stages
        assert sp.stage_of_layer == tuple(r // per for r in range(unit.reps))
    assert unit.reps % n_stages == 0, (unit.reps, n_stages)
    nmb = n_microbatches
    cfg = plan.cfg
    protos = [graph.blocks[i] for i in unit.indices[:unit.period]]
    embed_block = graph.blocks[0]
    head_block = graph.blocks[-1]

    def run_stage_layers(gparams, h):
        outer = Ctx(mode="train", plan=plan)

        def body(carry, step_params):
            c = Ctx(mode="train", plan=plan)
            c.state_in = {}
            c.state_out = {}
            e = {"h": carry, "positions": None, "cross": None}
            S = carry.shape[1]
            e["positions"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (carry.shape[0], S))
            for j, blk in enumerate(protos):
                e["h"] = lowering._run_block(c, blk, step_params, e,
                                             "train", j=j)
            return e["h"], None
        body = jax.checkpoint(body, prevent_cse=False)
        h, _ = jax.lax.scan(body, h, gparams)
        return h

    def embed(eparams, tokens):
        ctx = Ctx(mode="train", plan=plan)
        env = {"h": tokens,
               "positions": jnp.broadcast_to(
                   jnp.arange(tokens.shape[1], dtype=jnp.int32),
                   tokens.shape)}
        return lowering._run_block(ctx, embed_block, eparams, env, "train")

    def head_loss(hparams, tied, h, labels):
        ctx = Ctx(mode="train", plan=plan)
        env = {"h": h}
        for op in head_block.ops:
            if op.op == "unembed":
                break
            args = [env[i] for i in op.ins]
            env[op.out] = OPS[op.op](
                ctx, op, lowering._param_slice(op, hparams, None), *args)
        un = head_block.ops[-1]
        hn = env[un.ins[0]]
        table = tied if un.attrs.get("tied") else hparams["lm_head"]
        loss, _ = lowering._chunked_ce(ctx, hn, table, labels,
                                       cfg.vocab_size,
                                       plan.tiles.get("ce_chunk", 256))
        return loss

    def pipe(params, tokens_mb, labels_mb):
        """Runs inside shard_map; pod axis manual."""
        ax = jax.lax.axis_index(pp_axis)
        gparams = params[ukey]                     # layer dim already local
        eparams = params.get(embed_block.name, {})
        hparams = params.get(head_block.name, {})
        tied = params[embed_block.name]["table"] \
            if head_block.ops[-1].attrs.get("tied") else 0.0
        B, S = tokens_mb.shape[1], tokens_mb.shape[2]
        d = cfg.d_model
        dt = jnp.bfloat16 if plan.flow.precision == "bf16" else jnp.float32
        T = nmb + n_stages - 1
        perm = [(s, s + 1) for s in range(n_stages - 1)]

        def step(carry, t):
            h_out_prev, loss_acc = carry
            h_in = jax.lax.ppermute(h_out_prev, pp_axis, perm)
            mb = t - ax
            mb_c = jnp.clip(mb, 0, nmb - 1)
            toks = jax.lax.dynamic_index_in_dim(tokens_mb, mb_c, 0, False)
            labs = jax.lax.dynamic_index_in_dim(labels_mb, mb_c, 0, False)
            x = jax.lax.cond(ax == 0,
                             lambda: embed(eparams, toks).astype(dt),
                             lambda: h_in)
            h_out = run_stage_layers(gparams, x)
            # the accumulator is rank-1 so the per-stage partial loss can
            # leave the region sharded over pp_axis (out_specs P(pp_axis))
            lmb = jax.lax.cond(
                jnp.logical_and(ax == n_stages - 1,
                                jnp.logical_and(mb >= 0, mb < nmb)),
                lambda: head_loss(hparams, tied, h_out, labs).reshape(1),
                lambda: jnp.zeros((1,), jnp.float32))
            return (h_out, loss_acc + lmb), None

        h0 = jnp.zeros((B, S, d), dt)
        (_, loss), _ = jax.lax.scan(step, (h0, jnp.zeros((1,), jnp.float32)),
                                    jnp.arange(T, dtype=jnp.int32))
        # per-stage partial loss (non-zero on the last stage only), returned
        # sharded over pp_axis and summed outside the manual region, so the
        # region needs no psum
        return loss / nmb

    # shard_map wiring: stacked layer params split over pod; rest replicated
    def pspec_for(path_key: str):
        return P(pp_axis) if path_key == ukey else P()

    in_specs = ({k: jax.tree.map(lambda _: P(pp_axis), v) if k == ukey
                 else jax.tree.map(lambda _: P(), v)
                 for k, v in lowering.param_shapes(plan).items()},
                P(), P())

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        B = tokens.shape[0]
        assert B % nmb == 0
        tmb = tokens.reshape(nmb, B // nmb, -1)
        lmb = labels.reshape(nmb, B // nmb, -1)
        from repro.core.compat import shard_map
        f = shard_map(pipe, mesh, in_specs, P(pp_axis),
                      axis_names={pp_axis})
        return jnp.sum(f(params, tmb, lmb))

    return loss_fn
