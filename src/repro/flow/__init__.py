"""repro.flow — the single public entry point of the compilation flow.

The paper's contract is *frozen model in, optimized accelerator out*; this
package is that front door for the repro stack::

    from repro import flow

    cm = flow.compile("llama3.2-1b", "decode_32k", smoke=True)
    params = cm.init_params(jax.random.key(0))
    tokens, state = cm.generate(params, {"tokens": prompt}, steps=16)
    print(cm.describe())

``compile()`` runs the pass pipeline (optionally the design-space explorer)
and returns a :class:`CompiledModel` that owns the :class:`ExecutionPlan`,
the jitted ``train_step`` / ``prefill`` / ``decode`` / ``generate``
callables, ``init_params`` / ``init_state``, per-stage compile stats, and a
``describe()`` mirroring the paper's flow report.  Kernel-backend selection
happens behind it through the :class:`~repro.kernels.registry.KernelRegistry`
(``backend="auto"`` resolves per op: Pallas where the platform compiles it
natively, the reference path elsewhere).

Everything downstream (``launch/*``, ``serving.engine.Engine``,
``examples/*``) consumes a ``CompiledModel``; ``build_plan`` / ``make_apply``
remain as deprecated shims.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, get_smoke
from repro.configs.base import FlowConfig, ModelConfig, ShapeConfig
from repro.core import lowering
from repro.core.plan import ExecutionPlan, _build_plan
from repro.distributed.meshspec import MeshSpec
from repro.obs import TRACER
from repro.obs.trace import bridge

__all__ = ["compile", "CompiledModel", "MeshSpec"]

# every repro.obs span also lands in a JAX profiler session's trace, on the
# profiler's clock, while one records
bridge(jax.profiler.TraceAnnotation.is_enabled, jax.profiler.TraceAnnotation)


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


class CompiledModel:
    """The product of :func:`compile`: an ExecutionPlan plus the executable
    surface lowered from it.

    Jitted stages (``prefill``/``decode``/``train_step``/``generate_fori``)
    are built lazily and cached; the wall-clock of each stage's first
    invocation (trace + XLA compile) is recorded in ``stats["stages"]`` —
    the per-stage analogue of the paper's per-optimization build report.
    """

    def __init__(self, plan: ExecutionPlan, *, mesh=None,
                 explore_result=None, build_s: float = 0.0):
        self.plan = plan
        self.cfg: ModelConfig = plan.cfg
        self.flow: FlowConfig = plan.flow
        self.shape: ShapeConfig = plan.shape
        self.mesh = mesh
        self.rules = plan.rules
        self.explore_result = explore_result
        self.stats: Dict[str, Any] = {
            "plan_build_s": round(build_s, 4),
            "pass_timings_ms": dict(plan.pass_timings_ms),
            "stages": {},
        }
        self._apply = None
        self._loss_fn = None
        self._stages: Dict[str, Callable] = {}
        self._train_steps: Dict[Tuple[int, int], Callable] = {}

    @classmethod
    def from_plan(cls, plan: ExecutionPlan, mesh=None) -> "CompiledModel":
        """Wrap an already-built plan (legacy-path interop)."""
        return cls(plan, mesh=mesh)

    # -- lowering primitives -------------------------------------------------
    @property
    def apply(self) -> Callable:
        """apply(params, batch, state=None, cache_index=None, mode=...) ->
        (out, new_state, aux) — the un-jitted lowered program."""
        if self._apply is None:
            self._apply = lowering._make_apply(self.plan)
        return self._apply

    @property
    def loss_fn(self) -> Callable:
        if self._loss_fn is None:
            self._loss_fn = lowering.make_loss_fn(self.plan)
        return self._loss_fn

    def init_params(self, rng):
        return lowering.init_params(self.plan, rng)

    def init_state(self, batch_size: int, **kw):
        return lowering.init_state(self.plan, batch_size, **kw)

    def param_shapes(self):
        return lowering.param_shapes(self.plan)

    # -- jitted stages -------------------------------------------------------
    def _mesh_ctx(self):
        return self.mesh if self.mesh is not None else _nullcontext()

    def _wrap_timed(self, name: str, jfn: Callable) -> Callable:
        """Record the wall-clock of the stage's first call (trace + XLA
        compile) into ``stats['stages']``, and run every call in a
        ``model.<stage>`` span: the host side of the call, which returns
        once the work is dispatched."""
        label = "model." + name.split("[")[0]

        def fn(*args, **kw):
            with TRACER.span(label, cat="model"):
                st = self.stats["stages"]
                if name not in st:
                    sp = TRACER.timed(f"stage.{name}", cat="stage")
                    out = jfn(*args, **kw)
                    jax.block_until_ready(out)
                    sp.end()
                    st[name] = {"first_call_s": round(sp.elapsed_s, 4)}
                    return out
                return jfn(*args, **kw)
        return fn

    def _stage(self, name: str, build: Callable[[], Callable]) -> Callable:
        fn = self._stages.get(name)
        if fn is None:
            fn = self._wrap_timed(name, build())
            self._stages[name] = fn
        return fn

    @property
    def prefill(self) -> Callable:
        """Jitted prefill(params, batch) -> (logits, state, aux)."""
        def build():
            apply = self.apply
            with self._mesh_ctx():
                return jax.jit(lambda p, b: apply(p, b, mode="prefill"))
        return self._stage("prefill", build)

    @property
    def decode(self) -> Callable:
        """Jitted decode(params, batch, state, cache_index) ->
        (logits, new_state, aux); the state argument is donated."""
        def build():
            apply = self.apply
            with self._mesh_ctx():
                return jax.jit(
                    lambda p, b, st, i: apply(p, b, state=st, cache_index=i,
                                              mode="decode"),
                    donate_argnums=(2,))
        return self._stage("decode", build)

    def train_step(self, opt, microbatches: Optional[int] = None) -> Callable:
        """Jitted, donated train step for ``opt``:
        step(params, opt_state, batch) -> (params, opt_state, metrics)."""
        mb = microbatches if microbatches is not None \
            else max(self.flow.microbatches, 1)
        key = (id(opt), mb)
        fn = self._train_steps.get(key)
        if fn is None:
            from repro.train.trainer import make_train_step
            raw = make_train_step(self.plan, opt, microbatches=mb)
            with self._mesh_ctx():
                jfn = jax.jit(raw, donate_argnums=(0, 1))
            fn = self._wrap_timed(f"train_step[mb={mb}]", jfn)
            self._train_steps[key] = fn
        return fn

    # -- generation ----------------------------------------------------------
    def _sample(self, logits, rng, temperature: float):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            rng, logits / temperature, axis=-1).astype(jnp.int32)

    def generate(self, params, batch: Dict[str, Any], steps: int, *,
                 temperature: float = 0.0, seed: int = 0
                 ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """Prefill the prompt batch, then decode ``steps`` tokens through the
        jitted donated decode stage (host-side sampling loop)."""
        S = batch["tokens"].shape[1]
        logits, state, _ = self.prefill(params, batch)
        rng = jax.random.key(seed)
        tok = self._sample(logits[:, -1], rng, temperature)
        out = [tok]
        for t in range(steps - 1):
            rng, k = jax.random.split(rng)
            lg, state, _ = self.decode(params, {"tokens": tok[:, None]},
                                       state, jnp.int32(S + t))
            tok = self._sample(lg[:, -1], k, temperature)
            out.append(tok)
        return jnp.stack(out, axis=1), state

    def generate_fori(self, params, batch: Dict[str, Any],
                      steps: int) -> jnp.ndarray:
        """Fully on-device greedy generation: prefill plus the whole decode
        loop as one jitted program (the paper's autorun analogue)."""
        S = batch["tokens"].shape[1]
        apply = self.apply

        def build():
            def run(params, batch):
                logits, state, _ = apply(params, batch, mode="prefill")
                tok0 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                B = tok0.shape[0]
                toks = jnp.zeros((B, steps), jnp.int32)
                toks = toks.at[:, 0].set(tok0)

                def body(t, carry):
                    toks, state = carry
                    cur = jax.lax.dynamic_slice_in_dim(toks, t, 1, axis=1)
                    lg, state, _ = apply(params, {"tokens": cur}, state=state,
                                         cache_index=(S + t).astype(jnp.int32),
                                         mode="decode")
                    nxt = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
                    toks = jax.lax.dynamic_update_slice_in_dim(
                        toks, nxt[:, None], t + 1, axis=1)
                    return toks, state

                toks, _ = jax.lax.fori_loop(0, steps - 1, body, (toks, state))
                return toks

            with self._mesh_ctx():
                return jax.jit(run)

        return self._stage(f"generate_fori[{S}+{steps}]", build)(params, batch)

    def decode_segment(self, steps: int, *,
                       temperature: float = 0.0) -> Callable:
        """Jitted host-free multi-tick decode over externally managed state
        (the serving engine's paged KV pool):

            run(params, state, tok0, pos0, rng) -> (tokens, new_state, rng)

        ``tok0``/``pos0`` are (B,) int32 — each row's last sampled token and
        its absolute position; ``tokens`` is (B, steps).  The body replays
        the engine's per-tick host loop exactly — decode cell, then one
        ``jax.random.split`` per tick, then sample — so the produced tokens
        are byte-identical to ``steps`` host ticks (including the rng stream
        at temperature > 0), with a single device round-trip for the whole
        segment instead of one per token.  ``state`` is donated."""
        apply = self.apply
        sample = self._sample

        def build():
            def run(params, state, tok0, pos0, rng):
                B = tok0.shape[0]
                toks = jnp.zeros((B, steps), jnp.int32)

                def body(t, carry):
                    toks, state, rng, cur, pos = carry
                    lg, state, _ = apply(
                        params, {"tokens": cur[:, None],
                                 "positions": pos[:, None]},
                        state=state, cache_index=jnp.int32(0), mode="decode")
                    rng, k = jax.random.split(rng)
                    nxt = sample(lg[:, -1], k, temperature)
                    toks = jax.lax.dynamic_update_slice_in_dim(
                        toks, nxt[:, None], t, axis=1)
                    return toks, state, rng, nxt, pos + 1

                toks, state, rng, _, _ = jax.lax.fori_loop(
                    0, steps, body, (toks, state, rng, tok0, pos0))
                return toks, state, rng

            with self._mesh_ctx():
                return jax.jit(run, donate_argnums=(1,))

        return self._stage(
            f"decode_segment[T={steps},temp={temperature}]", build)

    # -- measured-time validation --------------------------------------------
    def _measure_inputs(self, seed: int = 0) -> Dict[str, Any]:
        """Concrete random inputs matching the cell's abstract shapes."""
        import numpy as np
        from repro.core.dse import abstract_inputs
        rng = np.random.RandomState(seed)
        out = {}
        for k, sds in abstract_inputs(self.cfg, self.shape).items():
            if sds.dtype == jnp.int32:
                out[k] = jnp.asarray(
                    rng.randint(0, self.cfg.vocab_size, sds.shape), jnp.int32)
            else:
                out[k] = jnp.asarray(rng.randn(*sds.shape), sds.dtype)
        return out

    def measure(self, stage: Optional[str] = None, iters: int = 3, *,
                seed: int = 0, trace_dir: Optional[str] = None
                ) -> Dict[str, Any]:
        """Wall-clock one stage of this compiled cell: AOT-compile it
        (recording ``per_device_bytes`` from ``memory_analysis()``), run it
        once to warm up, then time ``iters`` steps and report the best and
        mean.  ``stage`` defaults to the shape cell's kind (train -> the
        donated train step, prefill/decode -> the serving stages).  This is
        the DSE's measured-time validator (``validate="measure"``) — the
        on-device confirmation the paper got from hours of place & route.

        ``trace_dir`` brackets the timed loop in ``jax.profiler.trace``;
        the host-side ``measure.step`` spans land in that profile beside
        the device's operations (``repro.obs`` profiler bridge).
        """
        stage = stage if stage is not None else self.shape.kind
        B = self.shape.global_batch
        batch = self._measure_inputs(seed)
        if stage == "train":
            from repro.optim.adamw import AdamW
            from repro.train.trainer import make_train_step
            opt = AdamW()
            raw = make_train_step(self.plan, opt,
                                  microbatches=max(self.flow.microbatches, 1))
            params = self.init_params(jax.random.key(seed))
            args = [params, opt.init(params), batch]
            fn, donate = raw, (0, 1)
            def carry(out, args):      # re-feed donated params/opt state
                return [out[0], out[1], args[2]]
        elif stage == "decode":
            apply = self.apply
            params = self.init_params(jax.random.key(seed))
            state = self.init_state(B)
            tok = batch["tokens"].reshape(B, 1)

            def fn(p, b, st, i):
                logits, new_state, _ = apply(p, b, state=st, cache_index=i,
                                             mode="decode")
                return logits, new_state
            args = [params, {"tokens": tok}, state, jnp.int32(0)]
            donate = (2,)
            def carry(out, args):
                return [args[0], args[1], out[1], args[3] + 1]
        elif stage == "prefill":
            apply = self.apply
            params = self.init_params(jax.random.key(seed))
            fn = lambda p, b: apply(p, b, mode="prefill")[0]  # noqa: E731
            args = [params, batch]
            donate = ()
            def carry(out, args):
                return args
        else:
            raise ValueError(f"unknown stage {stage!r}; "
                             "expected train | prefill | decode")

        from repro.core.dse import per_device_bytes
        sp_compile = TRACER.timed("measure.compile", cat="measure",
                                  stage=stage)
        with self._mesh_ctx():
            compiled = jax.jit(fn, donate_argnums=donate).lower(
                *args).compile()
        sp_compile.end()
        compile_s = sp_compile.elapsed_s
        mem = compiled.memory_analysis()
        args = carry(compiled(*args), args)          # warm-up (not timed)
        jax.block_until_ready(args)
        times = []
        prof_ctx = jax.profiler.trace(trace_dir) if trace_dir \
            else _nullcontext()
        with prof_ctx:
            for _ in range(max(iters, 1)):
                sp = TRACER.timed("measure.step", cat="measure", stage=stage)
                out = compiled(*args)
                jax.block_until_ready(out)
                sp.end()
                times.append(sp.elapsed_s)
                args = carry(out, args)
        rec = {"stage": stage, "iters": len(times),
               "compile_s": round(compile_s, 4),
               "measured_step_s": min(times),
               "mean_step_s": sum(times) / len(times),
               "per_device_bytes": per_device_bytes(mem),
               "temp_bytes": mem.temp_size_in_bytes,
               "argument_bytes": mem.argument_size_in_bytes}
        self.stats.setdefault("measure", {})[stage] = rec
        return rec

    # -- reporting -----------------------------------------------------------
    def describe(self, stats: bool = False) -> str:
        """The flow report: plan summary (passes, units, tiles, kernel
        backends), DSE outcome when autotuned, and per-stage compile stats."""
        lines = [self.plan.describe(stats=stats)]
        if self.explore_result is not None:
            er = self.explore_result
            lines.append(f"  dse: best=[{er.best.knob_str()}] "
                         f"enumerated={er.n_enumerated} "
                         f"validated={len(er.validated)}")
        if stats and self.stats["stages"]:
            parts = [f"{k}={v['first_call_s']}s"
                     for k, v in self.stats["stages"].items()]
            lines.append("  stages: " + " ".join(parts))
        return "\n".join(lines)

    def __repr__(self) -> str:
        return (f"<CompiledModel {self.cfg.name} x {self.shape.name} "
                f"backend={self.flow.kernel_backend}>")


def _resolve_cfg(arch_or_cfg: Union[str, ModelConfig],
                 smoke: bool) -> ModelConfig:
    if isinstance(arch_or_cfg, str):
        return get_smoke(arch_or_cfg) if smoke else get_config(arch_or_cfg)
    return arch_or_cfg


def _resolve_shape(shape: Union[str, ShapeConfig]) -> ShapeConfig:
    if isinstance(shape, str):
        try:
            return SHAPES[shape]
        except KeyError:
            raise KeyError(f"unknown shape {shape!r}; known: "
                           f"{list(SHAPES)}") from None
    return shape


def _rules_for(mesh, flow: FlowConfig):
    from repro.core.passes.sharding import split_roles
    from repro.distributed.sharding import ShardingRules
    split = tuple((a, int(mesh.shape[a])) for a in mesh.axis_names)
    dp, tp, _pp = split_roles(flow, split)
    return ShardingRules(mesh, dp=dp or ("data",), tp=tp)


def _resolve_mesh(mesh) -> Tuple[Optional[Any], Optional[MeshSpec]]:
    """(runtime jax Mesh, MeshSpec) from any accepted mesh spelling.  A
    MeshSpec / axis-size dict is bound to the local devices; a live Mesh is
    passed through."""
    if mesh is None:
        return None, None
    spec = MeshSpec.of(mesh)
    if hasattr(mesh, "devices"):            # already a live jax Mesh
        return mesh, spec
    return spec.build(), spec


def compile(arch_or_cfg: Union[str, ModelConfig],
            shape: Union[str, ShapeConfig],
            flow: Optional[FlowConfig] = None, *,
            backend: str = "auto",
            autotune: bool = False,
            mesh=None,
            validate: str = "compile",
            verify: bool = False,
            smoke: bool = False) -> CompiledModel:
    """Compile one (model, shape) cell through the whole flow.

    Args:
      arch_or_cfg: registry arch name (``"llama3.2-1b"``) or a ModelConfig.
      shape: shape-cell name from ``repro.configs.SHAPES`` or a ShapeConfig.
      flow: FlowConfig knobs; defaults to ``FlowConfig(mode="folded")``.
      backend: kernel-backend policy (``auto`` | ``reference`` | ``pallas`` |
        ``pallas_interpret``).  A non-``auto`` value overrides the flow's
        ``kernel_backend``; the default keeps the flow's own setting.
      autotune: run the design-space explorer (estimator-pruned,
        compile-validated; results are cached per (cfg, shape, flow, mesh)
        fingerprint) and compile the winning flow.
      mesh: the device mesh — a :class:`MeshSpec`, an axis-size dict
        (``{"data": 2, "model": 2}``), or a live jax Mesh.  The factorization
        is recorded on the flow (``mesh_split``), the ShardingPass writes the
        partitioning decisions onto the plan, and the runtime binds them via
        ShardingRules (``model`` TP, other axes DP, ``flow.pp_axis`` PP).
      validate: with ``autotune``, how the top-k survivors are confirmed:
        ``"compile"`` (lower+compile+memory_analysis, the default) or
        ``"measure"`` (AOT-compile *and* wall-clock the stage via
        :meth:`CompiledModel.measure`, ranking survivors by measured step
        time).
      verify: run the static plan verifier (:func:`repro.analysis.verify_plan`)
        over the built plan *before any jit*.  The result is recorded on
        ``plan.verification`` (one ``verify:`` line in ``describe()``); any
        error-severity diagnostic raises
        :class:`~repro.analysis.PlanVerificationError` carrying the full
        diagnostic list.
      smoke: with a string arch, select the reduced (CPU-runnable) config.
    """
    cfg = _resolve_cfg(arch_or_cfg, smoke)
    shape = _resolve_shape(shape)
    flow = flow if flow is not None else FlowConfig(mode="folded")
    if backend != "auto" and backend != flow.kernel_backend:
        flow = dataclasses.replace(flow, kernel_backend=backend)
    if validate not in ("compile", "measure"):
        raise ValueError(f"unknown validate mode {validate!r}; "
                         "expected 'compile' | 'measure'")

    mesh_obj, mesh_spec = _resolve_mesh(mesh)
    if mesh_spec is not None and flow.mesh_split != mesh_spec.axes:
        flow = dataclasses.replace(flow, mesh_split=mesh_spec.axes)

    explore_result = None
    sp_build = TRACER.timed("flow.build", cat="compile", arch=cfg.name,
                            autotune=autotune)
    if autotune:
        from repro.core import dse
        n_dev = mesh_spec.size if mesh_spec is not None else 1
        if validate == "measure":
            validator = dse.measure_validator(cfg, shape, mesh=mesh_obj)
        else:
            validator = dse.compile_validator(cfg, shape)
        explore_result = dse.explore(
            cfg, shape, flow, devices=n_dev, validator=validator,
            rank_measured=validate == "measure")
        flow = explore_result.best.flow

    rules = None
    mesh_axes: Tuple[str, ...] = ()
    if mesh_obj is not None:
        rules = _rules_for(mesh_obj, flow)
        mesh_axes = tuple(mesh_obj.axis_names)

    if explore_result is not None and mesh_obj is None:
        plan = explore_result.plan          # already built for the best flow
    else:
        plan = _build_plan(cfg, flow, shape, mesh_axes=mesh_axes, rules=rules)
    if verify:
        from repro.analysis import PlanVerificationError, verify_plan
        result = verify_plan(plan)
        plan.verification = result
        if not result.ok:                   # gate: no jit for a bad plan
            raise PlanVerificationError(result)
    sp_build.end()
    return CompiledModel(plan, mesh=mesh_obj, explore_result=explore_result,
                         build_s=sp_build.elapsed_s)
