"""KernelRegistry — pluggable per-op kernel-backend selection.

The paper's flow emits one accelerator per network; end-to-end compilers that
followed it (DNNVM's heterogeneous ISA mapping, the FPGA-CNN survey's
backend taxonomy) put a *registry* between the op layer and the kernel
implementations: each op may have several implementations, keyed by backend,
each guarded by a capability predicate, and the flow resolves the pair at
plan-build time.

This module is that seam for the repro stack:

* implementations register under ``(op, backend)`` with backends drawn from
  ``{"ref", "pallas"}`` — ``pallas_interpret`` is the Pallas implementation
  executed through the interpreter (CPU validation), not a separate entry;
* every op in :data:`repro.core.ops_impl.OPS` implicitly owns a ``ref``
  entry (the pure-XLA implementation *is* the reference backend);
* ``resolve(op, "auto")`` picks per op: Pallas where a Pallas implementation
  exists and the platform runs Mosaic (TPU), the reference path elsewhere;
* the resolution for a whole plan (:meth:`KernelRegistry.resolve_all`) is
  recorded on the ``ExecutionPlan`` by the ``kernels`` pass, shows up in
  ``plan.describe()`` and is a DSE tunable (``FlowConfig.kernel_backend``).

Call-site capability predicates (dtype/rank/attribute constraints that are
only known with concrete operands) are checked at dispatch time by
:func:`plan_kernel`; a failing predicate falls back to the reference path
with a machine-readable reason (``DISPATCH_REJECTIONS`` counts them, and
the static verifier surfaces the statically-decidable ones as ``K204``
diagnostics via each impl's declared :class:`KernelContract`).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

from repro.obs import METRICS

BACKENDS = ("ref", "pallas", "pallas_interpret", "auto")

_ALIASES = {"reference": "ref", "ref": "ref", "pallas": "pallas",
            "pallas_interpret": "pallas_interpret", "auto": "auto"}


def canon_backend(name: str) -> str:
    """Canonical backend name (``reference`` → ``ref``)."""
    try:
        return _ALIASES[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel backend {name!r}; expected one of "
            f"{sorted(set(_ALIASES))}") from None


def _default_platform() -> str:
    import jax
    return jax.default_backend()


@dataclass(frozen=True)
class KernelContract:
    """The statically-checkable contract a kernel impl declares; consumed by
    :mod:`repro.analysis` (``verify_plan``) without compiling anything.

    * ``tile_key``/``workingset`` — which ``plan.tiles`` entry the kernel's
      BlockSpecs come from and its VMEM working set ``(tile, cfg) -> bytes``
      (checked against ``flow.vmem_budget_bytes``: K202);
    * ``donation_safe`` — whether the kernel's ``input_output_aliases`` use
      is safe under donated state (a donation-unsafe kernel under
      ``cache.donate_state`` is K203);
    * ``index_space`` — ``"block_table"`` marks a scalar-prefetch gather
      whose indices must stay inside the paged pool (K205 checks the pool
      geometry on the serving side);
    * ``static_reject`` — the statically-decidable part of the capability
      predicate, ``(op_attrs, cfg) -> Optional[reason]``: a non-None reason
      means dispatch will silently fall back to ref (surfaced as K204);
    * ``tile_candidates`` — ``(cfg, shape) -> tuple of tiles``: the
      kernel's searchable tile schedules (e.g. ``(block_q, block_kv)``
      pairs for flash attention).  Declaring it makes the ``tile_key``
      entry a recordable, warm-startable tunable: the serving autotune's
      ``tune_kernel_tiles`` benches each candidate through
      ``FlowConfig.tile_overrides`` and banks the winner in the tunedb."""
    tile_key: Optional[str] = None
    workingset: Optional[Callable[[Any, Any], int]] = None
    donation_safe: bool = True
    index_space: Optional[str] = None
    static_reject: Optional[Callable[[Dict[str, Any], Any],
                                     Optional[str]]] = None
    tile_candidates: Optional[Callable[[Any, Any],
                                       Tuple[Any, ...]]] = None


@dataclass(frozen=True)
class KernelImpl:
    """One registered kernel implementation.

    ``supports`` is the call-site capability predicate: it receives the
    keyword facts the op layer passes to :func:`plan_kernel` (operand arrays,
    attrs like ``groups``/``window``) and returns whether this implementation
    can handle them.  ``rejects`` is its machine-readable form — same facts
    in, ``None`` (accepted) or a reason string out; when registered,
    ``supports`` is derived from it.  ``platforms`` gates plan-time
    resolution (a Pallas kernel compiled through Mosaic is TPU-only; in
    interpret mode it runs anywhere).  ``contract`` is the declared static
    contract the verifier checks (see :class:`KernelContract`)."""
    op: str
    backend: str
    fn: Callable
    supports: Callable[..., bool] = field(default=lambda **kw: True)
    platforms: Tuple[str, ...] = ("cpu", "gpu", "tpu")
    rejects: Optional[Callable[..., Optional[str]]] = None
    contract: Optional[KernelContract] = None

    def reject_reason(self, **facts) -> Optional[str]:
        """``None`` when this impl can serve the call-site facts, else the
        machine-readable reason dispatch falls back to the reference path."""
        if self.rejects is not None:
            return self.rejects(**facts)
        if self.supports(**facts):
            return None
        return f"capability predicate rejected {self.op}/{self.backend}"

    def __repr__(self) -> str:
        return f"<KernelImpl {self.op}/{self.backend}>"


class KernelRegistry:
    """Maps ``(op, backend)`` → :class:`KernelImpl` and resolves backends."""

    def __init__(self):
        self._impls: Dict[Tuple[str, str], KernelImpl] = {}

    # -- registration -------------------------------------------------------
    def register(self, op: str, backend: str, fn: Optional[Callable] = None,
                 *, supports: Optional[Callable[..., bool]] = None,
                 rejects: Optional[Callable[..., Optional[str]]] = None,
                 contract: Optional[KernelContract] = None,
                 platforms: Tuple[str, ...] = ("cpu", "gpu", "tpu")):
        """Register ``fn`` as the ``backend`` implementation of ``op``.
        Usable directly or as a decorator.  ``rejects`` is the machine-
        readable capability predicate (facts -> Optional[reason]); when
        given, ``supports`` is derived from it."""
        backend = canon_backend(backend)
        if backend == "auto":
            raise ValueError("'auto' is a resolution policy, not a backend")
        if rejects is not None and supports is None:
            supports = lambda **kw: rejects(**kw) is None  # noqa: E731

        def _add(f: Callable) -> Callable:
            self._impls[(op, backend)] = KernelImpl(
                op, backend, f, supports or (lambda **kw: True), platforms,
                rejects=rejects, contract=contract)
            return f

        return _add if fn is None else _add(fn)

    # -- lookup -------------------------------------------------------------
    def _ref_ops(self) -> Dict[str, Callable]:
        from repro.core.ops_impl import OPS
        return OPS

    def ops(self) -> Tuple[str, ...]:
        """All ops the registry can resolve (reference table ∪ registered)."""
        names = set(self._ref_ops()) | {op for op, _ in self._impls}
        return tuple(sorted(names))

    def accelerated_ops(self) -> Tuple[str, ...]:
        """Ops with at least one non-reference implementation."""
        return tuple(sorted({op for (op, b) in self._impls if b != "ref"}))

    def has(self, op: str, backend: str) -> bool:
        backend = canon_backend(backend)
        if backend == "pallas_interpret":   # interpret reuses the pallas impl
            backend = "pallas"
        if backend == "ref":
            return (op, "ref") in self._impls or op in self._ref_ops()
        return (op, backend) in self._impls

    def get(self, op: str, backend: str) -> KernelImpl:
        backend = canon_backend(backend)
        key = "pallas" if backend == "pallas_interpret" else backend
        impl = self._impls.get((op, key))
        if impl is None and key == "ref":
            fn = self._ref_ops().get(op)
            if fn is not None:
                impl = KernelImpl(op, "ref", fn)
        if impl is None:
            raise KeyError(f"no {backend!r} implementation registered for "
                           f"op {op!r} (have: {self.backends(op)})")
        return impl

    def backends(self, op: str) -> Tuple[str, ...]:
        out = {b for (o, b) in self._impls if o == op}
        if op in self._ref_ops():
            out.add("ref")
        return tuple(sorted(out))

    # -- resolution ---------------------------------------------------------
    def resolve(self, op: str, backend: str = "auto",
                platform: Optional[str] = None) -> str:
        """Plan-time backend choice for one op.

        ``auto`` → Pallas where an implementation exists and the platform
        compiles it natively (TPU), reference elsewhere.  An explicit Pallas
        request degrades to ``ref`` for ops with no Pallas implementation
        (e.g. ``norm``), mirroring the old in-op string checks."""
        backend = canon_backend(backend)
        platform = platform if platform is not None else _default_platform()
        if backend == "auto":
            if (op, "pallas") in self._impls and platform == "tpu" \
                    and platform in self._impls[(op, "pallas")].platforms:
                return "pallas"
            return "ref"
        if backend in ("pallas", "pallas_interpret"):
            return backend if (op, "pallas") in self._impls else "ref"
        return "ref"

    def resolve_all(self, backend: str = "auto",
                    platform: Optional[str] = None) -> Dict[str, str]:
        """Resolution table for every known op (recorded on the plan)."""
        platform = platform if platform is not None else _default_platform()
        return {op: self.resolve(op, backend, platform) for op in self.ops()}


REGISTRY = KernelRegistry()

# dispatch-time fall-throughs to ref, keyed by (op, machine-readable reason).
# The verifier catches the statically-decidable subset (K204) at plan time;
# this counter makes the residual operand-dependent ones observable too.
DISPATCH_REJECTIONS: Dict[Tuple[str, str], int] = {}


def plan_kernel(plan, op: str, **facts) -> Optional[Tuple[Callable, bool]]:
    """Dispatch helper for the op layer.

    Returns ``(fn, interpret)`` when the plan resolves ``op`` to a Pallas
    implementation whose capability predicate accepts the call-site
    ``facts``; ``None`` means take the reference path (the reject reason is
    recorded in :data:`DISPATCH_REJECTIONS`).  Plans built by pipelines
    without the ``kernels`` pass fall back to resolving the flow's
    ``kernel_backend`` on the fly."""
    resolved = plan.kernels.get(op) if plan.kernels else None
    if resolved is None:
        resolved = REGISTRY.resolve(op, plan.flow.kernel_backend)
    if resolved not in ("pallas", "pallas_interpret"):
        return None
    impl = REGISTRY.get(op, "pallas")
    reason = impl.reject_reason(**facts)
    if reason is not None:
        key = (op, reason)
        DISPATCH_REJECTIONS[key] = DISPATCH_REJECTIONS.get(key, 0) + 1
        METRICS.counter("kernels.dispatch.rejections").inc()
        return None
    # counted at trace time, once per call site a jitted program lowers
    # through the kernel: the evidence that a plan's Pallas entries ran
    METRICS.counter(f"kernels.dispatch.{resolved}.{op}").inc()
    return impl.fn, resolved == "pallas_interpret"


# ---------------------------------------------------------------------------
# Built-in Pallas registrations (the kernels/ package)
# ---------------------------------------------------------------------------

def _matmul_reject(x=None, w=None, **kw) -> Optional[str]:
    if x is None or w is None:
        return "matmul operands not provided to the dispatch predicate"
    if not (x.ndim >= 2 and w.ndim == 2):
        return (f"operand ranks (x.ndim={x.ndim}, w.ndim={w.ndim}) need "
                "x.ndim >= 2 and w.ndim == 2")
    return None


def _attention_reject(window=None, cross=False, **kw) -> Optional[str]:
    # window == 0 is a degenerate cell some configs use to disable the
    # flash path; cross-attention caches K/V outside the kernel
    if window == 0:
        return "window=0 disables the flash path"
    if cross:
        return "cross-attention caches K/V outside the kernel"
    return None


def _attention_static_reject(attrs, cfg) -> Optional[str]:
    return _attention_reject(window=attrs.get("window"),
                             cross=attrs.get("cross", False))


def _conv2d_reject(groups=1, **kw) -> Optional[str]:
    if groups != 1:
        return f"grouped conv (groups={groups}) has no Pallas path"
    return None


def _depthwise_reject(x=None, w=None, stride=1, padding="SAME",
                      **kw) -> Optional[str]:
    from repro.kernels.depthwise_conv2d import VMEM_CAP, working_set
    if x is None or w is None:
        return "depthwise operands not provided to the dispatch predicate"
    if x.ndim != 4 or w.ndim != 4 or w.shape[2:] != (1, x.shape[-1]):
        return (f"weight {tuple(w.shape)} is not the (kh, kw, 1, C) filter "
                f"of a channel-multiplier-1 depthwise conv of {x.shape}")
    _, h, wd, c = x.shape
    if working_set(h, wd, w.shape[0], w.shape[1], stride, padding, 1,
                   min(c, 128), x.dtype.itemsize) > VMEM_CAP:
        return f"a {h}x{wd} image does not fit VMEM whole"
    return None


def _matmul_workingset(tile, cfg) -> int:
    # x(bm,bk) + w(bk,bn) in bf16 + fp32 accumulator + bf16 out tile —
    # the same model select_matmul_tile sizes against (passes/tiling.py)
    bm, bk, bn = tile
    return (bm * bk + bk * bn) * 2 + bm * bn * (4 + 2)


def _attention_workingset(tile, cfg) -> int:
    # q, k, v tiles + fp32 scores + fp32 accumulator
    bq, bk = tile
    hd = cfg.attention.head_dim if cfg.attention is not None else 0
    return (bq + 2 * bk) * hd * 2 + bq * bk * 4 + bq * hd * 4


def _decode_attention_workingset(tile, cfg) -> int:
    # one K and one V block of block_k positions + fp32 partials
    bk = int(tile)
    hd = cfg.attention.head_dim if cfg.attention is not None else 0
    return 2 * bk * hd * 2 + bk * 4


def _attention_tile_candidates(cfg, shape) -> Tuple[Tuple[int, int], ...]:
    """Searchable (block_q, block_kv) schedules for flash attention: the
    MXU-aligned grid around the selector's static choice, capped at the
    cell's sequence length (rule 2: blocks never exceed the problem)."""
    seq = max(int(getattr(shape, "seq_len", 128)), 128)
    qs = [q for q in (128, 256, 512) if q <= seq]
    kvs = [k for k in (128, 256, 512, 1024) if k <= seq]
    return tuple((q, k) for q in qs for k in kvs)


def _conv2d_tile_candidates(cfg, shape) -> Tuple[Tuple[int, int], ...]:
    """Searchable (block_h, block_c) schedules for the fused conv kernel:
    VPU-lane-aligned rows x channel blocks, capped at the image height."""
    h = int(getattr(cfg, "image_size", 0)) or 32
    hs = [b for b in (8, 16, 32) if b <= h]
    return tuple((bh, bc) for bh in hs for bc in (128, 256))


_MATMUL_CONTRACT = KernelContract(
    tile_key="matmul", workingset=_matmul_workingset)


def _register_builtin():
    from repro.kernels import ops as kops
    from repro.kernels.lru_scan import lru_scan

    REGISTRY.register("matmul", "pallas", kops.matmul_fused,
                      rejects=_matmul_reject, contract=_MATMUL_CONTRACT)
    REGISTRY.register("glu_matmul", "pallas", kops.matmul_fused,
                      rejects=_matmul_reject, contract=_MATMUL_CONTRACT)
    REGISTRY.register(
        "attention", "pallas", kops.flash_attention,
        rejects=_attention_reject,
        contract=KernelContract(tile_key="attention",
                                workingset=_attention_workingset,
                                static_reject=_attention_static_reject,
                                tile_candidates=_attention_tile_candidates))
    REGISTRY.register(
        "decode_attention", "pallas", kops.decode_attention,
        contract=KernelContract(tile_key="decode_attention",
                                workingset=_decode_attention_workingset))
    # paged-KV serving path: the Pallas kernel gathers pool blocks through
    # the block table (scalar prefetch); the explicit ref entry is the
    # fallback the serving engine's decode uses off-TPU.  index_space
    # declares the gather bounds contract the serving verifier checks
    # against the pool geometry (K205).
    from repro.kernels.ref import copy_block_ref, paged_decode_attention_ref
    _paged = KernelContract(index_space="block_table")
    REGISTRY.register("paged_decode_attention", "pallas",
                      kops.paged_decode_attention, contract=_paged)
    REGISTRY.register("paged_decode_attention", "ref",
                      paged_decode_attention_ref, contract=_paged)
    # prefix-cache copy-on-write fork: one pool block copied over another.
    # input_output_aliases donates the pool in place; safe because the COW
    # call site always copies src -> freshly-allocated dst (never aliased).
    _copy = KernelContract(index_space="block_table", donation_safe=True)
    REGISTRY.register("copy_block", "pallas", kops.copy_block,
                      contract=_copy)
    REGISTRY.register("copy_block", "ref", copy_block_ref, contract=_copy)
    REGISTRY.register(
        "conv2d", "pallas", kops.conv2d_fused,
        rejects=_conv2d_reject,
        contract=KernelContract(
            tile_key="conv2d",
            static_reject=lambda attrs, cfg:
                _conv2d_reject(groups=attrs.get("groups", 1)),
            tile_candidates=_conv2d_tile_candidates))
    REGISTRY.register(
        "depthwise_conv2d", "pallas", kops.depthwise_conv2d,
        rejects=_depthwise_reject,
        contract=KernelContract(tile_key="depthwise_conv2d"))
    REGISTRY.register("rg_lru", "pallas", lru_scan)


_register_builtin()
