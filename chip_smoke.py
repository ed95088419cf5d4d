"""Bring-up smoke run: the program's main path, once, on a TPU.

    python3 chip_smoke.py             # one chip: phases `serve` and `cnn`
    python3 chip_smoke.py --chips 4   # four chips: phase `train4` only

Phases, all through the entry points a user calls (``repro.flow.compile``
then ``Engine.run`` / ``CompiledModel.prefill`` / ``Trainer``), with random
weights made from ``--seed``:

* ``serve`` — llama3.2-1b at full width served by the continuous-batching
  engine (8 requests, prompts of 64-512 tokens, 32 new tokens each), then
  the same requests through ``backend="reference"`` in this process;
* ``cnn`` — ResNet-34 at 224 px, batch 8, prefill logits against
  ``backend="reference"``;
* ``train4`` — llama3.2-1b training on a ``{"data": 2, "model": 2}`` mesh:
  three AdamW steps; step-0 loss against an unsharded loss on one device.

Every phase checks its outputs (finite, in range, within the stated
tolerance of the reference), that every op with a Pallas kernel resolved to
the compiled kernel and was dispatched to it, and that no kernel dispatch
fell back to the reference path for a reason the plan did not declare.  The
times printed are smoke timings of one run, not benchmark metrics.  The last
line is ``{"ok": true, "device": {...}}``; any failure exits non-zero.

The script refuses to run without a TPU.  ``--rehearse`` is the CPU dress
rehearsal instead: the same phases at the smoke configs, with the kernels in
Pallas interpret mode (``JAX_PLATFORMS=cpu``; for ``--chips 4`` also
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  It refuses a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Tolerances, stated with their reasons.
#
# Logits: both sides run bf16 activations with f32 accumulation, but round
# at different points (the Pallas kernels keep attention probabilities and
# matmul epilogues in f32; the reference rounds them to bf16).  One bf16
# rounding is 2**-9 relative; over 16 transformer layers (or 34 conv
# layers) the independent roundings add up to a few percent of a row's
# largest logit.  The bound is the max abs difference over the row's max
# abs value.
LOGIT_RTOL = 5e-2
# How many sampled steps per request are compared: the prefill step and the
# first decode steps, while the greedy tokens so far agree (after the first
# disagreement the two runs condition on different tokens).
COMPARE_STEPS = 4
# Loss: a mean over every token of the batch averages the per-token
# rounding noise of the bf16 collectives; sharded and unsharded losses
# agree far inside 1%.
LOSS_RTOL = 1e-2
# Per-device peak memory on the mesh: a model that is really sharded puts
# about the same bytes on each chip; one that lands on the first chip shows
# a ratio of 2 or more.
PEAK_RATIO_MAX = 1.5


@dataclasses.dataclass(frozen=True)
class Sizes:
    smoke: bool                 # smoke configs (CPU rehearsal) or full width
    backend: str                # kernel backend of the system under test
    n_requests: int
    prompt_lens: tuple          # (low, high) of the synthetic prompts
    new_tokens: int
    max_batch: int
    max_seq_len: int
    block_size: int
    cnn_batch: int
    train_seq: int
    train_batch: int


FULL = Sizes(smoke=False, backend="auto", n_requests=8, prompt_lens=(64, 512),
             new_tokens=32, max_batch=8, max_seq_len=1024, block_size=16,
             cnn_batch=8, train_seq=1024, train_batch=8)
REHEARSAL = Sizes(smoke=True, backend="pallas_interpret", n_requests=4,
                  prompt_lens=(8, 24), new_tokens=4, max_batch=4,
                  max_seq_len=64, block_size=8, cnn_batch=2, train_seq=16,
                  train_batch=8)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else str(peak)


def relerr(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


# ---------------------------------------------------------------------------
# kernel-resolution evidence shared by the phases
# ---------------------------------------------------------------------------

class KernelAudit:
    """Checks, for one compiled model and the phase that runs it, that every
    op with a Pallas kernel resolved to ``expected`` (None: no such check)
    and that each op of ``must_run`` was dispatched to it, and that dispatch
    fell back to the reference path only for reasons the plan's static
    verifier declared (K204)."""

    def __init__(self, phase: str, cm, expected: str, must_run):
        from repro.kernels.registry import DISPATCH_REJECTIONS
        from repro.obs import METRICS
        self.phase, self.cm, self.expected = phase, cm, expected
        self.must_run = tuple(must_run)
        self.rejections0 = dict(DISPATCH_REJECTIONS)
        self.counts0 = {op: self._count(METRICS, op) for op in self.must_run}

    def _count(self, metrics, op: str) -> int:
        return metrics.counter(f"kernels.dispatch.{self.expected}.{op}").value

    def table(self) -> None:
        plan = self.cm.plan
        log(self.phase, f"{self.cm.cfg.name} kernel table: " + " ".join(
            f"{op}={b}" for op, b in sorted(plan.kernels.items())))
        if plan.verification is not None:
            log(self.phase, plan.verification.summary_line())

    def finish(self) -> None:
        from repro.kernels.registry import DISPATCH_REJECTIONS, REGISTRY
        from repro.obs import METRICS
        plan = self.cm.plan
        for op in REGISTRY.accelerated_ops() if self.expected else ():
            check(plan.kernels.get(op) == self.expected,
                  f"{op} resolved to {plan.kernels.get(op)!r}, not "
                  f"{self.expected!r}")
        for op in self.must_run:
            n = self._count(METRICS, op) - self.counts0[op]
            check(n > 0, f"{op} was never dispatched to its "
                         f"{self.expected} kernel")
        declared = {d.message for d in plan.verification.diagnostics
                    if d.code == "K204"}
        for (op, reason), n in DISPATCH_REJECTIONS.items():
            if n == self.rejections0.get((op, reason), 0):
                continue
            msg = f"{op} will fall back to ref at dispatch: {reason}"
            check(msg in declared,
                  f"undeclared dispatch rejection: {op}: {reason}")
            log(self.phase, f"declared fallback: {msg}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_serve(sz: Sizes, seed: int, dev) -> None:
    import jax
    import numpy as np
    from repro import flow
    from repro.configs import get_config, get_smoke
    from repro.configs.base import FlowConfig, ShapeConfig
    from repro.serving import Engine, EngineConfig, Request

    P = "serve"
    cfg = (get_smoke if sz.smoke else get_config)("llama3.2-1b")
    shape = ShapeConfig("smoke_serve", "decode", sz.max_seq_len,
                        sz.max_batch)
    ecfg = EngineConfig(max_batch=sz.max_batch, max_seq_len=sz.max_seq_len,
                        block_size=sz.block_size, capture_logits=True)
    rng = np.random.RandomState(seed)
    lens = np.linspace(*sz.prompt_lens, sz.n_requests).astype(int)
    reqs = [Request(f"r{i}", rng.randint(0, cfg.vocab_size, n),
                    max_new_tokens=sz.new_tokens)
            for i, n in enumerate(rng.permutation(lens))]
    log(P, f"{cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
           f"vocab={cfg.vocab_size}; {len(reqs)} requests, prompt lengths "
           f"{sorted(int(r.prompt_len) for r in reqs)}, "
           f"{sz.new_tokens} new tokens each")

    cm = flow.compile(cfg, shape, FlowConfig(mode="folded"),
                      backend=sz.backend, verify=True)
    audit = KernelAudit(P, cm, _expected(sz), ("matmul", "glu_matmul",
                                               "attention",
                                               "paged_decode_attention"))
    audit.table()
    params = cm.init_params(jax.random.key(seed))
    eng = Engine(cm, params, ecfg)
    t0 = time.perf_counter()
    first = eng.run(reqs)
    t1 = time.perf_counter()
    again = eng.run(reqs)
    t2 = time.perf_counter()
    audit.finish()
    log(P, f"smoke timing (one run, not a benchmark): compile+first run "
           f"{t1 - t0:.3f}s, second run {t2 - t1:.3f}s, compile ~"
           f"{(t1 - t0) - (t2 - t1):.3f}s; peak_bytes_in_use "
           f"{peak_bytes(dev)}")
    for a, b in zip(first.results, again.results):
        check(a.tokens == b.tokens, f"{a.rid}: a second run gave other "
                                    "tokens")

    cm_ref = flow.compile(cfg, shape, FlowConfig(mode="folded"),
                          backend="reference")
    ref = Engine(cm_ref, params, ecfg).run(reqs)
    got, want = first.by_id, ref.by_id
    matched = total = compared = 0
    worst = 0.0
    for r in reqs:
        a, b = got[r.rid], want[r.rid]
        check(len(a.tokens) == sz.new_tokens,
              f"{r.rid}: {len(a.tokens)} tokens, wanted {sz.new_tokens}")
        for lg in a.logits:
            check(bool(np.isfinite(lg).all()), f"{r.rid}: non-finite logits")
        check(all(0 <= t < cfg.vocab_size for t in a.tokens),
              f"{r.rid}: token outside the vocabulary")
        same = 0
        while same < len(a.tokens) and a.tokens[same] == b.tokens[same]:
            same += 1
        matched += same
        total += len(a.tokens)
        for t in range(min(COMPARE_STEPS, same + 1)):
            e = relerr(a.logits[t], b.logits[t])
            worst = max(worst, e)
            compared += 1
            check(e <= LOGIT_RTOL, f"{r.rid} step {t}: logits differ from "
                                   f"the reference by {e:.4g} > "
                                   f"{LOGIT_RTOL}")
    log(P, f"logits vs reference: worst relative error {worst:.6g} over "
           f"{compared} prefill/decode steps (tolerance {LOGIT_RTOL}); "
           f"greedy tokens matching the reference before the first "
           f"divergence: {matched}/{total}")


def phase_cnn(sz: Sizes, seed: int, dev) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import flow
    from repro.configs import get_config, get_smoke
    from repro.configs.base import FlowConfig, ShapeConfig

    P = "cnn"
    cfg = (get_smoke if sz.smoke else get_config)("resnet34")
    shape = ShapeConfig("smoke_cnn", "prefill", 1, sz.cnn_batch)
    log(P, f"{cfg.name} {cfg.image_size}px batch {sz.cnn_batch}")
    cm = flow.compile(cfg, shape, FlowConfig(mode="folded"),
                      backend=sz.backend, verify=True)
    audit = KernelAudit(P, cm, _expected(sz), ("conv2d", "matmul"))
    audit.table()
    params = cm.init_params(jax.random.key(seed))
    rng = np.random.RandomState(seed)
    images = jnp.asarray(rng.randn(sz.cnn_batch, cfg.image_size,
                                   cfg.image_size, cfg.image_channels),
                         jnp.float32)
    t0 = time.perf_counter()
    logits, _, _ = cm.prefill(params, {"images": images})
    logits = np.asarray(logits)
    t1 = time.perf_counter()
    jax.block_until_ready(cm.prefill(params, {"images": images}))
    t2 = time.perf_counter()
    audit.finish()
    log(P, f"smoke timing (one run, not a benchmark): compile+first run "
           f"{t1 - t0:.3f}s, second run {t2 - t1:.3f}s; peak_bytes_in_use "
           f"{peak_bytes(dev)}")
    check(logits.shape == (sz.cnn_batch, cfg.vocab_size),
          f"logits shape {logits.shape}")
    check(bool(np.isfinite(logits).all()), "non-finite logits")
    cm_ref = flow.compile(cfg, shape, FlowConfig(mode="folded"),
                          backend="reference")
    want = np.asarray(cm_ref.prefill(params, {"images": images})[0])
    errs = [relerr(logits[i], want[i]) for i in range(sz.cnn_batch)]
    top1 = int((logits.argmax(-1) == want.argmax(-1)).sum())
    log(P, f"logits vs reference: worst relative error {max(errs):.6g} "
           f"(tolerance {LOGIT_RTOL}); top-1 agreement {top1}/"
           f"{sz.cnn_batch}; logit scale {float(np.abs(want).max()):.4g}")
    check(max(errs) <= LOGIT_RTOL,
          f"logits differ from the reference by {max(errs):.4g}")


def phase_train4(sz: Sizes, seed: int, dev) -> None:
    import jax
    import numpy as np
    from repro import flow
    from repro.configs import get_config, get_smoke
    from repro.configs.base import FlowConfig, ShapeConfig
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.optim.adamw import AdamW
    from repro.train.trainer import Trainer, TrainerConfig

    P = "train4"
    devices = jax.devices()
    cfg = (get_smoke if sz.smoke else get_config)("llama3.2-1b")
    shape = ShapeConfig("smoke_train", "train", sz.train_seq, sz.train_batch)
    mesh = {"data": 2, "model": 2}
    log(P, f"{cfg.name} d_model={cfg.d_model} layers={cfg.n_layers}; "
           f"seq {sz.train_seq} global batch {sz.train_batch} on mesh {mesh}")
    # "auto" resolves a training cell to the reference path (the Pallas
    # kernels define no VJP), on the chip and in the rehearsal alike
    cm = flow.compile(cfg, shape, FlowConfig(mode="folded"), mesh=mesh,
                      verify=True)
    audit = KernelAudit(P, cm, None, ())
    audit.table()
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=sz.train_seq,
                                  global_batch=sz.train_batch, seed=seed))
    tr = Trainer(cm, AdamW(), TrainerConfig(steps=3, log_every=1))
    t0 = time.perf_counter()
    params, opt_state, hist = tr.fit(data, jax.random.key(seed))
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    audit.finish()
    losses = [l for _, l in hist]
    log(P, f"losses {losses}; smoke timing (not a benchmark): 3 steps incl. "
           f"compile {t1 - t0:.3f}s")
    check(len(losses) == 3 and all(np.isfinite(losses)),
          f"losses {losses}")
    leaves = jax.tree.leaves(params)
    spread = [len(x.sharding.device_set) for x in leaves]
    split = sum(1 for x in leaves if not x.sharding.is_fully_replicated)
    log(P, f"{len(leaves)} parameter arrays, {split} partitioned, "
           f"devices per array min {min(spread)}")
    check(min(spread) == len(devices), "a parameter does not span all "
                                       f"{len(devices)} devices")
    check(split > 0, "no parameter is partitioned")
    peaks = [d.memory_stats() for d in devices]
    if all(p and "peak_bytes_in_use" in p for p in peaks):
        pk = [p["peak_bytes_in_use"] for p in peaks]
        log(P, f"per-device peak_bytes_in_use {pk}")
        check(max(pk) <= PEAK_RATIO_MAX * min(pk),
              f"per-device peaks unbalanced: {pk}")
    else:
        log(P, "per-device peak_bytes_in_use n/a on this backend")
    del params, opt_state

    # unsharded step-0 loss on one device, over micro-batches of 2
    mb = 2
    cm1 = flow.compile(cfg, ShapeConfig("smoke_train1", "train",
                                        sz.train_seq, mb),
                       FlowConfig(mode="folded"))
    with jax.default_device(devices[0]):
        p1 = cm1.init_params(jax.random.key(seed))
        loss_fn = jax.jit(cm1.loss_fn)
        batch = data.get(0)
        parts = [float(loss_fn(p1, {k: v[i:i + mb]
                                    for k, v in batch.items()})[0])
                 for i in range(0, sz.train_batch, mb)]
    ref = sum(parts) / len(parts)
    err = abs(losses[0] - ref) / abs(ref)
    log(P, f"step-0 loss {losses[0]:.6f} vs unsharded {ref:.6f}: relative "
           f"difference {err:.3g} (tolerance {LOSS_RTOL})")
    check(err <= LOSS_RTOL, f"sharded step-0 loss {losses[0]} vs unsharded "
                            f"{ref}")


def _expected(sz: Sizes) -> str:
    return "pallas" if sz.backend == "auto" else sz.backend


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: phases serve + cnn; 4: phase train4 only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dress rehearsal at smoke size with Pallas "
                         "interpret kernels (refuses a TPU)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse and platform == "tpu":
        print("--rehearse runs interpret-mode kernels; it is for the CPU",
              file=sys.stderr)
        return 2
    if not args.rehearse and platform != "tpu":
        print(f"no TPU found (JAX platform {platform!r}); this smoke run "
              "needs the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices; JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"device {platform} {devices[0].device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    sz = REHEARSAL if args.rehearse else FULL
    phases = [phase_train4] if args.chips == 4 else [phase_serve, phase_cnn]
    for phase in phases:
        t0 = time.perf_counter()
        phase(sz, args.seed, devices[0])
        print(f"phase {phase.__name__[6:]} passed in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    if args.rehearse:
        print("rehearsal passed (CPU, smoke configs, interpret kernels)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
