"""CNN inference cells: back-to-back ``CompiledModel.prefill`` calls on
seeded image batches.

Traffic keys: ``batch`` (images per call), ``distinct_batches`` (how many
different batches the window cycles through), ``fetch`` (true: each call's
logits are copied to the host before the next call starts, as an online
caller waits for them; false: calls queue back to back, at most
``in_flight`` of them ahead of the host), ``rates`` (``{metric: count}``,
the count being ``images`` or ``calls``).

Configuration keys: ``reference``, the file of the plain reference
(``forward(config, seed, images, precision)``), and ``opcount``, the file
that counts the model's work (``convs(config)``, the
``(h, w, cin, cout, k, stride)`` of each convolution of one image, and
``flops_per_image(config)``).
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import traffic as traffic_mod
from bench import weights
from bench.harness import Phases, load_module, model_config


class Runner:
    def __init__(self, cell: Dict, config: Dict, traffic: Dict, seed: int,
                 backend: str = "auto"):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.backend = seed, backend
        self.batch = int(traffic["batch"])
        self.fetch = bool(traffic["fetch"])
        self.in_flight = 0 if self.fetch else int(traffic["in_flight"])
        self.ref = load_module(config["reference"])

    # -- set-up --------------------------------------------------------------
    def setup(self, phases: Phases) -> None:
        from repro import flow
        from repro.configs.base import FlowConfig, ShapeConfig
        self.mc = model_config(self.config)
        shape = ShapeConfig(f"bench_b{self.batch}", "prefill", 1, self.batch)
        fc = FlowConfig(**self.config.get("flow", {}))
        self.cm = phases.run("plan", lambda: flow.compile(
            self.mc, shape, fc, backend=self.backend))
        self.reseed(self.seed, phases)
        # compile (or load from the persistent cache) and run every shape
        # the window uses: one batch size, one image size
        phases.run("compile+warm", lambda: [self._call(b)
                                            for b in self.batches[:2]])

    def reseed(self, seed: int, phases: Phases = None) -> None:
        """Weights and images from ``seed`` for the compiled model."""
        import jax
        import jax.numpy as jnp
        phases = phases or Phases()
        self.seed = seed
        self.params = phases.run("init", lambda: jax.block_until_ready(
            weights.program_params(self.cm, seed)))
        img = (self.mc.image_size, self.mc.image_size, self.mc.image_channels)
        images = phases.run("inputs", lambda: jax.block_until_ready(
            traffic_mod.image_batches(self.traffic, seed, img,
                                      jnp.dtype(self.config["dtype"]))))
        self.batches = [images[i] for i in range(images.shape[0])]

    def _call(self, images):
        import jax
        out = self.cm.prefill(self.params, {"images": images})[0]
        return np.asarray(out) if self.fetch else jax.block_until_ready(out)

    # -- measured window -----------------------------------------------------
    def window(self, seconds: float) -> Dict:
        import jax
        prefill = self.cm.prefill
        params, batches = self.params, self.batches
        D = len(batches)
        keep: List = [None] * D
        pending: List = []
        n = 0
        per_s: List[int] = []       # calls issued by the end of each second
        t0 = time.perf_counter()
        while True:
            out = prefill(params, {"images": batches[n % D]})[0]
            if self.fetch:
                out = np.asarray(out)
            else:
                pending.append(out)
                if len(pending) > self.in_flight:
                    pending.pop(0).block_until_ready()
            keep[n % D] = out
            n += 1
            now = time.perf_counter() - t0
            if now >= len(per_s) + 1:
                per_s.append(n)
            if n >= D and now >= seconds:
                break
        jax.block_until_ready(pending)
        elapsed = time.perf_counter() - t0
        self.kept = keep
        return {"elapsed_s": elapsed, "calls": n, "images": n * self.batch,
                "attempted": n * self.batch, "failed": 0,
                "calls_each_second": np.diff(per_s, prepend=0).tolist()}

    # -- after the window ----------------------------------------------------
    def release(self) -> None:
        """Drop the program's state; what the check needs stays: the
        inputs (made by the benchmark) and the kept outputs."""
        self.kept = [np.asarray(k, np.float32) for k in self.kept]
        self.params = None

    def outputs(self, chunk: int = 64):
        """(images, program logits) of the last call on each batch, in
        chunks of up to ``chunk`` images for the reference."""
        import jax.numpy as jnp
        per = max(1, chunk // self.batch)
        for i in range(0, len(self.kept), per):
            yield (jnp.concatenate(self.batches[i:i + per]),
                   np.concatenate(self.kept[i:i + per]))

    def check(self) -> Dict[str, float]:
        worst = 0.0
        for images, got in self.outputs():
            want = np.asarray(self.ref.forward(self.config, self.seed, images))
            worst = max(worst, worst_rel_err(got, want))
        return {"worst_rel_err": worst}

    def check_control(self, precision: str = "int8") -> Dict[str, float]:
        """The reference at the control precision in the program's place."""
        worst = 0.0
        for images, _ in self.outputs():
            want = np.asarray(self.ref.forward(self.config, self.seed, images))
            ctrl = np.asarray(self.ref.forward(self.config, self.seed, images,
                                               precision=precision))
            worst = max(worst, worst_rel_err(ctrl, want))
        return {"worst_rel_err": worst}

    # -- work done in a window, for the per-layer readers -------------------
    def work(self, win: Dict) -> Dict:
        count = load_module(self.config["opcount"])
        return {"calls": [{"batch": self.batch, "count": win["calls"]}],
                "convs": count.convs(self.config),
                "model_flops": win["images"] * count.flops_per_image(
                    self.config)}


def worst_rel_err(got, want) -> float:
    """Largest, over rows, of the row's largest absolute logit difference
    over the row's largest absolute reference logit."""
    got = np.asarray(got, np.float32).reshape(want.shape)
    want = np.asarray(want, np.float32)
    num = np.abs(got - want).max(axis=-1)
    den = np.abs(want).max(axis=-1) + 1e-30
    return float((num / den).max())
