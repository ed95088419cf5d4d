"""Serving cells: waves of seeded requests through ``Engine.run``, the
continuous-batching engine over the paged KV pool, with greedy sampling.

Configuration keys (``serving``): ``max_batch`` slots, ``max_seq_len``,
``cache_len`` (the compiled prefill's cache length, at least the longest
prompt bucket), ``block_size``.  Traffic keys: ``requests_per_wave``
(queued together, so every slot stays full until the wave drains),
``prompt_len`` / ``output_len`` (bounded Pareto parameters),
``prompt_buckets`` / ``batch_buckets`` (the shape ladder the engine rounds
to, and the only shapes set-up compiles), ``check_requests`` (how many
finished requests the output check compares), ``rates``
(``{metric: count}``, the count being ``output_tokens``, ``prompt_tokens``
or ``requests`` of the waves the window served).

Configuration keys: ``reference``, the file of the plain reference
(``served_gaps(config, seed, prompts, served, precisions)``), and
``opcount``, the file that counts the model's work
(``model_flops(config, requests)``).
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
        self.sv = config["serving"]
        self.ref = load_module(config["reference"])

    # -- set-up --------------------------------------------------------------
    def setup(self, phases: Phases) -> None:
        from repro import flow
        from repro.configs.base import FlowConfig, ShapeConfig
        from repro.serving import EngineConfig
        sv, tr = self.sv, self.traffic
        self.mc = model_config(self.config)
        shape = ShapeConfig("bench_serve", "decode", int(sv["cache_len"]),
                            int(sv["max_batch"]))
        fc = FlowConfig(**self.config.get("flow", {}))
        self.cm = phases.run("plan", lambda: flow.compile(
            self.mc, shape, fc, backend=self.backend))
        self.ecfg = EngineConfig(
            max_batch=int(sv["max_batch"]), max_seq_len=int(sv["max_seq_len"]),
            block_size=int(sv["block_size"]),
            prompt_buckets=tuple(tr["prompt_buckets"]),
            batch_buckets=tuple(tr["batch_buckets"]), temperature=0.0,
            seed=0)
        self.reseed(self.seed, phases)
        phases.run("compile+warm", self._warm)

    def reseed(self, seed: int, phases: Phases = None) -> None:
        """Weights from ``seed`` for the compiled model, and an engine over
        them; the compiled programs stay."""
        import jax
        from repro.serving import Engine
        phases = phases or Phases()
        self.seed = seed
        self.params = phases.run("init", lambda: jax.block_until_ready(
            weights.program_params(self.cm, seed)))
        self.engine = Engine(self.cm, self.params, self.ecfg)

    def _requests(self, pairs, tag: str):
        from repro.serving import Request
        return [Request(f"{tag}{i}", p, max_new_tokens=o)
                for i, (p, o) in enumerate(pairs)]

    def _warm(self) -> None:
        """Run the engine over request groups that reach every shape a wave
        can: each prefill batch rung with each prompt bucket the traffic
        uses, each decode batch rung, and every prompt length of the mix
        left-padded to every bucket at or above its own (the admission
        path slices the prompt's K/V per padding)."""
        tr = self.traffic
        buckets = [b for b in tr["prompt_buckets"]]
        lengths = traffic_mod.distinct_prompt_lengths(tr)
        rng = np.random.default_rng(0)

        def bucket(n):
            return next(b for b in buckets if n <= b)

        groups: List[List[int]] = []
        for sp in buckets:
            top = [n for n in lengths if bucket(n) == sp]
            if not top:
                continue
            rest = [n for n in lengths if bucket(n) <= sp]
            for b in sorted(tr["batch_buckets"], reverse=True):
                take, rest = rest[:b - 1], rest[b - 1:]
                groups.append([top[-1]] + take
                              + [top[-1]] * (b - 1 - len(take)))
            while rest:         # more lengths than one ladder's rows
                b = max(tr["batch_buckets"])
                take, rest = rest[:b - 1], rest[b - 1:]
                groups.append([top[-1]] + take
                              + [top[-1]] * (b - 1 - len(take)))
        vocab = self.mc.vocab_size
        for gi, g in enumerate(groups):
            pairs = [(rng.integers(0, vocab, n, dtype=np.int32), 2) for n in g]
            self._run(self._requests(pairs, f"warm{gi}."))

    def _run(self, reqs):
        self.engine.last_cache = None      # free the last pool before a new one
        return self.engine.run(reqs)

    # -- measured window -----------------------------------------------------
    def window(self, seconds: float) -> Dict:
        vocab = self.mc.vocab_size
        waves = []
        reports = []
        k = 0
        t0 = time.perf_counter()
        while True:
            reqs = self._requests(traffic_mod.request_wave(
                self.traffic, self.seed, k, vocab), f"w{k}.")
            waves.append(reqs)
            reports.append(self._run(reqs))
            k += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.waves, self.reports = waves, reports
        gen = sum(r.metrics["generated_tokens"] for r in reports)
        n_req = sum(len(w) for w in waves)
        done = sum(1 for r in reports for res in r.results
                   if res.n_generated > 0 and res.finish_reason)
        return {"elapsed_s": elapsed, "waves": k, "output_tokens": gen,
                "prompt_tokens": sum(q.prompt_len for w in waves for q in w),
                "requests": done, "attempted": n_req, "failed": n_req - done}

    # -- after the window ----------------------------------------------------
    def release(self) -> None:
        """Free the program's state: weights, engine and KV pool."""
        self.engine.last_cache = None
        self.engine = self.params = None

    def served(self):
        """(request, result) of every request the window served."""
        return [(req, res) for w, rep in zip(self.waves, self.reports)
                for req, res in zip(w, sorted(rep.results,
                                              key=lambda r: _order(r.rid)))]

    def sample(self):
        """Requests to check, drawn from the seed among those the window
        finished: the longest (prompt plus served tokens) and others."""
        done = self.served()
        n = min(int(self.traffic["check_requests"]), len(done))
        longest = max(range(len(done)),
                      key=lambda i: done[i][0].prompt_len
                      + done[i][1].n_generated)
        rng = np.random.default_rng([int(self.seed), 7])
        others = [i for i in rng.permutation(len(done)) if i != longest]
        return [done[i] for i in [longest] + others[:n - 1]]

    def check(self, precisions=("f32",)) -> Dict[str, float]:
        picked = self.sample()
        gaps = self.ref.served_gaps(
            self.config, self.seed, [q.prompt for q, _ in picked],
            [r.tokens for _, r in picked], precisions)
        out = {"served_logit_gap": float(max(g.max() for g in gaps["served"])),
               "served_tokens": float(sum(len(r.tokens) for _, r in picked))}
        for p in precisions[1:]:
            out[f"{p}_logit_gap"] = float(max(g.max() for g in gaps[p]))
        return out

    def check_control(self, precision: str = "int8") -> Dict[str, float]:
        """The reference at the control precision in the program's place:
        at each served position, the gap of the token it puts first."""
        got = self.check(("f32", precision))
        return {"served_logit_gap": got[f"{precision}_logit_gap"]}

    # -- work done in a window, for the per-layer readers -------------------
    def work(self, win: Dict) -> Dict:
        count = load_module(self.config["opcount"])
        c = self.config
        reqs = [(req.prompt_len, res.n_generated)
                for req, res in self.served()]
        snaps = [rep.registry.snapshot() for rep in self.reports]

        def total(key):
            return sum(s.get(key, 0) for s in snaps)

        return {"requests": reqs,
                "model_flops": count.model_flops(c, reqs),
                "ticks": total("serving.ticks"),
                "prefill_calls": total("serving.prefill.batches"),
                "prefill_tokens": total("serving.tokens.prefill_computed"),
                "host_syncs": total("serving.host_syncs"),
                "tokens": total("serving.tokens.generated"),
                "n_requests": total("serving.requests"),
                "max_batch": int(self.sv["max_batch"])}


def _order(rid) -> int:
    return int(str(rid).rsplit(".", 1)[1])
