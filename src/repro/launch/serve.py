"""Batched serving driver.

Single-batch generation (the original mode)::

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --batch 4 --prompt-len 32 --steps 16

Continuous-batching replay (the serving subsystem, end to end)::

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests requests.jsonl --max-batch 4 --max-seq-len 64

where ``requests.jsonl`` holds one request per line, e.g.
``{"id": "a", "prompt": [1, 2, 3], "max_new_tokens": 8}`` or
``{"prompt_len": 12, "seed": 7}`` for a synthetic prompt.  Use
``--requests synthetic:N`` to replay N generated requests without a file.
``--serving-autotune`` first searches the decode-cell design space
(measured-ranked) and pins the winning flow + block size.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import flow as rflow
from repro.compile_cache import enable_compile_cache
from repro.configs.base import FlowConfig, ShapeConfig
from repro.serving import (Engine, EngineConfig, load_requests_jsonl,
                           synthetic_requests)


def _run_replay(args) -> None:
    spec = None if args.speculation in (None, "off", "none", "") \
        else args.speculation
    ecfg = EngineConfig(temperature=args.temperature,
                        max_batch=args.max_batch,
                        max_seq_len=args.max_seq_len,
                        block_size=args.block_size,
                        prefix_cache=bool(args.prefix_cache),
                        chunk_size=args.chunk_size,
                        chunked_prefill=args.chunked_prefill,
                        fori_seg=args.fori_seg,
                        speculation=spec,
                        trace=args.trace is not None)
    if args.serving_autotune:
        from repro.serving.autotune import ServingProfile, autotune_decode
        prof = ServingProfile(name="cli",
                              batch_buckets=ecfg.batch_buckets,
                              max_seq_len=args.max_seq_len,
                              block_sizes=(8, 16, 32))
        at = autotune_decode(args.arch, profile=prof, smoke=args.smoke,
                             validate=args.validate, db=args.tune_db)
        print(at.describe())
        cm = at.compile()
        ecfg = at.engine_config(
            temperature=args.temperature,
            trace=args.trace is not None,
            # explicit --prefix-cache / --no-prefix-cache overrides the
            # tuned pick; unset defers to the measured A/B
            prefix_cache=at.prefix_cache if args.prefix_cache is None
            else args.prefix_cache,
            # explicit CLI chunk/fori knobs likewise override the tuned ones
            **({"chunk_size": args.chunk_size,
                "chunked_prefill": True} if args.chunked_prefill else {}),
            **({"fori_seg": args.fori_seg} if args.fori_seg else {}),
            **({"speculation": spec, "fori_seg": 0} if spec else {}))
    else:
        shape = ShapeConfig("serve", "decode", args.max_seq_len,
                            args.max_batch)
        cm = rflow.compile(args.arch, shape, FlowConfig(mode="folded"),
                           backend=args.backend, smoke=args.smoke)
    params = cm.init_params(jax.random.key(0))
    eng = Engine(cm, params, ecfg)
    if args.requests.startswith("synthetic:"):
        n = int(args.requests.split(":", 1)[1])
        reqs = synthetic_requests(n, cm.cfg.vocab_size,
                                  prompt_len=args.prompt_len,
                                  max_new_tokens=args.steps)
    else:
        reqs = load_requests_jsonl(args.requests, cm.cfg.vocab_size)
    report = eng.run(reqs)
    print(eng.describe())
    if args.trace:
        eng.tracer.to_chrome(args.trace)
        print(f"wrote {len(eng.tracer)} trace events to {args.trace} "
              "(load in Perfetto / chrome://tracing, or summarize with "
              "python -m repro.launch.obs summarize)")
    if args.metrics:
        import json
        snap = report.registry.snapshot() if report.registry is not None \
            else dict(report.metrics)
        with open(args.metrics, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(snap)} metrics to {args.metrics}")
    m = report.metrics
    if m["prefix_cache"]:
        print(f"prefix-cache hit rate: {m['prefix_hit_rate'] * 100:.1f}% "
              f"({m['prefix_hits']} of {m['n_requests']} requests seeded; "
              f"{m['prefill_tokens_computed']} of {m['prompt_tokens_total']} "
              f"prompt tokens computed)")
    if m["speculation"]:
        print(f"speculation [{m['spec_drafter']}]: acceptance rate "
              f"{m['spec_acceptance_rate'] * 100:.1f}% "
              f"({m['spec_tokens_accepted']} of {m['spec_tokens_drafted']} "
              f"draft tokens accepted over {m['spec_ticks']} verify ticks; "
              f"{m['spec_rollback_tokens']} rolled back)")
    for r in report.results[: args.show]:
        print(f"  {r.rid}: prompt={r.prompt_len} -> {r.tokens} "
              f"({r.finish_reason}, {r.latency_s * 1e3:.0f}ms)")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--backend", default="auto",
                    help="kernel backend policy: auto | reference | pallas "
                         "| pallas_interpret")
    ap.add_argument("--on-device-loop", action="store_true")
    ap.add_argument("--autotune", action="store_true",
                    help="explore the pass design space (estimator-pruned, "
                         "compile-validated) for the decode cell")
    # continuous-batching replay mode
    ap.add_argument("--requests", default=None,
                    help="jsonl file (or synthetic:N) of requests to serve "
                         "through Engine.run with continuous batching")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots for the replay mode")
    ap.add_argument("--max-seq-len", type=int, default=128,
                    help="per-request prompt+generation cap (replay mode)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV-cache block size (replay mode)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="share identical prompt prefixes across requests "
                         "through the block index (copy-on-write; replay "
                         "mode); the replay report includes the hit rate. "
                         "Unset + --serving-autotune defers to the measured "
                         "A/B; --no-prefix-cache forces it off")
    ap.add_argument("--chunk-size", type=int, default=1,
                    help="catch-up chunk width k: prompt tails advance up "
                         "to k tokens per decode tick through the (B, k) "
                         "paged cell (replay mode)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="admit cold prompts without a batched prefill and "
                         "drain them k tokens per tick (vLLM-style chunked "
                         "prefill; replay mode)")
    ap.add_argument("--fori-seg", type=int, default=0,
                    help="host-free decode: run this many steady-state "
                         "decode ticks as one on-device fori_loop segment "
                         "(0 = per-tick host loop; replay mode)")
    ap.add_argument("--speculation", default="off",
                    help="speculative decoding: ngram:<k> (prompt-lookup "
                         "drafter), draft:<cfg>:<k> (small-model drafter), "
                         "null:<k>, or off.  Exact — greedy output is "
                         "byte-identical to the per-token loop; the replay "
                         "report prints the acceptance rate (replay mode)")
    ap.add_argument("--serving-autotune", action="store_true",
                    help="search the decode-cell flow space per batch "
                         "bucket and pin the winner before replay")
    ap.add_argument("--validate", default="measure",
                    choices=("measure", "compile", "none"),
                    help="autotune ranking mode (--serving-autotune)")
    ap.add_argument("--tune-db", default=None, metavar="PATH",
                    help="persistent autotune store (repro.tunedb JSONL): "
                         "--serving-autotune reads banked winners instead "
                         "of re-measuring and writes new ones back; "
                         "maintain with python -m repro.launch.tune")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a per-tick span timeline (EngineConfig."
                         "trace) and write it as Chrome trace-event JSON — "
                         "loads in Perfetto; replay mode only")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write the run's MetricsRegistry snapshot (dotted "
                         "metric names) as JSON; replay mode only")
    ap.add_argument("--show", type=int, default=4,
                    help="requests to print after a replay")
    args = ap.parse_args()

    if args.requests is not None:
        _run_replay(args)
        return

    shape = ShapeConfig("cli", "decode", args.prompt_len + args.steps,
                        args.batch)
    cm = rflow.compile(args.arch, shape, FlowConfig(mode="folded"),
                       backend=args.backend, autotune=args.autotune,
                       smoke=args.smoke)
    if args.autotune:
        print(cm.explore_result.describe())
    print(cm.describe(stats=True))
    cfg = cm.cfg
    params = cm.init_params(jax.random.key(0))
    eng = Engine(cm, params, EngineConfig(temperature=args.temperature))

    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.n_patch_tokens:
        batch["patches"] = jnp.asarray(
            rng.randn(args.batch, cfg.n_patch_tokens, cfg.d_vision),
            jnp.float32)
    if cfg.n_encoder_layers:
        batch["frames"] = jnp.asarray(
            rng.randn(args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)

    t0 = time.time()
    if args.on_device_loop:
        toks = eng.generate_fori(batch, args.steps)
    else:
        toks, _ = eng.generate(batch, args.steps)
    dt = time.time() - t0
    tps = args.batch * args.steps / dt
    print(f"generated {toks.shape} in {dt:.2f}s ({tps:.1f} tok/s)")
    print(np.asarray(toks)[:2])


if __name__ == "__main__":
    main()
