"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

``--smoke`` selects the reduced config (CPU-runnable); without it the full
config is used (real-cluster scale).  ``--pp`` enables the cross-pod pipeline
(streaming/CH execution mode).
"""
from __future__ import annotations

import argparse

import jax

from repro import flow as rflow
from repro.compile_cache import enable_compile_cache
from repro.configs.base import FlowConfig, ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticImages, SyntheticLM
from repro.optim.adamw import AdamW
from repro.train.trainer import Trainer, TrainerConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--mode", default="folded")
    ap.add_argument("--backend", default="auto",
                    help="kernel backend policy: auto | reference | pallas "
                         "| pallas_interpret")
    ap.add_argument("--autotune", action="store_true",
                    help="explore the pass design space (estimator-pruned, "
                         "compile-validated) instead of the fixed flow")
    args = ap.parse_args()

    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    cm = rflow.compile(
        args.arch, shape,
        FlowConfig(mode=args.mode, microbatches=args.microbatches),
        backend=args.backend, autotune=args.autotune, smoke=args.smoke)
    if args.autotune:
        print(cm.explore_result.describe())
    print(cm.describe(stats=True))
    cfg = cm.cfg

    if cfg.family == "cnn":
        data = SyntheticImages(
            DataConfig(vocab_size=cfg.vocab_size, seq_len=0,
                       global_batch=args.batch),
            cfg.image_size, cfg.image_channels, cfg.vocab_size)
    else:
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=args.seq,
                                      global_batch=args.batch))
    opt = AdamW(lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1),
                total_steps=args.steps,
                compress="int8_ef" if args.compress else None)
    tr = Trainer(cm, opt, TrainerConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, log_every=max(1, args.steps // 20)))
    _, _, hist = tr.fit(data, jax.random.key(0))
    for s, l in hist:
        print(f"step {s:6d}  loss {l:.4f}")


if __name__ == "__main__":
    main()
