"""The one traffic generator: reads a traffic file's parameters and makes
the inputs of a run from ``--seed``.

Every seed gets the same work: the same prompt and output lengths
(quantiles of the stated distribution), in the same order (drawn from
``ORDER_SEED``, one permutation per wave), and the same
number of images; the seed draws only the token ids and pixels.  So two
seeds differ in what the program computes, not in how much of it or in
how the engine batches it.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# the arrival order of every wave, the same for every seed and mix
ORDER_SEED = 0


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-point quantiles of a bounded Pareto
    distribution on ``[low, high]`` with tail index ``alpha`` (heavy-tailed:
    most requests short, a few near ``high``).  With ``levels``, only that
    many distinct quantiles, each repeated ``n / levels`` times."""
    lo, hi, a = float(spec["low"]), float(spec["high"]), float(spec["alpha"])
    k = int(spec.get("levels", n))
    if n % k:
        raise ValueError(f"{n} lengths do not split into {k} levels")
    q = (np.arange(k) + 0.5) / k
    x = lo / (1.0 - q * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
    return np.repeat(np.clip(np.rint(x), lo, hi).astype(np.int64), n // k)


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def request_wave(traffic: Dict, seed: int, wave: int,
                 vocab: int) -> List[Tuple[np.ndarray, int]]:
    """Wave ``wave`` of a request mix: ``(prompt token ids, max new
    tokens)`` pairs, queued all at once (a closed batch of
    ``requests_per_wave`` requests)."""
    n = int(traffic["requests_per_wave"])
    order = _rng(ORDER_SEED, 2, wave)
    prompts = order.permutation(quantile_lengths(traffic["prompt_len"], n))
    outputs = order.permutation(quantile_lengths(traffic["output_len"], n))
    rng = _rng(seed, 1, wave)
    return [(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outputs)]


def distinct_prompt_lengths(traffic: Dict) -> List[int]:
    n = int(traffic["requests_per_wave"])
    return sorted(set(int(x) for x in
                      quantile_lengths(traffic["prompt_len"], n)))


def image_batches(traffic: Dict, seed: int, image_shape, dtype):
    """``distinct_batches`` batches of ``batch`` images, made on the device
    in one jitted call: ``(distinct_batches, batch, H, W, C)``."""
    import jax
    from bench.weights import root_key
    shape = (int(traffic["distinct_batches"]), int(traffic["batch"]),
             *image_shape)
    make = jax.jit(lambda k: jax.random.normal(
        jax.random.fold_in(k, 0x1A6E), shape, dtype))
    return make(root_key(seed))
