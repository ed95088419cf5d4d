"""A decoder-only model's work, counted from its configuration file's
published keys: the projections of a layer, and the model FLOPs of served
requests."""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

from bench.opcount.attention import decode_attention


def projections(c: Dict) -> List[Tuple[int, int]]:
    """``(k, n)`` of each weight matrix a token passes through in one layer:
    Q, K, V, output, gate, up, down."""
    d, f = c["hidden_size"], c["intermediate_size"]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    Dh = d // H
    return [(d, H * Dh), (d, KV * Dh), (d, KV * Dh), (H * Dh, d),
            (d, f), (d, f), (f, d)]


def matmul_params(c: Dict) -> int:
    """Weights of all layers' projections (not the embedding)."""
    return c["num_hidden_layers"] * sum(k * n for k, n in projections(c))


def model_flops(c: Dict, requests: Iterable[Tuple[int, int]]) -> float:
    """FLOPs the model needs to serve ``(prompt_len, generated)`` requests:
    every prompt token through every projection and causal attention, the
    logits of the last prompt token, then each further generated token
    through the projections, attention over its sequence so far, and the
    logits."""
    d, V = c["hidden_size"], c["vocab_size"]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    Dh, nl = d // H, c["num_hidden_layers"]
    per_token = 2.0 * matmul_params(c)
    logits = 2.0 * d * V
    total = 0.0
    for P, G in requests:
        # prompt: position i attends over i + 1 keys
        total += P * per_token + logits
        total += nl * decode_attention(range(1, P + 1), H, KV, Dh)[0]
        # decode: token t (1 <= t < G) sits at position P + t - 1 and
        # attends over P + t keys
        steps = max(G - 1, 0)
        total += steps * (per_token + logits)
        total += nl * decode_attention(range(P + 1, P + G), H, KV, Dh)[0]
    return total


def decode_rows(requests: Iterable[Tuple[int, int]]) -> Iterable[int]:
    """Key lengths of every decode row the requests needed."""
    for P, G in requests:
        yield from range(P + 1, P + G)
