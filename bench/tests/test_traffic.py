"""Traffic generation: the same seed gives the same inputs, and every seed
the same work."""
import json
from pathlib import Path

import numpy as np

from bench import traffic

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def _chat():
    return json.loads((TRAFFIC / "chat_decode.json").read_text())


def test_requests_repeat_per_seed():
    a = traffic.request_wave(_chat(), 2 ** 33 + 5, 1, 200064)
    b = traffic.request_wave(_chat(), 2 ** 33 + 5, 1, 200064)
    assert [o for _, o in a] == [o for _, o in b]
    assert all(np.array_equal(p, q) for (p, _), (q, _) in zip(a, b))


def test_every_seed_gets_the_same_work():
    t = _chat()
    waves = [traffic.request_wave(t, s, w, 200064)
             for s, w in ((1, 0), (2, 0), (1, 3))]
    # the same lengths in the same order for every seed; the tokens differ
    assert [(len(p), o) for p, o in waves[0]] == \
        [(len(p), o) for p, o in waves[1]]
    assert not np.array_equal(waves[0][0][0], waves[1][0][0])
    # another wave: the same lengths in another order
    for key in (lambda r: len(r[0]), lambda r: r[1]):
        assert sorted(map(key, waves[0])) == sorted(map(key, waves[2]))
    assert [len(p) for p, _ in waves[0]] != [len(p) for p, _ in waves[2]]
    lens = [len(p) for p, _ in waves[0]]
    assert min(lens) >= 128 and max(lens) <= 1024
    assert len(waves[0]) == t["requests_per_wave"]


def test_heavy_tail():
    q = traffic.quantile_lengths({"low": 128, "high": 1024, "alpha": 1.2},
                                 48)
    assert np.median(q) < 300 < q.max()
    assert all(b in _chat()["prompt_buckets"] for b in (128, 1024))


def test_images_repeat_per_seed():
    import jax.numpy as jnp
    t = {"distinct_batches": 2, "batch": 3}
    a = traffic.image_batches(t, 2 ** 31 + 9, (8, 8, 3), jnp.bfloat16)
    b = traffic.image_batches(t, 2 ** 31 + 9, (8, 8, 3), jnp.bfloat16)
    c = traffic.image_batches(t, 4, (8, 8, 3), jnp.bfloat16)
    assert a.shape == (2, 3, 8, 8, 3) and a.dtype == jnp.bfloat16
    assert bool((a == b).all()) and not bool((a == c).all())
