"""The program's weights, made in one jitted call, are the ones a reference
regenerates leaf by leaf from the seed and the canonical name."""
import json
from pathlib import Path

import jax
import numpy as np

from bench import weights
from bench.harness import model_config

DATA = Path(__file__).parent / "data"


def test_program_tree_and_leaves_match_the_reference_rule():
    from repro import flow
    from repro.configs.base import FlowConfig, ShapeConfig
    cfg = json.loads((DATA / "phi4mini_tiny.json").read_text())
    cm = flow.compile(model_config(cfg), ShapeConfig("t", "decode", 64, 2),
                      FlowConfig(mode="folded"), backend="reference")
    seed = 2 ** 31 + 11
    params = weights.program_params(cm, seed)
    shapes = cm.param_shapes()
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(shapes)):
        assert a.shape == b.shape and a.dtype == b.dtype
    stacked = np.asarray(params["fold_layer0"]["0:wk"], np.float32)
    for li in range(cfg["num_hidden_layers"]):
        want = weights.leaf(seed, f"layer{li}/wk", stacked.shape[1:])
        np.testing.assert_array_equal(stacked[li], np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(params["embed"]["table"], np.float32),
        np.asarray(weights.leaf(seed, "embed/table",
                                params["embed"]["table"].shape)))
    other = weights.program_params(cm, seed + 1)
    assert not np.array_equal(np.asarray(other["embed"]["table"]),
                              np.asarray(params["embed"]["table"]))
