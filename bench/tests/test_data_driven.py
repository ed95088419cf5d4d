"""A new cell is files and entries only: a copy of the benchmark's directory
with a new configuration (naming a new reference and a new work counter),
a new traffic mix (naming new end-to-end metrics) and the cell's limits
runs through the harness, unedited, and reports the new metrics."""
import json
import shutil

import pytest

from bench import harness, run
from bench.tests.checkutil import CNN_LIMIT, DATA, LM_LIMIT, load

BENCH = harness.ROOT / "bench"

# each wraps an existing module and notes that it was called
WRAPPER = """from {module} import *  # noqa: F401,F403
from {module} import {fn} as _wrapped

CALLS = []


def {fn}(*args, **kw):
    CALLS.append(1)
    return _wrapped(*args, **kw)
"""

CASES = {
    "cnn": dict(config="resnet34_tiny.json", traffic="online_tiny.json",
                reference=("bench.reference.resnet", "forward"),
                opcount=("bench.opcount.resnet", "flops_per_image"),
                rates={"tiny_images_per_s": "images",
                       "tiny_calls_per_s": "calls"},
                limits=CNN_LIMIT),
    "lm_serve": dict(config="phi4mini_tiny.json", traffic="chat_tiny.json",
                     reference=("bench.reference.decoder_lm", "served_gaps"),
                     opcount=("bench.opcount.lm", "model_flops"),
                     rates={"prompt_tokens_per_s": "prompt_tokens",
                            "output_tokens_per_s": "output_tokens"},
                     limits=LM_LIMIT),
}


def _write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


@pytest.mark.parametrize("kind", sorted(CASES))
def test_new_cell_runs_from_files_alone(kind, tmp_path, monkeypatch):
    case = CASES[kind]
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for key in ("reference", "opcount"):
        module, fn = case[key]
        _write(tmp_path / "bench" / key / "newnet.py",
               WRAPPER.format(module=module, fn=fn))
    config = dict(load(case["config"]), reference="bench/reference/newnet.py",
                  opcount="bench/opcount/newnet.py")
    _write(tmp_path / "bench/configs/newnet.json", json.dumps(config))
    traffic = dict(load(case["traffic"]), rates=case["rates"])
    _write(tmp_path / "bench/traffic/newmix.json", json.dumps(traffic))
    _write(tmp_path / "bench/limits/newnet.newmix.json",
           json.dumps({"limits": case["limits"]}))
    bench = {
        "configs": [{"name": "newnet", "file": "bench/configs/newnet.json"}],
        "workloads": [{"name": "newnet.newmix", "config": "newnet",
                       "traffic": "newmix", "chips": 1}],
        "end_to_end": [{"name": n, "unit": "1/s"} for n in case["rates"]]
        + [{"name": "setup_s", "unit": "s"}],
        "per_layer": []}
    _write(tmp_path / "BENCHMARK.json", json.dumps(bench))
    assert (DATA / case["traffic"]).exists()

    monkeypatch.setattr(harness, "ROOT", tmp_path)
    cell, cfg, tr = harness.resolve_cell("newnet.newmix")
    assert tr["rates"] == case["rates"]
    r = run.execute(harness.load_benchmark(), cell, cfg, tr, 1, 0.2, False,
                    harness.limits_for("newnet.newmix"), backend="reference")

    assert r["correct"] is True
    assert set(r["metrics"]) == set(case["rates"]) | {"setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    for key in ("reference", "opcount"):
        assert harness.load_module(f"bench/{key}/newnet.py").CALLS
