"""BENCHMARK.json and the files the harness finds by its names agree: every
cell has its configuration, traffic and limits, reports setup_s, another
end-to-end metric and a per-layer metric, and every metric has a reader."""
import json
import re
from pathlib import Path

import pytest

from bench import run
from bench.harness import limits_for, load_benchmark, resolve_cell, runner_class

BENCH = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def bench():
    return load_benchmark()


def test_names_and_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(name.fullmatch(n) for n in names)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert next(m for m in bench["end_to_end"]
                if m["name"] == "setup_s")["bound"] <= 0.25


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  load_benchmark()["workloads"]])
def test_every_cell_resolves_and_reports(bench, cell):
    w, config, traffic = resolve_cell(cell)
    assert runner_class(traffic)
    assert limits_for(cell)
    assert set(traffic["rates"]) <= {m["name"] for m in
                                     run.end_to_end_for(bench, cell)}
    e2e = {m["name"] for m in run.end_to_end_for(bench, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = run.per_layer_for(bench, cell)
    assert layer and all(m["moves"] in e2e for m in layer)
    for m in layer:
        assert (BENCH / "metrics" / f"{m['name'].split('.')[0]}.py").exists()
    assert json.loads((BENCH / "peaks.json").read_text())["devices"]
