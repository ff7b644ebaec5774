"""A new configuration, traffic mix, cell and per-layer metric are found by
name when they are added as files only."""
import json
import os
import shutil

import pytest

from chipbench import harness as H
from chipbench import traffic as T
from chipbench.lastline import cell_metrics

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def copy(tmp_path, monkeypatch):
    root = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics", "cells", "rehearse"):
        shutil.copytree(os.path.join(HERE, sub), root / sub)
    monkeypatch.setattr(H, "HERE", str(root))
    monkeypatch.setattr(T, "HERE", str(root))
    return root


def test_new_files_are_found_by_name(copy):
    (copy / "configs" / "new-model.json").write_text(json.dumps(
        {"hidden_size": 1024, "num_hidden_layers": 3}))
    (copy / "rehearse" / "new-model.json").write_text(json.dumps(
        {"hidden_size": 32}))
    (copy / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "serve-open", "rate_per_s": 2.0, "cycle": {
            "documents": [64], "asks_per_document": 2,
            "prompt_tokens": {"min": 4, "max": 8},
            "answer_tokens": {"min": 2, "max": 4}}}))
    (copy / "metrics" / "new.metric-1.py").write_text(
        "def read(run):\n    return run.get('answer')\n")
    assert H.load_config("new-model", False)["hidden_size"] == 1024
    assert H.load_config("new-model", True) == {"hidden_size": 32,
                                                "num_hidden_layers": 3}
    offers = T.offers(T.load("new-mix"), 100, 1)
    first, second = next(offers), next(offers)
    assert len(first.prompt) - 64 in range(4, 9) and second.ask == 1
    assert H.read_metric("new.metric-1", {"answer": 42.0}) == 42.0
    # a reader that finds nothing to read returns nothing
    assert H.read_metric("new.metric-1", {}) is None


def test_new_entries_in_benchmark_json_select_the_metrics():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "new-cell", "config": "new-model",
                               "traffic": "new-mix", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "ttft_mean_ms":
            m["workloads"].append("new-cell")
    bench["per_layer"].append({"name": "new.metric-1", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "Gateway", "moves": "ttft_mean_ms",
                               "workloads": ["new-cell"]})
    assert set(cell_metrics(bench, "new-cell", False)) == {"ttft_mean_ms",
                                                           "setup_s"}
    assert set(cell_metrics(bench, "new-cell", True)) == {"new.metric-1"}


def test_every_metric_cell_and_config_of_the_benchmark_has_its_files():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           m["name"] + ".py")), m["name"]
    for w in bench["workloads"]:
        for sub, key in (("cells", "name"), ("traffic", "traffic"),
                         ("rehearse", "traffic"), ("configs", "config"),
                         ("rehearse", "config")):
            assert os.path.exists(os.path.join(HERE, sub,
                                               w[key] + ".json")), (sub, w)
