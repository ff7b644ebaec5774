"""The check of the last line refuses each malformed variant."""
import copy
import json
import os

import pytest

from chipbench.lastline import cell_metrics, problems

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELL = "mistral7b-doc-sessions"


def good(trace):
    line = {"correct": True, "attempted": 36, "failed": 0,
            "metrics": {n: {"value": 12.5, "unit": u}
                        for n, u in cell_metrics(BENCH, CELL, trace).items()},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 12_000_000_000}}
    if trace:
        line["device"].update(busy_s=30.0, window_s=45.0)
        line["breakdown"] = {"device_ops": [["fusion", 1.5]],
                             "idle_gaps": [["gateway.step", 2.0]]}
    return line


@pytest.mark.parametrize("trace", [False, True])
def test_a_whole_line_passes(trace):
    assert problems(good(trace), BENCH, CELL, trace, 1) == []


def test_each_cell_reports_setup_another_metric_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = cell_metrics(BENCH, w["name"], False)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell_metrics(BENCH, w["name"], True)


def broken():
    def edit(trace, fn):
        line = copy.deepcopy(good(trace))
        fn(line)
        return line, trace
    yield "missing metric", edit(False, lambda l: l["metrics"].pop(
        "tpot_mean_ms"))
    yield "missing traced metric", edit(True, lambda l: l["metrics"].pop(
        "idle_share.sessions"))
    yield "nan", edit(False, lambda l: l["metrics"]["ttft_mean_ms"].update(
        value=float("nan")))
    yield "none", edit(False, lambda l: l["metrics"]["ttft_mean_ms"].update(
        value=None))
    yield "wrong unit", edit(False, lambda l: l["metrics"]["setup_s"].update(
        unit="ms"))
    yield "busy 0", edit(True, lambda l: l["device"].update(busy_s=0.0))
    yield "busy above window", edit(True, lambda l: l["device"].update(
        busy_s=46.0))
    yield "no busy", edit(True, lambda l: l["device"].pop("busy_s"))
    yield "no memory peak", edit(False, lambda l: l["device"].pop(
        "memory_peak_bytes"))
    yield "null memory peak", edit(False, lambda l: l["device"].update(
        memory_peak_bytes=None))
    yield "wrong chip count", edit(False, lambda l: l["device"].update(
        count=4))
    yield "missing key", edit(False, lambda l: l.pop("failed"))
    yield "foreign metric", edit(False, lambda l: l["metrics"].update(
        train_tokens_per_s={"value": 1.0, "unit": "tokens/s"}))
    yield "long breakdown", edit(True, lambda l: l["breakdown"].update(
        device_ops=[["op", 1.0]] * 11))


@pytest.mark.parametrize("what,case", list(broken()),
                         ids=[w for w, _ in broken()])
def test_malformed_lines_are_refused(what, case):
    line, trace = case
    assert problems(line, BENCH, CELL, trace, 1), what


def test_a_roofline_share_above_105_is_refused():
    cell = "smollm2-train-seq2k"
    line = {"correct": True, "attempted": 90, "failed": 0,
            "metrics": {n: {"value": 20.0, "unit": u}
                        for n, u in cell_metrics(BENCH, cell, True).items()},
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                       "memory_peak_bytes": 1, "busy_s": 1.0,
                       "window_s": 2.0}}
    assert problems(line, BENCH, cell, True, 1) == []
    line["metrics"]["flash_fwd_roofline"]["value"] = 140.0
    assert problems(line, BENCH, cell, True, 1)
