"""The reduction from a trace to busy time, kernel time and idle gaps, on a
trace recorded on the chip and on a synthetic one."""
import os

import pytest

from chipbench import costs, xplane as X
from chipbench.peaks import peaks_for

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(HERE, "data", "trace_train_3steps.json.gz")


def test_recorded_chip_trace():
    r = X.reduce(X.load(RECORDED), ["train.step"])
    assert r["device"] == "/device:TPU:0"
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["window_s"] == pytest.approx(2.0247, abs=1e-3)
    assert r["busy_s"] == pytest.approx(2.0119, abs=1e-3)   # a union
    # kernels are found by name, under the instance suffixes and the whole
    # instruction text that the trace gives them
    k = r["kernels"]
    assert k["flash_fwd"]["calls"] == 47            # 12 a step, forward and
    assert k["flash_bwd_dq"]["calls"] == k["flash_bwd_dkv"]["calls"] == 24
    per_call = k["flash_fwd"]["seconds"] / 47
    flops, nbytes = costs.flash_fwd_cost(
        {"num_attention_heads": 32, "num_key_value_heads": 32,
         "hidden_size": 2048}, 4, 2048)
    least, _ = costs.roofline_seconds(flops, nbytes, peaks_for("v5 lite"))
    assert 100 * least / per_call == pytest.approx(2.77, abs=0.05)
    # the sum over events counts the while loops and their bodies twice
    total = sum(v["seconds"] for v in k.values())
    assert total > 1.5 * r["busy_s"]
    names = [n for n, _ in r["device_ops"]]
    assert names[0].startswith("flash_fwd") and len(names) <= 10
    # the idle gap at the head of the window lies under the harness's span
    assert r["idle_gaps"][0][0] == "train.step"
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-6)


def synthetic():
    dev = lambda lines: {"name": "/device:TPU:0", "lines": lines}  # noqa
    return {"planes": [
        dev([{"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while(...)", 100, 800],
            ["%flash_fwd.3 = (bf16[8,128,64]) custom-call()", 150, 100],
            ["fusion.7", 300, 200],
            ["%flash_fwd.4 = (bf16[8,128,64]) custom-call()", 600, 100],
            ["copy.1", 1000, 100]]},
            {"name": "XLA Ops", "events": [["fusion.9", 850, 200]]},
            {"name": "Steps", "events": [["step", 0, 5000]]}]),
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["fusion.7", 300, 50]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["chipbench.window", 0, 2000], ["gateway.step", 0, 950],
            ["generator.wait", 1100, 900]]}]}]}


def test_synthetic_overlap_on_two_lines_of_one_device():
    r = X.reduce(synthetic(), ["gateway.step", "generator.wait"])
    assert r["device"] == "/device:TPU:0"        # the busiest
    assert r["window_s"] == pytest.approx(2000e-9)
    # [100, 900] u [850, 1050] u [1000, 1100] = [100, 1100]: 1000 ns, where
    # the events sum to 1500
    assert r["busy_s"] == pytest.approx(1000e-9)
    assert r["busy_mean_s"] == pytest.approx((1000 + 50) / 2 * 1e-9)
    assert r["kernels"]["flash_fwd"] == {"seconds": pytest.approx(200e-9),
                                         "calls": 2}
    ops = dict(r["device_ops"])
    # less its body, and less the 50 ns the other line's fusion overlaps it
    assert ops["while_s32[]"] == pytest.approx(350e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["gateway.step"] == pytest.approx(100e-9)
    assert gaps["generator.wait"] == pytest.approx(900e-9)
    assert "Steps" not in str(r["device_ops"])


def test_busy_is_clipped_to_the_window():
    tr = synthetic()
    tr["planes"][2]["lines"][0]["events"][0] = ["chipbench.window", 500, 400]
    r = X.reduce(tr, [])
    assert r["window_s"] == pytest.approx(400e-9)
    assert r["busy_s"] == pytest.approx(400e-9) and r["idle_gaps"] == []


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        X.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_canon_and_union():
    assert X.canon("%flash_bwd_dq.9 = bf16[1]{0} custom-call()") \
        == "flash_bwd_dq"
    assert X.canon("fusion.123") == "fusion" and X.canon("copy") == "copy"
    assert X.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
