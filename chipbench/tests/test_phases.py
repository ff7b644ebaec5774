"""Device time by executable and scope and idle gaps by program span, on three
gateway steps cut from a chip trace and on synthetic traces."""
import json
import math
import os

import pytest

from chipbench import harness as H
from chipbench import phases as P, xplane as X
from chipbench.peaks import peaks_for

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(HERE, "data", "trace_serve_3steps.json.gz")


TRACE_METRICS = (
    "decode_device_ms.batch", "decode_device_ms.sessions",
    "prefill_chunk_device_ms.sessions", "paged_attn_roofline",
    "gap_fetch_ms.batch", "gap_host_loop_ms.batch", "admit_ms.sessions",
    "gap_admit_ms.sessions")


@pytest.fixture
def fresh(monkeypatch):
    """No analysis kept from another test."""
    monkeypatch.setattr(P, "_ANALYSES", {})


def test_recorded_serving_steps():
    a = P.analyse(P.load(RECORDED))
    assert a["device"] == "/device:TPU:0"
    assert a["window_s"] == pytest.approx(0.561424, abs=1e-6)
    # the spans tile the idle time exactly
    assert sum(a["gaps"].values()) == pytest.approx(
        a["window_s"] - a["busy_s"], abs=1e-9)
    assert a["idle_s"] == pytest.approx(a["window_s"] - a["busy_s"])
    # executables by name; their time is a part of the busy union
    exe = a["by_executable"]
    assert exe[P.DECODE]["calls"] == exe[P.DECODE]["whole_calls"] == 3
    assert exe[P.PREFILL_CHUNK]["calls"] == 2
    total = sum(r["seconds"] for r in exe.values())
    assert total <= a["busy_s"] + 1e-9
    assert total == pytest.approx(a["busy_s"], rel=0.01)
    assert 1e3 * exe[P.DECODE]["whole_s"] / 3 == pytest.approx(150.38,
                                                               abs=0.05)
    # the innermost span wins: the wait for the logits is serving.fetch's,
    # not its parents' (gateway.step covers every one of these gaps)
    gaps = a["gaps"]
    assert max(gaps, key=gaps.get) == "serving.fetch"
    assert gaps["serving.fetch"] + gaps["serving.pick"] > 0.7 * a["idle_s"]
    assert gaps["gateway.step"] < 0.01 * a["idle_s"]
    assert a["span_counts"]["serving.launch"] == 3
    assert a["span_counts"]["serving.admit"] == 1
    assert a["span_counts"]["serving.prefill_chunk"] == 2
    # the idle time inside the admission, its fetch apart
    assert 0 < a["gap_under_admit_s"] < gaps["serving.fetch"]
    # scopes: most of the decode step lies under paged_attention, and an
    # instruction without metadata takes its operand's scope
    scopes = a["by_scope"][P.DECODE]
    assert sum(scopes.values()) == pytest.approx(exe[P.DECODE]["seconds"],
                                                 rel=1e-3)
    paged = sum(v for k, v in scopes.items() if "paged_attention" in k)
    assert 0.8 < paged / exe[P.DECODE]["seconds"] < 0.9
    assert scopes["paged_attention/kv_scatter <-operand"] > 0.02
    assert scopes["no_scope"] < 0.08 * exe[P.DECODE]["seconds"]
    assert set(a["by_scope"]) == {P.DECODE, P.PREFILL_CHUNK}


def test_every_new_reader_reads_the_recorded_steps(fresh, monkeypatch,
                                                   capsys):
    monkeypatch.setattr(P, "newest_trace", lambda: RECORDED)
    run = {"trace": X.reduce(X.load(RECORDED), H.SPANS),
           "peaks": peaks_for("TPU v5 lite"),
           "cfg": H.load_config("mistral-7b-v0.3-serve-d16", False),
           "decode_context_tokens": 3 * 32 * 400}
    values = {name: H.read_metric(name, run) for name in TRACE_METRICS}
    assert all(v is not None and math.isfinite(v) for v in values.values())
    assert values["decode_device_ms.batch"] == pytest.approx(150.38, abs=0.05)
    assert values["decode_device_ms.sessions"] \
        == values["decode_device_ms.batch"]
    assert values["prefill_chunk_device_ms.sessions"] == pytest.approx(
        42.62, abs=0.05)
    # the two gap metrics are the window's idle time for each launch
    a = P.of_run(run)
    assert (values["gap_fetch_ms.batch"] + values["gap_host_loop_ms.batch"]) \
        * 3 == pytest.approx(1e3 * a["idle_s"], rel=1e-9)
    assert values["gap_fetch_ms.batch"] == pytest.approx(6.24, abs=0.02)
    assert 0 < values["paged_attn_roofline"] < 5
    assert values["admit_ms.sessions"] == pytest.approx(
        1e3 * a["span_mean_s"]["serving.admit"])
    assert 0 < values["gap_admit_ms.sessions"] < 2
    # the tables are printed once, as earlier lines
    out = capsys.readouterr().out
    assert out.count("device_by_executable: ") == 1
    assert "idle_gaps_by_program_span: " in out
    assert f"device_by_scope.{P.DECODE}: " in out
    rows = json.loads(out.split("idle_gaps_by_program_span: ")[1]
                      .splitlines()[0])
    assert rows[-1][0] == "outside_spans"


def synthetic(spans, modules=(), ops=((100, 300), (600, 200))):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                [f"%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} %p.{i})",
                 s, d] for i, (s, d) in enumerate(ops)]},
            {"name": "XLA Modules", "events": [list(m) for m in modules]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["chipbench.window", 0, 1000]] + [list(s) for s in spans]}]}]}


def test_a_gap_under_two_nested_spans_goes_to_the_inner_one():
    # busy [100, 400) and [600, 800); idle [0,100) [400,600) [800,1000)
    a = P.analyse(synthetic(
        [["gateway.step", 50, 900], ["gateway.replica_step", 300, 400],
         ["serving.fetch", 350, 150]]))
    gaps = {k: v * 1e9 for k, v in a["gaps"].items()}
    # [400, 500) lies under all three: the innermost takes it; the rest of
    # that gap is cut at the spans' ends and goes to what is still open
    assert gaps["serving.fetch"] == pytest.approx(100)
    assert gaps["gateway.replica_step"] == pytest.approx(100)
    assert gaps["gateway.step"] == pytest.approx(50 + 150)
    assert gaps["outside_spans"] == pytest.approx(50 + 50)
    assert sum(gaps.values()) == pytest.approx(1000 - 500)


def test_xplane_reduce_gives_that_gap_to_the_last_name_instead():
    # what PERF.md section 7 leaves to a benchmark PR: of the spans that
    # cover a gap's middle the last BY NAME takes the whole gap, here the
    # outermost
    r = X.reduce(synthetic(
        [["gateway.step", 50, 900], ["gateway.replica_step", 300, 400],
         ["serving.fetch", 350, 150]]),
        ["gateway.step", "gateway.replica_step", "serving.fetch"])
    assert dict(r["idle_gaps"]) == {"gateway.step": pytest.approx(500e-9)}


def test_executions_are_counted_whole_and_clipped():
    a = P.analyse(synthetic(
        [], modules=[["jit_serving_paged_decode(77)", 90, 320],
                     ["jit_serving_paged_decode(77)", 590, 600]]))
    row = a["by_executable"][P.DECODE]
    assert row["calls"] == 2 and row["whole_calls"] == 1
    assert row["seconds"] == pytest.approx(500e-9)     # busy inside them
    assert row["whole_s"] == pytest.approx(300e-9)
    assert "no_module" not in a["by_executable"]


def test_scope_from_the_instruction_or_its_first_operand():
    assert P.instruction(
        "%copy.681 = bf16[3073,8]{1,0:T(8,128)(2,1)} copy(bf16[3073,8]{0,1} "
        "%fusion.617), backend_config={}") == ("copy.681", "fusion.617")
    assert P.scope_of("jit(serving_paged_decode)/jit(main)/paged_attention/"
                      "kv_gather/gather") == "paged_attention/kv_gather"
    tr = synthetic([], modules=[["jit_serving_paged_decode(1)", 0, 1000]])
    tr["op_scopes"] = {P.DECODE: {"p.0": "mlp", "fusion.1": "head"}}
    scopes = P.analyse(tr)["by_scope"][P.DECODE]
    assert scopes == {"mlp <-operand": pytest.approx(300e-9),
                      "head": pytest.approx(200e-9)}


def test_a_program_without_names_gives_the_readers_nothing(fresh,
                                                           monkeypatch,
                                                           tmp_path):
    """A trace in which every module is jit_pure and no span is the
    program's: the readers of the trace's names return None, never 0."""
    path = tmp_path / "unnamed.json"
    path.write_text(json.dumps(synthetic(
        [["gateway.step", 50, 900]],
        modules=[["jit_pure(1)", 90, 320], ["jit_pure(2)", 590, 220]])))
    monkeypatch.setattr(P, "newest_trace", lambda: str(path))
    run = {"trace": X.reduce(X.load(str(path)), H.SPANS), "peaks": None,
           "cfg": {"family": "llama"}, "decode_context_tokens": 10}
    assert all(H.read_metric(n, run) is None
               for n in TRACE_METRICS if "gap_host" not in n)
    assert P.of_run(run)["span_counts"] == {}


def test_a_family_adds_its_spans_and_scopes_to_the_base_ones():
    spans = [["gateway.step", 50, 900], ["family.summarise", 350, 150]]
    base = P.analyse(synthetic(spans))
    assert "family.summarise" not in base["gaps"]
    own = P.analyse(synthetic(spans), P.PROGRAM_SPANS + ("family.summarise",))
    assert own["gaps"]["family.summarise"] == pytest.approx(100e-9)
    assert own["span_counts"] == {"family.summarise": 1}
    assert sum(own["gaps"].values()) == pytest.approx(sum(
        base["gaps"].values()))
    op = "jit(step)/jit(main)/paged_attention/chunk_summary/reduce"
    assert P.scope_of(op) == "paged_attention"
    assert P.scope_of(op, P.SCOPES + ("chunk_summary",)) \
        == "paged_attention/chunk_summary"
