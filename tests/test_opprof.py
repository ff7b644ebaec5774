"""Op-level compiled-program observatory (observability.opprof).

The acceptance bars:
  * per-op FLOPs/bytes extracted from a tiny model's compiled HLO are
    arithmetically exact for the dominant op (dot = 2*M*N*K) and agree
    with XLA's own ``cost_analysis`` module totals;
  * the op-class taxonomy is stable;
  * an injected recompile (second batch shape through the
    shape-polymorphic TrainStep) produces a second capture whose diff
    NAMES at least one op + the fingerprint flip + recompile growth;
  * ``roofline.gap_attribution_opclass`` gauges tile each phase total
    that ``roofline_attr`` reports exactly (all 7 classes published);
  * ``tools/bench_guard.py`` ``opprof:`` lane exits 1 on a synthetic
    20% top-op cost-share regression and skips dry-run wrappers;
  * ``tools/profile_report.py --json`` / ``telemetry_dump --opprof``
    smoke in the lint lane.

Everything runs on the CPU backend inside the 60s opprof budget.
"""
import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit, nn, optimizer
from paddle_tpu.observability import opprof, roofline_attr
from paddle_tpu.observability.metrics import get_registry

pytestmark = pytest.mark.opprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_opprof():
    opprof.enable()
    opprof.reset_captures()
    yield
    opprof.disable()
    opprof.reset_captures()


def _tiny_train_step(label="train_step", in_dim=16, out_dim=8):
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(in_dim, 32), nn.Tanh(),
                          nn.Linear(32, out_dim))
    opt = optimizer.SGD(learning_rate=0.1,
                        parameters=model.parameters())

    def loss_fn(x, y):
        d = model(x) - y
        return (d * d).mean()

    step = jit.TrainStep(loss_fn, opt, opprof_label=label)
    rng = np.random.RandomState(0)

    def batch(b):
        return (paddle.to_tensor(rng.rand(b, in_dim).astype("float32")),
                paddle.to_tensor(rng.rand(b, out_dim).astype("float32")))

    return step, batch


# -- cost extraction ----------------------------------------------------------

def test_hlo_cost_extraction_exact_dot_flops():
    import jax
    import jax.numpy as jnp

    def f(w, x):
        return jnp.tanh(x @ w).sum()

    m, k, n = 4, 8, 16
    compiled = jax.jit(f).lower(jnp.ones((k, n), jnp.float32),
                                jnp.ones((m, k), jnp.float32)).compile()
    prof = opprof.profile_compiled(compiled, label="probe")
    by_class = {}
    for r in prof.ops:
        by_class.setdefault(r["class"], 0.0)
        by_class[r["class"]] += r["flops"]
    # dot = 2*M*N*K, exactly — the number every MFU quote divides by
    assert by_class["matmul"] == 2 * m * n * k
    # XLA's own module totals agree on flops within the reduce-count
    # convention (ours counts reduce elements, XLA's varies by backend)
    tot = prof.totals()
    assert tot["flops"] == pytest.approx(
        prof.xla_totals.get("flops", tot["flops"]), rel=0.25)
    # bytes accessed: parser vs XLA exact on this fusion-free module
    assert tot["bytes"] == pytest.approx(
        prof.xla_totals.get("bytes accessed", tot["bytes"]), rel=0.25)
    # deterministic: same HLO text -> same fingerprint and same rows
    prof2 = opprof.profile_hlo_text(compiled.as_text(), label="probe")
    assert prof2.fingerprint == prof.fingerprint
    assert prof2.ops == prof.ops


def test_scan_body_expands_by_known_trip_count():
    import jax
    import jax.numpy as jnp

    trips = 16

    def g(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=trips)
        return out.sum()

    compiled = jax.jit(g).lower(jnp.ones((8, 8), jnp.float32),
                                jnp.ones((4, 8), jnp.float32)).compile()
    prof = opprof.profile_compiled(compiled, label="scan")
    dots = [r for r in prof.ops if r["class"] == "matmul"]
    assert dots, "scan-body dot not surfaced"
    # the while body's dot costs trip_count * (2*4*8*8): a scan-heavy
    # model (scan_layers=True Llama) must not undercount its stack
    assert sum(r["flops"] for r in dots) == trips * 2 * 4 * 8 * 8
    assert dots[0]["count"] == trips


# -- taxonomy -----------------------------------------------------------------

def test_taxonomy_stability():
    # the bucket scheme is closed and ordered
    assert opprof.OP_CLASSES == ("matmul", "attention", "collective",
                                 "elementwise", "reduce",
                                 "data-movement", "quant", "other")
    expect = {
        "dot_general": "matmul", "convolution": "matmul",
        "all_reduce": "collective", "reduce-scatter": "collective",
        "collective_permute.3": "collective",
        "reduce_sum": "reduce", "reduce.12": "reduce",
        "tanh": "elementwise", "add.7": "elementwise",
        "copy": "data-movement", "transpose.2": "data-movement",
        "broadcast_in_dim": "data-movement",
        "custom-call": "other",
    }
    for name, cls in expect.items():
        assert opprof.classify_op(name) == cls, name
    # attention context wins over the opcode (an attention dot is an
    # attention-optimization target, not a projection-matmul one)
    assert opprof.classify_op("dot_general",
                              "decoder/flash_attention/dot") == "attention"
    assert opprof.classify_op("fusion.7", "mha/softmax") == "attention"
    # quant scopes win over BOTH the opcode and an enclosing attention
    # scope: the inline cache dequant lives inside the attention calc,
    # and its cost is the quant lane's attribution target
    assert opprof.classify_op("convert.3",
                              "decoder/cachekv_dequant/convert") == "quant"
    assert opprof.classify_op("multiply",
                              "mha/cachekv_quant/mul") == "quant"
    assert opprof.classify_op("fusion.2",
                              "model/weight_dequant/mul") == "quant"


# -- capture hooks + diff -----------------------------------------------------

def test_trainstep_capture_and_recompile_diff_names_ops():
    step, batch = _tiny_train_step(label="t.train_step")
    x, y = batch(4)
    step(x, y)   # eager discovery
    step(x, y)   # first compiled execution -> capture 1
    assert opprof.recompile_counts() == {"t.train_step": 1}
    x2, y2 = batch(6)
    step(x2, y2)  # injected recompile: shape retrace -> capture 2
    assert opprof.recompile_counts() == {"t.train_step": 2}
    profs = opprof.get_captures()["t.train_step"]
    assert profs[0].fingerprint != profs[1].fingerprint
    old = {"captures": {"t.train_step": profs[0].to_dict()},
           "recompiles": {"t.train_step": 1}}
    new = {"captures": {"t.train_step": profs[1].to_dict()},
           "recompiles": {"t.train_step": 2}}
    d = opprof.diff(old, new, share_tol=0.0)
    named = d["appeared"] + d["disappeared"] + [c["op"]
                                               for c in d["changed"]]
    assert named, "recompile diff named no ops"
    assert d["fingerprint_changed"] == ["t.train_step"]
    assert d["recompile_growth"]["t.train_step"] == {"old": 1, "new": 2}


def test_static_function_capture_under_label():
    @jit.to_static
    def f(a):
        return paddle.tanh(a) * 2.0

    f._opprof_label = "t.static_fn"
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    with paddle.no_grad():
        f(x)  # trace
        f(x)  # warm transition -> capture
        f(x)  # warm: no further capture
    caps = opprof.get_captures()
    assert "t.static_fn" in caps and len(caps["t.static_fn"]) == 1
    classes = {r["class"] for r in caps["t.static_fn"][0].ops}
    assert "elementwise" in classes


def test_disabled_is_free_and_capture_never_raises():
    opprof.disable()
    step, batch = _tiny_train_step(label="t.off")
    x, y = batch(4)
    step(x, y)
    step(x, y)
    assert opprof.get_captures() == {}
    # a broken jitted object must not take down the caller
    opprof.enable()
    class Broken:
        def lower(self, *a, **k):
            raise RuntimeError("boom")
    assert opprof.maybe_capture("t.broken", Broken(), (1,)) is None
    assert "t.broken" not in opprof.get_captures()


# -- gap attribution ----------------------------------------------------------

def test_gap_attribution_opclass_tiles_phase_totals(tmp_path,
                                                    monkeypatch):
    model = {"configs": [
        {"config": "toy", "params": 1000, "batch": 1, "seq": 100,
         "t_compute_ms": 40.0, "t_memory_ms": 60.0, "bound": "memory",
         "tokens_per_s_bound": 1000.0, "measured_mfu_ceiling": 0.6},
    ]}
    p = tmp_path / "ROOFLINE.json"
    p.write_text(json.dumps(model))
    monkeypatch.setenv("PADDLE_ROOFLINE", str(p))
    roofline_attr.clear_cache()
    try:
        step, batch = _tiny_train_step(label="t.gap.train_step")
        x, y = batch(4)
        step(x, y)
        step(x, y)  # capture (label contains 'train' -> headline)
        attr = roofline_attr.observe_train_step(0.120, observed_mfu=0.2,
                                                tokens=100)
        assert attr is not None
        fam = get_registry().get("roofline.gap_attribution_opclass")
        assert fam is not None, "opclass gauges not published"
        split = {}
        for ch in fam.children():
            split.setdefault(ch.labels["phase"], {})[
                ch.labels["op_class"]] = ch.value
        phase_totals = {"compute": attr["compute_frac"],
                        "memory": attr["memory_frac"],
                        "overhead": attr["overhead_frac"]}
        for phase, total in phase_totals.items():
            parts = split[phase]
            # ALL classes published (zeros included: no stale values)
            assert set(parts) == set(opprof.OP_CLASSES)
            # the classes tile the phase total exactly (fp residual is
            # folded into the largest part by _tile_exactly)
            assert math.fsum(parts.values()) == pytest.approx(
                total, abs=1e-12)
            assert all(v >= 0.0 for v in parts.values())
        # a nonzero phase splits into at least one nonzero class
        assert any(v > 0 for v in split["compute"].values())
        # comm phases route entirely to the collective class
        split2 = opprof.attribute_gap(
            {"compute_frac": 0.2, "memory_frac": 0.1,
             "overhead_frac": 0.3, "comm_fracs": {"fsdp": 0.15}},
            opprof.get_captures()["t.gap.train_step"][-1])
        assert split2["comm:fsdp"]["collective"] == pytest.approx(0.15)
        assert math.fsum(split2["comm:fsdp"].values()) == \
            pytest.approx(0.15, abs=1e-12)
    finally:
        roofline_attr.clear_cache()


def test_gap_attribution_without_capture_is_silent():
    assert opprof.publish_gap_attribution(
        {"compute_frac": 0.5, "memory_frac": 0.2,
         "overhead_frac": 0.3}) is None


# -- artifacts + drift gate ---------------------------------------------------

def _fake_artifact(top_share, n_recompiles=0, flops=1e6):
    return {
        "kind": "opprof", "tpu": False,
        "captures": {"bench.train_step": {
            "label": "bench.train_step", "fingerprint": "f" * 16,
            "ops": [{"op": "dot_general", "class": "matmul",
                     "flops": flops, "bytes": 1e3, "out_bytes": 1e3,
                     "transcendentals": 0.0, "count": 1}],
            "xla_totals": {}}},
        "recompiles": {"bench.train_step": 1 + n_recompiles},
        "fingerprints": {"bench.train_step": ["f" * 16]},
        "capture_failures": 0,
        "headline": {"label": "bench.train_step",
                     "fingerprint": "f" * 16, "top_class": "matmul",
                     "top_share": top_share,
                     "top_op_classes": [["matmul", top_share]],
                     "n_recompiles": n_recompiles},
    }


def test_artifact_write_load_diff_roundtrip(tmp_path):
    step, batch = _tiny_train_step(label="t.art.train_step")
    x, y = batch(4)
    step(x, y)
    step(x, y)
    path = opprof.write_artifact(str(tmp_path))
    assert path and os.path.basename(path) == "OPPROF_r00.json"
    doc = opprof.load_artifact(path)
    assert doc is not None and "bench" not in doc["headline"]["label"]
    assert doc["headline"]["top_share"] > 0
    # numbering continues; a second write lands r01 and diffs clean
    x2, y2 = batch(6)
    step(x2, y2)
    path2 = opprof.write_artifact(str(tmp_path))
    assert os.path.basename(path2) == "OPPROF_r01.json"
    doc2 = opprof.load_artifact(path2)
    d = opprof.diff(doc, doc2, share_tol=0.0)
    assert (d["appeared"] or d["disappeared"] or d["changed"]
            or d["fingerprint_changed"])
    # a driver dry-run wrapper is NOT an artifact
    wrapper = tmp_path / "OPPROF_r02.json"
    wrapper.write_text(json.dumps({"n": 2, "cmd": "x", "rc": 1,
                                   "tail": ""}))
    assert opprof.load_artifact(str(wrapper)) is None


def test_bench_guard_opprof_lane_gates_synthetic_regression(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bg", os.path.join(REPO, "tools", "bench_guard.py"))
    bg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bg)
    # 3 healthy rounds at top_share 0.5, then a 20% cost-share
    # regression (0.5 -> 0.6 => headroom 0.5 -> 0.4)
    for i, share in enumerate((0.5, 0.5, 0.5, 0.6)):
        (tmp_path / f"OPPROF_r{i:02d}.json").write_text(
            json.dumps(_fake_artifact(share)))
    # a dry-run wrapper round skips cleanly (like multichip:)
    (tmp_path / "OPPROF_r04.json").write_text(
        json.dumps({"n": 4, "cmd": "python bench.py", "rc": 124,
                    "tail": "timeout"}))
    report = bg.run_check(str(tmp_path))
    key = "opprof:opprof_top_share_headroom/cpu"
    assert key in report["series"]
    res = report["series"][key]
    assert res["n_points"] == 4  # the wrapper contributed no point
    assert res["status"] == "regression"
    assert report["status"] == "regression"
    # the recompile-health series stayed flat -> pass
    assert report["series"][
        "opprof:opprof_recompile_health/cpu"]["status"] == "pass"
    # CLI contract: --check exits 1 on the regression
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_guard.py"),
         "--check", "--dir", str(tmp_path)],
        capture_output=True, text=True)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "opprof" in proc.stdout


def test_bench_guard_opprof_lane_passes_flat_history(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bg2", os.path.join(REPO, "tools", "bench_guard.py"))
    bg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bg)
    for i in range(3):
        (tmp_path / f"OPPROF_r{i:02d}.json").write_text(
            json.dumps(_fake_artifact(0.5)))
    report = bg.run_check(str(tmp_path))
    assert report["status"] == "pass"


# -- CLI gates (lint lane) ----------------------------------------------------

@pytest.mark.lint
@pytest.mark.quick
def test_profile_report_cli_names_injected_recompile():
    """profile_report --json is part of the lint lane: the demo
    workload's injected recompile must produce a diff that names at
    least one op, a fingerprint flip, and recompile growth."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "profile_report.py"), "--json"],
        cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    d = payload["diff"]
    named = d["appeared"] + d["disappeared"] + [c["op"]
                                               for c in d["changed"]]
    assert named, "demo recompile diff named no ops"
    assert d["fingerprint_changed"]
    assert payload["recompiles"]["demo.train_step"] == 2
    # gap split tiles its phases
    for phase, parts in payload["gap_attribution"].items():
        assert set(parts) == set(opprof.OP_CLASSES)
    # budget guard: this boots jax and compiles twice
    assert elapsed < 60.0, f"profile_report took {elapsed:.1f}s"


@pytest.mark.lint
@pytest.mark.quick
def test_profile_report_artifact_mode_reads_committed_round():
    """Artifact mode is jax-free and must stay snappy over the
    committed OPPROF_r*.json rounds."""
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "profile_report.py"),
         "--artifacts", "--json"],
        cwd=REPO, capture_output=True, text=True)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["headline"]["top_share"] > 0
    assert elapsed < 10.0, f"artifact mode took {elapsed:.1f}s"


@pytest.mark.lint
@pytest.mark.quick
def test_telemetry_dump_opprof_view():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "telemetry_dump.py"), "--opprof"],
        cwd=REPO, capture_output=True, text=True)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "# opprof OPPROF_r" in proc.stdout
    assert "gap attribution" in proc.stdout
    # stdlib-only path: no jax boot allowed in this view
    assert elapsed < 10.0, f"--opprof view took {elapsed:.1f}s"
