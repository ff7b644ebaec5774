"""Static-analysis subsystem (paddle_tpu.analysis).

All four engines, one flagging and one passing fixture per rule:
  DF001..DF006  — jaxpr dataflow analyses / registry alias audit
  TS101..TS105  — AST trace-safety lint
  SH201..SH204  — SPMD shard-safety (jaxpr propagation + PLAN_7B audit)
  MEM301/MEM302 — liveness peak-HBM budgeting (jaxpr + plan + serving)
plus the pass-registry integration (diagnostic passes via apply_pass),
the observability findings counters, the suppression/baseline machinery,
and the tier-1 lint gate (``pytest -m lint``) that runs tools/tpu_lint.py
over the shipped tree (paddle_tpu/, examples/, tools/, benchmarks/) AND
the tools/shard_check.py PLAN_7B gate.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import ast_lint
from paddle_tpu.analysis import findings as findings_mod
from paddle_tpu.static import ir

try:
    from jax._src.core import ClosedJaxpr, Jaxpr
except ImportError:  # pragma: no cover
    from jax.core import ClosedJaxpr, Jaxpr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return {f.rule for f in findings}


def _tensor(shape, seed=0):
    return paddle.to_tensor(
        np.random.RandomState(seed).randn(*shape).astype("float32"))


# ---------------------------------------------------------------------------
# DF001 — shape/dtype + structural consistency
# ---------------------------------------------------------------------------

def test_df001_flags_corrupt_jaxpr():
    closed = jax.make_jaxpr(lambda x: jnp.tanh(jnp.exp(x)))(1.0)
    jp = closed.jaxpr
    # "a transform pass dropped a producer": first eqn removed by hand
    bad = ClosedJaxpr(Jaxpr(jp.constvars, jp.invars, jp.outvars,
                            jp.eqns[1:], jp.effects), closed.consts)
    fs = analysis.check_shapes(bad)
    assert "DF001" in _rules(fs)
    assert any("before it is defined" in f.message for f in fs)


@pytest.mark.quick
def test_df001_passes_healthy_program():
    def fn(x):
        return paddle.tanh(x) + 1.0
    prog = ir.IrProgram.trace(fn, _tensor((3, 4)))
    assert analysis.check_shapes(prog) == []


# ---------------------------------------------------------------------------
# DF002 — dead code
# ---------------------------------------------------------------------------

def test_df002_flags_dead_eqns_and_passes_after_dce():
    def fn(x):
        dead = paddle.exp(x) * 3.0  # never reaches the output
        return paddle.tanh(x)
    prog = ir.IrProgram.trace(fn, _tensor((3, 4)))
    fs = analysis.check_dead_code(prog)
    assert "DF002" in _rules(fs)
    clean = ir.apply_pass(prog, "dead_code_elimination")
    assert analysis.check_dead_code(clean) == []


# ---------------------------------------------------------------------------
# DF003 — unused inputs
# ---------------------------------------------------------------------------

def test_df003_flags_unused_input_and_passes_when_used():
    def uses_one(x, y):
        return paddle.tanh(x)
    prog = ir.IrProgram.trace(uses_one, _tensor((2, 2)), _tensor((2, 2), 1))
    fs = analysis.check_unused_inputs(prog)
    assert "DF003" in _rules(fs)
    assert any("input #1" in f.message for f in fs)

    def uses_both(x, y):
        return x + y
    prog2 = ir.IrProgram.trace(uses_both, _tensor((2, 2)),
                               _tensor((2, 2), 1))
    assert analysis.check_unused_inputs(prog2) == []


# ---------------------------------------------------------------------------
# DF004 — collective ordering (the SPMD deadlock lint)
# ---------------------------------------------------------------------------

def _rank_jaxpr(fn, *args):
    return jax.make_jaxpr(fn, axis_env=[("i", 2)])(*args)


def test_df004_flags_mismatched_two_rank_program():
    # rank0: psum; psum      rank1: ppermute; psum  -> deadlock at #0
    r0 = _rank_jaxpr(lambda v: lax.psum(lax.psum(v, "i"), "i"), 1.0)
    r1 = _rank_jaxpr(
        lambda v: lax.psum(
            jnp.sum(lax.ppermute(v, "i", [(0, 1), (1, 0)])), "i"),
        jnp.ones((2,)))
    fs = analysis.check_collective_order([r0, r1])
    assert "DF004" in _rules(fs)
    assert any(f.severity == "error" and "deadlock" in f.message
               for f in fs)


def test_df004_passes_identical_rank_schedules():
    mk = lambda: _rank_jaxpr(
        lambda v: lax.psum(v, "i") + lax.pmax(v, "i"), 1.0)
    assert analysis.check_collective_order([mk(), mk()]) == []


def test_df004_flags_four_rank_missing_mid_sequence_collective():
    # three ranks run psum; pmax; psum — rank2 skips the mid pmax and
    # goes straight to its second psum: divergence at collective #1
    full = lambda: _rank_jaxpr(
        lambda v: lax.psum(lax.pmax(lax.psum(v, "i"), "i"), "i"), 1.0)
    missing = _rank_jaxpr(
        lambda v: lax.psum(lax.psum(v, "i"), "i"), 1.0)
    names = ["r0", "r1", "r2", "r3"]
    fs = analysis.check_collective_order(
        [full(), full(), missing, full()], rank_names=names)
    assert "DF004" in _rules(fs)
    hits = [f for f in fs if f.rule == "DF004"]
    assert len(hits) == 1                      # only the deviant rank
    assert hits[0].extra["ranks"] == ["r0", "r2"]
    assert hits[0].extra["index"] == 1         # mid-sequence, not #0
    assert "pmax" in hits[0].message


def test_df004_passes_identical_four_rank_schedules():
    mk = lambda: _rank_jaxpr(
        lambda v: lax.psum(lax.pmax(lax.psum(v, "i"), "i"), "i"), 1.0)
    assert analysis.check_collective_order(
        [mk() for _ in range(4)], rank_names=list("abcd")) == []


def test_df004_flags_divergent_cond_branches():
    closed = _rank_jaxpr(
        lambda p, x: lax.cond(p, lambda v: lax.psum(v, "i"),
                              lambda v: v, x), True, 1.0)
    fs = analysis.check_collective_order(closed)
    assert "DF004" in _rules(fs)
    assert any("branch" in f.message for f in fs)


def test_df004_passes_agreeing_cond_branches():
    closed = _rank_jaxpr(
        lambda p, x: lax.cond(p, lambda v: lax.psum(v, "i"),
                              lambda v: lax.psum(v * 2.0, "i"), x),
        True, 1.0)
    assert analysis.check_collective_order(closed) == []


def test_collective_schedule_recurses_into_pjit():
    closed = _rank_jaxpr(
        lambda x: jax.jit(lambda v: lax.psum(v, "i"))(x), 1.0)
    sched = analysis.collective_schedule(closed)
    assert [(prim, axes) for _, prim, axes in sched] == [("psum", ("i",))]


# ---------------------------------------------------------------------------
# DF005 — NaN-prone patterns
# ---------------------------------------------------------------------------

def test_df005_flags_log_of_unclamped_sub():
    closed = jax.make_jaxpr(lambda a, b: jnp.log(a - b))(1.0, 2.0)
    assert "DF005" in _rules(analysis.check_nan_prone(closed))


def test_df005_flags_div_by_unclamped_sub():
    closed = jax.make_jaxpr(lambda a, b: a / (a - b))(1.0, 2.0)
    assert "DF005" in _rules(analysis.check_nan_prone(closed))


def test_df005_passes_clamped_sub():
    closed = jax.make_jaxpr(
        lambda a, b: jnp.log(jnp.maximum(a - b, 1e-6)))(1.0, 2.0)
    assert analysis.check_nan_prone(closed) == []


# ---------------------------------------------------------------------------
# DF006 — inplace/donation alias audit
# ---------------------------------------------------------------------------

def test_df006_shipped_registry_is_clean():
    assert analysis.audit_inplace_aliases() == []


def test_df006_metadata_is_explicit_on_registry_entries():
    from paddle_tpu.ops.registry import get_alias
    exp_alias = get_alias(paddle.exp.op_name)
    assert exp_alias["preserves_shape"] and exp_alias["preserves_dtype"]
    cast_alias = get_alias(paddle.cast.op_name)
    assert not cast_alias["preserves_dtype"]
    reshape_alias = get_alias(paddle.reshape.op_name)
    assert not reshape_alias["preserves_shape"]


def test_df006_flags_wrong_and_missing_metadata(monkeypatch):
    from paddle_tpu.ops import inplace as inplace_mod
    from paddle_tpu.ops import registry

    @registry.defop(name="_lint_probe_tobool", differentiable=False)
    def _tobool(x):
        return x > 0

    @registry.defop(name="_lint_probe_plain", differentiable=False)
    def _plain(x):
        return x * 2

    try:
        # wrong: claims dtype-preserving but maps float32 -> bool
        registry.declare_alias("_lint_probe_tobool", preserves_dtype=True)
        ns = {"tobool": registry.get_op("_lint_probe_tobool"),
              "plain": registry.get_op("_lint_probe_plain")}
        monkeypatch.setattr(inplace_mod, "_INPLACE_NAMES",
                            ["tobool", "plain"])
        fs = analysis.audit_inplace_aliases(namespace=ns)
        assert any(f.rule == "DF006" and "preserves_dtype" in f.message
                   for f in fs)
        assert any(f.rule == "DF006" and "no alias metadata" in f.message
                   for f in fs)
    finally:
        registry.OP_REGISTRY.pop("_lint_probe_tobool", None)
        registry.OP_REGISTRY.pop("_lint_probe_plain", None)


def test_inplace_shape_contract_enforced():
    # the declared-metadata fix: a broadcast that would GROW the tensor
    # now raises instead of silently rebinding a larger buffer
    x = paddle.to_tensor(np.ones((1,), dtype="float32"))
    y = paddle.to_tensor(np.ones((3,), dtype="float32"))
    with pytest.raises(ValueError, match="grow"):
        paddle.add_(x, y)
    # the legitimate same-shape path still works
    z = paddle.to_tensor(np.ones((3,), dtype="float32"))
    paddle.add_(z, y)
    np.testing.assert_allclose(np.asarray(z._data), 2.0)


# ---------------------------------------------------------------------------
# pass-registry integration
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_diagnostic_passes_registered_and_applied():
    for name in analysis.DIAGNOSTIC_PASS_NAMES:
        assert name in ir.list_passes()
        assert ir.is_analysis_pass(name)
    assert not ir.is_analysis_pass("dead_code_elimination")

    def fn(x, y):
        dead = paddle.exp(x)
        return paddle.tanh(x)
    prog = ir.IrProgram.trace(fn, _tensor((2, 3)), _tensor((2, 3), 1))
    out = ir.apply_pass(prog, ["check_dead_code", "check_unused_inputs"])
    assert out.closed is prog.closed          # analysis never rewrites
    assert {"DF002", "DF003"} <= _rules(out.findings)
    assert out.applied_passes == ["check_dead_code", "check_unused_inputs"]
    # transform passes still transform, and keep accumulated findings
    opt = ir.apply_pass(out, "dead_code_elimination")
    assert opt.num_ops() < prog.num_ops()
    assert _rules(opt.findings) == _rules(out.findings)


def test_analyze_helper_runs_all_rules():
    def fn(x):
        return paddle.log(x - 1.0)
    prog = ir.IrProgram.trace(fn, _tensor((2, 2)))
    fs = analysis.analyze(prog)
    assert "DF005" in _rules(fs)


# ---------------------------------------------------------------------------
# TS101..TS104 — AST trace-safety lint
# ---------------------------------------------------------------------------

TS101_BAD = """
import paddle_tpu as paddle

@paddle.jit.to_static
def f(x):
    s = x * 2
    return float(s.sum())
"""

TS101_ITEM_BAD = """
from paddle_tpu import jit

@jit.to_static
def f(x):
    return x.mean().item()
"""

TS101_GOOD = """
def f(x):
    return float(x.sum())   # eager: host sync is fine outside jit
"""


def test_ts101_flags_host_sync_in_jit():
    assert "TS101" in _rules(ast_lint.lint_source(TS101_BAD))
    assert "TS101" in _rules(ast_lint.lint_source(TS101_ITEM_BAD))


def test_ts101_passes_outside_jit():
    assert ast_lint.lint_source(TS101_GOOD) == []


TS102_BAD = """
import jax

@jax.jit
def f(x):
    if x.sum() > 0:
        return x + 1
    return x - 1
"""

TS102_GOOD = """
import jax

@jax.jit
def f(x, training=True):
    if training:              # literal-defaulted param: static config
        return x + 1
    return x - 1
"""


def test_ts102_flags_data_dependent_branch():
    fs = ast_lint.lint_source(TS102_BAD)
    assert "TS102" in _rules(fs)


def test_ts102_passes_static_config_branch():
    assert "TS102" not in _rules(ast_lint.lint_source(TS102_GOOD))


TS103_BAD = """
import jax

def serve(fns, x):
    outs = []
    for fn in fns:
        step = jax.jit(fn)    # one compile per iteration
        outs.append(step(x))
    return outs
"""

TS103_GOOD = """
import jax

def serve(fns, x):
    steps = [jax.jit(f) for f in fns]
    return None
"""


def test_ts103_flags_jit_in_loop():
    assert "TS103" in _rules(ast_lint.lint_source(TS103_BAD))


def test_ts103_passes_hoisted_jit():
    assert "TS103" not in _rules(ast_lint.lint_source(TS103_GOOD))


TS104_BAD = """
import jax

TRACE_LOG = []

@jax.jit
def f(x):
    print(x)
    TRACE_LOG.append(x)
    return x * 2
"""

TS104_GOOD = """
import jax

@jax.jit
def f(x):
    print("entering f")       # constant print: harmless trace-time noise
    return x * 2
"""


def test_ts104_flags_trace_side_effects():
    fs = [f for f in ast_lint.lint_source(TS104_BAD) if f.rule == "TS104"]
    msgs = " ".join(f.message for f in fs)
    assert "print" in msgs and "TRACE_LOG" in msgs


def test_ts104_passes_constant_print():
    assert "TS104" not in _rules(ast_lint.lint_source(TS104_GOOD))


# ---------------------------------------------------------------------------
# suppressions + baseline
# ---------------------------------------------------------------------------

def test_inline_suppression_on_line():
    src = TS101_BAD.replace("return float(s.sum())",
                            "return float(s.sum())  # tpu-lint: disable=TS101")
    assert "TS101" not in _rules(ast_lint.lint_source(src))


def test_inline_suppression_on_def_line_covers_function():
    src = TS101_BAD.replace("def f(x):",
                            "def f(x):  # tpu-lint: disable=TS101")
    assert "TS101" not in _rules(ast_lint.lint_source(src))


def test_file_wide_suppression():
    src = "# tpu-lint: disable-file=TS101\n" + TS101_BAD
    assert "TS101" not in _rules(ast_lint.lint_source(src))


def test_baseline_roundtrip(tmp_path):
    fs = ast_lint.lint_source(TS101_BAD, path="pkg/mod.py")
    assert fs
    path = str(tmp_path / "baseline.json")
    findings_mod.write_baseline(fs, path)
    baseline = findings_mod.load_baseline(path)
    assert findings_mod.apply_baseline(fs, baseline) == []
    # a different finding is NOT masked by the baseline
    other = ast_lint.lint_source(TS102_BAD, path="pkg/other.py")
    assert findings_mod.apply_baseline(other, baseline) == other


def test_rule_catalog_is_stable():
    assert set(findings_mod.RULES) >= {
        "DF001", "DF002", "DF003", "DF004", "DF005", "DF006",
        "TS101", "TS102", "TS103", "TS104", "TS105",
        "SH201", "SH202", "SH203", "SH204", "MEM301", "MEM302",
        "CC401", "CC402", "CC403", "CC404", "CC405", "CC406"}
    for rule, meta in findings_mod.RULES.items():
        assert meta["severity"] in ("error", "warning")
        assert meta["doc"]
    assert findings_mod.RULES["SH201"]["severity"] == "error"
    assert findings_mod.RULES["MEM301"]["severity"] == "error"


# ---------------------------------------------------------------------------
# TS105 — fresh closure capture (silent recompile-per-call)
# ---------------------------------------------------------------------------

TS105_BAD = """
import numpy as np
import jax

def make_step(scale):
    table = np.array([1.0, 2.0, 3.0])
    @jax.jit
    def step(x):
        return x * table * scale
    return step
"""

TS105_CTOR_BAD = """
import numpy as np
import jax

def make_step():
    mask = np.tril(np.ones((4, 4)))
    def step(x):
        return x * mask
    return jax.jit(step)
"""

TS105_GOOD_MODULE_SCOPE = """
import numpy as np
import jax

TABLE = np.array([1.0, 2.0, 3.0])

def make_step(scale):
    @jax.jit
    def step(x):
        return x * TABLE * scale
    return step
"""

TS105_GOOD_ARGUMENT = """
import numpy as np
import jax

def make_step():
    table = np.array([1.0, 2.0, 3.0])
    @jax.jit
    def step(x, table):
        return x * table
    return step
"""


def test_ts105_flags_fresh_capture_in_decorated_closure():
    fs = [f for f in ast_lint.lint_source(TS105_BAD) if f.rule == "TS105"]
    assert len(fs) == 1
    assert "table" in fs[0].message and "recompile" in fs[0].message


def test_ts105_flags_fresh_capture_via_jit_ctor():
    assert "TS105" in _rules(ast_lint.lint_source(TS105_CTOR_BAD))


def test_ts105_passes_module_scope_and_argument():
    assert ast_lint.lint_source(TS105_GOOD_MODULE_SCOPE) == []
    assert ast_lint.lint_source(TS105_GOOD_ARGUMENT) == []


def test_ts105_suppressed_on_enclosing_def_line():
    src = TS105_BAD.replace("def make_step(scale):",
                            "def make_step(scale):  # tpu-lint: disable=TS105")
    assert "TS105" not in _rules(ast_lint.lint_source(src))


# ---------------------------------------------------------------------------
# SH201..SH204 — SPMD shard-safety (jaxpr propagation)
# ---------------------------------------------------------------------------

from paddle_tpu.analysis import memory as memory_mod  # noqa: E402
from paddle_tpu.analysis import sharding as sharding_mod  # noqa: E402


def _load_plan():
    with open(os.path.join(REPO, "PLAN_7B.json")) as fh:
        return json.load(fh)


def _load_roofline():
    with open(os.path.join(REPO, "ROOFLINE.json")) as fh:
        return json.load(fh)


def test_sh201_flags_non_divisible_input_and_passes_divisible():
    closed = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((3, 4)))
    fs = analysis.check_sharding(closed, {"x": 2}, in_specs=[("x", None)])
    assert "SH201" in _rules(fs)
    assert all(f.severity == "error" for f in fs if f.rule == "SH201")
    closed2 = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((4, 4)))
    assert analysis.check_sharding(
        closed2, {"x": 2}, in_specs=[("x", None)]) == []


def test_sh202_flags_one_sided_contraction_and_passes_matched():
    fn = lambda x, w: x @ w
    closed = jax.make_jaxpr(fn)(jnp.ones((8, 16)), jnp.ones((16, 4)))
    fs = analysis.check_sharding(
        closed, {"x": 4}, in_specs=[(None, "x"), (None, None)])
    assert "SH202" in _rules(fs)
    assert any("all-gather" in f.message for f in fs)
    # both operands sharded on the contraction dim: Partial out, no gather
    assert analysis.check_sharding(
        closed, {"x": 4}, in_specs=[(None, "x"), ("x", None)]) == []


def test_sh202_flags_elementwise_placement_disagreement():
    fn = lambda a, b: a + b
    closed = jax.make_jaxpr(fn)(jnp.ones((8, 8)), jnp.ones((8, 8)))
    fs = analysis.check_sharding(
        closed, {"x": 2, "y": 2}, in_specs=[("x", None), ("y", None)])
    assert "SH202" in _rules(fs)
    assert analysis.check_sharding(
        closed, {"x": 2}, in_specs=[("x", None), ("x", None)]) == []


def test_sh202_propagation_resolves_partial_through_psum():
    def fn(x, w):
        return lax.psum(x @ w, "i")
    closed = jax.make_jaxpr(fn, axis_env=[("i", 4)])(
        jnp.ones((8, 16)), jnp.ones((16, 4)))
    res = analysis.propagate_placements(
        closed, {"i": 4}, in_specs=[(None, "i"), ("i", None)])
    out_var = closed.jaxpr.outvars[0]
    assert res.var_specs[out_var].partial == frozenset()
    assert res.collective_bytes > 0


def test_sh203_flags_over_budget_and_passes_generous():
    closed = jax.make_jaxpr(
        lambda v: lax.psum(v, "i"), axis_env=[("i", 2)])(
        jnp.ones((1024, 1024)))
    fs = analysis.check_sharding(
        closed, {"i": 2}, collective_budget_bytes=10.0)
    assert "SH203" in _rules(fs)
    assert analysis.check_sharding(
        closed, {"i": 2}, collective_budget_bytes=1e12) == []


def test_sh203_plan_level_roofline_budget():
    plan, roof = _load_plan(), _load_roofline()
    # the shipped plan is compute-bound under the real roofline
    assert [f for f in analysis.check_plan_sharding(plan, roofline=roof)
            if f.rule == "SH203"] == []
    # a starved interconnect makes every variant ICI-bound
    starved = dict(roof, peak_ici=1e9)
    fs = analysis.check_plan_sharding(plan, roofline=starved)
    assert {f.extra["variant"] for f in fs if f.rule == "SH203"} \
        == {"s2", "s3", "s3_full"}


def test_sh204_flags_replicated_param_and_passes_sharded():
    params = {"w": ((4096, 4096), None),      # big, divisible, replicated
              "ln": ((4096,), None)}          # small: below min_bytes
    fs = analysis.check_fsdp_replication(params, {"z": 16}, "z")
    assert [f.rule for f in fs] == ["SH204"]
    assert fs[0].extra["param"] == "w"
    sharded = {"w": ((4096, 4096), ("z", None))}
    assert analysis.check_fsdp_replication(sharded, {"z": 16}, "z") == []


def test_divisible_dim_is_single_sourced():
    from paddle_tpu.distributed.sharding import _divisible_dim
    for shape, deg in [((7, 8), 4), ((16, 3), 4), ((5, 7), 2), ((8,), 8)]:
        assert _divisible_dim(shape, deg) \
            == analysis.divisible_dim(shape, deg)


# ---------------------------------------------------------------------------
# MEM301/MEM302 — liveness peak-HBM (jaxpr level)
# ---------------------------------------------------------------------------

def test_mem301_flags_tiny_budget_and_passes_generous():
    closed = jax.make_jaxpr(lambda x: jnp.tanh(x) @ x.T)(
        jnp.ones((256, 256)))
    fs = memory_mod.check_hbm(closed, budget_gib=1e-6)
    assert "MEM301" in _rules(fs)
    assert all(f.severity == "error" for f in fs if f.rule == "MEM301")
    fs = memory_mod.check_hbm(closed, budget_gib=64.0, donate=(0,))
    assert "MEM301" not in _rules(fs)


def test_mem302_flags_missing_donation_and_passes_donated():
    # x (4 MiB) dies at exp, whose registry alias metadata permits reuse
    closed = jax.make_jaxpr(lambda x: jnp.exp(x))(jnp.ones((1024, 1024)))
    fs = memory_mod.check_hbm(closed)
    assert [f.rule for f in fs] == ["MEM302"]
    assert "donate" in fs[0].message
    assert memory_mod.check_hbm(closed, donate=(0,)) == []


def test_peak_hbm_estimate_credits_donated_reuse():
    closed = jax.make_jaxpr(lambda x: jnp.exp(x))(
        jnp.ones((1024, 1024), jnp.float32))
    plain = memory_mod.peak_hbm_estimate(closed)
    donated = memory_mod.peak_hbm_estimate(closed, donate=(0,))
    mib = 1 << 20
    assert plain["peak_bytes"] == 8 * mib      # input + fresh output
    assert donated["peak_bytes"] == 4 * mib    # output reuses the input
    assert plain["missed_donations"] and not donated["missed_donations"]


# ---------------------------------------------------------------------------
# MEM301/MEM302 + SH201 — plan-level gate (PLAN_7B.json)
# ---------------------------------------------------------------------------

def test_plan_memory_shipped_variants_pass():
    plan = _load_plan()
    rows = []
    fs = memory_mod.check_plan_memory(plan, rows=rows)
    # documented-infeasible baselines (fits_v5e_16gib: false) are not
    # errors; the MEM302 headroom pointer to s3_full is expected
    assert not findings_mod.has_errors(fs)
    assert {f.rule for f in fs} <= {"MEM302"}
    by_name = {r["variant"]: r for r in rows}
    assert by_name["s3_full"]["fits"]
    # the recorded-bytes model reproduces the recorded live GiB
    assert abs(by_name["s2"]["live_gib"] - 47.384) < 0.01
    assert abs(by_name["s3_full"]["live_gib"] - 12.141) < 0.01


def test_mem301_flags_oversubscribed_s2_at_batch_64():
    plan = _load_plan()
    fs = memory_mod.check_plan_memory(plan, batch=64)
    flagged = {f.extra["variant"] for f in fs if f.rule == "MEM301"}
    assert "s2" in flagged
    assert findings_mod.has_errors(fs)
    s2 = [f for f in fs if f.rule == "MEM301"
          and f.extra["variant"] == "s2"][0]
    assert s2.extra["live_gib"] > 100          # 4x activations over 47 GiB


def test_mem302_plan_points_at_fitting_sibling():
    plan = _load_plan()
    fs = memory_mod.check_plan_memory(plan)
    sibs = {f.extra["variant"]: f.extra["sibling"] for f in fs
            if f.rule == "MEM302"}
    assert sibs == {"s2": "s3_full", "s3": "s3_full"}


def test_plan_sharding_shipped_mesh_passes_and_mesh7_flags_sh201():
    plan, roof = _load_plan(), _load_roofline()
    assert analysis.check_plan_sharding(plan, roofline=roof) == []
    fs = analysis.check_plan_sharding(plan, mesh_size=7)
    assert "SH201" in _rules(fs)
    flagged = {f.extra["param"] for f in fs if f.rule == "SH201"}
    assert "embed" in flagged and "wq" in flagged


def test_serving_buckets_shipped_pass_and_flag_paths():
    plan = _load_plan()
    rep = memory_mod.serving_bucket_report(plan)
    assert rep["findings"] == []
    assert all(r["fits"] for r in rep["rows"])
    assert max(r["bucket"] for r in rep["rows"]) == 2048
    # tiny budget: KV cache blows through it -> MEM301
    rep = memory_mod.serving_bucket_report(plan, hbm_gib=0.5)
    assert "MEM301" in {f.rule for f in rep["findings"]}
    # 7 chips cannot split 32 attention heads -> SH201
    rep = memory_mod.serving_bucket_report(plan, mesh_size=7)
    assert "SH201" in {f.rule for f in rep["findings"]}


# ---------------------------------------------------------------------------
# observability: analysis.findings{rule=...} counters
# ---------------------------------------------------------------------------

def test_analysis_passes_feed_metrics_registry():
    from paddle_tpu.observability import get_registry
    def fn(x):
        dead = paddle.exp(x) * 3.0
        return paddle.tanh(x)
    prog = ir.IrProgram.trace(fn, _tensor((3, 4)))
    fam = get_registry().counter(
        "analysis.findings",
        "findings emitted by static-analysis passes, by rule",
        labelnames=("rule",))
    expected = len(analysis.check_dead_code(prog))
    assert expected >= 1
    before = fam.labels(rule="DF002").value
    ir.apply_pass(prog, "check_dead_code")
    assert fam.labels(rule="DF002").value == before + expected


# ---------------------------------------------------------------------------
# CLI + tier-1 lint gate
# ---------------------------------------------------------------------------

def _run_cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpu_lint.py"),
         *args], cwd=cwd, capture_output=True, text=True)


def _run_shard_cli(*args, cwd=REPO):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "shard_check.py"),
         *args], cwd=cwd, capture_output=True, text=True)


@pytest.mark.lint
@pytest.mark.quick
def test_lint_gate_shipped_tree_is_clean_and_fast():
    proc = _run_cli("paddle_tpu", "examples", "tools", "benchmarks")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # the gate ran to its summary over the four trees (how long a case
    # may take is conftest's CASE_LIMIT_S, the same for every case)
    assert "finding(s): 0 error(s)" in proc.stdout, proc.stdout


@pytest.mark.lint
@pytest.mark.quick
def test_shard_check_gate_shipped_plan_is_clean_and_fast():
    proc = _run_shard_cli()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "s3_full" in proc.stdout
    assert "finding(s): 0 error(s)" in proc.stdout, proc.stdout


@pytest.mark.lint
@pytest.mark.quick
def test_trace_analyze_gate_demo_workload_attributes_cleanly():
    """The attribution CLI is part of the lint lane: trace_analyze
    --json over the gateway demo workload must produce complete
    waterfalls, a balanced goodput ledger, and no findings parse
    errors — the smoke gate for the observability.{waterfall,ledger,
    anomaly} stack."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_analyze.py"),
         "--json", "--top", "3"], cwd=REPO, capture_output=True,
        text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["n_traces"] >= 3 and payload["incomplete"] == 0
    assert payload["requests"] and payload["requests"][0]["critical_path"]
    led = payload["ledger"]
    assert led["chip_seconds"] > 0.0 and 0.0 < led["goodput_frac"] <= 1.0
    assert set(led["waste_seconds"]) == {
        "bucket_pad", "requeue_recompute", "evicted_prefix_recompute",
        "speculation_rejected", "recompile", "dequant"}
    assert {"prefill", "decode"} <= set(led["by_phase"])
    assert {"prefill", "decode"} <= set(payload["critical_path_summary"])


@pytest.mark.lint
@pytest.mark.quick
def test_ckpt_inspect_gate_selftest_is_clean_and_fast():
    """tools/ckpt_inspect.py rides the lint lane: its --selftest builds
    a synthetic checkpoint root (one sound step, one torn step, then a
    corrupted payload) with hand-crafted npy bytes and asserts its own
    verdicts — stdlib only: it must run where jax cannot be imported."""
    proc = subprocess.run(
        [sys.executable, "-S",      # -S: no site-packages to import
         os.path.join(REPO, "tools", "ckpt_inspect.py"),
         "--selftest"], cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "selftest" in (proc.stdout + proc.stderr).lower()


@pytest.mark.lint
@pytest.mark.quick
def test_session_inspect_gate_selftest_is_clean_and_fast():
    """tools/session_inspect.py rides the lint lane: its --selftest
    builds a synthetic session root (sound, torn-publish debris, token
    bit-rot under stale CRCs, chain-hash drift under a re-sealed
    document CRC) and asserts every verdict — stdlib only: it must run
    where neither numpy nor jax can be imported."""
    proc = subprocess.run(
        [sys.executable, "-S",      # -S: no site-packages to import
         os.path.join(REPO, "tools", "session_inspect.py"),
         "--selftest"], cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "selftest" in (proc.stdout + proc.stderr).lower()


def test_shard_check_cli_flags_oversubscribed_batch():
    proc = _run_shard_cli("--batch", "64", "--json")
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    flagged = {f["extra"]["variant"] for f in payload["findings"]
               if f["rule"] == "MEM301"}
    assert "s2" in flagged


def test_shard_check_cli_flags_non_divisible_mesh():
    proc = _run_shard_cli("--mesh", "7", "--json")
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert any(f["rule"] == "SH201" for f in payload["findings"])


def test_shard_check_cli_what_if_budget_passes():
    # a 64 GiB chip swallows every shipped variant -> exit 0, no MEM302
    proc = _run_shard_cli("--hbm-gib", "64", "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert all(r["fits"] for r in payload["variants"])


def test_cli_flags_errors_nonzero_and_emits_json(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(TS101_BAD)
    proc = _run_cli("--json", "--baseline", "none", str(bad),
                    cwd=str(tmp_path))
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert any(f["rule"] == "TS101" for f in payload["findings"])


def test_cli_baseline_accepts_known_findings(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(TS101_BAD)
    base = tmp_path / "base.json"
    proc = _run_cli("--write-baseline", "--baseline", str(base), str(bad),
                    cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    proc = _run_cli("--baseline", str(base), str(bad), cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# CC401-CC404 — static lock-discipline rules (analysis/concurrency.py)
# ---------------------------------------------------------------------------

from paddle_tpu.analysis import concurrency  # noqa: E402


CC401_BAD = """
import threading
A = threading.Lock()
B = threading.Lock()

def forward():
    with A:
        with B:
            pass

def backward():
    with B:
        with A:
            pass
"""

CC401_GOOD = """
import threading
A = threading.Lock()
B = threading.Lock()

def forward():
    with A:
        with B:
            pass

def backward():
    with A:
        with B:
            pass
"""

CC401_TRANSITIVE_BAD = """
import threading
A = threading.Lock()
B = threading.Lock()

def inner():
    with B:
        pass

def forward():
    with A:
        inner()          # A -> B through the call graph

def backward():
    with B:
        with A:
            pass
"""


def test_cc401_flags_lock_order_cycle():
    assert "CC401" in _rules(concurrency.analyze_source(CC401_BAD, "m.py"))


def test_cc401_passes_consistent_order():
    assert "CC401" not in _rules(concurrency.analyze_source(CC401_GOOD, "m.py"))


def test_cc401_sees_acquisitions_through_the_call_graph():
    fs = concurrency.analyze_source(CC401_TRANSITIVE_BAD, "m.py")
    assert "CC401" in _rules(fs)


CC402_BAD = """
import threading
import time
LOCK = threading.Lock()

def slow_path():
    with LOCK:
        time.sleep(0.5)
"""

CC402_GOOD = """
import threading
import time
LOCK = threading.Lock()

def slow_path():
    with LOCK:
        x = 1
    time.sleep(0.5)
"""


def test_cc402_flags_blocking_call_under_lock():
    assert "CC402" in _rules(concurrency.analyze_source(CC402_BAD, "m.py"))


def test_cc402_passes_blocking_call_outside_lock():
    assert "CC402" not in _rules(concurrency.analyze_source(CC402_GOOD, "m.py"))


CC403_BAD = """
import threading

class Owner:
    def __init__(self):
        self._lock = threading.Lock()
        self._callbacks = []

    def fire(self):
        with self._lock:
            for cb in self._callbacks:
                cb("event")
"""

CC403_GOOD = """
import threading

class Owner:
    def __init__(self):
        self._lock = threading.Lock()
        self._callbacks = []

    def fire(self):
        with self._lock:
            cbs = list(self._callbacks)
        for cb in cbs:
            cb("event")
"""


def test_cc403_flags_callback_under_lock():
    assert "CC403" in _rules(concurrency.analyze_source(CC403_BAD))


def test_cc403_passes_callback_after_snapshot():
    assert "CC403" not in _rules(concurrency.analyze_source(CC403_GOOD))


CC404_BAD = """
import threading

class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def bump(self):
        with self._lock:
            self._n += 1

    def sneak(self):
        self._n = 0          # bare write to lock-guarded state
"""

CC404_GOOD = """
import threading

class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def bump(self):
        with self._lock:
            self._n += 1

    def reset(self):
        with self._lock:
            self._n = 0
"""


def test_cc404_flags_unguarded_write_to_guarded_state():
    fs = concurrency.analyze_source(CC404_BAD)
    assert "CC404" in _rules(fs)
    assert any("sneak" in f.message for f in fs if f.rule == "CC404")


def test_cc404_passes_when_every_write_is_guarded():
    assert "CC404" not in _rules(concurrency.analyze_source(CC404_GOOD))


def test_cc404_exempts_init_time_writes():
    # __init__ constructs the state the lock will guard — not a race
    fs = concurrency.analyze_source(CC404_GOOD)
    assert not any(f.line <= 7 for f in fs if f.rule == "CC404")


def test_cc_suppression_comment_is_honored():
    src = CC402_BAD.replace("time.sleep(0.5)",
                            "time.sleep(0.5)  # tpu-lint: disable=CC402")
    assert "CC402" not in _rules(concurrency.analyze_source(src, "m.py"))


def test_cc_rules_have_catalog_severities():
    assert findings_mod.RULES["CC401"]["severity"] == "error"
    assert findings_mod.RULES["CC405"]["severity"] == "error"
    assert findings_mod.RULES["CC402"]["severity"] == "warning"


# ---------------------------------------------------------------------------
# CC405/CC406 — the runtime lock witness (utils/locks.py)
# ---------------------------------------------------------------------------


def _fresh_witness(monkeypatch, budget_s=None, value="1"):
    from paddle_tpu.utils import locks
    monkeypatch.setenv("PADDLE_LOCK_WITNESS", value)
    return locks.reset_witness(budget_s=budget_s)


def test_cc405_two_thread_inversion_drill(monkeypatch):
    """The seeded deadlock drill: thread 1 takes A then B, thread 2
    takes B then A (run to completion sequentially, so the drill can
    never actually deadlock) — the witness MUST record the CC405 order
    inversion."""
    import threading

    from paddle_tpu.utils import locks
    _fresh_witness(monkeypatch)
    a, b = locks.TracedLock("drill.A"), locks.TracedLock("drill.B")

    def forward():
        with a:
            with b:
                pass

    def backward():
        with b:
            with a:
                pass

    for fn in (forward, backward):
        t = threading.Thread(target=fn)
        t.start()
        t.join()
    found = [f for f in locks.get_witness().findings
             if f["rule"] == "CC405"]
    assert found, "inversion not witnessed"
    assert {"drill.A", "drill.B"} == set(found[0]["locks"])
    # and the typed Finding surface sees it too
    assert "CC405" in {f.rule for f in locks.witness_findings()}


def test_cc405_consistent_order_twin_stays_silent(monkeypatch):
    import threading

    from paddle_tpu.utils import locks
    _fresh_witness(monkeypatch)
    a, b = locks.TracedLock("twin.A"), locks.TracedLock("twin.B")

    def forward():
        with a:
            with b:
                pass

    for _ in range(2):
        t = threading.Thread(target=forward)
        t.start()
        t.join()
    assert not locks.get_witness().findings


def test_cc405_strict_mode_raises_and_releases(monkeypatch):
    from paddle_tpu.utils import locks
    _fresh_witness(monkeypatch, value="strict")
    a, b = locks.TracedLock("strict.A"), locks.TracedLock("strict.B")
    with a:
        with b:
            pass
    with b:
        with pytest.raises(locks.LockOrderInversion):
            a.acquire()
    # the refused acquisition must not leave either lock held
    assert a.acquire(timeout=0.1)
    a.release()


def test_cc406_over_budget_hold_is_recorded(monkeypatch):
    from paddle_tpu.utils import locks
    _fresh_witness(monkeypatch, budget_s=0.005)
    lk = locks.TracedLock("budget.L")
    with lk:
        time.sleep(0.02)
    w = locks.get_witness()
    assert any(f["rule"] == "CC406" for f in w.findings)
    assert w.max_hold("budget.L") >= 0.005


def test_witness_dump_roundtrips_through_audit(tmp_path, monkeypatch):
    import threading

    from paddle_tpu.utils import locks
    _fresh_witness(monkeypatch)
    a, b = locks.TracedLock("rt.A"), locks.TracedLock("rt.B")

    def forward():
        with a:
            with b:
                pass

    def backward():
        with b:
            with a:
                pass

    for fn in (forward, backward):
        t = threading.Thread(target=fn)
        t.start()
        t.join()
    path = tmp_path / "witness_test.json"
    locks.dump_witness(str(path))
    fs = concurrency.audit_witness_paths([str(tmp_path)])
    assert "CC405" in _rules(fs)


def test_witness_off_hands_out_raw_locks(monkeypatch):
    """The <1%% overhead guard, proven structurally: with the witness
    off the factories return RAW threading primitives — the hot path
    pays literally zero instrumentation."""
    import threading

    from paddle_tpu.utils import locks
    monkeypatch.delenv("PADDLE_LOCK_WITNESS", raising=False)
    assert type(locks.TracedLock("x")) is type(threading.Lock())
    assert type(locks.TracedRLock("x")) is type(threading.RLock())
    assert not locks.witness_enabled()


@pytest.mark.quick
def test_witness_off_overhead_under_one_percent(monkeypatch):
    """Belt to the structural suspenders: time a serving-step-shaped
    critical section (dict bookkeeping under a lock) with a plain
    threading.Lock vs a witness-off TracedLock. Identical types, so
    the budget only needs to absorb timer noise."""
    import threading

    from paddle_tpu.utils import locks
    monkeypatch.delenv("PADDLE_LOCK_WITNESS", raising=False)

    def drive(lk, n=20000):
        state = {}
        t0 = time.perf_counter()
        for i in range(n):
            with lk:
                state[i & 63] = i
        return time.perf_counter() - t0

    raw, traced = threading.Lock(), locks.TracedLock("serve.step")
    drive(raw), drive(traced)                      # warm both paths
    # same type -> same cost. Each ratio is of two loops run back to
    # back, so a busy machine slows both sides of it; the median of nine
    # drops the pairs a preemption split. 25% headroom still catches any
    # accidental wrapper (a Python-level __enter__ costs 3x and more)
    ratios = sorted(drive(traced) / drive(raw) for _ in range(9))
    assert ratios[4] < 1.25, ratios


@pytest.mark.lint
@pytest.mark.quick
def test_race_check_gate_shipped_tree_is_clean_and_fast():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "race_check.py"),
         "paddle_tpu", "tools", "benchmarks"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "finding(s): 0 error(s)" in proc.stdout, proc.stdout


def test_race_check_cli_flags_cycle_and_respects_baseline(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(CC401_BAD)

    def run(*args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "race_check.py"),
             *args], cwd=str(tmp_path), capture_output=True, text=True)

    proc = run("--json", "--baseline", "none", str(bad))
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert any(f["rule"] == "CC401" for f in payload["findings"])
    base = tmp_path / "base.json"
    proc = run("--write-baseline", "--baseline", str(base), str(bad))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    proc = run("--baseline", str(base), str(bad))
    assert proc.returncode == 0, proc.stdout + proc.stderr
