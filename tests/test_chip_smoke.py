"""Rehearse chip_smoke.py without the chip, and pin what keeps a measurement
path from passing without one.

The rehearsals run the script in this process at its ``--rehearse`` sizes,
once on one device and once with ``--chips 4``; the tests then each read one
aspect of what it printed. The test stands in for the two things only a TPU
can answer: the device assertion, and the presence of the Pallas kernels in
the step's HLO (off the chip the program takes the kernels' XLA references).
Everything else the script checks — falling loss, no warm compile, token
counts, served tokens against the plain forward, the prefix hit, the page
audit, parameter placement on a four-device mesh — it checks for real, and a
failed check fails the rehearsal fixture.
"""
import ast
import contextlib
import importlib
import io
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def _stand_ins(tmp_path):
    """chip_smoke with stand-ins for the chip; yields (module, the calls
    made to the kernel check)."""
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(REPO)
    mod = importlib.import_module("chip_smoke")
    kernel_checks = []
    mp.setattr(mod, "require_tpu", lambda min_devices=1: jax.devices())
    mp.setattr(mod, "require_kernels",
               lambda hlo, names: kernel_checks.append((len(hlo), names)) or 0)
    # the variable is read by JAX at import, so setting it here only tells
    # enable_persistent_cache() to place no directory: tier-1 stays
    # without an on-disk cache
    mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        yield mod, kernel_checks
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
        mp.undo()


def _rehearse(tmp_path, argv):
    out = io.StringIO()
    with _stand_ins(tmp_path) as (mod, kernel_checks), \
            contextlib.redirect_stdout(out):
        mod.main(argv)
    lines = out.getvalue().strip().splitlines()
    fields = dict(ln.split(": ", 1) for ln in lines[:-1])
    return {"fields": fields, "last": lines[-1], "kernels": kernel_checks}


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    return _rehearse(tmp_path_factory.mktemp("one"), ["--rehearse"])


@pytest.fixture(scope="module")
def four_chips(tmp_path_factory):
    return _rehearse(tmp_path_factory.mktemp("four"),
                     ["--rehearse", "--chips", "4"])


def test_last_line_is_the_contract(one_chip):
    dev = jax.devices()[0]
    assert json.loads(one_chip["last"]) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}
    assert list(json.loads(one_chip["last"])) == ["ok", "device"]
    assert not any(v.startswith('{"ok"') for v in one_chip["fields"].values())


def test_earlier_lines_say_what_is_worth_keeping(one_chip):
    f = one_chip["fields"]
    assert ast.literal_eval(f["versions"])["jax"] == jax.__version__
    assert set(ast.literal_eval(f["native"])) == {"available", "error"}
    assert "compile_cache_dir" in f and "device" in f
    assert set(ast.literal_eval(f["compile_cache"])) >= {
        "compile_cache_misses", "persistent_cache_hits"}
    assert not any(k.startswith("sharded.") for k in f)


def test_trainer_phase_rehearsed(one_chip):
    f = one_chip["fields"]
    losses = ast.literal_eval(f["trainer.losses"])
    assert len(losses) >= 4 and losses[-1] < losses[0]     # 1 + 3 warm
    assert "depth 2" in f["trainer.config"]
    assert int(f["trainer.params"]) > 0
    # the compiled step's HLO was handed to the kernel check, by name
    [(n_chars, names)] = one_chip["kernels"]
    assert n_chars > 0
    assert {"flash_fwd", "flash_bwd_dkv", "rms_norm_fwd",
            "rms_norm_bwd"} <= set(names)


def test_server_phase_rehearsed(one_chip):
    f = one_chip["fields"]
    tokens = ast.literal_eval(f["server.tokens"])
    assert len(tokens) >= 4 and len(set(tokens.values())) == 1
    assert int(f["server.prefix_hit_tokens"]) >= 32
    assert f["server.audit_pages"] == "0"
    assert "tolerance" in f["server.token_gap"]


def test_four_chip_option_runs_the_sharded_phase_alone(four_chips):
    assert json.loads(four_chips["last"])["device"]["count"] \
        == len(jax.devices()) >= 4
    phases = {k.split(".")[0] for k in four_chips["fields"] if "." in k}
    assert phases == {"sharded"}


def test_sharded_phase_rehearsed(four_chips):
    f = four_chips["fields"]
    assert ast.literal_eval(f["sharded.losses"]) \
        == ast.literal_eval(f["sharded.single_device_losses"])
    placed = ast.literal_eval(f["sharded.param_bytes"])
    assert len(placed["per_device"]) == 4
    assert max(placed["per_device"].values()) < 0.5 * placed["total"]
    assert ast.literal_eval(f["sharded.collectives"])["all-gather"] > 0
    assert len(four_chips["kernels"]) == 1


def test_device_assertion_has_no_cpu_branch(monkeypatch):
    """The one thing the rehearsals replace, exercised for real."""
    monkeypatch.syspath_prepend(REPO)
    with pytest.raises(SystemExit, match="needs a TPU"):
        importlib.import_module("chip_smoke").main(["--rehearse"])


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_measurement_entry_points_fail_without_a_chip(script):
    """No TPU: non-zero exit and no result line on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, script)],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout, proc.stdout
    assert "TPU" in proc.stderr


def test_peak_flops_raises_for_unknown_device():
    from types import SimpleNamespace

    from paddle_tpu.utils.flops import PEAK_BF16_FLOPS, peak_device_flops

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert peak_device_flops(v5e) == PEAK_BF16_FLOPS["v5 lite"] == 197e12
    with pytest.raises(ValueError, match="PEAK_BF16_FLOPS"):
        peak_device_flops(SimpleNamespace(platform="tpu",
                                          device_kind="TPU v9 imaginary"))
    with pytest.raises(ValueError, match="platform 'cpu'"):
        peak_device_flops(jax.devices("cpu")[0])


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory in
    code (JAX reads the variable itself); without it the cache is the fixed
    <checkout>/.jax_cache. Either way short compiles are cached too."""
    from jax.experimental.compilation_cache import compilation_cache as jcc

    from paddle_tpu.perf import compile_cache as cc

    dir_was = jax.config.jax_compilation_cache_dir
    floor_was = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        assert cc.enable_persistent_cache() == dir_was
        assert jax.config.jax_compilation_cache_dir == dir_was
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert cc.enable_persistent_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert set(cc.compile_metrics()) >= {"persistent_cache_hits",
                                             "persistent_cache_misses"}
    finally:
        jax.config.update("jax_compilation_cache_dir", dir_was)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor_was)
        jcc.reset_cache()
