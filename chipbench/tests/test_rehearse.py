"""--rehearse runs of the three runner kinds, untraced and traced: the same
code path at tiny sizes, whose last lines pass the harness's own check."""
import json
import os
import subprocess
import sys

import pytest

from chipbench.lastline import problems

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def rehearse(workload, trace, seed, tmp_path, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out, json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_prints_a_line_that_passes(workload, trace, tmp_path):
    out, line = rehearse(workload, trace, 2 ** 31 + 5, tmp_path)
    # off the chip there are no peaks, no Pallas kernels and no module line
    # in the trace to read
    no_chip = ("train.mfu", "flash_fwd_roofline", "flash_bwd_roofline",
               "serve.mbu.batch", "decode_device_ms.batch",
               "decode_device_ms.sessions",
               "prefill_chunk_device_ms.sessions", "paged_attn_roofline")
    assert problems(line, BENCH, workload, bool(trace), 1, no_chip) == []
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] != "tpu"   # says what it ran on
    assert list(line)[-1] == "compared"
    assert out.stderr.strip().splitlines()[-1].startswith("compared: ")
    early = out.stdout
    for key in ("fingerprints:", "flash_tilings:", "step_times:",
                "compiles_in_window:", "setup_phases_s:"):
        assert key in early
    assert '"FLAGS_flash_autotune": false' in early
    assert '"compiled": 0' in early


def test_no_chip_no_number(tmp_path):
    """Without --rehearse a run off the chip exits non-zero, no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "smollm2-train-seq2k", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert not out.stdout.strip().startswith("{")
    assert "needs a TPU" in out.stderr
