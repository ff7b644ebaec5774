"""The cell ``phi4flash-reason-open`` and its family ``sambay``: the
rehearsal's last line, the float8 control, two faults of the timed path that
have to come out as not correct, and the count functions against numbers
worked by hand. (``test_control.py`` and ``test_broken_path.py`` list their
cells by name, so this cell's cases live here.)"""
import inspect
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import families, serve
from chipbench import reference as R
from chipbench.lastline import problems
from chipbench.peaks import peaks_for

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "phi4flash-reason-open"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def load(sub, name):
    with open(os.path.join(HERE, sub, name + ".json")) as f:
        return json.load(f)


PUBLISHED = load("configs", "phi-4-mini-flash-serve")
FAMILY = families.of(PUBLISHED)
LIMIT = load("cells", CELL)["limits"]["served_logit_gap"]


# -- the rehearsal ------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_line_that_passes(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 5), "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    # off the chip: no module line, no peaks
    no_chip = ("decode_device_ms.reason", "prefill_chunk_device_ms.reason",
               "serve.mbu.reason", "ssm_scan_roofline",
               "decode_state_roofline")
    assert problems(line, BENCH, CELL, bool(trace), 1, no_chip) == []
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["leaked_pages"]["value"] == 0
    if trace:
        got = line["metrics"]
        # 2 rings of 8 rows, 3 states and one layer's K/V a row at the
        # rehearsal's widths, over rows that number some tens a sequence
        assert got["cache_bytes_per_token.reason"]["value"] > 64
        assert 0 < got["batch_occupancy.reason"]["value"] <= 4
    assert '"compiled": 0' in out.stdout


def test_every_reader_of_the_cell_has_its_file_and_returns_none_on_nothing():
    from chipbench import harness as H
    names = [m["name"] for m in BENCH["per_layer"]
             if CELL in m.get("workloads", ())]
    assert len(names) == 10
    for name in names:
        assert H.read_metric(name, {"cfg": PUBLISHED, "counters": {}}) \
            is None, name


# -- the control and the broken paths -----------------------------------------

SMALL = dict(family="sambay", hidden_size=128, intermediate_size=256,
             num_hidden_layers=8, num_attention_heads=8,
             num_key_value_heads=4, vocab_size=512, sliding_window=16,
             layer_norm_eps=1e-5, tie_word_embeddings=True,
             initializer_range=0.1, max_position_embeddings=512)


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_float8_serving_is_not_correct(seed):
    ids = np.random.default_rng(seed).integers(0, 512, (4, 96))
    rows = [list(range(31, 95))] * 4
    ref = R.served_logits(SMALL, seed, ids, rows)
    low = R.served_logits(SMALL, seed, ids, rows, precision="fp8")
    own = serve.token_gaps(ref, [lg.argmax(-1) for lg in ref])
    assert max(own) == 0.0
    assert max(serve.token_gaps(ref, [lo.argmax(-1) for lo in low])) > LIMIT


ARGS = ["--workload", CELL, "--seed", "2147483777", "--seconds", "1",
        "--trace", "0", "--rehearse"]


def last_line(capsys):
    from chipbench import run
    run.main(ARGS)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def private_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def rewritten(monkeypatch, block: str, old: str, new: str):
    """Put a block of the program's model in place with one expression of
    its source changed."""
    from paddle_tpu.models import sambay
    src = inspect.getsource(sambay._BLOCKS[block].__wrapped__)
    assert src.count(old) == 1, (block, old)
    scope = dict(vars(sambay))
    exec(src.replace(old, new), scope)
    monkeypatch.setitem(sambay._BLOCKS, block, sambay._jitted(scope[block]))


def test_the_sound_program_is_correct(capsys, private_cache):
    assert last_line(capsys)["correct"] is True


def test_a_window_off_by_one_is_not_correct(capsys, monkeypatch,
                                            private_cache):
    """Every row sees one row fewer than the window, in a chunk and in a
    decode step."""
    rewritten(monkeypatch, "_window_block_seq", "< window)", "< window - 1)")
    rewritten(monkeypatch, "_window_block_tok",
              "jnp.minimum(dec + 1, window)",
              "jnp.minimum(dec + 1, window - 1)")
    line = last_line(capsys)
    v = line["compared"]["served_logit_gap"]
    assert line["correct"] is False and v["value"] > v["limit"]


def test_a_state_not_reset_on_slot_reuse_is_not_correct(capsys, monkeypatch,
                                                        private_cache):
    """A chunk at row 0 carries on from what the slot's last sequence
    left."""
    rewritten(monkeypatch, "_ssm_block_seq", "carried = dec > 0",
              "carried = dec >= 0")
    line = last_line(capsys)
    v = line["compared"]["served_logit_gap"]
    assert line["correct"] is False and v["value"] > v["limit"]


# -- the counts ---------------------------------------------------------------

def test_published_layers_and_parameters():
    c = PUBLISHED
    assert FAMILY.layer_counts(c) == {"ssm": 8, "ssm_mem": 1, "window": 8,
                                      "full": 1, "gmu": 7, "cross": 7}
    mlp = 3 * 2560 * 10240
    ssm = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    window = 2560 * 5120 + 2560 * 2560
    assert FAMILY.layer_matmul_params(c, 0) == ssm + mlp == 119767040
    assert FAMILY.layer_matmul_params(c, 1) == window + mlp == 98_304_000
    assert FAMILY.layer_matmul_params(c, 16) == ssm + mlp
    assert FAMILY.layer_matmul_params(c, 17) == window + mlp
    assert FAMILY.layer_matmul_params(c, 18) == 2 * 2560 * 5120 + mlp \
        == 104_857_600
    assert FAMILY.layer_matmul_params(c, 19) == 2 * 2560 * 2560 + mlp \
        == 91_750_400
    assert FAMILY.matmul_params(c) == 9 * (ssm + mlp) + 9 * (window + mlp) \
        + 7 * 104_857_600 + 7 * 91_750_400 + 2560 * 200064 \
        == 3_851_059_200


def test_published_cache_bytes():
    c = PUBLISHED
    # 20 heads of 64, K and V, bfloat16
    assert FAMILY.kv_bytes_per_row(c) == 5120
    # h 5120 x 16 float32 and 3 rows of 5120 bfloat16
    assert FAMILY.ssm_state_bytes(c) == 327_680 + 30_720 == 358_400
    # 9 states and 8 rings of 512 rows
    assert FAMILY.slot_state_bytes(c) == 9 * 358_400 + 8 * 512 * 5120 \
        == 24_197_120
    # one step of 64 sequences, 1,000 rows each, every window full
    running, rows, window = 64, 64_000, 64 * 512
    state = FAMILY.decode_state_bytes(c, running, rows, window)
    assert state == 64 * 9 * 2 * 358_400 + 8 * (window + 64) * 5120 \
        + (8 * rows + 64) * 5120 == 4379443200
    assert FAMILY.decode_step_bytes(c, 1, running, rows, window) \
        == 2 * 3_851_059_200 + state
    # at 819 GB/s: 9.4 ms of weights and 5.3 ms of state and K/V
    assert 2 * 3_851_059_200 / 819e9 == pytest.approx(9.40e-3, rel=2e-3)
    assert state / 819e9 == pytest.approx(5.35e-3, rel=1e-2)


def test_scan_cost_of_one_layer_and_chunk():
    flops, nbytes = FAMILY.ssm_scan_cost(PUBLISHED, 256)
    assert flops == 256 * (8 * 5120 * 16 + 2 * 5120) == 170_393_600
    # a row: dt float32, input, B, C, output; the state read and written
    assert nbytes == 256 * (20480 + 10240 + 64 + 10240) + 2 * 327_680 \
        == 11_157_504
    from chipbench import costs
    least, bound = costs.roofline_seconds(flops, nbytes, peaks_for("v5e"))
    assert bound == "memory" and least == pytest.approx(13.6e-6, rel=1e-2)
