"""The cell ``mellum2-ide-mixed`` and its family ``mellum``: the
configuration against the catalog, the rehearsal's last line, the family's
twin readings (the reference rounded to float8 is not correct under the
cell's own limits, number by number, where bfloat16 reads a third of it),
two faults of the timed path that have to come out as not correct, and the
count functions against numbers worked by hand."""
import inspect
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import families, serve
from chipbench import harness as H
from chipbench import reference as R
from chipbench import traffic as T
from chipbench.lastline import problems
from chipbench.peaks import peaks_for

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "mellum2-ide-mixed"
CONFIG = "mellum2-12b-a2.5b-serve-d8"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
PUBLISHED = H.load_config(CONFIG, False)
FAMILY = families.of(PUBLISHED)
CELL_FILE = H.load_json("cells", CELL + ".json")
LIMITS = CELL_FILE["limits"]


# -- the configuration --------------------------------------------------------

def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == PUBLISHED["source"]
    differs = {k for k, v in row["config"].items() if PUBLISHED.get(k) != v}
    assert differs == set(entry["reduced"]) == set(PUBLISHED["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types"}
    assert PUBLISHED["published"] == {k: row["config"][k] for k in differs}
    # two whole periods of the published pattern, every layer sparse
    assert PUBLISHED["layer_types"] == row["config"]["layer_types"][:8] \
        == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert PUBLISHED["mlp_layer_types"] == ["sparse"] * 8
    assert (PUBLISHED["num_experts"], PUBLISHED["num_experts_per_tok"],
            PUBLISHED["sliding_window"], PUBLISHED["vocab_size"]) \
        == (64, 8, 1024, 98304)
    assert FAMILY.DISCRETE_CHOICES == ("router_topk",)
    for key in ("q and k norm", "rotation pairing", "draw scales",
                "intermediate_size"):
        assert len(PUBLISHED["assumed"][key]) > 40


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "ide-mixed", 1)
    t = H.load_json("traffic", "ide-mixed.json")
    assert (t["kind"], t["schedule"], t["jitter"], t["check_requests"]) \
        == ("serve-open", "file", 0.5, 8)
    c = t["cycle"]
    assert c["documents"] == [0, 0, 0, 0, 0, 0, 8192, 12288, 16384, 24576]
    assert (c["asks_per_document"], c["interleave"], c["pairing"]) \
        == (3, 1, 1)
    assert c["prompt_tokens"] == {"min": 128, "max": 1024,
                                  "dist": "loguniform"}
    assert c["answer_tokens"] == {"min": 128, "max": 384,
                                  "dist": "loguniform"}
    server = PUBLISHED["runner"]["server"]
    assert (server["max_batch"], server["s_max"], server["block_size"],
            server["prefill_chunk"], server["prefix_cache"]) \
        == (16, 32768, 16, 512, True)
    assert set(server["n_pages"]) == {"full", "window"}
    assert T.longest(t) <= 25984 <= server["s_max"]
    # a round: six short requests and four behind a context, a third cold
    lengths = T.group_lengths(c)
    assert len(lengths) == 3 and all(
        sum(d > 0 for d, _, _ in rnd) == 4 and len(rnd) == 10
        for rnd in lengths)
    assert serve.limit_problems(CELL_FILE, FAMILY, t) == []
    for metric in ("ttft_mean_ms", "tpot_mean_ms"):
        assert CELL in next(m for m in BENCH["end_to_end"]
                            if m["name"] == metric)["workloads"]


def test_the_page_counts_hold_what_the_deployment_says():
    """2,048 B a token and layer; the full group's 2 layers 1.61 GB over
    393,216 rows, the window group's 6 layers 1.21 GB over 98,304; a ring
    of 99 pages a slot, 16 slots."""
    server = PUBLISHED["runner"]["server"]
    row = FAMILY.kv_bytes_per_row(PUBLISHED)
    layers = FAMILY.layers_of(PUBLISHED)
    assert row == 2048 and layers == {"full": 2, "window": 6}
    pages = server["n_pages"]
    assert pages["full"] * 16 == 393216 and pages["window"] * 16 == 98304
    assert pages["full"] * 16 * layers["full"] * row == pytest.approx(
        1.61e9, rel=5e-3)
    assert pages["window"] * 16 * layers["window"] * row == pytest.approx(
        1.21e9, rel=5e-3)
    ring = -(-(1024 + server["prefill_chunk"]) // 16) + 3
    assert ring == 99 and 16 * ring <= pages["window"]


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
    assert problems(line, BENCH, CELL, bool(trace), 1,
                    CELL_FILE["no_chip"]) == []
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["leaked_pages"]["value"] == 0
    assert set(line["compared"]) == set(LIMITS) | {"leaked_pages",
                                                   "failed_requests"}
    if trace:
        got = {k: v["value"] for k, v in line["metrics"].items()}
        # 8 layers of 2 x 2 x 16 bfloat16: 1,024 B a row were nothing
        # released, 256 B of it the full group's
        assert 256 < got["cache_bytes_per_token.ide"] <= 1024 * 1.5
        assert got["moe_expert_imbalance.ide"] >= 1
        assert 0 < got["moe_experts_touched_share.ide"] <= 100
        assert got["prefix_hit_share.ide"] > 10
        assert 0 <= got["prefix_hit_cut_share.ide"] <= 100
        assert got["window_reclaim_ms.ide"] > 0
    assert '"compiled": 0' in out.stdout


def test_every_reader_of_the_cell_has_its_file_and_returns_none_on_nothing():
    names = [m["name"] for m in BENCH["per_layer"]
             if CELL in m.get("workloads", ())]
    assert len(names) == 20
    for name in names:
        assert H.read_metric(name, {"cfg": PUBLISHED, "counters": {}}) \
            is None, name


# -- the twin readings: what rounding does at a size a test can hold ----------

SMALL = H.load_json("tests", "control", CONFIG + ".json")


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_float8_fails_every_limit_and_bfloat16_reads_a_third_of_its_body(
        seed):
    """The reference rounded to float8 in the program's place fails each
    number the cell judges, on its own; rounded to bfloat16 (the stand-in
    for a sound program) its mean and its share off the reference's choice
    read a third of float8's or less."""
    ids = np.random.default_rng(seed).integers(0, 512, (8, 160))
    rows = [list(range(31, 159))] * 8              # 1,024 tokens compared
    ref = R.served_logits(SMALL, seed, ids, rows)
    read = {}
    for precision in ("bf16", "fp8"):
        low = R.served_logits(SMALL, seed, ids, rows, precision=precision)
        read[precision] = serve.gap_statistics(
            serve.token_gaps(ref, [lo.argmax(-1) for lo in low]))
    for name, limit in LIMITS.items():             # each number on its own
        assert read["fp8"][name] > limit, (name, read["fp8"][name])
    for name in serve.BODY:
        assert 3 * read["bf16"][name] < read["fp8"][name], (name, read)


# -- the broken paths ---------------------------------------------------------

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
    from paddle_tpu.models import mellum
    src = inspect.getsource(mellum._BLOCKS[block].__wrapped__)
    assert src.count(old) == 1, (block, old)
    scope = dict(vars(mellum))
    exec(src.replace(old, new), scope)
    monkeypatch.setitem(mellum._BLOCKS, block, mellum._jitted(scope[block]))


def judged_false(line):
    assert line["correct"] is False
    return {k for k, v in line["compared"].items()
            if v["value"] > v["limit"]}


def test_the_sound_program_is_correct(capsys, private_cache):
    assert last_line(capsys)["correct"] is True


def test_a_window_layer_that_reads_every_row_is_not_correct(
        capsys, monkeypatch, private_cache):
    """Chunks leave the window mask out: a window layer's query reads every
    row its pages still hold."""
    rewritten(monkeypatch, "_block_chunk",
              "& (pos[:, None] - kpos[None, :] < window)", "")
    assert judged_false(last_line(capsys)) & set(LIMITS)


def test_half_the_experts_are_not_correct(capsys, monkeypatch,
                                          private_cache):
    """Every block routes to half the experts the configuration says."""
    from paddle_tpu.models import mellum
    real = mellum.route

    def fewer(p, h, top_k, norm_topk):
        return real(p, h, top_k // 2, norm_topk)

    monkeypatch.setattr(mellum, "route", fewer)
    for block in ("_block_chunk", "_block_tok"):       # jitted anew
        monkeypatch.setitem(mellum._BLOCKS, block, mellum._jitted(
            mellum._BLOCKS[block].__wrapped__))
    assert judged_false(last_line(capsys)) & set(LIMITS)


# -- the counts ---------------------------------------------------------------

def test_published_parameters_by_part():
    c = PUBLISHED
    attention = 2 * 2304 * 32 * 128 + 2 * 2304 * 4 * 128
    assert attention == 21_233_664 == FAMILY.attention_params(c)
    assert FAMILY.expert_params(c) == 3 * 2304 * 896 == 6_193_152
    fixed = 8 * (attention + 2304 * 64) + 2304 * 98304
    assert FAMILY.fixed_matmul_params(c) == fixed == 397_541_376
    leaves = sum(int(np.prod(s)) for i in range(8)
                 for s in FAMILY.layer_shapes(c, i).values()) \
        + sum(int(np.prod(s)) for s in FAMILY.top_shapes(c).values())
    # 3.80B: the experts are 8 x 64 x 6.19M of it
    assert leaves == pytest.approx(3.80e9, rel=2e-3)
    assert leaves - fixed - 2304 * 98304 == pytest.approx(
        8 * 64 * 6_193_152, rel=1e-4)          # + norms


def test_published_cache_bytes_and_a_decode_step():
    c = PUBLISHED
    assert FAMILY.kv_bytes_per_row(c) == 2048
    # 8 running at 16,384 rows each, one step: a full layer reads them
    # all, a window layer 1,024 a sequence
    rows = FAMILY.kv_rows_read(c, 8, 8 * 16384)
    assert rows == {"full": 2 * 8 * 16384, "window": 6 * 8 * 1024}
    # below the window every layer reads what there is
    assert FAMILY.kv_rows_read(c, 4, 4 * 300) \
        == {"full": 2 * 1200, "window": 6 * 1200}
    nbytes = FAMILY.decode_step_bytes(c, 1, 8 * 45, rows)
    assert nbytes == 2 * (397_541_376 + 360 * 6_193_152) \
        + (262144 + 49152) * 2048
    # at 819 GB/s: 1.0 ms fixed, 5.4 ms experts, 0.8 ms K/V
    assert nbytes / 819e9 == pytest.approx(7.19e-3, rel=1e-2)


def test_costs_of_the_kernels():
    from chipbench import costs
    peaks = peaks_for("v5e")
    flops, nbytes = FAMILY.routed_experts_cost(PUBLISHED, 16 * 8, 45)
    assert flops == 128 * 6_193_152 * 2 and nbytes == 45 * 6_193_152 * 2
    assert costs.roofline_seconds(flops, nbytes, peaks)[1] == "memory"
    # a chunk of 512 rows that touches all 64: memory-bound still
    flops, nbytes = FAMILY.routed_experts_cost(PUBLISHED, 512 * 8, 64)
    least, bound = costs.roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(968e-6, rel=1e-2)
    flops, nbytes = FAMILY.kv_attention_cost(PUBLISHED, {"full": 1000,
                                                         "window": 24})
    assert flops == 1024 * 32 * 128 * 4 and nbytes == 1024 * 2048
    assert costs.roofline_seconds(flops, nbytes, peaks)[1] == "memory"


def test_a_share_is_taken_a_call_so_a_cut_trace_reads_what_a_whole_one_reads(
        monkeypatch):
    """The counters cover the window's 1,000 steps; the device trace holds
    all of them, or the first 470 (the profiler's cap on device events):
    the share is the same, 30 experts a layer of 12.4 MB at 819 GB/s over 6
    ms a step under experts_routed."""
    from chipbench import phases

    def analysis(calls):
        return {"by_executable": {phases.DECODE: {
                    "seconds": 8e-3 * calls, "calls": calls}},
                "by_scope": {phases.DECODE: {
                    "experts_routed": 6e-3 * calls, "head": 1e-3 * calls}},
                "span_counts": {}}

    run = {"cfg": PUBLISHED, "peaks": peaks_for("v5e"), "decode_steps": 1000,
           "counters": {"moe_experts_touched": 1000 * 8 * 30,
                        "moe_assignments_local_decode": 1000 * 8 * 48}}
    got = []
    for calls in (1000, 470):
        monkeypatch.setattr(phases, "of_run", lambda _, c=calls: analysis(c))
        got.append(H.read_metric("moe_experts_roofline.ide", dict(run)))
    least = 8 * 30 * 6_193_152 * 2 / 819e9
    assert got[0] == pytest.approx(got[1]) == pytest.approx(
        100 * least / 6e-3, rel=1e-3)
    assert 55 < got[0] < 65
