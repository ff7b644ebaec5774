"""The cell ``keye2-longctx-sessions`` and its family ``keye``: the
configuration against the catalog, the rehearsal's last line, the family's
twin readings (the reference rounded to float8 is not correct under the
cell's own limits, number by number, and reads well past bfloat16), faults of
the timed path that have to come out as not correct, and the count functions
against numbers worked by hand."""
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
CELL = "keye2-longctx-sessions"
CONFIG = "keye-vl-2.0-30b-a3b-serve-d6"
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
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == PUBLISHED["source"]
    differs = {k for k, v in row["config"].items() if PUBLISHED.get(k) != v}
    assert differs == set(entry["reduced"]) == set(PUBLISHED["reduced"]) \
        == {"num_hidden_layers"}
    assert PUBLISHED["published"] == {"num_hidden_layers": 48}
    assert PUBLISHED["num_hidden_layers"] == 6
    assert (PUBLISHED["num_experts"], PUBLISHED["num_experts_per_tok"],
            PUBLISHED["moe_intermediate_size"], PUBLISHED["vocab_size"],
            PUBLISHED["sa_config"]["topk"]) == (128, 8, 768, 151936, 2048)
    assert PUBLISHED["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert FAMILY.DISCRETE_CHOICES == ("router_topk", "indexer_topk")
    for key in ("q and k norm", "indexer rotation", "indexer tiles",
                "draw scales", "index key width", "vision tower"):
        assert len(PUBLISHED["assumed"][key]) > 40
    assert "eight pipeline stages" in PUBLISHED["deployment"]


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "longctx-sessions", 1)
    t = H.load_json("traffic", "longctx-sessions.json")
    assert (t["kind"], t["schedule"], t["jitter"], t["drain_seconds"],
            t["check_requests"]) == ("serve-open", "file", 0.5, 120, 8)
    c = t["cycle"]
    assert c["documents"] == [16384, 24576, 32768, 49152]
    assert (c["asks_per_document"], c["interleave"], c["pairing"]) \
        == (4, 1, 1)
    assert c["prompt_tokens"] == {"min": 64, "max": 256,
                                  "dist": "loguniform"}
    assert c["answer_tokens"] == {"min": 128, "max": 256,
                                  "dist": "loguniform"}
    server = PUBLISHED["runner"]["server"]
    assert server == {"max_batch": 8, "s_max": 65536, "block_size": 16,
                      "n_pages": 12288, "prefill_chunk": 512,
                      "prefix_cache": True, "compile": True}
    assert T.longest(t) <= 49152 + 256 + 256 <= server["s_max"]
    # 8 requests of 128 served tokens or more: the percentile has ten
    # beyond it
    assert t["check_requests"] * 128 >= 1000
    assert serve.limit_problems(CELL_FILE, FAMILY, t) == []
    for metric in ("ttft_mean_ms", "tpot_mean_ms"):
        assert CELL in next(m for m in BENCH["end_to_end"]
                            if m["name"] == metric)["workloads"]
    # the warm-up's decode steps go past topk: the kept-row path has run
    assert PUBLISHED["runner"]["warmup"]["prompt_tokens"] > 2048


def test_the_pools_hold_what_the_deployment_says():
    """2,048 B of K and V and, as the mathematics requires, 128 B of index
    key a token and layer; held, the key is 256 B: 13,824 B a token over 6
    layers, 2.72 GB over 196,608 rows; the weights 8.75 GB."""
    server = PUBLISHED["runner"]["server"]
    assert FAMILY.kv_bytes_per_row(PUBLISHED) == 2048
    assert FAMILY.index_key_bytes_per_row(PUBLISHED) == 128
    assert FAMILY.cache_bytes_per_row(PUBLISHED) == 13056
    rows = server["n_pages"] * server["block_size"]
    assert rows == 196608
    assert rows * 6 * (2048 + 256) == pytest.approx(2.72e9, rel=5e-3)
    leaves = sum(int(np.prod(s)) for i in range(6)
                 for s in FAMILY.layer_shapes(PUBLISHED, i).values()) \
        + sum(int(np.prod(s)) for s in FAMILY.top_shapes(PUBLISHED).values())
    assert 2 * leaves == pytest.approx(8.75e9, rel=2e-3)


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
        # 3 layers of (2 x 2 x 16 + 128) bfloat16 a row
        assert got["cache_bytes_per_token.longctx"] == 3 * 2 * (64 + 128)
        # documents of 24 to 64 rows against topk 16: selection bites
        assert 0 < got["dsa_selected_share.longctx"] < 100
        assert got["moe_expert_imbalance.longctx"] >= 1
        assert 0 < got["moe_experts_touched_share.longctx"] <= 100
        assert got["prefix_hit_share.longctx"] > 10
    assert '"compiled": 0' in out.stdout


def test_every_reader_of_the_cell_has_its_file_and_returns_none_on_nothing():
    names = [m["name"] for m in BENCH["per_layer"]
             if CELL in m.get("workloads", ())]
    assert len(names) == 20 and all(n.endswith(".longctx") for n in names)
    assert set(CELL_FILE["no_chip"]) <= set(names)
    for name in names:
        assert H.read_metric(name, {"cfg": PUBLISHED, "counters": {}}) \
            is None, name


# -- the twin readings: what rounding does at a size a test can hold ----------

SMALL = H.load_json("tests", "control", CONFIG + ".json")


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_float8_fails_every_limit_and_reads_well_past_bfloat16(seed):
    """The reference rounded to float8 in the program's place fails each
    number the cell judges, on its own; rounded to bfloat16 (the stand-in
    for a sound program) its mean reads a third of float8's or less. At
    this size 32 rows are kept of 160 and a flipped row is a thirtieth of
    a query's weight, so bfloat16's share off the reference's choice reads
    half of float8's, where at the cell's size 2,048 are kept."""
    assert SMALL["family"] == "keye"
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
    assert 3 * read["bf16"]["served_logit_gap_mean"] \
        < read["fp8"]["served_logit_gap_mean"], read
    assert 1.5 * read["bf16"]["served_off_argmax_share"] \
        < read["fp8"]["served_off_argmax_share"], read


# -- the broken paths ---------------------------------------------------------
# The rehearsal's and the control twin's configurations state
# ``initializer_range`` 0.1 at hidden 64 and 128: a product's gain, range x
# sqrt(fan-in), is then 0.8 to 1.1 as the cell's 0.02 at 2,048 gives 0.9, and
# the draws are the cell's own function (``leaf_draw``: W_o and W2 scaled by
# the depth). At the published widths the same three departures are read by
# ``keye_omission.py`` on the chip (PERF.md section 2).

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
    from paddle_tpu.models import keye
    src = inspect.getsource(keye._BLOCKS[block].__wrapped__)
    assert src.count(old) == 1, (block, old)
    scope = dict(vars(keye))
    exec(src.replace(old, new), scope)
    monkeypatch.setitem(keye._BLOCKS, block, keye._jitted(scope[block]))


def judged_false(line):
    assert line["correct"] is False
    return {k for k, v in line["compared"].items()
            if v["value"] > v["limit"]}


def test_the_sound_program_is_correct(capsys, private_cache):
    assert last_line(capsys)["correct"] is True


def test_the_newest_rows_in_the_indexers_place_are_not_correct(
        capsys, monkeypatch, private_cache):
    """A decode step keeps the newest rows where the indexer's best were
    asked for: the scores it selects from are the rows' own numbers."""
    rewritten(monkeypatch, "_block_tok",
              "rows, kept = select_indices(scores, valid, topk)",
              "rows, kept = select_indices(jnp.arange(s_max, dtype=F32)"
              "[None], valid, topk)")
    assert judged_false(last_line(capsys)) & set(LIMITS)


def test_chunks_that_attend_every_row_are_not_correct(
        capsys, monkeypatch, private_cache):
    """A chunk leaves the selection out: every query reads every row held
    at or before it."""
    rewritten(monkeypatch, "_block_chunk",
              "keep = select_rows(scores, valid, topk)", "keep = valid")
    assert judged_false(last_line(capsys)) & set(LIMITS)


def test_half_the_experts_are_not_correct(capsys, monkeypatch,
                                          private_cache):
    """Every block routes to half the experts the configuration says."""
    from paddle_tpu.models import keye, mellum
    real = mellum.route

    def fewer(p, h, top_k, norm_topk):
        return real(p, h, top_k // 2, norm_topk)

    monkeypatch.setattr(mellum, "route", fewer)
    for block in ("_block_chunk", "_block_tok"):       # jitted anew
        monkeypatch.setitem(keye._BLOCKS, block, keye._jitted(
            keye._BLOCKS[block].__wrapped__))
    assert judged_false(last_line(capsys)) & set(LIMITS)


# -- the counts ---------------------------------------------------------------

def test_published_parameters_by_part():
    c = PUBLISHED
    attention = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128
    indexer = 2048 * (16 * 64 + 64 + 16)
    assert attention == 18_874_368 and indexer == 2_260_992
    assert FAMILY.attention_params(c) == attention + indexer
    assert FAMILY.expert_params(c) == 3 * 2048 * 768 == 4_718_592
    fixed = 6 * (attention + indexer + 2048 * 128) + 2048 * 151936
    assert FAMILY.fixed_matmul_params(c) == fixed == 439_549_952
    leaves = sum(int(np.prod(s)) for i in range(6)
                 for s in FAMILY.layer_shapes(c, i).values()) \
        + sum(int(np.prod(s)) for s in FAMILY.top_shapes(c).values())
    # 4.37B here; a layer 625.4M, of which 128 x 4.72M are experts
    assert leaves == pytest.approx(4.375e9, rel=2e-3)
    assert leaves - fixed - 2048 * 151936 == pytest.approx(
        6 * 128 * 4_718_592, rel=1e-4)          # + norms and the key's bias


def test_published_cache_bytes_and_a_decode_step():
    c = PUBLISHED
    # 3 running at 30,000 rows each, one step: every held row's index key
    # scored in 6 layers, K and V of 2,048 read; 29 experts a layer touched
    scored, kept = 6 * 3 * 30000, 6 * 3 * 2048
    nbytes = FAMILY.decode_step_bytes(c, 1, 6 * 29, scored, kept)
    assert nbytes == 2 * (439_549_952 + 174 * 4_718_592) \
        + scored * 128 + kept * 2048
    # at 819 GB/s: 1.07 ms fixed, 2.0 ms experts, 0.18 ms keys and rows
    assert nbytes / 819e9 == pytest.approx(3.26e-3, rel=1e-2)


def test_costs_of_the_kernels():
    from chipbench import costs
    peaks = peaks_for("v5e")
    flops, nbytes = FAMILY.routed_experts_cost(PUBLISHED, 3 * 8, 20)
    assert flops == 24 * 4_718_592 * 2 and nbytes == 20 * 4_718_592 * 2
    assert costs.roofline_seconds(flops, nbytes, peaks)[1] == "memory"
    # a chunk of 512 rows that touches all 128: memory-bound still
    flops, nbytes = FAMILY.routed_experts_cost(PUBLISHED, 512 * 8, 128)
    least, bound = costs.roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(1.475e-3, rel=1e-2)
    # a chunk's index scores against 24,576 held rows: compute-bound
    flops, nbytes = FAMILY.indexer_cost(PUBLISHED, 512, 512 * 24576)
    assert flops == 512 * 24576 * 16 * 64 * 2 and nbytes == 24576 * 128
    assert costs.roofline_seconds(flops, nbytes, peaks)[1] == "compute"
    # a decode step's kept rows: memory-bound, 2,048 B a row
    flops, nbytes = FAMILY.sparse_attention_cost(PUBLISHED, 2048)
    assert flops == 2048 * 32 * 128 * 4 and nbytes == 2048 * 2048
    assert costs.roofline_seconds(flops, nbytes, peaks)[1] == "memory"


def test_a_share_is_taken_a_call_so_a_cut_trace_reads_what_a_whole_one_reads(
        monkeypatch):
    """The counters cover the window's 1,000 steps; the device trace holds
    all of them, or the first 470 (the profiler's cap on device events):
    every share is the same."""
    from chipbench import phases

    def analysis(calls):
        return {"by_executable": {phases.DECODE: {
                    "seconds": 18e-3 * calls, "calls": calls}},
                "by_scope": {phases.DECODE: {
                    "experts_routed": 2.5e-3 * calls,
                    "sparse_gather": 8e-3 * calls,
                    "sparse_attention": 0.5e-3 * calls}},
                "span_counts": {}}

    run = {"cfg": PUBLISHED, "peaks": peaks_for("v5e"), "decode_steps": 1000,
           "counters": {"moe_experts_touched": 1000 * 6 * 29,
                        "moe_assignments_local_decode": 1000 * 6 * 24,
                        "dsa_rows_scored_decode": 1000 * 6 * 3 * 30000,
                        "dsa_rows_selected_decode": 1000 * 6 * 3 * 2048}}
    got = {}
    for calls in (1000, 470):
        monkeypatch.setattr(phases, "of_run", lambda _, c=calls: analysis(c))
        for name in ("moe_experts_roofline", "dsa_sparse_attn_roofline",
                     "serve.mbu"):
            got.setdefault(name, []).append(
                H.read_metric(name + ".longctx", dict(run)))
    for name, (whole, cut) in got.items():
        assert whole == pytest.approx(cut), name
    assert got["moe_experts_roofline"][0] == pytest.approx(
        100 * 6 * 29 * 4_718_592 * 2 / 819e9 / 2.5e-3, rel=1e-3)
    assert got["dsa_sparse_attn_roofline"][0] == pytest.approx(
        100 * 6 * 3 * 2048 * 2048 / 819e9 / 8.5e-3, rel=1e-3)
    assert got["serve.mbu"][0] == pytest.approx(100 * 3.26e-3 / 18e-3,
                                                rel=1e-2)
    assert all(0 < v[0] < 100 for v in got.values())
