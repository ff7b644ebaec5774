"""The cell ``glm5-longdoc-sessions`` and its family ``glm_dsa``: the
rehearsal's last line, the family's twin readings (the reference rounded to
bfloat16 is correct under the cell's own limits where float8 is not, number
by number), two faults of the timed path that have to come out as not
correct, and the count functions against numbers worked by hand."""
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
from chipbench.lastline import problems
from chipbench.peaks import peaks_for

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "glm5-longdoc-sessions"
CONFIG = "glm-5-serve-ep16-d5"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
PUBLISHED = H.load_config(CONFIG, False)
FAMILY = families.of(PUBLISHED)
CELL_FILE = H.load_json("cells", CELL + ".json")
LIMITS = CELL_FILE["limits"]


# -- the configuration --------------------------------------------------------

def test_the_configuration_keeps_every_published_width():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == PUBLISHED["source"]
    differs = {k for k, v in row["config"].items() if PUBLISHED.get(k) != v}
    assert differs == set(entry["reduced"]) == set(PUBLISHED["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert PUBLISHED["published"] == {k: row["config"][k] for k in differs}
    assert (PUBLISHED["router_width"], PUBLISHED["num_experts_per_tok"]) \
        == (256, 8)
    assert FAMILY.DISCRETE_CHOICES == ("router_topk", "indexer_topk")


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "longdoc-sessions", 1)
    t = H.load_json("traffic", "longdoc-sessions.json")
    assert (t["kind"], t["schedule"], t["jitter"], t["check_requests"],
            t["drain_seconds"]) == ("serve-open", "file", 0.5, 6, 120)
    c = t["cycle"]
    assert c["documents"] == [8192, 12288, 16384, 24576]
    assert (c["asks_per_document"], c["interleave"], c["pairing"]) \
        == (4, 1, 1)
    assert c["prompt_tokens"] == {"min": 64, "max": 256,
                                  "dist": "loguniform"}
    assert c["answer_tokens"] == {"min": 192, "max": 384,
                                  "dist": "loguniform"}
    server = PUBLISHED["runner"]["server"]
    assert (server["max_batch"], server["s_max"], server["prefix_cache"]) \
        == (16, 32768, True)
    assert server["n_pages"] * server["block_size"] >= 262144
    from chipbench import traffic as T
    assert T.longest(t) <= server["s_max"]
    assert PUBLISHED["runner"]["warmup"]["prompt_tokens"] \
        > PUBLISHED["index_topk"]
    assert serve.limit_problems(CELL_FILE, FAMILY, t) == []


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
        # the rehearsal's 4 layers of (16 + 8) + 16 wide rows, bfloat16
        assert got["cache_bytes_per_token.longdoc"] == 4 * 40 * 2
        # 4 of the router's 16 experts are held
        assert 15 < got["moe_local_share.longdoc"] < 35
        assert got["moe_expert_imbalance.longdoc"] >= 1
        assert 0 < got["dsa_selected_share.longdoc"] < 100
        assert got["prefix_hit_share.longdoc"] > 40
    assert '"compiled": 0' in out.stdout


def test_every_reader_of_the_cell_has_its_file_and_returns_none_on_nothing():
    names = [m["name"] for m in BENCH["per_layer"]
             if CELL in m.get("workloads", ())]
    assert len(names) == 19
    for name in names:
        assert H.read_metric(name, {"cfg": PUBLISHED, "counters": {}}) \
            is None, name


# -- the twin readings: what rounding does at a size a test can hold ----------

SMALL = H.load_json("tests", "control", CONFIG + ".json")


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_float8_fails_every_limit_and_bfloat16_reads_a_third_of_its_body(
        seed):
    """The reference rounded to float8 in the program's place fails each
    number the cell judges, on its own. Rounded to bfloat16 (the stand-in
    for a sound program) its mean and its share off the reference's choice
    read a third of float8's or less. At this size (hidden 128, 32 of up to
    160 rows kept, 4 of 16 experts) a flipped choice weighs far more than at
    the published one, so the cell's own limits are not held against the
    bfloat16 pass here: the chip runs are (PERF.md section 2)."""
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
    from paddle_tpu.models import glm_dsa
    src = inspect.getsource(glm_dsa._BLOCKS[block].__wrapped__)
    assert src.count(old) == 1, (block, old)
    scope = dict(vars(glm_dsa))
    exec(src.replace(old, new), scope)
    monkeypatch.setitem(glm_dsa._BLOCKS, block,
                        glm_dsa._jitted(scope[block]))


def judged_false(line):
    assert line["correct"] is False
    return {k for k, v in line["compared"].items()
            if v["value"] > v["limit"]}


def test_the_sound_program_is_correct(capsys, private_cache):
    assert last_line(capsys)["correct"] is True


def test_the_newest_rows_in_the_indexers_place_are_not_correct(
        capsys, monkeypatch, private_cache):
    """Chunks and decode steps keep the newest ``index_topk`` rows, whatever
    the indexer scored."""
    newest = "jnp.broadcast_to(jnp.arange(s_max, dtype=F32), scores.shape)"
    rewritten(monkeypatch, "_block_chunk",
              "select_rows(scores, valid, topk)",
              f"select_rows({newest}, valid, topk)")
    rewritten(monkeypatch, "_block_tok",
              "select_indices(scores, valid, min(topk, s_max))",
              f"select_indices({newest}, valid, min(topk, s_max))")
    assert judged_false(last_line(capsys)) & set(LIMITS)


def test_a_routed_branch_left_out_is_not_correct(capsys, monkeypatch,
                                                 private_cache):
    """The shared expert alone: what a router that sends every token
    elsewhere would leave."""
    from paddle_tpu.models import glm_dsa
    real = glm_dsa.routed_experts

    def nothing(p, h, chosen, gates, held):
        y, counts = real(p, h, chosen, gates, held)
        return y * 0, counts

    monkeypatch.setattr(glm_dsa, "routed_experts", nothing)
    for block in ("_block_chunk", "_block_tok"):       # jitted anew
        monkeypatch.setitem(glm_dsa._BLOCKS, block, glm_dsa._jitted(
            glm_dsa._BLOCKS[block].__wrapped__))
    assert judged_false(last_line(capsys)) & set(LIMITS)


# -- the counts ---------------------------------------------------------------

def test_published_parameters_by_part():
    c = PUBLISHED
    mla = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448 \
        + 64 * 256 * 6144
    indexer = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
    assert mla == 165_019_648 and indexer == 9_371_648
    assert FAMILY.attention_params(c) == mla + indexer
    assert FAMILY.expert_params(c) == 3 * 6144 * 2048 == 37_748_736
    fixed = 5 * (mla + indexer) + 3 * 6144 * 12288 \
        + 4 * (6144 * 256 + 37_748_736) + 6144 * 19360
    assert FAMILY.fixed_matmul_params(c) == fixed == 1_374_683_136
    leaves = sum(int(np.prod(s)) for i in range(5)
                 for s in FAMILY.layer_shapes(c, i).values()) \
        + sum(int(np.prod(s)) for s in FAMILY.top_shapes(c).values())
    # 3.91B: the held experts are 4 x 16 x 37.7M of it
    assert leaves == pytest.approx(3.91e9, rel=2e-3)
    assert leaves - fixed - 6144 * 19360 == pytest.approx(
        4 * 16 * 37_748_736, rel=1e-4)       # + norms, biases


def test_published_cache_bytes_and_a_decode_step():
    c = PUBLISHED
    assert FAMILY.latent_bytes_per_row(c) == 1152
    assert FAMILY.index_key_bytes_per_row(c) == 256
    assert FAMILY.cache_bytes_per_row(c) == 5 * 1408 == 7040
    # one step: 8 running at 16,384 rows, 8 experts touched a layer
    scored, selected = 5 * 8 * 16384, 5 * 8 * 2048
    nbytes = FAMILY.decode_step_bytes(c, 1, 4 * 8, scored, selected)
    assert nbytes == 2 * (1_374_683_136 + 32 * 37_748_736) \
        + scored * 256 + selected * 1152
    # at 819 GB/s: 3.4 ms fixed, 2.9 ms experts, 0.2 ms keys, 0.1 ms rows
    assert nbytes / 819e9 == pytest.approx(6.62e-3, rel=1e-2)


def test_costs_of_the_three_kernels():
    from chipbench import costs
    peaks = peaks_for("v5e")
    # a chunk of 512 at 16,384 rows, one layer: compute-bound
    pairs = 512 * 16384
    flops, nbytes = FAMILY.indexer_cost(PUBLISHED, 512, pairs)
    assert flops == pairs * 32 * 128 * 2 and nbytes == pairs * 256
    least, bound = costs.roofline_seconds(flops, nbytes / 512, peaks)
    assert bound == "compute" and least == pytest.approx(349e-6, rel=1e-2)
    # decode: 2,048 rows a slot and layer, memory-bound
    flops, nbytes = FAMILY.sparse_attention_cost(PUBLISHED, 2048)
    assert flops == 2048 * 64 * (576 + 512) * 2 and nbytes == 2048 * 1152
    assert costs.roofline_seconds(flops, nbytes, peaks)[1] == "memory"
    flops, nbytes = FAMILY.routed_experts_cost(PUBLISHED, 8, 6)
    assert flops == 8 * 37_748_736 * 2 and nbytes == 6 * 37_748_736 * 2
    assert costs.roofline_seconds(flops, nbytes, peaks)[1] == "memory"
