"""The cell ``lfm2-agent-sessions`` and its family ``lfm2``: the
configuration against the catalog, the rehearsal's last line, the family's
twin readings (the reference rounded to float8 is not correct under the
cell's own limits, number by number, where bfloat16 reads a third of it),
faults of the timed path that have to come out as not correct, and the count
functions against numbers worked by hand. (A hit resumed from zeros and not
from its snapshot is NOT among those faults: the rows it spoils lie right
behind the boundary, under the new prompt, and the judged rows hundreds of
rows later read within the limits, PERF.md section 2; ``tests/test_lfm2.py``
holds a resumed request to a cold one bit for bit. Nor is the selection bias
left out: with 8 experts and some 45 compared tokens the rehearsal does not
see it; at the published router the omission script reads it past every
limit.)"""
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
CELL = "lfm2-agent-sessions"
CONFIG = "lfm2-24b-a2b-serve-d9"
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
                   if r["name"] == "LFM2-24B-A2B")
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] == PUBLISHED["source"]
    differs = {k for k, v in row["config"].items() if PUBLISHED.get(k) != v}
    assert differs == set(entry["reduced"]) == set(PUBLISHED["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers"}
    assert PUBLISHED["published"] == {k: row["config"][k] for k in differs}
    # one leading dense layer, then two whole periods of the published
    # pattern: published layers 1 to 9, nothing in them changed
    assert PUBLISHED["layer_types"] == row["config"]["layer_types"][1:10] \
        == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    assert PUBLISHED["num_dense_layers"] == 1
    assert (PUBLISHED["num_experts"], PUBLISHED["num_experts_per_tok"],
            PUBLISHED["conv_L_cache"], PUBLISHED["vocab_size"],
            PUBLISHED["moe_intermediate_size"],
            PUBLISHED["intermediate_size"]) \
        == (64, 4, 3, 65536, 1536, 11776)
    assert FAMILY.DISCRETE_CHOICES == ("router_topk",)
    assert FAMILY.layers_of(PUBLISHED) == {"conv": 7, "attn": 2, "dense": 1,
                                           "moe": 8}
    for key in ("tied embedding", "q and k norm", "gate denominator",
                "order of the thirds", "rotation pairing", "expert bias",
                "draw scales", "snapshot_rows"):
        assert len(PUBLISHED["assumed"][key]) > 40
    assert PUBLISHED["expert_bias_std"] == FAMILY.EXPERT_BIAS_STD


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "agent-sessions", 1)
    t = H.load_json("traffic", "agent-sessions.json")
    assert (t["kind"], t["schedule"], t["jitter"], t["drain_seconds"]) \
        == ("serve-open", "file", 0.5, 120)
    c = t["cycle"]
    assert c["documents"] == [6000, 9000, 13000, 19000]
    assert (c["asks_per_document"], c["interleave"], c["pairing"]) \
        == (6, 2, 1)
    assert c["prompt_tokens"] == {"min": 128, "max": 1024,
                                  "dist": "loguniform"}
    assert c["answer_tokens"] == {"min": 64, "max": 256,
                                  "dist": "loguniform"}
    # no context is a multiple of the 128 rows between snapshots, three of
    # four not of a block: every hit is cut back to a boundary
    assert all(d % 128 for d in c["documents"])
    assert sum(d % 16 > 0 for d in c["documents"]) == 3
    # ISSUE 44 asked for 8 compared requests; the shortest answer is 66
    # tokens, and a family that chooses needs 1,000 tokens compared
    # (``serve.limit_problems``): 16 x 66
    assert t["check_requests"] == 16
    server = PUBLISHED["runner"]["server"]
    assert (server["max_batch"], server["s_max"], server["block_size"],
            server["prefill_chunk"], server["prefix_cache"]) \
        == (32, 32768, 16, 512, True)
    assert T.longest(t) == 19798 <= server["s_max"]
    lengths = T.group_lengths(c)
    assert len(lengths) == 6 and all(len(rnd) == 4 for rnd in lengths)
    assert serve.limit_problems(CELL_FILE, FAMILY, t) == []
    for metric in ("ttft_mean_ms", "tpot_mean_ms"):
        assert CELL in next(m for m in BENCH["end_to_end"]
                            if m["name"] == metric)["workloads"]


def test_the_cache_holds_what_the_deployment_says():
    """4,096 B of K and V a cached token (2 attention layers), 57,344 B a
    snapshot of the 7 convolution layers' state, one every 128 rows: 448 B
    a token more; 393,216 rows, 1.61 GB and 0.18 GB."""
    server = PUBLISHED["runner"]["server"]
    assert FAMILY.kv_bytes_per_row(PUBLISHED) == 2048
    assert FAMILY.state_bytes_per_sequence(PUBLISHED) == 57344
    assert FAMILY.cache_bytes_per_row(PUBLISHED, 128) == 4096 + 448
    rows = server["n_pages"] * server["block_size"]
    assert rows == 393216 and rows // PUBLISHED["snapshot_rows"] == 3072
    assert rows * 4096 == pytest.approx(1.61e9, rel=5e-3)
    assert 3072 * 57344 == pytest.approx(0.176e9, rel=5e-3)
    # at the published depth: 10 attention and 30 convolution layers
    whole = dict(PUBLISHED, **PUBLISHED["published"])
    assert FAMILY.cache_bytes_per_row(whole, 128) == 20480 + 245760 / 128


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
    assert line["failed"] == 0
    assert line["compared"]["leaked_pages"]["value"] == 0
    assert set(line["compared"]) == set(LIMITS) | {"leaked_pages",
                                                   "failed_requests"}
    if trace:
        got = {k: v["value"] for k, v in line["metrics"].items()}
        # 2 attention layers x 2 x 2 x 64 bfloat16 = 1,024 B of K and V a
        # row, a snapshot of 7 x 2 x 256 every 8 rows 896 B more
        assert 1024 + 896 < got["cache_bytes_per_token.agent"] < 2048
        assert got["moe_expert_imbalance.agent"] >= 1
        assert 0 < got["moe_experts_touched_share.agent"] <= 100
        assert got["prefix_hit_share.agent"] > 10
        assert 0 < got["prefix_hit_cut_share.agent"] <= 100
        assert 0 < got["state_rows_cut_share.agent"] < 25
        assert got["state_restore_ms.agent"] > 0
    assert '"compiled": 0' in out.stdout


def test_every_reader_of_the_cell_has_its_file_and_returns_none_on_nothing():
    names = [m["name"] for m in BENCH["per_layer"]
             if CELL in m.get("workloads", ())]
    assert len(names) == 23 and all(n.endswith(".agent") for n in names)
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


def judged_false(line):
    assert line["correct"] is False
    return {k for k, v in line["compared"].items()
            if v["value"] > v["limit"]}


def rejitted(monkeypatch, *blocks):
    from paddle_tpu.models import lfm2
    for block in blocks:
        monkeypatch.setitem(lfm2._BLOCKS, block, lfm2._jitted(
            lfm2._BLOCKS[block].__wrapped__))


def test_the_sound_program_is_correct(capsys, private_cache):
    assert last_line(capsys)["correct"] is True


def test_a_decode_step_that_forgets_the_state_is_not_correct(
        capsys, monkeypatch, private_cache):
    """Every decode step's convolution reads zeros where the slot's two
    rows of ``z`` lie: each token is computed as a sequence's first."""
    import inspect
    from paddle_tpu.models import lfm2
    src = inspect.getsource(lfm2._BLOCKS["_conv_tok"].__wrapped__)
    old = "_short_conv(p, x[:, None, :], state, eps)"
    assert src.count(old) == 1
    scope = dict(vars(lfm2))
    exec(src.replace(old, "_short_conv(p, x[:, None, :], state * 0, eps)"),
         scope)
    monkeypatch.setitem(lfm2._BLOCKS, "_conv_tok",
                        lfm2._jitted(scope["_conv_tok"]))
    assert judged_false(last_line(capsys)) & set(LIMITS)


def test_half_the_experts_are_not_correct(capsys, monkeypatch,
                                          private_cache):
    """Every block routes to half the experts the configuration says."""
    from paddle_tpu.models import lfm2
    real = lfm2.route

    def fewer(p, h, top_k, norm_topk, scaling):
        return real(p, h, top_k // 2, norm_topk, scaling)

    monkeypatch.setattr(lfm2, "route", fewer)
    rejitted(monkeypatch, "_conv_chunk", "_attn_chunk", "_conv_tok",
             "_attn_tok")
    assert judged_false(last_line(capsys)) & set(LIMITS)


# -- the counts ---------------------------------------------------------------

def test_published_parameters_by_part():
    c = PUBLISHED
    assert FAMILY.short_conv_params(c) == 2048 * 6144 + 2048 * 2048 + 6144 \
        == 16_783_360
    assert FAMILY.attention_params(c) == 2 * 2048 * 2048 + 2 * 2048 * 512 \
        == 10_485_760
    assert FAMILY.expert_params(c) == 3 * 2048 * 1536 == 9_437_184
    fixed = 7 * 16_783_360 + 2 * 10_485_760 + 8 * 2048 * 64 \
        + 3 * 2048 * 11776 + 2048 * 65536
    assert FAMILY.fixed_matmul_params(c) == fixed == 346_073_088
    leaves = sum(int(np.prod(s)) for i in range(9)
                 for s in FAMILY.layer_shapes(c, i).values()) \
        + sum(int(np.prod(s)) for s in FAMILY.top_shapes(c).values())
    # 5.18B: the experts are 8 x 64 x 9.44M of it; the embedding is the
    # head, counted once
    assert leaves == pytest.approx(5.178e9, rel=1e-3)
    assert leaves - fixed == pytest.approx(8 * 64 * 9_437_184, rel=1e-4)


def test_published_bytes_of_a_decode_step():
    c = PUBLISHED
    # 8 running at 10,000 rows each, one step, 25 experts a layer touched:
    # 0.69 GB fixed, 3.77 GB experts, 0.16 GB K/V, 0.9 MB state
    rows = FAMILY.kv_rows_read(c, 8 * 10000)
    assert rows == 2 * 80000
    nbytes = FAMILY.decode_step_bytes(c, 1, 8 * 25, rows, 8)
    assert nbytes == 2 * (346_073_088 + 200 * 9_437_184) \
        + 160000 * 2048 + 2 * 8 * 57344
    assert nbytes / 819e9 == pytest.approx(5.86e-3, rel=1e-2)
    # the convolution operators alone: 7 x 33.6 MB of weights a step, the
    # slots' state read and written
    assert FAMILY.short_conv_decode_bytes(c, 1, 8) \
        == 7 * 16_783_360 * 2 + 2 * 8 * 57344
    # a chunk of 512 rows: 2 x 16.78M operations a row and layer
    assert FAMILY.short_conv_prefill_flops(c, 512) \
        == 2 * 16_783_360 * 512 * 7


def test_costs_of_the_kernels():
    from chipbench import costs
    peaks = peaks_for("v5e")
    flops, nbytes = FAMILY.routed_experts_cost(PUBLISHED, 32 * 4, 50)
    assert flops == 128 * 9_437_184 * 2 and nbytes == 50 * 9_437_184 * 2
    assert costs.roofline_seconds(flops, nbytes, peaks)[1] == "memory"
    # a chunk of 512 rows that touches all 64: memory-bound still, 1.47 ms
    flops, nbytes = FAMILY.routed_experts_cost(PUBLISHED, 512 * 4, 64)
    least, bound = costs.roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(1.475e-3, rel=1e-2)
    flops, nbytes = FAMILY.kv_attention_cost(PUBLISHED, 1024)
    assert flops == 1024 * 32 * 64 * 4 and nbytes == 1024 * 2048
    assert costs.roofline_seconds(flops, nbytes, peaks)[1] == "memory"


def test_the_new_readers_read_what_the_program_counts(monkeypatch):
    """``state_rows_cut_share``, ``prefix_hit_cut_share``,
    ``cache_bytes_per_token``, ``state_restore_ms`` and the two shares of
    ``short_conv`` from numbers worked by hand."""
    from chipbench import phases
    run = {"cfg": PUBLISHED, "hit_tokens": 99_000, "miss_tokens": 40_000,
           "counters": {"prefix_rows_cut": 1000, "prefix_matches": 100,
                        "prefix_hits_cut": 97},
           "gauges": {"kv_cache_bytes": 24577 * 16 * 4096,
                      "state_snapshot_bytes": 3073 * 57344,
                      "recurrent_state_bytes": 32 * 57344}}
    assert H.read_metric("state_rows_cut_share.agent", run) == 1.0
    assert H.read_metric("prefix_hit_cut_share.agent", run) == 97.0
    assert H.read_metric("cache_bytes_per_token.agent", run) \
        == pytest.approx(4096 + 448 + 4.7, abs=0.2)

    def analysis(_):
        return {"by_executable": {
                    phases.DECODE: {"seconds": 7e-3 * 1000, "calls": 1000},
                    phases.PREFILL_CHUNK: {"seconds": 18e-3 * 100,
                                           "calls": 100}},
                "by_scope": {
                    phases.DECODE: {"short_conv/in_proj": 0.2,
                                    "short_conv/state_write": 0.05,
                                    "no_scope": 0.05,
                                    "experts_routed": 5.0},
                    phases.PREFILL_CHUNK: {"short_conv/in_proj": 0.1,
                                           "short_conv/out_proj": 0.05}},
                "span_counts": {"serving.prefill_chunk": 100,
                                "serving.state_restore": 50,
                                "serving.state_snapshot": 100},
                "span_mean_s": {"serving.state_restore": 4e-6,
                                "serving.state_snapshot": 8e-6}}

    monkeypatch.setattr(phases, "of_run", analysis)
    run.update(peaks=peaks_for("v5e"), decode_steps=1000,
               occupancy_sum=8000, step_ms=[10.0] * 500)
    assert H.read_metric("state_restore_ms.agent", run) \
        == pytest.approx(1e3 * (50 * 4e-6 + 100 * 8e-6) / 500)
    least = (7 * 16_783_360 * 2 + 2 * 8 * 57344) / 819e9
    assert H.read_metric("short_conv_roofline.agent", dict(run)) \
        == pytest.approx(100 * least / 0.3e-3, rel=1e-3)
    least = 2 * 16_783_360 * 400 * 7 / 197e12
    assert H.read_metric("short_conv_prefill_roofline.agent", dict(run)) \
        == pytest.approx(100 * least / 1.5e-3, rel=1e-3)
