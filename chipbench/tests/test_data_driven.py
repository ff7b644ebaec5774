"""A new configuration, traffic mix, cell, per-layer metric and model family
are found by name when they are added as files only."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness as H
from chipbench import traffic as T
from chipbench.lastline import cell_metrics, problems

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def copy(tmp_path, monkeypatch):
    root = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics", "cells", "rehearse"):
        shutil.copytree(os.path.join(HERE, sub), root / sub)
    monkeypatch.setattr(H, "HERE", str(root))
    monkeypatch.setattr(T, "HERE", str(root))
    return root


def test_new_files_are_found_by_name(copy):
    (copy / "configs" / "new-model.json").write_text(json.dumps(
        {"hidden_size": 1024, "num_hidden_layers": 3}))
    (copy / "rehearse" / "new-model.json").write_text(json.dumps(
        {"hidden_size": 32}))
    (copy / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "serve-open", "rate_per_s": 2.0, "cycle": {
            "documents": [64], "asks_per_document": 2,
            "prompt_tokens": {"min": 4, "max": 8},
            "answer_tokens": {"min": 2, "max": 4}}}))
    (copy / "metrics" / "new.metric-1.py").write_text(
        "def read(run):\n    return run.get('answer')\n")
    assert H.load_config("new-model", False)["hidden_size"] == 1024
    assert H.load_config("new-model", True) == {"hidden_size": 32,
                                                "num_hidden_layers": 3}
    offers = T.offers(T.load("new-mix"), 100, 1)
    first, second = next(offers), next(offers)
    assert len(first.prompt) - 64 in range(4, 9) and second.ask == 1
    assert H.read_metric("new.metric-1", {"answer": 42.0}) == 42.0
    # a reader that finds nothing to read returns nothing
    assert H.read_metric("new.metric-1", {}) is None


def test_new_entries_in_benchmark_json_select_the_metrics():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "new-cell", "config": "new-model",
                               "traffic": "new-mix", "chips": 1, "why": "x"})
    for m in bench["end_to_end"]:
        if m["name"] == "ttft_mean_ms":
            m["workloads"].append("new-cell")
    bench["per_layer"].append({"name": "new.metric-1", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "Gateway", "moves": "ttft_mean_ms",
                               "workloads": ["new-cell"]})
    assert set(cell_metrics(bench, "new-cell", False)) == {"ttft_mean_ms",
                                                           "setup_s"}
    assert set(cell_metrics(bench, "new-cell", True)) == {"new.metric-1"}


def test_every_metric_cell_and_config_of_the_benchmark_has_its_files():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           m["name"] + ".py")), m["name"]
    for w in bench["workloads"]:
        for sub, key in (("cells", "name"), ("traffic", "traffic"),
                         ("rehearse", "traffic"), ("configs", "config"),
                         ("rehearse", "config")):
            assert os.path.exists(os.path.join(HERE, sub,
                                               w[key] + ".json")), (sub, w)


# -- a second family, added as files and entries only -------------------------

ROOT = os.path.dirname(HERE)
TRAIN, SERVE = "smollm2-1.7b-train-d6", "mistral-7b-v0.3-serve-d16"
CELLS = {  # a cell and its configuration, both of this name: (the
    # configuration it copies, its traffic, the keys it adds to the copy)
    "twin-train": (TRAIN, "train-fixed-2k", {}),
    "twin-chat": (SERVE, "chat-batch", {}),
    "twin-chat-no-rope": (SERVE, "doc-sessions", {"twin_drop_rope": True}),
    "twin-train-extra-leaf": (TRAIN, "train-fixed-2k",
                              {"twin_extra_leaf": True})}
SEED = 2 ** 31 + 77


def dump(path, data):
    path.write_text(json.dumps(data, indent=1))


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout that holds BENCHMARK.json and chipbench/ and, added to them,
    the family ``twin`` with its configurations, cells and one reader: new
    files and new entries, nothing that exists edited."""
    root = tmp_path_factory.mktemp("checkout")
    bench_dir = root / "chipbench"
    shutil.copytree(HERE, bench_dir, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "data"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    shutil.copy(os.path.join(HERE, "tests", "family_twin.py"),
                bench_dir / "families" / "twin.py")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell, (like, traffic, more) in CELLS.items():
        dump(bench_dir / "configs" / f"{cell}.json",
             dict(H.load_json("configs", f"{like}.json"), family="twin",
                  **more))
        shutil.copy(bench_dir / "rehearse" / f"{like}.json",
                    bench_dir / "rehearse" / f"{cell}.json")
        limits = next(w["name"] for w in bench["workloads"]
                      if w["config"] == like)
        shutil.copy(bench_dir / "cells" / f"{limits}.json",
                    bench_dir / "cells" / f"{cell}.json")
        bench["configs"].append({"name": cell, "file":
                                 f"chipbench/configs/{cell}.json"})
        bench["workloads"].append({"name": cell, "config": cell,
                                   "traffic": traffic, "chips": 1})
        e2e = {"train-fixed-2k": ("train_tokens_per_s",),
               "chat-batch": ("serve_tokens_per_s",),
               "doc-sessions": ("ttft_mean_ms", "tpot_mean_ms")}[traffic]
        for name in e2e:      # an end-to-end metric no cell reports yet
            if not any(m["name"] == name for m in bench["end_to_end"]):
                bench["end_to_end"].append({
                    "name": name, "unit": "tokens/s", "better": "higher",
                    "bound": 0.1, "source": "host_clock", "workloads": []})
        for m in bench["end_to_end"]:
            if m["name"] in e2e:
                m["workloads"].append(cell)
    (bench_dir / "metrics" / "twin.admissions.py").write_text(
        "def read(run):\n    return run['counters'].get('admissions')\n")
    (bench_dir / "metrics" / "twin.steps.py").write_text(
        "def read(run):\n    return run['counters'].get('step_seconds.count')"
        "\n")
    for name in ("twin.admissions", "twin.steps"):
        bench["per_layer"].append({
            "name": name, "unit": "requests", "better": "higher",
            "source": "program_counter", "layer": "Batcher / admission",
            "moves": "serve_tokens_per_s", "workloads": ["twin-chat"]})
    dump(root / "BENCHMARK.json", bench)
    assert all(p.read_bytes() == was for p, was in before.items())
    return root


def rehearse(checkout, cell, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(checkout / "jax_cache"))
    out = subprocess.run(
        [sys.executable, str(checkout / "chipbench" / "run.py"),
         "--workload", cell, "--seed", str(SEED), "--seconds", "2",
         "--trace", str(trace), "--rehearse"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=900)
    return out


def last_line(out):
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["twin-train", "twin-chat"])
def test_a_second_family_runs_as_files_only(checkout, cell):
    with open(checkout / "BENCHMARK.json") as f:
        bench = json.load(f)
    out = rehearse(checkout, cell)
    line = last_line(out)
    assert problems(line, bench, cell, False, 1) == []
    assert line["correct"] is True and line["failed"] == 0
    if cell == "twin-train":       # the leaves are the twin's own
        leaves = json.loads(out.stdout.split("worst_leaves: ")[1]
                            .splitlines()[0])
        assert set(leaves.values()) <= {
            "tok", "norm_out", "attn_q", "attn_k", "attn_v", "attn_o",
            "ffn_gate", "ffn_up", "ffn_down", "norm_attn", "norm_ffn"}


def test_its_counters_reach_the_readers(checkout):
    with open(checkout / "BENCHMARK.json") as f:
        bench = json.load(f)
    line = last_line(rehearse(checkout, "twin-chat", trace=1))
    no_chip = ("serve.mbu.batch", "decode_device_ms.batch",
               "paged_attn_roofline")
    assert problems(line, bench, "twin-chat", True, 1, no_chip) == []
    # the window's differences of the series the family lists: every request
    # admitted in it, every decode step; a series nobody made reads nothing
    assert line["metrics"]["twin.admissions"]["value"] >= 4
    assert line["metrics"]["twin.steps"]["value"] >= 4
    assert line["correct"] is True


def test_its_spans_and_scopes_reach_the_analysis(monkeypatch, tmp_path):
    import importlib.util
    from chipbench import phases as P
    from test_phases import synthetic
    spec = importlib.util.spec_from_file_location(
        "chipbench.families.twin", os.path.join(HERE, "tests",
                                                "family_twin.py"))
    twin = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(twin)
    monkeypatch.setitem(sys.modules, "chipbench.families.twin", twin)
    path = tmp_path / "trace.json"
    dump(path, dict(synthetic(
        [["gateway.step", 50, 900], ["twin.summarise", 350, 150]],
        modules=[["jit_serving_paged_decode(1)", 0, 1000]]),
        op_scopes={P.DECODE: {"p.0": P.scope_of(
            "jit(f)/twin_block/mlp/dot", P.SCOPES + twin.SCOPES)}}))
    monkeypatch.setattr(P, "newest_trace", lambda: str(path))
    monkeypatch.setattr(P, "_ANALYSES", {})
    a = P.of_run({"trace": {"kernels": {}}, "cfg": {"family": "twin"}})
    assert a["span_counts"] == {"twin.summarise": 1}
    assert a["gaps"]["twin.summarise"] == pytest.approx(100e-9)
    assert "twin_block/mlp <-operand" in a["by_scope"][P.DECODE]
    # a cell of the first family keeps the base names alone
    monkeypatch.setattr(P, "_ANALYSES", {})
    a = P.of_run({"trace": {"kernels": {}}, "cfg": {"family": "llama"}})
    assert "twin.summarise" not in a["gaps"] and a["span_counts"] == {}


def test_its_wrong_equations_are_not_correct(checkout):
    line = last_line(rehearse(checkout, "twin-chat-no-rope"))
    assert line["correct"] is False
    gap = line["compared"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert line["failed"] == 0          # the program served every request


def test_a_leaf_the_program_lacks_fails_by_name(checkout):
    out = rehearse(checkout, "twin-train-extra-leaf")
    assert out.returncode != 0
    assert not out.stdout.strip().splitlines()[-1].startswith("{")
    assert "leaf 'attn_gate': the program's model has no parameter " \
           "'model.layers_scanned.gate_attn_w'" in out.stderr


def test_the_harness_names_no_architecture():
    """What knows a model is its family's: the next Llama-only line in the
    harness fails here, not in a refused model_config PR."""
    import glob
    import re
    banned = re.compile(r"paddle_tpu\.models|LlamaForCausalLM|LlamaConfig"
                        r"|[\"']wq[\"']|q_proj")
    files = glob.glob(os.path.join(HERE, "*.py")) \
        + glob.glob(os.path.join(HERE, "metrics", "*.py"))
    assert len(files) > 30
    for path in files:
        with open(path) as f:
            hits = [ln for ln in f if banned.search(ln)]
        assert not hits, (os.path.relpath(path, HERE), hits)
    for name in os.listdir(os.path.join(HERE, "configs")):
        with open(os.path.join(HERE, "configs", name)) as f:
            assert json.load(f)["family"] == "llama", name


# sha256 over every leaf (name, shape, float32 bytes) of what weights.py makes
# at the rehearsal sizes, taken from commit c631271 (PR 26) before the leaves
# moved into families/llama.py: the same names, so the same streams
SEED_WEIGHTS = {
    ("smollm2-1.7b-train-d6", 1001): (
        "1a2e2bc9f4561cd587d2b32feb3b65e737ac03002763011e7c4f791883d0f161",
        "9efa4592e9b679907e143681dfcb6a48256c13e597b275addf8431622f847aa3",
        "bf0634ee79ba431abe49928cd26e65e2a4427a9497b8ff4644e56a4ff1fb6489"),
    ("smollm2-1.7b-train-d6", 3000001006): (
        "c6cfe5018eecb2d08c14725204ee046e82d9f87dc6fc36d6d4248d9ae9145976",
        "10eeda6c49551a9631809090526a3def4dc8b87f3487e20f95638f2d99b7727a",
        "32a1fe56ea46d8124cb6b0a5a7fff0b62d31d17b8acf04991fad3c40acfbf287"),
    ("mistral-7b-v0.3-serve-d16", 1001): (
        "76d395c242637ed3f03f9e963315a8c7cbcb07fa6307d8818aae833431f211e7",
        "b9e1374f804541457875b35d3ae83eac424f268213528d1b538060c16bbea49b",
        "1bd66ee13d234c0f5382a30a964b81c84c60e05d0ca9aab873ce9be7ce9ba89a"),
    ("mistral-7b-v0.3-serve-d16", 3000001006): (
        "10a201d7bf28b8e9d7f124c6d29c99fa0cc78e76b60d912ad63625f10610a691",
        "acbbacd4ace961c7ffd3bf08f604986c48d1f0e1fec91df6e0527f72be87a574",
        "7d802320321794b9d4e446c587aab631d71720f88a6d48369dbe90676c08f890"),
}


def sha(tree):
    h = hashlib.sha256()
    for k in sorted(tree):
        a = np.asarray(tree[k].astype("float32"))
        h.update(k.encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config,seed", sorted(SEED_WEIGHTS))
def test_the_seed_weights_are_the_parents(config, seed):
    from chipbench import weights as W
    cfg = H.load_config(config, True)
    assert (sha(W.make_top(cfg, seed)), sha(W.make_layer(cfg, seed, 0)),
            sha(W.make_stack(cfg, seed))) == SEED_WEIGHTS[config, seed]
