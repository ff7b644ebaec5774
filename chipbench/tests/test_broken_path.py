"""The rest of a run with the timed path broken underneath: ``correct`` has
to come out false. These drive ``run.main`` in this process at the rehearsal
sizes (which skips only the harness's look for a chip)."""
import json

import numpy as np
import pytest


def last_line(capsys, argv):
    from chipbench import run
    run.main(argv)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


ARGS = ["--seed", "2147483777", "--seconds", "1", "--trace", "0",
        "--rehearse"]


@pytest.fixture(autouse=True)
def private_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def test_sound_runs_are_correct(capsys):
    for cell in ("smollm2-train-seq2k", "mistral7b-doc-sessions"):
        assert last_line(capsys, ["--workload", cell] + ARGS)["correct"]


def test_a_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    from paddle_tpu import jit
    real = jit.TrainStep._run

    def frozen(self, entry, args):
        opt = self._opt
        keep = ([p._data for p in entry["params"]],
                dict(opt._master_weights),
                {k: dict(v) for k, v in opt._accumulators.items()})
        # the compiled step donates its state: run it on copies
        for p in entry["params"]:
            p._data = p._data.copy()
        opt._master_weights = {k: v.copy() for k, v in keep[1].items()}
        opt._accumulators = {k: {i: a.copy() for i, a in v.items()}
                             for k, v in keep[2].items()}
        loss = real(self, entry, args)
        for p, d in zip(entry["params"], keep[0]):
            p._data = d
        opt._master_weights, opt._accumulators = keep[1], keep[2]
        return loss

    monkeypatch.setattr(jit.TrainStep, "_run", frozen)
    line = last_line(capsys, ["--workload", "smollm2-train-seq2k"] + ARGS)
    assert line["correct"] is False
    bad = {k for k, v in line["compared"].items() if v["value"] > v["limit"]}
    assert "delta_norm_gap" in bad


def test_a_part_of_the_batch_left_out(capsys, monkeypatch):
    from chipbench import train
    real = train.to_device

    def half(batch):
        ids, labels = batch
        ids, labels = ids.copy(), labels.copy()
        ids[len(ids) // 2:] = ids[:1]          # rows repeated, not fed
        labels[len(ids) // 2:] = labels[:1]
        return real((ids, labels))

    monkeypatch.setattr(train, "to_device", half)
    line = last_line(capsys, ["--workload", "smollm2-train-seq2k"] + ARGS)
    assert line["correct"] is False


@pytest.mark.parametrize("cell", ["mistral7b-doc-sessions"])
def test_a_token_altered_where_it_is_produced(capsys, monkeypatch, cell):
    from paddle_tpu.inference.serving import PagedContinuousBatcher
    real = PagedContinuousBatcher._pick
    calls = {"n": 0}

    def altered(self, logits):
        picked = np.array(real(self, logits))
        calls["n"] += 1
        if calls["n"] % 3 == 0:                # every third selection
            picked = (picked + 1) % logits.shape[-1]
        return picked

    monkeypatch.setattr(PagedContinuousBatcher, "_pick", altered)
    line = last_line(capsys, ["--workload", cell] + ARGS)
    assert line["correct"] is False
    v = line["compared"]["served_logit_gap"]
    assert v["value"] > v["limit"]
