"""The control of "How correct is decided": the reference put in the
program's place and computed in float8, the nearest precision below the
bfloat16 both configurations state, has to come out as NOT correct under the
cells' own limits. On the chip it was read at the cells' own sizes (PERF.md,
section 2); here at a size a test run can hold."""
import json
import os

import numpy as np
import pytest

from chipbench import harness as H
from chipbench import reference as R
from chipbench import serve, train

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def limits(cell):
    with open(os.path.join(HERE, "cells", cell + ".json")) as f:
        return json.load(f)["limits"]


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_float8_training_is_not_correct(seed):
    cfg = dict(family="llama", hidden_size=64, intermediate_size=176,
               num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
               rms_norm_eps=1e-5, rope_theta=130000,
               tie_word_embeddings=True, initializer_range=0.02)
    opt = dict(learning_rate=3e-4, beta1=0.9, beta2=0.999, epsilon=1e-8,
               weight_decay=0.01)
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(3):
        toks = rng.integers(0, 256, (2, 33))
        batches.append((toks[:, :-1], toks[:, 1:]))
    ref = R.train_steps(cfg, seed, batches, opt)
    low = R.train_steps(cfg, seed, batches, opt, precision="fp8")
    lim = limits("smollm2-train-seq2k")
    assert H.judge(train.numbers_for(ref, ref, lim)[0])
    numbers = train.numbers_for(low, ref, lim)[0]
    assert not H.judge(numbers)
    assert numbers["grad_norm_gap"]["value"] > lim["grad_norm_gap"]


@pytest.mark.parametrize("cell", ["mistral7b-chat-batch",
                                  "mistral7b-doc-sessions"])
@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_float8_serving_is_not_correct(cell, seed):
    cfg = dict(family="llama", hidden_size=128, intermediate_size=384,
               num_hidden_layers=8,
               num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
               rms_norm_eps=1e-5, rope_theta=1e6, tie_word_embeddings=False,
               initializer_range=0.1)
    ids = np.random.default_rng(seed).integers(0, 512, (4, 96))
    rows = [list(range(31, 95))] * 4
    ref = R.served_logits(cfg, seed, ids, rows)
    low = R.served_logits(cfg, seed, ids, rows, precision="fp8")
    lim = limits(cell)["served_logit_gap"]
    own = serve.token_gaps(ref, [lg.argmax(-1) for lg in ref])
    assert max(own) == 0.0
    assert max(serve.token_gaps(ref, [lo.argmax(-1) for lo in low])) > lim
