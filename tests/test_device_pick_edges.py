"""Where the paged batcher chooses the next token, at the edges: of two equal
maxima the lower index is served, a whole-prompt admission picks its first
token on the host, and warm-up makes two executables and one capture each.
(The greedy and the sampling paths are tests/test_device_pick.py's, whose
toy families and server these cases use.)
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import PagedContinuousBatcher
from paddle_tpu.observability import opprof
from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.perf.compile_cache import compile_metrics

from test_device_pick import (FAMILIES, PROMPTS, SERVER, VOCAB, build, counted,
                              observatory, serve)


def twin_logits(model):
    """Makes the upper half of every logits row of ``model`` a copy of the
    lower: each maximum has an equal twin ``VOCAB / 2`` above it."""
    half = VOCAB // 2

    def twinned(fn):
        def call(*args, **kwargs):
            logits, *rest = fn(*args, **kwargs)
            low = logits[..., :half]
            return (paddle.concat([low, low], axis=-1), *rest)
        return call
    model.paged_decode_step = twinned(model.paged_decode_step)
    model.paged_prefill_into = twinned(model.paged_prefill_into)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_of_two_equal_maxima_the_lower_index_is_served(family):
    paddle.seed(11)
    model = FAMILIES[family][0]()
    model.eval()
    twin_logits(model)
    ties = []

    def host(batcher, logits):
        best = logits.argmax(-1)
        rows = np.arange(len(best))
        ties.append(logits[rows, best] == logits[rows, best + VOCAB // 2])
        return best
    want, _ = serve(family, model, compile=True, do_sample=True, tap=host)
    assert np.concatenate(ties).all()           # every choice was a tie
    got, _ = serve(family, model, compile=True)
    for g, w, n in zip(got, want, PROMPTS):
        np.testing.assert_array_equal(g, w)
        assert (g[n:] < VOCAB // 2).all()


def test_whole_prompt_admission_picks_its_first_token_on_the_host():
    """No ``prefill_chunk``: the prompt is prefilled uncompiled at its own
    length and its one first token is numpy's argmax of the fetched logits;
    the decode steps still choose on the device."""
    traffic = dict(lengths=(9, 9, 9), news=(4, 3, 5))   # one length, one
    (got, _), moved = counted(lambda: serve(              # eager program
        "llama", compile=True, prefill_chunk=None, **traffic))
    want, _ = serve("llama", compile=True, **traffic)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert moved[:2] == [12 - 3, 3]


@pytest.mark.parametrize("family", ["llama", "sambay", "glm_dsa", "mellum"])
def test_warm_up_makes_two_executables_and_one_capture_each(family,
                                                            observatory):
    """What ``chipbench/serve.py::warm_up`` sends, two requests through a
    compiled batcher: two ``to_static`` signatures (``compile.miss``), one
    capture under each of the two labels, and nothing more when the same
    shapes come again. ``glm_dsa`` and ``mellum`` keep ``step_counts``."""
    labels = ("serving.paged_decode", "serving.paged_prefill_chunk")
    captured = get_registry().counter(
        "opprof.captures_total", "", labelnames=("label",))
    before = {lb: captured.labels(label=lb).value for lb in labels}
    misses = compile_metrics()["compile_cache_misses"]
    b = PagedContinuousBatcher(
        build(family), **dict(SERVER, **FAMILIES[family][1], compile=True))
    rng = np.random.RandomState(1)
    with paddle.no_grad():
        for _ in range(2):              # the second round adds nothing
            for n in (30, 30):
                b.submit(rng.randint(0, VOCAB, n), 4)
            b.run_until_done()
    b.close()
    assert compile_metrics()["compile_cache_misses"] - misses == 2
    assert len(b._step_fn._cache) == len(b._chunk_fn._cache) == 1
    captures = opprof.get_captures()
    assert sorted(captures) == sorted(labels)
    assert [len(captures[lb]) for lb in labels] == [1, 1]
    assert [captured.labels(label=lb).value - before[lb]
            for lb in labels] == [1, 1]
