"""Where ``PagedContinuousBatcher`` chooses the next token. A greedy batcher's
two executables (``serving.paged_decode``, ``serving.paged_prefill_chunk``)
return the argmax as one more output and the host fetches int32 ids; a
sampling one fetches the logits and draws on the host. Held here, at toy
widths in float32 over the five families that have ``paged_decode_step``:
the served tokens equal the host pick's token for token (through admission
and decode, and where two maxima tie), the sampled path is the host's draw
from the fetched logits in the seeded order, the two series count what was
fetched and chosen, and the choice added no executable and no capture."""
import functools

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.inference.serving import PagedContinuousBatcher
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
from paddle_tpu.observability import opprof
from paddle_tpu.observability.metrics import get_registry

VOCAB = 128
# how each family is built at toy width, and what its batcher needs besides
FAMILIES = {
    "gpt": (lambda: GPT2ForCausalLM(GPT2Config(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128, dropout=0.0)),
        dict(n_pages=48)),
    "llama": (lambda: models.LlamaForCausalLM(models.llama_tiny_config(
        vocab_size=VOCAB, num_hidden_layers=2, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128)), dict(n_pages=48)),
    "sambay": (lambda: models.SambaYForCausalLM(
        models.sambay_tiny_config()), dict(n_pages=48)),
    "glm_dsa": (lambda: models.GlmDsaForCausalLM(
        models.glm_dsa_tiny_config()), dict(n_pages=48)),
    "mellum": (lambda: models.MellumForCausalLM(
        models.mellum_tiny_config()),
        dict(n_pages={"full": 48, "window": 30})),
}
SERVER = dict(max_batch=3, s_max=64, block_size=8, prefill_chunk=16)
# two prompts of more than one chunk and two of less, one more than the
# slots: every slot decodes beside others, one is used again
PROMPTS, NEWS = (21, 5, 33, 9), (5, 8, 3, 6)


@functools.lru_cache(maxsize=None)
def build(family):
    paddle.seed(11)
    model = FAMILIES[family][0]()
    model.eval()
    return model


def serve(family, model=None, tap=None, lengths=PROMPTS, news=NEWS,
          **server):
    """(the served sequences, stats) of seeded prompts of ``lengths``, the
    batcher closed; ``tap`` takes the place of ``_pick`` where given."""
    b = PagedContinuousBatcher(
        model or build(family),
        **dict(SERVER, **FAMILIES[family][1], **server))
    if tap is not None:
        b._pick = functools.partial(tap, b)
    rng = np.random.RandomState(0)
    rids = [b.submit(rng.randint(0, VOCAB, n), new)
            for n, new in zip(lengths, news)]
    with paddle.no_grad():
        out = b.run_until_done()
    assert b.audit_pages() == 0
    stats = b.stats()
    b.close()
    return [out[r] for r in rids], stats


def host_argmax(batcher, logits):
    """The parent's path: the whole logits on the host, numpy's argmax."""
    assert logits.ndim == 2 and logits.shape[-1] == VOCAB
    return logits.argmax(-1)


def series(name, **labels):
    s = get_registry().counter(name, labelnames=tuple(labels))
    return (s.labels(**labels) if labels else s).value


COUNTED = ("serving.picks_total", dict(where="device")), \
    ("serving.picks_total", dict(where="host")), \
    ("serving.fetch_bytes_total", {})


def counted(run):
    """``run()`` and by how much it moved (picks on the device, picks on the
    host, bytes fetched for selection)."""
    before = [series(name, **labels) for name, labels in COUNTED]
    out = run()
    return out, [series(name, **labels) - b
                 for (name, labels), b in zip(COUNTED, before)]


@functools.lru_cache(maxsize=None)
def greedy(family, compiled=True):
    """One greedy run a family for the tests that read it: (sequences,
    stats, what ``_pick`` was handed), and what the run counted."""
    seen = []

    def ids_only(batcher, fetched):
        seen.append(fetched)
        return PagedContinuousBatcher._pick(batcher, fetched)
    (seqs, stats), moved = counted(
        lambda: serve(family, compile=compiled, tap=ids_only))
    return seqs, stats, seen, moved


# every family through its two executables; the uncompiled wrappers (the
# same two functions, called op by op) for one family without and one with
# ``step_counts`` and page groups
@pytest.mark.parametrize("family,compiled", [
    (f, True) for f in FAMILIES] + [("llama", False), ("mellum", False)])
def test_greedy_serves_the_host_picks_tokens(family, compiled):
    # a batcher that samples fetches logits; numpy's argmax in the draw's
    # place is what a greedy batcher did before the executables chose
    want, _ = serve(family, compile=compiled, do_sample=True,
                    tap=host_argmax)
    got, stats, seen, _ = greedy(family, compiled)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # one id an admission, max_batch a decode step: no logits row came
    assert all(f.dtype == np.int32 and f.shape in ((1,), (3,))
               for f in seen)
    assert sum(f.shape == (1,) for f in seen) == len(PROMPTS)
    assert sum(f.shape == (3,) for f in seen) == stats["steps"]


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_sampling_batcher_draws_on_the_host_from_the_logits(family):
    knobs = dict(do_sample=True, temperature=0.8, top_k=20, top_p=0.9)
    rng, fetched_bytes = np.random.RandomState(5), []

    def replay(batcher, logits):
        # the parent's selection, replayed beside the batcher's own on a
        # generator of the same seed: one draw a row, parked rows too
        assert logits.ndim == 2 and logits.shape[-1] == VOCAB
        fetched_bytes.append(logits.nbytes)
        want = GPT2ForCausalLM._select_token(logits, True, 0.8, 20, 0.9,
                                             rng)
        got = PagedContinuousBatcher._pick(batcher, logits)
        np.testing.assert_array_equal(got, want)
        return got
    (first, _), moved = counted(lambda: serve(
        family, compile=True, seed=5, tap=replay, **knobs))
    assert moved == [0, sum(NEWS), sum(fetched_bytes)]
    assert any(not np.array_equal(a, g)
               for a, g in zip(first, greedy(family)[0]))


@pytest.mark.parametrize("family", list(FAMILIES))
def test_greedy_fetches_four_bytes_a_slot_and_counts_a_pick_a_token(family):
    _, stats, _, moved = greedy(family)
    # [B] int32 a decode step, [1] an admission
    assert moved == [sum(NEWS), 0, 4 * SERVER["max_batch"] * stats["steps"]
                     + 4 * len(PROMPTS)]


# -- set-up: what the choice may not add --------------------------------------

@pytest.fixture
def observatory():
    opprof.enable()
    opprof.reset_captures()
    yield
    opprof.reset_captures()
    opprof.disable()
