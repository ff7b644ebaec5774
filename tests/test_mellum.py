"""``models/mellum.py`` against its plain reference ``chipbench/families/
mellum.py`` at the tiny size, float32: the whole forward, prefill in chunks
and decode through the two page groups, and a prefix hit behind window pages
that went back and were reused. The page groups themselves are held in
``test_page_groups.py``."""
import functools
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
from chipbench import families, reference as R, weights as W  # noqa: E402
from paddle_tpu.inference.serving import PagedContinuousBatcher  # noqa: E402
from paddle_tpu.models import (MellumConfig, MellumForCausalLM,  # noqa: E402
                               mellum_tiny_config)
from paddle_tpu.models import mellum as M  # noqa: E402
from paddle_tpu.observability.metrics import get_registry  # noqa: E402

WINDOW, FULL = "sliding_attention", "full_attention"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 4,
        "beta_slow": 1, "attention_factor": 1.1386294361119891}
CFG = dict(family="mellum", hidden_size=64, num_hidden_layers=8,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           layer_types=([WINDOW] * 3 + [FULL]) * 2,
           mlp_layer_types=["sparse"] * 8, sliding_window=16, num_experts=8,
           num_experts_per_tok=2, moe_intermediate_size=32,
           norm_topk_prob=True, rms_norm_eps=1e-6, vocab_size=128,
           tie_word_embeddings=False, max_position_embeddings=512,
           initializer_range=0.1, prefill_key_block=32,
           rope_parameters={FULL: YARN, WINDOW: {"rope_type": "default",
                                                 "rope_theta": 500000}})
FAMILY = families.of(CFG)
TOL = 1e-4
SEED = 5


@functools.lru_cache(maxsize=None)
def build():
    paddle.seed(0)
    model = FAMILY.program_model(CFG, dtype="float32")
    model.eval()
    W.install(model, CFG, SEED, scanned=False)
    return model


class Tap:
    """Keeps, for every request, the logits row each of its tokens was
    picked from: admission picks from [1, V], a decode step from [B, V].
    Logits reach the host only where the batcher samples (a greedy one
    fetches the ids its executables chose), so ``SERVER`` samples from the
    one best row: the tokens greedy would serve."""

    def __init__(self, batcher):
        self.rows, self.last = {}, None
        pick, on_token = batcher._pick, batcher._tele.on_token

        def tapped_pick(logits):
            self.last = np.asarray(logits, np.float32)
            return pick(logits)

        def tapped_token(req):
            row = self.last[0] if len(self.last) == 1 \
                else self.last[req.slot]
            self.rows.setdefault(req.rid, []).append(row)
            return on_token(req)

        batcher._pick, batcher._tele.on_token = tapped_pick, tapped_token


SERVER = dict(max_batch=3, s_max=128, block_size=8,
              n_pages={"full": 48, "window": 30}, prefill_chunk=16,
              prefix_cache=True, compile=False, do_sample=True, top_k=1)


def serve(prompts, news, batcher=None, **server):
    b = batcher or PagedContinuousBatcher(build(), **dict(SERVER, **server))
    tap = Tap(b)
    rids = [b.submit(p, n) for p, n in zip(prompts, news)]
    with paddle.no_grad():
        out = b.run_until_done()
    assert b.audit_pages() == 0
    return [out[r] for r in rids], [np.stack(tap.rows[r]) for r in rids], b


def reference_rows(seqs, prompts):
    width = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), width), np.int64)
    rows = []
    for i, (seq, prompt) in enumerate(zip(seqs, prompts)):
        ids[i, :len(seq)] = seq
        rows.append(list(range(len(prompt) - 1, len(seq) - 1)))
    return R.served_logits(CFG, SEED, ids, rows)


# -- the whole forward --------------------------------------------------------

def test_forward_matches_the_reference_in_logits():
    """70 rows: past four windows of 16 and past YaRN's original 32."""
    ids = np.random.default_rng(0).integers(0, 128, (2, 70))
    ref = R.served_logits(CFG, SEED, ids, [list(range(70))] * 2)
    with paddle.no_grad():
        got = np.asarray(build()(paddle.to_tensor(ids))._data)
    np.testing.assert_allclose(got, np.stack(ref), atol=TOL, rtol=0)


def test_the_routers_choose_the_same_experts_at_the_same_gates():
    """Softmax over all 8, the 2 largest, renormalised: program and
    reference on one layer's weights."""
    w = R._f32(W.make_layer(CFG, SEED, 1))
    u = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    chosen, gates = M.route(w, u, 2, True)
    es = functools.partial(R.einsum, "f32")
    want, want_gates = FAMILY.router(es, u, w, FAMILY.sizes(CFG), CFG)
    assert np.array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_gates),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    raw = M.route(w, u, 2, False)[1]
    assert (np.asarray(raw).sum(-1) < 1.0).all()


def test_forward_gives_a_loss_and_the_tiny_config_is_the_tested_shape():
    c = mellum_tiny_config()
    assert c.layer_types == tuple(CFG["layer_types"])
    assert (c.sliding_window, c.num_experts, c.num_experts_per_tok) \
        == (16, 8, 2)
    ids = paddle.to_tensor(np.random.default_rng(2).integers(0, 128, (2, 9)))
    with paddle.no_grad():
        logits, loss = build()(ids, labels=ids)
    assert logits.shape == [2, 9, 128] and np.isfinite(float(loss))
    assert MellumConfig().layer_types[:8] == tuple(CFG["layer_types"])
    with pytest.raises(ValueError, match="layer_types"):
        MellumForCausalLM(mellum_tiny_config(layer_types=(WINDOW,)))


def test_yarn_tables_at_the_published_sizes():
    """low 18, high 35; the blend between; attention_factor on both."""
    cos, sin = M.rope_tables(4, 128, 500000.0, M._yarn_default())
    plain = M.rope_tables(4, 128, 500000.0)
    f = 500000.0 ** (-np.arange(64) * 2.0 / 128)
    r = np.clip((np.arange(64) - 18) / (35 - 18), 0, 1)
    inv = (1 - r) * f + r * f / 16
    assert r[18] == 0 and r[35] == 1
    factor = 0.1 * np.log(16.0) + 1.0
    np.testing.assert_allclose(cos[3], np.cos(3 * inv) * factor, atol=1e-6)
    np.testing.assert_allclose(sin[3], np.sin(3 * inv) * factor, atol=1e-6)
    np.testing.assert_allclose(plain[0][3], np.cos(3 * f), atol=1e-6)
    published = dict(CFG, head_dim=128, rope_parameters={
        FULL: dict(YARN, factor=16, original_max_position_embeddings=8192,
                   beta_fast=32), WINDOW: CFG["rope_parameters"][WINDOW]})
    np.testing.assert_allclose(
        FAMILY.inverse_frequencies(published, FULL), inv, rtol=1e-12)


# -- the served path ----------------------------------------------------------

def prompts_by_length():
    """Below a window, at it, past three windows of 16 rows."""
    rng = np.random.default_rng(3)
    return [rng.integers(0, 128, n) for n in (10, 16, 53)]


@pytest.mark.parametrize("compiled", [False, True])
def test_chunks_and_decode_match_the_reference_at_every_served_row(compiled):
    """Chunks of 16 over pages of 8, three slots side by side; the longest
    runs to 53 + 20 rows, so its window pages go back while it is
    prefilled and again while it decodes."""
    prompts, news = prompts_by_length(), [9, 12, 20]
    seqs, rows, b = serve(prompts, news, compile=compiled)
    assert b.stats()["kv_writer"] == "page"
    assert b._groups["window"].released_total > 0
    b.close()
    for got, ref in zip(rows, reference_rows(seqs, prompts)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def test_a_whole_prompt_prefill_matches_the_reference_too():
    """No chunks: the window ring is the whole table."""
    prompts, news = prompts_by_length(), [5, 5, 5]
    seqs, rows, b = serve(prompts, news, prefill_chunk=None)
    b.close()
    for got, ref in zip(rows, reference_rows(seqs, prompts)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def test_a_hit_behind_released_and_reused_pages_equals_a_cold_prefill():
    """A document of 48 rows is asked, short prompts then run its early
    window pages through the pool (14 pages: a sequence's ring is 7), and
    the document comes back: the hit holds the cached pages of the 16 rows
    before its boundary, whatever became of the rest, and every served row
    reads what a server that never saw the document gives."""
    rng = np.random.default_rng(4)
    doc = rng.integers(0, 128, 48)
    ask = [np.concatenate([doc, rng.integers(0, 128, n)]) for n in (5, 9)]
    fill = [rng.integers(0, 128, 30) for _ in range(3)]
    pages = {"full": 48, "window": 14}
    first, rows1, b = serve([ask[0]], [6], n_pages=pages, max_batch=2)
    window = b._groups["window"]
    for p in fill:                       # one at a time: the pool turns over
        serve([p], [4], batcher=b)
    assert window.reclaimed_total > 0
    hit0 = b.prefix_cache.stats()["hit_tokens"]
    second, rows2, _ = serve([ask[1]], [10], batcher=b)
    assert b.prefix_cache.stats()["hit_tokens"] - hit0 == 48
    b.close()
    cold, rows_cold, c = serve([ask[1]], [10], n_pages=pages, max_batch=2)
    c.close()
    assert np.array_equal(second[0], cold[0])
    np.testing.assert_allclose(rows2[0], rows_cold[0], atol=TOL, rtol=0)
    for got, ref in zip(rows1 + rows2, reference_rows(first + second,
                                                      [ask[0], ask[1]])):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def test_a_cut_hit_is_counted_and_still_serves_the_reference():
    """The pool so small that the document's trailing window pages are
    reclaimed too: the match is cut (here to nothing), counted, and the
    request is served like a cold one."""
    rng = np.random.default_rng(6)
    doc = rng.integers(0, 128, 48)
    ask = [np.concatenate([doc, rng.integers(0, 128, n)]) for n in (5, 9)]
    pages = {"full": 48, "window": 8}
    cut = get_registry().counter(
        "serving.prefix_hits_cut_total", "", labelnames=("why",)).labels(
            why="window_pages_reclaimed")
    found = get_registry().counter("serving.prefix_matches_total", "")
    _, _, b = serve([ask[0]], [4], n_pages=pages, max_batch=1)
    serve([rng.integers(0, 128, 60)], [4], batcher=b)
    cut0, found0 = cut.value, found.value
    hit0 = b.prefix_cache.stats()["hit_tokens"]
    seqs, rows, _ = serve([ask[1]], [8], batcher=b)
    assert (cut.value - cut0, found.value - found0) == (1, 1)
    assert b.prefix_cache.stats()["hit_tokens"] == hit0
    b.close()
    np.testing.assert_allclose(rows[0], reference_rows(seqs, [ask[1]])[0],
                               atol=TOL, rtol=0)


@pytest.mark.parametrize("option,value", [
    ("kv_quant", "int8"), ("cache_quant", "dynamic_int8"),
    ("tier_quant", "int8"), ("host_kv_gib", 1.0), ("disk_kv_dir", "/tmp/x"),
    ("session_store", "/tmp/x"), ("draft_model", object())])
def test_the_contract_refuses_by_name(option, value):
    with pytest.raises(ValueError, match=f"{option} is not supported for "
                                         f"MellumForCausalLM"):
        PagedContinuousBatcher(build(), **dict(SERVER, **{option: value}))


def test_the_steps_count_what_their_routers_did():
    """The batcher's series, from ``step_counts``: the indexer's two
    columns stay zero, every assignment is local (all 8 experts held)."""
    reg = get_registry()

    def prefill(name):
        series = reg.get(name)
        return series.labels(phase="prefill").value if series else 0

    names = ("serving.moe_assignments_total",
             "serving.moe_assignments_local_total",
             "serving.dsa_rows_scored_total")
    before = [prefill(n) for n in names]
    prompt = np.random.default_rng(7).integers(0, 128, 21)
    serve([prompt], [4], max_batch=1)
    made, local, scored = (prefill(n) - b for n, b in zip(names, before))
    assert made == local == 21 * 2 * 8         # rows x top-2 x 8 layers
    assert scored == 0


def test_the_cache_gauges_read_the_pools_as_allocated():
    _, _, b = serve([np.arange(5)], [2])
    held = get_registry().get("serving.kv_cache_bytes")
    # K and V, 2 heads x 8 rows x 16 dims float32 a page, scratch page too
    page = 2 * 2 * 8 * 16 * 4
    assert held.labels(group="full").value == 2 * 49 * page
    assert held.labels(group="window").value == 6 * 31 * page
    b.close()
