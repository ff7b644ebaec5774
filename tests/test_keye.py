"""Keye-VL 2's language model (``models/keye.py``: grouped-query attention
over per-head K/V pages of which a learned indexer keeps ``index_topk`` rows,
rotary positions with three axes, every expert held) against the repo's
plain reference for it, ``chipbench/families/keye.py``: the whole forward,
the rows selected, unequal position axes, and the served path
(``PagedContinuousBatcher``: chunked prefill, decode, a prefix-cache hit,
slots side by side at different lengths) at a small size of the same shape
on seeded weights: 3 layers, 8 experts of which 2 a token, ``topk`` 16
against sequences of 48 and more, so that selection bites.

Tolerance, and why: float32 model against the float32 reference, 1e-4 on
logits of a few units. Both compute the same sums in another order (chunks,
blocks of held rows with a running softmax, kept rows gathered, experts
group by group); nothing else differs, and the rows selected are the same
sets exactly (two index heads make exact ties at the 16th score common
here: both sides keep the lower rows of a tie first). Leaving out the
selection, or the two further position axes, moves the logits by 100 times
that tolerance or more.
"""
import functools
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:       # chipbench lies beside tests/, at the root
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from chipbench import families, reference as R, weights as W  # noqa: E402
from paddle_tpu.inference.serving import PagedContinuousBatcher  # noqa: E402
from paddle_tpu.models import (KeyeConfig, KeyeForCausalLM,  # noqa: E402
                               keye_tiny_config)
from paddle_tpu.models import dsa_select, glm_dsa, keye  # noqa: E402
from test_glm_dsa import Tap  # noqa: E402

CFG = dict(family="keye", hidden_size=64, num_hidden_layers=3,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           num_experts=8, num_local_experts=8, num_experts_per_tok=2,
           moe_intermediate_size=32, norm_topk_prob=True,
           decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6,
           rope_theta=1e7, index_rope_dim=8,
           rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default"},
           sa_config={"indexer_head_dim": 16, "indexer_num_heads": 2,
                      "indexer_num_kv_heads": 1, "topk": 16},
           vocab_size=128, max_position_embeddings=512,
           initializer_range=0.1, tie_word_embeddings=False,
           prefill_key_block=32)
FAMILY = families.of(CFG)
TOL = 1e-4
SEED = 3


@functools.lru_cache(maxsize=None)
def build():
    """One model for the whole file: serving changes nothing of it."""
    paddle.seed(0)
    model = FAMILY.program_model(CFG, dtype="float32")
    model.eval()
    W.install(model, CFG, SEED, scanned=False)
    return model


SERVER = dict(max_batch=3, s_max=128, block_size=8, n_pages=48,
              prefill_chunk=16, prefix_cache=True, compile=False,
              do_sample=True, top_k=1)


def serve(model, prompts, news, batcher=None, **server):
    b = batcher or PagedContinuousBatcher(model, **dict(SERVER, **server))
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


def walk(ids, positions=None, **kw):
    """The reference's full forward over ids [N, T] with ``layer_forward``'s
    own switches: (logits [N, T, V], the masks of rows kept [layer, N])."""
    tables = FAMILY.position_tables(ids.shape[1], CFG, positions)
    top = R._f32(W.make_top(CFG, SEED))
    hidden = FAMILY.embed_tokens(jnp.asarray(ids), top, CFG)
    kept = []
    for i in range(CFG["num_hidden_layers"]):
        w = R._f32(W.make_layer(CFG, SEED, i))
        out = [FAMILY.layer_forward(x, w, tables, CFG, i,
                                    return_selection=True, **kw)
               for x in hidden]
        hidden = jnp.stack([o[0] for o in out])
        kept.append(np.stack([np.asarray(o[1]) for o in out]))
    return np.stack([np.asarray(FAMILY.head_logits(x, top, CFG))
                     for x in hidden]), kept


def document_prompts():
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 128, 48)
    return [np.concatenate([doc, rng.integers(0, 128, n)])
            for n in (5, 9, 30)] + [rng.integers(0, 128, 7)]


def image_positions(seq: int) -> np.ndarray:
    """[3, seq]: text, then a 4 x 5 grid of patches at one temporal
    position (height and width count inside it), then text again from the
    largest position + 1: the three axes differ."""
    t = list(range(10)) + [10] * 20
    h = list(range(10)) + [10 + i // 5 for i in range(20)]
    w = list(range(10)) + [10 + i % 5 for i in range(20)]
    rest = np.arange(15, 15 + seq - 30)
    return np.stack([np.concatenate([a, rest]) for a in (t, h, w)])[:, :seq]


# -- the whole forward --------------------------------------------------------

def test_forward_matches_the_reference_in_logits_and_rows_selected():
    model = build()
    ids = np.random.default_rng(0).integers(0, 128, (2, 80))
    ref, ref_kept = walk(ids)
    with paddle.no_grad():
        got, kept = model(paddle.to_tensor(ids), return_selection=True)
    np.testing.assert_allclose(np.asarray(got._data), ref, atol=TOL, rtol=0)
    for layer, (mine, theirs) in enumerate(zip(kept, ref_kept)):
        mine = np.asarray(mine._data)
        assert np.array_equal(mine, theirs), layer
        # the rule: every row up to topk, exactly topk after
        assert np.array_equal(mine.sum(-1)[0],
                              np.minimum(np.arange(80) + 1, 16))
    served = R.served_logits(CFG, SEED, ids, [list(range(80))] * 2)
    np.testing.assert_allclose(np.stack(served), ref, atol=1e-5, rtol=0)
    # the selection counts: every row attended reads far from it
    assert np.abs(walk(ids, selection="all")[0] - ref).max() > 100 * TOL


def test_three_position_axes_that_differ_match_the_reference():
    model = build()
    ids = np.random.default_rng(4).integers(0, 128, (1, 60))
    pos = image_positions(60)
    assert not np.array_equal(pos[0], pos[1]) \
        and not np.array_equal(pos[1], pos[2])
    ref, ref_kept = walk(ids, positions=pos)
    with paddle.no_grad():
        got, kept = model(paddle.to_tensor(ids), return_selection=True,
                          position_ids=paddle.to_tensor(pos))
        plain = model(paddle.to_tensor(ids))
    np.testing.assert_allclose(np.asarray(got._data), ref, atol=TOL, rtol=0)
    for mine, theirs in zip(kept, ref_kept):
        assert np.array_equal(np.asarray(mine._data), theirs)
    # the axes count: the rows' own numbers on every axis read far from it
    assert np.abs(np.asarray(plain._data) - ref).max() > 100 * TOL


def test_equal_axes_are_one_axis_rotary():
    cos, sin, icos, isin = FAMILY.position_tables(40, CFG)
    t = np.arange(40, dtype=np.float64)[:, None]
    inv = 1e7 ** (-np.arange(8, dtype=np.float64) * 2.0 / 16)
    np.testing.assert_allclose(cos, np.cos(t * inv), atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(t * inv), atol=1e-6)
    inv_i = 1e7 ** (-np.arange(4, dtype=np.float64) * 2.0 / 8)
    np.testing.assert_allclose(icos, np.cos(t * inv_i), atol=1e-6)
    np.testing.assert_allclose(isin, np.sin(t * inv_i), atol=1e-6)
    model = build()
    ids = np.random.default_rng(6).integers(0, 128, (1, 40))
    with paddle.no_grad():
        plain = model(paddle.to_tensor(ids))
        equal = model(paddle.to_tensor(ids), position_ids=paddle.to_tensor(
            np.tile(np.arange(40), (3, 1))))
    assert np.array_equal(np.asarray(plain._data), np.asarray(equal._data))
    assert list(keye.axis_of_pair((2, 3, 3))) == [0, 0, 1, 1, 1, 2, 2, 2]
    assert list(FAMILY.axis_of_pair(CFG)) == [0, 0, 1, 1, 1, 2, 2, 2]


def test_the_reference_finds_the_kth_largest_without_a_sort():
    """``kth_largest`` against numpy's sort, over zeros of both signs,
    infinities and ties; ``kept_rows`` against the family ``glm_dsa``'s
    ``selected`` (``lax.top_k``), from queries that see fewer rows than
    ``topk`` to the last, on scores full of ties."""
    from chipbench.families.glm_dsa import selected
    rng = np.random.default_rng(11)
    x = rng.standard_normal((24, 1000)).astype(np.float32)
    x[:, ::7] = 0.0
    x[3, :500] = -np.inf
    x[4] = np.round(x[4])
    x[5, ::3] = -0.0
    x[6, 1::5] = np.inf
    for k in (1, 5, 100, 999, 1000):
        assert np.array_equal(np.asarray(FAMILY.kth_largest(jnp.asarray(x),
                                                            k)),
                              np.sort(x, -1)[:, -k])
    scores = jnp.asarray(np.round(2 * x[:, :900]))
    for qpos in (np.arange(24), np.arange(90, 114), np.arange(876, 900)):
        for topk in (1, 16, 100):
            assert np.array_equal(
                np.asarray(FAMILY.kept_rows(scores, jnp.asarray(qpos),
                                            topk)),
                np.asarray(selected(scores, jnp.asarray(qpos), topk)))


@pytest.mark.parametrize("room", [4, 1])
def test_the_reference_saves_work_and_changes_no_number(room, monkeypatch):
    """What lets the cell's reference end inside a run's time limit: a block
    of queries given the rows up to its span's end alone, and an expert
    over the rows that chose it, gathered (room 1: an expert's even share,
    so that the fuller ones take the every-row side). Held against one span
    of every row and the family ``mellum``'s every expert over every row:
    the same rows kept, the same output."""
    from chipbench.families import mellum
    ids = np.random.default_rng(5).integers(0, 128, 128 * 9 + 37)
    tables = FAMILY.position_tables(len(ids), CFG)
    x = FAMILY.embed_tokens(jnp.asarray(ids), R._f32(W.make_top(CFG, SEED)),
                            CFG)
    w = R._f32(W.make_layer(CFG, SEED, 0))
    monkeypatch.setattr(FAMILY, "_EXPERT_ROOM", room)
    got, kept = FAMILY.layer_forward(x, w, tables, CFG, 0,
                                     return_selection=True)
    monkeypatch.setattr(FAMILY, "_KEY_SPANS", 1)
    monkeypatch.setattr(FAMILY, "experts_by_rows", mellum.experts)
    plain, plain_kept = FAMILY.layer_forward(x, w, tables, CFG, 0,
                                             return_selection=True)
    assert np.array_equal(np.asarray(kept), np.asarray(plain_kept))
    assert int(np.asarray(kept)[-1].sum()) == CFG["sa_config"]["topk"]
    # float32 sums in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), atol=TOL)


def test_forward_gives_a_loss_and_the_tiny_config_is_the_tested_shape():
    model = build()
    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with paddle.no_grad():
        logits, loss = model(paddle.to_tensor(ids[:, :-1]),
                             labels=paddle.to_tensor(ids[:, 1:]))
    assert logits.shape == [2, 11, 128]
    assert 3.0 < float(loss) < 8.0
    assert model.num_params() == sum(
        int(np.prod(s)) for i in range(3)
        for s in FAMILY.layer_shapes(CFG, i).values()) + sum(
        int(np.prod(s)) for s in FAMILY.top_shapes(CFG).values())
    c = keye_tiny_config()
    assert (c.num_hidden_layers, c.num_experts, c.num_experts_per_tok,
            c.index_topk, c.mrope_section) == (3, 8, 2, 16, (2, 3, 3))
    with pytest.raises(ValueError, match="mrope_section"):
        KeyeForCausalLM(KeyeConfig(**dict(c.__dict__,
                                          mrope_section=(2, 3, 2))))
    with pytest.raises(ValueError, match="index_rope_dim"):
        KeyeForCausalLM(KeyeConfig(**dict(c.__dict__, index_rope_dim=24)))


def test_one_selection_serves_both_families():
    """``glm_dsa`` and ``keye`` run ``dsa_select``'s functions, not copies:
    ``tests/test_glm_dsa.py`` holds them to ``lax.top_k`` through the names
    ``glm_dsa`` had."""
    for name in ("select_rows", "select_indices", "kth_largest_bits",
                 "_order_bits", "_select"):
        assert getattr(glm_dsa, name) is getattr(dsa_select, name)
    assert glm_dsa._index_scores is dsa_select.index_scores
    for name in ("select_rows", "select_indices", "index_scores"):
        assert getattr(keye, name) is getattr(dsa_select, name)


# -- the served path ----------------------------------------------------------

@pytest.mark.parametrize("compiled", [False, True])
def test_chunks_and_decode_match_the_reference_at_every_served_row(compiled):
    """A document of 48 rows asked three times (two hits of its 6 pages),
    a prompt of 7 rows that stays under ``topk`` while the others are past
    it, three slots side by side at different lengths, chunks of 16 over
    pages of 8, held rows read 32 at a time."""
    model = build()
    prompts, news = document_prompts(), [12, 10, 20, 14]
    seqs, rows, b = serve(model, prompts, news, compile=compiled)
    assert b.prefix_cache.stats()["hit_tokens"] == 96
    assert b.stats()["kv_writer"] == "page"
    assert b.stats()["decode_attention_path"] == "dsa=gather"
    b.close()
    for got, ref in zip(rows, reference_rows(seqs, prompts)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def test_the_paged_path_takes_the_positions_it_is_given():
    """Chunks of 16 and two decode steps at positions whose axes differ,
    through the model's paged entries as the batcher calls them: a row's
    angle is what ``position_ids`` says, not its row number."""
    model = build()
    ids = np.random.default_rng(8).integers(0, 128, 50)
    pos = image_positions(50)
    ref, _ = walk(ids[None], positions=pos)
    cache = model.paged_alloc(9, 8)
    table = paddle.to_tensor(np.arange(1, 9, dtype=np.int32)[None])

    def ints(*v):
        return paddle.to_tensor(np.array(v, np.int32))

    with paddle.no_grad():
        for dec in (0, 16, 32):
            logits, cache = model.paged_prefill_into(
                paddle.to_tensor(ids[None, dec:dec + 16]), cache, table, 8,
                dec_base=ints(dec), logits_at=ints(15), n_valid=ints(16),
                position_ids=paddle.to_tensor(pos[:, dec:dec + 16]))
        np.testing.assert_allclose(np.asarray(logits._data)[0], ref[0, 47],
                                   atol=TOL, rtol=0)
        state = {"layers": cache, "block_tables": table,
                 "dec_lens": ints(48)}
        for row in (48, 49):
            state["position_ids"] = paddle.to_tensor(pos[:, row:row + 1])
            logits, state = model.paged_decode_step(
                paddle.to_tensor(ids[row:row + 1]), state)
            np.testing.assert_allclose(np.asarray(logits._data)[0],
                                       ref[0, row], atol=TOL, rtol=0)


def _poison(batcher, pages):
    """Huge K, V and index keys on ``pages`` of every layer: a row of
    theirs that were scored would win every selection."""
    cache = batcher._state["layers"]
    pools = [t for kv in cache["kv"] for t in kv] + list(cache["index"])
    for t in pools:
        t._data = t._data.at[np.asarray(pages)].set(1e4)


def test_rows_of_other_slots_pad_rows_and_freed_pages_are_never_selected():
    model = build()
    rng = np.random.default_rng(9)
    b = PagedContinuousBatcher(model, **dict(SERVER, prefix_cache=False))
    serve(model, [rng.integers(0, 128, 50)], [20], batcher=b)   # and freed
    assert b.free_page_count == 48
    _poison(b, list(range(48)) + [48])                  # free pages, scratch
    prompts = [rng.integers(0, 128, n) for n in (41, 19, 33)]   # pad rows
    seqs, rows, _ = serve(model, prompts, [18, 25, 9], batcher=b)
    b.close()
    for got, ref in zip(rows, reference_rows(seqs, prompts)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def _read(name, **labels):
    from paddle_tpu.observability.metrics import get_registry
    entry = get_registry().get(name)
    if entry is None:
        return 0
    return (entry.labels(**labels) if labels else entry).value


_SERIES = ("serving.dsa_rows_scored_total", "serving.dsa_rows_selected_total",
           "serving.moe_assignments_total",
           "serving.moe_assignments_local_total")


def _phased():
    return {(n, ph): _read(n, phase=ph) for n in _SERIES
            for ph in ("decode", "prefill")}


def test_the_steps_count_what_they_did_and_read_the_kept_rows_only():
    before, touched = _phased(), _read("serving.moe_experts_touched_total")
    model = build()
    prompt = [np.random.default_rng(2).integers(0, 128, 20)]
    _, _, b = serve(model, prompt, [5], prefix_cache=False)
    b.close()
    got = {k: v - before[k] for k, v in _phased().items()}
    scored, selected, assigned, local = _SERIES
    # counted on the device, a step's in its one fetch. A chunk of 16 and
    # one of 4 real rows (its 12 pad rows count nowhere): query t scores
    # t + 1 rows in each of 3 layers; 4 decode steps at 21..24 rows, each
    # past topk: every held row's index key scored, K and V of 16 read
    assert got[scored, "prefill"] == 3 * sum(range(1, 21))
    assert got[selected, "prefill"] == 3 * (sum(range(1, 17)) + 4 * 16)
    assert got[scored, "decode"] == 3 * (21 + 22 + 23 + 24)
    assert got[selected, "decode"] == 3 * 4 * 16
    # 3 layers, 2 experts a token, all held: 20 rows of chunks, 4 of steps
    assert got[assigned, "prefill"] == got[local, "prefill"] == 20 * 2 * 3
    assert got[assigned, "decode"] == got[local, "decode"] == 4 * 2 * 3
    assert 0 < _read("serving.moe_experts_touched_total") - touched <= 4 * 6
    assert _read("serving.kv_cache_bytes", group="full") \
        == 3 * 2 * 49 * 2 * 8 * 16 * 4
    assert _read("serving.index_key_cache_bytes") == 3 * 49 * 8 * 128 * 4


def test_while_no_slot_is_past_topk_every_held_row_is_kept():
    before = _phased()
    model = build()
    rng = np.random.default_rng(12)
    prompts = [rng.integers(0, 128, n) for n in (7, 10)]
    seqs, rows, b = serve(model, prompts, [5, 4], prefix_cache=False)
    b.close()
    for got, ref in zip(rows, reference_rows(seqs, prompts)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)
    got = {k: v - before[k] for k, v in _phased().items()}
    # rows held at each decode step: 8..11 and 11..13, in 3 layers: each
    # scored, and each read
    held = 3 * (8 + 9 + 10 + 11 + 11 + 12 + 13)
    assert got[_SERIES[0], "decode"] == got[_SERIES[1], "decode"] == held


@pytest.mark.parametrize("family", ["keye", "glm_dsa"])
def test_chunk_counts_pass_two_to_the_32_between_two_decode_steps(family):
    """``step_counts``' chunk half is an int32 running sum on the device;
    the batcher sets it aside as every admission leaves it, reads those
    with the next decode step's fetch (an admission waits for nothing
    more than its token) and takes differences modulo 2^32. Started near
    the wrap, two admissions and the decode steps
    behind them count what they scored; and four admissions of 1.2e9 pairs
    each (49,152-token documents) inside one gateway step, 4.8e9 in all,
    are counted whole, where one difference over all four wrapped."""
    if family == "keye":
        model, scored_of = build(), lambda n: 3 * n * (n + 1) // 2
    else:
        from test_glm_dsa import build as build_glm
        model, scored_of = build_glm(), lambda n: 4 * n * (n + 1) // 2
    b = PagedContinuousBatcher(model, **dict(SERVER, prefix_cache=False))
    counts = b._state["layers"]["step_counts"]
    near = np.iinfo(np.int32).max - 100      # int32 wraps inside a chunk
    counts._data = counts._data.at[1, :, 4].set(near)
    b._chunk_counts_seen = np.asarray(counts._data)[1].astype(np.int64)
    before = _read(_SERIES[0], phase="prefill")
    rng = np.random.default_rng(3)
    for n in (40, 25):
        b.submit(rng.integers(0, 128, n), 3)
    with paddle.no_grad():
        b.run_until_done()
    assert np.asarray(b._state["layers"]["step_counts"]._data)[1, 0, 4] < 0
    assert b._chunk_counts_due == []
    assert _read(_SERIES[0], phase="prefill") - before \
        == scored_of(40) + scored_of(25)
    layers = counts.shape[1]
    seen = b._chunk_counts_seen.copy()
    one = 49152 * 49153 // 2
    for k in range(1, 5):                       # the device's sum, wrapped
        total = seen + k * one * (np.arange(6) == 4)
        b._add_chunk_counts(((total + 2 ** 31) % 2 ** 32 - 2 ** 31)
                            .astype(np.int32))
    assert _read(_SERIES[0], phase="prefill") - before \
        == scored_of(40) + scored_of(25) + 4 * one * layers
    assert 4 * one > 2 ** 32
    b.close()


def test_pages_of_all_three_pools_are_clean_after_release():
    model = build()
    seqs, _, b = serve(model, document_prompts(), [6, 6, 6, 6])
    cached = b.prefix_cache.stats()["cached_pages"]
    assert cached > 0 and b.free_page_count == 48 - cached
    b._evict_cache_pages(cached)
    assert b.free_page_count == 48 and b.audit_pages() == 0
    cache = b._state["layers"]
    assert len(cache["kv"]) == len(cache["index"]) == 3
    assert cache["kv"][0][0].shape == cache["kv"][0][1].shape \
        == [49, 2, 8, 16]
    # the index key is held whole lanes wide
    assert cache["index"][0].shape == [49, 1, 8, 128]
    assert keye.key_width(64) == keye.key_width(16) == 128
    b.close()


@pytest.mark.parametrize("option,value", [
    ("kv_quant", "int8"), ("cache_quant", "dynamic_int8"),
    ("tier_quant", "int8"), ("draft_model", object()),
    ("session_store", "/tmp/none"), ("host_kv_gib", 0.5),
    ("disk_kv_dir", "/tmp/none")])
def test_the_contract_refuses_by_name(option, value):
    with pytest.raises(ValueError, match=f"{option} is not supported for "
                                         f"KeyeForCausalLM"):
        PagedContinuousBatcher(build(), **dict(SERVER, **{option: value}))


def test_the_batcher_says_the_positions_and_only_where_the_contract_asks():
    model = build()
    assert model.paged_serving_contract()["position_axes"] == 3
    b = PagedContinuousBatcher(model, **SERVER)
    args = b._slot_args(1, 5, first_row=32, rows=16)
    assert np.array_equal(np.asarray(args["position_ids"]._data),
                          np.tile(np.arange(32, 48), (3, 1)))
    b._dec[:] = [7, 0, 21]
    b._sync_tables()
    assert np.array_equal(np.asarray(b._state["position_ids"]._data),
                          np.tile([7, 0, 21], (3, 1)))
    b.close()
    from test_glm_dsa import build as build_glm
    other = PagedContinuousBatcher(build_glm(), **SERVER)
    assert "position_ids" not in other._slot_args(1, 5, 32, 16)
    other._sync_tables()
    assert "position_ids" not in other._state
    other.close()


def test_a_document_of_three_thousand_blocks_is_no_deeper_than_the_walks():
    """A 49,152-token document and its question are a chain of 3,088 blocks
    of 16: admission's page gate walks it (``evictable_pages``), eviction
    frees it leaf by leaf, and Python recurses a thousand deep at most."""
    from paddle_tpu.inference.prefix_cache import RadixPrefixCache
    cache = RadixPrefixCache(16)
    tokens = np.random.default_rng(0).integers(0, 151936, 49152 + 256)
    nodes = cache.insert(tokens, list(range(4096)), 0, len(tokens) // 16)
    assert len(nodes) == 3088 and cache.evictable_pages() == 0   # pinned
    cache.unpin(nodes)
    assert cache.evictable_pages() == 3088
    held = cache.match(tokens, max_blocks=3072)
    cache.pin(held)                      # a hit holds the document's part
    assert cache.evictable_pages() == 16
    assert sorted(cache.evict(10 ** 6)) == list(range(3072, 3088))
    cache.unpin(held)
    assert cache.evictable_pages() == len(cache.evict(10 ** 6)) == 3072
