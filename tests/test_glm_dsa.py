"""GLM-5's architecture (``models/glm_dsa.py``: latent attention that reads
only the rows a learned indexer keeps, routed experts of which a chip holds
a share) against the repo's plain reference for it,
``chipbench/families/glm_dsa.py``: the whole forward, the rows selected, and
the served path (``PagedContinuousBatcher``: chunked prefill, decode, a
prefix-cache hit, slots side by side) at a small size of the same shape on
seeded weights: 1 dense + 3 expert layers, 16 experts of which 4 a token,
``index_topk`` 16 against sequences of 64 and more.

Tolerance, and why: float32 model against the float32 reference, 1e-4 on
logits of a few units. Both compute the same sums in another order (chunks,
blocks of held rows with a running softmax, the absorbed form, experts
group by group); nothing else differs, and the rows selected are the same
sets exactly (two index heads make exact ties at the 16th score common
here: both sides keep the lower rows of a tie first). The omission tests
hold that leaving out the selection or the routed branch moves the logits
by 100 times that tolerance or more.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:       # chipbench lies beside tests/, at the root
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from chipbench import families, reference as R, weights as W  # noqa: E402
from paddle_tpu.inference.serving import PagedContinuousBatcher  # noqa: E402
from paddle_tpu.models import (GlmDsaConfig, GlmDsaForCausalLM,  # noqa: E402
                               glm_dsa_tiny_config)
from paddle_tpu.models import glm_dsa as G  # noqa: E402

CFG = dict(family="glm_dsa", hidden_size=64, intermediate_size=96,
           moe_intermediate_size=32, num_hidden_layers=4,
           first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
           v_head_dim=8, index_n_heads=2, index_head_dim=16, index_topk=16,
           n_routed_experts=16, router_width=16, num_experts_per_tok=4,
           n_shared_experts=1, routed_scaling_factor=2.5, rms_norm_eps=1e-5,
           rope_parameters={"rope_theta": 1e6}, vocab_size=128,
           max_position_embeddings=512, initializer_range=0.1,
           tie_word_embeddings=False, prefill_key_block=32)
FAMILY = families.of(CFG)
TOL = 1e-4
SEED = 3


@functools.lru_cache(maxsize=None)
def build(held=(0, 16)):
    """One model a share for the whole file: serving changes nothing of
    it."""
    cfg = dict(CFG, experts_held_start=held[0], n_routed_experts=held[1])
    paddle.seed(0)
    model = FAMILY.program_model(cfg, dtype="float32")
    model.eval()
    W.install(model, cfg, SEED, scanned=False)
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


def reference_rows(seqs, prompts, cfg=CFG):
    width = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), width), np.int64)
    rows = []
    for i, (seq, prompt) in enumerate(zip(seqs, prompts)):
        ids[i, :len(seq)] = seq
        rows.append(list(range(len(prompt) - 1, len(seq) - 1)))
    return R.served_logits(cfg, SEED, ids, rows)


def walk(ids, cfg=CFG, **kw):
    """The reference's full forward over ids [N, T] with ``layer_forward``'s
    own switches: (logits [N, T, V], the masks of rows kept [layer, N])."""
    tables = FAMILY.position_tables(ids.shape[1], cfg)
    top = R._f32(W.make_top(cfg, SEED))
    hidden = FAMILY.embed_tokens(jnp.asarray(ids), top, cfg)
    kept = []
    for i in range(cfg["num_hidden_layers"]):
        w = R._f32(W.make_layer(cfg, SEED, i))
        out = [FAMILY.layer_forward(x, w, tables, cfg, i,
                                    return_selection=True, **kw)
               for x in hidden]
        hidden = jnp.stack([o[0] for o in out])
        kept.append(np.stack([np.asarray(o[1]) for o in out]))
    return np.stack([np.asarray(FAMILY.head_logits(x, top, cfg))
                     for x in hidden]), kept


def document_prompts():
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 128, 48)
    return [np.concatenate([doc, rng.integers(0, 128, n)])
            for n in (5, 9, 30)] + [rng.integers(0, 128, 7)]


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
        # the rule: every row up to index_topk, exactly index_topk after
        assert np.array_equal(mine.sum(-1)[0],
                              np.minimum(np.arange(80) + 1, 16))
    served = R.served_logits(CFG, SEED, ids, [list(range(80))] * 2)
    np.testing.assert_allclose(np.stack(served), ref, atol=1e-5, rtol=0)


def test_forward_gives_a_loss():
    model = build()
    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    with paddle.no_grad():
        logits, loss = model(paddle.to_tensor(ids[:, :-1]),
                             labels=paddle.to_tensor(ids[:, 1:]))
    assert logits.shape == [2, 11, 128]
    assert 3.0 < float(loss) < 8.0
    assert model.num_params() == sum(
        int(np.prod(s)) for i in range(4)
        for s in FAMILY.layer_shapes(CFG, i).values()) + sum(
        int(np.prod(s)) for s in FAMILY.top_shapes(CFG).values())


def test_tiny_config_is_the_tested_shape():
    c = glm_dsa_tiny_config()
    assert (c.num_hidden_layers, c.first_k_dense_replace, c.moe_layers) \
        == (4, 1, 3)
    assert (c.n_routed_experts, c.num_experts_per_tok, c.index_topk) \
        == (16, 4, 16)
    assert GlmDsaForCausalLM(c).config.latent_width == 24
    with pytest.raises(ValueError, match="experts held"):
        GlmDsaForCausalLM(GlmDsaConfig(**dict(
            glm_dsa_tiny_config().__dict__, experts_held_start=8,
            experts_held_count=16)))


# -- selection ----------------------------------------------------------------

@pytest.mark.parametrize("width", [64, 128, 300])
def test_selection_is_top_k_with_the_lower_rows_of_a_tie_first(width):
    rng = np.random.default_rng(width)
    scores = rng.normal(size=(5, width)).astype(np.float32)
    scores[1, ::3] = 0.25            # a tie that straddles the 16th place
    scores[2] = np.round(scores[2])   # few values: ties everywhere
    lens = np.array([3, width - 1, width - 1, 40, 15])
    valid = np.arange(width)[None, :] <= lens[:, None]
    keep = np.asarray(G.select_rows(jnp.asarray(scores), jnp.asarray(valid),
                                    16))
    rows, kept = map(np.asarray, G.select_indices(
        jnp.asarray(scores), jnp.asarray(valid), 16))
    _, best = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), 16)
    for i in range(5):
        want = sorted(int(j) for j in np.asarray(best[i]) if valid[i, j])
        assert sorted(np.nonzero(keep[i])[0]) == want, i
        assert list(rows[i][kept[i]]) == want, i
        assert kept[i].sum() == min(16, lens[i] + 1)


@pytest.mark.parametrize("digit", [1, 2, 4])
def test_kth_largest_needs_no_sort(digit):
    x = np.random.default_rng(0).normal(size=(7, 200)).astype(np.float32)
    x[3, :50] = x[3, 50]                         # a tie across the place
    bits = G._order_bits(jnp.asarray(x))
    got = np.asarray(G.kth_largest_bits(bits, 9, digit))
    want = np.asarray(G._order_bits(jnp.asarray(np.sort(x, -1)[:, -9])))
    assert np.array_equal(got, want)
    none = G.kth_largest_bits(jnp.zeros((2, 40), jnp.uint32), 9, digit)
    assert not np.asarray(none).any()
    assert np.all(np.diff(np.asarray(G._order_bits(
        jnp.asarray([-np.inf, -2.0, -0.0, 0.0, 1e-30, 3.0, np.inf],
                    np.float32))).astype(np.int64)) >= 0)


def test_a_run_of_rows_is_written_by_the_page():
    pool = jnp.zeros((10, 1, 4, 3), jnp.float32)
    table = jnp.asarray([7, 2, 5, 9, 9, 9], jnp.int32)    # 9: scratch
    run = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3) + 1
    out = np.asarray(G._write_run(pool, table, jnp.int32(4), run))
    line = out[np.asarray(table[:3])].reshape(12, 3)
    assert np.array_equal(line[4:12], np.asarray(run))
    assert not line[:4].any() and not out[[0, 1, 3, 4, 6, 8]].any()
    # a run that ends with the table: the window is clamped, not the run
    out = np.asarray(G._write_run(pool, table[:3], jnp.int32(4), run))
    assert np.array_equal(out[[2, 5]].reshape(8, 3), np.asarray(run))


# -- the served path ----------------------------------------------------------

@pytest.mark.parametrize("compiled", [False, True])
def test_chunks_and_decode_match_the_reference_at_every_served_row(compiled):
    """A document of 48 rows asked three times (two hits of its 6 pages),
    a prompt of 7 rows that stays under ``index_topk``, three slots side by
    side, chunks of 16 over pages of 8, held rows read 32 at a time."""
    model = build()
    prompts, news = document_prompts(), [12, 10, 20, 14]
    seqs, rows, b = serve(model, prompts, news, compile=compiled)
    assert b.prefix_cache.stats()["hit_tokens"] == 96
    assert b.stats()["kv_writer"] == "page"
    assert b.stats()["decode_attention_path"] == "dsa=gather"
    b.close()
    for got, ref in zip(rows, reference_rows(seqs, prompts)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def test_absorbed_decode_equals_expanded_prefill():
    """The server attends in the absorbed form (the key half of W_kvb in
    the query, the value half in the output), ``forward`` in the expanded
    one: one model, the same logits."""
    model = build()
    prompts = [np.random.default_rng(5).integers(0, 128, 37)]
    seqs, rows, b = serve(model, prompts, [30], prefix_cache=False)
    b.close()
    with paddle.no_grad():
        full = np.asarray(model(paddle.to_tensor(seqs[0][None]))._data)[0]
    np.testing.assert_allclose(rows[0], full[36:-1], atol=TOL, rtol=0)


def _poison(batcher, pages):
    """Huge latent rows and index keys on ``pages`` of every layer: a row
    of theirs that were scored would win every selection."""
    cache = batcher._state["layers"]
    for pools in (cache["latent"], cache["index"]):
        for t in pools:
            t._data = t._data.at[np.asarray(pages)].set(1e4)


def test_rows_of_other_slots_pad_rows_and_freed_pages_are_never_selected():
    model = build()
    rng = np.random.default_rng(9)
    b = PagedContinuousBatcher(model, **dict(SERVER, prefix_cache=False))
    first = [rng.integers(0, 128, 50)]
    serve(model, first, [20], batcher=b)                # ran and was freed
    assert b.free_page_count == 48
    _poison(b, list(range(48)) + [48])                  # free pages, scratch
    prompts = [rng.integers(0, 128, n) for n in (41, 19, 33)]   # pad rows
    seqs, rows, _ = serve(model, prompts, [18, 25, 9], batcher=b)
    b.close()
    for got, ref in zip(rows, reference_rows(seqs, prompts)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def test_pages_of_both_pools_are_clean_after_release():
    model = build()
    seqs, _, b = serve(model, document_prompts(), [6, 6, 6, 6])
    cached = b.prefix_cache.stats()["cached_pages"]
    assert cached > 0 and b.free_page_count == 48 - cached
    b._evict_cache_pages(cached)
    assert b.free_page_count == 48 and b.audit_pages() == 0
    cache = b._state["layers"]
    assert len(cache["latent"]) == len(cache["index"]) == 4
    assert cache["latent"][0].shape == [49, 1, 8, 24]
    assert cache["index"][0].shape == [49, 1, 8, 16]
    b.close()


@pytest.mark.parametrize("option,value", [
    ("kv_quant", "int8"), ("cache_quant", "dynamic_int8"),
    ("tier_quant", "int8"), ("draft_model", object()),
    ("session_store", "/tmp/none"), ("host_kv_gib", 0.5),
    ("disk_kv_dir", "/tmp/none")])
def test_the_contract_refuses_by_name(option, value):
    with pytest.raises(ValueError, match=f"{option} is not supported for "
                                         f"GlmDsaForCausalLM"):
        PagedContinuousBatcher(build(), **dict(SERVER, **{option: value}))


def test_the_steps_count_what_they_did():
    from paddle_tpu.observability.metrics import get_registry
    reg = get_registry()

    def read(name, **labels):
        entry = reg.get(name)
        if entry is None:
            return 0
        return (entry.labels(**labels) if labels else entry).value

    names = ("serving.dsa_rows_scored_total",
             "serving.dsa_rows_selected_total",
             "serving.moe_assignments_total",
             "serving.moe_assignments_local_total")
    before = {(n, ph): read(n, phase=ph) for n in names
              for ph in ("decode", "prefill")}
    touched = read("serving.moe_experts_touched_total")
    model = build(held=(4, 4))
    prompt = [np.random.default_rng(2).integers(0, 128, 20)]
    _, _, b = serve(model, prompt, [5], prefix_cache=False)
    b.close()
    got = {k: read(k[0], phase=k[1]) - v for k, v in before.items()}
    # counted on the device, a step's in its one fetch. A chunk of 16 and
    # one of 4 real rows (its 12 pad rows count nowhere): query t scores
    # t + 1 rows in each of 4 layers; 4 decode steps at 21..24 rows
    assert got[names[0], "prefill"] == 4 * sum(range(1, 21))
    assert got[names[1], "prefill"] == 4 * (sum(range(1, 17)) + 4 * 16)
    assert got[names[0], "decode"] == 4 * (21 + 22 + 23 + 24)
    assert got[names[1], "decode"] == 4 * 4 * 16
    # 3 expert layers, 4 experts a token: 20 rows of chunks, 4 of steps
    assert got[names[2], "prefill"] == 20 * 4 * 3
    assert got[names[2], "decode"] == 4 * 4 * 3
    assert 0 < got[names[3], "prefill"] < got[names[2], "prefill"]
    assert 0 < got[names[3], "decode"] < got[names[2], "decode"]
    assert 0 < read("serving.moe_experts_touched_total") - touched <= 4 * 12
    assert read("serving.latent_cache_bytes") == 4 * 49 * 8 * 4 * (24 + 16)


def test_the_cache_gauge_reads_the_pools_as_allocated():
    """A latent row wider than one lane of 128 is held whole lanes wide
    (576 at the published widths is held 640 wide): the gauge says what
    the device holds, not what the mathematics requires."""
    from paddle_tpu.observability.metrics import get_registry
    assert G.lane_width(576) == 640 and G.lane_width(24) == 24
    model = GlmDsaForCausalLM(glm_dsa_tiny_config(
        num_hidden_layers=2, kv_lora_rank=136))
    cache = model.paged_alloc(5, 8)
    assert cache["latent"][0].shape == [5, 1, 8, 256]
    assert get_registry().get("serving.latent_cache_bytes").value \
        == 2 * 5 * 8 * 4 * (256 + 16)


# -- what the cell's lengths asked of shared code -----------------------------

def test_a_document_of_a_thousand_blocks_is_no_deeper_than_the_cache_walks():
    """A 24,576-token document is a chain of 1,536 blocks of 16: admission's
    page gate walks it (``evictable_pages``), and Python recurses a thousand
    deep at most."""
    from paddle_tpu.inference.prefix_cache import RadixPrefixCache
    cache = RadixPrefixCache(16)
    tokens = np.random.default_rng(0).integers(0, 19360, 24576 + 100)
    nodes = cache.insert(tokens, list(range(2000)), 0, len(tokens) // 16)
    assert len(nodes) == 1542 and cache.evictable_pages() == 0   # pinned
    cache.unpin(nodes)
    assert cache.evictable_pages() == 1542
    held = cache.match(tokens, max_blocks=1000)
    cache.pin(held)                      # a hit holds the chain's first part
    assert cache.evictable_pages() == 542
    assert sorted(cache.evict(10 ** 6)) == list(range(1000, 1542))
    cache.unpin(held)
    assert cache.evictable_pages() == len(cache.evict(10 ** 6)) == 1000


def test_a_finished_session_does_not_keep_the_server_on_the_device():
    """A client that keeps its streaming session after the request has
    finished (the benchmark's load generator does) held the gateway through
    it, and with it the replica's model and page pools: 9.7 GB that the
    plain reference of this cell needs for itself."""
    import gc
    import weakref
    from paddle_tpu.inference.gateway import Gateway
    batcher = PagedContinuousBatcher(build(), **SERVER)
    gateway = Gateway()
    gateway.add_replica("chip0", batcher)
    session = gateway.stream(np.arange(20) % 128, 4)
    with paddle.no_grad():
        tokens = list(session)
    assert len(tokens) == 4 and session.done
    assert len(gateway.pop_result(session.gid)) == 24   # lets the session go
    alive = weakref.ref(gateway), weakref.ref(batcher)
    batcher.close()
    del gateway, batcher
    gc.collect()
    assert alive[0]() is None and alive[1]() is None
    assert session.read_available() == [] and session.done
    assert list(session) == []
    session.close()                                  # still closes
