"""The page-granular K/V writer of the paged batcher: no two running
sequences write one page, under the prefix cache, on-demand growth with
preemption and chunked prefill; tokens equal the row-scatter route's and
the solo reference's; which writer a pool and a family get. (The batcher's
scheduling is held in tests/test_paged_batching.py.)"""
import numpy as np
import pytest

from paddle_tpu.inference.serving import PagedContinuousBatcher

from test_paged_batching import _llama, _model, _ref


# -- the page-granular K/V writer's invariant ------------------------------

def _kv_write_launches(engine="paged"):
    from paddle_tpu.observability.metrics import get_registry
    family = get_registry().get("serving_kv_write_launches_total")
    return {w: family.labels(engine=engine, writer=w).value
            for w in ("page", "row")}


def _watch_written_pages(b):
    """Wrap the batcher's decode launches: before each one, the page each
    running slot is about to write a row of must be backed, pairwise
    distinct, in no other slot's table and not the prefix cache's. Returns
    the list the launches are logged to (the running slots of each)."""
    seen = []

    def check():
        live = sorted(b._slot_req)
        cached = set(b.prefix_cache.pages()) if b.prefix_cache else set()
        for slot in live:
            page = int(b._bt[slot, int(b._dec[slot]) // b.block_size])
            assert page != b._scratch, f"slot {slot} writes no backed page"
            assert page not in cached, (slot, page)
            for other in range(b.max_batch):
                if other != slot:
                    assert page not in b._bt[other], (slot, other, page)
        # parked slots name nothing but scratch
        for slot in set(range(b.max_batch)) - set(live):
            assert set(int(p) for p in b._bt[slot]) == {b._scratch}
        seen.append(len(live))

    step_fn = b._step_fn

    def launch(tok, state):
        check()
        return step_fn(tok, state)

    b._step_fn = launch
    return seen


def _row_scatter_route(monkeypatch):
    """Put the Llama family back on the row scatter, decode step and chunk:
    what the page writers' tokens are held against."""
    import jax.numpy as jnp

    from paddle_tpu.incubate.nn.functional import decode_attention as da

    def row_run(pool, table, line, dec, run):
        rows = dec + jnp.arange(run.shape[0])
        block = pool.shape[2]
        pool = pool.at[table[rows // block], :, rows % block].set(
            run.astype(pool.dtype))
        return pool, da._gather_paged(pool, pool, table[None],
                                      pool.shape[1])[0][0]
    monkeypatch.setattr(da, "decode_kv_writer", lambda dtype: "row")
    monkeypatch.setattr(da, "_write_page_run", row_run)


# documents of whole and part pages, each asked several times with another
# question behind it; block_size 4
def _document_sessions(rng, n_docs=2, asks=3):
    docs = [rng.randint(0, 128, (n,)) for n in (16, 22)[:n_docs]]
    return [np.concatenate([docs[i % n_docs], rng.randint(0, 128, (q,))])
            for i, q in enumerate(rng.randint(1, 6, (n_docs * asks,)))]


@pytest.mark.parametrize("options", [
    dict(), dict(policy="ondemand", n_pages=13), dict(prefill_chunk=8)],
    ids=["reserve", "ondemand_preempting", "chunked"])
def test_no_two_sequences_write_one_page(options, monkeypatch):
    """The page writer's invariant, held under the prefix cache: documents
    asked several times share their FULL pages, and at every decode launch
    the pages the running slots write are pairwise distinct, in nobody
    else's table and not the cache's. Tokens equal the row-scatter route's
    and the solo reference's; ``audit_pages()`` is clean; every launch is
    counted under ``writer="page"``."""
    m = _llama()
    prompts = _document_sessions(np.random.RandomState(11))
    budgets = [7, 5, 9, 6, 8, 5]
    kw = dict(max_batch=3, s_max=48, block_size=4, compile=False,
              prefix_cache=True, **options)

    def serve(watch):
        b = PagedContinuousBatcher(m, **kw)
        seen = _watch_written_pages(b) if watch else None
        before = _kv_write_launches()
        rids = [b.submit(p, n) for p, n in zip(prompts, budgets)]
        outs = b.run_until_done()
        assert b.audit_pages() == 0
        s = dict(b.stats(), hit_tokens=b.prefix_cache.hit_tokens)
        counted = {w: n - before[w]
                   for w, n in _kv_write_launches().items()}
        b.close()
        return [outs[r] for r in rids], s, counted, seen

    got, s, counted, seen = serve(watch=True)
    assert s["kv_writer"] == "page" and counted["row"] == 0
    assert counted["page"] == len(seen) > 0
    assert s["hit_tokens"] > 0, "no page was shared"
    assert max(seen) > 1, "no two sequences ever ran together"
    assert counted["page"] == s["steps"]
    if options.get("policy") == "ondemand":
        assert s["preemptions"] > 0, "the pool never ran dry"
    for p, n, out in zip(prompts, budgets, got):
        np.testing.assert_array_equal(out, _ref(m, p, n))

    _row_scatter_route(monkeypatch)
    want, s, counted, _ = serve(watch=False)
    assert s["kv_writer"] == "row" and counted["page"] == 0
    assert counted["row"] > 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_an_int8_pool_keeps_the_row_scatter():
    """``cache_quant`` allocates int8 pools: rows are quantized on the way
    in by the general op, and the launches are counted under ``row``."""
    m = _llama()
    rng = np.random.RandomState(12)
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               compile=False, cache_quant="dynamic_int8")
    assert b.stats()["kv_writer"] == "row"
    before = _kv_write_launches()
    for _ in range(2):
        b.submit(rng.randint(0, 128, (5,)), 6)
    b.run_until_done()
    after = _kv_write_launches()
    assert after["row"] - before["row"] == b.stats()["steps"] > 0
    assert after["page"] == before["page"]


def test_kv_writer_of_a_family_without_the_word_is_row():
    """GPT-2's paged step (``block_multihead_attention``) scatters rows and
    says nothing: the batcher's default."""
    b = PagedContinuousBatcher(_model(), max_batch=2, s_max=32,
                               block_size=8, compile=False)
    assert b.stats()["kv_writer"] == "row"
