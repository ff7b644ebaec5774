"""Radix prefix KV cache + in-batcher speculative decoding (round 13).

Three layers, same exactness bar as tests/test_paged_batching.py:

  * pure-host radix-tree units — insert/match/evict/refcount under
    pressure, chain-hash summaries (no model, sub-second);
  * paged-batcher integration — shared-prefix admissions must be
    token-exact vs the solo greedy reference with the cache hitting,
    pages audited (``serving.pages_leaked`` stays 0) through eviction
    pressure and preemption;
  * speculative decoding — ``draft_model=`` output must equal
    non-speculative output token for token across seeds, alone and
    composed with the prefix cache.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.prefix_cache import RadixPrefixCache, chain_hashes
from paddle_tpu.inference.serving import PagedContinuousBatcher
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM

from greedy_ref import greedy_ref

pytestmark = pytest.mark.perf


# -- radix-tree units (no model) ----------------------------------------------

def test_radix_match_insert_refcount():
    c = RadixPrefixCache(block_size=4)
    toks = np.arange(11)                       # 2 full blocks + partial
    assert c.match(toks) == []
    created = c.insert(toks, pages=[7, 8], start_block=0, n_blocks=2)
    assert [n.page for n in created] == [7, 8]
    assert all(n.ref == 1 for n in created)    # born pinned by inserter
    path = c.match(toks)
    assert [n.page for n in path] == [7, 8]
    assert c.match(toks, max_blocks=1) == path[:1]
    # a diverging second block shares only the first node
    other = np.concatenate([np.arange(4), np.arange(50, 54)])
    assert [n.page for n in c.match(other)] == [7]
    c.unpin(created)
    with pytest.raises(RuntimeError):          # double release is a bug
        c.unpin(created[:1])


def test_radix_insert_skips_existing_blocks():
    c = RadixPrefixCache(block_size=4)
    toks = np.arange(8)
    c.insert(toks, pages=[0, 1], start_block=0, n_blocks=2)
    # same prefix again: the tree keeps ITS pages, nothing new adopted
    created = c.insert(toks, pages=[5, 6], start_block=2, n_blocks=2)
    assert created == []
    assert sorted(c.pages()) == [0, 1]


def test_radix_evict_lru_unpinned_leaves_only():
    c = RadixPrefixCache(block_size=2)
    hot = c.insert(np.arange(6), [0, 1, 2], 0, 3)       # chain A, pinned
    cold = c.insert(np.array([9, 9, 1, 1]), [3, 4], 0, 2)  # chain B
    c.unpin(cold)                                       # B is idle
    assert c.evictable_pages() == 2
    # pinned chain A is untouchable even under a too-large ask; B frees
    # bottom-up (leaf first)
    assert c.evict(10) == [4, 3]
    assert c.evictions == 2 and len(c) == 3
    assert c.evict(1) == []                             # nothing unpinned
    c.unpin(hot)
    assert c.evictable_pages() == 3


def test_radix_evict_lru_order():
    c = RadixPrefixCache(block_size=2)
    a = c.insert(np.array([1, 1]), [0], 0, 1)
    b = c.insert(np.array([2, 2]), [1], 0, 1)
    c.unpin(a)
    c.unpin(b)                   # released after a -> a is the LRU leaf
    assert c.evict(1) == [0]
    c.pin(b)                     # a re-match touches b…
    c.unpin(b)
    d = c.insert(np.array([3, 3]), [2], 0, 1)
    c.unpin(d)                   # …so b is now OLDER than d
    assert c.evict(2) == [1, 2]


def test_radix_interior_protected_by_pinned_descendant():
    c = RadixPrefixCache(block_size=2)
    nodes = c.insert(np.arange(4), [0, 1], 0, 2)
    c.unpin(nodes[:1])            # parent unpinned, leaf still pinned
    assert c.evictable_pages() == 0
    assert c.evict(2) == []
    c.unpin(nodes[1:])
    assert c.evict(2) == [1, 0]   # bottom-up once fully released


def test_chain_hashes_agree_with_summary():
    c = RadixPrefixCache(block_size=4)
    toks = np.arange(12)
    c.insert(toks, [0, 1, 2], 0, 3)
    s = c.summary()
    assert s["block_size"] == 4
    chain = chain_hashes(toks, 4)
    assert len(chain) == 3
    # every chain hash is advertised at its depth; a foreign prompt's
    # chain diverges from the first block
    assert [s["hashes"][h] for h in chain] == [1, 2, 3]
    assert chain_hashes(np.arange(50, 62), 4)[0] not in s["hashes"]


# -- paged-batcher integration ------------------------------------------------

def _model(seed=0):
    paddle.seed(seed)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=128,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    return m


def _refs(m, prompts, n):
    return [greedy_ref(m, p, n) for p in prompts]


def _shared_prompts(seed, n, shared_len=40):
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, 128, (shared_len,))
    return [np.concatenate([shared, rng.randint(0, 128, (5 + i,))])
            for i in range(n)]


def _pages_leaked():
    from paddle_tpu.observability.metrics import get_registry
    return get_registry().gauge("serving.pages_leaked", "t").value


def test_prefix_cache_batcher_token_exact_and_hits():
    m = _model()
    prompts = _shared_prompts(3, 4)
    refs = _refs(m, prompts, 8)
    with paddle.no_grad():
        b = PagedContinuousBatcher(m, max_batch=2, s_max=96, block_size=16,
                                   n_pages=24, compile=False,
                                   policy="ondemand", prefix_cache=True)
        rids = [b.submit(p, 8) for p in prompts]
        res = b.run_until_done()
    for rid, ref in zip(rids, refs):
        assert np.array_equal(res[rid], ref)
    st = b.prefix_cache.stats()
    assert st["hit_tokens"] > 0            # later requests reused the prefix
    b.audit_pages()
    assert _pages_leaked() == 0
    # every page is either free or owned by the cache once slots drain
    assert b.free_page_count + b.prefix_cache.cached_pages == b.n_pages


def test_prefix_cache_eviction_pressure_stays_exact():
    """A pool too small to keep every prefix resident: eviction must
    fire, pages must balance, output must stay exact."""
    m = _model()
    rng = np.random.RandomState(5)
    prompts = []
    for k in range(3):                      # 3 distinct 32-token prefixes
        shared = rng.randint(0, 128, (32,))
        prompts += [np.concatenate([shared, rng.randint(0, 128, (6 + i,))])
                    for i in range(2)]
    refs = _refs(m, prompts, 6)
    with paddle.no_grad():
        b = PagedContinuousBatcher(m, max_batch=2, s_max=64, block_size=16,
                                   n_pages=6, compile=False,
                                   policy="ondemand", prefix_cache=True)
        rids = [b.submit(p, 6) for p in prompts]
        res = b.run_until_done()
    for rid, ref in zip(rids, refs):
        assert np.array_equal(res[rid], ref)
    assert b.prefix_cache.evictions > 0     # pressure actually evicted
    b.audit_pages()
    assert _pages_leaked() == 0
    assert b.free_page_count + b.prefix_cache.cached_pages == b.n_pages


def test_prefix_cache_preemption_releases_pages():
    """ondemand preemption with the cache on: preempted requests resume
    exact, and no page leaks out of free ∪ block-table ∪ cache."""
    m = _model()
    prompts = _shared_prompts(7, 4, shared_len=32)
    refs = _refs(m, prompts, 10)
    with paddle.no_grad():
        b = PagedContinuousBatcher(m, max_batch=4, s_max=64, block_size=16,
                                   n_pages=12, compile=False,
                                   policy="ondemand", prefix_cache=True)
        rids = [b.submit(p, 10) for p in prompts]
        res = b.run_until_done()
    for rid, ref in zip(rids, refs):
        assert np.array_equal(res[rid], ref)
    b.audit_pages()
    assert _pages_leaked() == 0


# -- speculative decoding -----------------------------------------------------

@pytest.mark.exact
@pytest.mark.parametrize("draft_seed", [0, 1, 2])
def test_speculative_batcher_token_exact(draft_seed):
    """draft_seed=0 clones the target (high acceptance), others disagree
    (fallback-heavy) — output must be identical either way."""
    m = _model()
    dm = m if draft_seed == 0 else _model(draft_seed)
    prompts = _shared_prompts(11 + draft_seed, 3)
    refs = _refs(m, prompts, 8)
    with paddle.no_grad():
        b = PagedContinuousBatcher(m, max_batch=2, s_max=96, block_size=16,
                                   compile=False, draft_model=dm,
                                   draft_k=3)
        rids = [b.submit(p, 8) for p in prompts]
        res = b.run_until_done()
    for rid, ref in zip(rids, refs):
        assert np.array_equal(res[rid], ref)
    assert b.spec_stats["rounds"] > 0
    if draft_seed == 0:                     # self-draft must mostly match
        assert b.spec_stats["matched"] > 0
    b.audit_pages()


@pytest.mark.exact
def test_speculative_with_prefix_cache_composes():
    m = _model()
    dm = _model(9)
    prompts = _shared_prompts(13, 4)
    refs = _refs(m, prompts, 8)
    with paddle.no_grad():
        b = PagedContinuousBatcher(m, max_batch=2, s_max=96, block_size=16,
                                   n_pages=24, compile=False,
                                   policy="ondemand", prefix_cache=True,
                                   draft_model=dm, draft_k=3,
                                   prompt_buckets="pow2")
        rids = [b.submit(p, 8) for p in prompts]
        res = b.run_until_done()
    for rid, ref in zip(rids, refs):
        assert np.array_equal(res[rid], ref)
    assert b.prefix_cache.hit_tokens > 0
    assert b.spec_stats["rounds"] > 0
    b.audit_pages()
    assert _pages_leaked() == 0


def test_a_node_that_leaves_the_tree_tells_the_owner_of_its_snapshot():
    """``RadixPrefixCache.groups`` holds every owner of what nodes carry
    beside their page: a ``StateSnapshots`` among them hears ``forget`` for
    an evicted node, and the node's snapshot is free again."""
    from paddle_tpu.inference.prefix_cache import (RadixPrefixCache,
                                                   StateSnapshots)
    cache = RadixPrefixCache(4)
    store = StateSnapshots("state", rows=8, n=4, block_size=4)
    cache.groups = {"state": store}
    tokens = list(range(16))
    nodes = cache.insert(tokens, [10, 11, 12, 13], 0, 4)
    for block in (1, 3):
        store.take(0, block)
        store.adopt(0, block, nodes[block])
    cache.unpin(nodes)
    assert store.usable(cache.match(tokens)) == 4 and len(store.free) == 2
    assert cache.evict(1) == [13]              # the deepest block goes
    assert len(store.free) == 3 and store.audit() == 0
    assert store.usable(cache.match(tokens)) == 2
    assert cache.evict(3) == [12, 11, 10]
    assert len(store.free) == 4 and not store.owned
