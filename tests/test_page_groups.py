"""Page groups in ``PagedContinuousBatcher`` and ``prefix_cache.PageGroup``:
a model whose window layers keep the trailing rows alone has their pages
taken back while a sequence runs, kept evictable with the prefix cache's
nodes, and reclaimed in release order without a walk of the tree. The model
is ``models/mellum.py`` at its tiny size; what it computes is held in
``test_mellum.py``."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference.prefix_cache import (PageGroup,  # noqa: E402
                                               RadixPrefixCache)
from paddle_tpu.inference.serving import PagedContinuousBatcher  # noqa: E402
from paddle_tpu.models import (GlmDsaForCausalLM, LlamaForCausalLM,  # noqa: E402
                               MellumForCausalLM, glm_dsa_tiny_config,
                               llama_tiny_config, mellum_tiny_config)

WINDOW, CHUNK, BLOCK = 16, 16, 8


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = MellumForCausalLM(mellum_tiny_config())
    m.eval()
    return m


def batcher(model, **server):
    return PagedContinuousBatcher(model, **dict(dict(
        max_batch=3, s_max=128, block_size=BLOCK,
        n_pages={"full": 48, "window": 30}, prefill_chunk=CHUNK,
        prefix_cache=True, compile=False), **server))


def watch_held(b):
    """The most rows' worth of window pages any slot held, looked at
    whenever the group changes a slot's pages."""
    group = b._groups["window"]
    most = [0]
    advance = group.advance

    def watched(slot, dec, upto_row):
        done = advance(slot, dec, upto_row)
        most[0] = max(most[0], group.held(slot) * BLOCK)
        assert group.held(slot) <= group.ring
        return done

    group.advance = watched
    return most


@pytest.mark.parametrize("policy", ["reserve", "ondemand"])
def test_a_running_sequence_holds_a_window_and_a_chunk_of_pages(model,
                                                                policy):
    """At every chunk and decode step, at every length: no more than
    window + chunk + block rows' worth, while the full group holds every
    row."""
    b = batcher(model, policy=policy)
    most = watch_held(b)
    rng = np.random.default_rng(0)
    for n, new in ((100, 20), (7, 30), (33, 40)):
        b.submit(rng.integers(0, 128, n), new)
    with paddle.no_grad():
        b.run_until_done()
    assert 0 < most[0] <= WINDOW + CHUNK + BLOCK
    assert b.audit_pages() == 0
    b.close()


def test_the_audit_is_zero_by_group_after_a_drain(model):
    b = batcher(model)
    rng = np.random.default_rng(1)
    doc = rng.integers(0, 128, 40)
    for n in (3, 9, 20):
        b.submit(np.concatenate([doc, rng.integers(0, 128, n)]), 6)
    with paddle.no_grad():
        b.run_until_done()
    group = b._groups["window"]
    assert b.audit_pages() == 0 and group.audit() == 0
    assert not group.ref                       # nobody runs, nothing held
    assert len(group.free) + len(group.released) == group.n_pages
    assert set(group.released) == set(group.owner)
    # a page that went back and was not the tree's is free, not lost
    assert (group.table == group.scratch).all()
    group.free.pop()
    with pytest.raises(RuntimeError, match="page accounting bug in group "
                                           "'window'"):
        b.audit_pages()
    b.close()


def test_released_pages_are_reclaimed_oldest_release_first(model):
    """A document's blocks fall behind the window in order, so the queue
    holds them in order; a later sequence with no free page left takes
    them from the head, the node keeps its place in the tree and loses
    its window page alone."""
    b = batcher(model, n_pages={"full": 48, "window": 12}, max_batch=1)
    group = b._groups["window"]
    rng = np.random.default_rng(2)
    doc = rng.integers(0, 128, 64)
    b.submit(doc, 4)
    with paddle.no_grad():
        b.run_until_done()
    path = b.prefix_cache.match(doc)
    assert len(path) == 8
    queued = list(group.released)
    # blocks 0 .. 5 went back while the document was prefilled, in order
    assert queued[:6] == [group.of(n) for n in path[:6]]
    free = len(group.free)
    b.submit(rng.integers(0, 128, 8 * (free + 2)), 2)
    with paddle.no_grad():
        b.run_until_done()
    assert group.reclaimed_total >= 2
    gone = [group.of(n) < 0 for n in path]
    assert gone[0] and gone[1] and gone == sorted(gone, reverse=True)
    assert all(n.page >= 0 for n in path)      # the full group kept its own
    assert b.audit_pages() == 0
    b.close()


def test_window_pages_recycle_without_a_search_of_the_tree(model,
                                                           monkeypatch):
    """A step count, not a clock: while a long sequence turns the window
    group over many times, ``evict`` and its walk are never entered, and a
    page handed out costs one step at the queue's head."""
    calls = {"evict": 0, "walk": 0}
    evict, walk = RadixPrefixCache.evict, \
        RadixPrefixCache._lru_device_evictable
    monkeypatch.setattr(RadixPrefixCache, "evict", lambda self, n: (
        calls.__setitem__("evict", calls["evict"] + 1), evict(self, n))[1])
    monkeypatch.setattr(RadixPrefixCache, "_lru_device_evictable",
                        lambda self: (calls.__setitem__(
                            "walk", calls["walk"] + 1), walk(self))[1])
    b = batcher(model, n_pages={"full": 48, "window": 9}, max_batch=1)
    group = b._groups["window"]
    pops = [0]

    class Counted(type(group.released)):
        def popitem(self, last=True):
            pops[0] += 1
            return super().popitem(last)

    group.released = Counted(group.released)
    rng = np.random.default_rng(3)
    for _ in range(3):
        b.submit(rng.integers(0, 128, 100), 10)
        with paddle.no_grad():
            b.run_until_done()
    assert group.reclaimed_total > 2 * group.n_pages
    assert pops[0] == group.reclaimed_total
    assert calls == {"evict": 0, "walk": 0}
    assert b.audit_pages() == 0
    b.close()


def test_a_full_group_page_is_not_freed_for_the_window_group(model):
    """And the other way round: the window group turning over leaves the
    tree's nodes and their full-group pages where they are; evicting a
    node for the full group frees its window page as it goes."""
    b = batcher(model, n_pages={"full": 20, "window": 9}, max_batch=1)
    group = b._groups["window"]
    rng = np.random.default_rng(4)
    b.submit(rng.integers(0, 128, 100), 4)
    with paddle.no_grad():
        b.run_until_done()
    cached = b.prefix_cache.cached_pages
    assert cached == 12 and group.reclaimed_total > 0
    assert b.prefix_cache.evictions == 0
    b.submit(rng.integers(0, 128, 100), 4)    # the full group has to evict
    with paddle.no_grad():
        b.run_until_done()
    assert b.prefix_cache.evictions > 0
    assert b.audit_pages() == 0
    b.close()


def test_the_page_group_alone():
    """``PageGroup`` without a batcher: take, let go, adopt, reclaim,
    forget."""
    from paddle_tpu.inference.prefix_cache import _Node
    g = PageGroup("window", rows=16, n_pages=6, block_size=8, ring=5,
                  max_batch=2)
    assert g.blocks_back == 2
    assert g.advance(0, 0, 24) and g.held(0) == 3
    nodes = [_Node((i,), i, None, i, i + 1) for i in range(3)]
    for j, node in enumerate(nodes):
        g.adopt(0, j, node)
    assert [g.of(n) for n in nodes] == [int(g.table[0, j]) for j in range(3)]
    assert g.usable(nodes) == 3
    assert g.advance(0, 32, 40)                # blocks 0 and 1 fall behind
    assert list(g.released) == [g.of(nodes[0]), g.of(nodes[1])]
    g.start(1, nodes[:2])                      # a hit holds them again
    assert not g.released and g.ref[g.of(nodes[0])] == 1
    g.drop_slot(1)
    assert list(g.released) == [g.of(nodes[0]), g.of(nodes[1])]
    first = g.of(nodes[0])
    while g.free:
        g.take()
    assert g.take() == first and g.of(nodes[0]) == -1
    # the boundary behind block 2 needs blocks 1 and 2, that behind
    # block 1 blocks 0 and 1
    assert g.usable(nodes) == 3 and g.usable(nodes[:2]) == 0
    g.forget(nodes[1])                         # evicted: its page is free
    assert g.of(nodes[1]) == -1 and len(g.free) == 1
    assert g.usable(nodes) == 0
    with pytest.raises(RuntimeError, match="do not fit its ring"):
        g.advance(0, 32, 32 + 8 * 6)


def test_contracts_without_groups_take_the_paths_they_took():
    """``llama`` and ``glm_dsa``: no group object, no group table in the
    state, ``n_pages`` a number, the prefix cache inserted once at the
    admission's end; a page count by group is refused by name."""
    paddle.seed(0)
    for make, cfg in ((LlamaForCausalLM, llama_tiny_config()),
                      (GlmDsaForCausalLM, glm_dsa_tiny_config())):
        m = make(cfg)
        m.eval()
        assert "page_groups" not in getattr(
            m, "paged_serving_contract", dict)()
        b = PagedContinuousBatcher(m, max_batch=2, s_max=64, block_size=8,
                                   n_pages=16, prefill_chunk=16,
                                   prefix_cache=True, compile=False)
        assert b._groups == {} and "group_tables" not in b._state
        assert b._primary_group is None and b.n_pages == 16
        walks = []
        insert = b.prefix_cache.insert
        b.prefix_cache.insert = lambda *a, **k: (walks.append(k),
                                                 insert(*a, **k))[1]
        b.submit(np.arange(40) % 50, 3)
        with paddle.no_grad():
            b.run_until_done()
        assert walks == [{}]                   # once, from the root
        assert b.audit_pages() == 0
        b.close()
        with pytest.raises(ValueError, match="has no page groups"):
            PagedContinuousBatcher(m, max_batch=2, s_max=64, block_size=8,
                                   n_pages={"full": 16}, compile=False)


def test_a_grouped_model_needs_a_page_count_a_group(model):
    with pytest.raises(ValueError, match="n_pages gives a page count for "
                                         "each"):
        batcher(model, n_pages=48)
    with pytest.raises(ValueError, match="one sequence holds up to"):
        batcher(model, n_pages={"full": 48, "window": 4})


def test_admission_waits_while_the_window_group_could_not_hold_a_ring_each(
        model):
    """Three slots, but rings for two: the third request waits its turn,
    and nobody ever waits for a window page."""
    b = batcher(model, n_pages={"full": 48, "window": 14})
    assert b._groups["window"].ring == 7
    rng = np.random.default_rng(5)
    rids = [b.submit(rng.integers(0, 128, 20), 12) for _ in range(3)]
    with paddle.no_grad():
        b.step()
        assert len(b._slot_req) == 2 and b.pending == 1
        out = b.run_until_done()
    assert sorted(out) == sorted(rids) and b.audit_pages() == 0
    b.close()


# -- the owner of recurrent-state snapshots (``prefix_cache.StateSnapshots``) --

def _chain(n):
    from paddle_tpu.inference.prefix_cache import _Node
    return [_Node((i,), i, None, i, i + 1) for i in range(n)]


def test_the_snapshot_owner_alone():
    """``StateSnapshots`` without a batcher: take, adopt, resume, reclaim
    the one longest unused, forget, drop, audit."""
    from paddle_tpu.inference.prefix_cache import StateSnapshots
    s = StateSnapshots("state", rows=16, n=3, block_size=8)
    assert s.blocks == 2
    nodes = _chain(8)
    assert s.usable(nodes) == 0                # nothing held yet
    taken = [s.take(0, b) for b in (1, 3, 5)]  # boundaries at 16, 32, 48
    assert sorted(taken) == [0, 1, 2] and not s.free and s.audit() == 0
    for b in (1, 3, 5):
        s.adopt(0, b, nodes[b])
    assert [s.of(n) for n in nodes] == [-1, taken[0], -1, taken[1], -1,
                                        taken[2], -1, -1]
    # a match ends at the deepest boundary that has a snapshot, never
    # between two, and a path shorter than a boundary has none
    assert [s.usable(nodes[:m]) for m in range(9)] \
        == [0, 0, 2, 2, 4, 4, 6, 6, 6]
    assert s.resume(nodes[:2]) == taken[0] and s.resume([]) == -1
    # the store is full: the next boundary takes the one longest unused,
    # which is no longer the first (it was resumed from) but the second
    assert s.take(1, 7) == taken[1] and s.of(nodes[3]) == -1
    assert s.reclaimed_total == 1 and s.usable(nodes[:4]) == 2
    s.adopt(1, 7, nodes[7])
    assert s.usable(nodes) == 8
    # a node that has one already keeps it; the slot's goes back
    s.forget(nodes[5])
    assert s.of(nodes[5]) == -1 and s.free == [taken[2]]
    assert s.take(0, 1) == taken[2]
    s.adopt(0, 1, nodes[1])
    assert s.of(nodes[1]) == taken[0] and s.free == [taken[2]]
    # what a slot took and no node adopted goes back with the slot
    s.take(0, 5)
    assert not s.free and s.pending == {0: {5: taken[2]}, 1: {}}
    s.drop_slot(0)
    assert s.free == [taken[2]] and s.audit() == 0
    s.free.clear()
    with pytest.raises(RuntimeError, match="snapshot accounting"):
        s.audit()
    with pytest.raises(ValueError, match="whole blocks"):
        StateSnapshots("state", rows=12, n=3, block_size=8)


def test_snapshots_are_kept_in_steps_and_never_by_a_walk_of_the_tree(
        monkeypatch):
    """Two contexts of 90 rows, asked three times each, through a pool
    that holds them whole and a store of 12 snapshots that does not: the
    second context takes the first one's oldest snapshots and leaves its
    deepest, which the first one's next ask resumes from; boundaries are
    taken, adopted and reclaimed a step or two each (``steps`` grows with
    the boundaries passed, not with the tree), and ``evict`` and its walk
    are never entered."""
    from paddle_tpu.models import Lfm2ForCausalLM, lfm2_tiny_config
    calls = {"evict": 0, "walk": 0}
    evict, walk = RadixPrefixCache.evict, \
        RadixPrefixCache._lru_device_evictable
    monkeypatch.setattr(RadixPrefixCache, "evict", lambda self, n: (
        calls.__setitem__("evict", calls["evict"] + 1), evict(self, n))[1])
    monkeypatch.setattr(RadixPrefixCache, "_lru_device_evictable",
                        lambda self: (calls.__setitem__(
                            "walk", calls["walk"] + 1), walk(self))[1])
    paddle.seed(0)
    m = Lfm2ForCausalLM(lfm2_tiny_config())
    m.eval()
    b = PagedContinuousBatcher(m, max_batch=2, s_max=128, block_size=4,
                               n_pages=256, prefill_chunk=16,
                               prefix_cache=True, compile=False)
    store = b._snapshots
    store.n, store.free = 12, list(range(12))
    rng = np.random.default_rng(0)
    docs = [rng.integers(0, 128, 90) for _ in range(2)]
    per_request = []
    with paddle.no_grad():
        for ask in range(3):
            for doc in docs:
                before = store.steps
                b.submit(np.concatenate([doc, rng.integers(0, 128, 3)]), 2)
                b.run_until_done()
                per_request.append(store.steps - before)
    assert store.reclaimed_total > 0 and calls == {"evict": 0, "walk": 0}
    assert b.audit_pages() == 0
    # a cold context passes 11 boundaries: a take and an adoption each; a
    # second ask looks back over at most 11 boundaries for one that is
    # left, resumes, and passes the boundaries behind it; the tree holds
    # some 50 nodes by then and no count here grows with it
    assert max(per_request) <= 2 * 11 + 11 + 1, per_request
    assert per_request[2:] == [2] * 4          # one look back, one resume
    assert len(store.owned) == 12 and store.restored_total == 4
    assert b.prefix_cache.stats()["hit_tokens"] == 4 * 88
    b.close()
