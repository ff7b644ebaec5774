"""``models/lfm2.py``'s state snapshots on the served path, at the rehearsal
size, float32: a request resumed from a snapshot against the same request
prefilled cold, bit for bit, at match lengths on and off a boundary; a
reclaimed snapshot; snapshots that go with their evicted nodes; preemption by
re-prefill. The helpers and the model are ``test_lfm2.py``'s."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_lfm2 import (SERVER, TOL, build, prompts_by_length,  # noqa: E402
                       reference_rows, series, serve)
from paddle_tpu.inference.serving import PagedContinuousBatcher  # noqa: E402


@pytest.mark.parametrize("shared,resumed_at", [(48, 48), (45, 40), (51, 48),
                                               (8, 8), (7, 0)])
def test_a_request_resumed_from_a_snapshot_equals_a_cold_prefill_bit_for_bit(
        shared, resumed_at):
    """A context of ``shared`` rows is asked, then asked again under a new
    prompt: the match (whole blocks of 4) is cut to the snapshot boundary
    (every 8 rows) and the request starts from the snapshot; a server that
    never saw the context serves the same logits, bit for bit (one slot
    on both sides: a row's rounding depends on where it lies in a step's
    batch)."""
    rng = np.random.default_rng(7)
    doc = rng.integers(0, 256, shared)
    ask = [np.concatenate([doc, rng.integers(0, 256, n)]) for n in (5, 9)]
    first, rows1, b = serve([ask[0]], [6], max_batch=1)
    hit0 = b.prefix_cache.stats()["hit_tokens"]
    restored0 = b._snapshots.restored_total
    second, rows2, _ = serve([ask[1]], [10], batcher=b)
    assert b.prefix_cache.stats()["hit_tokens"] - hit0 == resumed_at
    assert b._snapshots.restored_total - restored0 == (resumed_at > 0)
    b.close()
    cold, rows_cold, c = serve([ask[1]], [10], max_batch=1)
    c.close()
    assert np.array_equal(second[0], cold[0])
    assert np.array_equal(rows2[0], rows_cold[0])
    for got, ref in zip(rows1 + rows2,
                        reference_rows(first + second, ask)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def test_a_reclaimed_snapshot_cuts_the_match_and_never_yields_a_wrong_state():
    """A store of 6 snapshots (the owner is told of no more of the device's
    48, so that the store and not the pool runs out): a context of 40 rows
    takes 5, two other prompts take theirs out of the context's oldest, and
    the context comes back: its match is cut to the deepest boundary that
    still has a snapshot, counted, and every row reads what a cold server
    gives."""
    rng = np.random.default_rng(8)
    doc = rng.integers(0, 256, 40)
    ask = [np.concatenate([doc, rng.integers(0, 256, n)]) for n in (3, 6)]
    b = PagedContinuousBatcher(build(), **dict(SERVER, max_batch=1))
    store = b._snapshots
    store.n, store.free = 6, list(range(6))
    serve([ask[0]], [4], batcher=b)
    assert len(store.owned) == 5
    held = [store.of(n) for n in b.prefix_cache.match(doc)]
    assert [i >= 0 for i in held] == [False, True] * 5
    serve([rng.integers(0, 256, 26)], [3], batcher=b)   # 3 boundaries
    assert store.reclaimed_total == 2
    held = [store.of(n) >= 0 for n in b.prefix_cache.match(doc)]
    assert held == [False] * 4 + [False, True] * 3      # rows 8 and 16 gone
    cut0 = series("serving.prefix_hits_cut_total", why="no_state_snapshot")
    rows_cut0 = series("serving.prefix_rows_cut_total")
    # reclaim the deepest too: the context's boundary at 40 was used least
    # recently of what is left once 24 and 32 have been resumed from
    store.owned.move_to_end(store.of(b.prefix_cache.match(doc)[5]))
    store.owned.move_to_end(store.of(b.prefix_cache.match(doc)[7]))
    serve([rng.integers(0, 256, 9)], [3], batcher=b)
    assert store.of(b.prefix_cache.match(doc)[9]) == -1
    hit0 = b.prefix_cache.stats()["hit_tokens"]
    second, rows2, _ = serve([ask[1]], [8], batcher=b)
    assert b.prefix_cache.stats()["hit_tokens"] - hit0 == 32
    assert series("serving.prefix_hits_cut_total",
                  why="no_state_snapshot") - cut0 == 1
    assert series("serving.prefix_rows_cut_total") - rows_cut0 == 8
    b.close()
    cold, rows_cold, c = serve([ask[1]], [8], max_batch=1)
    c.close()
    assert np.array_equal(second[0], cold[0])
    assert np.array_equal(rows2[0], rows_cold[0])


def test_snapshots_go_with_their_nodes_and_nothing_is_left_after_a_drain():
    """A pool of 20 pages: a second context evicts the first one's blocks,
    and the snapshots of the evicted nodes are free again; after the drain
    every snapshot is free or a node's (``audit_pages`` counts the rest)."""
    rng = np.random.default_rng(9)
    _, _, b = serve([rng.integers(0, 256, 50)], [4], n_pages=20,
                    max_batch=1)
    store = b._snapshots
    assert store.n == 10 and len(store.owned) == 6 and not store.pending
    serve([rng.integers(0, 256, 60)], [4], batcher=b)
    assert b.prefix_cache.evictions > 0
    assert len(store.owned) + len(store.free) == store.n
    in_tree = {store.of(n) for n in walk(b.prefix_cache)} - {-1}
    assert in_tree == set(store.owned)
    assert b.audit_pages() == 0
    assert series("serving.state_snapshots_held") == len(store.owned)
    # a snapshot nobody owns is a leak the audit finds
    store.free.pop()
    with pytest.raises(RuntimeError, match="snapshot accounting"):
        b.audit_pages()
    b.close()


def walk(cache):
    stack = list(cache._root.children.values())
    while stack:
        n = stack.pop()
        yield n
        stack.extend(n.children.values())


def test_a_preempted_request_resumes_by_prefill_and_serves_the_same_tokens():
    """``policy="ondemand"`` in a pool too small for three to finish: the
    latest is preempted, re-queued with what it has generated, prefilled
    again (from a snapshot where its prompt's blocks are cached) and serves
    the tokens an unhurried server does."""
    prompts = prompts_by_length()
    news = [30, 30, 30]
    want, _, easy = serve(prompts, news)
    easy.close()
    seqs, _, b = serve(prompts, news, policy="ondemand", n_pages=36)
    assert b.stats()["preemptions"] > 0
    b.close()
    for got, ref in zip(seqs, want):
        assert np.array_equal(got, ref)
