"""Two seeds offer the same work; the seed orders it."""
import collections
import itertools

import numpy as np
import pytest

from chipbench import traffic as T


@pytest.mark.parametrize("name,n", [("chat-batch", 128),
                                    ("doc-sessions", 64)])
def test_two_seeds_give_the_same_multiset_of_lengths(name, n):
    tr = T.load(name)
    seen = []
    for seed in (3, 2 ** 31 + 11):
        offers = list(itertools.islice(T.offers(tr, 32768, seed), n))
        seen.append(collections.Counter(
            (len(o.prompt), o.max_new) for o in offers))
        assert all(o.prompt.max() < 32768 for o in offers)
    assert seen[0] == seen[1]
    a = [len(o.prompt) for o in itertools.islice(T.offers(tr, 32768, 3), n)]
    b = [len(o.prompt) for o in itertools.islice(T.offers(tr, 32768, 4), n)]
    assert (a != b) == (tr.get("schedule", "seed") == "seed")


def test_same_seed_same_requests():
    tr = T.load("doc-sessions")
    a, b = (list(itertools.islice(T.offers(tr, 32768, 5), 20))
            for _ in range(2))
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               for x, y in zip(a, b))


def test_document_asks_share_their_prefix_and_lie_eight_apart():
    tr = T.load("doc-sessions")
    offers = list(itertools.islice(T.offers(tr, 32768, 9), 32))
    by_doc = collections.defaultdict(list)
    for o in offers:
        by_doc[(o.group, o.slot)].append(o)
    assert len(by_doc) == 8
    for asks in by_doc.values():
        assert [o.ask for o in asks] == [0, 1, 2, 3]
        doc = tr["cycle"]["documents"][asks[0].slot]
        assert all(np.array_equal(o.prompt[:doc], asks[0].prompt[:doc])
                   for o in asks)
        assert {b.index - a.index for a, b in zip(asks, asks[1:])} == {8}
    dues = [o.due for o in offers]
    assert dues == sorted(dues)
    assert dues[-1] == pytest.approx(31 / tr["rate_per_s"], abs=0.5)


def test_lengths_stay_inside_the_stated_ranges():
    for name in ("chat-batch", "doc-sessions"):
        cyc = T.load(name)["cycle"]
        for rnd in T.group_lengths(cyc):
            for _, q, a in rnd:
                assert cyc["prompt_tokens"]["min"] <= q \
                    <= cyc["prompt_tokens"]["max"]
                assert cyc["answer_tokens"]["min"] <= a \
                    <= cyc["answer_tokens"]["max"]


def test_train_batches_differ_by_row_step_and_seed():
    tr = T.load("train-fixed-2k")
    ids, labels = T.train_batch(tr, 49152, 7, 1)
    assert ids.shape == labels.shape == (4, 2048)
    assert np.array_equal(ids[:, 1:], labels[:, :-1])
    assert len({row.tobytes() for row in ids}) == 4
    assert not np.array_equal(ids, T.train_batch(tr, 49152, 7, 2)[0])
    assert not np.array_equal(ids, T.train_batch(tr, 49152, 8, 1)[0])
    assert np.array_equal(ids, T.train_batch(tr, 49152, 7, 1)[0])


def test_schedule_from_the_file_gives_every_seed_the_same_arrivals():
    tr = T.load("doc-sessions")
    assert tr["schedule"] == "file"
    a, b = (list(itertools.islice(T.offers(tr, 32768, s), 40))
            for s in (5, 6))
    assert [(x.due, x.group, x.slot, len(x.prompt), x.max_new) for x in a] \
        == [(y.due, y.group, y.slot, len(y.prompt), y.max_new) for y in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)   # ids are the seed's
    seeded = dict(tr, schedule="seed")
    c, d = (list(itertools.islice(T.offers(seeded, 32768, s), 40))
            for s in (5, 6))
    assert [x.slot for x in c] != [y.slot for y in d]
