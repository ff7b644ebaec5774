"""The one traffic generator. A traffic mix is a data file of parameters under
``chipbench/traffic/``; this module turns it and ``--seed`` into work.

Two seeds offer the same work: the multiset of prompt and answer lengths is
fixed by the file (a stratified draw from the stated distribution, paired by
a constant of the file), and the seed decides only the token ids, the order of
requests inside a round, and the arrival jitter.

Serving mixes are built from *groups*. A group has ``slots`` requests a round
(one per shared document, or ``requests_per_round`` unshared prompts) and
``asks_per_document`` rounds; every ask of a document repeats the document
and adds a new question. ``interleave`` groups at a time are dealt round by
round, so the asks of one document lie ``interleave * slots`` requests apart.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str, rehearse: bool = False) -> dict:
    sub = "rehearse" if rehearse else "traffic"
    with open(os.path.join(HERE, sub, f"{name}.json")) as f:
        return json.load(f)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


# -- training ----------------------------------------------------------------

def train_batch(traffic: dict, vocab: int, seed: int, step: int,
                shape=None):
    """(ids, labels) of one step: every row differs, labels are the next
    token. ``step`` 0 is the discovery batch."""
    b, s = shape or (traffic["batch"], traffic["seq"])
    toks = rng_for(seed, 1, step).integers(0, vocab, (b, s + 1),
                                           dtype=np.int64)
    return toks[:, :-1], toks[:, 1:]


# -- serving -----------------------------------------------------------------

def stratified(spec: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of the stated distribution: the same
    for every seed."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    dist = spec.get("dist", "loguniform")
    if dist == "loguniform":
        x = np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
    elif dist == "uniform":
        x = lo + q * (hi - lo)
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def group_lengths(cycle: dict):
    """[(document_tokens, question_tokens, answer_tokens)] of one group, by
    (round, slot); seed-independent."""
    docs = cycle.get("documents") or []
    slots = len(docs) or cycle["requests_per_round"]
    rounds = cycle.get("asks_per_document", 1)
    n = slots * rounds
    pair = np.random.default_rng(cycle.get("pairing", 1))
    q = stratified(cycle["prompt_tokens"], n)[pair.permutation(n)]
    a = stratified(cycle["answer_tokens"], n)[pair.permutation(n)]
    return [[(docs[s] if docs else 0, int(q[r * slots + s]),
              int(a[r * slots + s])) for s in range(slots)]
            for r in range(rounds)]


@dataclasses.dataclass
class Offer:
    index: int
    prompt: np.ndarray
    max_new: int
    due: float            # seconds after the window opens (open loop)
    group: int
    slot: int
    ask: int
    shared: int           # tokens this prompt shares with earlier asks


def offers(traffic: dict, vocab: int, seed: int):
    """The endless stream of requests of a serving mix."""
    cycle = traffic["cycle"]
    lengths = group_lengths(cycle)
    rounds, slots = len(lengths), len(lengths[0])
    k = cycle.get("interleave", 1)
    rate = traffic.get("rate_per_s")
    jitter = traffic.get("jitter", 0.0)
    # "schedule": "seed" draws the order and the jitter from --seed;
    # "file" draws them from the file's own constant, so that every seed
    # meets the same arrivals and differs in weights and token ids alone
    sched = seed if traffic.get("schedule", "seed") == "seed" \
        else cycle.get("pairing", 1)
    arrivals = rng_for(sched, 3)
    index, base = 0, 0
    while True:
        groups = []
        for g in range(base, base + k):
            order = rng_for(sched, 5, g).permutation(slots)
            r = rng_for(seed, 2, g)
            docs = [r.integers(0, vocab, lengths[0][s][0], dtype=np.int64)
                    for s in range(slots)]
            groups.append((g, r, order, docs))
        for rnd in range(rounds):
            for g, r, order, docs in groups:
                for s in order:
                    d, q, a = lengths[rnd][s]
                    prompt = np.concatenate(
                        [docs[s], r.integers(0, vocab, q, dtype=np.int64)])
                    due = 0.0
                    if rate:
                        due = (index + jitter * (arrivals.random() - 0.5)) \
                            / rate
                    yield Offer(index, prompt, a, due, g, int(s), rnd,
                                d if rnd else 0)
                    index += 1
        base += k


def longest(traffic: dict) -> int:
    """Tokens of the longest prompt plus answer the mix can ask for."""
    return max(d + q + a for rnd in group_lengths(traffic["cycle"])
               for d, q, a in rnd)
