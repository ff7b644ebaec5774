"""``models/dsa_select.py``: the rows a threshold search reads are the rows
held. Every case holds the blocked search (a pass walks blocks of ``W``
columns no further than the last one ``valid`` has an entry in) against
numpy's stable sort and against the one-count search it replaced (one
block of all ``T`` columns: the parent's masks), for a chunk's
``select_rows`` (``digit`` 1) and a decode step's ``select_indices``
(``digit`` 4). The cells' decode shapes and ``glm_dsa``'s chunk are one
block by ``select_block``'s rule, so the blocks are forced here by
patching its two constants, at shapes the CPU counts in milliseconds.
"""
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import dsa_select as D
from paddle_tpu.observability.metrics import get_registry

B, T, W, K = 8, 1024, 256, 64


@functools.cache
def _jitted(name, k):
    return {blocked: jax.jit(functools.partial(getattr(D, name), k=k))
            for blocked in (False, True)}


def _sizes(blocked, rows=B):
    """``select_block``'s constants for blocks of ``W`` columns of ``rows``
    queries, or for one block whatever the shape."""
    return dict(_RESIDENT_BYTES=0 if blocked else 1 << 40,
                _BLOCK_BYTES=W * rows * 4)


def _run(name, scores, valid, k, blocked):
    with mock.patch.multiple(D, **_sizes(blocked, scores.shape[0])):
        out = _jitted(name, k)[blocked](jnp.asarray(scores),
                                        jnp.asarray(valid))
    return jax.tree.map(np.asarray, out)


def _sorted_mask(scores, valid, k):
    """The k best valid rows by numpy's stable sort: ties to the lowest
    rows, every valid row where k or fewer are valid."""
    keep = np.zeros(scores.shape, bool)
    for i, (s, v) in enumerate(zip(scores, valid)):
        rows = np.flatnonzero(v)
        order = np.argsort(-(s[rows].astype(np.float32) + 0.0),
                           kind="stable")
        keep[i, rows[order[:k]]] = True
    return keep


def _holds(scores, valid, k=K):
    """Both entry points, blocked and whole, against the sort."""
    want = _sorted_mask(scores, valid, k)
    for blocked in (True, False):
        assert np.array_equal(_run("select_rows", scores, valid, k, blocked),
                              want), blocked
        rows, kept = _run("select_indices", scores, valid, k, blocked)
        for i in range(scores.shape[0]):
            assert np.array_equal(rows[i][kept[i]], np.flatnonzero(want[i]))
            assert kept[i].sum() == want[i].sum()
            assert not rows[i][~kept[i]].any()


def _scores(seed, shape=(B, T)):
    return np.random.default_rng(seed).normal(size=shape).astype(np.float32)


def _prefix(held, rows=B):
    """A chunk's ``valid``: query i sees columns up to ``held - rows + i``
    (the last query all ``held`` of them)."""
    pos = held - rows + np.arange(rows)
    return np.arange(T)[None, :] <= pos[:, None]


@pytest.mark.parametrize("held", [
    pytest.param(W - 56, id="shorter_than_a_block"),
    pytest.param(2 * W, id="on_a_blocks_edge"),
    pytest.param(2 * W + 1, id="one_past_the_edge"),
    pytest.param(T, id="the_whole_width")])
def test_held_rows_against_the_sort_and_the_whole_width(held):
    _holds(_scores(held), _prefix(held))


def test_fewer_than_k_valid_keeps_every_valid_row():
    valid = _prefix(K - 3)
    valid[0] = False                                 # a query with none
    scores = _scores(1)
    _holds(scores, valid)
    assert np.array_equal(_run("select_rows", scores, valid, K, True), valid)


@pytest.mark.parametrize("need", [4, 8, 13], ids=lambda n: f"need{n}")
def test_ties_across_a_blocks_edge_go_to_the_lowest_rows(need):
    """13 rows tie at the k-th score in columns 250 .. 262, over the edge
    between the first block and the second; ``need`` of them are kept."""
    scores = -np.abs(_scores(2)) - 1.0
    tied = np.arange(W - 6, W + 7)
    scores[:, tied] = 0.5
    rng = np.random.default_rng(3)
    for i in range(B):                                # K - need rows above
        others = np.setdiff1d(np.arange(T), tied)
        scores[i, rng.choice(others, K - need, replace=False)] = 2.0 + i
    valid = np.ones((B, T), bool)
    _holds(scores, valid)
    keep = _run("select_rows", scores, valid, K, True)
    assert np.array_equal(np.flatnonzero(keep[0, tied]), np.arange(need))


def test_a_valid_with_holes():
    rng = np.random.default_rng(4)
    valid = rng.random((B, T)) < 0.4
    valid[:, 3 * W + 17:] = False
    _holds(_scores(5), valid)


def test_a_square_causal_valid():
    """``_block_dense``'s: s queries over their own s rows."""
    s = 3 * W
    scores = _scores(6, (s, s))
    causal = np.tril(np.ones((s, s), bool))
    want = _sorted_mask(scores, causal, K)
    for blocked in (True, False):
        assert np.array_equal(
            _run("select_rows", scores, causal, K, blocked), want)


@pytest.mark.parametrize("digit", [1, 4])
@pytest.mark.parametrize("live", [W - 56, 2 * W, 2 * W + 1, T])
def test_columns_past_the_blocks_held_are_not_read(digit, live):
    """Past the last block ``live`` reaches into, the bits are all ones:
    read, they would be every query's k best."""
    rng = np.random.default_rng(live + digit)
    bits = rng.integers(1, 1 << 32, (B, T), dtype=np.uint32)
    bits[:, live:] = 0
    sort = -np.sort(-bits.astype(np.int64), -1)[:, K - 1]
    spoiled = bits.copy()
    spoiled[:, -(-live // W) * W:] = 0xFFFFFFFF
    with mock.patch.multiple(D, **_sizes(True)):
        got = jax.jit(lambda b, n: D.kth_largest_bits(b, K, digit, n))(
            jnp.asarray(spoiled), jnp.int32(live))
    assert np.array_equal(np.asarray(got), sort)
    whole = np.asarray(D.kth_largest_bits(jnp.asarray(spoiled), K, digit))
    assert np.array_equal(whole, sort) == (live > 3 * W)


@pytest.mark.parametrize("shape,block", [
    ((512, 65536), 4096),          # keye2's chunk: 8 MB of its 128 MiB
    ((512, 32768), 32768),         # glm5's chunk: 64 MiB stay in VMEM
    ((1, 65536), 65536),           # keye2's decode step, a running slot
    ((16, 32768), 32768),          # glm5's decode step
    ((512, 49152), 4096),
    ((1024, 65536), 2048),
    ((512, 65536 + 128), 3456),    # 513 tiles: 27 of them
    ((512, 40000), 40000)])        # not whole tiles: _select pads first
def test_the_block_follows_the_shape(shape, block):
    assert D.select_block(*shape) == block
    assert block % D._TILE == 0 or block == shape[1]
    assert shape[1] % block == 0


def _eqns(jaxpr):
    """Every equation under a jaxpr, those of nested jaxprs behind their
    own."""
    for eqn in jaxpr.eqns:
        yield eqn
        yield from _under(eqn)


def _under(eqn):
    """Every equation of the jaxprs an equation holds (a loop's body, a
    ``cond``'s branches)."""
    found = []
    for v in eqn.params.values():
        for j in v if isinstance(v, (tuple, list)) else (v,):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                found += _eqns(j)
    return found


def test_a_pass_slices_blocks_and_compares_nothing_full_width():
    """At ``keye2-longctx-sessions``' chunk shape, traced and not run: the
    32 passes are one loop whose body holds a ``while`` of traced length
    that slices 4,096 columns, and no comparison inside the passes sees
    all 65,536."""
    b, t = 512, 65536

    def chunk(scores, dec):
        valid = jnp.arange(t)[None, :] <= (dec + jnp.arange(b))[:, None]
        return D.select_rows(scores, valid, 2048)

    jaxpr = jax.make_jaxpr(chunk)(
        jax.ShapeDtypeStruct((b, t), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32)).jaxpr
    passes = [e for e in _eqns(jaxpr)
              if e.primitive.name in ("scan", "while")
              and any(i.primitive.name == "while" for i in _under(e))]
    assert len(passes) == 1
    assert passes[0].primitive.name == "while" \
        or passes[0].params["length"] == 32
    inside = _under(passes[0])
    blocks, = [e for e in inside if e.primitive.name == "while"]
    slices = [e for e in _under(blocks)
              if e.primitive.name == "dynamic_slice"]
    assert [e.params["slice_sizes"] for e in slices] == [(b, 4096)]
    compares = [e for e in inside if e.primitive.name in ("ge", "gt", "eq")]
    assert compares
    for e in compares:
        assert all(t not in v.aval.shape for v in e.invars)


def _count(**labels):
    entry = get_registry().get("dsa_select_blocks_total")
    return 0.0 if entry is None else entry.labels(**labels).value


def test_the_series_counts_one_a_shape_whatever_the_layers():
    labels = dict(queries=str(B), columns=str(T), block=str(W), digit="1")
    whole = dict(queries=str(B), columns=str(T), block=str(T), digit="4")
    scores, valid = jnp.asarray(_scores(7)), jnp.asarray(_prefix(700))
    D._blocks_counter.cache_clear()
    before = _count(**labels), _count(**whole)

    def step(scores):
        for _ in range(3):                            # a stack's layers
            scores = scores + D.select_rows(scores, valid, K)
        return scores

    with mock.patch.multiple(D, **_sizes(True)):
        step(scores)                                  # the eager first call
        jax.jit(step)(scores)
    D.select_indices(scores, valid, K)
    assert _count(**labels) == before[0] + 1
    assert _count(**whole) == before[1] + 1
