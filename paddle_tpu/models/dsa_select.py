"""Learned sparse attention's selection (DeepSeek-V3.2's indexer), as the
families that run it share it (``glm_dsa`` over latent rows, ``keye`` over
per-head K/V pages): the index scores ``I[t, s] = sum_j w[t, j] relu(qI[t, j]
. kI[s])`` and the ``index_topk`` best rows of them, without a sort. One
algorithm, whatever the cache the kept rows are then read from.

A prefill chunk takes ``select_rows`` (a mask over the rows held: its
attention is masked), a decode step ``select_indices`` (the kept rows' row
numbers in rising order: its attention gathers them). Both keep every valid
row while ``k`` or fewer are valid, and under a tie at the k-th largest
score the lowest rows first, as ``lax.top_k`` orders them.

The threshold is found by counting, ``kth_largest_bits``, and what bounds a
pass of it is the rows held, not ``s_max``: ``_select`` reads off ``valid``
the last column that may hold a row, and a pass walks the columns a block of
``select_block`` at a time no further than that (a ``fori_loop`` whose length
is traced: one executable whatever a slot holds). The block follows the
shape. Where the scores are small enough to stay in VMEM across the passes
(a decode step's one or sixteen queries; a chunk's 512 by 32,768) it is all
of them, and a pass is one count as it always was: so the fork between a
chunk's search and a decode step's is still ``digit`` alone. What reads the
scores once (their bits, the mask, the tie test) stays full width.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def index_scores(q_i, keys, w_i):
    """I of queries q_i [..., n, D] (head weights w_i [..., n]) against
    keys [..., T, D] (a query's own, or one set for all): [..., T]."""
    sc = jnp.einsum("...hd,...td->...ht", q_i, keys,
                    preferred_element_type=F32)
    return jnp.sum(jax.nn.relu(sc) * w_i[..., None], -2)


# -- selection: the index_topk-th largest without a sort ----------------------

def _order_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    b = lax.bitcast_convert_type(x.astype(F32) + 0.0, jnp.int32)
    key = jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)
    return lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def kth_largest_bits(bits, k: int, digit: int = 1, live=None):
    """For every row of bits [..., T] uint32 the largest value v with
    ``count(bits >= v) >= k`` (the k-th largest; 0 where fewer than k
    entries are above 0), found ``digit`` bits a pass from the top: a pass
    counts the entries at or above each of ``2 ** digit - 1`` candidates
    and keeps the largest that k entries reach. ``digit`` 1 is bisection
    (32 passes of one compare and count over the scores: a chunk's, whose
    passes are bound by reading them); 4 is 8 passes of 15 (a decode
    step's, whose passes are bound by their count).

    What bounds a pass is ``live``, the rows held (a traced count of
    columns: every entry at or past it is 0): the pass's count walks
    ``ceil(live / W)`` blocks of ``W = select_block(...)`` columns, and
    since every candidate is above 0 the columns left unread could not
    have counted: the same threshold, bit for bit. Without ``live``, or
    where one block is all of T, a pass is one count over T."""
    steps = jnp.arange(1, 1 << digit, dtype=jnp.uint32)
    rows, t_cols = math.prod(bits.shape[:-1]), bits.shape[-1]
    w = t_cols
    if live is not None:
        w = select_block(rows, t_cols, bits.dtype.itemsize)
        _blocks_counter(rows, t_cols, w, digit)

    def count(blk, cands):                      # [..., W], [..., c] -> [..., c]
        return jnp.sum(blk[..., None, :] >= cands[..., None], -1,
                       dtype=jnp.int32)

    def body(i, t):
        shift = jnp.uint32(32 - digit) - i.astype(jnp.uint32) * digit
        cands = t[..., None] | (steps << shift)               # [..., c]
        if w == t_cols:
            n = count(bits, cands)
        else:
            n = lax.fori_loop(
                0, (live + w - 1) // w,
                lambda j, n: n + count(
                    lax.dynamic_slice_in_dim(bits, j * w, w, -1), cands),
                jnp.zeros(cands.shape, jnp.int32))
        best = jnp.sum(n >= k, -1).astype(jnp.uint32)   # reach is monotone
        return t | (best << shift)

    return lax.fori_loop(0, 32 // digit, body,
                         jnp.zeros(bits.shape[:-1], jnp.uint32))


_TILE = 128
_BLOCK_BYTES = 8 << 20
_RESIDENT_BYTES = 64 << 20


def select_block(b: int, t: int, itemsize: int = 4) -> int:
    """W, the columns a step of the threshold search reads of bits [b, t]:
    a function of the shape alone, as ``flash_tiling`` and
    ``grouped_experts_tiling`` are.

    All of t where t is not whole tiles (``_select`` pads first), and where
    the bits are ``_RESIDENT_BYTES`` or less: XLA then keeps them in VMEM
    across the passes (a v5e has 128 MiB; a chunk's 512 queries by 32,768
    rows are 64 MiB and a pass over them takes 30 us, a decode step's one
    or sixteen queries far less), and a loop's steps would cost more than
    the columns they leave out. Past that a pass reads HBM, and W is the
    widest whole-tile divisor of t whose block of all b queries is
    ``_BLOCK_BYTES`` or less: 4,096 columns of a chunk's 512 queries. A
    step costs some 3 us whatever it reads and 8 MB take 11, while a search
    rounds the rows held up to whole blocks, half a block on average: over
    documents of 16k to 48k rows 4 MB and 32 MB a block both read slower
    (my chip run, PR 43: PERF.md section 6)."""
    if t % _TILE or b * t * itemsize <= _RESIDENT_BYTES:
        return t
    tiles = t // _TILE
    n = max(1, min(tiles, _BLOCK_BYTES // (b * itemsize * _TILE)))
    while tiles % n:
        n -= 1
    return n * _TILE


@functools.lru_cache(maxsize=None)
def _blocks_counter(queries: int, columns: int, block: int,
                    digit: int) -> None:
    from ..observability.metrics import get_registry
    get_registry().counter(
        "dsa_select_blocks_total",
        "threshold searches lowered, by the scores' shape, the columns a "
        "step of a pass reads (all of them: one count a pass, whatever "
        "the rows held) and the bits a pass settles (trace time: once a "
        "shape, whatever the layers)",
        labelnames=("queries", "columns", "block", "digit"),
    ).labels(queries=str(queries), columns=str(columns), block=str(block),
             digit=str(digit)).inc()


def _select(scores, valid, k: int, digit: int = 1):
    """The k best valid rows of scores [B, T] as a mask [B, tiles, _TILE]
    over T padded to whole tiles: every valid row where k or fewer are
    valid; under a tie at the k-th largest score the lowest rows first, as
    ``lax.top_k`` orders them. A threshold by bisection, its passes no
    further than the last column ``valid`` has an entry in; ranks among
    tied rows (a running count by tile) only where some query has a tie."""
    b, t = scores.shape
    pad = -t % _TILE
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    tiles = (t + pad) // _TILE
    bits = jnp.where(valid, _order_bits(scores), jnp.uint32(0))
    live = jnp.max(jnp.where(valid, jnp.arange(1, t + pad + 1), 0))
    thr = kth_largest_bits(bits, k, digit, live)[:, None]
    at_least = valid & (bits >= thr)

    def break_ties():
        above = valid & (bits > thr)
        tie = (valid & (bits == thr)).reshape(b, tiles, _TILE)
        need = k - jnp.sum(above, -1, dtype=jnp.int32)
        tie_in = jnp.cumsum(tie, -1, dtype=jnp.int32)
        before = jnp.cumsum(tie_in[..., -1], -1) - tie_in[..., -1]
        rank = tie_in + before[..., None]
        return (above.reshape(b, tiles, _TILE)
                | (tie & (rank <= need[:, None, None]))).reshape(b, -1)

    tied = jnp.any(jnp.sum(at_least, -1, dtype=jnp.int32) > k)
    return lax.cond(tied, break_ties, lambda: at_least).reshape(
        b, tiles, _TILE)


def select_rows(scores, valid, k: int):
    """``_select`` as a mask [B, T]."""
    return _select(scores, valid, k).reshape(scores.shape[0], -1)[
        :, :scores.shape[1]]


def select_indices(scores, valid, k: int):
    """``_select`` as (rows [B, k] int32 in rising order, kept [B, k] bool:
    False on the slots past the valid rows). No sort and no scatter: the
    j-th kept row is found by tile."""
    sel = _select(scores, valid, k, digit=4)
    tiles = sel.shape[1]
    per_tile = jnp.sum(sel, -1, dtype=jnp.int32)            # [B, tiles]
    upto = jnp.cumsum(per_tile, -1)
    j = jnp.arange(k, dtype=jnp.int32)
    tile_j = jnp.sum(upto[:, None, :] <= j[None, :, None], -1,
                     dtype=jnp.int32)                       # [B, k]
    kept = j[None, :] < upto[:, -1:]
    tile_j = jnp.minimum(tile_j, tiles - 1)
    rank = j[None, :] - (jnp.take_along_axis(upto, tile_j, 1)
                         - jnp.take_along_axis(per_tile, tile_j, 1))
    bits_j = jnp.take_along_axis(sel, tile_j[..., None], 1)  # [B, k, TILE]
    seen = jnp.cumsum(bits_j, -1, dtype=jnp.int32)
    pos = jnp.argmax(bits_j & (seen == rank[..., None] + 1), -1)
    rows = tile_j * _TILE + pos.astype(jnp.int32)
    return jnp.where(kept, rows, 0), kept
