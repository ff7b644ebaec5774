"""The Llama family's page-granular K/V writers
(incubate/nn/functional/decode_attention.py) against the row scatter they
replace where the call site knows the shape of the write.

``write_page_rows`` (the decode step: one row a sequence) and
``_write_page_run`` (a prompt's chunk: one run of rows of one sequence) read
whole pages, change them and scatter them back along the pool's first axis;
``_scatter_paged`` indexes axes 0 and 2. Held here: the pools are equal bit
for bit on every page but scratch (the pool's last page, which parked slots
and unbacked table entries name and nothing reads). That the page writers
leave the pool's layout alone on the chip is tests/test_chip_compile.py's
question; that the batcher never has two sequences write one page is
tests/test_paged_page_invariant.py's.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.nn.functional.decode_attention import (
    _gather_paged, _scatter_paged, _write_page_run, block_gqa_attention,
    block_gqa_decode_attention, decode_kv_writer, write_page_rows)

BLOCK, KV, HEAD_DIM = 16, 2, 8
DTYPES = pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                                 ids=["bf16", "f32"])


def _pools(n_pages, dtype, seed):
    """K and V pools of n_pages and the scratch page after them."""
    rng = np.random.default_rng(seed)
    shape = (n_pages + 1, KV, BLOCK, HEAD_DIM)
    return (jnp.asarray(rng.standard_normal(shape), dtype),
            jnp.asarray(rng.standard_normal(shape), dtype))


def _timelines(kc, vc, table):
    """One sequence's pages as a timeline a head, [KV, S_kv, D], of K and
    of V: how the general op reads them for a chunk."""
    gk, gv, _ = _gather_paged(kc, vc, table[None], KV)
    return gk[0], gv[0]


def _rows(n, dtype, seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((n, KV, HEAD_DIM)), dtype),
            jnp.asarray(rng.standard_normal((n, KV, HEAD_DIM)), dtype))


def _equal_but_scratch(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g[:-1].astype(jnp.float32)),
                                      np.asarray(w[:-1].astype(jnp.float32)))


def _page_step(kc, vc, bt, dec, k, v):
    """The decode entry's write."""
    page = jnp.take_along_axis(bt, (dec // BLOCK)[:, None], axis=1)[:, 0]
    return (write_page_rows(kc, page, dec % BLOCK, k),
            write_page_rows(vc, page, dec % BLOCK, v))


def _row_step(kc, vc, bt, dec, k, v):
    return _scatter_paged(kc, vc, bt, jnp.arange(bt.shape[0]), dec, k, v,
                          BLOCK)


# three slots of four pages; what each case puts where
DECODE_CASES = {
    "offset_0": ([0, 16, 48], "ordered", ()),
    "last_row_of_a_page": ([15, 31, 63], "ordered", ()),
    "mixed_offsets_permuted_tables": ([3, 29, 40], "permuted", ()),
    "parked_slots_all_name_scratch": ([0, 21, 0], "permuted", (0, 2)),
    "every_slot_parked": ([0, 0, 0], "ordered", (0, 1, 2)),
}


def _tables(kind, parked, slots=3, pages_per_seq=4, seed=0):
    n_pages = slots * pages_per_seq
    bt = np.arange(n_pages) if kind == "ordered" \
        else np.random.default_rng(seed).permutation(n_pages)
    bt = bt.reshape(slots, pages_per_seq).astype(np.int32)
    for slot in parked:
        bt[slot] = n_pages                     # the scratch page
    return n_pages, jnp.asarray(bt)


@DTYPES
@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_page_writer_equals_the_row_scatter(case, dtype):
    dec, kind, parked = DECODE_CASES[case]
    n_pages, bt = _tables(kind, parked)
    kc, vc = _pools(n_pages, dtype, seed=1)
    k, v = _rows(3, dtype, seed=2)
    dec = jnp.asarray(dec, jnp.int32)
    got = _page_step(kc, vc, bt, dec, k, v)
    _equal_but_scratch(got, _row_step(kc, vc, bt, dec, k, v))
    # and something was written, unless every slot is parked
    changed = not np.array_equal(np.asarray(got[0][:-1], np.float32),
                                 np.asarray(kc[:-1], np.float32))
    assert changed == (len(parked) < 3)


@DTYPES
def test_consecutive_steps_cross_a_page_boundary(dtype):
    """Rows 13 .. 18 of one slot and 29 .. 34 of another, a step at a time,
    each writer carrying its own pools: the row before the boundary stays
    when the next page takes the row after it."""
    n_pages, bt = _tables("permuted", (1,), seed=3)
    page_pools = row_pools = _pools(n_pages, dtype, seed=4)
    for step in range(6):
        k, v = _rows(3, dtype, seed=10 + step)
        dec = jnp.asarray([13 + step, 0, 29 + step], jnp.int32)
        page_pools = _page_step(*page_pools, bt, dec, k, v)
        row_pools = _row_step(*row_pools, bt, dec, k, v)
        _equal_but_scratch(page_pools, row_pools)


def test_page_writer_casts_rows_to_the_pools_dtype():
    n_pages, bt = _tables("ordered", ())
    kc, vc = _pools(n_pages, jnp.bfloat16, seed=5)
    k, v = _rows(3, jnp.float32, seed=6)
    dec = jnp.asarray([1, 17, 33], jnp.int32)
    _equal_but_scratch(_page_step(kc, vc, bt, dec, k, v),
                       _row_step(kc, vc, bt, dec, k, v))


# one slot of 20 pages (320 rows): (rows in the run, first row)
PAGES_PER_SEQ = 20
CHUNK_CASES = {
    "t256_dec0": (256, 0),
    "t256_block_aligned": (256, 48),
    "t256_not_aligned": (256, 37),
    "t256_ends_on_the_last_page": (256, 64),
    "t100_dec0": (100, 0),
    "t100_block_aligned": (100, 32),
    "t100_not_aligned": (100, 37),
    "t100_ends_on_the_last_page": (100, 220),
    "t1_last_row": (1, 319),
}


def _chunk_tables(backed, seed=7):
    """One sequence's table over a pool three times its size; entries past
    ``backed`` pages name scratch, as a slot's not yet grown into."""
    n_pages = 3 * PAGES_PER_SEQ
    bt = np.random.default_rng(seed).permutation(n_pages)[:PAGES_PER_SEQ]
    bt = bt.astype(np.int32)
    bt[backed:] = n_pages
    return n_pages, jnp.asarray(bt)


@DTYPES
@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_chunk_writer_equals_the_row_scatter(case, dtype):
    t, dec = CHUNK_CASES[case]
    n_pages, table = _chunk_tables(PAGES_PER_SEQ)
    kc, vc = _pools(n_pages, dtype, seed=8)
    k, v = _rows(t, dtype, seed=9)
    tk, tv = _timelines(kc, vc, table)
    kc2, line_k = _write_page_run(kc, table, tk, dec, k)
    vc2, line_v = _write_page_run(vc, table, tv, dec, v)
    want = _scatter_paged(kc, vc, table[None], jnp.zeros((t,), jnp.int32),
                          dec + jnp.arange(t), k, v, BLOCK)
    _equal_but_scratch((kc2, vc2), want)
    # the timeline handed to the scores is the pages' rows, the run in it
    for line, rows in zip((line_k, line_v), _timelines(*want, table)):
        np.testing.assert_array_equal(np.asarray(line, np.float32),
                                      np.asarray(rows, np.float32))


@DTYPES
def test_chunk_writer_over_a_table_backed_in_part(dtype):
    """A slot that holds 6 of its 20 pages (``ondemand``) takes a run that
    ends inside the sixth: the 14 entries that name scratch are duplicates
    in the scatter along the first axis, and disturb no backed page."""
    n_pages, table = _chunk_tables(6)
    kc, vc = _pools(n_pages, dtype, seed=11)
    k, v = _rows(50, dtype, seed=12)
    tk, tv = _timelines(kc, vc, table)
    got = (_write_page_run(kc, table, tk, 40, k)[0],
           _write_page_run(vc, table, tv, 40, v)[0])
    want = _scatter_paged(kc, vc, table[None], jnp.zeros((50,), jnp.int32),
                          40 + jnp.arange(50), k, v, BLOCK)
    _equal_but_scratch(got, want)


def _rope_tables(rows):
    pos = np.arange(rows)[:, None] / 10000 ** (
        np.arange(HEAD_DIM // 2) / (HEAD_DIM // 2))
    return jnp.cos(pos), jnp.sin(pos)


@pytest.mark.parametrize("t,dec,enc", [(256, 37, 0), (100, 0, 100),
                                       (33, 64, 0)],
                         ids=["chunk_256_at_37", "whole_prompt_100",
                              "chunk_33_at_64"])
def test_one_sequence_through_the_general_op_equals_the_row_route(t, dec,
                                                                  enc):
    """``block_gqa_attention`` with one sequence (a chunk, or a whole
    prompt in encoder mode) writes by the page; the same call with a second,
    empty sequence beside it (``bsz`` 2) keeps the row scatter and the
    gather. Pools equal bit for bit off scratch, outputs to float32
    rounding (one timeline for all tokens against one a token)."""
    heads = 4
    n_pages, table = _chunk_tables(PAGES_PER_SEQ)
    kc, vc = _pools(n_pages, jnp.float32, seed=13)
    k, v = _rows(t, jnp.float32, seed=14)
    q = jnp.asarray(np.random.default_rng(15).standard_normal(
        (t, heads, HEAD_DIM)), jnp.float32)
    cos, sin = _rope_tables(PAGES_PER_SEQ * BLOCK)

    def call(bsz):
        pad = [0] * (bsz - 1)
        bt = jnp.concatenate([table[None]] + [
            jnp.full((1, PAGES_PER_SEQ), n_pages, jnp.int32) for _ in pad])
        return block_gqa_attention(
            q, k, v, kc, vc, jnp.asarray([enc] + pad, jnp.int32),
            jnp.asarray([dec] + pad, jnp.int32),
            jnp.asarray([t] + pad, jnp.int32),
            jnp.asarray([0, t] + [t] * len(pad), jnp.int32), bt,
            block_size=BLOCK, rope_cos=cos, rope_sin=sin)

    got, want = call(1), call(2)
    np.testing.assert_allclose(np.asarray(got[0]._data),
                               np.asarray(want[0]._data), atol=1e-5, rtol=0)
    _equal_but_scratch([p._data for p in got[1:]],
                       [p._data for p in want[1:]])


def test_decode_entry_writes_by_the_page_and_an_int8_pool_by_the_row():
    """The entry's pools equal the general op's (the row scatter) off
    scratch with parked slots among the running; the word for a pool is
    ``page`` where it is float and ``row`` where it is int8, and an int8
    pool still goes to the general op, which refuses it without scales."""
    n_pages, bt = _tables("permuted", (1,), seed=16)
    kc, vc = _pools(n_pages, jnp.float32, seed=17)
    k, v = _rows(3, jnp.float32, seed=18)
    q = jnp.asarray(np.random.default_rng(19).standard_normal(
        (3, 4, HEAD_DIM)), jnp.float32)
    cos, sin = _rope_tables(4 * BLOCK)
    dec = jnp.asarray([15, 0, 16], jnp.int32)
    ones = jnp.ones((3,), jnp.int32)
    got = block_gqa_decode_attention(q, k, v, kc, vc, dec, bt,
                                     rope_cos=cos, rope_sin=sin)
    want = block_gqa_attention(q, k, v, kc, vc, 0 * ones, dec, ones,
                               jnp.arange(4, dtype=jnp.int32), bt,
                               rope_cos=cos, rope_sin=sin)
    np.testing.assert_array_equal(np.asarray(got[0]._data)[[0, 2]],
                                  np.asarray(want[0]._data)[[0, 2]])
    _equal_but_scratch([p._data for p in got[1:]],
                       [p._data for p in want[1:]])
    assert decode_kv_writer(jnp.float32) == "page"
    assert decode_kv_writer(jnp.bfloat16) == "page"
    assert decode_kv_writer(jnp.int8) == "row"
    with pytest.raises(ValueError, match="int8 cache pool but no quant"):
        block_gqa_decode_attention(q, k, v, kc.astype(jnp.int8),
                                   vc.astype(jnp.int8), dec, bt)
