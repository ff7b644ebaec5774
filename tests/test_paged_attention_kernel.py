"""The Pallas paged decode-attention kernel (ops/pallas/paged_attention.py),
interpreted on the CPU at small shapes.

Each case is held against two things: the gather route the kernel replaces
(``block_gqa_attention`` called as the decode step calls it) and a float32
``jax.numpy`` reference that walks the block table by hand. Tolerances:
float32 pools agree to 1e-5 (the same sums in another order); bfloat16 pools
to 2e-2, which is the kernel's probabilities rounded to bfloat16 before the
MXU (2^-9 relative, on outputs of order 1) plus the output's own rounding to
bfloat16 (2^-8) — what the chip's default matmul precision does to the
gather route as well. Whether Mosaic takes the kernel at the serving cell's
shapes is tests/test_chip_compile.py's question, not this file's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.nn.functional.decode_attention import (
    block_gqa_attention, block_gqa_decode_attention, decode_attention_path)
from paddle_tpu.ops.pallas.paged_attention import (paged_attention_decode,
                                                   supported)

BLOCK, HEAD_DIM, BLOCKS_PER_SEQ = 16, 128, 6
CAPACITY = BLOCK * BLOCKS_PER_SEQ
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _case(batch, heads, kv_heads, dtype, seed=0, tables="permuted"):
    """Pool, queries and a block table; the pool's last page is the
    batcher's scratch page, which idle slots point at."""
    rng = np.random.default_rng(seed)
    n_pages = batch * BLOCKS_PER_SEQ
    kc = rng.standard_normal((n_pages + 1, kv_heads, BLOCK, HEAD_DIM))
    vc = rng.standard_normal((n_pages + 1, kv_heads, BLOCK, HEAD_DIM))
    q = rng.standard_normal((batch, heads, HEAD_DIM))
    if tables == "permuted":
        bt = rng.permutation(n_pages).reshape(batch, BLOCKS_PER_SEQ)
    else:                      # every sequence starts on the same two pages
        bt = np.arange(n_pages).reshape(batch, BLOCKS_PER_SEQ)
        bt[:, :2] = bt[0, :2]
    return (jnp.asarray(q, dtype), jnp.asarray(kc, dtype),
            jnp.asarray(vc, dtype), bt.astype(np.int32))


def _reference(q, kc, vc, bt, kv_len):
    """float32, one sequence at a time, rows [0, kv_len) of its pages."""
    heads, kv_heads = q.shape[1], kc.shape[1]
    out = []
    for b, n in enumerate(kv_len):
        k = jnp.concatenate([kc[p] for p in bt[b]], axis=1)[:, :n]
        v = jnp.concatenate([vc[p] for p in bt[b]], axis=1)[:, :n]
        qg = q[b].reshape(kv_heads, heads // kv_heads, HEAD_DIM)
        s = jnp.einsum("grd,gsd->grs", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) / HEAD_DIM ** 0.5
        o = jnp.einsum("grs,gsd->grd", jax.nn.softmax(s, axis=-1),
                       v.astype(jnp.float32))
        out.append(o.reshape(heads, HEAD_DIM))
    return np.asarray(jnp.stack(out))


def _gather_route(q, kc, vc, bt, kv_len):
    """The general op as the decode step called it before the kernel. It
    writes this step's K/V row (zeros here) at row kv_len - 1 before it
    reads; the pools it returns are what the kernel is then given, so both
    read the same rows."""
    bsz, heads, _ = q.shape
    kv_heads = kc.shape[1]
    dec = jnp.asarray(kv_len - 1, jnp.int32)
    ones = jnp.ones((bsz,), jnp.int32)
    row = jnp.zeros((bsz, kv_heads, HEAD_DIM), q.dtype)
    out, kc2, vc2 = block_gqa_attention(
        q, row, row, kc, vc, jnp.zeros_like(ones), dec, ones,
        jnp.arange(bsz + 1, dtype=jnp.int32), jnp.asarray(bt))
    return (np.asarray(out._data.astype(jnp.float32)).reshape(q.shape),
            kc2._data, vc2._data)


def _kernel(q, kc, vc, bt, kv_len, **kw):
    out = paged_attention_decode(q, kc, vc, jnp.asarray(bt),
                                 jnp.asarray(kv_len, jnp.int32),
                                 interpret=True, **kw)
    assert out.shape == q.shape and out.dtype == q.dtype
    return np.asarray(out.astype(jnp.float32))


def _rope_tables():
    pos = np.arange(CAPACITY)[:, None] / 10000 ** (
        np.arange(HEAD_DIM // 2) / (HEAD_DIM // 2))
    return jnp.cos(pos), jnp.sin(pos)


# kv_len of 1, exactly a page, a page plus one, every page of the slot
LENGTHS = np.array([1, BLOCK, BLOCK + 1, CAPACITY], np.int32)


@pytest.mark.parametrize("tables", ["permuted", "shared_pages"])
@pytest.mark.parametrize("heads,kv_heads,dtype", [
    (32, 8, jnp.bfloat16), (4, 4, jnp.float32), (32, 8, jnp.float32)],
    ids=["gqa32_8_bf16", "mha4_4_f32", "gqa32_8_f32"])
def test_kernel_matches_gather_route_and_reference(heads, kv_heads, dtype,
                                                   tables):
    q, kc, vc, bt = _case(len(LENGTHS), heads, kv_heads, dtype,
                          tables=tables)
    if tables == "shared_pages":
        # a shared page is a prefix-cache hit: read by all, written by none,
        # so every sequence's own row lies past the shared pages
        lengths = np.maximum(LENGTHS, 2 * BLOCK + 1)
    else:
        lengths = LENGTHS
    gather, kc2, vc2 = _gather_route(q, kc, vc, bt, lengths)
    got = _kernel(q, kc2, vc2, bt, lengths)
    np.testing.assert_allclose(got, gather, atol=TOL[dtype], rtol=0)
    np.testing.assert_allclose(got, _reference(q, kc2, vc2, bt, lengths),
                               atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("pages_per_block", [1, 2, 4, BLOCKS_PER_SEQ])
def test_any_compute_block_width_gives_the_same_result(pages_per_block):
    """4 pages a block leaves the slot's 6 pages a ragged last block; 1 and
    the whole slot are the ends."""
    q, kc, vc, bt = _case(5, 8, 2, jnp.float32, seed=1)
    lengths = np.array([3, 32, 33, 70, CAPACITY], np.int32)
    got = _kernel(q, kc, vc, bt, lengths, pages_per_block=pages_per_block)
    np.testing.assert_allclose(got, _reference(q, kc, vc, bt, lengths),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("pages_per_block", [1, 4])
def test_rows_before_the_first_row_do_not_count(pages_per_block):
    """A window layer's table starts at the page its window starts in:
    ``kv_start`` rows of that page lie before the window. Without it the
    kernel is the one it was."""
    q, kc, vc, bt = _case(5, 8, 2, jnp.float32, seed=2)
    lengths = np.array([1, 9, 33, 70, CAPACITY], np.int32)
    starts = np.array([0, 8, 15, 3, BLOCK - 1], np.int32)
    got = _kernel(q, kc, vc, bt, lengths, pages_per_block=pages_per_block,
                  kv_start=jnp.asarray(starts))
    heads, kv_heads = q.shape[1], kc.shape[1]
    for b, (n, a) in enumerate(zip(lengths, starts)):
        k = jnp.concatenate([kc[p] for p in bt[b]], axis=1)[:, a:n]
        v = jnp.concatenate([vc[p] for p in bt[b]], axis=1)[:, a:n]
        qg = q[b].reshape(kv_heads, heads // kv_heads, HEAD_DIM)
        s = jnp.einsum("grd,gsd->grs", qg, k) / HEAD_DIM ** 0.5
        want = jnp.einsum("grs,gsd->grd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(got[b], np.asarray(want).reshape(
            heads, HEAD_DIM), atol=1e-5, rtol=0)
    zero = _kernel(q, kc, vc, bt, lengths, pages_per_block=pages_per_block,
                   kv_start=jnp.zeros(5, jnp.int32))
    np.testing.assert_allclose(
        zero, _kernel(q, kc, vc, bt, lengths,
                      pages_per_block=pages_per_block), atol=1e-6, rtol=0)


def test_idle_slot_reads_the_scratch_page_and_disturbs_nobody():
    """A slot with no request: ``dec_lens`` 0, every table entry the scratch
    page. Its output is ignored by the batcher; it has to be finite and the
    other slots' outputs untouched, wherever the idle slot sits."""
    q, kc, vc, bt = _case(4, 8, 2, jnp.float32, seed=2)
    lengths = np.array([40, 1, 96, 17], np.int32)
    want = _reference(q, kc, vc, bt, lengths)
    for idle in (0, 1, 3):
        bt_i, len_i = bt.copy(), lengths.copy()
        bt_i[idle], len_i[idle] = kc.shape[0] - 1, 1
        got = _kernel(q, kc, vc, bt_i, len_i)
        assert np.isfinite(got).all()
        live = [b for b in range(4) if b != idle]
        np.testing.assert_allclose(got[live], want[live], atol=1e-5, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_nothing_beyond_the_held_rows_decides_a_result(dtype):
    """NaN in every page a sequence does not hold, in every row past
    ``kv_len`` of its last page, and in the table entries past its last
    page (pointing at a poisoned page): the output is finite and equal to
    the clean pool's."""
    batch = 4
    q, kc, vc, _ = _case(batch, 8, 2, dtype, seed=3)
    bt = np.arange(batch * BLOCKS_PER_SEQ, dtype=np.int32).reshape(
        batch, BLOCKS_PER_SEQ)
    lengths = np.array([1, BLOCK, BLOCK + 5, CAPACITY - 3], np.int32)
    clean = _kernel(q, kc, vc, bt, lengths)

    held = np.zeros((kc.shape[0], BLOCK), bool)      # [page, row]
    for b, n in enumerate(lengths):
        rows = np.arange(CAPACITY) < n
        held[bt[b]] = rows.reshape(BLOCKS_PER_SEQ, BLOCK)
    poison = jnp.asarray(~held)[:, None, :, None]
    kc_p = jnp.where(poison, jnp.nan, kc)
    vc_p = jnp.where(poison, jnp.nan, vc)
    bt_p = bt.copy()
    for b, n in enumerate(lengths):
        bt_p[b, -(-n // BLOCK):] = kc.shape[0] - 1   # all NaN by now
    assert bool(jnp.isnan(kc_p[-1]).all())

    got = _kernel(q, kc_p, vc_p, bt_p, lengths)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


def test_lengths_outside_the_slot_are_clamped():
    """A parked slot's counter can run on (the decode block increments every
    slot on the device): 0 reads one row, past the capacity reads the slot."""
    q, kc, vc, bt = _case(2, 4, 4, jnp.float32, seed=4)
    got = _kernel(q, kc, vc, bt, np.array([0, CAPACITY + 9], np.int32))
    want = _reference(q, kc, vc, bt, np.array([1, CAPACITY], np.int32))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_decode_entry_takes_the_gather_route_off_the_chip():
    """On the CPU the entry writes its rows by the page and then attends
    the gathered timelines: output and pools are the general op's, bit for
    bit, so tier-1's token-equality tests see the numbers they saw."""
    q, kc, vc, bt = _case(3, 8, 2, jnp.float32, seed=5)
    assert decode_attention_path(kc.shape, kc.dtype, 8) == "gather"
    rng = np.random.default_rng(6)
    k = jnp.asarray(rng.standard_normal((3, 2, HEAD_DIM)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((3, 2, HEAD_DIM)), jnp.float32)
    cos, sin = _rope_tables()
    dec = jnp.asarray([0, 16, 50], jnp.int32)
    ones = jnp.ones((3,), jnp.int32)
    got = block_gqa_decode_attention(q, k, v, kc, vc, dec, jnp.asarray(bt),
                                     rope_cos=cos, rope_sin=sin)
    want = block_gqa_attention(q, k, v, kc, vc, 0 * ones, dec, ones,
                               jnp.arange(4, dtype=jnp.int32),
                               jnp.asarray(bt), rope_cos=cos, rope_sin=sin)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g._data),
                                      np.asarray(w._data))


def test_decode_entry_kernel_route_matches_the_gather_route(monkeypatch):
    """The entry's kernel branch (RoPE, the page writer, the kernel) against
    its gather branch, the kernel interpreted: what the chip runs against
    what tier-1 runs, from the same q, k, v and pool."""
    import functools

    from paddle_tpu.ops import pallas as pl_ops
    from paddle_tpu.ops.pallas import paged_attention as pa

    q, kc, vc, bt = _case(4, 8, 2, jnp.float32, seed=7)
    rng = np.random.default_rng(8)
    k = jnp.asarray(rng.standard_normal((4, 2, HEAD_DIM)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((4, 2, HEAD_DIM)), jnp.float32)
    cos, sin = _rope_tables()
    dec = jnp.asarray([0, 15, 16, CAPACITY - 1], jnp.int32)
    args = (q, k, v, kc, vc, dec, jnp.asarray(bt))
    want = block_gqa_decode_attention(*args, rope_cos=cos, rope_sin=sin)

    monkeypatch.setattr(pl_ops, "on_tpu", lambda: True)
    monkeypatch.setattr(pa, "paged_attention_decode", functools.partial(
        pa.paged_attention_decode, interpret=True))
    assert decode_attention_path(kc.shape, kc.dtype, 8) == "kernel"
    got = block_gqa_decode_attention(*args, rope_cos=cos, rope_sin=sin)
    np.testing.assert_allclose(np.asarray(got[0]._data),
                               np.asarray(want[0]._data), atol=1e-5, rtol=0)
    for g, w in zip(got[1:], want[1:]):          # the pools, written into
        np.testing.assert_array_equal(np.asarray(g._data),
                                      np.asarray(w._data))


def test_batcher_through_the_kernel_route_is_token_exact(monkeypatch):
    """``PagedContinuousBatcher(compile=True)`` over a Llama whose
    ``paged_decode_step`` takes the kernel (interpreted; the route forced
    as the chip would choose it) in the eager first call and in the
    compiled step, chunked prefill and slot reuse included: the same tokens
    as over the gather route, and the batcher's word says ``kernel``."""
    import functools

    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn.functional import decode_attention as da
    from paddle_tpu.inference.serving import PagedContinuousBatcher
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.ops.pallas import paged_attention as pa

    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny_config(
        hidden_size=512, num_attention_heads=4, num_key_value_heads=2))
    model.eval()
    assert model.config.head_dim == HEAD_DIM
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, (n,)) for n in (5, 19, 33)]

    def serve():
        b = PagedContinuousBatcher(model, max_batch=2, s_max=64,
                                   block_size=8, compile=True,
                                   prefill_chunk=16)
        rids = [b.submit(p, 9) for p in prompts]
        out = b.run_until_done()
        assert b.audit_pages() == 0
        return b.stats()["decode_attention_path"], [out[r] for r in rids]

    word, want = serve()
    assert word == "gather"
    monkeypatch.setattr(da, "decode_attention_path", lambda *a: "kernel")
    monkeypatch.setattr(pa, "paged_attention_decode", functools.partial(
        pa.paged_attention_decode, interpret=True))
    word, got = serve()
    assert word == "kernel"
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("shape,dtype,heads,ok", [
    ((3073, 8, 16, 128), jnp.bfloat16, 32, True),
    ((3073, 8, 16, 128), jnp.float32, 32, True),
    ((65, 2, 8, 128), jnp.bfloat16, 2, False),    # half a bf16 tile a page
    ((65, 2, 8, 128), jnp.float32, 2, True),
    ((65, 4, 16, 64), jnp.bfloat16, 4, False),    # half a lane row
    ((3073, 8, 16, 128), jnp.int8, 32, False),    # quantized: gather route
    ((65, 3, 16, 128), jnp.bfloat16, 32, False),  # heads do not group
])
def test_supported_pool_shapes(shape, dtype, heads, ok):
    assert supported(shape, dtype, heads) is ok
