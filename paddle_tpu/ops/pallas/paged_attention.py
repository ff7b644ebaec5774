"""Pallas paged decode attention (TPU): one query token a sequence against
the pages that sequence holds, read straight from the page pool.

The serving decode step's attention (reference: the decode branch of
block_multihead_attention's CUDA kernel, which walks the block table). The
XLA route (``decode_attention._gather_paged``) assembles every slot's whole
``blocks_per_seq * block_size`` timeline from the pool in every layer,
whatever is cached; here the pool stays in HBM and the kernel walks the
block table:

  * ``kv_len`` and the block table arrive as scalar prefetch (SMEM), so page
    addresses are known before any vector work;
  * the grid runs over sequences; per sequence a loop runs over compute
    blocks of ``pages_per_block`` pages and **stops at the last page the
    sequence holds** — time follows cached tokens, not slot capacity;
  * a page of the pool ``[n_pages, KV, block, D]`` holds all kv heads
    contiguously, so one asynchronous copy a page brings K (another V) into
    one of two VMEM buffers while the other is computed on; the first block
    of the next sequence is fetched under the last block of this one;
  * grouped heads stay unexpanded: the ``H / KV`` query heads of a kv head
    are the rows of one matmul against that head's rows of the block;
  * online softmax across blocks; scores, running maximum, running sum and
    the output accumulator are float32, q / K / V go to the MXU as stored.

Rows past ``kv_len`` (the tail of the last page, pages of the block that the
sequence does not hold, whatever an earlier block left in the buffer) never
reach a result: their scores are replaced before the maximum, and their V
rows are zeroed before the matmul, so not even a NaN there can.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_call
from .flash_attention import NEG_INF, _idiv, _imod

# Tokens a compute block covers, and what its four buffers (K and V, two
# each) may take of VMEM. On the v5e, 16 launches over 32 slots of 16-token
# pages took 3.3 ms at 512 tokens a block against 3.7 at 128 and 5.6 at 64
# with 64-768 rows cached, and 15.0 against 24.2 and 41.9 ms with 4,096
# (573 GB/s of K/V): fewer, larger blocks amortise the waits on the copies.
_BLOCK_TOKENS = 512
_BUFFER_BYTES = 8 << 20


def supported(pool_shape, pool_dtype, q_heads: int) -> bool:
    """A float pool whose pages Mosaic takes as they lie: one kv head's
    ``[block, D]`` of a page is whole (8, 128) tiles of 32-bit words."""
    _, kvh, block, hd = pool_shape
    dtype = jnp.dtype(pool_dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize > 4:
        return False
    rows_a_tile = 8 * (4 // dtype.itemsize)
    return hd % 128 == 0 and block % rows_a_tile == 0 and q_heads % kvh == 0


def _decode_kernel(lens_ref, bt_ref, *refs, batch, blocks_per_seq,
                   pages_per_block, scale, from_row=False):
    # ``from_row``: a third scalar-prefetch array, the first row of each
    # sequence's table that counts (a window layer's table starts at the
    # page the window starts in, not at the row)
    start_ref = refs[0] if from_row else None
    q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, slot_ref, m_ref, l_ref, \
        acc_ref = refs[1:] if from_row else refs
    b = pl.program_id(0)
    n_pool, kvh, bs, hd = k_hbm.shape
    rep = q_ref.shape[1]
    ppb = pages_per_block
    span = ppb * bs                       # tokens a compute block covers

    def held(seq):
        """(rows, pages) of a sequence's timeline; an idle slot holds one
        row, and nothing may reach past the slot's capacity."""
        n = jnp.clip(lens_ref[seq], 1, blocks_per_seq * bs)
        return n, _idiv(n + (bs - 1), bs)

    def page_copies(seq, blk, slot):
        """(held, K copy, V copy) for each page of one compute block."""
        _, n_pages = held(seq)
        out = []
        for p in range(ppb):
            j = blk * ppb + p
            page = bt_ref[seq * blocks_per_seq
                          + jnp.minimum(j, blocks_per_seq - 1)]
            page = jnp.clip(page, 0, n_pool - 1)
            out.append((j < n_pages,
                        pltpu.make_async_copy(k_hbm.at[page],
                                              kbuf.at[slot, p],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[page],
                                              vbuf.at[slot, p],
                                              sems.at[1, slot])))
        return out

    def start(seq, blk, slot, also=True):
        for ok, k_copy, v_copy in page_copies(seq, blk, slot):
            @pl.when(jnp.logical_and(ok, also))
            def _():
                k_copy.start()
                v_copy.start()

    def wait(seq, blk, slot):
        for ok, k_copy, v_copy in page_copies(seq, blk, slot):
            @pl.when(ok)
            def _():
                k_copy.wait()
                v_copy.wait()

    n, _ = held(b)
    n_blocks = _idiv(n + (span - 1), span)

    @pl.when(b == 0)
    def _():
        slot_ref[0] = 0
        start(0, 0, 0)

    slot0 = slot_ref[0]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(i, carry):
        slot = _imod(slot0 + i, 2)
        # fetch what is computed next under this block's compute: this
        # sequence's next block, or the next sequence's first
        last = i + 1 >= n_blocks
        nxt_seq = jnp.where(last, b + 1, b)
        start(jnp.minimum(nxt_seq, batch - 1), jnp.where(last, 0, i + 1),
              1 - slot, also=nxt_seq < batch)
        wait(b, i, slot)

        base = i * span
        across = base + lax.broadcasted_iota(jnp.int32, (rep, span), 1)
        down = base + lax.broadcasted_iota(jnp.int32, (span, hd), 0)
        in_len, v_live = across < n, down < n
        if from_row:
            in_len &= across >= start_ref[b]
            v_live &= down >= start_ref[b]
        for g in range(kvh):
            q = q_ref[g]                                   # [rep, D]
            k = kbuf[slot, :, g].reshape(span, hd)
            v = vbuf[slot, :, g].reshape(span, hd)
            if q.dtype != k.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(in_len, s, NEG_INF)
            m_prev = m_ref[g]                              # [rep, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[g] = alpha * l_ref[g] + p.sum(axis=-1, keepdims=True)
            v = jnp.where(v_live, v, jnp.zeros_like(v))
            acc_ref[g] = alpha * acc_ref[g] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new
        return carry

    lax.fori_loop(0, n_blocks, block, None)
    slot_ref[0] = _imod(slot0 + n_blocks, 2)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret",
                                             "scale"))
def _decode_call(kv_len, block_tables, q, key_cache, value_cache,
                 kv_start=None, *, pages_per_block, interpret, scale=None):
    """The launch, jitted on its own: a step calls it once a layer, and
    the eager first call of a ``to_static`` step would otherwise trace,
    lower and compile the kernel anew for every layer (57 s of set-up at
    16 layers on the v5e). Without ``kv_start`` the kernel is the one it
    was before there was one."""
    from_row = kv_start is not None
    scalars = (kv_len, block_tables) + ((kv_start,) if from_row else ())
    batch, kvh, rep, hd = q.shape
    bs = key_cache.shape[2]
    blocks_per_seq = block_tables.shape[0] // batch
    buf = (2, pages_per_block, kvh, bs, hd)
    row = pl.BlockSpec((None, kvh, rep, hd), lambda b, *_: (b, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_decode_kernel, batch=batch,
                          blocks_per_seq=blocks_per_seq,
                          pages_per_block=pages_per_block,
                          scale=scale or 1.0 / float(hd) ** 0.5,
                          **({"from_row": True} if from_row else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(batch,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM(buf, key_cache.dtype),
                pltpu.VMEM(buf, value_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),       # (K | V, buffer)
                pltpu.SMEM((1,), jnp.int32),           # buffer in turn
                pltpu.VMEM((kvh, rep, 1), jnp.float32),
                pltpu.VMEM((kvh, rep, 1), jnp.float32),
                pltpu.VMEM((kvh, rep, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # sequences in order: each one's first block is fetched by the one
        # before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention_decode",
    )(*scalars, q, key_cache, value_cache)


def paged_attention_decode(q, key_cache, value_cache, block_tables, kv_len,
                           *, pages_per_block=None, interpret=False,
                           scale=None, kv_start=None):
    """q [B, H, D] (after RoPE) against the pool ``[n_pages, KV, block, D]``
    through ``block_tables [B, blocks_per_seq]``; ``kv_len [B]`` rows of
    each sequence count, this step's row among them (it is in the pool
    already). ``scale`` multiplies the scores (``D ** -0.5`` where None: a
    caller whose rows are wider than its heads says so). ``kv_start [B]``,
    where given: rows of the table before it do not count (a layer that
    keeps a window hands in the pages the window lies in: its first row is
    somewhere in the first of them; ``kv_start < kv_len``, within the
    table's first page). Returns [B, H, D] in q's dtype."""
    batch, heads, hd = q.shape
    _, kvh, bs, _ = key_cache.shape
    if pages_per_block is None:
        page_bytes = kvh * bs * hd * key_cache.dtype.itemsize
        pages_per_block = max(1, min(block_tables.shape[1],
                                     _BLOCK_TOKENS // bs,
                                     _BUFFER_BYTES // (4 * page_bytes)))
    out = kernel_call(
        functools.partial(_decode_call, pages_per_block=pages_per_block,
                          interpret=interpret, scale=scale),
        kv_len.astype(jnp.int32), block_tables.astype(jnp.int32).reshape(-1),
        q.reshape(batch, kvh, heads // kvh, hd), key_cache, value_cache,
        *(() if kv_start is None else (kv_start.astype(jnp.int32),)))
    return out.reshape(batch, heads, hd)
