"""Pallas flash attention v2 (TPU).

The reference's fused attention tier: third_party/flashattn dynloaded by
phi/backends/dynload/flashattn.cc, used via phi/kernels/gpu/
flash_attn_kernel.cu:128 (FlashAttnKernel + FlashAttnUnpaddedKernel: causal,
dropout, attn_mask, varlen, GQA). TPU-native equivalent: blockwise
streaming-softmax kernels where BOTH Q and K/V move in tiles — the K/V
stream rides the grid's innermost dimension, so VMEM use is bounded by the
tiling, constant in sequence length (v1 pinned whole-sequence K/V per
program and broke at long context).

How much a grid step holds, and in which type the MXU gets it:
  * ``flash_tiling`` computes each kernel's tiling from the call's shape (a
    pure function: no sweep, no flag, the same answer every run). A grid
    step holds up to 2,048 rows each of Q and K — at 2,048 tokens one head's
    whole sequence, grid (b*h, 1, 1) — because a step of the pipeline costs
    a third of a microsecond whatever it computes; its body walks score
    tiles of ``sub_q x sub_k`` in ``fori_loop``s whose bound is the diagonal,
    so causal work above it costs neither a grid step nor a loop step, and
    only the score tiles the diagonal crosses pay for the compare.
  * operands go to the MXU in the type they arrive in (bfloat16 inputs:
    bfloat16 products accumulated in float32; ``p`` and ``ds`` are rounded
    to the inputs' type before their products, as the dense path's
    ``probs.astype`` does); softmax statistics, ``lse``, ``delta`` and every
    accumulator stay float32. float32 inputs multiply in float32.
  * score tiles are held keys-down (``s^T = k @ q^T``): the softmax reduces
    over sublanes, the row statistics are lanes-wide vectors (the form
    ``lse`` is stored in), ``p^T`` and ``ds^T`` enter their products as they
    are, and a head of 64 stands over whole 128-lane runs of the sequence
    (the kernels read K^T / V^T and write O^T / dQ^T; XLA turns them in the
    copies it makes around the call anyway).

Feature surface:
  * causal masking — see above; grid tiles wholly above the diagonal (only
    past 2,048 tokens) are skipped (`pl.when`) and their index maps alias
    the diagonal tile so the pipeline never DMAs them
  * GQA natively: K/V tiles are addressed per kv-head via the index map
    (no host-side head expansion; group mapping is pure index arithmetic)
  * additive attention mask, streamed in [block_q, block_k] tiles
  * varlen/padding via per-batch kv_seqlens (rows and cols >= len masked);
    arbitrary sequence lengths are handled by padding (``padded_len``) and
    masking the tail through the same path
  * dropout on the attention probabilities using the in-kernel TPU PRNG,
    regenerated bit-exactly in the backward kernels from (seed, head, and
    the score tile's coordinates): under dropout all three kernels share
    one tiling

Forward saves only (out, logsumexp); backward recomputes scores blockwise
(flash-attention-2 two-pass: a dq kernel gridded like the forward, and a
dk/dv kernel gridded over K/V tiles with the Q stream innermost).

Layout: [b*h, s, d] head-major at the kernels' boundary (callers reshape
from the framework's [b, s, h, d]).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_call

# the smallest tile and the granularity of every other: what a caller who
# names one block gets for the other, and what every call ran before PR 37
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
_LANES = 128  # a vreg's lanes: the granularity of every tile

# flash_tiling's constants. Mosaic scopes a kernel 16 MiB of a v5e's VMEM by
# default; the tiling counts what vmem_bytes names and leaves the rest to
# what the compiler keeps beside it.
VMEM_BUDGET = 13 * 2 ** 20
MAX_BLOCK = 2048          # rows of Q, and of K, one grid step may hold
SUB_Q, SUB_K = 512, 512   # the body's score tile: queries x keys


class Tile(NamedTuple):
    block_q: int   # rows of Q a grid step holds
    block_k: int   # rows of K and V a grid step holds
    sub_q: int     # the score tile the body computes at a time: its rows
    sub_k: int     # and its columns


class Tilings(NamedTuple):
    fwd: Tile
    dq: Tile
    dkv: Tile


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _idiv(a, b):
    """Truncating integer division for index maps and kernel scalars.

    Python ``//`` on a traced i32 lowers to floor-division's sign-correction
    graph (sign/rem/select wrapped in a closed_call), which the Mosaic
    scalar core rejects; every quantity here is nonnegative, so truncating
    ``lax.div`` is exact and lowers to one scalar op."""
    if hasattr(a, "dtype"):
        return jax.lax.div(a, jnp.int32(b))
    return a // b


def _imod(a, b):
    if hasattr(a, "dtype"):
        return jax.lax.rem(a, jnp.int32(b))
    return a % b


def _keep_mask(seed_ref, b, qi, ki, nq, nk, q_start, k_start, shape,
               dropout_p, tpu_prng):
    """Deterministic keep mask: the bwd kernels regenerate it bit-exactly.

    TPU compile path: the hardware PRNG seeded with (seed, tile) where tile
    linearizes (head, q-tile, k-tile) — libtpu's prng_set_seed accepts at
    most TWO seed values, so the coordinates fold into one index that the
    forward and both backward kernels compute identically. Interpret path
    (no prng_seed lowering on CPU): a counter-based murmur3-finalizer hash
    of the ABSOLUTE (query, key) position, so any tile decomposition
    reproduces the same mask. ``shape`` is (keys, queries): every kernel
    holds its score tile keys-down and draws the mask the same way."""
    if tpu_prng:
        pltpu.prng_seed(seed_ref[0], (b * nq + qi) * nk + ki)
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    else:
        queries = (q_start + _iota(shape, 1)).astype(jnp.uint32)
        keys = (k_start + _iota(shape, 0)).astype(jnp.uint32)
        b_u = jnp.uint32(0) + b.astype(jnp.uint32) if hasattr(b, "astype") \
            else jnp.uint32(b)
        seed_u = seed_ref[0].astype(jnp.uint32)
        x = (queries * jnp.uint32(0x9E3779B9)) ^ (keys
                                                  * jnp.uint32(0x85EBCA6B))
        x = x ^ (b_u * jnp.uint32(0xC2B2AE35)) ^ (seed_u
                                                  * jnp.uint32(0x27D4EB2F))
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16)
        bits = x
    thresh = jnp.uint32(min(int(dropout_p * (2 ** 32)), 2 ** 32 - 1))
    return bits >= thresh


def _split_scale(scale):
    """(factor folded into q, factor left for the float32 scores).

    A power of two (1/8 at d = 64) multiplies a bfloat16 q exactly, so it
    rides the operand and costs nothing per score; any other scale (d = 128)
    stays on the float32 scores, where today's kernel applied it."""
    if math.frexp(scale)[0] == 0.5:
        return scale, 1.0
    return 1.0, scale


def _nt(a, b):
    """a @ b^T, float32 out: operands go to the MXU in the type they have."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scores_t(q, k, mask_ref, rows, cols, sl, q0, k0, s_scale, *, diagonal,
              has_mask, has_seqlens):
    """One score tile, keys down and queries across (``k @ q^T``), with every
    mask applied: its first row is key ``k0``, its first column query ``q0``.
    Held this way the softmax reduces over sublanes, the row statistics are
    lanes-wide vectors (the form ``lse`` is stored in), and ``p^T`` and
    ``ds^T`` enter their products as they are. ``diagonal`` is static: tiles
    wholly under the diagonal skip the compare."""
    st = _nt(k, q)
    if s_scale != 1.0:
        st = st * s_scale
    if has_mask:
        st = st + mask_ref[0, 0, rows, cols].astype(jnp.float32).T
    if diagonal or has_seqlens:
        keys = k0 + _iota(st.shape, 0)
        queries = q0 + _iota(st.shape, 1)
        if diagonal:
            st = jnp.where(keys <= queries, st, NEG_INF)
        if has_seqlens:
            st = jnp.where((keys < sl) & (queries < sl), st, NEG_INF)
    return st


def _sub(i, n, block):
    """Slice sub-tile ``i`` of ``n`` rows (or lanes) from a grid tile of
    ``block``; the whole of it where there is one (Mosaic cannot prove a
    64-lane offset aligned)."""
    if n == block:
        return slice(None)
    return pl.ds(pl.multiple_of(i * n, n), n)


def _k_extent(q0, k_base, tile, causal):
    """Sub-tiles of this grid step's K tile that a score tile whose first
    query is ``q0`` visits, as (wholly under the diagonal, visited): the
    body's loop bound IS the diagonal, so nothing above it is computed."""
    n_all = tile.block_k // tile.sub_k
    if not causal:
        return n_all, n_all
    ck = tile.sub_k
    last_q = q0 + tile.sub_q - 1 - k_base
    n_vis = jnp.minimum(_idiv(jnp.maximum(last_q + ck, 0), ck), n_all)
    n_full = jnp.minimum(_idiv(jnp.maximum(q0 + 1 - k_base, 0), ck), n_vis)
    return n_full, n_vis


def _q_extent(k0, q_base, tile, causal):
    """The dkv kernel's mirror of ``_k_extent``: of this grid step's Q
    tile, (first sub-tile visited, first one wholly under the diagonal)."""
    n_all = tile.block_q // tile.sub_q
    if not causal:
        return 0, 0
    cq = tile.sub_q
    x = k0 - q_base
    r_vis = jnp.minimum(_idiv(jnp.maximum(x, 0), cq), n_all)
    r_full = jnp.clip(_idiv(jnp.maximum(x + tile.sub_k + cq - 2, 0), cq),
                      r_vis, n_all)
    return r_vis, r_full


def _two_loops(lo, mid, hi, diag_first, body, carry):
    """Run ``body(diagonal)(i, carry)`` over [lo, hi): the part that may
    touch the diagonal masked, the rest not. ``diag_first``: [lo, mid) is
    the masked part (the dkv kernel), else [mid, hi) is (forward, dq).
    Without a diagonal the extents are Python ints and ``mid`` is where
    the masked part is empty: one loop."""
    if isinstance(mid, int):
        return jax.lax.fori_loop(lo, hi, body(False), carry)
    carry = jax.lax.fori_loop(lo, mid, body(diag_first), carry)
    return jax.lax.fori_loop(mid, hi, body(not diag_first), carry)


def _drop(seed_ref, b, q0, k0, tile, nq, nk, dropout_p, tpu_prng):
    """The keep mask of the score tile at (``q0``, ``k0``) and what a kept
    probability is multiplied by. Seeded by the score tile's coordinates:
    kernels that share ``sub_q`` and ``sub_k`` draw the same mask."""
    bq, bk, cq, ck = tile
    keep = _keep_mask(seed_ref, b, _idiv(q0, cq), _idiv(k0, ck),
                      nq * (bq // cq), nk * (bk // ck), q0, k0, (ck, cq),
                      dropout_p, tpu_prng)
    return keep, 1.0 / (1.0 - dropout_p)


def _fwd_kernel(*refs, tile, causal, scale, dropout_p, has_mask,
                has_seqlens, hq, tpu_prng=True):
    if has_mask:
        (q_ref, k_ref, vt_ref, mask_ref, seq_ref, seed_ref,
         ot_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (q_ref, k_ref, vt_ref, seq_ref, seed_ref,
         ot_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
        mask_ref = None
    bq, bk, cq, ck = tile
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    k_base = ki * bk
    sl = seq_ref[_idiv(b, hq)] if has_seqlens else None
    q_scale, s_scale = _split_scale(scale)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def q_tile(r, _):
        rows = _sub(r, cq, bq)
        q0 = qi * bq + r * cq
        q = q_ref[0, rows, :]
        if q_scale != 1.0:
            q = q * q_scale

        def step(diagonal):
            def body(j, carry):
                m_prev, l_prev, acc = carry     # [1, cq], [1, cq], [d, cq]
                cols = _sub(j, ck, bk)
                k0 = k_base + j * ck
                st = _scores_t(q, k_ref[0, cols, :], mask_ref, rows, cols,
                               sl, q0, k0, s_scale, diagonal=diagonal,
                               has_mask=has_mask, has_seqlens=has_seqlens)
                m_next = jnp.maximum(m_prev,
                                     jnp.max(st, axis=0, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                pt = jnp.exp(st - m_next)
                l_next = l_prev * alpha + jnp.sum(pt, axis=0, keepdims=True)
                if dropout_p > 0.0:
                    keep, inv = _drop(seed_ref, b, q0, k0, tile, nq, nk,
                                      dropout_p, tpu_prng)
                    pt = jnp.where(keep, pt * inv, 0.0)
                vt = vt_ref[0, :, cols]                       # [d, ck]
                acc = acc * alpha + jnp.dot(
                    vt, pt.astype(vt.dtype),
                    preferred_element_type=jnp.float32)
                return m_next, l_next, acc
            return body

        n_full, n_vis = _k_extent(q0, k_base, tile, causal)
        m, l, acc = _two_loops(
            0, n_full, n_vis, False, step,
            (m_ref[:, rows], l_ref[:, rows], acc_ref[:, rows]))
        m_ref[:, rows] = m
        l_ref[:, rows] = l
        acc_ref[:, rows] = acc
        return _

    def _compute():
        jax.lax.fori_loop(0, bq // cq, q_tile, 0)

    if causal:
        pl.when(k_base <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-20)
        ot_ref[0] = (acc_ref[:] / l).astype(ot_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l)


def _bwd_dq_kernel(*refs, tile, causal, scale, dropout_p, has_mask,
                   has_seqlens, hq, tpu_prng=True):
    if has_mask:
        (q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
         seq_ref, seed_ref, dqt_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, delta_ref,
         seq_ref, seed_ref, dqt_ref, acc_ref) = refs
        mask_ref = None
    bq, bk, cq, ck = tile
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    k_base = ki * bk
    sl = seq_ref[_idiv(b, hq)] if has_seqlens else None
    q_scale, s_scale = _split_scale(scale)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def q_tile(r, _):
        rows = _sub(r, cq, bq)
        q0 = qi * bq + r * cq
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        if q_scale != 1.0:
            q = q * q_scale
        lse, delta = lse_ref[0, :, rows], delta_ref[0, :, rows]

        def step(diagonal):
            def body(j, acc):                                   # [d, cq]
                cols = _sub(j, ck, bk)
                k0 = k_base + j * ck
                st = _scores_t(q, k_ref[0, cols, :], mask_ref, rows, cols,
                               sl, q0, k0, s_scale, diagonal=diagonal,
                               has_mask=has_mask, has_seqlens=has_seqlens)
                pt = jnp.exp(st - lse)
                dpt = _nt(v_ref[0, cols, :], do)
                if dropout_p > 0.0:
                    keep, inv = _drop(seed_ref, b, q0, k0, tile, nq, nk,
                                      dropout_p, tpu_prng)
                    dpt = jnp.where(keep, dpt * inv, 0.0)
                dst = pt * (dpt - delta)
                kt = kt_ref[0, :, cols]                       # [d, ck]
                return acc + jnp.dot(kt, dst.astype(kt.dtype),
                                     preferred_element_type=jnp.float32)
            return body

        n_full, n_vis = _k_extent(q0, k_base, tile, causal)
        acc_ref[:, rows] = _two_loops(0, n_full, n_vis, False, step,
                                      acc_ref[:, rows])
        return _

    def _compute():
        jax.lax.fori_loop(0, bq // cq, q_tile, 0)

    if causal:
        pl.when(k_base <= qi * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        dqt_ref[0] = (acc_ref[:] * scale).astype(dqt_ref.dtype)


def _bwd_dkv_kernel(*refs, tile, causal, scale, dropout_p, has_mask,
                    has_seqlens, hq, tpu_prng=True):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, seq_ref,
         seed_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seq_ref,
         seed_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
        mask_ref = None
    bq, bk, cq, ck = tile
    b, ki, qj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk, nq = pl.num_programs(1), pl.num_programs(2)
    q_base = qj * bq
    sl = seq_ref[_idiv(b, hq)] if has_seqlens else None
    k_scale, s_scale = _split_scale(scale)

    @pl.when(qj == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def k_tile(c, _):
        cols = _sub(c, ck, bk)
        k0 = ki * bk + c * ck
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]
        ks = k * k_scale if k_scale != 1.0 else k   # once a K tile, not a step

        def step(diagonal):
            def body(r, carry):
                dk, dv = carry
                rows = _sub(r, cq, bq)
                q0 = q_base + r * cq
                q, do = q_ref[0, rows, :], do_ref[0, rows, :]
                st = _scores_t(q, ks, mask_ref, rows, cols, sl, q0, k0,
                               s_scale, diagonal=diagonal, has_mask=has_mask,
                               has_seqlens=has_seqlens)
                pt = jnp.exp(st - lse_ref[0, :, rows])
                dpt = _nt(v, do)
                if dropout_p > 0.0:
                    keep, inv = _drop(seed_ref, b, q0, k0, tile, nq, nk,
                                      dropout_p, tpu_prng)
                    p_v = jnp.where(keep, pt * inv, 0.0)
                    dpt = jnp.where(keep, dpt * inv, 0.0)
                else:
                    p_v = pt
                dv = dv + jnp.dot(p_v.astype(do.dtype), do,
                                  preferred_element_type=jnp.float32)
                dst = pt * (dpt - delta_ref[0, :, rows])
                dk = dk + jnp.dot(dst.astype(q.dtype), q,
                                  preferred_element_type=jnp.float32)
                return dk, dv
            return body

        r_vis, r_full = _q_extent(k0, q_base, tile, causal)
        dk, dv = _two_loops(r_vis, r_full, bq // cq, True, step,
                            (dk_acc[cols, :], dv_acc[cols, :]))
        dk_acc[cols, :] = dk
        dv_acc[cols, :] = dv
        return _

    def _compute():
        jax.lax.fori_loop(0, bk // ck, k_tile, 0)

    if causal:
        pl.when(q_base + bq - 1 >= ki * bk)(_compute)
    else:
        _compute()

    @pl.when(qj == nq - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _t(x):
    """[bh, s, d] <-> [bh, d, s]: the kernels read K^T and V^T and write
    O^T and dQ^T, so that a head's ``d`` rows stand over lanes-wide runs of
    the sequence; XLA folds the turn into the copies around the call."""
    return jnp.swapaxes(x, 1, 2)


def _specs(hq, hkv, tile, d, causal, has_mask, mask_hm, q_major):
    """Block specs of one kernel. ``q_major``: grid (b*hq, nq, nk) with the
    K stream innermost (forward, dq); else (b*hq, nk, nq) with the Q stream
    innermost (dkv). Tiles wholly above the diagonal are aliased to the
    diagonal one: the pipeline sees a repeated block index and skips the
    DMA (their step is skipped by ``pl.when``)."""
    group = hq // hkv
    block_q, block_k = tile.block_q, tile.block_k

    def qk(i, j):
        qi, ki = (i, j) if q_major else (j, i)
        if causal and q_major:
            ki = jnp.minimum(ki, _idiv(qi * block_q + block_q - 1, block_k))
        elif causal:
            qi = jnp.maximum(qi, _idiv(ki * block_k, block_q))
        return qi, ki

    def kv_row(b):
        return _idiv(b, hq) * hkv + _idiv(_imod(b, hq), group)

    specs = dict(
        q=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, qk(i, j)[0], 0)),
        qt=pl.BlockSpec((1, d, block_q), lambda b, i, j: (b, 0, qk(i, j)[0])),
        row=pl.BlockSpec((1, 1, block_q),
                         lambda b, i, j: (b, 0, qk(i, j)[0])),
        k=pl.BlockSpec((1, block_k, d),
                       lambda b, i, j: (kv_row(b), qk(i, j)[1], 0)),
        kt=pl.BlockSpec((1, d, block_k),
                        lambda b, i, j: (kv_row(b), 0, qk(i, j)[1])),
        # dk and dv come out per Q head (the GQA group is summed outside)
        dk=pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, qk(i, j)[1], 0)),
        # per-batch scalars ride SMEM whole (rank-1 blocked specs violate
        # the Mosaic lane-tiling rule); kernels index them by _idiv(b, hq)
        smem=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    if has_mask:
        specs["mask"] = pl.BlockSpec(
            (1, 1, block_q, block_k),
            lambda b, i, j: (_idiv(b, hq),
                             _imod(b, hq) if mask_hm > 1 else 0, *qk(i, j)))
    return specs


def _launch(name, kernel, tile, q_major, operands, outs, scratch, *, q, hq,
            hkv, mask, seqlens, seed_arr, causal, dropout_p, interpret):
    """One of the three ``pallas_call``s. ``operands``: (spec name, array)
    of the tensors the kernel reads before mask, lengths and seed; ``outs``:
    (spec name, shape, dtype) of what it writes."""
    bh, s, d = q.shape
    has_mask = mask is not None
    has_seqlens = seqlens is not None
    if seqlens is None:
        seqlens = jnp.full((bh // hq,), s, jnp.int32)
    specs = _specs(hq, hkv, tile, d, causal, has_mask,
                   mask.shape[1] if has_mask else 1, q_major)
    if has_mask:
        operands = operands + [("mask", mask)]
    operands = operands + [("smem", seqlens), ("smem", seed_arr)]
    nq, nk = s // tile.block_q, s // tile.block_k
    return kernel_call(pl.pallas_call(
        functools.partial(kernel, tile=tile, causal=causal,
                          scale=1.0 / (d ** 0.5), dropout_p=dropout_p,
                          has_mask=has_mask, has_seqlens=has_seqlens, hq=hq,
                          tpu_prng=not interpret),
        grid=(bh, nq, nk) if q_major else (bh, nk, nq),
        in_specs=[specs[n] for n, _ in operands],
        out_specs=[specs[n] for n, _, _ in outs],
        out_shape=[jax.ShapeDtypeStruct(shape, dtype)
                   for _, shape, dtype in outs],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    ), *(a for _, a in operands))


def _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p, hq, hkv,
              tiles, interpret):
    bh, s, d = q.shape
    bq = tiles.fwd.block_q
    out_t, lse = _launch(
        "flash_fwd", _fwd_kernel, tiles.fwd, True,
        [("q", q), ("k", k), ("kt", _t(v))],
        [("qt", (bh, d, s), q.dtype), ("row", (bh, 1, s), jnp.float32)],
        [(d, bq), (1, bq), (1, bq)],
        q=q, hq=hq, hkv=hkv, mask=mask, seqlens=seqlens, seed_arr=seed_arr,
        causal=causal, dropout_p=dropout_p, interpret=interpret)
    return _t(out_t), lse


def _bwd_call(q, k, v, o, do, lse, mask, seqlens, seed_arr, causal,
              dropout_p, hq, hkv, tiles, interpret):
    bh, s, d = q.shape
    bhkv = k.shape[0]
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1)[:, None, :]
    common = dict(q=q, hq=hq, hkv=hkv, mask=mask, seqlens=seqlens,
                  seed_arr=seed_arr, causal=causal, dropout_p=dropout_p,
                  interpret=interpret)
    dq_t, = _launch(
        "flash_bwd_dq", _bwd_dq_kernel, tiles.dq, True,
        [("q", q), ("k", k), ("kt", _t(k)), ("k", v), ("q", do),
         ("row", lse), ("row", delta)],
        [("qt", (bh, d, s), q.dtype)], [(d, tiles.dq.block_q)], **common)
    # dk/dv: grid over K/V tiles, Q stream innermost. Outputs are per Q-head;
    # the GQA group-sum happens outside the kernel (one cheap XLA reduce).
    bk = tiles.dkv.block_k
    dk, dv = _launch(
        "flash_bwd_dkv", _bwd_dkv_kernel, tiles.dkv, False,
        [("q", q), ("k", k), ("k", v), ("q", do), ("row", lse),
         ("row", delta)],
        [("dk", (bh, s, d), k.dtype), ("dk", (bh, s, d), v.dtype)],
        [(bk, d), (bk, d)], **common)
    group = hq // hkv
    if group > 1:
        b = bh // hq
        dk = dk.reshape(b, hkv, group, s, d).sum(axis=2).reshape(bhkv, s, d)
        dv = dv.reshape(b, hkv, group, s, d).sum(axis=2).reshape(bhkv, s, d)
    return _t(dq_t), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, mask, seqlens, causal, dropout_p, hq, hkv, tiles, interpret):
    seed_arr = jnp.zeros((1,), jnp.int32)
    out, _ = _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p,
                       hq, hkv, tiles, interpret)
    return out


def _flash_fwd(q, k, v, mask, seqlens, causal, dropout_p, hq, hkv, tiles, interpret):
    seed_arr = jnp.zeros((1,), jnp.int32)
    out, lse = _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p,
                         hq, hkv, tiles, interpret)
    return out, (q, k, v, mask, seqlens, out, lse)


def _flash_bwd(causal, dropout_p, hq, hkv, tiles, interpret,
               res, g):
    q, k, v, mask, seqlens, out, lse = res
    seed_arr = jnp.zeros((1,), jnp.int32)
    dq, dk, dv = _bwd_call(q, k, v, out, g, lse, mask, seqlens, seed_arr,
                           causal, dropout_p, hq, hkv, tiles, interpret)
    dmask = jnp.zeros_like(mask) if mask is not None else None
    dseq = (np.zeros(seqlens.shape, jax.dtypes.float0)
            if seqlens is not None else None)
    return dq, dk, dv, dmask, dseq


_flash.defvjp(_flash_fwd, _flash_bwd)

# dropout needs a live seed that must not retrace per step, so the dropout
# entry point skips custom_vjp bookkeeping complexity: training dropout runs
# through _flash_dropout with the seed as a traced array and a manual vjp.


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_drop(q, k, v, mask, seqlens, seed_arr, causal, dropout_p, hq, hkv,
                tiles, interpret):
    out, _ = _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p,
                       hq, hkv, tiles, interpret)
    return out


def _flash_drop_fwd(q, k, v, mask, seqlens, seed_arr, causal, dropout_p, hq,
                    hkv, tiles, interpret):
    out, lse = _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p,
                         hq, hkv, tiles, interpret)
    return out, (q, k, v, mask, seqlens, seed_arr, out, lse)


def _flash_drop_bwd(causal, dropout_p, hq, hkv, tiles, interpret,
                    res, g):
    q, k, v, mask, seqlens, seed_arr, out, lse = res
    dq, dk, dv = _bwd_call(q, k, v, out, g, lse, mask, seqlens, seed_arr,
                           causal, dropout_p, hq, hkv, tiles, interpret)
    dmask = jnp.zeros_like(mask) if mask is not None else None
    dseq = (np.zeros(seqlens.shape, jax.dtypes.float0)
            if seqlens is not None else None)
    dseed = np.zeros(seed_arr.shape, jax.dtypes.float0)
    return dq, dk, dv, dmask, dseq, dseed


_flash_drop.defvjp(_flash_drop_fwd, _flash_drop_bwd)


def supported(seq_len: int, head_dim: int, block_q: int = DEFAULT_BLOCK_Q,
              block_k: int = DEFAULT_BLOCK_K) -> bool:
    """v2 pads arbitrary sequence lengths; only the head dim is constrained
    (TPU sublane alignment)."""
    return head_dim % 8 == 0 and seq_len >= 1


def padded_len(s: int) -> int:
    """The length the shape-derived tiling pads ``s`` to. Tiles of 512 (or
    256) rows are kept where padding to them wastes at most a sixteenth of
    the sequence; otherwise 128's granularity, so that 2,176 tokens run as
    2,304 (nine tiles of 256) and never as 4,096. Below 128: the next power
    of two, one tile."""
    if s < _LANES:
        return max(8, 1 << (s - 1).bit_length())
    for unit in (512, 256):
        if (-s % unit) * 16 <= s:
            return s + -s % unit
    return s + -s % _LANES


def _largest_divisor(n: int, unit: int, cap: int) -> int:
    """The largest multiple of ``unit`` that divides ``n`` and is at most
    ``cap`` (``unit`` itself divides ``n``)."""
    return max(m for m in range(unit, max(min(n, cap), unit) + 1, unit)
               if n % m == 0)


def vmem_bytes(kernel: str, tile: Tile, d: int, itemsize: int,
               has_mask: bool) -> int:
    """VMEM one grid step of ``kernel`` holds under ``tile``: the tiles the
    pipeline double-buffers (a ``[rows, d]`` tile occupies whole 128-lane
    rows; a turned ``[d, rows]`` one does not), the float32 accumulators
    and statistics, the additive mask tile, and the float32 score-sized
    temporaries of one step of the body."""
    bq, bk, cq, ck = tile
    lanes = -(-d // _LANES) * _LANES
    q_tile, k_tile = bq * lanes * itemsize, bk * lanes * itemsize
    qt_tile, kt_tile = d * bq * itemsize, d * bk * itemsize
    row = 8 * bq * 4                      # a [1, block_q] float32 row
    scores = cq * ck * 4
    if kernel == "fwd":       # q, k, v^T in; o^T, lse out; acc, m, l
        n = 2 * (q_tile + k_tile + kt_tile) + 2 * (qt_tile + row) \
            + d * bq * 4 + 2 * row + 3 * scores            # s^T, p^T, p16
    elif kernel == "dq":      # q, do, k, v, k^T, lse, delta in; dq^T out
        n = 2 * (2 * q_tile + 2 * k_tile + kt_tile + 2 * row) \
            + 2 * qt_tile + d * bq * 4 + 4 * scores        # + dp^T, ds^T
    elif kernel == "dkv":     # q, do, k, v, lse, delta in; dk, dv out
        n = 2 * (2 * q_tile + 2 * k_tile + 2 * row) + 2 * 2 * k_tile \
            + 2 * bk * lanes * 4 + 4 * scores
    else:
        raise ValueError(f"unknown flash kernel {kernel!r}")
    if has_mask:
        n += 2 * bq * bk * 4
    return n


def flash_tiling(kernel: str, s: int, d: int, itemsize: int, causal: bool,
                 has_mask: bool, dropout: bool) -> Tile:
    """The tiling of ``kernel`` ("fwd", "dq", "dkv") for a call's shape: a
    pure function of its arguments, the same answer every run.

    A grid step holds as much of the sequence as ``VMEM_BUDGET`` allows, up
    to ``MAX_BLOCK`` rows each of Q and K (a step of the pipeline costs a
    third of a microsecond whatever it computes), and its body walks score
    tiles of ``sub_q x sub_k`` to the diagonal. What does not fit is taken
    first from the operand the kernel streams (K for the forward and dq, Q
    for dkv), then from the one it holds, then from the score tile. The
    hardware dropout mask is drawn per score tile from its coordinates, so
    under dropout all three kernels get one tiling: the one that fits the
    hungriest of them. ``causal`` belongs to the call's shape but moves
    nothing today: the diagonal bounds the body's loops, not its tiles."""
    s_pad = padded_len(s)
    if s_pad < _LANES:
        return Tile(s_pad, s_pad, s_pad, s_pad)
    kernels = ("fwd", "dq", "dkv") if dropout else (kernel,)
    stream_q = kernel == "dkv" and not dropout
    sub = [_largest_divisor(s_pad, _LANES, SUB_Q),
           _largest_divisor(s_pad, _LANES, SUB_K)]
    blk = [_largest_divisor(s_pad, sub[0], MAX_BLOCK),
           _largest_divisor(s_pad, sub[1], MAX_BLOCK)]

    def fits():
        t = Tile(blk[0], blk[1], sub[0], sub[1])
        return all(vmem_bytes(kn, t, d, itemsize, has_mask) <= VMEM_BUDGET
                   for kn in kernels)

    order = (0, 1) if stream_q else (1, 0)
    while not fits():
        for i in order:
            if blk[i] > sub[i]:
                blk[i] = _largest_divisor(s_pad, sub[i], blk[i] - 1)
                break
        else:
            i = 1 if sub[1] >= sub[0] else 0    # the score tile's longer side
            if sub[i] == _LANES:
                break               # 128 x 128: what every call ran before
            sub[i] = blk[i] = _largest_divisor(s_pad, _LANES, sub[i] - 1)
    return Tile(blk[0], blk[1], sub[0], sub[1])


def _explicit_tile(block_q: int, block_k: int) -> Tile:
    """A caller's (block_q, block_k) as the grid tile of all three kernels;
    the body's score tile is the default's, where it divides them."""
    return Tile(block_q, block_k,
                math.gcd(block_q, SUB_Q), math.gcd(block_k, SUB_K))


def _tiling_counter(kernel: str, tile: Tile, operand) -> None:
    from ...observability.metrics import get_registry
    get_registry().counter(
        "flash_tiling_total",
        "flash-attention tilings resolved, by kernel, grid tile and the "
        "type the MXU's operands have (trace time: once an executable)",
        labelnames=("kernel", "block_q", "block_k", "operand"),
    ).labels(kernel=kernel, block_q=str(tile.block_q),
             block_k=str(tile.block_k), operand=str(operand)).inc()


def _resolve_blocks(q, k, v, causal, attn_mask, dropout_p, block_q, block_k,
                    interpret) -> Tilings:
    """Pick the tiling of each kernel for this call.

    Explicit blocks always win, for all three kernels (a caller passing
    128/128 gets 128/128 even when the autotuner would prefer another
    tiling). With both unset and FLAGS_flash_autotune on, consult the
    autotune cache; on a miss, on real hardware, measure the candidates
    ONCE per (shape, dtype) signature. Traced calls (the training path
    always traces through jax.vjp) tune on synthesized concrete arrays
    matching the tracer's aval — tuning needs the shapes, not the values.
    A sweep in which the compiler refuses every tiling raises here.
    Sequences below DEFAULT_BLOCK_Q skip the consult entirely: they are one
    tile. Otherwise, and by default, ``flash_tiling``: computed from the
    shape, never measured.
    """
    s, d = q.shape[1], q.shape[3]
    chosen = None
    if block_q is not None or block_k is not None:
        chosen = (block_q or DEFAULT_BLOCK_Q, block_k or DEFAULT_BLOCK_K)
    elif not interpret and s >= DEFAULT_BLOCK_Q:
        from ...core.flags import get_flag
        if get_flag("FLAGS_flash_autotune"):
            from . import autotune, on_tpu
            chosen = autotune.cached_blocks(q, k, causal,
                                            attn_mask is not None, dropout_p)
            if chosen is None and on_tpu():
                if isinstance(q, jax.core.Tracer):
                    qc, kc, vc, mc = autotune.synth_like(q, k, v, attn_mask)
                else:
                    qc, kc, vc, mc = q, k, v, attn_mask
                chosen, _ = autotune.tune_flash_blocks(
                    qc, kc, vc, causal=causal, attn_mask=mc,
                    dropout_p=dropout_p)
    if chosen is not None:
        unit = math.lcm(*chosen)
        if s < unit:
            # shrink blocks for short sequences rather than padding 8x
            unit = padded_len(s) if s < _LANES else _LANES
            chosen = (unit, unit)
        tile = _explicit_tile(*chosen)
        return Tilings(tile, tile, tile)
    return Tilings(*(flash_tiling(kn, s, d, q.dtype.itemsize, causal,
                                  attn_mask is not None, dropout_p > 0.0)
                     for kn in Tilings._fields))


def flash_attention_pallas(q, k, v, causal: bool = True, attn_mask=None,
                           dropout_p: float = 0.0, seed=0, kv_seqlens=None,
                           block_q=None, block_k=None,
                           interpret: bool = False):
    """Blockwise flash attention.

    q: [b, s, hq, d]; k/v: [b, s, hkv, d] with hq % hkv == 0 (GQA handled
    in-kernel). attn_mask: additive float [b, 1|hq, s, s]. kv_seqlens:
    [b] int32 valid lengths (varlen/padding). dropout_p with `seed` applies
    in-kernel dropout to the attention probabilities. Returns [b, s, hq, d].
    """
    b, s, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if sk != s:
        raise ValueError("flash_attention_pallas: q and k sequence lengths "
                         f"differ ({s} vs {sk}); use the dense path for "
                         "cross-attention")
    if hq % hkv:
        raise ValueError(f"GQA needs hq % hkv == 0, got {hq}/{hkv}")
    if not supported(s, d):
        raise ValueError(f"flash_attention_pallas: unsupported head_dim {d}")
    tiles = _resolve_blocks(q, k, v, causal, attn_mask, dropout_p,
                            block_q, block_k, interpret)
    for kn, tile in zip(tiles._fields, tiles):
        _tiling_counter(kn, tile, q.dtype)

    # arbitrary lengths: pad to a length every kernel's tiles divide and
    # mask the tail via seqlens
    unit = math.lcm(*(t.block_q for t in tiles), *(t.block_k for t in tiles))
    s_pad = ((s + unit - 1) // unit) * unit
    pad = s_pad - s
    seqlens = kv_seqlens
    if pad:
        padw = [(0, 0), (0, pad), (0, 0), (0, 0)]
        q = jnp.pad(q, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        if attn_mask is not None:
            attn_mask = jnp.pad(attn_mask,
                                [(0, 0), (0, 0), (0, pad), (0, pad)])
        if seqlens is None:
            seqlens = jnp.full((b,), s, jnp.int32)
    if seqlens is not None:
        seqlens = jnp.asarray(seqlens, jnp.int32)

    def to_bh(x, h):
        return jnp.einsum("bshd->bhsd", x).reshape(b * h, s_pad, d)

    qbh, kbh, vbh = to_bh(q, hq), to_bh(k, hkv), to_bh(v, hkv)
    if dropout_p > 0.0:
        seed_arr = jnp.asarray(seed, jnp.int32).reshape((1,))
        out = _flash_drop(qbh, kbh, vbh, attn_mask, seqlens, seed_arr,
                          causal, float(dropout_p), hq, hkv, tiles,
                          interpret)
    else:
        out = _flash(qbh, kbh, vbh, attn_mask, seqlens, causal, 0.0, hq,
                     hkv, tiles, interpret)
    out = jnp.einsum("bhsd->bshd", out.reshape(b, hq, s_pad, d))
    return out[:, :s] if pad else out
