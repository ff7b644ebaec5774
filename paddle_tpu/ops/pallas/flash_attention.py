"""Pallas flash attention v2 (TPU).

The reference's fused attention tier: third_party/flashattn dynloaded by
phi/backends/dynload/flashattn.cc, used via phi/kernels/gpu/
flash_attn_kernel.cu:128 (FlashAttnKernel + FlashAttnUnpaddedKernel: causal,
dropout, attn_mask, varlen, GQA). TPU-native equivalent: blockwise
streaming-softmax kernels where BOTH Q and K/V move in tiles — the K/V
stream rides the grid's innermost dimension, so VMEM use is O(block_q *
block_k), constant in sequence length (v1 pinned whole-sequence K/V per
program and broke at long context).

Feature surface:
  * causal masking — fully-masked K/V tiles are skipped (`pl.when`) and
    their index maps alias the diagonal tile so the pipeline never DMAs them
  * GQA natively: K/V tiles are addressed per kv-head via the index map
    (no host-side head expansion; group mapping is pure index arithmetic)
  * additive attention mask, streamed in [block_q, block_k] tiles
  * varlen/padding via per-batch kv_seqlens (rows and cols >= len masked);
    arbitrary sequence lengths are handled by padding to the block size and
    masking the tail through the same path
  * dropout on the attention probabilities using the in-kernel TPU PRNG,
    regenerated bit-exactly in the backward kernels from (seed, head, qi, ki)

Forward saves only (out, logsumexp); backward recomputes scores blockwise
(flash-attention-2 two-pass: a dq kernel gridded like the forward, and a
dk/dv kernel gridded over K/V tiles with the Q stream innermost).

Layout: [b*h, s, d] head-major inside the kernels (callers reshape from the
framework's [b, s, h, d]).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_call

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
_LANES = 128  # m/l scratch lane-replication width (TPU vreg lane count)


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
    of the ABSOLUTE (row, col) position, so any tile decomposition
    reproduces the same mask."""
    if tpu_prng:
        pltpu.prng_seed(seed_ref[0], (b * nq + qi) * nk + ki)
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    else:
        rows = (q_start + _iota(shape, 0)).astype(jnp.uint32)
        cols = (k_start + _iota(shape, 1)).astype(jnp.uint32)
        b_u = jnp.uint32(0) + b.astype(jnp.uint32) if hasattr(b, "astype") \
            else jnp.uint32(b)
        seed_u = seed_ref[0].astype(jnp.uint32)
        x = (rows * jnp.uint32(0x9E3779B9)) ^ (cols * jnp.uint32(0x85EBCA6B))
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


def _tile_scores(q, k, mask_ref, sl, q_start, k_start, *, causal,
                 has_mask, has_seqlens):
    """Scaled scores for one (q, k) tile with every mask applied.
    ``sl`` is this batch row's kv length (scalar, read from SMEM by the
    caller) or None."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    shape = s.shape
    if has_mask:
        s = s + mask_ref[0, 0].astype(jnp.float32)
    if causal:
        rows = q_start + _iota(shape, 0)
        cols = k_start + _iota(shape, 1)
        s = jnp.where(cols <= rows, s, NEG_INF)
    if has_seqlens:
        rows = q_start + _iota(shape, 0)
        cols = k_start + _iota(shape, 1)
        s = jnp.where((cols < sl) & (rows < sl), s, NEG_INF)
    return s


def _fwd_kernel(*refs, block_q, block_k, causal, scale, dropout_p, has_mask,
                has_seqlens, hq, tpu_prng=True):
    if has_mask:
        (q_ref, k_ref, v_ref, mask_ref, seq_ref, seed_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (q_ref, k_ref, v_ref, seq_ref, seed_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
        mask_ref = None
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k
    sl = seq_ref[_idiv(b, hq)] if has_seqlens else None

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = _tile_scores(q, k, mask_ref, sl, q_start, k_start,
                         causal=causal, has_mask=has_mask,
                         has_seqlens=has_seqlens)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, b, qi, ki, pl.num_programs(1),
                              pl.num_programs(2), q_start, k_start,
                              p.shape, dropout_p, tpu_prng)
            p_use = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        else:
            p_use = p
        m_ref[:] = m_next
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha[:, :1] + jnp.dot(
            p_use, v, preferred_element_type=jnp.float32)

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(l[:, 0])


def _bwd_dq_kernel(*refs, block_q, block_k, causal, scale, dropout_p,
                   has_mask, has_seqlens, hq, tpu_prng=True):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, seq_ref,
         seed_ref, dq_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seq_ref,
         seed_ref, dq_ref, acc_ref) = refs
        mask_ref = None
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    q_start = qi * block_q
    k_start = ki * block_k
    sl = seq_ref[_idiv(b, hq)] if has_seqlens else None

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = _tile_scores(q, k, mask_ref, sl, q_start, k_start,
                         causal=causal, has_mask=has_mask,
                         has_seqlens=has_seqlens)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, b, qi, ki, pl.num_programs(1),
                              pl.num_programs(2), q_start, k_start,
                              p.shape, dropout_p, tpu_prng)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        ds = p * (dp - delta[:, None])
        acc_ref[:] = acc_ref[:] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32)

    if causal:
        pl.when(k_start <= q_start + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, block_q, block_k, causal, scale, dropout_p,
                    has_mask, has_seqlens, hq, tpu_prng=True):
    if has_mask:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref, seq_ref,
         seed_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seq_ref,
         seed_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
        mask_ref = None
    b, ki, qj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)
    q_start = qj * block_q
    k_start = ki * block_k
    sl = seq_ref[_idiv(b, hq)] if has_seqlens else None

    @pl.when(qj == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = _tile_scores(q, k, mask_ref, sl, q_start, k_start,
                         causal=causal, has_mask=has_mask,
                         has_seqlens=has_seqlens)
        p = jnp.exp(s - lse[:, None])  # [block_q, block_k]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            # seed coords are (head, q-tile, k-tile) — identical to forward;
            # this grid is (bh, nk, nq), so nq/nk swap program axes
            keep = _keep_mask(seed_ref, b, qj, ki, pl.num_programs(2),
                              pl.num_programs(1), q_start, k_start,
                              p.shape, dropout_p, tpu_prng)
            inv = 1.0 / (1.0 - dropout_p)
            p_v = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            p_v = p
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p_v, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        # q was pre-scaled, so ds.T @ q already carries `scale`
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(q_start + block_q - 1 >= k_start)(_compute)
    else:
        _compute()

    @pl.when(qj == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _common_specs(hq, hkv, block_q, block_k, s, d, causal, has_mask, mask_hm):
    """Index maps shared by the forward and dq kernels (grid b*hq, nq, nk)."""
    group = hq // hkv

    def kv_row(b):
        return _idiv(b, hq) * hkv + _idiv(_imod(b, hq), group)

    def ki_eff(qi, ki):
        if not causal:
            return ki
        # alias fully-masked tiles to the diagonal tile: the pipeline sees a
        # repeated block index and skips the DMA
        return jnp.minimum(ki, _idiv(qi * block_q + block_q - 1, block_k))

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0))
    k_spec = pl.BlockSpec((1, block_k, d),
                          lambda b, qi, ki: (kv_row(b), ki_eff(qi, ki), 0))
    v_spec = pl.BlockSpec((1, block_k, d),
                          lambda b, qi, ki: (kv_row(b), ki_eff(qi, ki), 0))
    mask_spec = None
    if has_mask:
        mask_spec = pl.BlockSpec(
            (1, 1, block_q, block_k),
            lambda b, qi, ki: (_idiv(b, hq),
                               _imod(b, hq) if mask_hm > 1 else 0,
                               qi, ki_eff(qi, ki)))
    # per-batch scalars ride SMEM whole (rank-1 blocked specs violate the
    # Mosaic lane-tiling rule); kernels index them by _idiv(b, hq)
    seq_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi))
    return q_spec, k_spec, v_spec, mask_spec, seq_spec, seed_spec, row_spec


def _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p, hq, hkv,
              block_q, block_k, interpret):
    bh, s, d = q.shape
    scale = 1.0 / (d ** 0.5)
    has_mask = mask is not None
    mask_hm = mask.shape[1] if has_mask else 1
    has_seqlens = seqlens is not None
    if seqlens is None:
        seqlens = jnp.full((bh // hq,), s, jnp.int32)
    (q_spec, k_spec, v_spec, mask_spec, seq_spec, seed_spec,
     row_spec) = _common_specs(hq, hkv, block_q, block_k, s, d, causal,
                               has_mask, mask_hm)
    in_specs = [q_spec, k_spec, v_spec]
    args = [q, k, v]
    if has_mask:
        in_specs.append(mask_spec)
        args.append(mask)
    in_specs += [seq_spec, seed_spec]
    args += [seqlens, seed_arr]

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        scale=scale, dropout_p=dropout_p, has_mask=has_mask,
        has_seqlens=has_seqlens, hq=hq, tpu_prng=not interpret)
    out, lse = kernel_call(pl.pallas_call(
        kernel,
        grid=(bh, s // block_q, s // block_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            row_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    ), *args)
    return out, lse


def _bwd_call(q, k, v, o, do, lse, mask, seqlens, seed_arr, causal,
              dropout_p, hq, hkv, block_q, block_k, interpret):
    bh, s, d = q.shape
    bhkv = k.shape[0]
    scale = 1.0 / (d ** 0.5)
    has_mask = mask is not None
    mask_hm = mask.shape[1] if has_mask else 1
    has_seqlens = seqlens is not None
    if seqlens is None:
        seqlens = jnp.full((bh // hq,), s, jnp.int32)
    group = hq // hkv
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(
        axis=-1)[:, None, :]

    (q_spec, k_spec, v_spec, mask_spec, seq_spec, seed_spec,
     row_spec) = _common_specs(hq, hkv, block_q, block_k, s, d, causal,
                               has_mask, mask_hm)
    in_specs = [q_spec, k_spec, v_spec, q_spec, row_spec, row_spec]
    args = [q, k, v, do, lse, delta]
    if has_mask:
        in_specs.append(mask_spec)
        args.append(mask)
    in_specs += [seq_spec, seed_spec]
    args += [seqlens, seed_arr]

    dq = kernel_call(pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, dropout_p=dropout_p,
                          has_mask=has_mask, has_seqlens=has_seqlens,
                          hq=hq, tpu_prng=not interpret),
        grid=(bh, s // block_q, s // block_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    ), *args)

    # dk/dv: grid over K/V tiles, Q stream innermost. Outputs are per Q-head;
    # the GQA group-sum happens outside the kernel (one cheap XLA reduce).
    def kv_row(b):
        return _idiv(b, hq) * hkv + _idiv(_imod(b, hq), group)

    def qj_eff(ki, qj):
        if not causal:
            return qj
        return jnp.maximum(qj, _idiv(ki * block_k, block_q))

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d),
                     lambda b, ki, qj: (b, qj_eff(ki, qj), 0)),
        pl.BlockSpec((1, block_k, d), lambda b, ki, qj: (kv_row(b), ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, ki, qj: (kv_row(b), ki, 0)),
        pl.BlockSpec((1, block_q, d),
                     lambda b, ki, qj: (b, qj_eff(ki, qj), 0)),
        pl.BlockSpec((1, 1, block_q),
                     lambda b, ki, qj: (b, 0, qj_eff(ki, qj))),
        pl.BlockSpec((1, 1, block_q),
                     lambda b, ki, qj: (b, 0, qj_eff(ki, qj))),
    ]
    dkv_args = [q, k, v, do, lse, delta]
    if has_mask:
        dkv_in_specs.append(pl.BlockSpec(
            (1, 1, block_q, block_k),
            lambda b, ki, qj: (_idiv(b, hq),
                               _imod(b, hq) if mask_hm > 1 else 0,
                               qj_eff(ki, qj), ki)))
        dkv_args.append(mask)
    dkv_in_specs += [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    dkv_args += [seqlens, seed_arr]

    dk_ph, dv_ph = kernel_call(pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, scale=scale, dropout_p=dropout_p,
                          has_mask=has_mask, has_seqlens=has_seqlens,
                          hq=hq, tpu_prng=not interpret),
        grid=(bh, s // block_k, s // block_q),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, ki, qj: (b, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, ki, qj: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    ), *dkv_args)

    if group > 1:
        b = bh // hq
        dk = dk_ph.reshape(b, hkv, group, s, d).sum(axis=2).reshape(bhkv, s, d)
        dv = dv_ph.reshape(b, hkv, group, s, d).sum(axis=2).reshape(bhkv, s, d)
    else:
        dk, dv = dk_ph, dv_ph
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, mask, seqlens, causal, dropout_p, hq, hkv, block_q,
           block_k, interpret):
    seed_arr = jnp.zeros((1,), jnp.int32)
    out, _ = _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p,
                       hq, hkv, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, mask, seqlens, causal, dropout_p, hq, hkv, block_q,
               block_k, interpret):
    seed_arr = jnp.zeros((1,), jnp.int32)
    out, lse = _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p,
                         hq, hkv, block_q, block_k, interpret)
    return out, (q, k, v, mask, seqlens, out, lse)


def _flash_bwd(causal, dropout_p, hq, hkv, block_q, block_k, interpret,
               res, g):
    q, k, v, mask, seqlens, out, lse = res
    seed_arr = jnp.zeros((1,), jnp.int32)
    dq, dk, dv = _bwd_call(q, k, v, out, g, lse, mask, seqlens, seed_arr,
                           causal, dropout_p, hq, hkv, block_q, block_k,
                           interpret)
    dmask = jnp.zeros_like(mask) if mask is not None else None
    dseq = (np.zeros(seqlens.shape, jax.dtypes.float0)
            if seqlens is not None else None)
    return dq, dk, dv, dmask, dseq


_flash.defvjp(_flash_fwd, _flash_bwd)

# dropout needs a live seed that must not retrace per step, so the dropout
# entry point skips custom_vjp bookkeeping complexity: training dropout runs
# through _flash_dropout with the seed as a traced array and a manual vjp.


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash_drop(q, k, v, mask, seqlens, seed_arr, causal, dropout_p, hq, hkv,
                block_q, block_k, interpret):
    out, _ = _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p,
                       hq, hkv, block_q, block_k, interpret)
    return out


def _flash_drop_fwd(q, k, v, mask, seqlens, seed_arr, causal, dropout_p, hq,
                    hkv, block_q, block_k, interpret):
    out, lse = _fwd_call(q, k, v, mask, seqlens, seed_arr, causal, dropout_p,
                         hq, hkv, block_q, block_k, interpret)
    return out, (q, k, v, mask, seqlens, seed_arr, out, lse)


def _flash_drop_bwd(causal, dropout_p, hq, hkv, block_q, block_k, interpret,
                    res, g):
    q, k, v, mask, seqlens, seed_arr, out, lse = res
    dq, dk, dv = _bwd_call(q, k, v, out, g, lse, mask, seqlens, seed_arr,
                           causal, dropout_p, hq, hkv, block_q, block_k,
                           interpret)
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


def _resolve_blocks(q, k, v, causal, attn_mask, dropout_p, block_q, block_k,
                    interpret):
    """Pick the (block_q, block_k) tiling for this call.

    Explicit blocks always win (a caller passing 128/128 gets 128/128 even
    when the autotuner would prefer another tiling). With both unset and
    FLAGS_flash_autotune on, consult the autotune cache; on a miss, on
    real hardware, measure the candidates ONCE per (shape, dtype)
    signature. Traced calls (the training path always traces through
    jax.vjp) tune on synthesized concrete arrays matching the tracer's
    aval — tuning needs the shapes, not the values — so the flag works
    for compiled training, not just eager inference. A sweep in which
    the compiler refuses every tiling raises here. Sequences below
    DEFAULT_BLOCK_Q skip the consult entirely: the short-sequence shrink
    below would override any tuned tiling, so tuning them would burn
    compiles for a discarded answer.
    """
    if block_q is not None or block_k is not None:
        return (block_q or DEFAULT_BLOCK_Q, block_k or DEFAULT_BLOCK_K)
    s = q.shape[1]
    if not interpret and s >= DEFAULT_BLOCK_Q:
        from ...core.flags import get_flag
        if get_flag("FLAGS_flash_autotune"):
            from . import autotune, on_tpu
            tuned = autotune.cached_blocks(q, k, causal,
                                           attn_mask is not None, dropout_p)
            if tuned is None and on_tpu():
                if isinstance(q, jax.core.Tracer):
                    qc, kc, vc, mc = autotune.synth_like(q, k, v, attn_mask)
                else:
                    qc, kc, vc, mc = q, k, v, attn_mask
                tuned, _ = autotune.tune_flash_blocks(
                    qc, kc, vc, causal=causal, attn_mask=mc,
                    dropout_p=dropout_p)
            if tuned is not None:
                return tuned
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K


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
    block_q, block_k = _resolve_blocks(q, k, v, causal, attn_mask, dropout_p,
                                       block_q, block_k, interpret)

    # arbitrary lengths: pad to the block lcm and mask the tail via seqlens
    unit = math.lcm(block_q, block_k)
    if s < unit:
        # shrink blocks for short sequences rather than padding 8x
        block_q = block_k = unit = max(8, 1 << (s - 1).bit_length()) \
            if s < 128 else 128
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
                          causal, float(dropout_p), hq, hkv, block_q,
                          block_k, interpret)
    else:
        out = _flash(qbh, kbh, vbh, attn_mask, seqlens, causal, 0.0, hq,
                     hkv, block_q, block_k, interpret)
    out = jnp.einsum("bhsd->bshd", out.reshape(b, hq, s_pad, d))
    return out[:, :s] if pad else out
