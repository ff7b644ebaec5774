"""Pallas block-sparse attention (TPU).

Reference: the GPU-only sparse_attention op
(phi/kernels/gpu/sparse_attention_kernel.cu — per-element CSR masking).
TPU-native: sparsity lives at TILE granularity and the GRID ITSELF is
compressed — the block pattern becomes a scalar-prefetched per-row tile
list (kmap/counts), so the kernel's innermost grid dimension walks ONLY
active K/V tiles: masked tiles cost neither MXU FLOPs NOR HBM DMA (the
canonical Mosaic block-sparse pattern; the streaming-softmax carry is
shared with flash_attention.py's v2 kernel). Padding entries repeat the
last active tile index, which the pipeline deduplicates.

Backward recomputes through the DENSE masked path under custom_vjp —
block-sparse serving/inference is the forward-latency case; training with
static block patterns can use attn_mask on the flash kernel instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _bs_fwd_kernel(kmap_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                   m_ref, l_ref, *, scale):
    qi, t = pl.program_id(1), pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(t < cnt_ref[qi])
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_prev, l_prev = m_ref[:], l_ref[:]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        m_ref[:] = m_next
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha[:, :1] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(t == nt - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def compress_block_mask(block_mask):
    """[nq, nk] bool -> (kmap [nq, T] int32, counts [nq] int32): each
    row's active tile indices, padded by repeating the last active index
    (or 0 for empty rows) so the pipeline dedupes the padding DMA."""
    bm = np.asarray(block_mask) != 0
    nq = bm.shape[0]
    counts = bm.sum(axis=1).astype(np.int32)
    T = max(int(counts.max()), 1)
    kmap = np.zeros((nq, T), np.int32)
    for r in range(nq):
        idx = np.nonzero(bm[r])[0]
        if idx.size:
            kmap[r, :idx.size] = idx
            kmap[r, idx.size:] = idx[-1]
    return kmap, counts


def _bs_fwd(q, k, v, kmap, counts, block_q, block_k, interpret):
    bh, s, d = q.shape
    scale = 1.0 / (d ** 0.5)
    nq, T = kmap.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq, T),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, qi, t, km, cnt: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, t, km, cnt: (b, km[qi, t], 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, t, km, cnt: (b, km[qi, t], 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, qi, t, km, cnt: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_bs_fwd_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="block_sparse_fwd",
    )(kmap, counts, q, k, v)


def _dense_masked(q, k, v, block_mask, block_q, block_k):
    """Dense reference with the block pattern expanded — the bwd path.
    Fully-masked rows output ZERO (matching the kernel's l=0 finalize, not
    softmax's uniform-over-equal-scores artifact)."""
    bh, s, d = q.shape
    elem_mask = jnp.repeat(jnp.repeat(block_mask != 0, block_q, 0),
                           block_k, 1)  # [s, s]
    scores = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (d ** 0.5)
    scores = jnp.where(elem_mask[None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    row_live = elem_mask.any(axis=-1)  # [s]
    p = jnp.where(row_live[None, :, None], p, 0.0)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _bs(q, k, v, kmap_t, counts_t, block_q_k, interpret):
    return _bs_fwd(q, k, v, np.asarray(kmap_t), np.asarray(counts_t),
                   block_q_k[0], block_q_k[1], interpret)


def _bs_vjp_fwd(q, k, v, kmap_t, counts_t, block_q_k, interpret):
    out = _bs_fwd(q, k, v, np.asarray(kmap_t), np.asarray(counts_t),
                  block_q_k[0], block_q_k[1], interpret)
    return out, (q, k, v)


def _bs_vjp_bwd(kmap_t, counts_t, block_q_k, interpret, res, g):
    q, k, v = res
    block_q, block_k = block_q_k
    # the dense mask is only materialized here, on the bwd path
    kmap, counts = np.asarray(kmap_t), np.asarray(counts_t)
    nq = kmap.shape[0]
    nk = q.shape[1] // block_k
    bm = np.zeros((nq, nk), bool)
    for r in range(nq):
        bm[r, kmap[r, :counts[r]]] = True
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _dense_masked(q_, k_, v_, jnp.asarray(bm),
                                         block_q, block_k), q, k, v)
    return vjp(g)


_bs.defvjp(_bs_vjp_fwd, _bs_vjp_bwd)


def block_sparse_attention_pallas(q, k, v, block_mask, block_q=128,
                                  block_k=128, interpret=False):
    """q/k/v: [b, s, h, d]; block_mask: [s//block_q, s//block_k] (0 = the
    whole tile is masked out; a STATIC numpy pattern). Returns
    [b, s, h, d]."""
    b, s, h, d = q.shape
    if s % block_q or s % block_k:
        raise ValueError(f"seq {s} must divide blocks ({block_q},{block_k})")
    bm_np = np.asarray(block_mask)
    if bm_np.shape != (s // block_q, s // block_k):
        raise ValueError(f"block_mask shape {bm_np.shape} != "
                         f"{(s // block_q, s // block_k)}")
    kmap, counts = compress_block_mask(bm_np)

    def to_bh(x):
        return jnp.einsum("bshd->bhsd", x).reshape(b * h, s, d)

    out = _bs(to_bh(q), to_bh(k), to_bh(v),
              _Hashable(kmap), _Hashable(counts), (block_q, block_k),
              interpret)
    return jnp.einsum("bhsd->bshd", out.reshape(b, h, s, d))


class _Hashable:
    """Wrap a static numpy array so it can sit in nondiff_argnums."""

    def __init__(self, arr):
        self.arr = np.asarray(arr)

    def __array__(self, dtype=None):
        a = self.arr
        return a.astype(dtype) if dtype is not None else a

    def __eq__(self, other):
        return isinstance(other, _Hashable) and \
            self.arr.dtype == other.arr.dtype and \
            self.arr.shape == other.arr.shape and \
            (self.arr == other.arr).all()

    def __hash__(self):
        return hash((self.arr.dtype.str, self.arr.shape,
                     self.arr.tobytes()))
