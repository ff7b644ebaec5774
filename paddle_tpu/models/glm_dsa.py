"""GLM-5's decoder (``model_type: glm_moe_dsa``): DeepSeek-V3's latent
attention (MLA) and routed experts with DeepSeek-V3.2's sparse-attention
indexer.

A block is ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``:

  * **MLA.** ``cq = RMSNorm(h W_qa)``; ``q = cq W_qb`` -> H heads of
    [nope | rope]; ``[ckv | k_pe] = h W_kva``, ``ckv = RMSNorm(ckv)``; RoPE
    (interleaved pairs) on q's rope part and on the one ``k_pe`` all heads
    share; ``[k_nope | v]`` of a head ``= ckv W_kvb``. Scores
    ``(q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)``, causal, softmax
    in float32 over the rows the indexer keeps.
  * **Indexer.** ``qI = cq W_Iq`` (n heads of D), ``kI = LayerNorm(h W_Ik)``
    (one key a token), RoPE on the first ``rope`` dims of both, ``w = h W_Iw
    / sqrt(n D)``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``. Query
    t attends the rows s <= t whose I[t, s] is among the ``index_topk``
    largest (every row while t + 1 <= index_topk).
  * **FFN.** The first ``first_k_dense_replace`` blocks a SwiGLU; after them
    ``sum_e g_e SwiGLU_e(h) + SwiGLU_shared(h)``: ``s = sigmoid(h W_r)`` in
    float32, the ``num_experts_per_tok`` chosen are the largest of ``s +
    bias`` (the bias decides the choice alone), ``g = routed_scaling_factor
    * s / sum s`` over the chosen. The layer is told which experts it holds
    (``experts_held_start``, ``experts_held_count``): the router keeps its
    full width, and only chosen experts among those held add to the result
    (expert parallelism's share of the layer, without its exchange).
    Dropless: assignments are sorted by expert and multiplied group by
    group, whatever the skew.

Serving. What a token leaves behind is, per layer, ONE latent row ``[ckv |
k_pe]`` (after norm and RoPE) and ONE index key, in two page pools under
one page numbering: the batcher's block table, prefix cache and page audit
see pages only. A prefill chunk scores the slot's held rows block by block,
finds each query's threshold (the index_topk-th largest score, by bisection
on the scores' bits: no sort) and attends over the rows kept, in
the absorbed form (``W_kvb``'s key half folded into the query, its value
half into the output: heads of ``kv_lora_rank + rope`` over the one latent
row), blocks of rows at a time up to the rows held. A decode step scores
every row of each slot, keeps exactly index_topk (ties: the lowest row
first), gathers those rows from the pages by token and attends over them
in the absorbed form. ``forward`` is the expanded form over whole
sequences.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..incubate.nn.functional.decode_attention import \
    write_page_rows as _write_rows
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..ops.registry import dispatch
from .dsa_select import (_order_bits, _select,  # noqa: F401
                         index_scores as _index_scores, kth_largest_bits,
                         select_indices, select_rows)
from .routed_experts import (F32, _counts_of_chunk,  # noqa: F401
                             _counts_of_step, _mm, _swiglu, _tile_rows,
                             expert_counts, routed_experts)

_NEG = -1e30


@dataclass
class GlmDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    experts_held_start: int = 0
    experts_held_count: int = 256
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    prefill_key_block: int = 2048       # held rows a chunk reads at a time
    rope_theta: float = 1e6
    max_position_embeddings: int = 202752
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    @property
    def moe_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.first_k_dense_replace)


def glm_dsa_tiny_config(**overrides) -> GlmDsaConfig:
    """Test-scale config of the same shape: one dense block, three expert
    blocks holding all 16 experts, 4 a token, 16 rows kept."""
    return GlmDsaConfig(**dict(dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=4,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, index_n_heads=2, index_head_dim=16, index_topk=16,
        n_routed_experts=16, num_experts_per_tok=4, experts_held_start=0,
        experts_held_count=16, prefill_key_block=32,
        max_position_embeddings=512,
        initializer_range=0.1), **overrides))


# -- arithmetic on arrays -----------------------------------------------------
# Pure ``jax.numpy`` over a dict of one block's weights; the model calls the
# block functions through ``ops.registry.dispatch`` so that the weights are
# recorded as the executable's state. Matrix products accumulate in float32;
# norms, softmax, the router and the index scores are float32.

def _rms(x, gain, eps):
    xf = x.astype(F32)
    return (xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * gain.astype(F32)).astype(x.dtype)


def _layer_norm(x, gain, bias, eps):
    xf = x.astype(F32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + eps) * gain.astype(F32)
            + bias.astype(F32)).astype(x.dtype)


def _rope(x, cos, sin):
    """Rotate pairs (2i, 2i+1) of x [N, ..., R] by the rows' angles
    cos / sin [N, R/2] (float32)."""
    xf = x.astype(F32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(
        x.shape).astype(x.dtype)


def rope_tables(positions: int, rope: int, theta: float):
    """(cos, sin) [positions, rope / 2] float32, angles in float64."""
    inv = 1.0 / (theta ** (np.arange(0, rope, 2, dtype=np.float64) / rope))
    ang = np.outer(np.arange(positions, dtype=np.float64), inv)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def lane_width(width: int) -> int:
    """The width a pool row is held at: whole lanes of 128 once it is wider
    than one. A 576-wide row is tiled to 640 on the chip either way; a pool
    declared 576 wide is given a page-minor layout by the runtime, and every
    executable then copies it in and out."""
    return -(-width // 128) * 128 if width > 128 else width


def _widen(x, width: int):
    """x [..., w] with zeros up to ``width``."""
    pad = width - x.shape[-1]
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _attn_inputs(p, x, cos, sin, eps, ieps):
    """Rows x [N, d] at angles cos / sin [N, R/2] -> what attention and the
    indexer read of them: (q [N, H * (nope + R)] before RoPE, the latent
    row [N, C + R], qI [N, n, D], kI [N, D], w [N, n] float32)."""
    n = x.shape[0]
    rope = 2 * cos.shape[1]
    lora = p["kv_a_g"].shape[0]
    h = _rms(x, p["ln1_g"], eps)
    with jax.named_scope("mla_q"):
        cq = _rms(_mm(h, p["q_a_w"]), p["q_a_g"], eps)
        q = _mm(cq, p["q_b_w"])
    with jax.named_scope("mla_latent"):
        kv = _mm(h, p["kv_a_w"])
        ckv = _rms(kv[:, :lora], p["kv_a_g"], eps)
        k_pe = _rope(kv[:, lora:], cos, sin)
        latent = jnp.concatenate([ckv, k_pe], -1)
    with jax.named_scope("indexer"):
        n_idx = p["iw_w"].shape[1]
        q_i = _mm(cq, p["iq_w"]).reshape(n, n_idx, -1)
        q_i = jnp.concatenate([_rope(q_i[..., :rope], cos, sin),
                               q_i[..., rope:]], -1)
        k_i = _layer_norm(_mm(h, p["ik_w"]), p["ik_g"], p["ik_b"], ieps)
        k_i = jnp.concatenate([_rope(k_i[:, :rope], cos, sin),
                               k_i[:, rope:]], -1)
        w_i = _mm(h, p["iw_w"], F32) * (n_idx * q_i.shape[-1]) ** -0.5
    return q, latent, q_i, k_i, w_i


def _split_q(q, heads, rope, cos, sin):
    """q [N, H * (nope + R)] -> (q_nope [N, H, nope], q_pe [N, H, R])."""
    q = q.reshape(q.shape[0], heads, -1)
    nope = q.shape[-1] - rope
    return q[..., :nope], _rope(q[..., nope:], cos, sin)


def _kv_b(p, heads):
    """``W_kvb`` [C, H * (nope + v)] as (key half [C, H, nope], value half
    [C, H, v])."""
    lora = p["kv_b_w"].shape[0]
    v = p["o_w"].shape[0] // heads
    w = p["kv_b_w"].reshape(lora, heads, -1)
    return w[..., :w.shape[-1] - v], w[..., w.shape[-1] - v:]


def _absorb_q(p, q_nope, q_pe):
    """Queries of the absorbed form: [N, H, C + R]."""
    w_k, _ = _kv_b(p, q_nope.shape[1])
    q_abs = jnp.einsum("nhd,chd->nhc", q_nope, w_k,
                       preferred_element_type=F32).astype(q_nope.dtype)
    return jnp.concatenate([q_abs, q_pe], -1)


def _absorbed_out(p, o_lat, dtype):
    """o_lat [N, H, C] float32 (attention over latent rows) -> [N, d]."""
    with jax.named_scope("o_proj"):
        _, w_v = _kv_b(p, o_lat.shape[1])
        o = jnp.einsum("nhc,chv->nhv", o_lat.astype(dtype), w_v,
                       preferred_element_type=F32).astype(dtype)
        return _mm(o.reshape(o.shape[0], -1), p["o_w"])


# -- the expert layer ---------------------------------------------------------

def route(p, h, top_k: int, scaling: float):
    """(chosen experts [N, k] int32, gates [N, k] float32) of rows h."""
    s = jax.nn.sigmoid(_mm(h, p["router_w"], F32))
    _, chosen = lax.top_k(s + p["router_b"].astype(F32), top_k)
    picked = jnp.take_along_axis(s, chosen, -1)
    gates = scaling * picked / jnp.sum(picked, -1, keepdims=True)
    return chosen.astype(jnp.int32), gates


def _ffn(p, x, eps, held, top_k, scaling, active=None):
    """x [N, d] -> (x + FFN(RMSNorm(x)), counts [4] int32: the assignments
    to experts held here, all the assignments the router made, the held
    experts touched, the fullest one's tokens; zeros of a dense layer).
    Rows that are not ``active`` (parked slots, a chunk's pad rows) are
    routed nowhere."""
    h = _rms(x, p["ln2_g"], eps)
    if "router_w" not in p:
        with jax.named_scope("mlp"):
            return x + _swiglu(h, p["mlp_w1"], p["mlp_w2"]), \
                jnp.zeros(4, jnp.int32)
    with jax.named_scope("router"):
        chosen, gates = route(p, h, top_k, scaling)
        routed = x.shape[0]
        if active is not None:
            chosen = jnp.where(active[:, None], chosen, -1)
            routed = jnp.sum(active, dtype=jnp.int32)
    with jax.named_scope("experts_routed"):
        y, counts = routed_experts(p, h, chosen, gates, held)
    with jax.named_scope("expert_shared"):
        y = y + _swiglu(h, p["sh_w1"], p["sh_w2"])
    return x + y, expert_counts(counts, routed * top_k)


# -- blocks -------------------------------------------------------------------

def _block_dense(p, x, cos, sin, eps, ieps, heads, topk, held, top_k,
                 scaling):
    """One sequence x [S, d], the expanded form: every head's keys and
    values made from the latent rows."""
    s = x.shape[0]
    rope = 2 * cos.shape[1]
    q, latent, q_i, k_i, w_i = _attn_inputs(p, x, cos, sin, eps, ieps)
    q_nope, q_pe = _split_q(q, heads, rope, cos, sin)
    lora = latent.shape[1] - rope
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    with jax.named_scope("indexer"):
        with jax.named_scope("index_scores"):
            scores = _index_scores(q_i, k_i, w_i)
        with jax.named_scope("index_topk"):
            keep = select_rows(scores, causal, topk) if topk < s \
                else causal
    with jax.named_scope("sparse_attention"):
        w_k, w_v = _kv_b(p, heads)
        k_nope = jnp.einsum("tc,chd->thd", latent[:, :lora], w_k,
                            preferred_element_type=F32).astype(x.dtype)
        v = jnp.einsum("tc,chv->thv", latent[:, :lora], w_v,
                       preferred_element_type=F32).astype(x.dtype)
        att = (jnp.einsum("qhd,thd->hqt", q_nope, k_nope,
                          preferred_element_type=F32)
               + jnp.einsum("qhr,tr->hqt", q_pe, latent[:, lora:],
                            preferred_element_type=F32)) \
            / math.sqrt(q_nope.shape[-1] + rope)
        probs = jax.nn.softmax(jnp.where(keep[None], att, _NEG), -1)
        ctx = jnp.einsum("hqt,thv->qhv", probs.astype(x.dtype), v,
                         preferred_element_type=F32).astype(x.dtype)
    with jax.named_scope("o_proj"):
        x = x + _mm(ctx.reshape(s, -1), p["o_w"])
    x, counts = _ffn(p, x, eps, held, top_k, scaling)
    return x, keep, counts


def _page_window(table, dec, rows, block):
    """The pages that back rows dec .. dec + rows of a timeline whose pages
    are ``table`` [P]: (their ids [rows / block + 1], the row of the window
    at which ``dec`` lies)."""
    n = min(rows // block + 1, table.shape[0])
    first = jnp.minimum(dec // block, table.shape[0] - n)
    return lax.dynamic_slice_in_dim(table, first, n), dec - first * block


def _write_run(pool, table, dec, run):
    """Rows ``run`` [S, W] at rows dec .. dec + S of one timeline, by the
    page: the pages under the run are read, the run laid over them, and the
    pages put back along the pool's first axis."""
    block = pool.shape[2]
    pages, at = _page_window(table, dec, run.shape[0], block)
    cur = pool[pages].reshape(-1, pool.shape[-1])
    cur = lax.dynamic_update_slice_in_dim(
        cur, _widen(run, pool.shape[-1]).astype(pool.dtype), at, 0)
    return pool.at[pages].set(cur.reshape(pages.shape[0], 1, block, -1))


def _key_block(want: int, s_max: int, block: int) -> int:
    """Held rows a chunk reads at a time: ``want`` or the next size below
    it that is whole pages and divides the timeline."""
    kb = max(block, min(want, s_max) // block * block)
    while s_max % kb:
        kb -= block
    return kb


def _block_chunk(p, x, lat_pool, idx_pool, table, dec, n_valid, cos_t, sin_t,
                 eps, ieps, heads, topk, held, top_k, scaling, kb):
    """One sequence's chunk x [S, d] at rows dec .. dec + S of the timeline
    whose pages are ``table`` [P]: its latent rows and index keys go into
    the pages, then every query scores the rows held, keeps those at or
    above its index_topk-th, and attends over them (absorbed form), a block
    of rows at a time and no further than the rows held. The first
    ``n_valid`` rows are real: the pad rows behind them are routed nowhere
    and counted nowhere."""
    s = x.shape[0]
    block = lat_pool.shape[2]
    s_max = table.shape[0] * block
    kb = _key_block(kb, s_max, block)
    pos = dec + jnp.arange(s)
    cos, sin = cos_t[pos], sin_t[pos]
    rope = 2 * cos.shape[1]
    q, latent, q_i, k_i, w_i = _attn_inputs(p, x, cos, sin, eps, ieps)
    with jax.named_scope("latent_write"):
        lat_pool = _write_run(lat_pool, table, dec, latent)
        idx_pool = _write_run(idx_pool, table, dec, k_i)
    n_blocks = (dec + s + kb - 1) // kb

    def pages_of(i):
        return lax.dynamic_slice_in_dim(table, i * (kb // block),
                                        kb // block)

    with jax.named_scope("indexer"):
        with jax.named_scope("index_scores"):
            def score(i, buf):
                keys = idx_pool[pages_of(i)].reshape(kb, -1)
                return lax.dynamic_update_slice_in_dim(
                    buf, _index_scores(q_i, keys, w_i), i * kb, 1)
            scores = lax.fori_loop(0, n_blocks, score,
                                   jnp.zeros((s, s_max), F32))
        with jax.named_scope("index_topk"):
            valid = jnp.arange(s_max)[None, :] <= pos[:, None]
            keep = select_rows(scores, valid, topk)
            real = jnp.arange(s) < n_valid
            chose = jnp.stack([jnp.sum(m & real[:, None], dtype=jnp.int32)
                               for m in (valid, keep)])
    with jax.named_scope("mla_q"):
        q_nope, q_pe = _split_q(q, heads, rope, cos, sin)
        qc = _widen(_absorb_q(p, q_nope, q_pe), lat_pool.shape[-1])
    lora = latent.shape[1] - rope
    scale = 1.0 / math.sqrt(q_nope.shape[-1] + rope)

    def attend(i, carry):
        m, l, acc = carry
        with jax.named_scope("sparse_gather"):
            rows = lat_pool[pages_of(i)].reshape(kb, -1)
            ok = lax.dynamic_slice_in_dim(keep, i * kb, kb, 1)
        sc = jnp.einsum("qhc,tc->hqt", qc, rows,
                        preferred_element_type=F32) * scale
        sc = jnp.where(ok[None], sc, _NEG)
        m2 = jnp.maximum(m, jnp.max(sc, -1))
        pr = jnp.exp(sc - m2[..., None])
        corr = jnp.exp(m - m2)
        acc = acc * corr[..., None] + jnp.einsum(
            "hqt,tc->hqc", pr.astype(rows.dtype), rows[:, :lora],
            preferred_element_type=F32)
        return m2, l * corr + jnp.sum(pr, -1), acc

    with jax.named_scope("sparse_attention"):
        m, l, acc = lax.fori_loop(
            0, n_blocks, attend,
            (jnp.full((heads, s), _NEG, F32), jnp.zeros((heads, s), F32),
             jnp.zeros((heads, s, lora), F32)))
        o_lat = jnp.moveaxis(acc / l[..., None], 0, 1)
    x = x + _absorbed_out(p, o_lat, x.dtype)
    x, counts = _ffn(p, x, eps, held, top_k, scaling, active=real)
    return x, lat_pool, idx_pool, jnp.concatenate([counts, chose])


def _block_tok(p, x, lat_pool, idx_pool, table, dec, cos_t, sin_t, eps,
               ieps, heads, topk, held, top_k, scaling):
    """One token a slot: x [B, d] at row ``dec`` [B] of each slot's
    timeline (pages ``table`` [B, P])."""
    b = x.shape[0]
    block = lat_pool.shape[2]
    s_max = table.shape[1] * block
    cos, sin = cos_t[dec], sin_t[dec]
    rope = 2 * cos.shape[1]
    q, latent, q_i, k_i, w_i = _attn_inputs(p, x, cos, sin, eps, ieps)
    with jax.named_scope("latent_write"):
        page = jnp.take_along_axis(table, (dec // block)[:, None], 1)[:, 0]
        lat_pool = _write_rows(lat_pool, page, dec % block,
                               _widen(latent, lat_pool.shape[-1])[:, None])
        idx_pool = _write_rows(idx_pool, page, dec % block, k_i[:, None])
    with jax.named_scope("indexer"):
        with jax.named_scope("index_scores"):
            keys = idx_pool[table.reshape(-1)].reshape(b, s_max, -1)
            scores = _index_scores(q_i, keys, w_i)
        with jax.named_scope("index_topk"):
            valid = jnp.arange(s_max)[None, :] <= dec[:, None]
            rows, kept = select_indices(scores, valid, min(topk, s_max))
            active = dec > 0                         # a parked slot: 0
            chose = jnp.stack([jnp.sum(m & active[:, None], dtype=jnp.int32)
                               for m in (valid, kept)])
    with jax.named_scope("sparse_gather"):
        flat = jnp.take_along_axis(table, rows // block, 1) * block \
            + rows % block
        picked = lat_pool.reshape(-1, lat_pool.shape[-1])[flat]  # [B, k, W]
    with jax.named_scope("mla_q"):
        q_nope, q_pe = _split_q(q, heads, rope, cos, sin)
        qc = _widen(_absorb_q(p, q_nope, q_pe), lat_pool.shape[-1])
    lora = latent.shape[1] - rope
    with jax.named_scope("sparse_attention"):
        sc = jnp.einsum("bhc,btc->bht", qc, picked,
                        preferred_element_type=F32) \
            / math.sqrt(q_nope.shape[-1] + rope)
        probs = jax.nn.softmax(jnp.where(kept[:, None], sc, _NEG), -1)
        o_lat = jnp.einsum("bht,btc->bhc", probs.astype(picked.dtype),
                           picked[..., :lora], preferred_element_type=F32)
    x = x + _absorbed_out(p, o_lat, x.dtype)
    x, counts = _ffn(p, x, eps, held, top_k, scaling, active=active)
    return x, lat_pool, idx_pool, jnp.concatenate([counts, chose])


def _head(top, x, eps):
    with jax.named_scope("head"):
        return _mm(_rms(x, top["norm_g"], eps), top["head_w"])


_STATIC = {"eps", "ieps", "heads", "topk", "held", "top_k", "scaling", "kb"}


def _jitted(fn):
    names = [n for n in fn.__code__.co_varnames[:fn.__code__.co_argcount]
             if n in _STATIC]
    return jax.jit(fn, static_argnames=names)


def _block_dense_batch(p, x, cos, sin, eps, ieps, heads, topk, held, top_k,
                       scaling):
    """x [B, S, d]: ``_block_dense`` a sequence at a time."""
    return lax.map(lambda xs: _block_dense(p, xs, cos, sin, eps, ieps, heads,
                                           topk, held, top_k, scaling), x)


# the first call of a ``to_static`` function is eager: jitted a kind of
# block, it compiles a handful of programs and not one per operation
_BLOCKS = {fn.__name__: _jitted(fn) for fn in (
    _block_dense_batch, _block_chunk, _block_tok, _head, _counts_of_step,
    _counts_of_chunk)}


def _run(name, *args, **kwargs):
    return dispatch(_BLOCKS[name], args, kwargs, op_name=f"glm_dsa{name}")


# -- parameters ---------------------------------------------------------------

class _Weight(Layer):
    """One array created in ``dtype``: a matrix drawn at ``std``, or a
    vector held at ``const``."""

    def __init__(self, shape, dtype, std=None, const=None):
        super().__init__(dtype=dtype)
        init = I.Constant(const) if const is not None else I.Normal(0.0, std)
        self.weight = self.create_parameter(list(shape),
                                            default_initializer=init)


class GlmDsaAttention(Layer):
    """MLA's weights and, beside them, the indexer's."""

    def __init__(self, cfg: GlmDsaConfig):
        super().__init__(dtype=cfg.dtype)
        d, heads, std, dt = cfg.hidden_size, cfg.num_attention_heads, \
            cfg.initializer_range, cfg.dtype
        self.q_a_proj = _Weight((d, cfg.q_lora_rank), dt, std)
        self.q_a_layernorm = _Weight((cfg.q_lora_rank,), dt, const=1.0)
        self.q_b_proj = _Weight((cfg.q_lora_rank, heads * cfg.qk_head_dim),
                                dt, std)
        self.kv_a_proj_with_mqa = _Weight((d, cfg.latent_width), dt, std)
        self.kv_a_layernorm = _Weight((cfg.kv_lora_rank,), dt, const=1.0)
        self.kv_b_proj = _Weight(
            (cfg.kv_lora_rank,
             heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dt, std)
        self.o_proj = _Weight((heads * cfg.v_head_dim, d), dt, std)
        width = cfg.index_n_heads * cfg.index_head_dim
        self.indexer_wq_b = _Weight((cfg.q_lora_rank, width), dt, std)
        self.indexer_wk = _Weight((d, cfg.index_head_dim), dt, std)
        self.indexer_k_norm = _Weight((cfg.index_head_dim,), dt, const=1.0)
        self.indexer_k_norm_bias = _Weight((cfg.index_head_dim,), dt,
                                           const=0.0)
        self.indexer_weights_proj = _Weight((d, cfg.index_n_heads), dt, std)

    def leaves(self):
        return {"q_a_w": self.q_a_proj.weight,
                "q_a_g": self.q_a_layernorm.weight,
                "q_b_w": self.q_b_proj.weight,
                "kv_a_w": self.kv_a_proj_with_mqa.weight,
                "kv_a_g": self.kv_a_layernorm.weight,
                "kv_b_w": self.kv_b_proj.weight,
                "o_w": self.o_proj.weight,
                "iq_w": self.indexer_wq_b.weight,
                "ik_w": self.indexer_wk.weight,
                "ik_g": self.indexer_k_norm.weight,
                "ik_b": self.indexer_k_norm_bias.weight,
                "iw_w": self.indexer_weights_proj.weight}


class GlmDsaMLP(Layer):
    """A SwiGLU: ``fc1`` is [gate | up]."""

    def __init__(self, cfg: GlmDsaConfig, width: int):
        super().__init__(dtype=cfg.dtype)
        std = cfg.initializer_range
        self.fc1 = _Weight((cfg.hidden_size, 2 * width), cfg.dtype, std)
        self.fc2 = _Weight((width, cfg.hidden_size), cfg.dtype, std)


class GlmDsaMoE(Layer):
    """The router at its full width, the experts held here stacked on a
    leading axis, the shared expert whole."""

    def __init__(self, cfg: GlmDsaConfig):
        super().__init__(dtype=cfg.dtype)
        d, f, std, dt = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.initializer_range, cfg.dtype
        n = cfg.experts_held_count
        self.gate = _Weight((d, cfg.n_routed_experts), dt, std)
        self.e_score_correction_bias = _Weight((cfg.n_routed_experts,), dt,
                                               const=0.0)
        self.experts_fc1 = _Weight((n, d, 2 * f), dt, std)
        self.experts_fc2 = _Weight((n, f, d), dt, std)
        self.shared_experts = GlmDsaMLP(cfg, f * cfg.n_shared_experts)

    def leaves(self):
        return {"router_w": self.gate.weight,
                "router_b": self.e_score_correction_bias.weight,
                "exp_w1": self.experts_fc1.weight,
                "exp_w2": self.experts_fc2.weight,
                "sh_w1": self.shared_experts.fc1.weight,
                "sh_w2": self.shared_experts.fc2.weight}


class GlmDsaDecoderLayer(Layer):
    def __init__(self, cfg: GlmDsaConfig, index: int):
        super().__init__(dtype=cfg.dtype)
        self.moe = cfg.is_moe(index)
        self.input_layernorm = _Weight((cfg.hidden_size,), cfg.dtype,
                                       const=1.0)
        self.self_attn = GlmDsaAttention(cfg)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,),
                                                cfg.dtype, const=1.0)
        self.mlp = GlmDsaMoE(cfg) if self.moe \
            else GlmDsaMLP(cfg, cfg.intermediate_size)

    def leaves(self):
        """The block's weights under the names the arithmetic reads."""
        ffn = self.mlp.leaves() if self.moe else {
            "mlp_w1": self.mlp.fc1.weight, "mlp_w2": self.mlp.fc2.weight}
        return dict(self.self_attn.leaves(), **ffn,
                    ln1_g=self.input_layernorm.weight,
                    ln2_g=self.post_attention_layernorm.weight)


class GlmDsaModel(Layer):
    def __init__(self, cfg: GlmDsaConfig):
        super().__init__(dtype=cfg.dtype)
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                    cfg.dtype, cfg.initializer_range)
        self.layers = [GlmDsaDecoderLayer(cfg, i)
                       for i in range(cfg.num_hidden_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", layer)
        self.norm = _Weight((cfg.hidden_size,), cfg.dtype, const=1.0)


class GlmDsaForCausalLM(Layer):
    """``GlmDsaForCausalLM(GlmDsaConfig(...))``; ``forward(ids)`` gives the
    logits of every position, ``PagedContinuousBatcher(model, ...)`` serves
    it."""

    def __init__(self, config: GlmDsaConfig):
        super().__init__(dtype=config.dtype)
        c = config
        if c.qk_rope_head_dim % 2 or c.qk_rope_head_dim > c.index_head_dim:
            raise ValueError("RoPE rotates pairs of the first "
                             "qk_rope_head_dim dims of an index key")
        if not 0 < c.experts_held_count <= c.n_routed_experts - \
                c.experts_held_start or c.experts_held_start < 0:
            raise ValueError(
                f"experts held {c.experts_held_start} .. "
                f"{c.experts_held_start + c.experts_held_count} are not "
                f"among the router's {c.n_routed_experts}")
        if c.n_shared_experts < 1:
            raise ValueError("the layer has a shared expert")
        self.config = config
        self.model = GlmDsaModel(config)
        self.lm_head = _Weight((c.hidden_size, c.vocab_size), c.dtype,
                               c.initializer_range)
        import paddle_tpu as paddle
        cos, sin = rope_tables(c.max_position_embeddings, c.qk_rope_head_dim,
                               c.rope_theta)
        # angles made once in float64: arguments of the executables, not
        # constants folded into them
        self._rope = (paddle.to_tensor(cos), paddle.to_tensor(sin))

    def _top(self):
        return {"norm_g": self.model.norm.weight,
                "head_w": self.lm_head.weight}

    def _statics(self):
        c = self.config
        return dict(eps=c.rms_norm_eps, ieps=c.index_norm_eps,
                    heads=c.num_attention_heads, topk=c.index_topk,
                    held=(c.experts_held_start, c.experts_held_count),
                    top_k=c.num_experts_per_tok,
                    scaling=float(c.routed_scaling_factor))

    # -- the whole forward --------------------------------------------------
    def forward(self, input_ids, labels=None, return_selection=False):
        cfg = self.config
        s = input_ids.shape[1]
        with jax.named_scope("embed"):
            x = F.embedding(input_ids, self.model.embed_tokens.weight)
        cos, sin = (t[:s] for t in self._rope)
        kept = []
        for layer in self.model.layers:
            x, keep, _ = _run("_block_dense_batch", layer.leaves(), x, cos,
                              sin, **self._statics())
            kept.append(keep)
        logits = _run("_head", self._top(), x, eps=cfg.rms_norm_eps)
        if return_selection:
            return logits, kept
        if labels is None:
            return logits
        loss = F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]).astype("float32"),
            labels.reshape([-1]))
        return logits, loss

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())

    # -- serving ------------------------------------------------------------
    def paged_serving_contract(self) -> dict:
        """What ``PagedContinuousBatcher`` has to know of this model's
        cache. A block-table page backs ``block_size`` rows of every
        layer's latent row and index key: pages alone, so the prefix cache
        works as it stands (a hit brings both pools' pages).
        ``step_counts``: the cache holds what the steps chose (the
        batcher's ``_init_step_counts_series`` says in what order), and a
        chunk is told how many of its rows are real. ``unsupported``:
        batcher options that assume a page holds per-head K and V, each
        with the reason."""
        kv = "a page holds latent rows and index keys, not per-head K and V"
        return {
            "slot_state": False,
            "step_counts": True,
            "unsupported": {
                "kv_quant": f"no calibrated int8 path: {kv}",
                "cache_quant": f"no dynamic int8 path: {kv}",
                "tier_quant": "needs a host tier",
                "host_kv_gib": f"the host tier spills (K, V) pairs: {kv}",
                "disk_kv_dir": "needs a host tier",
                "draft_model": "the multi-token-prediction head is not "
                               "loaded, and a draft's pool would need "
                               "latent pages of its own",
                "session_store": f"a paused session is spilled as (K, V) "
                                 f"pairs: {kv}",
            }}

    def paged_alloc(self, n_pages, block_size=16, cache_dtype=None):
        """The cache: per layer a latent pool ``[n_pages, 1, block,
        kv_lora_rank + rope]`` (held ``lane_width`` wide, zeros past the
        row) and an index-key pool ``[n_pages, 1, block,
        index_head_dim]`` under one page numbering, and ``step_counts`` [2,
        layers, 6] int32: what the last decode step chose, and all the
        chunks so far."""
        import paddle_tpu as paddle
        from ..observability.metrics import get_registry
        cfg = self.config
        if cache_dtype not in (None, cfg.dtype):
            raise ValueError(f"cache_dtype {cache_dtype!r}: the cache is "
                             f"held in the model's dtype")
        n = cfg.num_hidden_layers

        def pools(width):
            return [paddle.zeros([n_pages, 1, block_size, width],
                                 dtype=cfg.dtype) for _ in range(n)]

        cache = {"latent": pools(lane_width(cfg.latent_width)),
                 "index": pools(cfg.index_head_dim),
                 "step_counts": paddle.zeros([2, n, 6], dtype="int32")}
        get_registry().gauge(
            "serving.latent_cache_bytes",
            "bytes of the latent-row and index-key page pools as "
            "allocated, all layers"
        ).set(sum(t._data.nbytes
                  for t in cache["latent"] + cache["index"]))
        return cache

    def paged_decode_attention_path(self, cache) -> str:
        """Selection, gather and absorbed attention are XLA's."""
        return "dsa=gather"

    def paged_kv_writer(self, cache) -> str:
        """Both pools take their rows by the page."""
        return "page"

    def _ints(self, t, default):
        import paddle_tpu as paddle
        if t is None:
            return paddle.to_tensor(np.array(default, np.int32))
        return t.reshape([]).astype("int32")

    def paged_prefill_into(self, input_ids, layers, block_tables,
                           block_size=16, dec_base=None, logits_at=None,
                           n_valid=None):
        """One sequence's chunk ``input_ids [1, S]`` at rows ``dec_base ..
        dec_base + S`` of the timeline whose pages ``block_tables [1, P]``
        names, its first ``n_valid`` rows real (all of them by default).
        Returns (logits [1, V] of row ``logits_at``, the cache)."""
        import paddle_tpu as paddle
        cfg = self.config
        b, s = input_ids.shape
        if b != 1:
            raise ValueError("a prefill is one sequence: input_ids [1, S]")
        dec = self._ints(dec_base, 0)
        at = self._ints(logits_at, s - 1)
        real = self._ints(n_valid, s)
        table = block_tables.astype("int32").reshape([-1])
        latent, index = list(layers["latent"]), list(layers["index"])
        with jax.named_scope("embed"):
            x = F.embedding(input_ids.reshape([s]),
                            self.model.embed_tokens.weight)
        cos, sin = self._rope
        per_layer = []
        for i, layer in enumerate(self.model.layers):
            x, latent[i], index[i], c = _run(
                "_block_chunk", layer.leaves(), x, latent[i], index[i],
                table, dec, real, cos, sin, kb=cfg.prefill_key_block,
                **self._statics())
            per_layer.append(c)
        counts = _run("_counts_of_chunk", layers["step_counts"], *per_layer)
        x = paddle.index_select(x, at.reshape([1]), axis=0)
        logits = _run("_head", self._top(), x, eps=cfg.rms_norm_eps)
        return logits, {"latent": latent, "index": index,
                        "step_counts": counts}

    def paged_decode_step(self, tok, state):
        """One token a slot. tok [B]; ``state`` as the batcher keeps it:
        ``layers`` (``paged_alloc``'s), ``block_tables`` [B, pages a slot],
        ``dec_lens`` [B], the rows a slot holds before this step."""
        cfg = self.config
        dec = state["dec_lens"].astype("int32")
        table = state["block_tables"].astype("int32")
        cache = state["layers"]
        latent, index = list(cache["latent"]), list(cache["index"])
        with jax.named_scope("embed"):
            x = F.embedding(tok, self.model.embed_tokens.weight)
        cos, sin = self._rope
        per_layer = []
        for i, layer in enumerate(self.model.layers):
            x, latent[i], index[i], c = _run(
                "_block_tok", layer.leaves(), x, latent[i], index[i], table,
                dec, cos, sin, **self._statics())
            per_layer.append(c)
        counts = _run("_counts_of_step", cache["step_counts"], *per_layer)
        logits = _run("_head", self._top(), x, eps=cfg.rms_norm_eps)
        layers = {"latent": latent, "index": index, "step_counts": counts}
        return logits, dict(state, layers=layers,
                            dec_lens=state["dec_lens"] + 1)
