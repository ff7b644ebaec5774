"""LFM2's mixture-of-experts decoder (``model_type: lfm2_moe``): most layers'
operator is a *gated short convolution*, one in four is grouped-query
attention (``layer_types``); the leading layers carry a dense SwiGLU, the
others a sparse expert layer behind a sigmoid router whose bias moves the
choice alone.

A block is ``x += Operator(RMSNorm(x)); x += FFN(RMSNorm(x))``:

  * **Gated short convolution.** ``[B | C | X] = u W_in`` (thirds of 3d),
    ``z_t = B_t X_t``, ``c_t = sum_k w[:, k] z_{t-(L-1)+k}`` a channel
    (depthwise, causal, L = ``conv_L_cache`` taps, ``z`` before the first
    row zero), output ``(C_t c_t) W_out``; no bias, no activation. What a
    sequence has to keep of a layer, whatever its length: the last L - 1
    rows of ``z``.
  * **Attention.** ``q = u W_q`` (H heads of D), ``k = u W_k``, ``v = u
    W_v`` (KV heads of D), every head's ``q`` and ``k`` through an RMSNorm
    over its D dims (one gain for all heads) before the rotation, which
    turns pairs ``(j, j + D/2)`` by ``t * theta^(-2j/D)``; causal softmax of
    ``q . k / sqrt(D)`` in float32, H / KV query heads a key head.
  * **Experts.** ``s = sigmoid(h W_r)`` in float32; the
    ``num_experts_per_tok`` largest of ``s + b`` chosen (``use_expert_bias``:
    ``b`` takes part in the choice only; ties to the lower index); gates
    ``s_e / (sum of the chosen s + 1e-6)`` (``norm_topk_prob``) times
    ``routed_scaling_factor``; the chosen experts' SwiGLUs at their gates.
    No shared expert. The grouped product is ``routed_experts``'; this
    model holds every expert.

The embedding is the head too (tied), behind a final RMSNorm.

Serving. Two kinds of cache in one manager. An attention layer leaves one K
and one V row a token in a pool of pages ``[pages, KV / 2, block, 2 D]``:
heads of 64 lie **two a lane row** (head 2g in lanes 0 .. 63, head 2g + 1 in
64 .. 127), so the one Pallas decode kernel and the page writer take the pool
as it lies and no byte of it is padding; a query head is laid into its key
head's half of the lanes with zeros in the other (the scores are exact, the
other half of the weighted values is dropped). A convolution layer keeps its
L - 1 rows of ``z`` a *slot* (``"slots"``: ``[max_batch, conv layers, L - 1,
d]``), and **snapshots** of them at block boundaries (``"snapshots"``:
``[n + 1, conv layers, L - 1, d]``, the last one scratch) so that the prefix
cache can resume a sequence behind a cached prefix: every ``snapshot_rows``
rows of a prompt the chunk executable itself writes the state at that
boundary (a slice of the rows of ``z`` it has in hand) where
``snapshot_to`` names a snapshot, and the first chunk after a hit starts
from the snapshot ``snapshot_from`` names and not from the slot. Rows
written while decoding take no snapshot: a later turn resumes from the last
boundary of a prompt. ``forward`` is the plain form over whole sequences.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..incubate.nn.functional.decode_attention import (decode_attention_path,
                                                       write_page_rows)
from ..nn import functional as F
from ..nn.layer import Layer
from ..ops.registry import dispatch
from .glm_dsa import _Weight, _key_block, _page_window, _rms
from .mellum import _rope_half, _rows_of, _write_run, rope_tables
from .routed_experts import (F32, _counts_of_chunk, _counts_of_step, _mm,
                             _swiglu, expert_counts, routed_experts)

_NEG = -1e30
CONV, FULL = "conv", "full_attention"


@dataclass
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple = ()            # CONV / FULL a layer; () = 3 : 1
    conv_L_cache: int = 3              # taps of the short convolution
    intermediate_size: int = 11776     # the leading layers' dense SwiGLU
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    prefill_key_block: int = 1024      # held rows an attention chunk reads
    snapshot_rows: int = 128           # a state snapshot every so many rows
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                FULL if i % 4 == 2 else CONV
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def state_rows(self) -> int:
        """Rows of ``z`` a convolution layer carries."""
        return self.conv_L_cache - 1


def lfm2_tiny_config(**overrides) -> Lfm2Config:
    """Test-scale config of the same shape: a dense convolution layer, then
    two periods of an attention layer and three convolution layers; four
    heads of 64 over two key heads (one lane row), 8 experts, 2 a token."""
    return Lfm2Config(**dict(dict(
        vocab_size=128, hidden_size=256, num_hidden_layers=9,
        num_attention_heads=4, num_key_value_heads=2,
        layer_types=(CONV,) + (FULL, CONV, CONV, CONV) * 2,
        intermediate_size=96, num_dense_layers=1, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32,
        prefill_key_block=32, snapshot_rows=8, max_position_embeddings=512,
        initializer_range=0.1), **overrides))


# -- arithmetic on arrays -----------------------------------------------------
# Pure ``jax.numpy`` over a dict of one block's weights, called through
# ``ops.registry.dispatch`` so that the weights are the executable's state.
# Products accumulate in float32; norms, softmax, the router and the
# convolution's sum are float32; ``z`` is held in the activations' dtype.

def route(p, h, top_k: int, norm_topk: bool, scaling: float):
    """(chosen experts [N, k] int32, gates [N, k] float32) of rows h: the k
    largest of ``sigmoid + bias`` (ties to the lower index), gated by the
    sigmoid alone."""
    s = jax.nn.sigmoid(_mm(h, p["router_w"], F32))
    biased = s + p["router_b"].astype(F32) if "router_b" in p else s
    _, chosen = lax.top_k(biased, top_k)
    picked = jnp.take_along_axis(s, chosen, -1)
    if norm_topk:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)
    return chosen.astype(jnp.int32), scaling * picked


def _ffn(p, x, eps, top_k, norm_topk, scaling, active=None):
    """x [N, d] -> (x + FFN(RMSNorm(x)), counts [6] int32 as ``step_counts``
    holds them: zeros of a dense layer, the indexer's two columns zero).
    Rows that are not ``active`` (parked slots, a chunk's pad rows) are
    routed nowhere."""
    h = _rms(x, p["ln2_g"], eps)
    if "router_w" not in p:
        with jax.named_scope("mlp"):
            return x + _swiglu(h, p["mlp_w1"], p["mlp_w2"]), \
                jnp.zeros(6, jnp.int32)
    with jax.named_scope("router"):
        chosen, gates = route(p, h, top_k, norm_topk, scaling)
        routed = x.shape[0]
        if active is not None:
            chosen = jnp.where(active[:, None], chosen, -1)
            routed = jnp.sum(active, dtype=jnp.int32)
    with jax.named_scope("experts_routed"):
        y, counts = routed_experts(p, h, chosen, gates,
                                   (0, p["exp_w1"].shape[0]))
    return x + y, jnp.concatenate([expert_counts(counts, routed * top_k),
                                   jnp.zeros(2, jnp.int32)])


def _short_conv(p, x, before, eps):
    """The operator over rows x [..., S, d] that follow the rows of ``z``
    ``before`` [..., L - 1, d]: (x + operator's output, ``[before ; z]``
    [..., L - 1 + S, d]). Row t of the chunk reads rows t .. t + L - 1 of
    that. (Leading axes: a decode step's slots, each a sequence of one
    row.)"""
    s, d = x.shape[-2:]
    with jax.named_scope("in_proj"):
        bcx = _mm(_rms(x, p["ln1_g"], eps), p["in_w"], F32)
    with jax.named_scope("gate_conv"):
        z = (bcx[..., :d] * bcx[..., 2 * d:]).astype(x.dtype)
        rows = jnp.concatenate([before.astype(x.dtype), z], -2)
        taps = p["conv_w"].astype(F32)                       # [d, L]
        zf = rows.astype(F32)
        c = sum(taps[:, k] * zf[..., k:k + s, :]
                for k in range(taps.shape[1]))
        y = (bcx[..., d:2 * d] * c).astype(x.dtype)
    with jax.named_scope("out_proj"):
        return x + _mm(y, p["out_w"]), rows


def _conv_dense(p, x, eps, top_k, norm_topk, scaling):
    """One sequence x [S, d], nothing carried."""
    keep = p["conv_w"].shape[1] - 1
    with jax.named_scope("short_conv"):
        x, _ = _short_conv(p, x, jnp.zeros((keep, x.shape[1]), x.dtype), eps)
    return _ffn(p, x, eps, top_k, norm_topk, scaling)[0]


def _conv_chunk(p, x, before, n_valid, bounds, eps, top_k, norm_topk,
                scaling):
    """One sequence's chunk x [S, d] behind the state ``before`` [L - 1, d].
    Returns x, the state after the chunk's ``n_valid``-th row (the pad rows
    behind it change nothing), the states at the rows ``bounds`` [n] of the
    chunk (``bounds[j]`` rows of it done) and the counts."""
    keep = before.shape[0]
    with jax.named_scope("short_conv"):
        x, rows = _short_conv(p, x, before, eps)
        with jax.named_scope("state_write"):
            after = lax.dynamic_slice_in_dim(rows, n_valid, keep, 0)
            at = jax.vmap(lambda r: lax.dynamic_slice_in_dim(
                rows, r, keep, 0))(bounds)
    x, counts = _ffn(p, x, eps, top_k, norm_topk, scaling,
                     active=jnp.arange(x.shape[0]) < n_valid)
    return x, after, at, counts


def _conv_tok(p, x, state, dec, eps, top_k, norm_topk, scaling):
    """One token a slot: x [B, d] behind each slot's ``state`` [B, L - 1,
    d], every slot a sequence of one row. A parked slot (``dec`` 0) routes
    nowhere and keeps its state."""
    active = dec > 0
    with jax.named_scope("short_conv"):
        x, rows = _short_conv(p, x[:, None, :], state, eps)  # [B, L, d]
        with jax.named_scope("state_write"):
            state = jnp.where(active[:, None, None], rows[:, 1:], state)
    x, counts = _ffn(p, x[:, 0], eps, top_k, norm_topk, scaling,
                     active=active)
    return x, state, counts


def _qkv(p, x, cos, sin, eps, heads, kv_heads):
    """Rows x [N, d] at angles cos / sin [N, D/2] -> (q [N, H, D], k and v
    [N, KV, D]): projections, the heads' norms, the rotation."""
    with jax.named_scope("qkv_rope"):
        n = x.shape[0]
        h = _rms(x, p["ln1_g"], eps)
        q = _rms(_mm(h, p["q_w"]).reshape(n, heads, -1), p["q_g"], eps)
        k = _rms(_mm(h, p["k_w"]).reshape(n, kv_heads, -1), p["k_g"], eps)
        v = _mm(h, p["v_w"]).reshape(n, kv_heads, -1)
        return _rope_half(q, cos, sin), _rope_half(k, cos, sin), v


def _out(p, x, ctx):
    """x + the heads' outputs ctx [N, H, D] side by side times ``W_o``."""
    with jax.named_scope("o_proj"):
        return x + _mm(ctx.reshape(ctx.shape[0], -1).astype(x.dtype),
                       p["o_w"])


def _paired(q, kv_heads):
    """q [N, H, D] -> [KV / 2, 2, H / KV, N, D]: the query heads of key
    head 2g + h are rows of one product against half h of lane row g."""
    n, heads, d = q.shape
    return jnp.transpose(
        q.reshape(n, kv_heads // 2, 2, heads // kv_heads, d), (1, 2, 3, 0, 4))


def _unpaired(ctx):
    """[KV / 2, 2, rep, N, D] -> [N, H, D]."""
    g, two, rep, n, d = ctx.shape
    return jnp.transpose(ctx, (3, 0, 1, 2, 4)).reshape(n, g * two * rep, d)


def _halves(rows):
    """Packed rows [KV / 2, T, 2 D] -> [KV / 2, T, 2, D]: the two key heads
    of a lane row."""
    g, t, wide = rows.shape
    return rows.reshape(g, t, 2, wide // 2)


def _attn_dense(p, x, cos, sin, eps, heads, kv_heads, top_k, norm_topk,
                scaling):
    """One sequence x [S, d], nothing cached."""
    s = x.shape[0]
    q, k, v = _qkv(p, x, cos, sin, eps, heads, kv_heads)
    ok = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    rep = heads // kv_heads
    with jax.named_scope("full_attention"), jax.named_scope("scores"):
        qg = jnp.transpose(q.reshape(s, kv_heads, rep, -1), (1, 2, 0, 3))
        sc = jnp.einsum("grsd,tgd->grst", qg, k,
                        preferred_element_type=F32) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(ok, sc, _NEG), -1)
        ctx = jnp.einsum("grst,tgd->sgrd", probs.astype(v.dtype), v,
                         preferred_element_type=F32)
    x = _out(p, x, ctx.reshape(s, heads, -1))
    return _ffn(p, x, eps, top_k, norm_topk, scaling)[0]


def _attn_chunk(p, x, k_pool, v_pool, table, dec, n_valid, cos_t, sin_t,
                eps, heads, kv_heads, top_k, norm_topk, scaling, kb):
    """One sequence's chunk x [S, d] at rows dec .. dec + S of the timeline
    whose pages ``table`` [P] names: its K and V rows go into the pages, two
    heads a lane row, then every query attends over the rows held, ``kb``
    rows at a time with a running softmax and no further than the rows
    held. The first ``n_valid`` rows are real."""
    s = x.shape[0]
    block, wide = k_pool.shape[2], k_pool.shape[3]
    pos = dec + jnp.arange(s)
    q, k, v = _qkv(p, x, cos_t[pos], sin_t[pos], eps, heads, kv_heads)
    qg = _paired(q, kv_heads)
    scale = 1.0 / math.sqrt(q.shape[-1])
    with jax.named_scope("kv_write"):
        under, at = _page_window(table, dec, s, block)
        k_pool = _write_run(k_pool, under, at, k.reshape(s, -1, wide))
        v_pool = _write_run(v_pool, under, at, v.reshape(s, -1, wide))
    kb = _key_block(kb, table.shape[0] * block, block)

    def attend(i, carry):
        m, l, acc = carry
        pages = lax.dynamic_slice_in_dim(table, i * (kb // block),
                                         kb // block)
        ok = (i * kb + jnp.arange(kb))[None, :] <= pos[:, None]
        sc = jnp.einsum("ghrsd,gthd->ghrst", qg,
                        _halves(_rows_of(k_pool, pages)),
                        preferred_element_type=F32) * scale
        sc = jnp.where(ok, sc, _NEG)
        m2 = jnp.maximum(m, jnp.max(sc, -1))
        pr = jnp.exp(sc - m2[..., None])
        corr = jnp.exp(m - m2)
        acc = acc * corr[..., None] + jnp.einsum(
            "ghrst,gthd->ghrsd", pr.astype(v_pool.dtype),
            _halves(_rows_of(v_pool, pages)), preferred_element_type=F32)
        return m2, l * corr + jnp.sum(pr, -1), acc

    with jax.named_scope("full_attention"), jax.named_scope("scores"):
        lead = qg.shape[:4]
        m, l, acc = lax.fori_loop(
            0, (dec + s + kb - 1) // kb, attend,
            (jnp.full(lead, _NEG, F32), jnp.zeros(lead, F32),
             jnp.zeros(qg.shape, F32)))
        ctx = acc / l[..., None]
    x = _out(p, x, _unpaired(ctx))
    x, counts = _ffn(p, x, eps, top_k, norm_topk, scaling,
                     active=jnp.arange(s) < n_valid)
    return x, k_pool, v_pool, counts


def _decode_scores(q, k_pool, v_pool, table, kv_len):
    """q [B, H, D] against the first ``kv_len`` rows each slot's ``table``
    [B, n] backs, out of pools that hold two key heads a lane row: every
    query head is laid into its key head's half of the lanes, so the
    Pallas kernel over the pages in place (on the chip) and the gathered
    rows (elsewhere) both see plain heads of 2 D; the half of the weighted
    values that is the other head's is dropped."""
    b, heads, d = q.shape
    lane_rows = k_pool.shape[1]
    half = (jnp.arange(heads) * 2 * lane_rows // heads) % 2 == 1
    none = jnp.zeros_like(q)
    qp = jnp.where(half[None, :, None], jnp.concatenate([none, q], -1),
                   jnp.concatenate([q, none], -1))
    if decode_attention_path(k_pool.shape, k_pool.dtype, heads) == "kernel":
        from ..ops.pallas.paged_attention import paged_attention_decode
        ctx = paged_attention_decode(qp, k_pool, v_pool, table, kv_len,
                                     scale=d ** -0.5)
    else:
        rows = table.shape[1] * k_pool.shape[2]

        def gathered(pool):
            got = pool[table.reshape(-1)].reshape(
                b, -1, lane_rows, pool.shape[2], 2 * d)
            return jnp.moveaxis(got, 2, 1).reshape(b, lane_rows, rows, 2 * d)

        ok = jnp.arange(rows)[None, :] < kv_len[:, None]
        sc = jnp.einsum("bgrd,bgtd->bgrt",
                        qp.reshape(b, lane_rows, heads // lane_rows, 2 * d),
                        gathered(k_pool), preferred_element_type=F32) \
            / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(ok[:, None, None, :], sc, _NEG), -1)
        ctx = jnp.einsum("bgrt,bgtd->bgrd", probs.astype(v_pool.dtype),
                         gathered(v_pool),
                         preferred_element_type=F32).reshape(b, heads, 2 * d)
    return jnp.where(half[None, :, None], ctx[..., d:], ctx[..., :d])


def _attn_tok(p, x, k_pool, v_pool, table, dec, cos_t, sin_t, eps, heads,
              kv_heads, top_k, norm_topk, scaling):
    """One token a slot: x [B, d] at row ``dec`` [B] of each slot's
    timeline (``table`` [B, P] its pages)."""
    block, wide = k_pool.shape[2], k_pool.shape[3]
    q, k, v = _qkv(p, x, cos_t[dec], sin_t[dec], eps, heads, kv_heads)
    page = jnp.take_along_axis(table, (dec // block)[:, None], 1)[:, 0]
    with jax.named_scope("kv_write"):
        k_pool = write_page_rows(k_pool, page, dec % block,
                                 k.reshape(k.shape[0], -1, wide))
        v_pool = write_page_rows(v_pool, page, dec % block,
                                 v.reshape(v.shape[0], -1, wide))
    with jax.named_scope("full_attention"), jax.named_scope("scores"):
        ctx = _decode_scores(q, k_pool, v_pool, table, dec + 1)
    x = _out(p, x, ctx)
    x, counts = _ffn(p, x, eps, top_k, norm_topk, scaling, active=dec > 0)
    return x, k_pool, v_pool, counts


def _state_before(slots, snaps, slot, dec, snapshot_from):
    """The state a chunk at row ``dec`` of ``slot`` starts from, every
    convolution layer's [C, L - 1, d]: the snapshot ``snapshot_from`` names
    (the first chunk behind a cached prefix), else the slot's own as its
    last chunk left it, zeros at row 0."""
    with jax.named_scope("short_conv"), jax.named_scope("state_read"):
        own = jnp.where(dec > 0, slots[slot], 0)
        return jnp.where(snapshot_from >= 0,
                         snaps[jnp.maximum(snapshot_from, 0)], own)


def _state_after_chunk(slots, snaps, slot, snapshot_to, after, at):
    """A chunk's states go where they are kept: ``after`` [C, L - 1, d] into
    the slot, the boundary states ``at`` [C, n, L - 1, d] into the
    snapshots ``snapshot_to`` [n] names (-1: none; they land in the scratch
    snapshot, which nothing reads)."""
    with jax.named_scope("short_conv"), jax.named_scope("state_write"):
        to = jnp.where(snapshot_to < 0, snaps.shape[0] - 1, snapshot_to)
        return slots.at[slot].set(after), \
            snaps.at[to].set(jnp.moveaxis(at, 0, 1))


def _boundaries(dec, n: int, every: int, rows: int):
    """Entry j of a chunk's ``snapshot_to`` is for the j-th multiple of
    ``every`` past its first row ``dec``: as rows of the chunk done, no
    further than its ``rows``."""
    return jnp.minimum((dec // every + 1 + jnp.arange(n, dtype=jnp.int32))
                       * every - dec, rows)


def _stack(*arrays):
    return jnp.stack(arrays)


def _stack_slots(*states):
    """The layers' [B, L - 1, d] -> ``slots`` [B, C, L - 1, d]."""
    with jax.named_scope("short_conv"), jax.named_scope("state_write"):
        return jnp.stack(states, 1)


def _head(top, x, eps):
    """Final norm and the tied head: logits over the embedding's rows."""
    with jax.named_scope("head"):
        return jnp.einsum("...d,vd->...v", _rms(x, top["norm_g"], eps),
                          top["embed"],
                          preferred_element_type=F32).astype(x.dtype)


_STATIC = ("eps", "heads", "kv_heads", "top_k", "norm_topk", "scaling", "kb",
           "n", "every", "rows")


def _jitted(fn):
    code = fn.__code__
    names = [n for n in code.co_varnames[:code.co_argcount
                                         + code.co_kwonlyargcount]
             if n in _STATIC]
    return jax.jit(fn, static_argnames=names)


def _conv_dense_batch(p, x, eps, top_k, norm_topk, scaling):
    return lax.map(lambda xs: _conv_dense(p, xs, eps, top_k, norm_topk,
                                          scaling), x)


def _attn_dense_batch(p, x, cos, sin, eps, heads, kv_heads, top_k,
                      norm_topk, scaling):
    return lax.map(lambda xs: _attn_dense(
        p, xs, cos, sin, eps, heads, kv_heads, top_k, norm_topk, scaling), x)


# the first call of a ``to_static`` function is eager: jitted a kind of
# block, it compiles a handful of programs and not one per operation
_BLOCKS = {fn.__name__: _jitted(fn) for fn in (
    _conv_dense_batch, _attn_dense_batch, _conv_chunk, _attn_chunk,
    _conv_tok, _attn_tok, _state_before, _state_after_chunk, _boundaries,
    _stack,
    _stack_slots, _head, _counts_of_step, _counts_of_chunk)}


def _run(name, *args, **kwargs):
    return dispatch(_BLOCKS[name], args, kwargs, op_name=f"lfm2{name}")


# -- parameters ---------------------------------------------------------------

class Lfm2ShortConv(Layer):
    def __init__(self, cfg: Lfm2Config):
        super().__init__(dtype=cfg.dtype)
        d, std, dt = cfg.hidden_size, cfg.initializer_range, cfg.dtype
        self.in_proj = _Weight((d, 3 * d), dt, std)
        self.conv = _Weight((d, cfg.conv_L_cache), dt, std)
        self.out_proj = _Weight((d, d), dt, std)


class Lfm2Attention(Layer):
    def __init__(self, cfg: Lfm2Config):
        super().__init__(dtype=cfg.dtype)
        d, hd, std, dt = cfg.hidden_size, cfg.head_dim, \
            cfg.initializer_range, cfg.dtype
        self.q_proj = _Weight((d, cfg.num_attention_heads * hd), dt, std)
        self.k_proj = _Weight((d, cfg.num_key_value_heads * hd), dt, std)
        self.v_proj = _Weight((d, cfg.num_key_value_heads * hd), dt, std)
        self.out_proj = _Weight((cfg.num_attention_heads * hd, d), dt, std)
        self.q_layernorm = _Weight((hd,), dt, const=1.0)
        self.k_layernorm = _Weight((hd,), dt, const=1.0)


class Lfm2MLP(Layer):
    """A SwiGLU: ``fc1`` is [gate | up]."""

    def __init__(self, cfg: Lfm2Config):
        super().__init__(dtype=cfg.dtype)
        std = cfg.initializer_range
        self.fc1 = _Weight((cfg.hidden_size, 2 * cfg.intermediate_size),
                           cfg.dtype, std)
        self.fc2 = _Weight((cfg.intermediate_size, cfg.hidden_size),
                           cfg.dtype, std)


class Lfm2MoE(Layer):
    """The router over every expert with its selection bias, the experts
    stacked on a leading axis (``experts_fc1`` is [gate | up])."""

    def __init__(self, cfg: Lfm2Config):
        super().__init__(dtype=cfg.dtype)
        d, f, n, std, dt = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.num_experts, cfg.initializer_range, cfg.dtype
        self.gate = _Weight((d, n), dt, std)
        if cfg.use_expert_bias:
            self.expert_bias = _Weight((n,), dt, const=0.0)
        self.experts_fc1 = _Weight((n, d, 2 * f), dt, std)
        self.experts_fc2 = _Weight((n, f, d), dt, std)


class Lfm2DecoderLayer(Layer):
    def __init__(self, cfg: Lfm2Config, layer: int):
        super().__init__(dtype=cfg.dtype)
        self.kind = cfg.layer_types[layer]
        self.operator_norm = _Weight((cfg.hidden_size,), cfg.dtype, const=1.0)
        if self.kind == CONV:
            self.conv = Lfm2ShortConv(cfg)
        else:
            self.self_attn = Lfm2Attention(cfg)
        self.ffn_norm = _Weight((cfg.hidden_size,), cfg.dtype, const=1.0)
        self.feed_forward = Lfm2MLP(cfg) if layer < cfg.num_dense_layers \
            else Lfm2MoE(cfg)

    def leaves(self):
        """The block's weights under the names the arithmetic reads."""
        out = {"ln1_g": self.operator_norm.weight,
               "ln2_g": self.ffn_norm.weight}
        if self.kind == CONV:
            c = self.conv
            out.update(in_w=c.in_proj.weight, conv_w=c.conv.weight,
                       out_w=c.out_proj.weight)
        else:
            a = self.self_attn
            out.update(q_w=a.q_proj.weight, k_w=a.k_proj.weight,
                       v_w=a.v_proj.weight, o_w=a.out_proj.weight,
                       q_g=a.q_layernorm.weight, k_g=a.k_layernorm.weight)
        f = self.feed_forward
        if isinstance(f, Lfm2MLP):
            out.update(mlp_w1=f.fc1.weight, mlp_w2=f.fc2.weight)
        else:
            out.update(router_w=f.gate.weight, exp_w1=f.experts_fc1.weight,
                       exp_w2=f.experts_fc2.weight)
            if hasattr(f, "expert_bias"):
                out["router_b"] = f.expert_bias.weight
        return out


class Lfm2Model(Layer):
    def __init__(self, cfg: Lfm2Config):
        super().__init__(dtype=cfg.dtype)
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                    cfg.dtype, cfg.initializer_range)
        self.layers = [Lfm2DecoderLayer(cfg, i)
                       for i in range(cfg.num_hidden_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", layer)
        self.embedding_norm = _Weight((cfg.hidden_size,), cfg.dtype,
                                      const=1.0)


class Lfm2ForCausalLM(Layer):
    """``Lfm2ForCausalLM(Lfm2Config(...))``; ``forward(ids)`` gives the
    logits of every position, ``PagedContinuousBatcher(model, ...)`` serves
    it, with the prefix cache where asked."""

    def __init__(self, config: Lfm2Config):
        super().__init__(dtype=config.dtype)
        c = config
        if len(c.layer_types) != c.num_hidden_layers or \
                set(c.layer_types) - {CONV, FULL}:
            raise ValueError(f"layer_types names a kind ({CONV} or {FULL}) "
                             f"for each of the {c.num_hidden_layers} layers")
        if c.hidden_size % c.num_attention_heads \
                or c.num_attention_heads % c.num_key_value_heads \
                or c.num_key_value_heads % 2 or c.head_dim % 2:
            raise ValueError(
                "heads divide the hidden size, query heads share key heads "
                "in whole groups, key heads lie two a lane row, and the "
                "rotation turns pairs (j, j + D/2)")
        if not 0 < c.num_experts_per_tok <= c.num_experts:
            raise ValueError("num_experts_per_tok of num_experts")
        if c.conv_L_cache < 2:
            raise ValueError("a short convolution has two taps or more")
        self.config = config
        self.model = Lfm2Model(config)
        self._conv_layers = [i for i, k in enumerate(c.layer_types)
                             if k == CONV]
        import paddle_tpu as paddle
        # angles made once in float64: arguments of the executables, not
        # constants folded into them
        self._rope = tuple(paddle.to_tensor(t) for t in rope_tables(
            c.max_position_embeddings, c.head_dim, c.rope_theta))

    def _top(self):
        return {"norm_g": self.model.embedding_norm.weight,
                "embed": self.model.embed_tokens.weight}

    def _statics(self, layer: int):
        c = self.config
        ffn = dict(eps=c.norm_eps, top_k=c.num_experts_per_tok,
                   norm_topk=bool(c.norm_topk_prob),
                   scaling=float(c.routed_scaling_factor))
        if c.layer_types[layer] == FULL:
            ffn.update(heads=c.num_attention_heads,
                       kv_heads=c.num_key_value_heads)
        return ffn

    # -- the whole forward --------------------------------------------------
    def forward(self, input_ids, labels=None):
        cfg = self.config
        s = input_ids.shape[1]
        with jax.named_scope("embed"):
            x = F.embedding(input_ids, self.model.embed_tokens.weight)
        cos, sin = (t[:s] for t in self._rope)
        for i, layer in enumerate(self.model.layers):
            if layer.kind == CONV:
                x = _run("_conv_dense_batch", layer.leaves(), x,
                         **self._statics(i))
            else:
                x = _run("_attn_dense_batch", layer.leaves(), x, cos, sin,
                         **self._statics(i))
        logits = _run("_head", self._top(), x, eps=cfg.norm_eps)
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
        cache. ``slot_state``: ``paged_alloc`` takes ``max_batch`` and
        keeps the convolution layers' rows of ``z`` under ``"slots"``,
        indexed by slot, and ``paged_prefill_into`` takes the ``slot`` and
        the chunk's ``n_valid`` rows. ``state_snapshots``: with the prefix
        cache on, ``paged_alloc`` takes ``n_snapshots`` and keeps that many
        copies of a slot's state under ``"snapshots"``; a prefill is told
        which of them to start from (``snapshot_from`` [1], -1: the slot's
        own) and which to write at the boundaries it passes
        (``snapshot_to``: entry j is for the j-th multiple of ``rows`` after
        the chunk's first row, -1 for none), so a cached prefix is usable
        up to the deepest boundary whose snapshot is still held. Rows
        written by decode steps take no snapshot. ``step_counts``: the
        cache holds what the steps' routers chose. ``unsupported``: batcher
        options that assume a sequence's cache is pages of K and V alone,
        each with the reason."""
        state = "the convolution layers' state is not pages"
        return {
            "slot_state": True,
            "step_counts": True,
            "state_snapshots": {"rows": self.config.snapshot_rows},
            "unsupported": {
                "kv_quant": f"no calibrated int8 path for pools of two "
                            f"heads a lane row beside float state; {state}",
                "cache_quant": f"no dynamic int8 path; {state}",
                "tier_quant": "needs a host tier",
                "host_kv_gib": f"the host tier spills a node's (K, V) "
                               f"pages and knows no state snapshot; {state}",
                "disk_kv_dir": "needs a host tier",
                "draft_model": "a rejected proposal would have to roll the "
                               "convolution state back",
                "session_store": f"a paused session is spilled as (K, V) "
                                 f"pages and knows no state snapshot; "
                                 f"{state}",
            }}

    def paged_alloc(self, n_pages, block_size=16, cache_dtype=None,
                    max_batch=None, n_snapshots=0):
        """The cache: per attention layer a (K, V) pair of pools ``[n_pages,
        KV / 2, block, 2 D]``; ``"slots"``: the convolution layers' state by
        slot; ``"snapshots"``: ``n_snapshots`` copies of a slot's state and
        one scratch behind them; ``step_counts`` [2, layers, 6] int32."""
        import paddle_tpu as paddle
        from ..observability.metrics import get_registry
        cfg = self.config
        if max_batch is None:
            raise ValueError("paged_alloc needs max_batch: the convolution "
                             "layers' state is an array indexed by slot")
        if cache_dtype not in (None, cfg.dtype):
            raise ValueError(f"cache_dtype {cache_dtype!r}: the cache is "
                             f"held in the model's dtype")
        n = cfg.num_hidden_layers
        state = [len(self._conv_layers), cfg.state_rows, cfg.hidden_size]

        def pool():
            return paddle.zeros(
                [n_pages, cfg.num_key_value_heads // 2, block_size,
                 2 * cfg.head_dim], dtype=cfg.dtype)

        cache = {
            "kv": [(pool(), pool()) for k in cfg.layer_types if k == FULL],
            "slots": {"conv": paddle.zeros([max_batch] + state,
                                           dtype=cfg.dtype)},
            "snapshots": paddle.zeros([n_snapshots + 1] + state,
                                      dtype=cfg.dtype),
            "step_counts": paddle.zeros([2, n, 6], dtype="int32")}
        reg = get_registry()
        reg.gauge(
            "serving.kv_cache_bytes",
            "bytes of a page group's K and V pools as allocated, all its "
            "layers", labelnames=("group",)).labels(group="full").set(sum(
                k._data.nbytes + v._data.nbytes for k, v in cache["kv"]))
        reg.gauge(
            "serving.state_snapshot_bytes",
            "bytes of the store of recurrent-state snapshots as allocated "
            "(the scratch snapshot among them)").set(
                cache["snapshots"]._data.nbytes)
        return cache

    def paged_decode_attention_path(self, cache) -> str:
        k = cache["kv"][0][0]
        return decode_attention_path(tuple(k.shape), k._data.dtype,
                                     self.config.num_attention_heads)

    def paged_kv_writer(self, cache) -> str:
        """Every pool takes its rows by the page."""
        return "page"

    def _ints(self, t, default, shape=()):
        import paddle_tpu as paddle
        if t is None:
            return paddle.to_tensor(np.full(shape, default, np.int32))
        return t.reshape(list(shape)).astype("int32")

    def paged_prefill_into(self, input_ids, layers, block_tables,
                           block_size=16, dec_base=None, logits_at=None,
                           n_valid=None, slot=None, snapshot_from=None,
                           snapshot_to=None):
        """One sequence's chunk ``input_ids [1, S]`` at rows ``dec_base ..
        dec_base + S`` of the timeline of ``slot`` whose pages
        ``block_tables [1, P]`` names, its first ``n_valid`` rows real (all
        of them by default). The convolution layers start from the snapshot
        ``snapshot_from`` where it names one, else from the slot's state
        (zeros at row 0), and leave the state at every boundary
        ``snapshot_to`` names a snapshot for. Returns (logits [1, V] of row
        ``logits_at``, the cache)."""
        import paddle_tpu as paddle
        cfg = self.config
        b, s = input_ids.shape
        if b != 1:
            raise ValueError("a prefill is one sequence: input_ids [1, S]")
        if slot is None:
            raise ValueError("a prefill is told its slot: the convolution "
                             "layers' state is kept by slot")
        dec = self._ints(dec_base, 0)
        at = self._ints(logits_at, s - 1)
        real = self._ints(n_valid, s)
        slot = self._ints(slot, 0)
        src = self._ints(snapshot_from, -1)
        every = cfg.snapshot_rows
        to = self._ints(snapshot_to, -1, (-(-s // every),)) \
            if snapshot_to is None else snapshot_to.astype("int32")
        bounds = _run("_boundaries", dec, n=to.shape[0], every=every, rows=s)
        table = block_tables.astype("int32").reshape([-1])
        kv = list(layers["kv"])
        slots, snaps = layers["slots"]["conv"], layers["snapshots"]
        before = _run("_state_before", slots, snaps, slot, dec, src)
        with jax.named_scope("embed"):
            x = F.embedding(input_ids.reshape([s]),
                            self.model.embed_tokens.weight)
        cos, sin = self._rope
        per_layer, after, marks = [], [], []
        ai = ci = 0
        for i, layer in enumerate(self.model.layers):
            if layer.kind == CONV:
                x, st, mk, c = _run(
                    "_conv_chunk", layer.leaves(), x, before[ci], real,
                    bounds, **self._statics(i))
                after.append(st)
                marks.append(mk)
                ci += 1
            else:
                x, k, v, c = _run(
                    "_attn_chunk", layer.leaves(), x, kv[ai][0], kv[ai][1],
                    table, dec, real, cos, sin, kb=cfg.prefill_key_block,
                    **self._statics(i))
                kv[ai] = (k, v)
                ai += 1
            per_layer.append(c)
        slots, snaps = _run("_state_after_chunk", slots, snaps, slot, to,
                            _run("_stack", *after), _run("_stack", *marks))
        counts = _run("_counts_of_chunk", layers["step_counts"], *per_layer)
        x = paddle.index_select(x, at.reshape([1]), axis=0)
        logits = _run("_head", self._top(), x, eps=cfg.norm_eps)
        return logits, {"kv": kv, "slots": {"conv": slots},
                        "snapshots": snaps, "step_counts": counts}

    def paged_decode_step(self, tok, state):
        """One token a slot. tok [B]; ``state`` as the batcher keeps it:
        ``layers`` (``paged_alloc``'s), ``block_tables`` [B, pages a slot],
        ``dec_lens`` [B] the rows a slot holds before this step (0: the
        slot is parked, its state is left alone)."""
        cfg = self.config
        dec = state["dec_lens"].astype("int32")
        table = state["block_tables"].astype("int32")
        cache = state["layers"]
        kv = list(cache["kv"])
        slots = cache["slots"]["conv"]
        with jax.named_scope("embed"):
            x = F.embedding(tok, self.model.embed_tokens.weight)
        cos, sin = self._rope
        per_layer, states = [], []
        ai = ci = 0
        for i, layer in enumerate(self.model.layers):
            if layer.kind == CONV:
                x, st, c = _run("_conv_tok", layer.leaves(), x, slots[:, ci],
                                dec, **self._statics(i))
                states.append(st)
                ci += 1
            else:
                x, k, v, c = _run(
                    "_attn_tok", layer.leaves(), x, kv[ai][0], kv[ai][1],
                    table, dec, cos, sin, **self._statics(i))
                kv[ai] = (k, v)
                ai += 1
            per_layer.append(c)
        counts = _run("_counts_of_step", cache["step_counts"], *per_layer)
        logits = _run("_head", self._top(), x, eps=cfg.norm_eps)
        layers = {"kv": kv, "slots": {"conv": _run("_stack_slots", *states)},
                  "snapshots": cache["snapshots"], "step_counts": counts}
        return logits, dict(state, layers=layers,
                            dec_lens=state["dec_lens"] + 1)
