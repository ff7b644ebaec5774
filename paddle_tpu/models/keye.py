"""Keye-VL 2's language model (``model_type: KeyeVL2``): grouped-query
attention over per-head K and V rows, of which DeepSeek-V3.2's indexer keeps
``index_topk`` a query (``sa_config``), under rotary positions with three
axes (``mrope_section``), and a sparse expert layer in every block. The
vision tower in front of it is not here: its sizes are not published.

A block is ``x += Attn(RMSNorm(x)); x += Experts(RMSNorm(x))``:

  * **Attention.** ``q = h W_q`` (H heads of D), ``k = h W_k``, ``v = h W_v``
    (KV heads of D), no bias; every head's ``q`` and ``k`` pass an RMSNorm
    over their D dims (one gain for all heads) before the rotation. The
    rotation turns pairs ``(i, i + D/2)`` by ``p[a(i), t] * theta^(-2i/D)``:
    a token has three positions (temporal, height, width) and pair ``i``
    reads the axis ``mrope_section`` gives it (the first 16 pairs the
    temporal one, the next 24 height, the last 24 width). Text has all
    three equal, which is the plain rotation.
  * **Indexer.** ``qI = h W_Iq`` (n heads of DI), ``kI = LayerNorm(h W_Ik)``
    (one key a token), the first ``index_rope_dim`` dims of both turned by
    the temporal position (pairs ``(i, i + index_rope_dim / 2)``), ``w = h
    W_Iw / sqrt(n) / sqrt(DI)``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
    kI[s])``. Query t attends the rows s <= t whose I[t, s] is among the
    ``index_topk`` largest (every row while t + 1 <= index_topk): the
    selection is ``dsa_select``'s, which ``glm_dsa`` runs too.
  * Scores ``q . k / sqrt(D)`` over the rows kept, H / KV query heads a key
    head, softmax in float32.
  * **Experts.** ``mellum``'s layer: a softmax router over all experts in
    float32, the ``num_experts_per_tok`` largest, gates renormalised over
    the chosen, no shared expert; the grouped product is ``routed_experts``'.

Serving. A token leaves, a layer, one K row and one V row (KV pieces of D)
and ONE index key, in three page pools under one page numbering: the
batcher's block table, prefix cache and page audit see pages only. The index
key is held whole lanes wide (``key_width``: 64 -> 128, zeros behind it): a
pool narrower than the lanes got a page-minor layout and was copied by every
executable (``glm_dsa``'s finding). Positions are the batcher's to say
(``position_ids`` [3, N]; the contract's ``position_axes``): a row's angle is
not its row number by construction. A prefill chunk writes its rows by the
page, scores the slot's held index keys block by block, finds each query's
threshold by bisection and attends over the rows kept with a mask and a
running softmax, a block of rows at a time up to the rows held. A decode
step: every row a slot holds is scored, ``index_topk`` of them are kept
(ties: the lowest row first; every row while a slot holds that many or
fewer), their K and V rows are gathered from the pages (a KV piece an
index) and attention runs over those alone, a running slot at a time, so
that a parked slot costs nothing. ``forward`` is the plain form over whole
sequences.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..incubate.nn.functional.decode_attention import write_page_rows
from ..nn import functional as F
from ..nn.layer import Layer
from ..ops.registry import dispatch
from .dsa_select import index_scores, select_indices, select_rows
from .glm_dsa import (_key_block, _layer_norm, _page_window, _rms, _Weight,
                      _widen)
from .glm_dsa import _write_run as _write_key_run
from .mellum import (_ffn, _grouped, _head, _out, _rope_half,
                     _rows_of, _ungrouped, _write_run, rope_tables)
from .routed_experts import F32, _counts_of_chunk, _counts_of_step, _mm

_NEG = -1e30
POSITION_AXES = 3
_DECODE_KEY_BLOCK = 4096        # index keys a decode step scores at a time


@dataclass
class KeyeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    mrope_section: tuple = (16, 24, 24)    # pairs of each position axis
    index_n_heads: int = 16
    index_head_dim: int = 64
    index_rope_dim: int = 32               # the key's dims that are rotated
    index_topk: int = 2048
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    index_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    prefill_key_block: int = 1024          # held rows a chunk reads at a time
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        self.mrope_section = tuple(int(n) for n in self.mrope_section)


def keye_tiny_config(**overrides) -> KeyeConfig:
    """Test-scale config of the same shape: 3 layers, 8 experts, 2 a token,
    16 rows kept."""
    return KeyeConfig(**dict(dict(
        vocab_size=128, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mrope_section=(2, 3, 3), index_n_heads=2, index_head_dim=16,
        index_rope_dim=8, index_topk=16, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32,
        prefill_key_block=32, max_position_embeddings=512,
        initializer_range=0.1), **overrides))


# -- rotary tables ------------------------------------------------------------

def axis_of_pair(section) -> np.ndarray:
    """int32 [D/2]: the position axis whose angle pair i turns by."""
    return np.repeat(np.arange(len(section)), section).astype(np.int32)


def key_width(width: int) -> int:
    """The width an index key is held at: whole lanes of 128, zeros behind
    the key (64 -> 128). ``glm_dsa.lane_width`` leaves a row of one lane or
    less as it is; a 64-wide pool would be tiled to 128 on the chip either
    way, and what the runtime does with a pool declared narrower than the
    lanes is what PR 36 found."""
    return -(-width // 128) * 128


def _angles(cos_t, sin_t, icos_t, isin_t, axis, pos):
    """The rows' angles from their positions ``pos`` [3, N]: (cos, sin)
    [N, D/2] with pair i read at ``pos[axis[i]]``, and the indexer's
    [N, R/2] at the temporal position. One lookup a step, not one a layer."""
    with jax.named_scope("rope_angles"):
        def per_axis(table):
            rows = table[pos]                             # [3, N, D/2]
            out = rows[0]
            for a in range(1, rows.shape[0]):
                out = jnp.where(axis == a, rows[a], out)
            return out

        return per_axis(cos_t), per_axis(sin_t), icos_t[pos[0]], \
            isin_t[pos[0]]


# -- arithmetic on arrays -----------------------------------------------------
# Pure ``jax.numpy`` over a dict of one block's weights, called through
# ``ops.registry.dispatch`` so that the weights are the executable's state.
# Products accumulate in float32; norms, softmax, the router and the index
# scores are float32.

def _rope_first(x, cos, sin):
    """The first R = 2 x cos' width dims of x [N, heads, W] turned, pairs
    (i, i + R/2); the dims behind them as they are."""
    rope = 2 * cos.shape[1]
    return jnp.concatenate([_rope_half(x[..., :rope], cos, sin),
                            x[..., rope:]], -1)


def _attn_inputs(p, x, ang, eps, ieps, heads, kv_heads):
    """Rows x [N, d] at angles ``ang`` (``_angles``') -> what attention and
    the indexer read of them: q [N, H, D], k and v [N, KV, D], qI [N, n,
    DI], kI [N, DI], w [N, n] float32."""
    cos, sin, icos, isin = ang
    n = x.shape[0]
    h = _rms(x, p["ln1_g"], eps)
    with jax.named_scope("qkv_rope"):
        q = _rms(_mm(h, p["q_w"]).reshape(n, heads, -1), p["q_g"], eps)
        k = _rms(_mm(h, p["k_w"]).reshape(n, kv_heads, -1), p["k_g"], eps)
        v = _mm(h, p["v_w"]).reshape(n, kv_heads, -1)
        q, k = _rope_half(q, cos, sin), _rope_half(k, cos, sin)
    with jax.named_scope("indexer"):
        n_idx = p["iw_w"].shape[1]
        q_i = _rope_first(_mm(h, p["iq_w"]).reshape(n, n_idx, -1), icos,
                          isin)
        k_i = _layer_norm(_mm(h, p["ik_w"]), p["ik_g"], p["ik_b"], ieps)
        k_i = _rope_first(k_i[:, None, :], icos, isin)[:, 0]
        w_i = _mm(h, p["iw_w"], F32) * n_idx ** -0.5 * q_i.shape[-1] ** -0.5
    return q, k, v, q_i, k_i, w_i


def _counts(ffn_counts, chose):
    """A layer's row of ``step_counts``: the experts' four columns, then the
    (query, row) pairs scored and read."""
    return jnp.concatenate([ffn_counts[:4], chose])


def _block_dense(p, x, ang, eps, ieps, heads, kv_heads, topk, top_k,
                 norm_topk):
    """One sequence x [S, d], nothing cached."""
    s = x.shape[0]
    q, k, v, q_i, k_i, w_i = _attn_inputs(p, x, ang, eps, ieps, heads,
                                          kv_heads)
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    with jax.named_scope("indexer"):
        with jax.named_scope("index_scores"):
            scores = index_scores(q_i, k_i, w_i)
        with jax.named_scope("index_topk"):
            keep = select_rows(scores, causal, topk) if topk < s else causal
    with jax.named_scope("sparse_attention"):
        sc = jnp.einsum("grsd,tgd->grst", _grouped(q, kv_heads), k,
                        preferred_element_type=F32) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(keep, sc, _NEG), -1)
        ctx = jnp.einsum("grst,tgd->grsd", probs.astype(v.dtype), v,
                         preferred_element_type=F32)
    x = _out(p, x, _ungrouped(ctx))
    return _ffn(p, x, eps, top_k, norm_topk)[0], keep


def _block_dense_batch(p, x, ang, eps, ieps, heads, kv_heads, topk, top_k,
                       norm_topk):
    """x [B, S, d]: ``_block_dense`` a sequence at a time."""
    return lax.map(lambda xs: _block_dense(p, xs, ang, eps, ieps, heads,
                                           kv_heads, topk, top_k, norm_topk),
                   x)


def _block_chunk(p, x, k_pool, v_pool, idx_pool, table, dec, n_valid, ang,
                 eps, ieps, heads, kv_heads, topk, top_k, norm_topk, kb):
    """One sequence's chunk x [S, d] at rows dec .. dec + S of the timeline
    whose pages are ``table`` [P]: its K and V rows and index keys go into
    the pages, then every query scores the rows held, keeps those at or
    above its index_topk-th, and attends over them, a block of ``kb`` rows
    at a time and no further than the rows held. The first ``n_valid`` rows
    are real: the pad rows behind them are routed nowhere and counted
    nowhere."""
    s = x.shape[0]
    block = k_pool.shape[2]
    s_max = table.shape[0] * block
    kb = _key_block(kb, s_max, block)
    pos = dec + jnp.arange(s)
    q, k, v, q_i, k_i, w_i = _attn_inputs(p, x, ang, eps, ieps, heads,
                                          kv_heads)
    with jax.named_scope("kv_write"):
        under, at = _page_window(table, dec, s, block)
        k_pool = _write_run(k_pool, under, at, k)
        v_pool = _write_run(v_pool, under, at, v)
        idx_pool = _write_key_run(idx_pool, table, dec, k_i)
    n_blocks = (dec + s + kb - 1) // kb

    def pages_of(i):
        return lax.dynamic_slice_in_dim(table, i * (kb // block),
                                        kb // block)

    with jax.named_scope("indexer"):
        with jax.named_scope("index_scores"):
            q_w = _widen(q_i, idx_pool.shape[-1])

            def score(i, buf):
                keys = idx_pool[pages_of(i)].reshape(kb, -1)
                return lax.dynamic_update_slice_in_dim(
                    buf, index_scores(q_w, keys, w_i), i * kb, 1)
            scores = lax.fori_loop(0, n_blocks, score,
                                   jnp.zeros((s, s_max), F32))
        with jax.named_scope("index_topk"):
            valid = jnp.arange(s_max)[None, :] <= pos[:, None]
            keep = select_rows(scores, valid, topk)
            real = jnp.arange(s) < n_valid
            chose = jnp.stack([jnp.sum(m & real[:, None], dtype=jnp.int32)
                               for m in (valid, keep)])
    qg = _grouped(q, kv_heads)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def attend(i, carry):
        m, l, acc = carry
        with jax.named_scope("sparse_gather"):
            pages = pages_of(i)
            k_rows, v_rows = _rows_of(k_pool, pages), _rows_of(v_pool, pages)
            ok = lax.dynamic_slice_in_dim(keep, i * kb, kb, 1)
        sc = jnp.einsum("grsd,gtd->grst", qg, k_rows,
                        preferred_element_type=F32) * scale
        sc = jnp.where(ok, sc, _NEG)
        m2 = jnp.maximum(m, jnp.max(sc, -1))
        pr = jnp.exp(sc - m2[..., None])
        corr = jnp.exp(m - m2)
        acc = acc * corr[..., None] + jnp.einsum(
            "grst,gtd->grsd", pr.astype(v_rows.dtype), v_rows,
            preferred_element_type=F32)
        return m2, l * corr + jnp.sum(pr, -1), acc

    with jax.named_scope("sparse_attention"):
        lead = qg.shape[:3]
        m, l, acc = lax.fori_loop(
            0, n_blocks, attend,
            (jnp.full(lead, _NEG, F32), jnp.zeros(lead, F32),
             jnp.zeros(qg.shape, F32)))
        ctx = acc / l[..., None]
    x = _out(p, x, _ungrouped(ctx))
    x, counts = _ffn(p, x, eps, top_k, norm_topk, active=real)
    return x, k_pool, v_pool, idx_pool, _counts(counts, chose)


def _kept_rows(pool, pages, offs):
    """Rows ``offs`` [k] of pages ``pages`` [k] out of a pool ``[pages, KV,
    block, D]``: [k, KV, D]. A row's KV pieces lie a page's ``block`` rows
    apart, so each is fetched by its own index into the pool's rows laid
    end to end (a gather whose slice spans the KV axis makes XLA transpose
    the whole pool first: 200 MB a layer and pool at the cell's size)."""
    _, kvh, block, d = pool.shape
    at = (pages[:, None] * kvh + jnp.arange(kvh)[None, :]) * block \
        + offs[:, None]
    return pool.reshape(-1, d)[at]


def _block_tok(p, x, k_pool, v_pool, idx_pool, table, dec, ang, eps, ieps,
               heads, kv_heads, topk, top_k, norm_topk):
    """One token a slot: x [B, d] at row ``dec`` [B] of each slot's timeline
    (pages ``table`` [B, P])."""
    block = k_pool.shape[2]
    s_max = table.shape[1] * block
    topk = min(topk, s_max)
    q, k, v, q_i, k_i, w_i = _attn_inputs(p, x, ang, eps, ieps, heads,
                                          kv_heads)
    with jax.named_scope("kv_write"):
        page = jnp.take_along_axis(table, (dec // block)[:, None], 1)[:, 0]
        k_pool = write_page_rows(k_pool, page, dec % block, k)
        v_pool = write_page_rows(v_pool, page, dec % block, v)
        idx_pool = write_page_rows(
            idx_pool, page, dec % block,
            _widen(k_i, idx_pool.shape[-1])[:, None])
    active = dec > 0                                 # a parked slot: 0
    hd = q.shape[-1]
    kb = _key_block(_DECODE_KEY_BLOCK, s_max, block)

    def slot_kept_rows(args):
        """One running slot: its held index keys scored, index_topk rows
        kept (all of them while it holds no more), their K and V gathered,
        attention over those alone."""
        q_b, qi_b, wi_b, tab, at = args
        with jax.named_scope("indexer"):
            with jax.named_scope("index_scores"):
                q_w = _widen(qi_b, idx_pool.shape[-1])

                def score(i, buf):          # the rows held, kb at a time
                    pages = lax.dynamic_slice_in_dim(tab, i * (kb // block),
                                                     kb // block)
                    keys = idx_pool[pages].reshape(kb, -1)
                    return lax.dynamic_update_slice_in_dim(
                        buf, index_scores(q_w, keys, wi_b)[None], i * kb, 1)
                scores = lax.fori_loop(0, at // kb + 1, score,
                                       jnp.zeros((1, s_max), F32))
            with jax.named_scope("index_topk"):
                valid = (jnp.arange(s_max) <= at)[None]
                rows, kept = select_indices(scores, valid, topk)
                rows, kept = rows[0], kept[0]
                chose = jnp.stack([jnp.sum(m, dtype=jnp.int32)
                                   for m in (valid, kept)])
        with jax.named_scope("sparse_gather"):
            pages = tab[rows // block]
            k_rows = _kept_rows(k_pool, pages, rows % block)   # [k, KV, D]
            v_rows = _kept_rows(v_pool, pages, rows % block)
        with jax.named_scope("sparse_attention"):
            sc = jnp.einsum("grd,tgd->grt",
                            q_b.reshape(kv_heads, heads // kv_heads, hd),
                            k_rows, preferred_element_type=F32) \
                / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(kept, sc, _NEG), -1)
            ctx = jnp.einsum("grt,tgd->grd", probs.astype(v_rows.dtype),
                             v_rows, preferred_element_type=F32)
        return ctx.reshape(heads, hd), chose

    def slot(args):
        """A slot at a time, and a parked slot not at all: the rows scored
        and the pieces gathered are a running slot's own."""
        return lax.cond(
            args[0], lambda: slot_kept_rows(args[1:]),
            lambda: (jnp.zeros((heads, hd), F32), jnp.zeros(2, jnp.int32)))

    ctx, chose = lax.map(slot, (active, q, q_i, w_i, table, dec))
    x = _out(p, x, ctx)
    x, counts = _ffn(p, x, eps, top_k, norm_topk, active=active)
    return x, k_pool, v_pool, idx_pool, _counts(
        counts, jnp.sum(chose, 0, dtype=jnp.int32))


_STATIC = ("eps", "ieps", "heads", "kv_heads", "topk", "top_k", "norm_topk",
           "kb")


def _jitted(fn):
    names = [n for n in fn.__code__.co_varnames[:fn.__code__.co_argcount]
             if n in _STATIC]
    return jax.jit(fn, static_argnames=names)


# the first call of a ``to_static`` function is eager: jitted a kind of
# block, it compiles a handful of programs and not one per operation
_BLOCKS = {fn.__name__: _jitted(fn) for fn in (
    _angles, _block_dense_batch, _block_chunk, _block_tok, _head,
    _counts_of_step, _counts_of_chunk)}


def _run(name, *args, **kwargs):
    return dispatch(_BLOCKS[name], args, kwargs, op_name=f"keye{name}")


# -- parameters ---------------------------------------------------------------

class KeyeAttention(Layer):
    """The attention's weights and, beside them, the indexer's."""

    def __init__(self, cfg: KeyeConfig):
        super().__init__(dtype=cfg.dtype)
        d, hd, std, dt = cfg.hidden_size, cfg.head_dim, \
            cfg.initializer_range, cfg.dtype
        self.q_proj = _Weight((d, cfg.num_attention_heads * hd), dt, std)
        self.k_proj = _Weight((d, cfg.num_key_value_heads * hd), dt, std)
        self.v_proj = _Weight((d, cfg.num_key_value_heads * hd), dt, std)
        self.o_proj = _Weight((cfg.num_attention_heads * hd, d), dt, std)
        self.q_norm = _Weight((hd,), dt, const=1.0)
        self.k_norm = _Weight((hd,), dt, const=1.0)
        di = cfg.index_head_dim
        self.indexer_wq = _Weight((d, cfg.index_n_heads * di), dt, std)
        self.indexer_wk = _Weight((d, di), dt, std)
        self.indexer_k_norm = _Weight((di,), dt, const=1.0)
        self.indexer_k_norm_bias = _Weight((di,), dt, const=0.0)
        self.indexer_weights_proj = _Weight((d, cfg.index_n_heads), dt, std)


class KeyeMoE(Layer):
    """The router over every expert, the experts stacked on a leading
    axis (``experts_fc1`` is [gate | up])."""

    def __init__(self, cfg: KeyeConfig):
        super().__init__(dtype=cfg.dtype)
        d, f, n, std, dt = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.num_experts, cfg.initializer_range, cfg.dtype
        self.gate = _Weight((d, n), dt, std)
        self.experts_fc1 = _Weight((n, d, 2 * f), dt, std)
        self.experts_fc2 = _Weight((n, f, d), dt, std)


class KeyeDecoderLayer(Layer):
    def __init__(self, cfg: KeyeConfig):
        super().__init__(dtype=cfg.dtype)
        self.input_layernorm = _Weight((cfg.hidden_size,), cfg.dtype,
                                       const=1.0)
        self.self_attn = KeyeAttention(cfg)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,),
                                                cfg.dtype, const=1.0)
        self.mlp = KeyeMoE(cfg)

    def leaves(self):
        """The block's weights under the names the arithmetic reads."""
        a, m = self.self_attn, self.mlp
        return {"ln1_g": self.input_layernorm.weight,
                "q_w": a.q_proj.weight, "k_w": a.k_proj.weight,
                "v_w": a.v_proj.weight, "o_w": a.o_proj.weight,
                "q_g": a.q_norm.weight, "k_g": a.k_norm.weight,
                "iq_w": a.indexer_wq.weight, "ik_w": a.indexer_wk.weight,
                "ik_g": a.indexer_k_norm.weight,
                "ik_b": a.indexer_k_norm_bias.weight,
                "iw_w": a.indexer_weights_proj.weight,
                "ln2_g": self.post_attention_layernorm.weight,
                "router_w": m.gate.weight, "exp_w1": m.experts_fc1.weight,
                "exp_w2": m.experts_fc2.weight}


class KeyeModel(Layer):
    def __init__(self, cfg: KeyeConfig):
        super().__init__(dtype=cfg.dtype)
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                    cfg.dtype, cfg.initializer_range)
        self.layers = [KeyeDecoderLayer(cfg)
                       for _ in range(cfg.num_hidden_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", layer)
        self.norm = _Weight((cfg.hidden_size,), cfg.dtype, const=1.0)


class KeyeForCausalLM(Layer):
    """``KeyeForCausalLM(KeyeConfig(...))``; ``forward(ids)`` gives the
    logits of every position, ``PagedContinuousBatcher(model, ...)`` serves
    it."""

    def __init__(self, config: KeyeConfig):
        super().__init__(dtype=config.dtype)
        c = config
        if c.num_attention_heads % c.num_key_value_heads or c.head_dim % 2:
            raise ValueError("query heads share key heads in whole groups, "
                             "and the rotation turns pairs (i, i + D/2)")
        if len(c.mrope_section) != POSITION_AXES or \
                sum(c.mrope_section) != c.head_dim // 2:
            raise ValueError(f"mrope_section gives each of the "
                             f"{POSITION_AXES} position axes its pairs, "
                             f"{c.head_dim // 2} in all")
        if c.index_rope_dim % 2 or c.index_rope_dim > c.index_head_dim:
            raise ValueError("the rotation turns pairs of the first "
                             "index_rope_dim dims of an index key")
        if not 0 < c.num_experts_per_tok <= c.num_experts:
            raise ValueError("num_experts_per_tok of num_experts")
        self.config = config
        self.model = KeyeModel(config)
        self.lm_head = _Weight((c.hidden_size, c.vocab_size), c.dtype,
                               c.initializer_range)
        import paddle_tpu as paddle
        # angles made once in float64: arguments of the executables, not
        # constants folded into them
        self._rope = tuple(paddle.to_tensor(t) for t in (
            *rope_tables(c.max_position_embeddings, c.head_dim,
                         c.rope_theta),
            *rope_tables(c.max_position_embeddings, c.index_rope_dim,
                         c.rope_theta),
            axis_of_pair(c.mrope_section)))

    def _top(self):
        return {"norm_g": self.model.norm.weight,
                "head_w": self.lm_head.weight}

    def _statics(self):
        c = self.config
        return dict(eps=c.rms_norm_eps, ieps=c.index_norm_eps,
                    heads=c.num_attention_heads,
                    kv_heads=c.num_key_value_heads, topk=c.index_topk,
                    top_k=c.num_experts_per_tok,
                    norm_topk=bool(c.norm_topk_prob))

    def _angles_of(self, position_ids, rows):
        """``_angles`` of ``position_ids`` [3, N]; absent, of the rows' own
        numbers on every axis (text)."""
        import paddle_tpu as paddle
        if position_ids is None:
            position_ids = paddle.stack([rows] * POSITION_AXES)
        return _run("_angles", *self._rope, position_ids.astype("int32"))

    # -- the whole forward --------------------------------------------------
    def forward(self, input_ids, labels=None, position_ids=None,
                return_selection=False):
        """``position_ids`` [3, T]: every sequence's (temporal, height,
        width) positions; absent, 0 .. T on each."""
        import paddle_tpu as paddle
        cfg = self.config
        s = input_ids.shape[1]
        with jax.named_scope("embed"):
            x = F.embedding(input_ids, self.model.embed_tokens.weight)
        ang = self._angles_of(position_ids, paddle.arange(s, dtype="int32"))
        kept = []
        for layer in self.model.layers:
            x, keep = _run("_block_dense_batch", layer.leaves(), x, ang,
                           **self._statics())
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
        layer's K, V and index key: pages alone, so the prefix cache works
        as it stands (a hit brings all three pools' pages).
        ``position_axes``: a row's rotary position has that many axes and
        is the batcher's to say: a chunk takes ``position_ids`` [3, S], a
        decode step ``state["position_ids"]`` [3, B]. ``step_counts``: the
        cache holds what the steps chose, and a chunk is told how many of
        its rows are real. ``unsupported``: batcher options that assume a
        page holds K and V alone, each with the reason."""
        kv = "a page holds K, V and index keys under one numbering"
        return {
            "slot_state": False,
            "step_counts": True,
            "position_axes": POSITION_AXES,
            "unsupported": {
                "kv_quant": f"no calibrated int8 path: {kv}",
                "cache_quant": f"no dynamic int8 path: {kv}",
                "tier_quant": "needs a host tier",
                "host_kv_gib": f"the host tier spills (K, V) pairs: {kv}",
                "disk_kv_dir": "needs a host tier",
                "draft_model": "a draft's pool would need index-key pages "
                               "of its own",
                "session_store": f"a paused session is spilled as (K, V) "
                                 f"pairs: {kv}",
            }}

    def paged_alloc(self, n_pages, block_size=16, cache_dtype=None):
        """The cache: per layer a (K, V) pair of pools ``[n_pages, KV,
        block, D]`` and an index-key pool ``[n_pages, 1, block,
        lane_width(index_head_dim)]`` under one page numbering, and
        ``step_counts`` [2, layers, 6] int32: what the last decode step
        chose, and all the chunks so far."""
        import paddle_tpu as paddle
        from ..observability.metrics import get_registry
        cfg = self.config
        if cache_dtype not in (None, cfg.dtype):
            raise ValueError(f"cache_dtype {cache_dtype!r}: the cache is "
                             f"held in the model's dtype")
        n = cfg.num_hidden_layers

        def pool(*shape):
            return paddle.zeros([n_pages, *shape], dtype=cfg.dtype)

        kv = (cfg.num_key_value_heads, block_size, cfg.head_dim)
        key = (1, block_size, key_width(cfg.index_head_dim))
        cache = {"kv": [(pool(*kv), pool(*kv)) for _ in range(n)],
                 "index": [pool(*key) for _ in range(n)],
                 "step_counts": paddle.zeros([2, n, 6], dtype="int32")}
        reg = get_registry()
        reg.gauge(
            "serving.kv_cache_bytes",
            "bytes of a page group's K and V pools as allocated, all its "
            "layers", labelnames=("group",)).labels(group="full").set(
                sum(k._data.nbytes + v._data.nbytes for k, v in cache["kv"]))
        reg.gauge(
            "serving.index_key_cache_bytes",
            "bytes of the index-key page pools as allocated, all layers"
        ).set(sum(t._data.nbytes for t in cache["index"]))
        return cache

    def paged_decode_attention_path(self, cache) -> str:
        """The kept rows' K and V pieces are gathered by XLA."""
        return "dsa=gather"

    def paged_kv_writer(self, cache) -> str:
        """Every pool takes its rows by the page."""
        return "page"

    def _ints(self, t, default):
        import paddle_tpu as paddle
        if t is None:
            return paddle.to_tensor(np.array(default, np.int32))
        return t.reshape([]).astype("int32")

    def paged_prefill_into(self, input_ids, layers, block_tables,
                           block_size=16, dec_base=None, logits_at=None,
                           n_valid=None, position_ids=None):
        """One sequence's chunk ``input_ids [1, S]`` at rows ``dec_base ..
        dec_base + S`` of the timeline whose pages ``block_tables [1, P]``
        names, its first ``n_valid`` rows real (all of them by default), at
        the positions ``position_ids`` [3, S] (the rows' own numbers by
        default). Returns (logits [1, V] of row ``logits_at``, the cache)."""
        import paddle_tpu as paddle
        cfg = self.config
        b, s = input_ids.shape
        if b != 1:
            raise ValueError("a prefill is one sequence: input_ids [1, S]")
        dec = self._ints(dec_base, 0)
        at = self._ints(logits_at, s - 1)
        real = self._ints(n_valid, s)
        table = block_tables.astype("int32").reshape([-1])
        kv, index = list(layers["kv"]), list(layers["index"])
        with jax.named_scope("embed"):
            x = F.embedding(input_ids.reshape([s]),
                            self.model.embed_tokens.weight)
        ang = self._angles_of(position_ids,
                           dec + paddle.arange(s, dtype="int32"))
        per_layer = []
        for i, layer in enumerate(self.model.layers):
            x, k, v, index[i], c = _run(
                "_block_chunk", layer.leaves(), x, kv[i][0], kv[i][1],
                index[i], table, dec, real, ang, kb=cfg.prefill_key_block,
                **self._statics())
            kv[i] = (k, v)
            per_layer.append(c)
        counts = _run("_counts_of_chunk", layers["step_counts"], *per_layer)
        x = paddle.index_select(x, at.reshape([1]), axis=0)
        logits = _run("_head", self._top(), x, eps=cfg.rms_norm_eps)
        return logits, {"kv": kv, "index": index, "step_counts": counts}

    def paged_decode_step(self, tok, state):
        """One token a slot. tok [B]; ``state`` as the batcher keeps it:
        ``layers`` (``paged_alloc``'s), ``block_tables`` [B, pages a slot],
        ``dec_lens`` [B] the rows a slot holds before this step,
        ``position_ids`` [3, B] the new rows' positions (``dec_lens`` on
        every axis where absent)."""
        cfg = self.config
        dec = state["dec_lens"].astype("int32")
        table = state["block_tables"].astype("int32")
        cache = state["layers"]
        kv, index = list(cache["kv"]), list(cache["index"])
        with jax.named_scope("embed"):
            x = F.embedding(tok, self.model.embed_tokens.weight)
        ang = self._angles_of(state.get("position_ids"), dec)
        per_layer = []
        for i, layer in enumerate(self.model.layers):
            x, k, v, index[i], c = _run(
                "_block_tok", layer.leaves(), x, kv[i][0], kv[i][1],
                index[i], table, dec, ang, **self._statics())
            kv[i] = (k, v)
            per_layer.append(c)
        counts = _run("_counts_of_step", cache["step_counts"], *per_layer)
        logits = _run("_head", self._top(), x, eps=cfg.rms_norm_eps)
        layers = {"kv": kv, "index": index, "step_counts": counts}
        return logits, dict(state, layers=layers,
                            dec_lens=state["dec_lens"] + 1)
