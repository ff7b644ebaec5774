"""Mellum 2's decoder (``model_type: mellum``): grouped-query attention whose
layers alternate between a sliding window and the whole sequence
(``layer_types``), each kind with rotary tables of its own, and a sparse
expert layer in every block.

A block is ``x += Attn(RMSNorm(x)); x += Experts(RMSNorm(x))``:

  * **Attention.** ``q = h W_q`` (H heads of D), ``k = h W_k``, ``v = h W_v``
    (KV heads of D), no bias; every head's ``q`` and ``k`` pass an RMSNorm
    over their D dims (one gain for all heads) before the rotation; the
    rotation turns pairs ``(i, i + D/2)``. A window layer's angles are the
    plain ``t * theta^(-2i/D)`` and its query ``t`` reads the keys ``s`` with
    ``0 <= t - s < sliding_window``; a full layer's angles are YaRN's
    (arXiv:2309.00071: the low frequencies divided by ``factor``, a linear
    ramp between the dims that make ``beta_fast`` and ``beta_slow`` turns in
    the original context), with ``attention_factor`` on cos and sin both, and
    its query reads every key at or before it. Scores ``q . k / sqrt(D)``,
    H / KV query heads a key head, softmax in float32.
  * **Experts.** ``p = softmax(h W_r)`` over all experts in float32, the
    ``num_experts_per_tok`` largest chosen, gates ``p_e / sum of the chosen
    p`` (``norm_topk_prob``); the sum of the chosen experts' SwiGLUs at
    their gates. No shared expert. The grouped product is
    ``routed_experts``' (the module the ``glm_dsa`` family calls too); this
    model holds every expert.

Serving. A token leaves one K and one V row a layer behind. The layers form
two **page groups**, each with its own pool of pages and its own page
numbering: the full layers keep every row, so a sequence's ``full`` table
names a page for every block it holds; the window layers need the trailing
``sliding_window`` rows alone, so the batcher takes the pages behind the
window back as the sequence moves and the ``window`` table is a ring: block
``j`` of the timeline lies at entry ``j % ring``. A prefill chunk writes its
rows by the page and attends over the rows held: a full layer block by
block of ``prefill_key_block`` rows with a running softmax, a window layer
over the pages the window and the chunk lie in. A decode step writes its row
by the page and reads the pages through the one Pallas decode kernel: a full
layer's whole table, a window layer's the pages the window lies in with the
first row that counts. ``forward`` is the plain form over whole sequences.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

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
from .routed_experts import (F32, _counts_of_chunk, _counts_of_step, _mm,
                             expert_counts, routed_experts)

_NEG = -1e30
WINDOW, FULL = "sliding_attention", "full_attention"


def _yarn_default() -> dict:
    return {"factor": 16.0, "original_max_position_embeddings": 8192,
            "beta_fast": 32.0, "beta_slow": 1.0,
            "attention_factor": 1.2772588722239782}


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    layer_types: tuple = ()            # WINDOW / FULL a layer; () = 3 : 1
    sliding_window: int = 1024
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0       # both kinds of layer
    yarn: dict = field(default_factory=_yarn_default)    # the full layers'
    prefill_key_block: int = 1024      # held rows a full layer's chunk reads
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = tuple(
                FULL if i % 4 == 3 else WINDOW
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)

    def window_of(self, layer: int) -> int:
        """Rows a layer's query reads back; 0 for a full layer."""
        return self.sliding_window if self.layer_types[layer] == WINDOW else 0


def mellum_tiny_config(**overrides) -> MellumConfig:
    """Test-scale config of the same shape: two periods of three window
    layers (16 rows) and a full one, 8 experts, 2 a token."""
    return MellumConfig(**dict(dict(
        vocab_size=128, hidden_size=64, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, prefill_key_block=32,
        yarn={"factor": 4.0, "original_max_position_embeddings": 32,
              "beta_fast": 4.0, "beta_slow": 1.0,
              "attention_factor": 0.1 * math.log(4.0) + 1.0},
        max_position_embeddings=512, initializer_range=0.1), **overrides))


# -- rotary tables ------------------------------------------------------------

def rope_tables(positions: int, dim: int, theta: float, yarn=None):
    """(cos, sin) [positions, dim / 2] float32, angles made in float64: the
    plain ``t * theta^(-2i/dim)``, or with ``yarn`` (its five numbers)
    YaRN's blend of that frequency and the one divided by ``factor``, and
    ``attention_factor`` on both tables."""
    half = dim // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)
    scale = 1.0
    if yarn:
        def turns_dim(beta):
            return dim * math.log(yarn["original_max_position_embeddings"]
                                  / (beta * 2 * math.pi)) \
                / (2 * math.log(theta))
        low = max(math.floor(turns_dim(yarn["beta_fast"])), 0)
        high = min(math.ceil(turns_dim(yarn["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        inv = (1.0 - ramp) * inv + ramp * inv / yarn["factor"]
        scale = yarn["attention_factor"]
    ang = np.outer(np.arange(positions, dtype=np.float64), inv)
    return (np.cos(ang) * scale).astype(np.float32), \
        (np.sin(ang) * scale).astype(np.float32)


# -- arithmetic on arrays -----------------------------------------------------
# Pure ``jax.numpy`` over a dict of one block's weights, called through
# ``ops.registry.dispatch`` so that the weights are the executable's state.
# Products accumulate in float32; norms, softmax and the router are float32.

def _rope_half(x, cos, sin):
    """Rotate pairs (i, i + D/2) of x [N, heads, D] by the rows' angles
    cos / sin [N, D/2] (float32)."""
    xf = x.astype(F32)
    half = x.shape[-1] // 2
    x1, x2 = xf[..., :half], xf[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(x.dtype)


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


def _kind(window: int) -> str:
    return "window_attention" if window else "full_attention"


def _out(p, x, ctx):
    """x + the heads' outputs ctx [N, H, D] side by side times ``W_o``."""
    with jax.named_scope("o_proj"):
        return x + _mm(ctx.reshape(ctx.shape[0], -1).astype(x.dtype),
                       p["o_w"])


def route(p, h, top_k: int, norm_topk: bool):
    """(chosen experts [N, k] int32, gates [N, k] float32) of rows h: a
    softmax over every expert, the k largest (ties to the lower index)."""
    probs = jax.nn.softmax(_mm(h, p["router_w"], F32), -1)
    picked, chosen = lax.top_k(probs, top_k)
    if norm_topk:
        picked = picked / jnp.sum(picked, -1, keepdims=True)
    return chosen.astype(jnp.int32), picked


def _ffn(p, x, eps, top_k, norm_topk, active=None):
    """x [N, d] -> (x + Experts(RMSNorm(x)), counts [6] int32 as
    ``step_counts`` holds them: the indexer's two columns stay zero). Rows
    that are not ``active`` (parked slots, a chunk's pad rows) are routed
    nowhere."""
    h = _rms(x, p["ln2_g"], eps)
    with jax.named_scope("router"):
        chosen, gates = route(p, h, top_k, norm_topk)
        routed = x.shape[0]
        if active is not None:
            chosen = jnp.where(active[:, None], chosen, -1)
            routed = jnp.sum(active, dtype=jnp.int32)
    with jax.named_scope("experts_routed"):
        y, counts = routed_experts(p, h, chosen, gates,
                                   (0, p["exp_w1"].shape[0]))
    return x + y, jnp.concatenate([expert_counts(counts, routed * top_k),
                                   jnp.zeros(2, jnp.int32)])


def _grouped(q, kv_heads):
    """q [N, H, D] -> [KV, H / KV, N, D]: the query heads of a key head are
    rows of one product against that head's keys."""
    n, heads, d = q.shape
    return jnp.transpose(q.reshape(n, kv_heads, heads // kv_heads, d),
                         (1, 2, 0, 3))


def _ungrouped(ctx):
    """[KV, rep, N, D] -> [N, H, D]."""
    kvh, rep, n, d = ctx.shape
    return jnp.transpose(ctx, (2, 0, 1, 3)).reshape(n, kvh * rep, d)


def _block_dense(p, x, cos, sin, eps, heads, kv_heads, window, top_k,
                 norm_topk):
    """One sequence x [S, d], nothing cached."""
    s = x.shape[0]
    q, k, v = _qkv(p, x, cos, sin, eps, heads, kv_heads)
    pos = jnp.arange(s)
    ok = pos[None, :] <= pos[:, None]
    if window:
        ok &= pos[:, None] - pos[None, :] < window
    with jax.named_scope(_kind(window)), jax.named_scope("scores"):
        sc = jnp.einsum("grsd,tgd->grst", _grouped(q, kv_heads), k,
                        preferred_element_type=F32) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(ok, sc, _NEG), -1)
        ctx = jnp.einsum("grst,tgd->grsd", probs.astype(v.dtype), v,
                         preferred_element_type=F32)
    x = _out(p, x, _ungrouped(ctx))
    return _ffn(p, x, eps, top_k, norm_topk)


def _block_dense_batch(p, x, cos, sin, eps, heads, kv_heads, window, top_k,
                       norm_topk):
    """x [B, S, d]: ``_block_dense`` a sequence at a time."""
    return lax.map(lambda xs: _block_dense(
        p, xs, cos, sin, eps, heads, kv_heads, window, top_k, norm_topk)[0],
        x)


def _rows_of(pool, pages):
    """The rows the pages [n] back, in order: [KV, n * block, D]."""
    got = pool[pages]                                  # [n, KV, block, D]
    return jnp.moveaxis(got, 1, 0).reshape(got.shape[1], -1, got.shape[3])


def _write_run(pool, pages, at, run):
    """Rows ``run`` [S, KV, D] from row ``at`` of the rows that the pages
    [n] back, by the page: read, the run laid over them, put back along the
    pool's first axis (entries that name the scratch page more than once
    decide nothing: nobody reads it)."""
    n, (_, kvh, block, hd) = pages.shape[0], pool.shape
    cur = jnp.moveaxis(pool[pages], 1, 2).reshape(n * block, kvh, hd)
    cur = lax.dynamic_update_slice_in_dim(cur, run.astype(pool.dtype), at, 0)
    return pool.at[pages].set(
        jnp.moveaxis(cur.reshape(n, block, kvh, hd), 2, 1))


def _ring_pages(ring, first, n: int):
    """The pages of blocks first .. first + n of a timeline whose window
    table is ``ring`` [..., R]: block j lies at entry j % R."""
    at = (first[..., None] + jnp.arange(min(n, ring.shape[-1]))) \
        % ring.shape[-1]
    return jnp.take_along_axis(ring, at, -1) if ring.ndim > 1 else ring[at]


def _block_chunk(p, x, k_pool, v_pool, table, dec, n_valid, cos_t, sin_t,
                 eps, heads, kv_heads, window, top_k, norm_topk, kb):
    """One sequence's chunk x [S, d] at rows dec .. dec + S of its timeline:
    its K and V rows go into the pages, then every query attends over the
    rows held. ``table`` [P] names the pages: a full layer's (``window``
    0) a page a block, read ``kb`` rows at a time with a running softmax
    and no further than the rows held; a window layer's as a ring, read
    from the page the first query's window starts in to the chunk's last.
    The first ``n_valid`` rows are real: the pad rows behind them are routed
    nowhere and counted nowhere."""
    s = x.shape[0]
    block = k_pool.shape[2]
    pos = dec + jnp.arange(s)
    q, k, v = _qkv(p, x, cos_t[pos], sin_t[pos], eps, heads, kv_heads)
    qg = _grouped(q, kv_heads)
    scale = 1.0 / math.sqrt(q.shape[-1])
    if window:
        first = jnp.maximum(dec - (window - 1), 0) // block
        with jax.named_scope("kv_write"):
            under = _ring_pages(table, dec // block, s // block + 1)
            k_pool = _write_run(k_pool, under, dec % block, k)
            v_pool = _write_run(v_pool, under, dec % block, v)
        with jax.named_scope("window_attention"), jax.named_scope("scores"):
            pages = _ring_pages(table, first,
                                (window + s - 2) // block + 2)
            kpos = first * block + jnp.arange(pages.shape[0] * block)
            ok = (kpos[None, :] <= pos[:, None]) \
                & (pos[:, None] - kpos[None, :] < window)
            sc = jnp.einsum("grsd,gtd->grst", qg, _rows_of(k_pool, pages),
                            preferred_element_type=F32) * scale
            probs = jax.nn.softmax(jnp.where(ok, sc, _NEG), -1)
            ctx = jnp.einsum("grst,gtd->grsd", probs.astype(v_pool.dtype),
                             _rows_of(v_pool, pages),
                             preferred_element_type=F32)
    else:
        with jax.named_scope("kv_write"):
            under, at = _page_window(table, dec, s, block)
            k_pool = _write_run(k_pool, under, at, k)
            v_pool = _write_run(v_pool, under, at, v)
        kb = _key_block(kb, table.shape[0] * block, block)

        def attend(i, carry):
            m, l, acc = carry
            pages = lax.dynamic_slice_in_dim(table, i * (kb // block),
                                             kb // block)
            ok = (i * kb + jnp.arange(kb))[None, :] <= pos[:, None]
            sc = jnp.einsum("grsd,gtd->grst", qg, _rows_of(k_pool, pages),
                            preferred_element_type=F32) * scale
            sc = jnp.where(ok, sc, _NEG)
            m2 = jnp.maximum(m, jnp.max(sc, -1))
            pr = jnp.exp(sc - m2[..., None])
            corr = jnp.exp(m - m2)
            acc = acc * corr[..., None] + jnp.einsum(
                "grst,gtd->grsd", pr.astype(v_pool.dtype),
                _rows_of(v_pool, pages), preferred_element_type=F32)
            return m2, l * corr + jnp.sum(pr, -1), acc

        with jax.named_scope("full_attention"), jax.named_scope("scores"):
            lead = qg.shape[:3]
            m, l, acc = lax.fori_loop(
                0, (dec + s + kb - 1) // kb, attend,
                (jnp.full(lead, _NEG, F32), jnp.zeros(lead, F32),
                 jnp.zeros(qg.shape, F32)))
            ctx = acc / l[..., None]
    x = _out(p, x, _ungrouped(ctx))
    x, counts = _ffn(p, x, eps, top_k, norm_topk,
                     active=jnp.arange(s) < n_valid)
    return x, k_pool, v_pool, counts


def _decode_scores(q, k_pool, v_pool, table, kv_len, kv_start):
    """q [B, H, D] against rows kv_start .. kv_len of the rows each slot's
    ``table`` [B, n] backs: the Pallas kernel over the pages in place on
    the chip, the gathered rows elsewhere."""
    if decode_attention_path(k_pool.shape, k_pool.dtype, q.shape[1]) \
            == "kernel":
        from ..ops.pallas.paged_attention import paged_attention_decode
        return paged_attention_decode(q, k_pool, v_pool, table, kv_len,
                                      kv_start=kv_start)
    b, heads, d = q.shape
    kvh = k_pool.shape[1]
    rows = table.shape[1] * k_pool.shape[2]

    def gathered(pool):
        got = pool[table.reshape(-1)].reshape(b, -1, kvh, pool.shape[2], d)
        return jnp.moveaxis(got, 2, 1).reshape(b, kvh, rows, d)

    at = jnp.arange(rows)[None, :]
    ok = (at < kv_len[:, None]) if kv_start is None \
        else (at < kv_len[:, None]) & (at >= kv_start[:, None])
    sc = jnp.einsum("bgrd,bgtd->bgrt", q.reshape(b, kvh, heads // kvh, d),
                    gathered(k_pool), preferred_element_type=F32) \
        / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(ok[:, None, None, :], sc, _NEG), -1)
    return jnp.einsum("bgrt,bgtd->bgrd", probs.astype(v_pool.dtype),
                      gathered(v_pool),
                      preferred_element_type=F32).reshape(b, heads, d)


def _block_tok(p, x, k_pool, v_pool, table, dec, cos_t, sin_t, eps, heads,
               kv_heads, window, top_k, norm_topk):
    """One token a slot: x [B, d] at row ``dec`` [B] of each slot's timeline
    (``table`` [B, P]: a full layer's pages, or a window layer's ring)."""
    block = k_pool.shape[2]
    q, k, v = _qkv(p, x, cos_t[dec], sin_t[dec], eps, heads, kv_heads)
    if window:
        since = jnp.maximum(dec - (window - 1), 0)
        first = since // block
        page = _ring_pages(table, dec // block, 1)[:, 0]
        pages = _ring_pages(table, first, (window + block - 2) // block + 1)
        kv_len, kv_start = dec + 1 - first * block, since - first * block
    else:
        page = jnp.take_along_axis(table, (dec // block)[:, None], 1)[:, 0]
        pages, kv_len, kv_start = table, dec + 1, None
    with jax.named_scope("kv_write"):
        k_pool = write_page_rows(k_pool, page, dec % block, k)
        v_pool = write_page_rows(v_pool, page, dec % block, v)
    with jax.named_scope(_kind(window)), jax.named_scope("scores"):
        ctx = _decode_scores(q, k_pool, v_pool, pages, kv_len, kv_start)
    x = _out(p, x, ctx)
    x, counts = _ffn(p, x, eps, top_k, norm_topk, active=dec > 0)
    return x, k_pool, v_pool, counts


def _head(top, x, eps):
    with jax.named_scope("head"):
        return _mm(_rms(x, top["norm_g"], eps), top["head_w"])


_STATIC = ("eps", "heads", "kv_heads", "window", "top_k", "norm_topk", "kb")


def _jitted(fn):
    names = [n for n in fn.__code__.co_varnames[:fn.__code__.co_argcount]
             if n in _STATIC]
    return jax.jit(fn, static_argnames=names)


# the first call of a ``to_static`` function is eager: jitted a kind of
# block, it compiles a handful of programs and not one per operation
_BLOCKS = {fn.__name__: _jitted(fn) for fn in (
    _block_dense_batch, _block_chunk, _block_tok, _head, _counts_of_step,
    _counts_of_chunk)}


def _run(name, *args, **kwargs):
    return dispatch(_BLOCKS[name], args, kwargs, op_name=f"mellum{name}")


# -- parameters ---------------------------------------------------------------

class MellumAttention(Layer):
    def __init__(self, cfg: MellumConfig):
        super().__init__(dtype=cfg.dtype)
        d, hd, std, dt = cfg.hidden_size, cfg.head_dim, \
            cfg.initializer_range, cfg.dtype
        self.q_proj = _Weight((d, cfg.num_attention_heads * hd), dt, std)
        self.k_proj = _Weight((d, cfg.num_key_value_heads * hd), dt, std)
        self.v_proj = _Weight((d, cfg.num_key_value_heads * hd), dt, std)
        self.o_proj = _Weight((cfg.num_attention_heads * hd, d), dt, std)
        self.q_norm = _Weight((hd,), dt, const=1.0)
        self.k_norm = _Weight((hd,), dt, const=1.0)


class MellumMoE(Layer):
    """The router over every expert, the experts stacked on a leading
    axis (``experts_fc1`` is [gate | up])."""

    def __init__(self, cfg: MellumConfig):
        super().__init__(dtype=cfg.dtype)
        d, f, n, std, dt = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.num_experts, cfg.initializer_range, cfg.dtype
        self.gate = _Weight((d, n), dt, std)
        self.experts_fc1 = _Weight((n, d, 2 * f), dt, std)
        self.experts_fc2 = _Weight((n, f, d), dt, std)


class MellumDecoderLayer(Layer):
    def __init__(self, cfg: MellumConfig):
        super().__init__(dtype=cfg.dtype)
        self.input_layernorm = _Weight((cfg.hidden_size,), cfg.dtype,
                                       const=1.0)
        self.self_attn = MellumAttention(cfg)
        self.post_attention_layernorm = _Weight((cfg.hidden_size,),
                                                cfg.dtype, const=1.0)
        self.mlp = MellumMoE(cfg)

    def leaves(self):
        """The block's weights under the names the arithmetic reads."""
        a, m = self.self_attn, self.mlp
        return {"ln1_g": self.input_layernorm.weight,
                "q_w": a.q_proj.weight, "k_w": a.k_proj.weight,
                "v_w": a.v_proj.weight, "o_w": a.o_proj.weight,
                "q_g": a.q_norm.weight, "k_g": a.k_norm.weight,
                "ln2_g": self.post_attention_layernorm.weight,
                "router_w": m.gate.weight, "exp_w1": m.experts_fc1.weight,
                "exp_w2": m.experts_fc2.weight}


class MellumModel(Layer):
    def __init__(self, cfg: MellumConfig):
        super().__init__(dtype=cfg.dtype)
        self.embed_tokens = _Weight((cfg.vocab_size, cfg.hidden_size),
                                    cfg.dtype, cfg.initializer_range)
        self.layers = [MellumDecoderLayer(cfg)
                       for _ in range(cfg.num_hidden_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", layer)
        self.norm = _Weight((cfg.hidden_size,), cfg.dtype, const=1.0)


PAGE_GROUPS = ("full", "window")


class MellumForCausalLM(Layer):
    """``MellumForCausalLM(MellumConfig(...))``; ``forward(ids)`` gives the
    logits of every position, ``PagedContinuousBatcher(model, ...)`` serves
    it."""

    def __init__(self, config: MellumConfig):
        super().__init__(dtype=config.dtype)
        c = config
        if len(c.layer_types) != c.num_hidden_layers or \
                set(c.layer_types) - {WINDOW, FULL}:
            raise ValueError(f"layer_types names a kind ({WINDOW} or "
                             f"{FULL}) for each of the "
                             f"{c.num_hidden_layers} layers")
        if c.num_attention_heads % c.num_key_value_heads or c.head_dim % 2:
            raise ValueError("query heads share key heads in whole groups, "
                             "and the rotation turns pairs (i, i + D/2)")
        if not 0 < c.num_experts_per_tok <= c.num_experts:
            raise ValueError("num_experts_per_tok of num_experts")
        self.config = config
        self.model = MellumModel(config)
        self.lm_head = _Weight((c.hidden_size, c.vocab_size), c.dtype,
                               c.initializer_range)
        import paddle_tpu as paddle
        # angles made once in float64: arguments of the executables, not
        # constants folded into them
        self._rope = {
            kind: tuple(paddle.to_tensor(t) for t in rope_tables(
                c.max_position_embeddings, c.head_dim, c.rope_theta, yarn))
            for kind, yarn in ((WINDOW, None), (FULL, c.yarn))}

    def _top(self):
        return {"norm_g": self.model.norm.weight,
                "head_w": self.lm_head.weight}

    def _statics(self, layer: int):
        c = self.config
        return dict(eps=c.rms_norm_eps, heads=c.num_attention_heads,
                    kv_heads=c.num_key_value_heads,
                    window=c.window_of(layer), top_k=c.num_experts_per_tok,
                    norm_topk=bool(c.norm_topk_prob))

    def _group_of(self, layer: int) -> str:
        return "window" if self.config.window_of(layer) else "full"

    # -- the whole forward --------------------------------------------------
    def forward(self, input_ids, labels=None):
        cfg = self.config
        s = input_ids.shape[1]
        with jax.named_scope("embed"):
            x = F.embedding(input_ids, self.model.embed_tokens.weight)
        for i, layer in enumerate(self.model.layers):
            cos, sin = (t[:s] for t in self._rope[cfg.layer_types[i]])
            x = _run("_block_dense_batch", layer.leaves(), x, cos, sin,
                     **self._statics(i))
        logits = _run("_head", self._top(), x, eps=cfg.rms_norm_eps)
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
        cache. ``page_groups``: the layers keep their K and V rows in two
        pools of pages, each with a page numbering and a page count of its
        own; ``rows`` None keeps every row of a sequence, a number keeps
        the trailing rows alone (the batcher takes the pages behind them
        back while the sequence runs and hands the group's table over as a
        ring). ``step_counts``: the cache holds what the steps' routers
        chose, and a chunk is told how many of its rows are real.
        ``unsupported``: batcher options that assume one pool whose pages
        back every layer, each with the reason."""
        groups = "the cache is two page groups (full and window layers)"
        cfg = self.config
        layers = {g: [i for i in range(cfg.num_hidden_layers)
                      if self._group_of(i) == g] for g in PAGE_GROUPS}
        return {
            "slot_state": False,
            "step_counts": True,
            "page_groups": {
                "full": {"rows": None, "layers": layers["full"]},
                "window": {"rows": cfg.sliding_window,
                           "layers": layers["window"]}},
            "unsupported": {
                "kv_quant": f"no calibrated int8 path: {groups}",
                "cache_quant": f"no dynamic int8 path: {groups}",
                "tier_quant": "needs a host tier",
                "host_kv_gib": f"the host tier spills one pool's (K, V) "
                               f"pairs: {groups}",
                "disk_kv_dir": "needs a host tier",
                "draft_model": "the multi-token-prediction head is not "
                               "loaded, and a draft's pool would need "
                               "page groups of its own",
                "session_store": f"a paused session is spilled as one "
                                 f"pool's (K, V) pairs: {groups}",
            }}

    def paged_alloc(self, n_pages, block_size=16, cache_dtype=None):
        """The cache: per layer a (K, V) pair of pools ``[pages of the
        layer's group, KV, block, D]``, and ``step_counts`` [2, layers, 6]
        int32. ``n_pages``: the page count of each group (``{"full": ...,
        "window": ...}``, the scratch page among them)."""
        import paddle_tpu as paddle
        from ..observability.metrics import get_registry
        cfg = self.config
        if cache_dtype not in (None, cfg.dtype):
            raise ValueError(f"cache_dtype {cache_dtype!r}: the cache is "
                             f"held in the model's dtype")
        if not isinstance(n_pages, dict) or set(n_pages) != set(PAGE_GROUPS):
            raise ValueError(f"n_pages gives a page count for each of the "
                             f"groups {PAGE_GROUPS}, not {n_pages!r}")
        n = cfg.num_hidden_layers

        def pool(layer):
            return paddle.zeros(
                [n_pages[self._group_of(layer)], cfg.num_key_value_heads,
                 block_size, cfg.head_dim], dtype=cfg.dtype)

        cache = {"kv": [(pool(i), pool(i)) for i in range(n)],
                 "step_counts": paddle.zeros([2, n, 6], dtype="int32")}
        held = get_registry().gauge(
            "serving.kv_cache_bytes",
            "bytes of a page group's K and V pools as allocated, all its "
            "layers", labelnames=("group",))
        for g in PAGE_GROUPS:
            held.labels(group=g).set(sum(
                k._data.nbytes + v._data.nbytes
                for i, (k, v) in enumerate(cache["kv"])
                if self._group_of(i) == g))
        return cache

    def paged_decode_attention_path(self, cache) -> str:
        k = cache["kv"][0][0]
        return decode_attention_path(tuple(k.shape), k._data.dtype,
                                     self.config.num_attention_heads)

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
                           n_valid=None, group_tables=None):
        """One sequence's chunk ``input_ids [1, S]`` at rows ``dec_base ..
        dec_base + S`` of the timeline whose full-group pages
        ``block_tables [1, P]`` names and whose window-group ring is
        ``group_tables["window"] [1, R]``, its first ``n_valid`` rows real
        (all of them by default). Returns (logits [1, V] of row
        ``logits_at``, the cache)."""
        import paddle_tpu as paddle
        cfg = self.config
        b, s = input_ids.shape
        if b != 1:
            raise ValueError("a prefill is one sequence: input_ids [1, S]")
        if group_tables is None:
            raise ValueError("the window layers' pages come in "
                             "group_tables['window']")
        dec = self._ints(dec_base, 0)
        at = self._ints(logits_at, s - 1)
        real = self._ints(n_valid, s)
        tables = {"full": block_tables.astype("int32").reshape([-1]),
                  "window": group_tables["window"].astype("int32")
                  .reshape([-1])}
        kv = list(layers["kv"])
        with jax.named_scope("embed"):
            x = F.embedding(input_ids.reshape([s]),
                            self.model.embed_tokens.weight)
        per_layer = []
        for i, layer in enumerate(self.model.layers):
            cos, sin = self._rope[cfg.layer_types[i]]
            x, k, v, c = _run(
                "_block_chunk", layer.leaves(), x, kv[i][0], kv[i][1],
                tables[self._group_of(i)], dec, real, cos, sin,
                kb=cfg.prefill_key_block, **self._statics(i))
            kv[i] = (k, v)
            per_layer.append(c)
        counts = _run("_counts_of_chunk", layers["step_counts"], *per_layer)
        x = paddle.index_select(x, at.reshape([1]), axis=0)
        logits = _run("_head", self._top(), x, eps=cfg.rms_norm_eps)
        return logits, {"kv": kv, "step_counts": counts}

    def paged_decode_step(self, tok, state):
        """One token a slot. tok [B]; ``state`` as the batcher keeps it:
        ``layers`` (``paged_alloc``'s), ``block_tables`` [B, pages a slot]
        of the full group, ``group_tables["window"]`` [B, R] the window
        group's rings, ``dec_lens`` [B] the rows a slot holds before this
        step."""
        cfg = self.config
        dec = state["dec_lens"].astype("int32")
        tables = {"full": state["block_tables"].astype("int32"),
                  "window": state["group_tables"]["window"].astype("int32")}
        cache = state["layers"]
        kv = list(cache["kv"])
        with jax.named_scope("embed"):
            x = F.embedding(tok, self.model.embed_tokens.weight)
        per_layer = []
        for i, layer in enumerate(self.model.layers):
            cos, sin = self._rope[cfg.layer_types[i]]
            x, k, v, c = _run(
                "_block_tok", layer.leaves(), x, kv[i][0], kv[i][1],
                tables[self._group_of(i)], dec, cos, sin,
                **self._statics(i))
            kv[i] = (k, v)
            per_layer.append(c)
        counts = _run("_counts_of_step", cache["step_counts"], *per_layer)
        logits = _run("_head", self._top(), x, eps=cfg.rms_norm_eps)
        return logits, dict(state, layers={"kv": kv, "step_counts": counts},
                            dec_lens=state["dec_lens"] + 1)
