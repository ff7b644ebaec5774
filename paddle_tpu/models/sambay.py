"""SambaY: the hybrid decoder of Phi-4-mini-flash-reasoning
(``model_type: phi4flash``, arXiv:2507.06607).

Four kinds of token mixer in one stack of L blocks (L divisible by 4), each
block ``x += Mix(LN(x)); x += MLP(LN'(x))`` with LayerNorm and a gated MLP,
and no positional term anywhere:

  * l < L/2: even l a **state-space** layer (Mamba-1: causal depthwise
    convolution, selective scan with a per-channel state of 16), odd l
    **window attention** (``sliding_window`` rows, the current one among
    them);
  * l = L/2: state-space, whose scan output is kept as the memory M;
  * l = L/2 + 1: **full attention**, whose K and V are kept;
  * l >= L/2 + 2: even l a **gated memory unit** ``(silu(u W1) * M) W2``,
    odd l **cross attention** to the kept K and V (no K/V projection).

Every attention is differential attention: query heads pair as (2p, 2p+1),
key heads as (2g, 2g+1) with g = p // 2, the value of a group is the two
value heads side by side; ``o_p = (1 - lam0) RMSNorm(A1 - lam A2)``.

Serving. A sequence's cache is three things, and the model owns their layout
behind ``paged_alloc`` / ``paged_prefill_into`` / ``paged_decode_step``:

  * ONE page pool, written by the full layer and read by it and by every
    cross layer: ``[n_pages, KV/2, block, 2h]`` for K and for V, a key group
    (k_2g | k_2g+1) and a value group a row. With the queries of a group
    zero-padded to 2h on the other head's half, differential attention over
    it is plain grouped attention over heads of 2h, which is what the paged
    decode kernel (``ops/pallas/paged_attention``) computes;
  * a ring of ``sliding_window`` rows for each window layer and slot, in the
    pool's page layout, so that the same kernel reads it through a fixed
    table: row ``p % window`` holds position p, and with no positional term
    the order of rows inside the window does not matter;
  * for each state-space layer and slot the state h (float32) and the last
    rows of the convolution's input.

Rings and states never grow; they sit in arrays indexed by slot inside the
state pytree the batcher donates. A prefill chunk that starts at row 0
starts the slot's state from zero inside the executable; a later chunk
starts from what the last one left; a released slot's state is abandoned.

Prompt positions whose logits nobody reads do not run the cross-decoder
(layers above L/2 + 1), as the published design has it: a chunk runs the
self-decoder and the full layer's K/V projection over all its rows, and
everything above only for the row whose logits are asked for.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

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

F32 = jnp.float32


@dataclass
class SambaYConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None        # None: ceil(hidden / 16)
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or math.ceil(self.hidden_size / 16)

    def mixer_kind(self, layer: int) -> str:
        half = self.num_hidden_layers // 2
        if layer < half:
            return "window" if layer % 2 else "ssm"
        if layer == half:
            return "ssm_mem"
        if layer == half + 1:
            return "full"
        return "cross" if layer % 2 else "gmu"

    @staticmethod
    def lam0(layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)


def sambay_tiny_config(**overrides) -> SambaYConfig:
    """Test-scale config: the whole pattern once."""
    return SambaYConfig(**dict(dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
        sliding_window=8, max_position_embeddings=512,
        initializer_range=0.1), **overrides))


# -- the layers' arithmetic, on arrays ----------------------------------------
# Each function below is pure ``jax.numpy`` over a dict of one block's
# weights; the model calls them through ``ops.registry.dispatch`` so that the
# weights are recorded as the executable's state. Matrix products accumulate
# in float32; norms, softmax and the scan are float32.

def _mm(x, w, out=None):
    return jnp.dot(x, w, preferred_element_type=F32).astype(out or x.dtype)


def _norm(x, gain, bias, eps):
    xf = x.astype(F32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + eps) * gain.astype(F32)
            + bias.astype(F32)).astype(x.dtype)


def _mlp_residual(p, x, eps):
    with jax.named_scope("norm"):
        u = _norm(x, p["ln2_g"], p["ln2_b"], eps)
    with jax.named_scope("mlp"):
        gp = _mm(u, p["mlp_w1"], F32)
        ffn = gp.shape[-1] // 2
        act = jax.nn.silu(gp[..., :ffn]) * gp[..., ffn:]
        return x + _mm(act.astype(x.dtype), p["mlp_w2"])


def _ssm_inputs(p, a_ext, rows):
    """From the convolution's input (``taps - 1`` earlier rows first) to what
    the scan reads: the activated input, dt, B and C, all float32."""
    taps = p["conv_w"].shape[0]
    n = p["a_log"].shape[1]
    rank = p["dt_w"].shape[0]
    with jax.named_scope("conv"):
        acc = p["conv_b"].astype(F32)
        for k in range(taps):
            acc = acc + p["conv_w"][k].astype(F32) \
                * lax.slice_in_dim(a_ext, k, k + rows, axis=-2).astype(F32)
        act = jax.nn.silu(acc)
    with jax.named_scope("in_proj"):
        rbc = _mm(act.astype(a_ext.dtype), p["x_w"])
        r, b_t, c_t = jnp.split(rbc, [rank, rank + n], axis=-1)
        dt = jax.nn.softplus(_mm(r, p["dt_w"], F32) + p["dt_b"].astype(F32))
    return act, dt, b_t.astype(F32), c_t.astype(F32)


def _ssm_seq(p, u, h0, conv0, n_valid):
    """State-space mixer over one sequence's rows u [S, d] from the state
    (h0 [d_inner, n] float32, conv0 [taps - 1, d_inner]); rows from
    ``n_valid`` on are padding and leave the state as it is. Returns (output
    [S, d], scan output y [S, d_inner], h, conv)."""
    rows = u.shape[0]
    di = p["in_w"].shape[1] // 2
    taps = p["conv_w"].shape[0]
    with jax.named_scope("in_proj"):
        az = _mm(u, p["in_w"])
        a, z = az[:, :di], az[:, di:]
    a_ext = jnp.concatenate([conv0.astype(a.dtype), a])
    conv1 = lax.dynamic_slice_in_dim(a_ext, n_valid, taps - 1)
    act, dt, b_t, c_t = _ssm_inputs(p, a_ext, rows)
    dt = jnp.where(jnp.arange(rows)[:, None] < n_valid, dt, 0.0)
    with jax.named_scope("ssm_scan"):
        a_neg = -jnp.exp(p["a_log"].astype(F32))
        skip = p["d_skip"].astype(F32)

        def step(h, xs):
            dt_t, x_t, bt, ct = xs
            h = jnp.exp(dt_t[:, None] * a_neg) * h \
                + (dt_t * x_t)[:, None] * bt[None, :]
            return h, jnp.sum(h * ct[None, :], -1) + skip * x_t

        h1, y = lax.scan(step, h0, (dt, act, b_t, c_t),
                         unroll=min(8, rows))
    with jax.named_scope("out_proj"):
        out = _mm((y * jax.nn.silu(z.astype(F32))).astype(u.dtype),
                  p["out_w"])
    return out, y.astype(u.dtype), h1, conv1


def _ssm_tok(p, u, h, conv):
    """One token a slot: u [B, d], h [B, d_inner, n], conv [B, taps - 1,
    d_inner]."""
    di = p["in_w"].shape[1] // 2
    with jax.named_scope("in_proj"):
        az = _mm(u, p["in_w"])
        a, z = az[:, :di], az[:, di:]
    a_ext = jnp.concatenate([conv.astype(a.dtype), a[:, None]], axis=1)
    act, dt, b_t, c_t = _ssm_inputs(p, a_ext, 1)
    act, dt, b_t, c_t = act[:, 0], dt[:, 0], b_t[:, 0], c_t[:, 0]
    with jax.named_scope("ssm_step"):
        a_neg = -jnp.exp(p["a_log"].astype(F32))
        h1 = jnp.exp(dt[:, :, None] * a_neg[None]) * h \
            + (dt * act)[:, :, None] * b_t[:, None, :]
        y = jnp.sum(h1 * c_t[:, None, :], -1) + p["d_skip"].astype(F32) * act
    with jax.named_scope("out_proj"):
        out = _mm((y * jax.nn.silu(z.astype(F32))).astype(u.dtype),
                  p["out_w"])
    return out, y.astype(u.dtype), h1, a_ext[:, 1:]


def _heads(p):
    """(query heads, key/value heads, head size) of an attention block."""
    h = p["lq1"].shape[0]
    d = p["o_w"].shape[0]
    kv = (p["qkv_w"].shape[1] - d) // (2 * h) if "qkv_w" in p else None
    return d // h, kv, h


def _pad_queries(q):
    """q [..., H, h] -> [..., H, 2h]: an even head (which scores against
    k_2g) on the first half of its group's key row, an odd head (against
    k_2g+1) on the second, zeros on the other."""
    heads, h = q.shape[-2:]
    even = (jnp.arange(heads) % 2 == 0)[:, None]
    zero = jnp.zeros_like(q)
    return jnp.concatenate([jnp.where(even, q, zero),
                            jnp.where(even, zero, q)], axis=-1)


def _attend(qp, keys, values, ok, scale):
    """Grouped attention over explicit rows: qp [B, Q, H, 2h], keys / values
    [B, T, G, 2h], ok [B, Q, T] -> [B, Q, H, 2h] float32."""
    b, q, heads, width = qp.shape
    groups = keys.shape[2]
    qg = qp.reshape(b, q, groups, heads // groups, width)
    scores = jnp.einsum("bqgrd,btgd->bgrqt", qg, keys,
                        preferred_element_type=F32) * scale
    probs = jax.nn.softmax(jnp.where(ok[:, None, None], scores, -1e30), -1)
    out = jnp.einsum("bgrqt,btgd->bqgrd", probs.astype(values.dtype), values,
                     preferred_element_type=F32)
    return out.reshape(b, q, heads, width)


def _diff_out(p, att, lam_0, dtype):
    """att [..., H, 2h] (A1 of a pair on the even head, A2 on the odd) ->
    the mixer's output [..., d]."""
    heads, width = att.shape[-2:]
    lam = jnp.exp(jnp.sum(p["lq1"].astype(F32) * p["lk1"].astype(F32))) \
        - jnp.exp(jnp.sum(p["lq2"].astype(F32) * p["lk2"].astype(F32))) \
        + lam_0
    pairs = att.astype(F32).reshape(att.shape[:-2] + (heads // 2, 2, width))
    diff = pairs[..., 0, :] - lam * pairs[..., 1, :]
    rms = lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True) + 1e-5)
    out = (1.0 - lam_0) * diff * rms * p["sub_g"].astype(F32)
    out = out.reshape(att.shape[:-2] + (heads // 2 * width,)).astype(dtype)
    return _mm(out, p["o_w"]) + p["o_b"]


def _project(p, u, cross=False):
    """(padded queries [..., H, 2h], key groups, value groups [..., G, 2h]);
    a cross layer projects the queries alone."""
    heads, kv, h = _heads(p)
    lead = u.shape[:-1]
    if cross:
        q = _mm(u, p["q_w"]) + p["q_b"]
        return _pad_queries(q.reshape(lead + (heads, h))), None, None
    d = heads * h
    qkv = _mm(u, p["qkv_w"]) + p["qkv_b"]
    q, k, v = jnp.split(qkv, [d, d + kv * h], axis=-1)
    return (_pad_queries(q.reshape(lead + (heads, h))),
            k.reshape(lead + (kv // 2, 2 * h)),
            v.reshape(lead + (kv // 2, 2 * h)))


def _pages_to_rows(pages):
    """[P, G, block, D] -> [P * block, G, D]."""
    n, g, bs, d = pages.shape
    return jnp.moveaxis(pages, 1, 2).reshape(n * bs, g, d)


def _rows_to_pages(rows, block):
    """[P * block, G, D] -> [P, G, block, D]."""
    t, g, d = rows.shape
    return jnp.moveaxis(rows.reshape(t // block, block, g, d), 2, 1)


def decode_route(pool) -> str:
    """``"kernel"`` where the paged decode kernel takes this page layout on
    this backend, ``"gather"`` otherwise."""
    from ..incubate.nn.functional.decode_attention import \
        decode_attention_path
    groups = pool.shape[1]
    return decode_attention_path(tuple(pool.shape), pool.dtype, groups)


def _paged_attend(qp, kc, vc, table, kv_len, scale):
    """One query row a sequence against the pages ``table`` names: qp [B, H,
    2h] -> [B, H, 2h]; ``kv_len`` rows of each sequence count."""
    with jax.named_scope("scores"):
        if decode_route(kc) == "kernel":
            from ..ops.pallas.paged_attention import paged_attention_decode
            return paged_attention_decode(qp, kc, vc, table, kv_len,
                                          scale=scale)
        b, pages = table.shape
        n, g, bs, d = kc.shape
        # spelled out, not through ``_pages_to_rows``: XLA:CPU then has no
        # bfloat16 x bfloat16 -> float32 dot for the product below (jax 0.9)
        keys = jnp.moveaxis(kc[table.reshape(-1)].reshape(b, pages, g, bs, d),
                            2, 3).reshape(b, pages * bs, g, d)
        vals = jnp.moveaxis(vc[table.reshape(-1)].reshape(b, pages, g, bs, d),
                            2, 3).reshape(b, pages * bs, g, d)
        ok = jnp.arange(pages * bs)[None, None, :] < kv_len[:, None, None]
        return _attend(qp[:, None], keys, vals, ok, scale)[:, 0]


# -- blocks: LN, mixer, residual, LN, MLP, residual ---------------------------

def _ssm_block_seq(p, x, h_all, conv_all, slot, dec, n_valid, eps):
    """A state-space block over one sequence's chunk x [S, d]; the slot's
    state is read (or taken as zero where the chunk starts the timeline) and
    written back."""
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    carried = dec > 0
    h0 = jnp.where(carried, h_all[slot], 0.0)
    conv0 = jnp.where(carried, conv_all[slot], jnp.zeros_like(conv_all[0]))
    with jax.named_scope("ssm"):
        out, y, h1, conv1 = _ssm_seq(p, u, h0, conv0, n_valid)
    x = _mlp_residual(p, x + out, eps)
    return (x, y, h_all.at[slot].set(h1),
            conv_all.at[slot].set(conv1.astype(conv_all.dtype)))


def _ssm_block_tok(p, x, h, conv, eps):
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    with jax.named_scope("ssm"):
        out, y, h1, conv1 = _ssm_tok(p, u, h, conv)
    return _mlp_residual(p, x + out, eps), y, h1, conv1.astype(conv.dtype)


def _ssm_block_dense(p, x, eps):
    """x [B, S, d], every sequence from a zero state."""
    di, n = p["a_log"].shape
    taps = p["conv_w"].shape[0]
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    with jax.named_scope("ssm"):
        out, y, _, _ = jax.vmap(
            lambda us: _ssm_seq(p, us, jnp.zeros((di, n), F32),
                                jnp.zeros((taps - 1, di), x.dtype),
                                x.shape[1]))(u)
    return _mlp_residual(p, x + out, eps), y


def _gmu_block(p, x, memory, eps):
    """Position-wise: x [..., d], memory [..., d_inner]."""
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(_mm(u, p["gmu_w1"], F32))
        out = _mm((gate * memory.astype(F32)).astype(x.dtype), p["gmu_w2"])
    return _mlp_residual(p, x + out, eps)


def _attention_block_dense(p, x, kept, lam_0, eps, window, scope):
    """An attention block over whole sequences x [B, S, d]. ``kept``: the
    full layer's (keys, values) for a cross layer, None otherwise. Returns
    (x, keys, values)."""
    b, s, _ = x.shape
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    with jax.named_scope(scope):
        qp, keys, vals = _project(p, u, cross=kept is not None)
        if kept is not None:
            keys, vals = kept
        pos = jnp.arange(s)
        ok = pos[None, :] <= pos[:, None]
        if window:
            ok &= pos[:, None] - pos[None, :] < window
        with jax.named_scope("scores"):
            att = _attend(qp, keys, vals, jnp.broadcast_to(ok, (b, s, s)),
                          1.0 / math.sqrt(qp.shape[-1] // 2))
        out = _diff_out(p, att, lam_0, x.dtype)
    return _mlp_residual(p, x + out, eps), keys, vals


def _window_block_seq(p, x, rk, rv, slot, dec, n_valid, lam_0, eps, window):
    """A window block over one sequence's chunk x [S, d] at rows dec ..
    dec + S of its timeline. The slot's ring (row p % R holds position p)
    gives the rows before the chunk; the chunk's valid rows then take
    their places in it."""
    s = x.shape[0]
    block = rk.shape[2]
    ring_pages, ring = window // block, window
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    with jax.named_scope("window_attention"):
        qp, k, v = _project(p, u)
        old_k = _pages_to_rows(lax.dynamic_slice_in_dim(
            rk, slot * ring_pages, ring_pages))
        old_v = _pages_to_rows(lax.dynamic_slice_in_dim(
            rv, slot * ring_pages, ring_pages))
        r = jnp.arange(ring)
        old_pos = dec - 1 - ((dec - 1 - r) % ring)       # < 0: nothing yet
        qpos = dec + jnp.arange(s)
        kpos = jnp.concatenate([old_pos, qpos])
        ok = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None]) \
            & (qpos[:, None] - kpos[None, :] < window)
        with jax.named_scope("scores"):
            att = _attend(qp[None], jnp.concatenate([old_k, k])[None],
                          jnp.concatenate([old_v, v])[None], ok[None],
                          1.0 / math.sqrt(qp.shape[-1] // 2))[0]
        out = _diff_out(p, att, lam_0, x.dtype)
        with jax.named_scope("kv_write"):
            last = dec + n_valid - 1
            new_pos = last - ((last - r) % ring)     # the ring after the chunk
            take = jnp.clip(new_pos - dec, 0, s - 1)
            fresh = (new_pos >= dec)[:, None, None]
            rk = lax.dynamic_update_slice_in_dim(
                rk, _rows_to_pages(jnp.where(fresh, k[take].astype(rk.dtype),
                                             old_k), block),
                slot * ring_pages, 0)
            rv = lax.dynamic_update_slice_in_dim(
                rv, _rows_to_pages(jnp.where(fresh, v[take].astype(rv.dtype),
                                             old_v), block),
                slot * ring_pages, 0)
    return _mlp_residual(p, x + out, eps), rk, rv


def _window_block_tok(p, x, rk, rv, dec, lam_0, eps, window):
    """One token a slot: x [B, d]; its K/V row goes to ring row
    dec % window, then min(dec + 1, window) rows count."""
    b = x.shape[0]
    block = rk.shape[2]
    ring_pages = window // block
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    with jax.named_scope("window_attention"):
        qp, k, v = _project(p, u)
        row = dec % window
        page = jnp.arange(b) * ring_pages + row // block
        with jax.named_scope("kv_write"):
            rk = _write_rows(rk, page, row % block, k)
            rv = _write_rows(rv, page, row % block, v)
        table = jnp.arange(b * ring_pages, dtype=jnp.int32).reshape(
            b, ring_pages)
        att = _paged_attend(qp, rk, rv, table, jnp.minimum(dec + 1, window),
                            1.0 / math.sqrt(qp.shape[-1] // 2))
        out = _diff_out(p, att, lam_0, x.dtype)
    return _mlp_residual(p, x + out, eps), rk, rv


def _pool_block_tok(p, x, kc, vc, table, dec, lam_0, eps, cross):
    """The full layer (writes its K/V row at ``dec``) or a cross layer
    (reads only) for one token a slot: x [B, d]."""
    block = kc.shape[2]
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    with jax.named_scope("cross_attention" if cross else "full_attention"):
        qp, k, v = _project(p, u, cross=cross)
        if not cross:
            page = jnp.take_along_axis(table, (dec // block)[:, None],
                                       axis=1)[:, 0]
            with jax.named_scope("kv_scatter"):
                kc = _write_rows(kc, page, dec % block, k)
                vc = _write_rows(vc, page, dec % block, v)
        att = _paged_attend(qp, kc, vc, table, dec + 1,
                            1.0 / math.sqrt(qp.shape[-1] // 2))
        out = _diff_out(p, att, lam_0, x.dtype)
    return _mlp_residual(p, x + out, eps), kc, vc


def _full_write_seq(p, x, kc, vc, table, dec, eps):
    """The full layer's K/V of a whole chunk x [S, d] into the slot's pages
    at rows dec .. dec + S. Returns the pool and the slot's whole timeline
    as rows [T, G, 2h], the chunk's among them."""
    block = kc.shape[2]
    d = p["o_w"].shape[0]
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    with jax.named_scope("full_attention"):
        kv = _mm(u, p["qkv_w"][:, d:]) + p["qkv_b"][d:]
        k, v = jnp.split(kv, 2, axis=-1)
        groups = kc.shape[1]
        k = k.reshape(x.shape[0], groups, -1).astype(kc.dtype)
        v = v.reshape(x.shape[0], groups, -1).astype(vc.dtype)
        with jax.named_scope("kv_scatter"):
            rows_k = lax.dynamic_update_slice_in_dim(
                _pages_to_rows(kc[table[0]]), k, dec, 0)
            rows_v = lax.dynamic_update_slice_in_dim(
                _pages_to_rows(vc[table[0]]), v, dec, 0)
            kc = kc.at[table[0]].set(_rows_to_pages(rows_k, block))
            vc = vc.at[table[0]].set(_rows_to_pages(rows_v, block))
    return kc, vc, rows_k, rows_v


def _rows_block_tok(p, x, rows_k, rows_v, kv_len, lam_0, eps, cross):
    """The full or a cross layer for the one row of a chunk whose logits
    are read: x [1, d] against the slot's timeline as rows."""
    with jax.named_scope("norm"):
        u = _norm(x, p["ln1_g"], p["ln1_b"], eps)
    with jax.named_scope("cross_attention" if cross else "full_attention"):
        qp = _project(p, u, cross=cross)[0]
        ok = jnp.arange(rows_k.shape[0])[None, None, :] < kv_len
        with jax.named_scope("scores"):
            att = _attend(qp[:, None], rows_k[None], rows_v[None], ok,
                          1.0 / math.sqrt(qp.shape[-1] // 2))[:, 0]
        out = _diff_out(p, att, lam_0, x.dtype)
    return _mlp_residual(p, x + out, eps)


def _head(top, x, eps):
    with jax.named_scope("head"):
        x = _norm(x, top["norm_g"], top["norm_b"], eps)
        return lax.dot_general(
            x, top["embed"], (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=F32).astype(x.dtype)


_STATIC = {"eps", "window", "cross", "scope"}


def _jitted(fn):
    names = [n for n in fn.__code__.co_varnames[:fn.__code__.co_argcount]
             if n in _STATIC]
    return jax.jit(fn, static_argnames=names)


# the first call of a ``to_static`` function is eager: jitted a kind of
# block, it compiles a handful of programs and not one per operation
_BLOCKS = {fn.__name__: _jitted(fn) for fn in (
    _ssm_block_seq, _ssm_block_tok, _ssm_block_dense, _gmu_block,
    _attention_block_dense, _window_block_seq, _window_block_tok,
    _pool_block_tok, _full_write_seq, _rows_block_tok, _head)}


def _run(name, *args, **kwargs):
    return dispatch(_BLOCKS[name], args, kwargs, op_name=f"sambay{name}")


# -- parameters ---------------------------------------------------------------

class _Dense(Layer):
    """weight [in, out] and an optional bias, created in ``dtype``."""

    def __init__(self, n_in, n_out, bias, std, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            [n_in, n_out], default_initializer=I.Normal(0.0, std))
        self.bias = self.create_parameter([n_out], is_bias=True) \
            if bias else None


class _LayerNorm(Layer):
    def __init__(self, width, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            [width], default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter([width], is_bias=True)


class _Gain(Layer):
    def __init__(self, width, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            [width], default_initializer=I.Constant(1.0))


class SambaYMLP(Layer):
    def __init__(self, cfg: SambaYConfig):
        super().__init__(dtype=cfg.dtype)
        std = cfg.initializer_range
        self.fc1 = _Dense(cfg.hidden_size, 2 * cfg.intermediate_size, False,
                          std, cfg.dtype)
        self.fc2 = _Dense(cfg.intermediate_size, cfg.hidden_size, False, std,
                          cfg.dtype)

    def leaves(self):
        return {"mlp_w1": self.fc1.weight, "mlp_w2": self.fc2.weight}


class SambaYMamba(Layer):
    """The state-space mixer's weights (Mamba-1)."""

    def __init__(self, cfg: SambaYConfig):
        super().__init__(dtype=cfg.dtype)
        d, di, n = cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state
        std = cfg.initializer_range
        self.in_proj = _Dense(d, 2 * di, False, std, cfg.dtype)
        self.conv1d = _Dense(cfg.mamba_d_conv, di, True, 0.5, cfg.dtype)
        self.x_proj = _Dense(di, cfg.dt_rank + 2 * n, False, std, cfg.dtype)
        self.dt_proj = _Dense(cfg.dt_rank, di, True, std, cfg.dtype)
        # steps near softplus(-4) = 0.018 and A = -(1 .. n), as Mamba starts
        self.dt_proj.bias.set_value(np.full((di,), -4.0, np.float32))
        self.A_log = self.create_parameter(
            [di, n], default_initializer=I.Constant(0.0))
        self.A_log.set_value(np.log(np.tile(
            np.arange(1, n + 1, dtype=np.float32), (di, 1))))
        self.D = self.create_parameter(
            [di], default_initializer=I.Constant(1.0))
        self.out_proj = _Dense(di, d, False, std, cfg.dtype)

    def leaves(self):
        return {"in_w": self.in_proj.weight, "conv_w": self.conv1d.weight,
                "conv_b": self.conv1d.bias, "x_w": self.x_proj.weight,
                "dt_w": self.dt_proj.weight, "dt_b": self.dt_proj.bias,
                "a_log": self.A_log, "d_skip": self.D,
                "out_w": self.out_proj.weight}


class SambaYGMU(Layer):
    def __init__(self, cfg: SambaYConfig):
        super().__init__(dtype=cfg.dtype)
        std = cfg.initializer_range
        self.in_proj = _Dense(cfg.hidden_size, cfg.d_inner, False, std,
                              cfg.dtype)
        self.out_proj = _Dense(cfg.d_inner, cfg.hidden_size, False, std,
                               cfg.dtype)

    def leaves(self):
        return {"gmu_w1": self.in_proj.weight, "gmu_w2": self.out_proj.weight}


class SambaYDiffAttention(Layer):
    """Differential attention's weights; a cross layer has no K/V
    projection."""

    def __init__(self, cfg: SambaYConfig, cross: bool):
        super().__init__(dtype=cfg.dtype)
        d, h = cfg.hidden_size, cfg.head_dim
        std = cfg.initializer_range
        self.cross = cross
        if cross:
            self.Wq = _Dense(d, d, True, std, cfg.dtype)
        else:
            self.Wqkv = _Dense(d, d + 2 * cfg.num_key_value_heads * h, True,
                               std, cfg.dtype)
        self.out_proj = _Dense(d, d, True, std, cfg.dtype)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                [h], default_initializer=I.Normal(0.0, 0.1)))
        self.subln = _Gain(2 * h, cfg.dtype)

    def leaves(self):
        proj = {"q_w": self.Wq.weight, "q_b": self.Wq.bias} if self.cross \
            else {"qkv_w": self.Wqkv.weight, "qkv_b": self.Wqkv.bias}
        return dict(proj, o_w=self.out_proj.weight, o_b=self.out_proj.bias,
                    lq1=self.lambda_q1, lk1=self.lambda_k1,
                    lq2=self.lambda_q2, lk2=self.lambda_k2,
                    sub_g=self.subln.weight)


class SambaYDecoderLayer(Layer):
    def __init__(self, cfg: SambaYConfig, index: int):
        super().__init__(dtype=cfg.dtype)
        self.kind = cfg.mixer_kind(index)
        self.lam0 = cfg.lam0(index)
        self.input_layernorm = _LayerNorm(cfg.hidden_size, cfg.dtype)
        if self.kind in ("ssm", "ssm_mem"):
            self.mixer = SambaYMamba(cfg)
        elif self.kind == "gmu":
            self.mixer = SambaYGMU(cfg)
        else:
            self.mixer = SambaYDiffAttention(cfg, cross=self.kind == "cross")
        self.post_attention_layernorm = _LayerNorm(cfg.hidden_size, cfg.dtype)
        self.mlp = SambaYMLP(cfg)

    def leaves(self):
        """The block's weights under the names the arithmetic reads."""
        return dict(self.mixer.leaves(), **self.mlp.leaves(),
                    ln1_g=self.input_layernorm.weight,
                    ln1_b=self.input_layernorm.bias,
                    ln2_g=self.post_attention_layernorm.weight,
                    ln2_b=self.post_attention_layernorm.bias)


class SambaYModel(Layer):
    def __init__(self, cfg: SambaYConfig):
        super().__init__(dtype=cfg.dtype)
        self.config = cfg
        self.embed_tokens = _Dense(cfg.vocab_size, cfg.hidden_size, False,
                                   cfg.initializer_range, cfg.dtype)
        self.layers = [SambaYDecoderLayer(cfg, i)
                       for i in range(cfg.num_hidden_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layers.{i}", layer)
        self.norm = _LayerNorm(cfg.hidden_size, cfg.dtype)

    def top(self):
        return {"embed": self.embed_tokens.weight,
                "norm_g": self.norm.weight, "norm_b": self.norm.bias}


class SambaYForCausalLM(Layer):
    """``SambaYForCausalLM(SambaYConfig(...))``; ``forward(ids)`` gives the
    logits of every position, ``PagedContinuousBatcher(model, ...)`` serves
    it."""

    def __init__(self, config: SambaYConfig):
        super().__init__(dtype=config.dtype)
        if config.num_hidden_layers % 4:
            raise ValueError("num_hidden_layers must be divisible by 4: the "
                             "pattern is (state-space, attention) pairs in "
                             "two halves")
        if not config.tie_word_embeddings:
            raise ValueError("the head is tied to the embedding")
        if config.num_attention_heads % 4 or \
                config.num_attention_heads != 2 * config.num_key_value_heads:
            raise ValueError("differential attention pairs query heads "
                             "(2p, 2p+1) with key heads (2g, 2g+1), g = "
                             "p // 2: heads = 2 x kv heads, divisible by 4")
        self.config = config
        self.model = SambaYModel(config)

    # -- the whole forward --------------------------------------------------
    def forward(self, input_ids, labels=None):
        cfg = self.config
        eps = cfg.layer_norm_eps
        with jax.named_scope("embed"):
            x = F.embedding(input_ids, self.model.embed_tokens.weight)
        memory = kept = None
        for layer in self.model.layers:
            p = layer.leaves()
            if layer.kind in ("ssm", "ssm_mem"):
                x, y = _run("_ssm_block_dense", p, x, eps=eps)
                if layer.kind == "ssm_mem":
                    memory = y
            elif layer.kind == "gmu":
                x = _run("_gmu_block", p, x, memory, eps=eps)
            else:
                x, keys, vals = _run(
                    "_attention_block_dense", p, x,
                    kept if layer.kind == "cross" else None,
                    F32(layer.lam0), eps=eps,
                    window=cfg.sliding_window
                    if layer.kind == "window" else 0,
                    scope=f"{layer.kind}_attention")
                if layer.kind == "full":
                    kept = (keys, vals)
        logits = _run("_head", self.model.top(), x, eps=eps)
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
        keeps per-slot arrays under ``"slots"`` of what it returns, and
        ``paged_prefill_into`` takes the ``slot`` and the chunk's
        ``n_valid`` rows. A block-table page backs ``block_size`` rows of
        ONE layer's K and V (the full layer's). ``unsupported``: batcher
        options that assume every layer holds every token as pages of K
        and V, each with the reason."""
        pages = "recurrent state and window rings are not pages"
        return {
            "slot_state": True,
            "window_rows": self.config.sliding_window,
            "window_rings": sum(l.kind == "window"
                                for l in self.model.layers),
            "unsupported": {
                "prefix_cache": f"a cached prefix is pages and snapshots "
                                f"of a few rows of state; {pages}: a "
                                f"window layer gets a prefix cache by "
                                f"keeping its rows as a page group "
                                f"(models/mellum.py); a state of a few "
                                f"rows a layer is snapshotted at block "
                                f"boundaries (models/lfm2.py's "
                                f"state_snapshots), and this model's "
                                f"float32 state-space h and its rings are "
                                f"not",
                "kv_quant": "no calibrated int8 path for a pool of key "
                            "groups beside float state",
                "cache_quant": "no dynamic int8 path for a pool of key "
                               "groups beside float state",
                "tier_quant": "needs prefix_cache",
                "draft_model": "a rejected proposal would have to roll the "
                               "recurrent state back",
                "session_store": f"a paused session resumes from cached "
                                 f"pages; {pages}",
            }}

    def paged_alloc(self, n_pages, block_size=16, cache_dtype=None,
                    max_batch=None):
        """The cache: ``{"pool": (K, V)}`` of ``[n_pages, KV/2, block, 2h]``
        that the full layer writes, and under ``"slots"`` what does not
        grow, indexed by slot: a ring of ``sliding_window`` rows in the
        pool's page layout for each window layer, (h, conv rows) for each
        state-space layer."""
        import paddle_tpu as paddle
        cfg = self.config
        if max_batch is None:
            raise ValueError("paged_alloc needs max_batch: rings and "
                             "recurrent state are arrays indexed by slot")
        if cache_dtype not in (None, cfg.dtype):
            raise ValueError(f"cache_dtype {cache_dtype!r}: the cache is "
                             f"held in the model's dtype")
        if cfg.sliding_window % block_size:
            raise ValueError(
                f"sliding_window {cfg.sliding_window} is not whole pages of "
                f"{block_size} rows: a window ring is read as pages")
        groups, width = cfg.num_key_value_heads // 2, 2 * cfg.head_dim
        ring_pages = max_batch * (cfg.sliding_window // block_size)
        di = cfg.d_inner

        def pages(n):
            return (paddle.zeros([n, groups, block_size, width],
                                 dtype=cfg.dtype),
                    paddle.zeros([n, groups, block_size, width],
                                 dtype=cfg.dtype))

        kinds = [l.kind for l in self.model.layers]
        return {
            "pool": pages(n_pages),
            "slots": {
                "rings": [pages(ring_pages)
                          for _ in range(kinds.count("window"))],
                "ssm": [(paddle.zeros([max_batch, di, cfg.mamba_d_state],
                                      dtype="float32"),
                         paddle.zeros([max_batch, cfg.mamba_d_conv - 1, di],
                                      dtype=cfg.dtype))
                        for _ in range(kinds.count("ssm")
                                       + kinds.count("ssm_mem"))]}}

    def paged_decode_attention_path(self, cache) -> str:
        """The route each kind of attention takes in ``paged_decode_step``
        over ``cache``: rings and pool share a page layout, so one word
        decides all three."""
        route = decode_route(cache["pool"][0]._data)
        return f"window={route},full={route},cross={route}"

    def paged_kv_writer(self, cache) -> str:
        """Rings and pool are float and take their rows by the page."""
        return "page"

    def paged_prefill_into(self, input_ids, layers, block_tables,
                           block_size=16, dec_base=None, logits_at=None,
                           slot=None, n_valid=None):
        """One sequence's chunk ``input_ids [1, S]`` at rows ``dec_base ..
        dec_base + S`` of slot ``slot``'s timeline, of which ``n_valid``
        are real (the rest pad a fixed-width chunk and leave every state
        alone). ``dec_base`` 0 (or None) starts the slot's state from zero.
        Returns (logits [1, V] of row ``logits_at``, the cache)."""
        import paddle_tpu as paddle
        cfg = self.config
        b, s = input_ids.shape
        if b != 1 or slot is None:
            raise ValueError("a prefill is one sequence into its slot: "
                             "input_ids [1, S] and slot")
        eps = cfg.layer_norm_eps

        def scalar(t, default):
            if t is None:
                return paddle.to_tensor(np.array(default, np.int32))
            return t.reshape([]).astype("int32")

        dec = scalar(dec_base, 0)
        at = scalar(logits_at, s - 1)
        slot = scalar(slot, 0)
        n_valid = scalar(n_valid, s)
        table = block_tables.astype("int32")
        kc, vc = layers["pool"]
        rings = list(layers["slots"]["rings"])
        ssm = list(layers["slots"]["ssm"])
        with jax.named_scope("embed"):
            x = F.embedding(input_ids.reshape([s]),
                            self.model.embed_tokens.weight)
        i_ring = i_ssm = 0
        half = cfg.num_hidden_layers // 2
        for layer in self.model.layers[:half + 1]:
            p = layer.leaves()
            if layer.kind == "window":
                rk, rv = rings[i_ring]
                x, rk, rv = _run("_window_block_seq", p, x, rk, rv, slot,
                                 dec, n_valid, F32(layer.lam0), eps=eps,
                                 window=cfg.sliding_window)
                rings[i_ring] = (rk, rv)
                i_ring += 1
            else:
                h, conv = ssm[i_ssm]
                x, memory, h, conv = _run("_ssm_block_seq", p, x, h, conv,
                                          slot, dec, n_valid, eps=eps)
                ssm[i_ssm] = (h, conv)
                i_ssm += 1
        # the full layer's K/V of every row; above it, the one row whose
        # logits are read
        full = self.model.layers[half + 1]
        kc, vc, rows_k, rows_v = _run("_full_write_seq", full.leaves(), x,
                                      kc, vc, table, dec, eps=eps)
        x = paddle.index_select(x, at.reshape([1]), axis=0)
        memory = paddle.index_select(memory, at.reshape([1]), axis=0)
        kv_len = dec + at + 1
        for layer in self.model.layers[half + 1:]:
            p = layer.leaves()
            if layer.kind == "gmu":
                x = _run("_gmu_block", p, x, memory, eps=eps)
            else:
                x = _run("_rows_block_tok", p, x, rows_k, rows_v, kv_len,
                         F32(layer.lam0), eps=eps,
                         cross=layer.kind == "cross")
        logits = _run("_head", self.model.top(), x, eps=eps)
        return logits, {"pool": (kc, vc),
                        "slots": {"rings": rings, "ssm": ssm}}

    def paged_decode_step(self, tok, state):
        """One token a slot. tok [B]; ``state`` as the batcher keeps it:
        ``layers`` (``paged_alloc``'s), ``block_tables`` [B, pages a slot],
        ``dec_lens`` [B], the rows a slot holds before this step."""
        cfg = self.config
        eps = cfg.layer_norm_eps
        dec = state["dec_lens"].astype("int32")
        table = state["block_tables"].astype("int32")
        cache = state["layers"]
        kc, vc = cache["pool"]
        rings = list(cache["slots"]["rings"])
        ssm = list(cache["slots"]["ssm"])
        with jax.named_scope("embed"):
            x = F.embedding(tok, self.model.embed_tokens.weight)
        i_ring = i_ssm = 0
        memory = None
        for layer in self.model.layers:
            p = layer.leaves()
            if layer.kind in ("ssm", "ssm_mem"):
                h, conv = ssm[i_ssm]
                x, y, h, conv = _run("_ssm_block_tok", p, x, h, conv,
                                     eps=eps)
                ssm[i_ssm] = (h, conv)
                i_ssm += 1
                if layer.kind == "ssm_mem":
                    memory = y
            elif layer.kind == "window":
                rk, rv = rings[i_ring]
                x, rk, rv = _run("_window_block_tok", p, x, rk, rv, dec,
                                 F32(layer.lam0), eps=eps,
                                 window=cfg.sliding_window)
                rings[i_ring] = (rk, rv)
                i_ring += 1
            elif layer.kind == "gmu":
                x = _run("_gmu_block", p, x, memory, eps=eps)
            else:
                x, kc, vc = _run("_pool_block_tok", p, x, kc, vc, table, dec,
                                 F32(layer.lam0), eps=eps,
                                 cross=layer.kind == "cross")
        logits = _run("_head", self.model.top(), x, eps=eps)
        layers = {"pool": (kc, vc), "slots": {"rings": rings, "ssm": ssm}}
        return logits, dict(state, layers=layers,
                            dec_lens=state["dec_lens"] + 1)
