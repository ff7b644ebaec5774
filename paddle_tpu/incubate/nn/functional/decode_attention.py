"""Inference-serving attention functionals.

Reference surface:
- masked_multihead_attention
  (python/paddle/incubate/nn/functional/masked_multihead_attention.py:19,
   CUDA kernel phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu)
- block_multihead_attention (paged KV cache)
  (python/paddle/incubate/nn/functional/block_multihead_attention.py:19)
- variable_length_memory_efficient_attention
  (python/paddle/incubate/nn/functional/
   variable_length_memory_efficient_attention.py:28)

TPU design: these are the serving-side attention ops. The general
paged-cache read is a gather over the block table (every slot's whole
timeline, whatever is cached); the general cache write is a row scatter
(``_scatter_paged``). The decode step (one token a sequence) has an entry of
its own, ``block_gqa_decode_attention``, which on the chip reads only the
pages a sequence holds through the Pallas kernel of
``ops/pallas/paged_attention``. Where the call site knows that a float pool
takes one row a sequence (that entry) or one run of rows of one sequence (a
prompt's chunk), whole pages are read, changed and written back along the
pool's first axis (``write_page_rows``, ``_write_page_run``): a row scatter
indexes axes 0 and 2 of ``[pages, KV, block, D]``, XLA:TPU gives it a layout
of its own, and every pool array was copied into that layout and back each
step.
Quantized-cache args (qkv_out_scale, cache_k_quant_scales, ...) are gated —
the quantization tier on TPU lives in paddle_tpu.quantization.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....core.tensor import Tensor


def _arr(x):
    if x is None:
        return None
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


_NEG = -1e9


# -- shared paged-cache machinery (used by both the MHA and GQA routes) ----

def _token_timeline(cu_q, dec, token_num):
    """Map packed-token index -> (sequence, local offset, kv-timeline row).
    Decode appends after the existing prefix (dec), prefill starts at 0
    (dec is 0 in encoder mode)."""
    tok = jnp.arange(token_num)
    seq_of = jnp.searchsorted(cu_q, tok, side="right") - 1     # [T]
    local = tok - cu_q[seq_of]
    pos = dec[seq_of] + local
    return seq_of, local, pos


def cachekv_scales_from_dense(arr):
    """Per-layer static cachekv-int8 scale dicts from a dense cache
    [L, 2, B, H, S, D]: per-head |K|/|V| amax -> (quant=127/amax,
    dequant=amax/127). Model-agnostic (GPT-2 and Llama calibrations both
    feed their prefill caches through this)."""
    amax = jnp.max(jnp.abs(arr.astype(jnp.float32)), axis=(2, 4, 5))
    amax = jnp.maximum(amax, 1e-6)                    # [L, 2, H]
    return [{"kq": 127.0 / amax[li, 0], "vq": 127.0 / amax[li, 1],
             "kdq": amax[li, 0] / 127.0, "vdq": amax[li, 1] / 127.0}
            for li in range(arr.shape[0])]


def cachekv_scale_kwargs(scales, li):
    """Block-attention kwargs for layer li's cache quantization (empty
    when the int8 cache is disabled)."""
    if scales is None:
        return {}
    sc = scales[li]
    return {"cache_k_quant_scales": sc["kq"],
            "cache_v_quant_scales": sc["vq"],
            "cache_k_dequant_scales": sc["kdq"],
            "cache_v_dequant_scales": sc["vdq"]}


def _cachekv_scales(kc, k_quant, v_quant, k_dequant, v_dequant,
                    dynamic=False, compute=False):
    """Validate the cachekv-int8 contract and return the four scale
    arrays. All-or-nothing: partial scale sets would silently skip
    quantization, and an int8 pool without scales would astype-truncate
    raw fp rows into int8 codes — both are loud errors instead. In
    dynamic mode, computing scales from this call's rows is an EXPLICIT
    prefill-caller opt-in (compute=True); a call with neither scales nor
    the opt-in errors even under jit tracing, so a compiled decode that
    forgot to thread the prefill's scales can never silently re-derive
    them from one token and dequantize the cached timeline wrong."""
    scales = (_arr(k_quant), _arr(v_quant), _arr(k_dequant),
              _arr(v_dequant))
    given = [s is not None for s in scales]
    if any(given) and not all(given):
        raise ValueError("cachekv int8 needs all four scale tensors "
                         "(k/v quant + k/v dequant)")
    is_int8 = jnp.issubdtype(kc.dtype, jnp.integer)
    if compute and not dynamic:
        raise ValueError("compute_dynamic_scales requires "
                         "use_dynamic_cachekv_quant=True")
    if compute and all(given):
        raise ValueError("compute_dynamic_scales with scales already "
                         "given is ambiguous: drop one of them")
    if is_int8 and not all(given) and not (dynamic and compute):
        raise ValueError(
            "int8 cache pool but no quant scales: calibrate first, thread "
            "the prefill's scales, or opt in with compute_dynamic_scales="
            "True on the prefill call (a raw astype would truncate fp "
            "rows into int8 codes)")
    if all(given) and not is_int8:
        raise ValueError("cachekv quant scales given but the cache pool "
                         f"dtype is {kc.dtype}; allocate int8 pools")
    if dynamic and not is_int8:
        raise ValueError(
            "use_dynamic_cachekv_quant with a non-int8 cache pool "
            f"({kc.dtype}): quantized codes in fp rows would pay the "
            f"quant noise with zero memory saving; allocate int8 pools")
    return scales


def _dynamic_prefill_scales(kt, vt, seq_of, bsz, valid_mask=None):
    """Per-(sequence, head) amax scales from THIS call's K/V rows — the
    reference's DynamicQuantCacheKernel: prefill fills [B, H] quant
    (127/amax) and dequant (amax/127) tensors that decode then consumes.
    kt/vt [T, H, D]. valid_mask [T] (optional) drops rows from the amax
    statistics — chunked prefill's zero-pad tail must not contaminate a
    sequence's scales (the unchunked path sees no padding)."""
    ak = jnp.abs(kt.astype(jnp.float32)).max(-1)              # [T, H]
    av = jnp.abs(vt.astype(jnp.float32)).max(-1)
    if valid_mask is not None:
        ak = jnp.where(valid_mask[:, None], ak, 0.0)
        av = jnp.where(valid_mask[:, None], av, 0.0)
    ka = jax.ops.segment_max(ak, seq_of, num_segments=bsz)    # [B, H]
    va = jax.ops.segment_max(av, seq_of, num_segments=bsz)
    ka = jnp.maximum(ka, 1e-6)
    va = jnp.maximum(va, 1e-6)
    return {"kq": 127.0 / ka, "vq": 127.0 / va,
            "kdq": ka / 127.0, "vdq": va / 127.0}


def _per_token_scale(scale, seq_of):
    """Broadcastable quant scale for [T, H, D] rows: [H] static or
    [B, H] dynamic (indexed per token's sequence)."""
    if scale.ndim == 2:
        return scale[seq_of][:, :, None]
    return scale[None, :, None]


def _per_seq_scale(scale, bsz):
    """Broadcastable dequant scale for the gathered [B, H, S, D]
    timeline: [H] static or [B, H] dynamic."""
    if scale.ndim == 2:
        if scale.shape[0] != bsz:
            raise ValueError(f"dynamic cachekv scales are per sequence: "
                             f"got {scale.shape[0]} rows for batch {bsz}")
        return scale[:, :, None, None]
    return scale[None, :, None, None]


def _dynamic_compute_allowed(enc, this):
    """Dynamic-mode scale computation is a PREFILL-caller contract
    (explicit compute_dynamic_scales opt-in): a decode step that wrongly
    opts in must not derive a sequence's scales from one token. Prefill
    shapes are enc > 0 (whole-prompt call) or enc == 0 with this > 1
    (chunked-prefill append); a single-token call (enc == 0, this == 1)
    is decode-shaped and rejected. With concrete lengths (host-driven
    serving loops) this is enforced loudly; under jit tracing the values
    are unknowable and the documented contract governs."""
    try:
        if not bool(((enc > 0) | (this > 1)).all()):
            # any() would let a MIXED batch derive the decode rows'
            # scales from one token — scale computation is a pure-prefill
            # contract
            raise ValueError(
                "compute_dynamic_scales on a call with decode-mode "
                "sequences (seq_lens_encoder == 0, seq_lens_this_time == "
                "1): thread the scales the prefill call returned")
    except jax.errors.TracerBoolConversionError:
        pass


def _rope_pairs(u, cos_t, sin_t):
    """Rotate [T, H, D] rows by their positions' [T, D/2] tables
    (interleaved-pair convention, computed in fp32)."""
    uf = u.astype(jnp.float32)
    u1, u2 = uf[..., 0::2], uf[..., 1::2]
    c, s = cos_t[:, None, :], sin_t[:, None, :]
    return jnp.stack([u1 * c - u2 * s, u2 * c + u1 * s],
                     axis=-1).reshape(u.shape).astype(u.dtype)


def _scatter_paged(kc, vc, bt, seq_of, pos, kt, vt, block_size,
                   k_quant=None, v_quant=None):
    """Write each token's k/v row at (block_tables[seq, pos//bs], pos%bs).

    k_quant/v_quant: optional quant scales — per-head STATIC [H]
    (reference cache_k_quant_scales) or per-(sequence, head) DYNAMIC
    [B, H]. Rows are quantized to int8 on the way in, so the pool holds
    int8 and cache HBM halves vs bf16 (quarters vs fp32).
    """
    if k_quant is not None:
        # named scope so opprof's "quant" op-class can attribute the
        # encode cost in compiled-program profiles
        with jax.named_scope("cachekv_quant"):
            kt = jnp.clip(jnp.round(kt.astype(jnp.float32)
                                    * _per_token_scale(k_quant, seq_of)),
                          -127, 127).astype(jnp.int8)
            vt = jnp.clip(jnp.round(vt.astype(jnp.float32)
                                    * _per_token_scale(v_quant, seq_of)),
                          -127, 127).astype(jnp.int8)
    phys = bt[seq_of, pos // block_size]
    off = pos % block_size
    return (kc.at[phys, :, off].set(kt.astype(kc.dtype)),
            vc.at[phys, :, off].set(vt.astype(vc.dtype)))


def write_page_rows(pool, page, off, rows):
    """One row a sequence into the page layout: rows [B, G, D] at
    (page [B], off [B]). Whole pages are read, changed and scattered back
    along the pool's first axis, which leaves the pool's layout alone.

    Leans on: no two sequences name the same ``page`` with rows that both
    count. A row scatter kept both rows of a page written at two offsets;
    here the page scattered last wins whole and the other row is lost. The
    paged batchers hold that: the prefix cache shares FULL pages only and a
    sequence writes at or after its first unmatched row, so a page being
    written has one owner; parked slots all name the scratch page, which
    nothing reads (tests/test_paged_batching.py holds the batcher to it).
    """
    cur = pool[page]
    hit = (jnp.arange(pool.shape[2])[None, :] == off[:, None])
    return pool.at[page].set(jnp.where(hit[:, None, :, None],
                                       rows[:, :, None, :].astype(pool.dtype),
                                       cur))


def _write_page_run(pool, table, line, dec, run):
    """One sequence's run of rows ``run`` [T, KV, D] at rows dec .. dec + T
    of its timeline ``line`` [KV, S_kv, D] (its pages ``table``
    [blocks_per_seq] as ``_gather_paged`` reads them): the run is laid over
    the timeline and the pages are scattered back along the pool's first
    axis (no layout of its own, as above). The caller keeps
    dec + T within the table, as for ``_scatter_paged``. Table entries that
    are not backed all name the scratch page: which of the duplicates lands
    there decides nothing. Returns the pool and the timeline with the run
    in it, which is what the scores read."""
    n, (_, kvh, block, hd) = table.shape[0], pool.shape
    line = jax.lax.dynamic_update_slice_in_dim(
        line, jnp.moveaxis(run, 0, 1).astype(pool.dtype), dec, 1)
    pages = jnp.moveaxis(line.reshape(kvh, n, block, hd), 0, 1)
    return pool.at[table].set(pages), line


def _gather_paged(kc, vc, bt, heads, k_dequant=None, v_dequant=None,
                  out_dtype=None):
    """Assemble every sequence's kv timeline from its pages:
    [B, heads, blocks_per_seq*block_size, D]. k_dequant/v_dequant [H]
    undo a quantized pool (reference cache_k_dequant_scales)."""
    bsz, blocks_per_seq = bt.shape
    bs_, hd = kc.shape[2], kc.shape[3]
    s_kv = blocks_per_seq * bs_
    gk = kc[bt.reshape(-1)].reshape(bsz, blocks_per_seq, heads, bs_, hd)
    gv = vc[bt.reshape(-1)].reshape(bsz, blocks_per_seq, heads, bs_, hd)
    gk = jnp.moveaxis(gk, 2, 1).reshape(bsz, heads, s_kv, hd)
    gv = jnp.moveaxis(gv, 2, 1).reshape(bsz, heads, s_kv, hd)
    if k_dequant is not None:
        # named scope mirrors _scatter_paged's cachekv_quant: the decode
        # path's inline dequant (XLA fuses it into the attention matmul)
        # shows up as the "quant" op-class in opprof
        with jax.named_scope("cachekv_dequant"):
            scale_k = _per_seq_scale(k_dequant, bsz)
            scale_v = _per_seq_scale(v_dequant, bsz)
            gk = (gk.astype(jnp.float32) * scale_k).astype(out_dtype)
            gv = (gv.astype(jnp.float32) * scale_v).astype(out_dtype)
    return gk, gv, s_kv


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               cum_offsets=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               qkv_out_scale=None, out_shift=None,
                               out_smooth=None, seq_len=1, rotary_emb_dims=0,
                               use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0):
    """One-token decode attention over a dense KV cache.

    x: [B, 3*H*D] (this step's fused qkv). cache_kv: [2, B, H, S_max, D].
    sequence_lengths: [B, 1] current lengths (timestep per sequence);
    defaults to 0 (first step). Returns (out [B, H*D], cache_kv_out).
    """
    if qkv_out_scale is not None or out_scale != -1:
        raise NotImplementedError(
            "quantized decode path: use paddle_tpu.quantization")
    xq = _arr(x)
    cache = _arr(cache_kv)
    if cache is None:
        raise ValueError("cache_kv is required")
    _, bsz, nh, s_max, hd = cache.shape
    qkv = xq.reshape(bsz, 3, nh, hd)
    if bias is not None:
        qkv = qkv + _arr(bias)[None]
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]          # [B, H, D]

    if sequence_lengths is not None:
        t = _arr(sequence_lengths).reshape(bsz).astype(jnp.int32)
    else:
        t = jnp.zeros((bsz,), jnp.int32)

    if rotary_tensor is not None and rotary_emb_dims > 0:
        # rotary_tensor [B, 1, 1, S, D]: cos/sin interleaved table; apply to
        # q and k at position t (reference decode rope)
        rot = _arr(rotary_tensor)[:, 0, 0]              # [B, S, D]
        rt = jnp.take_along_axis(rot, t[:, None, None], axis=1)[:, 0]  # [B,D]
        cos, sin = rt[..., 0::2], rt[..., 1::2]

        def _rope(u):
            u1, u2 = u[..., 0::2], u[..., 1::2]
            c, s = cos[:, None, :], sin[:, None, :]
            return jnp.stack([u1 * c - u2 * s, u2 * c + u1 * s],
                             axis=-1).reshape(u.shape)
        q, k = _rope(q), _rope(k)

    # scatter this step's k/v at row t of each sequence
    b_idx = jnp.arange(bsz)
    ck = cache[0].at[b_idx, :, t].set(k)
    cv = cache[1].at[b_idx, :, t].set(v)
    new_cache = jnp.stack([ck, cv])

    scores = jnp.einsum("bhd,bhsd->bhs", q, ck) / jnp.sqrt(
        jnp.asarray(hd, jnp.float32)).astype(q.dtype)
    pos = jnp.arange(s_max)[None, None, :]
    scores = jnp.where(pos <= t[:, None, None], scores,
                       jnp.asarray(_NEG, scores.dtype))
    if src_mask is not None:
        m = _arr(src_mask)[:, 0, 0]                     # [B, S_mask]
        s_mask = m.shape[-1]
        scores = scores.at[:, :, :s_mask].add(m[:, None, :].astype(scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhs,bhsd->bhd", probs.astype(q.dtype), cv)
    return Tensor(out.reshape(bsz, nh * hd)), Tensor(new_cache)


def block_multihead_attention(qkv, key_cache, value_cache, seq_lens_encoder,
                              seq_lens_decoder, seq_lens_this_time,
                              padding_offsets, cum_offsets, cu_seqlens_q,
                              cu_seqlens_k, block_tables, pre_key_cache=None,
                              pre_value_cache=None, cache_k_quant_scales=None,
                              cache_v_quant_scales=None,
                              cache_k_dequant_scales=None,
                              cache_v_dequant_scales=None, qkv_out_scale=None,
                              qkv_bias=None, out_shift=None, out_smooth=None,
                              rope_emb=None, mask=None, tgt_mask=None,
                              max_seq_len=-1, block_size=64,
                              use_neox_style=False,
                              use_dynamic_cachekv_quant=False,
                              compute_dynamic_scales=False,
                              dynamic_scale_valid=None,
                              quant_round_type=1, quant_max_bound=127.0,
                              quant_min_bound=-127.0, out_scale=-1,
                              compute_dtype="default"):
    """Paged-KV attention (vLLM-style block cache; ref
    block_multihead_attention.py:19).

    qkv: [token_num, 3*H*D] packed unpadded tokens (sequences concatenated,
    boundaries in cu_seqlens_q). key_cache/value_cache:
    [max_block_num, H, block_size, D]. block_tables: [B, blocks_per_seq]
    maps sequence-local block index -> physical cache block. Per sequence,
    mode is prefill when seq_lens_encoder[i] > 0 (writes the whole prompt
    into its blocks, causal attention over it) or decode when
    seq_lens_this_time[i] == 1 (appends at seq_lens_decoder[i], attends to
    the full prefix through the block table).

    Cache-KV int8: pass cache_k/v_quant_scales + dequant_scales of shape
    [num_head] (static mode) or [B, num_head]
    (use_dynamic_cachekv_quant=True: per-sequence scales the reference's
    DynamicQuantCacheKernel fills at prefill) with int8 cache pools —
    rows quantize on the scatter, the gathered timeline dequantizes
    before the dot. Computing scales from this call's K/V is an EXPLICIT
    prefill-caller opt-in: pass compute_dynamic_scales=True (and no
    scale tensors) and the op RETURNS them as a fifth element, a
    (kq, vq, kdq, vdq) tuple of [B, H] tensors for later chunk/decode
    calls to consume. dynamic_scale_valid [B] int32 (optional) limits
    the scale statistics to each sequence's leading N rows of THIS call
    — chunked prefill passes the unpadded length so the zero-pad tail
    cannot contaminate the scales.

    Returns (out [token_num, H*D], qkv, key_cache_out, value_cache_out
    [, scales]).
    """
    if qkv_out_scale is not None or out_scale != -1:
        raise NotImplementedError(
            "quantized activation path: use paddle_tpu.quantization")
    qkv_a = _arr(qkv)
    kc, vc = _arr(key_cache), _arr(value_cache)
    kq, vq, kdq, vdq = _cachekv_scales(
        kc, cache_k_quant_scales, cache_v_quant_scales,
        cache_k_dequant_scales, cache_v_dequant_scales,
        dynamic=use_dynamic_cachekv_quant,
        compute=compute_dynamic_scales)
    enc = _arr(seq_lens_encoder).reshape(-1).astype(jnp.int32)
    dec = _arr(seq_lens_decoder).reshape(-1).astype(jnp.int32)
    this = _arr(seq_lens_this_time).reshape(-1).astype(jnp.int32)
    cu_q = _arr(cu_seqlens_q).reshape(-1).astype(jnp.int32)
    bt = _arr(block_tables).astype(jnp.int32)
    bsz, blocks_per_seq = bt.shape
    nh, bs_, hd = kc.shape[1], kc.shape[2], kc.shape[3]
    token_num = qkv_a.shape[0]

    qkv3 = qkv_a.reshape(token_num, 3, nh, hd)
    if qkv_bias is not None:
        qkv3 = qkv3 + _arr(qkv_bias).reshape(1, 3, nh, hd)
    qt, kt, vt = qkv3[:, 0], qkv3[:, 1], qkv3[:, 2]    # [T, H, D]

    seq_of, local, pos = _token_timeline(cu_q, dec, token_num)
    if rope_emb is not None:
        # rope_emb [2, B, 1, S, D/...]: cos at [0], sin at [1]
        re = _arr(rope_emb)
        cos_t = re[0][seq_of, 0, pos]                          # [T, Dr]
        sin_t = re[1][seq_of, 0, pos]

        def _rope(u):
            if use_neox_style:
                d2 = u.shape[-1] // 2
                u1, u2 = u[..., :d2], u[..., d2:]
                c = cos_t[:, None, :d2]
                s = sin_t[:, None, :d2]
                return jnp.concatenate([u1 * c - u2 * s, u2 * c + u1 * s],
                                       axis=-1).astype(u.dtype)
            u1, u2 = u[..., 0::2], u[..., 1::2]
            c = cos_t[:, None, 0::2]
            s = sin_t[:, None, 0::2]
            return jnp.stack([u1 * c - u2 * s, u2 * c + u1 * s],
                             axis=-1).reshape(u.shape).astype(u.dtype)
        qt, kt = _rope(qt), _rope(kt)

    new_scales = None
    if compute_dynamic_scales:
        _dynamic_compute_allowed(enc, this)
        valid_mask = None
        if dynamic_scale_valid is not None:
            nv = _arr(dynamic_scale_valid).reshape(-1).astype(jnp.int32)
            valid_mask = local < nv[seq_of]
        new_scales = _dynamic_prefill_scales(kt, vt, seq_of, bsz,
                                             valid_mask)
        kq, vq, kdq, vdq = (new_scales["kq"], new_scales["vq"],
                            new_scales["kdq"], new_scales["vdq"])
    kc, vc = _scatter_paged(kc, vc, bt, seq_of, pos, kt, vt, bs_,
                            k_quant=kq, v_quant=vq)
    kv_len = jnp.where(enc > 0, enc, dec + this)               # [B]
    gk, gv, s_kv = _gather_paged(kc, vc, bt, nh, k_dequant=kdq,
                                 v_dequant=vdq, out_dtype=qt.dtype)

    # dense scores per token over its sequence's timeline
    scores = jnp.einsum("thd,tshd->ths", qt,
                        jnp.moveaxis(gk[seq_of], 1, 2)) / jnp.sqrt(
        jnp.asarray(hd, jnp.float32)).astype(qt.dtype)
    kv_pos = jnp.arange(s_kv)[None, None, :]
    causal_ok = kv_pos <= pos[:, None, None]
    in_len = kv_pos < kv_len[seq_of][:, None, None]
    scores = jnp.where(causal_ok & in_len, scores,
                       jnp.asarray(_NEG, scores.dtype))
    # caller-supplied additive masks: `mask` [B, 1, S_q, S_k] indexed by each
    # token's (sequence, local query row); `tgt_mask` [B, 1, 1, S_k] for the
    # decode step
    for m in (mask, tgt_mask):
        if m is None:
            continue
        m_a = _arr(m)
        rows = (m_a[seq_of, 0, jnp.minimum(local, m_a.shape[2] - 1)]
                .astype(scores.dtype))                       # [T, S_mask]
        s_m = min(rows.shape[-1], s_kv)
        scores = scores.at[:, :, :s_m].add(rows[:, None, :s_m])
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("ths,tshd->thd", probs.astype(qt.dtype),
                     jnp.moveaxis(gv[seq_of], 1, 2))
    result = (Tensor(out.reshape(token_num, nh * hd)), Tensor(qkv_a),
              Tensor(kc), Tensor(vc))
    if new_scales is not None:
        result += ((Tensor(new_scales["kq"]), Tensor(new_scales["vq"]),
                    Tensor(new_scales["kdq"]),
                    Tensor(new_scales["vdq"])),)
    return result


def _grouped_scores(qt, tk, tv, pos, kv_len):
    """Causal grouped-query attention of packed tokens qt [T, H, D], token t
    at row pos[t] of a timeline of which kv_len[t] rows count, against
    unexpanded K / V: one timeline a token, tk / tv [T, KV, S, D], or one
    for all of them, [KV, S, D]. Float32 throughout. Returns [T, KV, rep,
    D]."""
    token_num, nh, hd = qt.shape
    kvh, s_kv = tk.shape[-3], tk.shape[-2]
    timeline = "tgsd" if tk.ndim == 4 else "gsd"
    # q regrouped [T, KV, rep, D] against the timeline [T, KV, S, D]
    qg = qt.reshape(token_num, kvh, nh // kvh, hd).astype(jnp.float32)
    kv_pos = jnp.arange(s_kv)[None, None, None, :]
    ok = (kv_pos <= pos[:, None, None, None]) \
        & (kv_pos < kv_len[:, None, None, None])
    scores = jnp.einsum(f"tgrd,{timeline}->tgrs", qg,
                        tk.astype(jnp.float32)) * (1.0 / float(hd) ** 0.5)
    probs = jax.nn.softmax(jnp.where(ok, scores, _NEG), axis=-1)
    return jnp.einsum(f"tgrs,{timeline}->tgrd", probs,
                      tv.astype(jnp.float32))


def block_gqa_attention(q, k, v, key_cache, value_cache, seq_lens_encoder,
                        seq_lens_decoder, seq_lens_this_time, cu_seqlens_q,
                        block_tables, block_size=64, rope_cos=None,
                        rope_sin=None, cache_k_quant_scales=None,
                        cache_v_quant_scales=None,
                        cache_k_dequant_scales=None,
                        cache_v_dequant_scales=None,
                        use_dynamic_cachekv_quant=False,
                        compute_dynamic_scales=False,
                        dynamic_scale_valid=None):
    """Paged-KV attention with UNEXPANDED grouped-query heads (the GQA
    sibling of block_multihead_attention; reference analog:
    block_multihead_attention.py:19 serving Llama-family models, where
    the CUDA kernel reads kv heads grouped).

    q: [T, H, D]; k/v: [T, KV, D] — packed unpadded tokens, sequence
    boundaries in cu_seqlens_q. key_cache/value_cache:
    [n_pages, KV, block_size, D]. block_tables: [B, blocks_per_seq].
    Per sequence: prefill when seq_lens_encoder[i] > 0, decode (append at
    seq_lens_decoder[i]) when seq_lens_this_time[i] == 1.

    rope_cos/rope_sin: optional [S, D/2] tables — when given, q and k are
    rotated (interleaved-pair convention, fp32) at each token's timeline
    position BEFORE the cache write, so prefill and decode share one RoPE
    rule. The grouped einsums keep kv heads unexpanded: [T, KV, rep, D]
    against the gathered [T, KV, S_kv, D] timeline, which is both the
    memory win of GQA and an MXU-friendly batched matmul.

    Cache-KV int8: same scale contract as block_multihead_attention —
    static [KV] per-head scales, or dynamic [B, KV] per-sequence scales
    (use_dynamic_cachekv_quant=True). A prefill call opting in with
    compute_dynamic_scales=True (and no scale tensors) computes them
    and RETURNS them as a fourth element; dynamic_scale_valid [B]
    limits the statistics to each sequence's leading rows (chunked
    prefill's pad-tail guard).

    Returns (out [T, H*D], key_cache_out, value_cache_out [, scales]).
    """
    qt, kt, vt = _arr(q), _arr(k), _arr(v)
    kc, vc = _arr(key_cache), _arr(value_cache)
    kq, vq, kdq, vdq = _cachekv_scales(
        kc, cache_k_quant_scales, cache_v_quant_scales,
        cache_k_dequant_scales, cache_v_dequant_scales,
        dynamic=use_dynamic_cachekv_quant,
        compute=compute_dynamic_scales)
    enc = _arr(seq_lens_encoder).reshape(-1).astype(jnp.int32)
    dec = _arr(seq_lens_decoder).reshape(-1).astype(jnp.int32)
    this = _arr(seq_lens_this_time).reshape(-1).astype(jnp.int32)
    cu_q = _arr(cu_seqlens_q).reshape(-1).astype(jnp.int32)
    bt = _arr(block_tables).astype(jnp.int32)
    bsz, blocks_per_seq = bt.shape
    kvh, bs_, hd = kc.shape[1], kc.shape[2], kc.shape[3]
    token_num, nh, _ = qt.shape

    seq_of, local, pos = _token_timeline(cu_q, dec, token_num)

    # the scopes are metadata of the traced program: the trace's device
    # events are put down to rope, scatter, gather and scores by them
    if rope_cos is not None:
        with jax.named_scope("qkv_rope"):
            cos_t = _arr(rope_cos)[pos].astype(jnp.float32)    # [T, D/2]
            sin_t = _arr(rope_sin)[pos].astype(jnp.float32)
            qt, kt = _rope_pairs(qt, cos_t, sin_t), \
                _rope_pairs(kt, cos_t, sin_t)

    new_scales = None
    if compute_dynamic_scales:
        _dynamic_compute_allowed(enc, this)
        valid_mask = None
        if dynamic_scale_valid is not None:
            nv = _arr(dynamic_scale_valid).reshape(-1).astype(jnp.int32)
            valid_mask = local < nv[seq_of]
        new_scales = _dynamic_prefill_scales(kt, vt, seq_of, bsz,
                                             valid_mask)
        kq, vq, kdq, vdq = (new_scales["kq"], new_scales["vq"],
                            new_scales["kdq"], new_scales["vdq"])
    kv_len = jnp.where(enc > 0, enc, dec + this)
    with jax.named_scope("paged_attention"):
        if bsz == 1 and kq is None:
            # One sequence and no scales, so a float pool (_cachekv_scales
            # refuses an int8 pool without them), which is how the batcher
            # admits a prompt, whole or a chunk: the slot's pages are read
            # once, as the timeline to attend, and the run of rows is
            # written into them by the page.
            with jax.named_scope("kv_gather"):
                gk, gv, _ = _gather_paged(kc, vc, bt, kvh)
            with jax.named_scope("kv_scatter"):
                kc, tk = _write_page_run(kc, bt[0], gk[0], dec[0], kt)
                vc, tv = _write_page_run(vc, bt[0], gv[0], dec[0], vt)
        else:
            with jax.named_scope("kv_scatter"):
                kc, vc = _scatter_paged(kc, vc, bt, seq_of, pos, kt, vt, bs_,
                                        k_quant=kq, v_quant=vq)
            with jax.named_scope("kv_gather"):
                gk, gv, _ = _gather_paged(kc, vc, bt, kvh, k_dequant=kdq,
                                          v_dequant=vdq, out_dtype=qt.dtype)
            # One sequence: every token attends the same timeline. Indexing
            # it per token copies it T times when the op runs eagerly (10.7
            # GB for a 640-token prompt at 32 x 128 heads and a 2048-row
            # slot: the chip ran out of memory); XLA folds that gather away
            # only inside one jit program.
            tk, tv = (gk[0], gv[0]) if bsz == 1 else (gk[seq_of], gv[seq_of])
        with jax.named_scope("scores"):
            out = _grouped_scores(qt, tk, tv, pos, kv_len[seq_of])
    result = (Tensor(out.reshape(token_num, nh * hd).astype(qt.dtype)),
              Tensor(kc), Tensor(vc))
    if new_scales is not None:
        result += ((Tensor(new_scales["kq"]), Tensor(new_scales["vq"]),
                    Tensor(new_scales["kdq"]),
                    Tensor(new_scales["vdq"])),)
    return result


def decode_attention_path(pool_shape, pool_dtype, q_heads) -> str:
    """Which route ``block_gqa_decode_attention`` takes for a page pool:
    ``"kernel"`` (ops/pallas/paged_attention) on the chip over a pool that
    is not quantized and whose pages Mosaic takes as they lie, ``"gather"``
    (the gathered timelines of ``block_gqa_attention``) otherwise. Decided
    from what can be observed — the backend and the pool — like every kernel
    of ops/pallas."""
    from ....ops import pallas as _pl
    from ....ops.pallas.paged_attention import supported
    if _pl.on_tpu() and supported(pool_shape, pool_dtype, q_heads):
        return "kernel"
    return "gather"


def decode_kv_writer(pool_dtype) -> str:
    """How ``block_gqa_decode_attention`` writes the step's rows into a
    pool: ``"page"`` (``write_page_rows``) for a float pool, on every
    backend; ``"row"`` (``_scatter_paged``, inside the general op) for an
    int8 pool, whose rows are quantized on the way in."""
    return "page" if jnp.issubdtype(pool_dtype, jnp.floating) else "row"


def block_gqa_decode_attention(q, k, v, key_cache, value_cache,
                               seq_lens_decoder, block_tables,
                               rope_cos=None, rope_sin=None,
                               **cachekv_quant):
    """The decode step's paged GQA attention: every sequence contributes
    exactly one token, appended at ``seq_lens_decoder[i]``.

    q [B, H, D], k / v [B, KV, D]; pool, block table and RoPE tables as in
    ``block_gqa_attention``, whose decode case (``seq_lens_encoder == 0``,
    ``seq_lens_this_time == 1``) this computes. That every sequence adds
    one token is not a fact the general op can read off a traced
    ``seq_lens_this_time``, so the caller that knows it (the model's
    ``paged_decode_step``) says so by calling this entry: RoPE, the row
    written by the page (``write_page_rows``), then on the chip the Pallas
    kernel reading the pages a sequence holds in place, and the gathered
    timelines where ``decode_attention_path`` says ``"gather"``. With
    cache-quantization scales (``cachekv_quant``: the general op's
    keywords) or an int8 pool the general op runs, row scatter and all.
    Returns (out [B, H*D], key_cache_out, value_cache_out).
    """
    qt, kt, vt = _arr(q), _arr(k), _arr(v)
    kc, vc = _arr(key_cache), _arr(value_cache)
    bsz, nh, hd = qt.shape
    if cachekv_quant or decode_kv_writer(kc.dtype) == "row":
        ones = jnp.ones((bsz,), jnp.int32)
        return block_gqa_attention(
            q, k, v, key_cache, value_cache, jnp.zeros_like(ones),
            seq_lens_decoder, ones, jnp.arange(bsz + 1, dtype=jnp.int32),
            block_tables, rope_cos=rope_cos, rope_sin=rope_sin,
            **cachekv_quant)
    dec = _arr(seq_lens_decoder).reshape(-1).astype(jnp.int32)
    bt = _arr(block_tables).astype(jnp.int32)
    block = kc.shape[2]
    if rope_cos is not None:
        with jax.named_scope("qkv_rope"):
            cos_t = _arr(rope_cos)[dec].astype(jnp.float32)    # [B, D/2]
            sin_t = _arr(rope_sin)[dec].astype(jnp.float32)
            qt, kt = _rope_pairs(qt, cos_t, sin_t), \
                _rope_pairs(kt, cos_t, sin_t)
    with jax.named_scope("paged_attention"):
        with jax.named_scope("kv_scatter"):
            page = jnp.take_along_axis(bt, (dec // block)[:, None],
                                       axis=1)[:, 0]
            kc = write_page_rows(kc, page, dec % block, kt)
            vc = write_page_rows(vc, page, dec % block, vt)
        # this step's row is in the pool: dec + 1 rows count
        if decode_attention_path(kc.shape, kc.dtype, nh) == "kernel":
            from ....ops.pallas.paged_attention import paged_attention_decode
            with jax.named_scope("scores"):
                out = paged_attention_decode(qt, kc, vc, bt, dec + 1)
        else:
            with jax.named_scope("kv_gather"):
                gk, gv, _ = _gather_paged(kc, vc, bt, kc.shape[1])
            with jax.named_scope("scores"):
                out = _grouped_scores(qt, gk, gv, dec, dec + 1).astype(
                    qt.dtype)
    return Tensor(out.reshape(bsz, nh * hd)), Tensor(kc), Tensor(vc)


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0):
    """Variable-length attention with per-sequence lengths (ref
    variable_length_memory_efficient_attention.py:28; CUTLASS kernel on
    GPU — here one masked sdpa that XLA/Pallas fuses).

    query/key/value: [B, H, S, D]; seq_lens/kv_seq_lens: [B, 1].
    """
    q, k, v = _arr(query), _arr(key), _arr(value)
    ql = _arr(seq_lens).reshape(-1).astype(jnp.int32)
    kl = _arr(kv_seq_lens).reshape(-1).astype(jnp.int32)
    bsz, nh, sq, hd = q.shape
    sk = k.shape[2]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * jnp.asarray(
        scale, jnp.float32).astype(q.dtype)
    if mask is not None:
        scores = scores + _arr(mask).astype(scores.dtype)
    q_pos = jnp.arange(sq)[None, None, :, None]
    k_pos = jnp.arange(sk)[None, None, None, :]
    ok = (q_pos < ql[:, None, None, None]) & (k_pos < kl[:, None, None, None])
    if causal:
        ok = ok & (k_pos <= q_pos + pre_cache_length)
    scores = jnp.where(ok, scores, jnp.asarray(_NEG, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)
    # zero rows beyond each sequence's query length (reference zero-pads)
    out = jnp.where(q_pos < ql[:, None, None, None], out, 0.0)
    return Tensor(out.astype(q.dtype))


__all__ = ["masked_multihead_attention", "block_multihead_attention",
           "block_gqa_attention", "block_gqa_decode_attention",
           "decode_attention_path", "decode_kv_writer", "write_page_rows",
           "cachekv_scales_from_dense",
           "cachekv_scale_kwargs",
           "variable_length_memory_efficient_attention"]
