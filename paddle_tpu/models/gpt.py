"""GPT-2 model family (BASELINE.md config #2, GPT-2 124M compiled-path bench).

Reference fixture: test/auto_parallel/get_gpt_model.py and the fused
transformer tier (phi/kernels/fusion). TPU-first: pre-norm blocks, learned
positional embeddings, GELU MLP, attention through the fused SDPA path.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.common import Dropout, Embedding, Linear
from ..nn.norm import LayerNorm


# shared cachekv-int8 calibration helpers live beside the scale contract
# in incubate.nn.functional.decode_attention (model-agnostic)
from ..incubate.nn.functional.decode_attention import (  # noqa: E402
    cachekv_scale_kwargs as _cache_scale_kwargs,
    cachekv_scales_from_dense as _cachekv_scales_from)


@dataclass
class GPT2Config:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    layer_norm_epsilon: float = 1e-5
    dropout: float = 0.1
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def gpt2_124m_config(**overrides) -> GPT2Config:
    cfg = GPT2Config()
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


class GPT2Attention(Layer):
    def __init__(self, config: GPT2Config):
        super().__init__()
        init = I.Normal(std=config.initializer_range)
        self.c_attn = Linear(config.hidden_size, 3 * config.hidden_size,
                             weight_attr=init)
        self.c_proj = Linear(config.hidden_size, config.hidden_size,
                             weight_attr=init)
        self.config = config
        self.resid_dropout = Dropout(config.dropout)

    def forward(self, hidden, return_kv=False):
        b, s, _ = hidden.shape
        h, d = self.config.num_attention_heads, self.config.head_dim
        qkv = self.c_attn(hidden).reshape([b, s, 3, h, d])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=self.config.dropout if self.training else 0.0)
        out = self.c_proj(out.reshape([b, s, h * d]))
        out = self.resid_dropout(out)
        if return_kv:
            # cache layout [B, H, S, D] (masked_multihead_attention's)
            return out, k.transpose([0, 2, 1, 3]), v.transpose([0, 2, 1, 3])
        return out


class GPT2MLP(Layer):
    def __init__(self, config: GPT2Config):
        super().__init__()
        init = I.Normal(std=config.initializer_range)
        self.c_fc = Linear(config.hidden_size, config.intermediate_size,
                           weight_attr=init)
        self.c_proj = Linear(config.intermediate_size, config.hidden_size,
                             weight_attr=init)
        self.dropout = Dropout(config.dropout)

    def forward(self, x):
        return self.dropout(self.c_proj(F.gelu(self.c_fc(x), approximate=True)))


class GPT2Block(Layer):
    def __init__(self, config: GPT2Config):
        super().__init__()
        self.ln_1 = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        self.attn = GPT2Attention(config)
        self.ln_2 = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        self.mlp = GPT2MLP(config)

    def forward(self, hidden):
        hidden = hidden + self.attn(self.ln_1(hidden))
        return hidden + self.mlp(self.ln_2(hidden))

    def forward_kv(self, hidden):
        """Prefill: dense causal attention + this layer's K/V for the cache."""
        attn_out, k, v = self.attn(self.ln_1(hidden), return_kv=True)
        hidden = hidden + attn_out
        return hidden + self.mlp(self.ln_2(hidden)), k, v

    def decode(self, hidden, cache_kv, t):
        """One-token decode over the dense KV cache.

        hidden: [B, 1, E]; cache_kv: [2, B, H, S_max, D]; t: [B, 1] current
        lengths. The attention is masked_multihead_attention (reference
        masked_multihead_attention.py:19 / its fused CUDA kernel) — scatter
        this step's K/V at row t, attend over the prefix. Returns
        (hidden', new_cache).
        """
        from ..incubate.nn.functional.decode_attention import \
            masked_multihead_attention
        b = hidden.shape[0]
        x = self.ln_1(hidden)
        qkv = self.attn.c_attn(x.reshape([b, -1]))       # [B, 3*H*D]
        out, new_cache = masked_multihead_attention(
            qkv, cache_kv, sequence_lengths=t)
        attn_out = self.attn.resid_dropout(
            self.attn.c_proj(out.reshape([b, 1, -1])))
        hidden = hidden + attn_out
        return hidden + self.mlp(self.ln_2(hidden)), new_cache


class GPT2Model(Layer):
    def __init__(self, config: GPT2Config):
        super().__init__()
        self.config = config
        init = I.Normal(std=config.initializer_range)
        self.wte = Embedding(config.vocab_size, config.hidden_size,
                             weight_attr=init)
        self.wpe = Embedding(config.max_position_embeddings,
                             config.hidden_size, weight_attr=init)
        self.drop = Dropout(config.dropout)
        self.h = [GPT2Block(config) for _ in range(config.num_hidden_layers)]
        for i, blk in enumerate(self.h):
            self.add_sublayer(f"h.{i}", blk)
        self.ln_f = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids):
        from .. import ops
        _, s = input_ids.shape
        pos = ops.arange(0, s, dtype="int32").unsqueeze(0)
        hidden = self.wte(input_ids) + self.wpe(pos)
        hidden = self.drop(hidden)
        for blk in self.h:
            hidden = blk(hidden)
        return self.ln_f(hidden)

    def forward_prefill(self, input_ids, s_max):
        """Dense prompt pass that also fills the decode KV caches.

        Returns (hidden [B, S, E], caches [L, 2, B, H, s_max, D]).
        """
        import paddle_tpu as paddle
        from .. import ops
        b, s = input_ids.shape
        if s > s_max:
            raise ValueError(f"prompt length {s} exceeds cache size {s_max}")
        pos = ops.arange(0, s, dtype="int32").unsqueeze(0)
        hidden = self.drop(self.wte(input_ids) + self.wpe(pos))
        h, d = self.config.num_attention_heads, self.config.head_dim
        pad = (paddle.zeros([b, h, s_max - s, d],
                            dtype=self.config.dtype)
               if s < s_max else None)
        caches = []
        for blk in self.h:
            hidden, k, v = blk.forward_kv(hidden)
            if pad is not None:
                k = ops.concat([k, pad.astype(k.dtype)], axis=2)
                v = ops.concat([v, pad.astype(v.dtype)], axis=2)
            caches.append(ops.stack([k, v]))
        return self.ln_f(hidden), ops.stack(caches)


class GPT2ForCausalLM(Layer):
    def __init__(self, config: GPT2Config):
        super().__init__()
        self.config = config
        self.transformer = GPT2Model(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  weight_attr=I.Normal(
                                      std=config.initializer_range),
                                  bias_attr=False)

    def forward(self, input_ids, labels=None):
        hidden = self.transformer(input_ids)
        logits = self._logits(hidden)
        if labels is None:
            return logits
        loss = F.cross_entropy(
            logits.reshape([-1, self.config.vocab_size]).astype("float32"),
            labels.reshape([-1]))
        return logits, loss

    def _logits(self, hidden):
        if self.lm_head is None:
            from .. import ops
            return ops.matmul(hidden, self.transformer.wte.weight,
                              transpose_y=True)
        return self.lm_head(hidden)

    def prefill(self, input_ids, s_max, n_valid=None):
        """Prompt pass for incremental decode (the serving path).

        Returns (last_logits [B, 1, V], caches [L, 2, B, H, s_max, D],
        t [B, 1] int32 — the next write position).

        ``n_valid`` ([B, 1] int32) marks the true prompt length when
        ``input_ids`` is right-padded onto a bucket ladder: the last-token
        hidden state is gathered at position n_valid-1 (a dynamic gather,
        so ONE executable per bucket serves every prompt length) and decode
        resumes at t = n_valid, overwriting the pad rows of the cache
        before any step can attend them.
        """
        import paddle_tpu as paddle
        b, s = input_ids.shape
        hidden, caches = self.transformer.forward_prefill(input_ids, s_max)
        if n_valid is None:
            last = hidden[:, s - 1:s]
            t = paddle.full([b, 1], s, dtype="int32")
        else:
            from .. import ops
            idx = (n_valid - 1).astype("int32").reshape([b, 1, 1])
            last = ops.take_along_axis(hidden, idx, axis=1)
            t = n_valid.astype("int32")
        logits = self._logits(last)
        return logits, caches, t

    def decode_step(self, tok, caches, t):
        """One incremental token through every layer's KV cache.

        tok: [B, 1] int; caches: [L, 2, B, H, S_max, D]; t: [B, 1] int32.
        All shapes are static, so `jit.to_static(model.decode_step)`
        compiles ONE executable that serves every step — the XLA analog of
        the reference's fused decode kernels
        (masked_multihead_attention_kernel.cu). Returns
        (logits [B, 1, V], caches', t+1).
        """
        from .. import ops
        hidden = self.transformer.wte(tok) + self.transformer.wpe(t)
        hidden = self.transformer.drop(hidden)
        new_caches = []
        for i, blk in enumerate(self.transformer.h):
            hidden, nc = blk.decode(hidden, caches[i], t)
            new_caches.append(nc)
        hidden = self.transformer.ln_f(hidden)
        return self._logits(hidden), ops.stack(new_caches), t + 1

    # -- paged-KV serving route (vLLM-style block cache) --------------------

    def paged_alloc(self, n_pages, block_size=64, cache_dtype=None):
        """Allocate the physical KV page pool: per layer, (kc, vc) of
        [n_pages, H, block_size, D]. Pages are position-free storage —
        a block table maps (sequence, logical block) -> pool row, so the
        same pool serves many sequences of different lengths. After
        calibrate_cachekv_int8 the pools allocate int8; cache_dtype
        overrides explicitly (dynamic-quant callers)."""
        import paddle_tpu as paddle
        cfg = self.config
        h, d = cfg.num_attention_heads, cfg.head_dim
        dtype = cache_dtype or (
            "int8" if self._cachekv_scales is not None else cfg.dtype)
        return [(paddle.zeros([n_pages, h, block_size, d], dtype=dtype),
                 paddle.zeros([n_pages, h, block_size, d], dtype=dtype))
                for _ in range(cfg.num_hidden_layers)]

    _cachekv_scales = None

    def calibrate_cachekv_int8(self, sample_ids):
        """Static per-head int8 cache scales from a calibration batch
        (reference cache_k_quant_scales, static mode) — mirrors the Llama
        API; see _cachekv_scales_from. Pass None to disable."""
        if sample_ids is None:
            self._cachekv_scales = None
            return None
        import paddle_tpu as paddle
        b, s = sample_ids.shape
        with paddle.no_grad():
            _, caches, _ = self.prefill(sample_ids, s)
        self._cachekv_scales = _cachekv_scales_from(caches._data)
        return self._cachekv_scales

    def paged_prefill_into(self, input_ids, layers, block_tables,
                           block_size=64, dec_base=None, logits_at=None,
                           dynamic_cache_scales=False, cache_scales=None,
                           dynamic_scale_valid=None, logits_all=False):
        """Prompt pass writing KV into a CALLER-OWNED page pool.

        input_ids [B, s]; layers: ``paged_alloc`` pool; block_tables
        [B, blocks_per_seq] int32 rows naming each sequence's pages.
        Returns (last_logits [B, V], new_layers). This is the admission
        primitive continuous batchers use: the pool persists across
        requests, only the named pages are written.

        dec_base [B] int32 (optional): CHUNKED-prefill mode — this call
        appends s tokens after an existing prefix of dec_base rows
        (multi-token decode-mode append: pos = dec_base + local, causal
        within the chunk, attending the whole prefix). A fixed chunk
        width makes prompt processing reuse ONE executable for every
        prompt length instead of compiling per length.

        Dynamic cachekv-int8 x chunked composition (reference analog:
        block_multihead_attention takes cache quant scales AND chunked
        input in one op): dynamic_cache_scales=True computes per-
        (sequence, head) scales from this call (the FIRST chunk /
        unchunked prompt; dynamic_scale_valid [B] masks a pad tail out
        of the statistics) and returns them third; cache_scales (the
        per-layer scale dicts a first chunk returned) makes LATER chunks
        quantize with those same scales, so the whole chunk loop is
        bit-consistent with a single-call prefill given the same scales.
        """
        import paddle_tpu as paddle
        from ..incubate.nn.functional.decode_attention import \
            block_multihead_attention

        if dynamic_cache_scales and cache_scales is not None:
            raise ValueError("dynamic_cache_scales computes scales; "
                             "cache_scales consumes them — pass one")
        b, s = input_ids.shape
        bt = block_tables
        if dec_base is None:
            enc = paddle.to_tensor(np.full((b,), s, np.int32))
            dec = paddle.to_tensor(np.zeros((b,), np.int32))
            pos_row = paddle.to_tensor(
                np.tile(np.arange(s, dtype=np.int32), (b, 1)))
        else:
            enc = paddle.to_tensor(np.zeros((b,), np.int32))
            dec = dec_base
            pos_row = dec_base.reshape([b, 1]) + paddle.to_tensor(
                np.arange(s, dtype=np.int32)).reshape([1, s])
            # chunked pad rows can run past the position table when slot
            # capacity (blocks_per_seq*block_size) exceeds
            # max_position_embeddings; clamp EXPLICITLY — pad rows are
            # masked/overwritten before any bounded read, but the safety
            # must not hang on jnp's silent gather clamping (ADVICE r3)
            pos_row = paddle.clip(
                pos_row, 0, self.config.max_position_embeddings - 1)
        cu_q = paddle.to_tensor(np.arange(b + 1, dtype=np.int32) * s)

        # packed-token forward: hidden is [T, E] (sequences concatenated)
        ids_flat = input_ids.reshape([b * s])
        pos_flat = pos_row.reshape([b * s])
        hidden = self.transformer.wte(ids_flat) + self.transformer.wpe(
            pos_flat)
        hidden = self.transformer.drop(hidden)
        this = paddle.to_tensor(np.full((b,), s, np.int32))
        layers_state = []
        scales_out = [] if dynamic_cache_scales else None
        for li, (blk, (kc, vc)) in enumerate(zip(self.transformer.h,
                                                 layers)):
            x = blk.ln_1(hidden)
            qkv = blk.attn.c_attn(x)                     # [T, 3*H*D]
            if dynamic_cache_scales:
                extra = dict(use_dynamic_cachekv_quant=True,
                             compute_dynamic_scales=True,
                             dynamic_scale_valid=dynamic_scale_valid)
            else:
                extra = _cache_scale_kwargs(
                    cache_scales if cache_scales is not None
                    else self._cachekv_scales, li)
            res = block_multihead_attention(
                qkv, kc, vc, enc, dec, this, None, None, cu_q, cu_q,
                bt, block_size=block_size, **extra)
            if dynamic_cache_scales:
                out, _, kc, vc, (kq, vq, kdq, vdq) = res
                scales_out.append({"kq": kq, "vq": vq,
                                   "kdq": kdq, "vdq": vdq})
            else:
                out, _, kc, vc = res
            hidden = hidden + blk.attn.resid_dropout(blk.attn.c_proj(out))
            hidden = hidden + blk.mlp(blk.ln_2(hidden))
            layers_state.append((kc, vc))
        hidden = self.transformer.ln_f(hidden)
        h3 = hidden.reshape([b, s, -1])
        if logits_all:
            # speculative verify: the target scores EVERY appended
            # position in one pass (s = draft_k + 1, so the full lm
            # head over s positions is the point, not a waste)
            logits = self._logits(h3)                    # [b, s, V]
        elif logits_at is not None:
            # chunked prefill: project ONLY the requested position (the
            # lm head over all C positions would be C x the needed FLOPs)
            oh = F.one_hot(logits_at.reshape([b]).astype("int64"),
                           s).astype(h3.dtype)
            logits = self._logits(paddle.einsum("bs,bse->be", oh, h3))
        else:
            logits = self._logits(h3[:, s - 1])
        if dynamic_cache_scales:
            return logits, layers_state, scales_out
        return logits, layers_state

    @staticmethod
    def _paged_state(layers_state, bt, b, s, block_size, blocks_per_seq):
        """The SHARED paged-decode state convention (GPT-2 and Llama build
        identical dicts, so one batcher / one compiled-step recipe serves
        both families)."""
        import paddle_tpu as paddle
        return {"layers": layers_state, "block_tables": bt,
                "dec_lens": paddle.to_tensor(np.full((b,), s, np.int32)),
                "block_size": block_size,
                "capacity": blocks_per_seq * block_size,
                # per-step constants (batch-size-only): built once, not on
                # the hot decode path
                "zeros_b": paddle.to_tensor(np.zeros((b,), np.int32)),
                "ones_b": paddle.to_tensor(np.ones((b,), np.int32)),
                "cu_b": paddle.to_tensor(np.arange(b + 1, dtype=np.int32))}

    @staticmethod
    def _paged_prefill_impl(model, input_ids, block_size, blocks_per_seq):
        """Shared fresh-pool prefill: allocate pages, identity block table,
        run the model's pool-writing prefill, wrap the state dict."""
        import paddle_tpu as paddle
        cfg = model.config
        b, s = input_ids.shape
        if blocks_per_seq is None:
            blocks_per_seq = (cfg.max_position_embeddings + block_size - 1) \
                // block_size
        n_blocks = b * blocks_per_seq
        bt = paddle.to_tensor(
            np.arange(n_blocks, dtype=np.int32).reshape(b, blocks_per_seq))
        layers = model.paged_alloc(n_blocks, block_size)
        logits, layers_state = model.paged_prefill_into(
            input_ids, layers, bt, block_size)
        return logits, GPT2ForCausalLM._paged_state(
            layers_state, bt, b, s, block_size, blocks_per_seq)

    @staticmethod
    def _paged_generate_loop(model, input_ids, max_new_tokens, block_size,
                             blocks_per_seq, decode_fn):
        """Shared greedy paged-decode driver (capacity validation + the
        prefill/step loop), parameterized the way _generate_loop and
        _beam_loop are."""
        from .. import ops
        b, s = input_ids.shape
        needed = s + max_new_tokens
        if needed > model.config.max_position_embeddings:
            # silent-clip hazard: position tables and the block table would
            # both clip-index and corrupt live pages
            raise ValueError(
                f"prompt {s} + {max_new_tokens} new tokens exceeds "
                f"max_position_embeddings="
                f"{model.config.max_position_embeddings}")
        if blocks_per_seq is None:
            # size the page pool to the actual timeline, not the model max
            blocks_per_seq = (needed + block_size - 1) // block_size
        elif needed > blocks_per_seq * block_size:
            raise ValueError(
                f"paged cache capacity {blocks_per_seq * block_size} too "
                f"small for prompt {s} + {max_new_tokens} new tokens")
        logits, state = model.paged_prefill(input_ids, block_size,
                                            blocks_per_seq)
        step = decode_fn if decode_fn is not None else model.paged_decode_step
        toks = [input_ids]
        tok = ops.argmax(logits, axis=-1).reshape([b])
        for i in range(max_new_tokens):
            toks.append(tok.reshape([b, 1]))
            if i + 1 == max_new_tokens:
                break
            logits, state = step(tok.astype(input_ids.dtype), state)
            tok = ops.argmax(logits, axis=-1).reshape([b])
        return ops.concat([x.astype("int64") for x in toks], axis=1)

    @staticmethod
    def _speculative_loop(target, draft, input_ids, max_new_tokens,
                          draft_k, block_size, eos_id, compile,
                          return_stats):
        """Greedy speculative decoding over the paged cache (beyond the
        reference, which has no in-tree speculative decoding; the serving
        analog is the draft/verify split in modern engines).

        The cheap DRAFT model proposes ``draft_k`` tokens autoregressively;
        the TARGET scores all proposals in ONE forward (paged_prefill_into
        with logits_all=True) and accepts the longest prefix matching its
        own greedy choices, plus its correction token — so each target
        dispatch yields 1..draft_k+1 tokens, and the output is EXACTLY the
        target's greedy sequence. Rollback after a rejection is free by
        construction: the host owns ``dec_lens``, bounded attention never
        reads rows past it, and stale rows are overwritten on the next
        append. Works across families (any draft/target pair sharing a
        vocab — both implement the shared paged-state convention)."""
        import paddle_tpu as paddle
        from .. import ops

        if input_ids.shape[0] != 1:
            raise ValueError("speculative decoding is single-sequence "
                             "(batch it at the serving layer)")
        if draft_k < 1:
            raise ValueError("draft_k must be >= 1")
        if draft.config.vocab_size != target.config.vocab_size:
            raise ValueError(
                f"draft vocab {draft.config.vocab_size} != target vocab "
                f"{target.config.vocab_size}")
        _, L = input_ids.shape
        if max_new_tokens <= 0:
            # generate(ids, 0) returns the prompt unchanged — match it
            out = paddle.to_tensor(
                np.asarray(input_ids._data).astype(np.int64))
            if not return_stats:
                return out
            return out, {"rounds": 0, "proposed": 0, "matched": 0,
                         "acceptance_rate": 0.0,
                         "tokens_per_target_dispatch": 0.0}
        needed = L + max_new_tokens
        for m, who in ((target, "target"), (draft, "draft")):
            if needed > m.config.max_position_embeddings:
                raise ValueError(
                    f"prompt {L} + {max_new_tokens} exceeds the {who}'s "
                    f"max_position_embeddings="
                    f"{m.config.max_position_embeddings}")
        bps = (needed + block_size - 1) // block_size

        with paddle.no_grad():
            t_logits, t_state = target.paged_prefill(input_ids, block_size,
                                                     bps)
            d_logits, d_state = draft.paged_prefill(input_ids, block_size,
                                                    bps)
        def _verify_body(ids, layers, bt, dec):
            return target.paged_prefill_into(
                ids, layers, bt, block_size, dec_base=dec,
                logits_all=True)

        def _catchup_body(ids, layers, bt, dec, at):
            # variable-length draft append (1 token after a rejection, 2
            # after a fully-accepted round — see d_rows below); returns
            # the LAST position's logits, i.e. the first proposal
            return draft.paged_prefill_into(
                ids, layers, bt, block_size, dec_base=dec, logits_at=at)

        if compile:
            from .. import jit
            t_step = jit.to_static(target.paged_decode_step,
                                   donate_args=(1,))
            d_step = jit.to_static(draft.paged_decode_step,
                                   donate_args=(1,))
            verify = jit.to_static(_verify_body, donate_args=(1,))
            catchup = jit.to_static(_catchup_body, donate_args=(1,))
        else:
            t_step, d_step = target.paged_decode_step, draft.paged_decode_step
            verify, catchup = _verify_body, _catchup_body

        # invariants: the TARGET cache holds rows for prompt +
        # accepted[:-1] (``accepted[-1]`` is pending, the next input);
        # the DRAFT cache holds correct rows for the first ``d_rows``
        # positions of prompt + accepted — after a fully-accepted round
        # it runs one short (the last proposal was never fed back), so
        # each round starts by appending accepted[d_rows - L:] to the
        # draft (1 token after a rejection, 2 after a full accept),
        # whose last-position logits ARE the first proposal.
        accepted = [int(np.asarray(t_logits._data)[0].argmax())]
        d_rows = L
        rounds = proposed = matched = 0
        with paddle.no_grad():
            while True:
                if eos_id is not None and eos_id in accepted:
                    accepted = accepted[:accepted.index(eos_id) + 1]
                    break
                remaining = max_new_tokens - len(accepted)
                if remaining <= 0:
                    break
                valid = L + len(accepted) - 1
                k = min(draft_k, remaining - 1)
                if k == 0:
                    # budget for exactly one more: plain target step
                    t_state["dec_lens"] = paddle.to_tensor(
                        np.array([valid], np.int32))
                    lg, t_state = t_step(paddle.to_tensor(
                        np.array([accepted[-1]], np.int64)), t_state)
                    accepted.append(int(np.asarray(lg._data)[0].argmax()))
                    continue
                # draft catch-up append ending at pending -> proposal 1
                cu = accepted[d_rows - L:]
                dl, d_state["layers"] = catchup(
                    paddle.to_tensor(np.array([cu], np.int64)),
                    d_state["layers"], d_state["block_tables"],
                    paddle.to_tensor(np.array([d_rows], np.int32)),
                    paddle.to_tensor(np.array([len(cu) - 1], np.int32)))
                d_rows += len(cu)
                tok = int(np.asarray(dl._data)[0].argmax())
                props = [tok]
                # k-1 single draft steps propose the rest
                d_state["dec_lens"] = paddle.to_tensor(
                    np.array([d_rows], np.int32))
                for _ in range(k - 1):
                    dl, d_state = d_step(paddle.to_tensor(
                        np.array([tok], np.int64)), d_state)
                    tok = int(np.asarray(dl._data)[0].argmax())
                    props.append(tok)
                d_rows += k - 1              # rows for props[:k-1] inputs
                # target scores pending + all k proposals in one pass
                ids_v = paddle.to_tensor(
                    np.array([[accepted[-1]] + props], np.int64))
                vlogits, t_state["layers"] = verify(
                    ids_v, t_state["layers"], t_state["block_tables"],
                    paddle.to_tensor(np.array([valid], np.int32)))
                g = np.asarray(vlogits._data)[0].argmax(-1)   # [k+1]
                j = 0
                while j < k and props[j] == int(g[j]):
                    j += 1
                accepted += props[:j] + [int(g[j])]
                rounds += 1
                proposed += k
                matched += j
                # draft rows correct through prompt + accepted[:-1] at
                # most (rejected proposals' rows are stale); a full
                # accept leaves it one short of even that
                d_rows = min(d_rows, L + len(accepted) - 1)
        if eos_id is not None and eos_id in accepted:
            accepted = accepted[:accepted.index(eos_id) + 1]
        out = paddle.to_tensor(np.concatenate(
            [np.asarray(input_ids._data).reshape(-1),
             np.asarray(accepted, np.int64)])[None])
        if not return_stats:
            return out
        return out, {
            "rounds": rounds, "proposed": proposed, "matched": matched,
            "acceptance_rate": matched / max(proposed, 1),
            "tokens_per_target_dispatch":
                len(accepted) / max(rounds, 1) if rounds else 1.0,
        }

    def generate_paged_speculative(self, input_ids, max_new_tokens,
                                   draft_model, draft_k=4, block_size=64,
                                   eos_id=None, compile=True,
                                   return_stats=False):
        """Greedy speculative decoding: ``draft_model`` proposes
        ``draft_k`` tokens per round, this model verifies them in one
        forward — token-exact vs ``generate``/``generate_paged`` while
        spending 1 target dispatch per 1..draft_k+1 accepted tokens (the
        dispatch-latency lever). See _speculative_loop."""
        return self._speculative_loop(self, draft_model, input_ids,
                                      max_new_tokens, draft_k, block_size,
                                      eos_id, compile, return_stats)

    def paged_prefill(self, input_ids, block_size=64, blocks_per_seq=None):
        """Prompt pass through the paged block cache
        (block_multihead_attention, reference
        incubate/nn/functional/block_multihead_attention.py:19).

        Returns (last_logits [B, V], state dict). The cache is a pool of
        physical [block_size] pages per layer; block_tables maps each
        sequence's logical block index to its page — decode appends into
        pages instead of one dense [B, S_max] strip, so cache memory
        scales with actual lengths and pages are shareable/evictable.
        """
        return self._paged_prefill_impl(self, input_ids, block_size,
                                        blocks_per_seq)

    def paged_decode_step(self, tok, state):
        """One token per sequence through the paged cache (decode mode:
        seq_lens_this_time == 1, append at dec_lens). tok: [B]."""
        import paddle_tpu as paddle
        from ..incubate.nn.functional.decode_attention import \
            block_multihead_attention

        cfg = self.config
        b = tok.shape[0]
        t = state["dec_lens"]
        bt = state["block_tables"]
        enc, this, cu_q = state["zeros_b"], state["ones_b"], state["cu_b"]
        hidden = self.transformer.wte(tok) + self.transformer.wpe(t)
        hidden = self.transformer.drop(hidden)
        dyn = state.get("cache_scales")
        new_layers = []
        for li, (blk, (kc, vc)) in enumerate(zip(self.transformer.h,
                                                 state["layers"])):
            x = blk.ln_1(hidden)
            qkv = blk.attn.c_attn(x)                     # [B, 3*H*D]
            if dyn is not None:
                # per-(slot, head) scales ride the state (dynamic int8)
                kwargs = dict(_cache_scale_kwargs(dyn, li),
                              use_dynamic_cachekv_quant=True)
            else:
                kwargs = _cache_scale_kwargs(self._cachekv_scales, li)
            out, _, kc, vc = block_multihead_attention(
                qkv, kc, vc, enc, t, this, None, None, cu_q, cu_q, bt,
                block_size=state["block_size"], **kwargs)
            hidden = hidden + blk.attn.resid_dropout(blk.attn.c_proj(out))
            hidden = hidden + blk.mlp(blk.ln_2(hidden))
            new_layers.append((kc, vc))
        hidden = self.transformer.ln_f(hidden)
        logits = self._logits(hidden)
        new_state = dict(state, layers=new_layers, dec_lens=t + 1)
        return logits, new_state

    def generate_paged(self, input_ids, max_new_tokens, block_size=64,
                       blocks_per_seq=None, decode_fn=None):
        """Greedy decode over the paged block cache (the serving route the
        reference exposes as block_multihead_attention + AnalysisPredictor;
        here the cache pages live in HBM and XLA compiles the step).

        decode_fn: optionally ``jit.to_static(model.paged_decode_step)`` —
        the state pytree has static shapes, so one executable serves every
        step here too."""
        return self._paged_generate_loop(self, input_ids, max_new_tokens,
                                         block_size, blocks_per_seq,
                                         decode_fn)

    @staticmethod
    def _select_token(logits_np, do_sample, temperature, top_k, top_p, rng):
        """Next-token selection on host logits [B, V] (reference surface:
        generation_utils' TopKProcess/TopPProcess + sampling).

        Greedy unless do_sample; sampling applies temperature, then top-k
        truncation, then nucleus (top-p) truncation, then draws from the
        renormalized distribution."""
        if not do_sample:
            return logits_np.argmax(-1)
        logits = logits_np.astype(np.float64) / max(temperature, 1e-6)
        out = np.empty(logits.shape[0], np.int64)
        for b in range(logits.shape[0]):
            row = logits[b]
            if top_k and 0 < top_k < row.shape[-1]:
                kth = np.partition(row, -top_k)[-top_k]
                row = np.where(row < kth, -np.inf, row)
            probs = np.exp(row - row.max())
            probs /= probs.sum()
            if top_p is not None and 0 < top_p < 1.0:
                order = np.argsort(-probs)
                csum = np.cumsum(probs[order])
                # keep the smallest prefix reaching top_p (always >= 1)
                cutoff = int(np.searchsorted(csum, top_p) + 1)
                keep = order[:cutoff]
                mask = np.zeros_like(probs, bool)
                mask[keep] = True
                probs = np.where(mask, probs, 0.0)
                probs /= probs.sum()
            out[b] = rng.choice(probs.shape[-1], p=probs)
        return out

    @staticmethod
    def _generate_loop(prefill_fn, step_fn, input_ids, max_new_tokens,
                       do_sample, temperature, top_k, top_p, seed,
                       eos_id=None, pad_id=None):
        """Shared incremental-decode driver (GPT-2 and Llama): prefill,
        then step/pick until the budget, with greedy selection staying on
        device and sampling reading logits to host.

        eos_id: per-row early stop (reference generation_utils'
        eos_token_id semantics) — once a row emits EOS, its later
        positions emit ``pad_id`` (default: eos_id) and the loop exits
        as soon as EVERY row has finished. The finished test is the one
        host sync per step; greedy decoding without eos_id stays fully
        on device.

        NOTE on the hot path: each step's returned caches are fresh
        buffers (functional update); true in-place reuse needs donation
        support in StaticFunction — tracked for the serving tier."""
        import paddle_tpu as paddle
        from .. import ops
        b = input_ids.shape[0]
        rng = np.random.RandomState(seed)
        if pad_id is None:
            pad_id = eos_id
        done = np.zeros((b,), bool)

        def pick(lg):
            if not do_sample:
                # greedy stays ON DEVICE: no host round trip per step
                return ops.argmax(lg[:, -1], axis=-1).reshape([b, 1])
            sel = GPT2ForCausalLM._select_token(
                np.asarray(lg._data)[:, -1], True, temperature, top_k,
                top_p, rng)
            return paddle.to_tensor(sel.reshape(b, 1))

        def apply_eos(tok):
            """Mask finished rows to pad and fold this step's EOS hits
            into `done` (host-side: the mask drives python control flow)."""
            tok_np = np.asarray(tok._data).reshape(b)
            out = np.where(done, pad_id, tok_np)
            done[:] = done | (out == eos_id)
            return paddle.to_tensor(out.reshape(b, 1))

        logits, caches, t = prefill_fn()
        toks = [input_ids]
        tok = pick(logits)
        if eos_id is not None:
            tok = apply_eos(tok)
        for i in range(max_new_tokens):
            toks.append(tok)
            if i + 1 == max_new_tokens or (eos_id is not None
                                           and bool(done.all())):
                break
            logits, caches, t = step_fn(tok.astype(input_ids.dtype),
                                        caches, t)
            tok = pick(logits)
            if eos_id is not None:
                tok = apply_eos(tok)
        out = ops.concat([x.astype("int64") for x in toks], axis=1)
        if eos_id is not None and len(toks) - 1 < max_new_tokens:
            # every row finished early: right-pad to the requested length
            # so the output shape stays [B, S + max_new_tokens]
            short = max_new_tokens - (len(toks) - 1)
            pad = paddle.to_tensor(
                np.full((b, short), pad_id, np.int64))
            out = ops.concat([out, pad], axis=1)
        return out

    @staticmethod
    def _resolve_s_max(config, s, max_new_tokens, s_max):
        """Default + validate the cache size (shared by every generate
        flavor in both model families): positions past the embedding
        table would CLIP silently (jnp.take), so reject loudly."""
        if s_max is None:
            s_max = min(config.max_position_embeddings, s + max_new_tokens)
        if s_max > config.max_position_embeddings:
            raise ValueError(
                f"s_max={s_max} exceeds max_position_embeddings="
                f"{config.max_position_embeddings}")
        if s + max_new_tokens > s_max:
            raise ValueError(f"s_max={s_max} too small for prompt {s} + "
                             f"{max_new_tokens} new tokens")
        return s_max

    @staticmethod
    def _beam_loop(prefill_fn, step_fn, input_ids, max_new_tokens,
                   num_beams, length_penalty):
        """Shared beam-search driver over the KV cache.

        Beams ride the batch dimension: inputs expand to B*W rows, the
        per-beam caches reorder by index_select along the cache's batch
        axis at every step (the KV-cache beam shuffle the reference's
        beam_search_decode does), and ONE decode executable at batch B*W
        serves every step. No EOS handling — fixed-length beams; the best
        beam per batch wins by summed log-prob / len**length_penalty.
        """
        import paddle_tpu as paddle
        from .. import ops
        b, s = input_ids.shape
        w = num_beams
        ids_np = np.asarray(input_ids._data)
        # prefill ONCE at batch B, then fan the caches out to B*W rows —
        # the W beams of a batch share the prompt's KV exactly
        logits, caches, t = prefill_fn(input_ids)

        def logprobs(lg):
            x = np.asarray(lg._data)[:, -1].astype(np.float64)
            x = x - x.max(-1, keepdims=True)
            return x - np.log(np.exp(x).sum(-1, keepdims=True))

        v = logits.shape[-1]
        if w > v:
            raise ValueError(f"num_beams={w} exceeds vocab_size={v}: the "
                             f"seed step cannot pick {w} distinct tokens")
        rep = paddle.to_tensor(np.repeat(np.arange(b, dtype=np.int64), w))
        caches = ops.index_select(caches, rep, axis=2)
        t = ops.index_select(t, rep, axis=0)
        # seed: the W beams of each batch start DISTINCT (top-W tokens of
        # the prompt's next-token distribution)
        lp0 = logprobs(logits)                            # [B, V]
        top0 = np.argsort(-lp0, axis=-1)[:, :w]           # [B, W]
        beam_scores = np.take_along_axis(lp0, top0, -1)   # [B, W]
        beam_tokens = [top0.reshape(b * w, 1)]            # list of [BW, 1]
        tok = paddle.to_tensor(beam_tokens[0])
        for i in range(1, max_new_tokens):
            logits, caches, t = step_fn(
                tok.astype(input_ids.dtype), caches, t)
            lp = logprobs(logits).reshape(b, w, v)        # [B, W, V]
            total = beam_scores[..., None] + lp           # [B, W, V]
            flat = total.reshape(b, w * v)
            best = np.argsort(-flat, axis=-1)[:, :w]      # [B, W]
            src_beam = best // v                          # [B, W]
            token = best % v                              # [B, W]
            beam_scores = np.take_along_axis(flat, best, -1)
            # reorder every beam-carrying structure by the source beams
            gather = (np.arange(b)[:, None] * w + src_beam).reshape(-1)
            gidx = paddle.to_tensor(gather.astype(np.int64))
            caches = ops.index_select(caches, gidx, axis=2)
            t = ops.index_select(t, gidx, axis=0)
            beam_tokens = [tk[gather] for tk in beam_tokens]
            beam_tokens.append(token.reshape(b * w, 1))
            tok = paddle.to_tensor(beam_tokens[-1])
        # best beam per batch (length fixed, penalty kept for API parity)
        denom = max_new_tokens ** length_penalty if length_penalty else 1.0
        best_beam = (beam_scores / denom).argmax(-1)      # [B]
        rows = np.arange(b) * w + best_beam
        gen = np.concatenate([tk[rows] for tk in beam_tokens], axis=1)
        return paddle.to_tensor(
            np.concatenate([ids_np.astype(np.int64), gen], axis=1))

    def generate_beam(self, input_ids, max_new_tokens, num_beams=4,
                      s_max=None, decode_fn=None, length_penalty=0.0):
        """Beam search over the KV cache (reference generation's
        beam_search mode). Returns the best beam per batch,
        [B, S + max_new_tokens]."""
        _, s = input_ids.shape
        s_max = self._resolve_s_max(self.config, s, max_new_tokens, s_max)
        step = decode_fn if decode_fn is not None else self.decode_step
        return self._beam_loop(lambda ids: self.prefill(ids, s_max), step,
                               input_ids, max_new_tokens, num_beams,
                               length_penalty)

    def generate(self, input_ids, max_new_tokens, s_max=None,
                 decode_fn=None, do_sample=False, temperature=1.0,
                 top_k=0, top_p=None, seed=None, eos_id=None, pad_id=None):
        """Incremental decode over the KV cache — greedy by default;
        ``do_sample=True`` draws with temperature / top-k / top-p
        (nucleus) truncation, seeded via ``seed`` for reproducibility.
        ``eos_id`` stops each row at its end-of-sequence token (later
        positions emit ``pad_id``, default eos_id) and ends the loop
        early once every row is done; output shape stays
        [B, S + max_new_tokens].

        decode_fn: optionally a compiled decode step (e.g.
        ``jit.to_static(model.decode_step)``) so every token reuses one
        executable; defaults to the eager step. Returns [B, S + new] ids.
        """
        import paddle_tpu as paddle
        from .. import ops
        _, s = input_ids.shape
        s_max = self._resolve_s_max(self.config, s, max_new_tokens, s_max)
        step = decode_fn if decode_fn is not None else self.decode_step
        return self._generate_loop(
            lambda: self.prefill(input_ids, s_max), step, input_ids,
            max_new_tokens, do_sample, temperature, top_k, top_p, seed,
            eos_id=eos_id, pad_id=pad_id)

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())
