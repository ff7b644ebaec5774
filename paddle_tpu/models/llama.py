"""Llama model family (flagship; BASELINE.md config #3, Llama-2-7B).

The reference ships Llama through PaddleNLP on top of the fused-op tier
(fused rope: python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py,
flash attention: paddle/phi/kernels/gpu/flash_attn_kernel.cu:128, rmsnorm in
fusion kernels). This is a TPU-first redesign, not a port:

- static shapes end to end, single fused attention contraction (XLA fuses
  the softmax chain; Pallas flash kernel swaps in on TPU),
- GQA (n_kv_heads < n_heads) expressed as an einsum over grouped heads so the
  MXU sees large batched matmuls,
- RoPE applied as a cheap elementwise rotation fused by XLA into the
  projection matmuls,
- optional tensor parallelism via the mp sharded layers (GSPMD inserts the
  Megatron collectives over ICI).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as dtype_mod
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.common import Embedding, Linear
from ..nn.norm import RMSNorm


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "float32"
    # context parallelism: when set, attention runs as a ring over this mesh
    # axis (sequence sharded; exact global attention via ICI ppermute)
    sep_mesh: Optional[object] = None
    sep_axis: str = "sep"
    # sep_impl: "ring" (ppermute K/V rotation, any head count),
    # "ulysses" (all-to-all heads<->sequence — needs heads divisible by
    # the sep axis; one dense full-seq contraction per head subset), or
    # "auto" (ulysses when its shape contract holds, else ring —
    # ops.ulysses_attention.choose_sep_impl)
    sep_impl: str = "ring"
    # activation recompute: re-run each decoder layer's forward in the
    # backward instead of keeping its residuals (fleet/recompute analog —
    # trades ~30% step FLOPs for O(layers) less activation HBM)
    use_recompute: bool = False
    # recompute_granularity (reference knob on its recompute configs):
    #   "full"      — save only layer inputs, recompute everything
    #   "selective" — jax.checkpoint_policies.dots_with_no_batch_dims_
    #                 saveable: matmul outputs stay resident, only the
    #                 cheap elementwise/softmax work replays (the TPU
    #                 analog of the reference's core_attn tier: most of
    #                 the memory win at a fraction of the recompute FLOPs)
    recompute_granularity: str = "full"
    # scan_layers: run the decoder stack as ONE lax.scan over stacked
    # [L, ...] weights — the layer body is traced/compiled once, so XLA
    # compile time is O(1) in depth instead of O(L). The canonical TPU
    # pattern for deep stacks; numerics identical to the unrolled loop.
    scan_layers: bool = False
    # Mixture-of-experts MLP (Mixtral-style): num_experts > 1 replaces each
    # layer's SwiGLU with a routed expert bank (gshard top-k gate, stacked
    # expert weights, optional expert parallelism over ep_mesh/ep_axis —
    # GSPMD inserts the dispatch/combine collectives). The gate's
    # load-balancing aux loss is added to the LM loss with moe_aux_coeff.
    num_experts: int = 1
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_coeff: float = 0.01
    ep_mesh: Optional[object] = None
    ep_axis: str = "ep"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def llama2_7b_config(**overrides) -> LlamaConfig:
    cfg = LlamaConfig()
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def llama_tiny_config(**overrides) -> LlamaConfig:
    """Test-scale config (the reference's tiny GPT fixture analog,
    test/auto_parallel/get_gpt_model.py)."""
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=176,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _rope_cos_sin(seq_len: int, head_dim: int, theta: float, dtype):
    """Precompute RoPE tables: [seq, head_dim//2]."""
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2,
                                          dtype=np.float32) / head_dim))
    t = np.arange(seq_len, dtype=np.float32)
    freqs = np.outer(t, inv_freq)  # [seq, hd/2]
    return (jnp.asarray(np.cos(freqs), dtype=dtype),
            jnp.asarray(np.sin(freqs), dtype=dtype))


def apply_rotary_pos_emb(x, cos, sin):
    """Rotate [B, S, H, D] by the (cos, sin) tables ([S, D/2]).

    Interleaved-pair convention (fused_rotary_position_embedding analog):
    even/odd feature pairs are rotated in fp32 then cast back — elementwise,
    so XLA fuses it into the surrounding matmuls.
    """
    x32 = x.astype(jnp.float32)
    x1 = x32[..., 0::2]
    x2 = x32[..., 1::2]
    c = cos[None, :, None, :].astype(jnp.float32)
    s = sin[None, :, None, :].astype(jnp.float32)
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


class LlamaAttention(Layer):
    """GQA attention with RoPE."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        h, kv = config.num_attention_heads, config.num_key_value_heads
        d = config.head_dim
        init = I.Normal(std=config.initializer_range)
        self.q_proj = Linear(config.hidden_size, h * d, weight_attr=init,
                             bias_attr=False)
        self.k_proj = Linear(config.hidden_size, kv * d, weight_attr=init,
                             bias_attr=False)
        self.v_proj = Linear(config.hidden_size, kv * d, weight_attr=init,
                             bias_attr=False)
        self.o_proj = Linear(h * d, config.hidden_size, weight_attr=init,
                             bias_attr=False)

    def forward(self, hidden, cos, sin, attn_mask=None, return_kv=False):
        b, s, _ = hidden.shape
        cfg = self.config
        h, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = self.q_proj(hidden).reshape([b, s, h, d])
        k = self.k_proj(hidden).reshape([b, s, kv, d])
        v = self.v_proj(hidden).reshape([b, s, kv, d])
        q = apply_rotary_pos_emb_t(q, cos, sin)
        k = apply_rotary_pos_emb_t(k, cos, sin)
        if return_kv:
            # decode-cache layout [B, KV, S, D], post-RoPE, unexpanded GQA
            kv_out = (k.transpose([0, 2, 1, 3]), v.transpose([0, 2, 1, 3]))
        if cfg.sep_mesh is not None:
            # context parallelism: exact global attention with K/V blocks
            # rotating the ICI ring (SURVEY.md §5's CP gap filler). GQA kv
            # heads stay unexpanded — the ring ships h/kv less K/V traffic.
            # Masked/padded batches ride the ring too: the mask's query rows
            # are sequence-sharded, each step slices the block's columns.
            # an explicit mask is the COMPLETE attention spec (callers bake
            # causality into it), matching the dense path's is_causal rule
            impl = getattr(cfg, "sep_impl", "ring")
            if impl == "auto":
                from ..distributed.auto_parallel import ProcessMesh
                from ..ops.ulysses_attention import choose_sep_impl
                jm = (cfg.sep_mesh.jax_mesh
                      if isinstance(cfg.sep_mesh, ProcessMesh)
                      else cfg.sep_mesh)
                impl = choose_sep_impl(
                    jm, cfg.sep_axis, h, kv, int(q.shape[1]),
                    attn_mask.shape[1] if attn_mask is not None else None)
            if impl == "ulysses":
                from ..ops.ulysses_attention import ulysses_attention
                out = ulysses_attention(q, k, v, mesh=cfg.sep_mesh,
                                        axis_name=cfg.sep_axis,
                                        causal=attn_mask is None,
                                        attn_mask=attn_mask)
            else:
                from ..ops.ring_attention import ring_attention
                out = ring_attention(q, k, v, mesh=cfg.sep_mesh,
                                     axis_name=cfg.sep_axis,
                                     causal=attn_mask is None,
                                     attn_mask=attn_mask)
        else:
            from ..nn.functional import _pallas_attention_eligible
            mask_arr = None if attn_mask is None else attn_mask._data
            if kv != h and not _pallas_attention_eligible(
                    q._data, k._data, mask_arr, 0.0):
                # GQA on the dense XLA path: repeat kv heads to full head
                # count; XLA keeps this as a broadcast feeding the batched
                # matmul (no copy). The Pallas kernel handles GQA natively.
                rep = h // kv
                k = k.unsqueeze(3).expand(
                    [b, s, kv, rep, d]).reshape([b, s, h, d])
                v = v.unsqueeze(3).expand(
                    [b, s, kv, rep, d]).reshape([b, s, h, d])
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                 is_causal=attn_mask is None)
        out = out.reshape([b, s, h * d])
        out = self.o_proj(out)
        if return_kv:
            return out, kv_out[0], kv_out[1]
        return out


def apply_rotary_pos_emb_t(x: Tensor, cos, sin) -> Tensor:
    """Tensor-level RoPE wired through the op layer so autograd sees it."""
    from ..ops.registry import dispatch
    return dispatch(apply_rotary_pos_emb, (x, cos, sin), {}, "rope")


def _rope_at(x, cos_tab, sin_tab, t):
    """Rotate [B, H, D] by per-batch positions t [B] (decode step RoPE)."""
    c = cos_tab[t][:, None, :].astype(jnp.float32)   # [B, 1, D/2]
    s = sin_tab[t][:, None, :].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


def _decode_attn(q, k_new, v_new, cache_k, cache_v, t, cos_tab, sin_tab):
    """One-token GQA decode over the dense cache (the serving hot op).

    q [B, H, D] (pre-RoPE); k_new/v_new [B, KV, D] (pre-RoPE);
    cache_k/v [B, KV, S_max, D] (post-RoPE rows); t [B] write positions.
    RoPE applies at position t, the new K/V row scatters in, and the
    attention runs grouped (GQA unexpanded — [B, KV, rep, D] against
    [B, KV, S, D]). Returns (ctx [B, H*D], cache_k', cache_v').
    Reference analog: masked_multihead_attention_kernel.cu, with GQA.
    """
    b, h, d = q.shape
    kvh = cache_k.shape[1]
    s_max = cache_k.shape[2]
    q = _rope_at(q, cos_tab, sin_tab, t)
    k_new = _rope_at(k_new, cos_tab, sin_tab, t)
    b_idx = jnp.arange(b)
    ck = cache_k.at[b_idx, :, t].set(k_new.astype(cache_k.dtype))
    cv = cache_v.at[b_idx, :, t].set(v_new.astype(cache_v.dtype))
    rep = h // kvh
    qg = q.reshape(b, kvh, rep, d)
    scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum("bgrd,bgsd->bgrs", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * scale
    pos = jnp.arange(s_max)[None, None, None, :]
    scores = jnp.where(pos <= t[:, None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bgrs,bgsd->bgrd", probs, cv.astype(jnp.float32))
    return ctx.reshape(b, h * d).astype(q.dtype), ck, cv


class LlamaMLP(Layer):
    """SwiGLU MLP: down(silu(gate(x)) * up(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        init = I.Normal(std=config.initializer_range)
        self.gate_proj = Linear(config.hidden_size, config.intermediate_size,
                                weight_attr=init, bias_attr=False)
        self.up_proj = Linear(config.hidden_size, config.intermediate_size,
                              weight_attr=init, bias_attr=False)
        self.down_proj = Linear(config.intermediate_size, config.hidden_size,
                                weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


def _make_expert_bank_cls():
    """Build the SwiGLU expert bank class lazily (the moe package imports
    back into models; a deferred class avoids the cycle at import time)."""
    from ..incubate.distributed.models.moe.moe_layer import _MoEBase

    class _LlamaExpertBank(_MoEBase):
        """Routed SwiGLU experts over stacked [E, h, I]/[E, I, h] weights."""

        def __init__(self, config: "LlamaConfig"):
            _MoEBase.__init__(
                self, config.hidden_size, config.num_experts,
                gate={"type": "gshard", "top_k": config.moe_top_k},
                capacity_factor=config.moe_capacity_factor,
                ep_mesh=config.ep_mesh,
                ep_axis=config.ep_axis if config.ep_mesh is not None
                else None)
            E, h, ims = (config.num_experts, config.hidden_size,
                         config.intermediate_size)
            init = I.Normal(std=config.initializer_range)
            self.gate_w = self.create_parameter([E, h, ims],
                                                default_initializer=init)
            self.up_w = self.create_parameter([E, h, ims],
                                              default_initializer=init)
            self.down_w = self.create_parameter([E, ims, h],
                                                default_initializer=init)
            if config.ep_mesh is not None:
                from ..distributed.auto_parallel import (Replicate, Shard,
                                                         shard_tensor)
                pl = [Shard(0) if n == config.ep_axis else Replicate()
                      for n in config.ep_mesh.dim_names]
                for p in (self.gate_w, self.up_w, self.down_w):
                    shard_tensor(p, config.ep_mesh, pl)

        def _run_experts(self, x):
            """x [E, C, h] → SwiGLU per expert (batched einsums)."""
            import paddle_tpu as paddle
            g = F.silu(paddle.einsum("ecd,edh->ech", x, self.gate_w))
            u = paddle.einsum("ecd,edh->ech", x, self.up_w)
            return paddle.einsum("ech,ehd->ecd", g * u, self.down_w)

    return _LlamaExpertBank


_EXPERT_BANK_CLS = None


class LlamaMoEMLP(Layer):
    """Mixtral-style routed SwiGLU expert bank.

    Stacked expert weights [E, h, I]/[E, I, h] with the shared MoE routing
    machinery (gshard top-k gate → dispatch [N,E,C] → per-expert SwiGLU →
    combine). Expert parallelism: with cfg.ep_mesh/ep_axis the expert dim
    is Shard(0) over the ep axis and GSPMD inserts the all-to-alls —
    reference surface: incubate/distributed/models/moe (moe_layer.py:263)
    composed with the llama FFN.
    """

    def __init__(self, config: LlamaConfig):
        global _EXPERT_BANK_CLS
        super().__init__(dtype=config.dtype)
        if _EXPERT_BANK_CLS is None:
            _EXPERT_BANK_CLS = _make_expert_bank_cls()
        self.moe = _EXPERT_BANK_CLS(config)

    @property
    def l_aux(self):
        return self.moe.l_aux

    def forward(self, x):
        return self.moe(x)


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.self_attn = LlamaAttention(config)
        self.mlp = (LlamaMoEMLP(config) if config.num_experts > 1
                    else LlamaMLP(config))
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)

    def forward(self, hidden, cos, sin, attn_mask=None):
        residual = hidden
        hidden = self.input_layernorm(hidden)
        hidden = self.self_attn(hidden, cos, sin, attn_mask)
        hidden = residual + hidden
        residual = hidden
        hidden = self.post_attention_layernorm(hidden)
        hidden = self.mlp(hidden)
        return residual + hidden

    def forward_kv(self, hidden, cos, sin):
        """Prefill: dense forward + this layer's post-RoPE K/V for the
        decode cache ([B, KV, S, D])."""
        attn_out, k, v = self.self_attn(self.input_layernorm(hidden),
                                        cos, sin, return_kv=True)
        hidden = hidden + attn_out
        return hidden + self.mlp(self.post_attention_layernorm(hidden)), k, v

    def decode(self, hidden, cache_kv, t, cos_tab, sin_tab):
        """One-token decode over the dense KV cache.

        hidden [B, 1, E]; cache_kv [2, B, KV, S_max, D]; t [B] int32.
        Returns (hidden', new_cache)."""
        from ..ops.registry import dispatch
        attn = self.self_attn
        cfg = attn.config
        b = hidden.shape[0]
        h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        x = self.input_layernorm(hidden)
        q = attn.q_proj(x).reshape([b, h, d])
        k = attn.k_proj(x).reshape([b, kvh, d])
        v = attn.v_proj(x).reshape([b, kvh, d])
        ctx, ck, cv = dispatch(
            _decode_attn,
            (q, k, v, cache_kv[0], cache_kv[1], t, Tensor(cos_tab),
             Tensor(sin_tab)), {}, "llama_decode_attn")
        hidden = hidden + attn.o_proj(ctx.reshape([b, 1, h * d]))
        from .. import ops
        new_cache = ops.stack([ck, cv])
        return (hidden + self.mlp(self.post_attention_layernorm(hidden)),
                new_cache)


class ScannedLlamaLayers(Layer):
    """The whole decoder stack as ONE ``lax.scan``.

    Parameters are stacked [L, ...] arrays; the scan body (rmsnorm → GQA
    attention with RoPE → rmsnorm → SwiGLU) is traced exactly once, so XLA
    compile time stops growing with depth. ``remat`` re-runs each layer in
    the backward (jax.checkpoint inside scan = the recompute analog with
    O(1) compile). Flash attention (Pallas) slots into the body when
    eligible. Numerics match the unrolled LlamaDecoderLayer stack.
    """

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.l_aux = None
        L = config.num_hidden_layers
        hs = config.hidden_size
        h, kv, d = (config.num_attention_heads, config.num_key_value_heads,
                    config.head_dim)
        ims = config.intermediate_size
        init = I.Normal(std=config.initializer_range)
        ones = I.Constant(1.0)

        def p(shape, initializer=init):
            return self.create_parameter(shape,
                                         default_initializer=initializer)

        self.q_w = p([L, hs, h * d])
        self.k_w = p([L, hs, kv * d])
        self.v_w = p([L, hs, kv * d])
        self.o_w = p([L, h * d, hs])
        if config.num_experts > 1:
            # routed SwiGLU expert bank, stacked over layers AND experts:
            # the scan body routes with this layer's [E, ...] slices (same
            # gshard top-2 + capacity machinery as the unrolled
            # _LlamaExpertBank, in pure jnp)
            if config.moe_top_k != 2:
                # same contract the unrolled path enforces via
                # GShardGate.__init__ — the gshard aux loss is a top-1
                # indicator over top-2 routing
                raise AssertionError("gshard gate requires top_k = 2")
            E = config.num_experts
            self.router_w = p([L, hs, E])
            self.router_b = p([L, E], I.Constant(0.0))
            self.moe_gate_w = p([L, E, hs, ims])
            self.moe_up_w = p([L, E, hs, ims])
            self.moe_down_w = p([L, E, ims, hs])
        else:
            self.gate_w = p([L, hs, ims])
            self.up_w = p([L, hs, ims])
            self.down_w = p([L, ims, hs])
        self.ln1_w = p([L, hs], ones)
        self.ln2_w = p([L, hs], ones)

    def forward(self, hidden, cos, sin, attn_mask=None):
        from ..core.flags import get_flag
        from ..ops import pallas as _pl
        from ..ops.registry import dispatch
        cfg = self.config
        h, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        eps = cfg.rms_norm_eps
        seq = int(hidden.shape[1])
        ring_impl = None
        if cfg.sep_mesh is not None:
            # context parallelism inside the scan body: the ring shard_map
            # runs per scanned layer (scan-of-shard_map — the layer body is
            # still traced once; K/V blocks rotate the ICI ring each step)
            from ..distributed.auto_parallel import ProcessMesh
            from ..ops.ring_attention import (_DP_NAMES, _MP_NAMES,
                                              _cached_impl, _pick_axis)
            jmesh = (cfg.sep_mesh.jax_mesh
                     if isinstance(cfg.sep_mesh, ProcessMesh)
                     else cfg.sep_mesh)
            if seq % jmesh.shape[cfg.sep_axis]:
                raise ValueError(
                    f"sequence length {seq} not divisible by sep axis "
                    f"size {jmesh.shape[cfg.sep_axis]}")
            batch = int(hidden.shape[0])
            from ..ops.ring_attention import _axes_size
            batch_axis = _pick_axis(jmesh.axis_names, _DP_NAMES,
                                    cfg.sep_axis)
            head_axis = _pick_axis(jmesh.axis_names, _MP_NAMES, cfg.sep_axis)
            if batch_axis is not None and \
                    batch % _axes_size(jmesh, batch_axis):
                batch_axis = None
            if head_axis is not None and (
                    h % _axes_size(jmesh, head_axis)
                    or kv % _axes_size(jmesh, head_axis)):
                head_axis = None
            # explicit mask == complete attention spec (non-causal ring),
            # matching the dense branch's `mask is None` causality rule.
            # Flags passed positionally to share lru_cache slots with the
            # public ring_attention() call sites.
            sep_impl = getattr(cfg, "sep_impl", "ring")
            if sep_impl == "auto":
                from ..ops.ulysses_attention import choose_sep_impl
                sep_impl = choose_sep_impl(
                    jmesh, cfg.sep_axis, h, kv, seq,
                    attn_mask.shape[1] if attn_mask is not None else None)
            if sep_impl == "ulysses":
                # all-to-all CP (heads<->sequence): wins when heads are
                # plentiful (h, kv divisible by the sep axis) and a
                # P-step ring's per-hop latency would dominate; heads
                # shard jointly over (mp, sep) when divisible
                from ..ops.ulysses_attention import (
                    resolve_ulysses_head_axis, ulysses_attention_impl,
                    validate_ulysses)
                u_head_axis = resolve_ulysses_head_axis(
                    jmesh, cfg.sep_axis, head_axis, h, kv)
                validate_ulysses(
                    jmesh, cfg.sep_axis, h, kv, seq,
                    attn_mask.shape[1] if attn_mask is not None else None,
                    head_axis=u_head_axis)
                ring_impl = ulysses_attention_impl(
                    jmesh, cfg.sep_axis, causal=attn_mask is None,
                    batch_axis=batch_axis, head_axis=u_head_axis,
                    has_mask=attn_mask is not None,
                    mask_headed=attn_mask is not None
                    and attn_mask.shape[1] > 1,
                    has_seqlens=False)
            else:
                ring_impl = _cached_impl(jmesh, cfg.sep_axis,
                                         attn_mask is None,
                                         batch_axis, head_axis,
                                         attn_mask is not None, False)
        use_flash = (ring_impl is None and attn_mask is None
                     and _pl.on_tpu()
                     and get_flag("FLAGS_use_pallas_attention"))
        if use_flash:
            from ..ops.pallas.flash_attention import supported
            use_flash = supported(seq, d)
        remat = cfg.use_recompute and self.training
        moe = cfg.num_experts > 1
        if moe:
            from ..incubate.distributed.models.moe.moe_layer import (
                _compute_capacity, moe_masks_jnp)
            E, top_k = cfg.num_experts, cfg.moe_top_k
            cap_factor = cfg.moe_capacity_factor

        def _impl(hidden, cos, sin, mask, qw, kw, vw, ow, *mlp_and_ln):
            if moe:
                rw, rb, mgw, muw, mdw, ln1, ln2 = mlp_and_ln
                mlp_ws = (rw, rb, mgw, muw, mdw)
            else:
                gw, uw, dw, ln1, ln2 = mlp_and_ln
                mlp_ws = (gw, uw, dw)

            def rms(x, w):
                xf = x.astype(jnp.float32)
                r = jax.lax.rsqrt(
                    jnp.mean(xf * xf, -1, keepdims=True) + eps)
                return (xf * r * w.astype(jnp.float32)).astype(x.dtype)

            def rope(x):
                # same pure-jnp RoPE as the unrolled path — ONE definition
                return apply_rotary_pos_emb(x, cos, sin)

            def mlp_dense(x2, ws):
                gw_, uw_, dw_ = ws
                return (jax.nn.silu(x2 @ gw_) * (x2 @ uw_)) @ dw_, 0.0

            def mlp_moe(x2, ws):
                """Routed SwiGLU experts — pure-jnp mirror of the unrolled
                _LlamaExpertBank (gshard top-2 probs, capacity priority
                masks, dense dispatch/combine einsums). Returns
                (mlp_out, this layer's aux loss)."""
                rw_, rb_, mgw_, muw_, mdw_ = ws
                b, s, hs_ = x2.shape
                n = b * s
                x2d = x2.reshape(n, hs_)
                probs = jax.nn.softmax(x2d @ rw_ + rb_, axis=-1)
                topk_val, topk_idx = jax.lax.top_k(probs, top_k)
                # gshard load-balance loss (top-1 indicator is constant)
                me = probs.astype(jnp.float32).mean(axis=0)
                ce = jax.lax.stop_gradient(jax.nn.one_hot(
                    topk_idx[:, 0], E, dtype=jnp.float32).mean(axis=0))
                aux_l = (me * ce).sum() * float(E)
                capacity = _compute_capacity(n, E, top_k, cap_factor)
                combine, dispatchm = moe_masks_jnp(
                    topk_val, topk_idx, num_experts=E, capacity=capacity,
                    norm_mode="sum")
                ein = jnp.einsum("nec,nd->ecd",
                                 dispatchm.astype(x2d.dtype), x2d)
                g = jax.nn.silu(jnp.einsum("ecd,edh->ech", ein, mgw_))
                u = jnp.einsum("ecd,edh->ech", ein, muw_)
                eo = jnp.einsum("ech,ehd->ecd", g * u, mdw_)
                out = jnp.einsum("nec,ecd->nd", combine.astype(eo.dtype), eo)
                return out.reshape(b, s, hs_), aux_l

            mlp_fn = mlp_moe if moe else mlp_dense

            def body_fn(carry, per_layer):
                h_, aux = carry
                (qw_, kw_, vw_, ow_, l1, l2), ws = per_layer
                b, s, _ = h_.shape
                x = rms(h_, l1)
                q = rope((x @ qw_).reshape(b, s, h, d))
                k = rope((x @ kw_).reshape(b, s, kv, d))
                v = (x @ vw_).reshape(b, s, kv, d)
                if ring_impl is not None:
                    # raw-jnp ring call (we are already inside the traced
                    # scan body; the op-level dispatch wrapper is above us)
                    ctx = (ring_impl(q, k, v) if mask is None
                           else ring_impl(q, k, v, mask))
                elif use_flash:
                    # GQA is native in the v2 kernel: K/V stay at kv heads
                    # (the index map expands the group in-kernel)
                    from ..ops.pallas.flash_attention import \
                        flash_attention_pallas
                    ctx = flash_attention_pallas(q, k, v, causal=True)
                else:
                    if kv != h:
                        rep = h // kv
                        k = jnp.broadcast_to(k[:, :, :, None],
                                             (b, s, kv, rep, d)
                                             ).reshape(b, s, h, d)
                        v = jnp.broadcast_to(v[:, :, :, None],
                                             (b, s, kv, rep, d)
                                             ).reshape(b, s, h, d)
                    scale = 1.0 / (d ** 0.5)
                    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
                    if mask is not None:
                        if mask.dtype == jnp.bool_:
                            # keep/drop mask, matching _sdpa_op semantics
                            scores = jnp.where(
                                mask, scores,
                                jnp.finfo(jnp.float32).min)
                        else:
                            scores = scores + mask
                    else:
                        causal = jnp.tril(jnp.ones((s, s), bool))
                        scores = jnp.where(causal[None, None], scores, -1e9)
                    probs = jax.nn.softmax(
                        scores.astype(jnp.float32), -1).astype(h_.dtype)
                    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
                h1 = h_ + ctx.reshape(b, s, h * d) @ ow_
                x2 = rms(h1, l2)
                mlp, aux_l = mlp_fn(x2, ws)
                return (h1 + mlp, aux + aux_l), None

            if remat:
                gran = getattr(cfg, "recompute_granularity", "full")
                if gran in ("selective", "core_attn", "dots"):
                    body = jax.checkpoint(
                        body_fn,
                        policy=jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable)
                elif gran == "full":
                    body = jax.checkpoint(body_fn)
                else:
                    raise ValueError(
                        f"unknown recompute_granularity '{gran}' "
                        f"(use 'full' or 'selective')")
            else:
                body = body_fn
            xs = ((qw, kw, vw, ow, ln1, ln2), mlp_ws)
            (out, aux), _ = jax.lax.scan(
                body, (hidden, jnp.float32(0.0)), xs)
            return out, aux

        if moe:
            mlp_params = (self.router_w, self.router_b, self.moe_gate_w,
                          self.moe_up_w, self.moe_down_w)
        else:
            mlp_params = (self.gate_w, self.up_w, self.down_w)
        out, aux = dispatch(
            _impl,
            (hidden, Tensor(cos), Tensor(sin), attn_mask, self.q_w,
             self.k_w, self.v_w, self.o_w, *mlp_params,
             self.ln1_w, self.ln2_w),
            {}, op_name="llama_scanned_layers")
        # summed load-balance aux across the scanned stack; the LM head
        # adds moe_aux_coeff * l_aux exactly like the unrolled path
        self.l_aux = aux if moe else None
        return out


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=I.Normal(std=config.initializer_range))
        if config.scan_layers:
            self.layers_scanned = ScannedLlamaLayers(config)
            self.layers = []
        else:
            self.layers = [LlamaDecoderLayer(config)
                           for _ in range(config.num_hidden_layers)]
            for i, l in enumerate(self.layers):
                self.add_sublayer(f"layers.{i}", l)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        jdt = dtype_mod.to_jax_dtype(config.dtype)
        self._cos, self._sin = _rope_cos_sin(
            config.max_position_embeddings, config.head_dim, config.rope_theta,
            jdt)

    def _anchor(self, hidden):
        """Re-anchor activation sharding at layer boundaries.

        ``shard_llama(..., batch_axes=, sep_axis=)`` installs an activation
        placement (batch over the data axes, sequence over the context-
        parallel axis, hidden replicated — the Megatron contract where
        row-parallel outputs are reduced over mp). Without the anchor, the
        eager discovery pass lets GSPMD pick a different output sharding per
        op and the batch-sharded residual meets an (seq, hidden)-sharded
        branch — an involuntary full rematerialization (reference analog:
        phi/infermeta/spmd_rules/* keep these transitions cheap by
        construction)."""
        anchor = getattr(self, "_act_anchor", None)
        if anchor is None:
            return hidden
        from ..distributed.auto_parallel import shard_tensor
        mesh, placements = anchor
        return shard_tensor(hidden, mesh, placements)

    def forward_prefill(self, input_ids, s_max):
        """Dense prompt pass that also fills the decode KV caches.

        Returns (hidden [B, S, E], caches [L, 2, B, KV, s_max, D]).
        Serving uses the unrolled stack (scan_layers exposes no per-layer
        K/V) and runs mesh-free (no sep ring)."""
        import paddle_tpu as paddle
        from .. import ops
        if self.config.scan_layers:
            raise ValueError("incremental decode needs the unrolled stack: "
                             "build the model with scan_layers=False for "
                             "serving")
        if self.config.sep_mesh is not None:
            # the ring would fill the cache through context-parallel
            # attention while decode attends a single dense cache — the
            # mismatch would be silent; refuse instead
            raise ValueError("incremental decode is mesh-free: clear "
                             "config.sep_mesh for serving (context "
                             "parallelism is a training-time layout)")
        b, s = input_ids.shape
        if s > s_max:
            raise ValueError(f"prompt length {s} exceeds cache size {s_max}")
        hidden = self.embed_tokens(input_ids)
        cos, sin = self._cos[:s], self._sin[:s]
        kvh, d = self.config.num_key_value_heads, self.config.head_dim
        pad = (paddle.zeros([b, kvh, s_max - s, d], dtype=self.config.dtype)
               if s < s_max else None)
        caches = []
        for layer in self.layers:
            hidden, k, v = layer.forward_kv(hidden, cos, sin)
            if pad is not None:
                k = ops.concat([k, pad.astype(k.dtype)], axis=2)
                v = ops.concat([v, pad.astype(v.dtype)], axis=2)
            caches.append(ops.stack([k, v]))
        return self.norm(hidden), ops.stack(caches)

    def forward(self, input_ids, attn_mask=None):
        _, s = input_ids.shape
        hidden = self.embed_tokens(input_ids)
        # NOTE: no anchor directly on the embedding output — a gather's
        # output sharding (hidden over fsdp, from the vocab-parallel table)
        # has no efficient reshard rule, and constraining it forces an
        # involuntary full rematerialization. The first layer's elementwise
        # and dot ops bridge to the anchored layout cheaply instead.
        cos, sin = self._cos[:s], self._sin[:s]
        if self.config.scan_layers:
            # one scan op: recompute (jax.checkpoint) handled inside
            hidden = self.layers_scanned(hidden, cos, sin, attn_mask)
            hidden = self._anchor(hidden)
        elif self.config.use_recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            for layer in self.layers:
                trainable = any(not p.stop_gradient
                                for p in layer.parameters())
                hidden = recompute(layer, hidden, cos, sin, attn_mask,
                                   _trainable_hint=trainable)
                hidden = self._anchor(hidden)
        else:
            for layer in self.layers:
                hidden = layer(hidden, cos, sin, attn_mask)
                hidden = self._anchor(hidden)
        return self.norm(hidden)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  weight_attr=I.Normal(
                                      std=config.initializer_range),
                                  bias_attr=False)

    def forward(self, input_ids, labels=None, attn_mask=None):
        hidden = self.model(input_ids, attn_mask)
        if self.lm_head is None:
            from .. import ops
            logits = ops.matmul(hidden, self.model.embed_tokens.weight,
                                transpose_y=True)
        else:
            logits = self.lm_head(hidden)
        if labels is None:
            return logits
        loss = F.cross_entropy(
            logits.reshape([-1, self.config.vocab_size]).astype("float32"),
            labels.reshape([-1]))
        if self.config.num_experts > 1 and self.config.moe_aux_coeff:
            if self.config.scan_layers:
                aux = self.model.layers_scanned.l_aux
                if aux is not None:
                    loss = loss + self.config.moe_aux_coeff * aux
            else:
                for layer in self.model.layers:
                    aux = getattr(layer.mlp, "l_aux", None)
                    if aux is not None:
                        loss = loss + self.config.moe_aux_coeff * aux
        return logits, loss

    # -- incremental (KV-cache) decode — the serving path -------------------

    def prefill(self, input_ids, s_max, n_valid=None):
        """Prompt pass for incremental decode. Returns
        (last_logits [B, 1, V], caches [L, 2, B, KV, s_max, D], t [B]).

        ``n_valid`` ([B, 1] int32): true prompt lengths when ``input_ids``
        is right-padded onto a bucket ladder — the final hidden state is
        gathered at n_valid-1 and decode resumes at t = n_valid (pad cache
        rows are overwritten before any decode step can attend them)."""
        import paddle_tpu as paddle
        b, s = input_ids.shape
        hidden, caches = self.model.forward_prefill(input_ids, s_max)
        if n_valid is None:
            last = hidden[:, s - 1:s]
            # t is [B, 1] — the shared decode-state convention (GPT-2 and
            # the serving batcher use the same shape)
            t = paddle.to_tensor(np.full((b, 1), s, np.int32))
        else:
            from .. import ops
            idx = (n_valid - 1).astype("int32").reshape([b, 1, 1])
            last = ops.take_along_axis(hidden, idx, axis=1)
            t = n_valid.astype("int32")
        logits = self._lm_logits(last)
        return logits, caches, t

    def _lm_logits(self, hidden):
        if self.lm_head is None:
            from .. import ops
            return ops.matmul(hidden, self.model.embed_tokens.weight,
                              transpose_y=True)
        return self.lm_head(hidden)

    def decode_step(self, tok, caches, t):
        """One incremental token through every layer's KV cache.

        tok [B, 1] int; caches [L, 2, B, KV, S_max, D]; t [B, 1] int32.
        Static shapes — ``jit.to_static(model.decode_step)`` compiles ONE
        executable that serves every step. Returns (logits, caches', t+1).
        """
        from .. import ops
        model = self.model
        hidden = model.embed_tokens(tok)           # [B, 1, E]
        cos_tab, sin_tab = model._cos, model._sin
        t_flat = t.reshape([-1])
        new_caches = []
        for i, layer in enumerate(model.layers):
            hidden, nc = layer.decode(hidden, caches[i], t_flat, cos_tab,
                                      sin_tab)
            new_caches.append(nc)
        hidden = model.norm(hidden)
        return self._lm_logits(hidden), ops.stack(new_caches), t + 1

    def generate(self, input_ids, max_new_tokens, s_max=None,
                 decode_fn=None, do_sample=False, temperature=1.0,
                 top_k=0, top_p=None, seed=None, eos_id=None, pad_id=None):
        """Incremental decode over the KV cache — greedy by default;
        ``do_sample`` draws with temperature / top-k / top-p, ``eos_id``
        stops rows early (shared driver semantics with the GPT-2 zoo)."""
        from .gpt import GPT2ForCausalLM
        _, s = input_ids.shape
        s_max = GPT2ForCausalLM._resolve_s_max(self.config, s,
                                               max_new_tokens, s_max)
        step = decode_fn if decode_fn is not None else self.decode_step
        return GPT2ForCausalLM._generate_loop(
            lambda: self.prefill(input_ids, s_max), step, input_ids,
            max_new_tokens, do_sample, temperature, top_k, top_p, seed,
            eos_id=eos_id, pad_id=pad_id)

    # -- paged-KV serving route (vLLM-style block cache, GQA-native) --------

    def _check_paged_servable(self):
        if self.config.scan_layers:
            raise ValueError("paged decode needs the unrolled stack: build "
                             "the model with scan_layers=False for serving")
        if self.config.sep_mesh is not None:
            raise ValueError("paged decode is mesh-free: clear "
                             "config.sep_mesh for serving")

    def paged_alloc(self, n_pages, block_size=64, cache_dtype=None):
        """Physical KV page pool: per layer, (kc, vc) of
        [n_pages, KV, block_size, D] — GQA caches at kv-head count
        (unexpanded), so the pool is H/KV times smaller than an
        MHA-equivalent one. After calibrate_cachekv_int8 the pools
        allocate int8 (half of bf16, quarter of fp32 cache HBM);
        cache_dtype overrides explicitly (dynamic-quant callers)."""
        import paddle_tpu as paddle
        cfg = self.config
        kvh, d = cfg.num_key_value_heads, cfg.head_dim
        dtype = cache_dtype or (
            "int8" if self._cachekv_scales is not None else cfg.dtype)
        return [(paddle.zeros([n_pages, kvh, block_size, d], dtype=dtype),
                 paddle.zeros([n_pages, kvh, block_size, d], dtype=dtype))
                for _ in range(cfg.num_hidden_layers)]

    _cachekv_scales = None

    def calibrate_cachekv_int8(self, sample_ids):
        """Install STATIC per-kv-head int8 cache scales from a calibration
        batch (reference cache_k_quant_scales surface, static mode): run
        the dense prefill, take each layer's per-head |K|/|V| amax over
        the post-RoPE rows, and store (quant=127/amax, dequant=amax/127)
        per layer. Afterwards every paged route — generate_paged and
        PagedContinuousBatcher — reads/writes an int8 page pool.
        Call with eval-mode weights; pass None to disable again."""
        if sample_ids is None:
            self._cachekv_scales = None
            return None
        import paddle_tpu as paddle
        from ..incubate.nn.functional.decode_attention import \
            cachekv_scales_from_dense as _cachekv_scales_from
        b, s = sample_ids.shape
        with paddle.no_grad():
            _, caches = self.model.forward_prefill(sample_ids, s)
        # caches [L, 2, B, KV, s, D] (post-RoPE rows, matching what the
        # paged route quantizes)
        self._cachekv_scales = _cachekv_scales_from(caches._data)
        return self._cachekv_scales

    def paged_prefill_into(self, input_ids, layers, block_tables,
                           block_size=64, dec_base=None, logits_at=None,
                           dynamic_cache_scales=False, cache_scales=None,
                           dynamic_scale_valid=None, logits_all=False):
        """Prompt pass writing post-RoPE K / raw V into a CALLER-OWNED page
        pool (block_gqa_attention in encoder mode). input_ids [B, s];
        block_tables [B, blocks_per_seq]. Returns (last_logits [B, V],
        new_layers) — the admission primitive for PagedContinuousBatcher.

        dec_base [B] int32 (optional): chunked-prefill append mode — see
        the GPT-2 docstring; RoPE positions follow the timeline
        (dec_base + local) inside the op, so chunks are exact.

        dynamic_cache_scales: dynamic cachekv-int8 prefill — the pools
        must be int8, each layer's op computes per-(sequence, head)
        scales from the prompt, and the return gains a third element:
        a per-layer list of scale dicts for paged_decode_step's
        state["cache_scales"]. dynamic_scale_valid [B] masks a chunked
        pad tail out of the scale statistics; cache_scales (per-layer
        dicts a first chunk returned) makes LATER chunks quantize with
        those same scales — the chunked x dynamic-int8 composition
        (reference: block_multihead_attention.py takes quant scales and
        chunked input in one op).
        """
        import paddle_tpu as paddle
        from ..incubate.nn.functional.decode_attention import (
            block_gqa_attention, cachekv_scale_kwargs as _scale_kwargs)

        if dynamic_cache_scales and cache_scales is not None:
            raise ValueError("dynamic_cache_scales computes scales; "
                             "cache_scales consumes them — pass one")
        self._check_paged_servable()
        cfg = self.config
        b, s = input_ids.shape
        h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        if dec_base is None:
            enc = paddle.to_tensor(np.full((b,), s, np.int32))
            dec = paddle.to_tensor(np.zeros((b,), np.int32))
        else:
            enc = paddle.to_tensor(np.zeros((b,), np.int32))
            dec = dec_base
        this = paddle.to_tensor(np.full((b,), s, np.int32))
        cu_q = paddle.to_tensor(np.arange(b + 1, dtype=np.int32) * s)
        model = self.model
        cos_tab, sin_tab = model._cos, model._sin

        # the scopes are metadata of the traced program: a device event of
        # the trace is put down to a phase of the model by them
        with jax.named_scope("embed"):
            hidden = model.embed_tokens(input_ids)     # [B, s, E]
        layers_state = []
        scales_out = [] if dynamic_cache_scales else None
        for li, (layer, (kc, vc)) in enumerate(zip(model.layers, layers)):
            attn = layer.self_attn
            with jax.named_scope("attn_norm"):
                x = layer.input_layernorm(hidden)
            with jax.named_scope("qkv_rope"):
                q = attn.q_proj(x).reshape([b * s, h, d])
                k = attn.k_proj(x).reshape([b * s, kvh, d])
                v = attn.v_proj(x).reshape([b * s, kvh, d])
            if dynamic_cache_scales:
                extra = dict(use_dynamic_cachekv_quant=True,
                             compute_dynamic_scales=True,
                             dynamic_scale_valid=dynamic_scale_valid)
            else:
                extra = _scale_kwargs(
                    cache_scales if cache_scales is not None
                    else self._cachekv_scales, li)
            res = block_gqa_attention(
                q, k, v, kc, vc, enc, dec, this, cu_q, block_tables,
                block_size=block_size, rope_cos=Tensor(cos_tab),
                rope_sin=Tensor(sin_tab), **extra)
            if dynamic_cache_scales:
                out, kc, vc, (kq, vq, kdq, vdq) = res
                scales_out.append({"kq": kq, "vq": vq,
                                   "kdq": kdq, "vdq": vdq})
            else:
                out, kc, vc = res
            with jax.named_scope("o_proj"):
                hidden = hidden + attn.o_proj(out.reshape([b, s, h * d]))
            with jax.named_scope("mlp_norm"):
                x = layer.post_attention_layernorm(hidden)
            with jax.named_scope("mlp"):
                hidden = hidden + layer.mlp(x)
            layers_state.append((kc, vc))
        with jax.named_scope("head"):
            hidden = model.norm(hidden)
            if logits_all:
                # speculative verify: score every appended position in one
                # pass (s = draft_k + 1)
                logits = self._lm_logits(hidden)             # [b, s, V]
            elif logits_at is not None:
                # chunked prefill: project ONLY the requested position (the
                # lm head over all C positions would be C x the needed
                # FLOPs)
                oh = F.one_hot(logits_at.reshape([b]).astype("int64"),
                               s).astype(hidden.dtype)
                logits = self._lm_logits(paddle.einsum("bs,bse->be", oh,
                                                       hidden))
            else:
                logits = self._lm_logits(hidden[:, s - 1])
        if dynamic_cache_scales:
            return logits, layers_state, scales_out
        return logits, layers_state

    def _layer_cache_scales(self, li):
        """block_gqa_attention kwargs for layer li's cache quantization
        (empty when the int8 cache is disabled)."""
        from ..incubate.nn.functional.decode_attention import \
            cachekv_scale_kwargs
        return cachekv_scale_kwargs(self._cachekv_scales, li)

    def paged_prefill(self, input_ids, block_size=64, blocks_per_seq=None):
        """Prompt pass through a freshly allocated paged cache. Returns
        (last_logits [B, V], state dict) in the shared paged-state
        convention (same keys as the GPT-2 route, so one batcher and one
        compiled-step recipe serve both families)."""
        from .gpt import GPT2ForCausalLM
        return GPT2ForCausalLM._paged_prefill_impl(self, input_ids,
                                                   block_size,
                                                   blocks_per_seq)

    def paged_decode_attention_path(self, pool) -> str:
        """``"kernel"`` or ``"gather"``: the route ``paged_decode_step``'s
        attention takes over ``pool`` (a ``paged_alloc`` result) on this
        backend — what the batcher labels its decode launches with."""
        from ..incubate.nn.functional.decode_attention import \
            decode_attention_path
        kc = pool[0][0]
        return decode_attention_path(tuple(kc.shape), kc._data.dtype,
                                     self.config.num_attention_heads)

    def paged_kv_writer(self, pool) -> str:
        """``"page"`` or ``"row"``: how ``paged_decode_step`` writes a
        step's K/V rows into ``pool`` (whole pages along the first axis for
        a float pool, the row scatter for an int8 one) — what the batcher
        labels ``serving_kv_write_launches_total`` with."""
        from ..incubate.nn.functional.decode_attention import \
            decode_kv_writer
        return decode_kv_writer(pool[0][0]._data.dtype)

    def paged_decode_step(self, tok, state):
        """One token per sequence through the paged GQA cache. tok: [B].
        Static shapes — ``jit.to_static(model.paged_decode_step)`` serves
        every step with one executable. Decode by construction, so the
        attention is the decode-only entry (rows written by the page, the
        Pallas paged kernel on the chip), not the general
        ``block_gqa_attention``."""
        from ..incubate.nn.functional.decode_attention import (
            block_gqa_decode_attention, cachekv_scale_kwargs)

        self._check_paged_servable()
        cfg = self.config
        b = tok.shape[0]
        h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        t = state["dec_lens"]
        bt = state["block_tables"]
        model = self.model
        cos_tab, sin_tab = model._cos, model._sin

        with jax.named_scope("embed"):
            hidden = model.embed_tokens(tok.reshape([b, 1]))   # [B, 1, E]
        dyn = state.get("cache_scales")
        new_layers = []
        for li, (layer, (kc, vc)) in enumerate(zip(model.layers,
                                                   state["layers"])):
            attn = layer.self_attn
            with jax.named_scope("attn_norm"):
                x = layer.input_layernorm(hidden)
            with jax.named_scope("qkv_rope"):
                q = attn.q_proj(x).reshape([b, h, d])
                k = attn.k_proj(x).reshape([b, kvh, d])
                v = attn.v_proj(x).reshape([b, kvh, d])
            if dyn is not None:
                # dynamic cachekv int8: per-(slot, head) scales ride the
                # state, fixed by each sequence's prefill
                kwargs = dict(cachekv_scale_kwargs(dyn, li),
                              use_dynamic_cachekv_quant=True)
            else:
                kwargs = self._layer_cache_scales(li)
            out, kc, vc = block_gqa_decode_attention(
                q, k, v, kc, vc, t, bt, rope_cos=Tensor(cos_tab),
                rope_sin=Tensor(sin_tab), **kwargs)
            with jax.named_scope("o_proj"):
                hidden = hidden + attn.o_proj(out.reshape([b, 1, h * d]))
            with jax.named_scope("mlp_norm"):
                x = layer.post_attention_layernorm(hidden)
            with jax.named_scope("mlp"):
                hidden = hidden + layer.mlp(x)
            new_layers.append((kc, vc))
        with jax.named_scope("head"):
            hidden = model.norm(hidden)
            logits = self._lm_logits(hidden[:, 0])         # [B, V]
        new_state = dict(state, layers=new_layers, dec_lens=t + 1)
        return logits, new_state

    def generate_paged(self, input_ids, max_new_tokens, block_size=64,
                       blocks_per_seq=None, decode_fn=None):
        """Greedy decode over the paged GQA cache (shared driver with
        GPT-2; reference surface block_multihead_attention + the serving
        predictor)."""
        from .gpt import GPT2ForCausalLM
        return GPT2ForCausalLM._paged_generate_loop(
            self, input_ids, max_new_tokens, block_size, blocks_per_seq,
            decode_fn)

    def generate_paged_speculative(self, input_ids, max_new_tokens,
                                   draft_model, draft_k=4, block_size=64,
                                   eos_id=None, compile=True,
                                   return_stats=False):
        """Greedy speculative decoding (shared loop with GPT-2): any
        draft sharing this model's vocab works — including a GPT-2-family
        draft for a Llama target, since both speak the shared paged-state
        convention. Token-exact vs generate()/generate_paged()."""
        from .gpt import GPT2ForCausalLM
        return GPT2ForCausalLM._speculative_loop(
            self, draft_model, input_ids, max_new_tokens, draft_k,
            block_size, eos_id, compile, return_stats)

    def generate_beam(self, input_ids, max_new_tokens, num_beams=4,
                      s_max=None, decode_fn=None, length_penalty=0.0):
        """Beam search over the GQA KV cache (shared driver with GPT-2)."""
        from .gpt import GPT2ForCausalLM
        _, s = input_ids.shape
        s_max = GPT2ForCausalLM._resolve_s_max(self.config, s,
                                               max_new_tokens, s_max)
        step = decode_fn if decode_fn is not None else self.decode_step
        return GPT2ForCausalLM._beam_loop(
            lambda ids: self.prefill(ids, s_max), step, input_ids,
            max_new_tokens, num_beams, length_penalty)

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in self.parameters())


def shard_llama(model: "LlamaForCausalLM", mesh, mp_axis: str = "mp",
                fsdp_axis: Optional[str] = None,
                batch_axes: Optional[Sequence[str]] = None,
                sep_axis: Optional[str] = None,
                ep_axis: str = "ep"):
    """Apply Megatron-style TP (+ optional FSDP) placements to a Llama model.

    The reference expresses this with dedicated parallel layer classes
    (fleet/layers/mpu/mp_layers.py) and per-op collectives; TPU-first the same
    plan is pure sharding metadata — GSPMD inserts the identity/allreduce/
    allgather collectives over ICI:
      - q/k/v/gate/up projections: column-parallel  -> Shard(out_dim) on mp
      - o/down projections:        row-parallel     -> Shard(in_dim)  on mp
      - token embedding:           vocab-parallel   -> Shard(vocab)   on mp
      - lm_head:                   column-parallel  -> Shard(vocab)   on mp
      - optional fsdp axis: every 2D weight additionally Shard on its other
        dim (ZeRO-3-style parameter sharding as placements, SURVEY.md §7).
      - optional batch_axes/sep_axis: install the activation anchor
        (batch over batch_axes, sequence over sep_axis, hidden replicated)
        that LlamaModel re-applies at every layer boundary so GSPMD never
        drifts into an involuntary full rematerialization.
    """
    from ..distributed.auto_parallel import Replicate, Shard, shard_tensor

    names = mesh.dim_names

    def place(param, mp_dim=None, fsdp_dim=None, ep_dim=None):
        placements = []
        for ax in names:
            if ax == mp_axis and mp_dim is not None:
                placements.append(Shard(mp_dim))
            elif fsdp_axis is not None and ax == fsdp_axis \
                    and fsdp_dim is not None:
                placements.append(Shard(fsdp_dim))
            elif ax == ep_axis and ep_dim is not None:
                placements.append(Shard(ep_dim))
            else:
                placements.append(Replicate())
        shard_tensor(param, mesh, placements)

    # Embedding: vocab-parallel over BOTH mp and fsdp (Megatron
    # VocabParallelEmbedding, fleet/layers/mpu/mp_layers.py) — never the
    # hidden dim. A gather from a hidden-sharded table has no efficient
    # GSPMD reshard to the (batch, seq)-sharded activation layout
    # (involuntary full remat); a vocab-sharded table partitions the
    # lookup along the index sharding plus one allreduce.
    place(model.model.embed_tokens.weight, mp_dim=0, fsdp_dim=0)
    if model.config.scan_layers:
        # stacked [L, in, out] weights: the layer dim leads, so the 2D
        # placements shift by one (same TP plan, scan-compatible)
        sc = model.model.layers_scanned
        if model.config.num_experts > 1:
            # stacked [L, E, in, out] expert banks: expert dim Shard(1)
            # over ep, TP/FSDP shift one more for the leading layer dim;
            # the router stays replicated (same invariant as unrolled)
            for col in (sc.q_w, sc.k_w, sc.v_w):
                place(col, mp_dim=2, fsdp_dim=1)
            place(sc.o_w, mp_dim=1, fsdp_dim=2)
            place(sc.moe_gate_w, mp_dim=3, fsdp_dim=2, ep_dim=1)
            place(sc.moe_up_w, mp_dim=3, fsdp_dim=2, ep_dim=1)
            place(sc.moe_down_w, mp_dim=2, fsdp_dim=3, ep_dim=1)
            place(sc.router_w)
            place(sc.router_b)
        else:
            for col in (sc.q_w, sc.k_w, sc.v_w, sc.gate_w, sc.up_w):
                place(col, mp_dim=2, fsdp_dim=1)
            for row in (sc.o_w, sc.down_w):
                place(row, mp_dim=1, fsdp_dim=2)
        place(sc.ln1_w)
        place(sc.ln2_w)
    else:
        for layer in model.model.layers:
            attn, mlp = layer.self_attn, layer.mlp
            cols = [attn.q_proj, attn.k_proj, attn.v_proj]
            rows = [attn.o_proj]
            if isinstance(mlp, LlamaMoEMLP):
                # expert dim Shard(0) over ep; TP splits each expert's FFN
                # dims, FSDP takes the other dim; the router's tiny linear
                # is replicated EXPLICITLY so every parameter of an MoE
                # model carries a placement (dist-checkpoint audits rely
                # on that invariant)
                place(mlp.moe.gate_w, mp_dim=2, fsdp_dim=1, ep_dim=0)
                place(mlp.moe.up_w, mp_dim=2, fsdp_dim=1, ep_dim=0)
                place(mlp.moe.down_w, mp_dim=1, fsdp_dim=2, ep_dim=0)
                place(mlp.moe.gate.gate.weight)
                if mlp.moe.gate.gate.bias is not None:
                    place(mlp.moe.gate.gate.bias)
            else:
                cols += [mlp.gate_proj, mlp.up_proj]
                rows.append(mlp.down_proj)
            for col in cols:
                place(col.weight, mp_dim=1, fsdp_dim=0)
            for row in rows:
                place(row.weight, mp_dim=0, fsdp_dim=1)
            place(layer.input_layernorm.weight)
            place(layer.post_attention_layernorm.weight)
    place(model.model.norm.weight)
    if model.lm_head is not None:
        place(model.lm_head.weight, mp_dim=1, fsdp_dim=0)
    if batch_axes or sep_axis:
        act = []
        for ax in names:
            if batch_axes and ax in batch_axes and mesh.get_dim_size(ax) > 1:
                act.append(Shard(0))
            elif sep_axis and ax == sep_axis and mesh.get_dim_size(ax) > 1:
                act.append(Shard(1))
            else:
                act.append(Replicate())
        model.model._act_anchor = (mesh, act)
    return model
