"""Family ``llama``: the decoder-only transformer of the Mistral and SmolLM2
configurations (``chipbench/families/__init__.py`` says what a family gives).

Equations, as the models' reference implementations state them:
x += Attn(RMSNorm(x)); x += SwiGLU(RMSNorm(x)); rotary embedding on
interleaved pairs (the convention of the models' own reference code; the
Hugging Face port permutes the projection columns to use half-split pairs
instead, which with seeded random weights is the same model); grouped-query
causal attention with softmax in float32.

Nothing of the program is imported here but inside ``program_model``: the
reference half takes nothing the program has made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..costs import head_dim
from ..reference import F32, einsum

SPANS = ()        # the base tuples of phases.py are this family's names
SCOPES = ()
COUNTERS = ()


# -- 1. the program's model ---------------------------------------------------

def program_model(cfg: dict, **extra):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "max_position_embeddings",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings",
            "initializer_range")
    return LlamaForCausalLM(LlamaConfig(**{k: cfg[k] for k in keys},
                                        **extra))


# -- 2. the leaves ------------------------------------------------------------

def layer_kind(cfg, layer: int):
    return "decoder"


def layer_shapes(cfg, layer: int) -> dict:
    hs, d = cfg["hidden_size"], head_dim(cfg)
    h, kv, ims = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["intermediate_size"]
    return {"wq": (hs, h * d), "wk": (hs, kv * d), "wv": (hs, kv * d),
            "wo": (h * d, hs), "w_gate": (hs, ims), "w_up": (hs, ims),
            "w_down": (ims, hs), "ln1": (hs,), "ln2": (hs,)}


def top_shapes(cfg) -> dict:
    hs, v = cfg["hidden_size"], cfg["vocab_size"]
    shapes = {"embed": (v, hs), "norm": (hs,)}
    if not cfg.get("tie_word_embeddings"):
        shapes["lm_head"] = (hs, v)
    return shapes


def leaf_draw(cfg, leaf: str):
    """Norm gains around one, matrices ([in, out]) at the configuration's
    ``initializer_range``."""
    if leaf in ("ln1", "ln2", "norm"):
        return ("gain", 1.0)
    return ("matrix", cfg["initializer_range"])


_SCANNED = {"wq": "q_w", "wk": "k_w", "wv": "v_w", "wo": "o_w",
            "w_gate": "gate_w", "w_up": "up_w", "w_down": "down_w",
            "ln1": "ln1_w", "ln2": "ln2_w"}
_UNROLLED = {"wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
             "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
             "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
             "w_down": "mlp.down_proj.weight",
             "ln1": "input_layernorm.weight",
             "ln2": "post_attention_layernorm.weight"}
_TOP = {"embed": "model.embed_tokens.weight", "norm": "model.norm.weight",
        "lm_head": "lm_head.weight"}


def parameter_name(leaf: str, layer=None, scanned: bool = False) -> str:
    if leaf in _TOP:
        return _TOP[leaf]
    if scanned:
        return f"model.layers_scanned.{_SCANNED[leaf]}"
    return f"model.layers.{layer}.{_UNROLLED[leaf]}"


# -- 3. the equations ---------------------------------------------------------

def position_tables(seq: int, cfg):
    d = head_dim(cfg)
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64)
                                       / d))
    ang = np.outer(np.arange(seq, dtype=np.float64), inv)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rope(x, cos, sin):
    """x [S, H, D]: rotate pairs (2i, 2i+1) by position * theta^(-2i/D)."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def embed_tokens(ids, top, cfg):
    return top["embed"][ids]


def layer_forward(x, w, tables, cfg, layer, precision="f32"):
    """One decoder layer over one sequence. x [S, hidden] float32; ``w`` the
    layer's leaves in float32."""
    es = functools.partial(einsum, precision)
    cos, sin = tables
    s = x.shape[0]
    h, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    eps = cfg["rms_norm_eps"]
    a = _rms(x, w["ln1"], eps)
    q = _rope(es("se,ef->sf", a, w["wq"]).reshape(s, h, d), cos, sin)
    k = _rope(es("se,ef->sf", a, w["wk"]).reshape(s, kv, d), cos, sin)
    v = es("se,ef->sf", a, w["wv"]).reshape(s, kv, d)
    g = h // kv
    qg = q.reshape(s, kv, g, d)
    scores = es("qkgd,tkd->kgqt", qg, k) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    ctx = es("kgqt,tkd->qkgd", probs, v).reshape(s, h * d)
    x = x + es("sf,fe->se", ctx, w["wo"])
    b = _rms(x, w["ln2"], eps)
    mlp = jax.nn.silu(es("se,ef->sf", b, w["w_gate"])) \
        * es("se,ef->sf", b, w["w_up"])
    return x + es("sf,fe->se", mlp, w["w_down"])


def head_logits(x, top, cfg, precision="f32"):
    """Final norm and output head over rows x [N, hidden]."""
    x = _rms(x, top["norm"], cfg["rms_norm_eps"])
    if cfg.get("tie_word_embeddings"):
        return einsum(precision, "ne,ve->nv", x, top["embed"])
    return einsum(precision, "ne,ev->nv", x, top["lm_head"])
