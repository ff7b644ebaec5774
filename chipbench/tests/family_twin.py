"""A second family for ``test_data_driven.py``, which copies this file to
``families/twin.py`` of a temporary checkout: the program's Llama model under
a leaf table, equations and trace names of its own. ``twin_extra_leaf`` in a
configuration adds a leaf the program's model does not have;
``twin_drop_rope`` leaves the rotary embedding out of the equations."""
import jax
import jax.numpy as jnp
import numpy as np

from ..reference import F32, einsum
from .llama import program_model  # noqa: F401  (the program has no twin)

SPANS = ("twin.summarise",)
SCOPES = ("twin_block",)
COUNTERS = (
    ("admissions", "serving_admissions_total", {"engine": "paged"}),
    ("step_seconds", "serving_step_seconds", {"engine": "paged"}),
    ("never_made", "twin_series_nobody_registers_total", {}))

_LAYER = {"attn_q": "self_attn.q_proj.weight",
          "attn_k": "self_attn.k_proj.weight",
          "attn_v": "self_attn.v_proj.weight",
          "attn_o": "self_attn.o_proj.weight",
          "ffn_gate": "mlp.gate_proj.weight", "ffn_up": "mlp.up_proj.weight",
          "ffn_down": "mlp.down_proj.weight",
          "norm_attn": "input_layernorm.weight",
          "norm_ffn": "post_attention_layernorm.weight",
          "attn_gate": "self_attn.gate.weight"}
_STACKED = {"attn_q": "q_w", "attn_k": "k_w", "attn_v": "v_w",
            "attn_o": "o_w", "ffn_gate": "gate_w", "ffn_up": "up_w",
            "ffn_down": "down_w", "norm_attn": "ln1_w", "norm_ffn": "ln2_w",
            "attn_gate": "gate_attn_w"}
_TOP = {"tok": "model.embed_tokens.weight", "norm_out": "model.norm.weight",
        "out": "lm_head.weight"}


def _sizes(cfg):
    heads = cfg["num_attention_heads"]
    return heads, cfg["num_key_value_heads"], cfg["hidden_size"] // heads


def layer_kind(cfg, layer):
    return 0


def layer_shapes(cfg, layer):
    e, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, d = _sizes(cfg)
    shapes = {"attn_q": (e, h * d), "attn_k": (e, kv * d),
              "attn_v": (e, kv * d), "attn_o": (h * d, e),
              "ffn_gate": (e, f), "ffn_up": (e, f), "ffn_down": (f, e),
              "norm_attn": (e,), "norm_ffn": (e,)}
    if cfg.get("twin_extra_leaf"):
        shapes["attn_gate"] = (e, h)
    return shapes


def top_shapes(cfg):
    shapes = {"tok": (cfg["vocab_size"], cfg["hidden_size"]),
              "norm_out": (cfg["hidden_size"],)}
    if not cfg.get("tie_word_embeddings"):
        shapes["out"] = (cfg["hidden_size"], cfg["vocab_size"])
    return shapes


def leaf_draw(cfg, leaf):
    if leaf.startswith("norm_"):
        return ("gain", 1.0)
    return ("matrix", cfg["initializer_range"])


def parameter_name(leaf, layer=None, scanned=False):
    if leaf in _TOP:
        return _TOP[leaf]
    if scanned:
        return "model.layers_scanned." + _STACKED[leaf]
    return f"model.layers.{layer}.{_LAYER[leaf]}"


def position_tables(seq, cfg):
    d = _sizes(cfg)[2]
    inv = cfg["rope_theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    return jnp.asarray(np.outer(np.arange(seq), inv), F32)      # angles


def _rotate(x, angles):
    """x [S, H, D]: pair (2i, 2i+1) as a complex number times e^(i angle)."""
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) \
        * jnp.exp(1j * angles)[:, None, :]
    return jnp.stack([z.real, z.imag], -1).reshape(x.shape)


def _norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def embed_tokens(ids, top, cfg):
    return jnp.take(top["tok"], ids, axis=0)


def layer_forward(x, w, tables, cfg, layer, precision="f32"):
    h, kv, d = _sizes(cfg)
    s = x.shape[0]
    a = _norm(x, w["norm_attn"], cfg["rms_norm_eps"])
    q = einsum(precision, "se,ef->sf", a, w["attn_q"]).reshape(s, h, d)
    k = einsum(precision, "se,ef->sf", a, w["attn_k"]).reshape(s, kv, d)
    v = einsum(precision, "se,ef->sf", a, w["attn_v"]).reshape(s, kv, d)
    if not cfg.get("twin_drop_rope"):
        q, k = _rotate(q, tables), _rotate(k, tables)
    k, v = (jnp.repeat(t, h // kv, axis=1) for t in (k, v))  # a head each
    scores = einsum(precision, "qhd,thd->hqt", q, k) / d ** 0.5
    ahead = jnp.arange(s)[None, :] > jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(ahead[None], -jnp.inf, scores), -1)
    mixed = einsum(precision, "hqt,thd->qhd", probs, v).reshape(s, h * d)
    x = x + einsum(precision, "sf,fe->se", mixed, w["attn_o"])
    b = _norm(x, w["norm_ffn"], cfg["rms_norm_eps"])
    up = jax.nn.silu(einsum(precision, "se,ef->sf", b, w["ffn_gate"])) \
        * einsum(precision, "se,ef->sf", b, w["ffn_up"])
    return x + einsum(precision, "sf,fe->se", up, w["ffn_down"])


def head_logits(x, top, cfg, precision="f32"):
    x = _norm(x, top["norm_out"], cfg["rms_norm_eps"])
    if cfg.get("tie_word_embeddings"):
        return einsum(precision, "ne,ve->nv", x, top["tok"])
    return einsum(precision, "ne,ev->nv", x, top["out"])
