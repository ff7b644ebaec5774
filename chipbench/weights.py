"""Weights from ``--seed``, made by the benchmark and by nothing else.

Both sides of the comparison that decides ``correct`` get their weights from
here: the program has them installed into its model (``Parameter.set_value``),
the plain reference makes them again from the seed after the program's state
is freed. Neither takes anything the other has made.

Every leaf is its own stream (seed, leaf name, layer), so one layer, or the
whole stacked model, is one jitted call on the device. Values are drawn in
float32 and rounded to bfloat16, the type both configurations hold them in;
the float32 master copy of training starts from the rounded value.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "ln1", "ln2")


def layer_shapes(cfg) -> dict:
    from .costs import head_dim
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


def seed_key(seed: int):
    """A key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, name, shape, std):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32)
    if len(shape) == 1:            # norm gains: around one, not all equal
        return (1.0 + 0.1 * x).astype(jnp.bfloat16)
    return (std * x).astype(jnp.bfloat16)


def _layer(key, cfg_items, layer):
    cfg = dict(cfg_items)
    k = jax.random.fold_in(key, layer + 1)
    return {n: _leaf(k, n, s, cfg["initializer_range"])
            for n, s in layer_shapes(cfg).items()}


def _freeze(cfg):
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "vocab_size", "initializer_range",
            "tie_word_embeddings", "head_dim")
    return tuple((k, cfg[k]) for k in keys if k in cfg)


_make_layer = jax.jit(_layer, static_argnums=(1,))


def make_layer(cfg, seed: int, layer: int) -> dict:
    """One decoder layer's leaves ([in, out] matrices, bfloat16)."""
    return _make_layer(seed_key(seed), _freeze(cfg), jnp.int32(layer))


def _stack(key, cfg_items, n_layers):
    return jax.vmap(lambda i: _layer(key, cfg_items, i))(
        jnp.arange(n_layers, dtype=jnp.int32))


_make_stack = jax.jit(_stack, static_argnums=(1, 2))


def make_stack(cfg, seed: int) -> dict:
    """All layers stacked on a leading axis, in one call."""
    return _make_stack(seed_key(seed), _freeze(cfg),
                       cfg["num_hidden_layers"])


def _top(key, cfg_items):
    cfg = dict(cfg_items)
    k = jax.random.fold_in(key, 0)
    return {n: _leaf(k, n, s, cfg["initializer_range"])
            for n, s in top_shapes(cfg).items()}


_make_top = jax.jit(_top, static_argnums=(1,))


def make_top(cfg, seed: int) -> dict:
    """Embedding, final norm and (where untied) the output head."""
    return _make_top(seed_key(seed), _freeze(cfg))


# names of the program's parameters for each of our leaves
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


def install(model, cfg, seed: int, scanned: bool) -> None:
    """Put the seed's weights into the program's model through its public
    ``named_parameters`` / ``set_value``. Every parameter must be covered."""
    params = dict(model.named_parameters())
    todo = set(params)

    def put(name, value):
        params[name].set_value(value)
        todo.discard(name)

    for leaf, value in make_top(cfg, seed).items():
        put(_TOP[leaf], value)
    if scanned:
        for leaf, value in make_stack(cfg, seed).items():
            put(f"model.layers_scanned.{_SCANNED[leaf]}", value)
    else:
        for i in range(cfg["num_hidden_layers"]):
            for leaf, value in make_layer(cfg, seed, i).items():
                put(f"model.layers.{i}.{_UNROLLED[leaf]}", value)
    if todo:
        raise RuntimeError(f"parameters without seed weights: {sorted(todo)}")
