"""Weights from ``--seed``, made by the benchmark and by nothing else.

Both sides of the comparison that decides ``correct`` get their weights from
here: the program has them installed into its model (``Parameter.set_value``),
the plain reference makes them again from the seed after the program's state
is freed. Neither takes anything the other has made.

Which leaves a model has, their shapes, how each is drawn and what the
program calls it are its family's (``chipbench/families/``). Every leaf is
its own stream (seed, leaf name, layer), so one layer, or the whole stacked
model, is one jitted call on the device. Values are drawn in float32 and
rounded to bfloat16, the type the configurations hold them in; the float32
master copy of training starts from the rounded value.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from . import families


def seed_key(seed: int):
    """A key for any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, name, shape, how, value):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32)
    if how == "gain":              # around its centre, not all equal
        return (value + 0.1 * x).astype(jnp.bfloat16)
    if how == "matrix":
        return (value * x).astype(jnp.bfloat16)
    raise ValueError(f"leaf {name!r}: drawn {how!r}, not as a matrix or a "
                     f"gain")


def _table(family, cfg, shapes: dict) -> tuple:
    """((leaf, shape, how, value), ...): what a jitted maker is keyed by."""
    return tuple((n, tuple(s)) + tuple(family.leaf_draw(cfg, n))
                 for n, s in shapes.items())


def _layer(key, table, layer):
    k = jax.random.fold_in(key, layer + 1)
    return {n: _leaf(k, n, *rest) for n, *rest in table}


_make_layer = jax.jit(_layer, static_argnums=(1,))


def make_layer(cfg, seed: int, layer: int) -> dict:
    """One layer's leaves ([in, out] matrices, bfloat16)."""
    family = families.of(cfg)
    return _make_layer(seed_key(seed),
                       _table(family, cfg, family.layer_shapes(cfg, layer)),
                       jnp.int32(layer))


def _stack(key, table, n_layers):
    return jax.vmap(lambda i: _layer(key, table, i))(
        jnp.arange(n_layers, dtype=jnp.int32))


_make_stack = jax.jit(_stack, static_argnums=(1, 2))


def make_stack(cfg, seed: int) -> dict:
    """All layers stacked on a leading axis, in one call. Only for a model
    whose layers are all of one kind."""
    family = families.of(cfg)
    kinds = set(families.layer_kinds(family, cfg))
    if len(kinds) > 1:
        raise ValueError(f"layers of kinds {sorted(map(str, kinds))} do not "
                         f"stack")
    return _make_stack(seed_key(seed),
                       _table(family, cfg, family.layer_shapes(cfg, 0)),
                       cfg["num_hidden_layers"])


def _top(key, table):
    k = jax.random.fold_in(key, 0)
    return {n: _leaf(k, n, *rest) for n, *rest in table}


_make_top = jax.jit(_top, static_argnums=(1,))


def make_top(cfg, seed: int) -> dict:
    """The leaves outside the layers: embedding, final norm, output head."""
    family = families.of(cfg)
    return _make_top(seed_key(seed),
                     _table(family, cfg, family.top_shapes(cfg)))


def install(model, cfg, seed: int, scanned: bool) -> None:
    """Put the seed's weights into the program's model through its public
    ``named_parameters`` / ``set_value``. Every parameter must be covered,
    and every leaf must have its parameter."""
    name_of = families.of(cfg).parameter_name
    params = dict(model.named_parameters())
    todo = set(params)

    def put(leaf, layer, value):
        name = name_of(leaf, layer, scanned)
        if name not in params:
            raise RuntimeError(f"leaf {leaf!r}: the program's model has no "
                               f"parameter {name!r}")
        params[name].set_value(value)
        todo.discard(name)

    for leaf, value in make_top(cfg, seed).items():
        put(leaf, None, value)
    if scanned:
        for leaf, value in make_stack(cfg, seed).items():
            put(leaf, None, value)
    else:
        for i in range(cfg["num_hidden_layers"]):
            for leaf, value in make_layer(cfg, seed, i).items():
                put(leaf, i, value)
    if todo:
        raise RuntimeError(f"parameters without seed weights: {sorted(todo)}")
