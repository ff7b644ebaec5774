"""One module a model family, found by the name a configuration's file gives
under ``"family"``: ``chipbench/families/<family>.py``.

A family's module holds everything the harness may know about what a model
*is*; the runners, ``weights.py``, ``reference.py`` and ``phases.py`` ask it
and keep only what is the same for every model. A new architecture is a new
module here beside its configuration, traffic, cell and reader files, and
no edit to a file that exists. What a module gives (PERF.md section 3, "How
a family is added"):

1. the program's model, as a user builds it:
   ``program_model(cfg, **extra)``. The only place that imports the program.
2. the leaves: ``layer_shapes(cfg, layer)`` and ``top_shapes(cfg)``
   ({leaf: shape}); ``leaf_draw(cfg, leaf)``, how a leaf is drawn from its
   stream (``("matrix", std)`` or ``("gain", centre)``);
   ``parameter_name(leaf, layer, scanned)``, the program's name for a leaf
   (``layer`` is None for a top leaf and, with ``scanned``, for the stacked
   parameter of all layers); ``layer_kind(cfg, layer)``, equal for layers
   with the same leaves and equations (a stack, and the training
   reference's scan, take one kind only).
3. the equations, plain ``jax.numpy`` in float32 with every matrix product
   through ``reference.einsum`` (the float8 control's one switch):
   ``position_tables(seq, cfg)``, ``embed_tokens(ids, top, cfg)``,
   ``layer_forward(x, w, tables, cfg, layer, precision)`` over one sequence
   (``layer`` is the first layer of its kind: one program a kind),
   ``head_logits(x, top, cfg, precision)``.
4. the trace's names: ``SPANS`` and ``SCOPES`` the family's mechanism opens,
   beyond the base tuples of ``phases.py``.
5. the window's counters: ``COUNTERS``, registry series as
   ``(key, name, labels)``, whose differences over the window reach the
   readers as ``run["counters"][key]``.
"""
from __future__ import annotations

import importlib
import re

_NAME = re.compile(r"[A-Za-z0-9_]+\Z")


def of(cfg: dict):
    """The module of the family a configuration names."""
    name = cfg.get("family")
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"the configuration names no family: {name!r}")
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as exc:
        if exc.name != f"{__name__}.{name}":
            raise
        raise ValueError(f"no chipbench/families/{name}.py for the family "
                         f"{name!r}") from exc


def layer_kinds(family, cfg: dict) -> list:
    return [family.layer_kind(cfg, i)
            for i in range(cfg["num_hidden_layers"])]
