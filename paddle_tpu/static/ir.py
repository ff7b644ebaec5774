"""Jaxpr-level IR program + pass registry.

Reference surface: the PIR/legacy-IR pass infrastructure —
``paddle/fluid/framework/ir/pass.h`` (Pass/PassRegistry),
``python/paddle/base/framework.py`` Program text, and pass names like
``dead_code_elimination_pass`` / ``constant_folding_pass`` registered per
graph pass. The reference runs passes over its own ProgramDesc/PIR graph;
TPU-native the IR **is** the jaxpr — already SSA, typed, and functional —
so passes here are jaxpr→jaxpr transforms and the "executor" is either
direct jaxpr evaluation or one XLA compile of the transformed program.

This gives static-graph users a real surface: trace a python function to
an ``IrProgram``, inspect/print its IR, run named passes over it, and
execute the result — instead of the tape facade alone.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Union

import jax
import jax.numpy as jnp

from jax._src.core import (ClosedJaxpr, DropVar, Jaxpr, Literal, Var,
                           jaxpr_as_fun)

__all__ = ["IrProgram", "register_pass", "apply_pass", "list_passes",
           "is_analysis_pass"]


class IrProgram:
    """A traced program: ClosedJaxpr + the pytree structure of its I/O.

    ``IrProgram.trace(fn, *example_args)`` builds one;
    ``apply_pass(prog, "dead_code_elimination")`` transforms it;
    ``prog(*args)`` evaluates it (``prog.compile()`` for the XLA-compiled
    form). ``str(prog)`` prints the IR — the ProgramDesc-text analog.
    """

    def __init__(self, closed: ClosedJaxpr, in_tree, out_tree,
                 passes: Sequence[str] = (), findings: Sequence = ()):
        self.closed = closed
        self._in_tree = in_tree
        self._out_tree = out_tree
        self.applied_passes = list(passes)
        # diagnostic findings accumulated by analysis passes (apply_pass
        # with a name registered via register_pass(..., analysis=True))
        self.findings = list(findings)

    # -- construction -------------------------------------------------------
    @classmethod
    def trace(cls, fn: Callable, *example_args, **example_kwargs):
        from ..core.tensor import Tensor

        def unwrap(x):
            return x._data if isinstance(x, Tensor) else x

        ex_args = jax.tree_util.tree_map(unwrap, example_args)
        ex_kwargs = jax.tree_util.tree_map(unwrap, example_kwargs)

        def jnp_fn(*a, **k):
            wrapped_a = jax.tree_util.tree_map(Tensor, a)
            wrapped_k = jax.tree_util.tree_map(Tensor, k)
            out = fn(*wrapped_a, **wrapped_k)
            return jax.tree_util.tree_map(
                lambda t: t._data if isinstance(t, Tensor) else t, out,
                is_leaf=lambda t: isinstance(t, Tensor))

        flat, in_tree = jax.tree_util.tree_flatten((ex_args, ex_kwargs))
        out_tree_store = {}

        def flat_fn(*flat_args):
            a, k = jax.tree_util.tree_unflatten(in_tree, flat_args)
            out = jnp_fn(*a, **k)
            out_flat, out_tree = jax.tree_util.tree_flatten(out)
            out_tree_store["tree"] = out_tree
            return out_flat

        closed = jax.make_jaxpr(flat_fn)(*flat)
        return cls(closed, in_tree, out_tree_store["tree"])

    # -- introspection ------------------------------------------------------
    @property
    def eqns(self):
        return self.closed.jaxpr.eqns

    def ops(self) -> List[str]:
        return [str(e.primitive) for e in self.eqns]

    def num_ops(self) -> int:
        return len(self.eqns)

    def __str__(self):
        return str(self.closed.jaxpr)

    # -- execution ----------------------------------------------------------
    def _flat_args(self, args, kwargs):
        from ..core.tensor import Tensor

        def unwrap(x):
            return x._data if isinstance(x, Tensor) else x

        a = jax.tree_util.tree_map(unwrap, args)
        k = jax.tree_util.tree_map(unwrap, kwargs)
        flat, tree = jax.tree_util.tree_flatten((a, k))
        if tree != self._in_tree:
            raise ValueError("argument structure differs from the traced "
                             "example")
        return flat

    def __call__(self, *args, **kwargs):
        flat = self._flat_args(args, kwargs)
        out_flat = jaxpr_as_fun(self.closed)(*flat)
        return jax.tree_util.tree_unflatten(self._out_tree, list(out_flat))

    def compile(self):
        """One XLA executable for the (transformed) program."""
        fn = jax.jit(jaxpr_as_fun(self.closed))

        def run(*args, **kwargs):
            flat = self._flat_args(args, kwargs)
            out_flat = fn(*flat)
            return jax.tree_util.tree_unflatten(self._out_tree,
                                                list(out_flat))
        return run

    def _with(self, closed: ClosedJaxpr, pass_name: str) -> "IrProgram":
        return IrProgram(closed, self._in_tree, self._out_tree,
                         self.applied_passes + [pass_name], self.findings)

    def _with_findings(self, findings, pass_name: str) -> "IrProgram":
        """Analysis passes leave the program untouched; their findings
        accumulate on the returned program (``prog.findings``)."""
        return IrProgram(self.closed, self._in_tree, self._out_tree,
                         self.applied_passes + [pass_name],
                         self.findings + list(findings))


# ---------------------------------------------------------------------------
# Pass registry (PassRegistry / REGISTER_PASS analog). Two pass kinds:
#   transform passes:  ClosedJaxpr -> ClosedJaxpr  (the original contract)
#   analysis passes:   ClosedJaxpr -> [Finding]    (register_pass(...,
#       analysis=True); read-only diagnostics, the reference's diagnostic
#       graph passes) — apply_pass attaches the findings to the program
#       instead of replacing its jaxpr.
# ---------------------------------------------------------------------------

PASS_REGISTRY: Dict[str, Callable[[ClosedJaxpr], ClosedJaxpr]] = {}
ANALYSIS_PASSES: set = set()


def register_pass(name: str, analysis: bool = False):
    def deco(fn):
        PASS_REGISTRY[name] = fn
        if analysis:
            ANALYSIS_PASSES.add(name)
        return fn
    return deco


def list_passes() -> List[str]:
    return sorted(PASS_REGISTRY)


def is_analysis_pass(name: str) -> bool:
    return name in ANALYSIS_PASSES


def apply_pass(program: IrProgram,
               name: Union[str, Sequence[str]]) -> IrProgram:
    """Run one named pass (or a list, in order) over the program.
    Transform passes rewrite the jaxpr; analysis passes append their
    findings to ``program.findings`` and leave the jaxpr alone."""
    names = [name] if isinstance(name, str) else list(name)
    for n in names:
        if n not in PASS_REGISTRY:
            raise KeyError(f"unknown pass '{n}'; known: {list_passes()}")
        if n in ANALYSIS_PASSES:
            program = program._with_findings(
                PASS_REGISTRY[n](program.closed), n)
        else:
            program = program._with(PASS_REGISTRY[n](program.closed), n)
    return program


@register_pass("dead_code_elimination")
def _dce(closed: ClosedJaxpr) -> ClosedJaxpr:
    """Drop eqns whose outputs never reach the program outputs, and the
    constants that only fed dead eqns (dead_code_elimination_pass analog).

    Self-contained backward liveness walk — effectful eqns are kept, and
    subprogram calls (pjit/scan/...) are treated as opaque (conservative:
    their inner dead code is XLA's job anyway)."""
    jaxpr = closed.jaxpr
    live = {v for v in jaxpr.outvars if isinstance(v, Var)}
    kept = []
    for eqn in reversed(jaxpr.eqns):
        if eqn.effects or any(o in live for o in eqn.outvars):
            kept.append(eqn)
            live.update(v for v in eqn.invars if isinstance(v, Var))
    kept.reverse()
    constvars, consts = [], []
    for var, val in zip(jaxpr.constvars, closed.consts):
        if var in live:
            constvars.append(var)
            consts.append(val)
    new_jaxpr = Jaxpr(constvars, jaxpr.invars, jaxpr.outvars, kept,
                      jaxpr.effects)
    return ClosedJaxpr(new_jaxpr, consts)


@register_pass("constant_folding")
def _constant_folding(closed: ClosedJaxpr) -> ClosedJaxpr:
    """Evaluate eqns whose inputs are all compile-time constants
    (constant_folding_pass analog). Folded values become jaxpr consts;
    effectful eqns and subprogram calls (pjit/scan/cond/while) are left
    alone."""
    jaxpr = closed.jaxpr
    const_env = dict(zip(jaxpr.constvars, closed.consts))
    skip = {"pjit", "custom_jvp_call", "custom_vjp_call", "scan", "cond",
            "while", "shard_map"}
    new_eqns = []
    for eqn in jaxpr.eqns:
        if str(eqn.primitive) in skip or eqn.effects:
            new_eqns.append(eqn)
            continue

        def val_of(v):
            if isinstance(v, Literal):
                return v.val
            return const_env.get(v, _MISSING)

        vals = [val_of(v) for v in eqn.invars]
        if any(v is _MISSING for v in vals):
            new_eqns.append(eqn)
            continue
        try:
            outs = eqn.primitive.bind(*vals, **eqn.params)
        except Exception:
            new_eqns.append(eqn)
            continue
        if not eqn.primitive.multiple_results:
            outs = [outs]
        for var, val in zip(eqn.outvars, outs):
            const_env[var] = val
    # consts actually referenced by the remaining program
    live = set()
    for eqn in new_eqns:
        live.update(v for v in eqn.invars if isinstance(v, Var))
    live.update(v for v in jaxpr.outvars if isinstance(v, Var))
    arg_vars = set(jaxpr.invars)
    constvars, consts = [], []
    for var, val in const_env.items():
        if var in live and var not in arg_vars:
            constvars.append(var)
            consts.append(jnp.asarray(val))
    new_jaxpr = Jaxpr(constvars, jaxpr.invars, jaxpr.outvars, new_eqns,
                      jaxpr.effects)
    return ClosedJaxpr(new_jaxpr, consts)


_MISSING = object()


@register_pass("common_subexpression_elimination")
def _cse(closed: ClosedJaxpr) -> ClosedJaxpr:
    """Reuse the first occurrence of structurally identical pure eqns
    (the reference folds these in its graph passes too)."""
    jaxpr = closed.jaxpr
    sub: Dict[Var, Var] = {}
    seen: Dict[tuple, list] = {}
    new_eqns = []
    skip = {"pjit", "scan", "cond", "while", "shard_map"}
    for eqn in jaxpr.eqns:
        invars = [sub.get(v, v) if isinstance(v, Var) else v
                  for v in eqn.invars]

        def key_of(v):
            if isinstance(v, Literal):
                return ("lit", repr(v.val))
            return ("var", id(v))

        if str(eqn.primitive) in skip or eqn.effects:
            new_eqns.append(eqn.replace(invars=invars))
            continue
        key = (str(eqn.primitive), tuple(key_of(v) for v in invars),
               repr(sorted(eqn.params.items(), key=lambda kv: kv[0])))
        prior = seen.get(key)
        # a prior eqn can only substitute outputs it actually MATERIALIZED:
        # mapping a live output onto the prior's DropVar ('_') would build
        # an invalid jaxpr (check_jaxpr: "Variable '_' not defined")
        if prior is not None and all(
                isinstance(cur, DropVar) or not isinstance(pre, DropVar)
                for cur, pre in zip(eqn.outvars, prior)):
            for old, new in zip(eqn.outvars, prior):
                sub[old] = new
            continue
        new_eqn = eqn.replace(invars=invars)
        seen[key] = list(new_eqn.outvars)
        new_eqns.append(new_eqn)
    outvars = [sub.get(v, v) if isinstance(v, Var) else v
               for v in jaxpr.outvars]
    new_jaxpr = Jaxpr(jaxpr.constvars, jaxpr.invars, outvars, new_eqns,
                      jaxpr.effects)
    return ClosedJaxpr(new_jaxpr, closed.consts)
