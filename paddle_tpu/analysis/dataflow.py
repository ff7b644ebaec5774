"""Jaxpr dataflow analyses (DF* rules) over ``static.ir.IrProgram``.

Each analysis is ``ClosedJaxpr -> List[Finding]`` — read-only diagnostics,
the counterpart of the transform passes in ``static/ir.py`` (the reference
ships both kinds over its IR: transform passes *and* diagnostic passes).
``analysis/passes.py`` registers these in the same pass registry so
``list_passes()`` surfaces them and ``apply_pass`` runs them without
touching the program.

Rules:
* DF001 shape/dtype consistency — def-before-use / double-def scan plus
  jax's own ``check_jaxpr`` re-check (catches corrupt hand-written passes)
* DF002 dead code — eqn results that never reach the outputs
* DF003 unused inputs — invars nothing reads
* DF004 collective ordering — every rank must see the identical collective
  sequence per mesh axis (cross-rank compare + cond-branch divergence)
* DF005 NaN-prone patterns — log/sqrt/rsqrt/div fed by unclamped subs
* DF006 inplace/donation alias audit — ops/inplace.py contract vs the
  alias metadata declared in ops/registry.py
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from jax._src.core import (ClosedJaxpr, DropVar, Jaxpr, Literal, Var,
                           check_jaxpr)

from .findings import Finding

__all__ = ["check_shapes", "check_dead_code", "check_unused_inputs",
           "collective_schedule", "check_collective_order",
           "check_nan_prone", "audit_inplace_aliases", "run_all"]


def _closed(program) -> ClosedJaxpr:
    """Accept an IrProgram or a bare ClosedJaxpr."""
    return getattr(program, "closed", program)


def _prim(eqn) -> str:
    return str(eqn.primitive)


# ---------------------------------------------------------------------------
# DF001 — structural + type consistency
# ---------------------------------------------------------------------------

def check_shapes(program) -> List[Finding]:
    closed = _closed(program)
    jaxpr = closed.jaxpr
    findings: List[Finding] = []
    defined = set(jaxpr.constvars) | set(jaxpr.invars)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, Var) and v not in defined:
                findings.append(Finding(
                    "DF001",
                    f"eqn #{i} ({_prim(eqn)}) reads {v} before it is "
                    "defined — a transform pass dropped its producer",
                    line=i))
        for o in eqn.outvars:
            if isinstance(o, DropVar):
                continue
            if o in defined:
                findings.append(Finding(
                    "DF001",
                    f"eqn #{i} ({_prim(eqn)}) redefines {o} — SSA "
                    "violated", line=i))
            defined.add(o)
    for v in jaxpr.outvars:
        if isinstance(v, Var) and not isinstance(v, DropVar) \
                and v not in defined:
            findings.append(Finding(
                "DF001", f"program output {v} is never defined", line=0))
    if not findings:
        try:
            check_jaxpr(jaxpr)
        except Exception as e:  # JaxprTypeError and friends
            findings.append(Finding(
                "DF001", f"jax type re-check failed: {e}", line=0))
    return findings


# ---------------------------------------------------------------------------
# DF002 / DF003 — liveness
# ---------------------------------------------------------------------------

def _live_vars(jaxpr: Jaxpr) -> set:
    """Vars that (transitively) feed outputs or effectful eqns."""
    live = {v for v in jaxpr.outvars if isinstance(v, Var)}
    for eqn in reversed(jaxpr.eqns):
        if eqn.effects or any(o in live for o in eqn.outvars):
            live.update(v for v in eqn.invars if isinstance(v, Var))
    return live


def check_dead_code(program) -> List[Finding]:
    closed = _closed(program)
    jaxpr = closed.jaxpr
    live = _live_vars(jaxpr)
    findings = []
    for i, eqn in enumerate(jaxpr.eqns):
        if not eqn.effects and not any(o in live for o in eqn.outvars):
            findings.append(Finding(
                "DF002",
                f"eqn #{i} ({_prim(eqn)}) result never reaches the "
                "outputs; the dead_code_elimination pass would remove it",
                line=i))
    return findings


def check_unused_inputs(program) -> List[Finding]:
    closed = _closed(program)
    jaxpr = closed.jaxpr
    read = {v for v in jaxpr.outvars if isinstance(v, Var)}
    for eqn in jaxpr.eqns:
        read.update(v for v in eqn.invars if isinstance(v, Var))
    findings = []
    for i, v in enumerate(jaxpr.invars):
        if v not in read:
            findings.append(Finding(
                "DF003",
                f"input #{i} ({v.aval.str_short()}) is never read — "
                "it still costs host→device transfer and a donation slot",
                line=i))
    return findings


# ---------------------------------------------------------------------------
# DF004 — collective ordering
# ---------------------------------------------------------------------------

def _collective_prims() -> frozenset:
    try:
        from ..distributed.collective import COLLECTIVE_PRIMITIVES
        return COLLECTIVE_PRIMITIVES
    except Exception:  # standalone / partial-import contexts
        return frozenset({
            "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
            "all_to_all", "psum_scatter", "reduce_scatter", "pbroadcast"})


def _axes_of(params: dict) -> Tuple[str, ...]:
    axes = params.get("axes", params.get("axis_name", ()))
    if axes is None:
        axes = ()
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def collective_schedule(program, _path: str = "") -> List[Tuple]:
    """Ordered list of ``(path, primitive, axes)`` for every collective
    eqn, recursing into call/control-flow subjaxprs (pjit/scan/while/cond
    — cond branches get distinct paths so divergence is visible)."""
    closed = _closed(program)
    jaxpr = closed.jaxpr if isinstance(closed, ClosedJaxpr) else closed
    prims = _collective_prims()
    sched: List[Tuple] = []
    for i, eqn in enumerate(jaxpr.eqns):
        name = _prim(eqn)
        if name in prims:
            sched.append((_path, name, _axes_of(eqn.params)))
        for key, val in eqn.params.items():
            subs = val if isinstance(val, (tuple, list)) else (val,)
            for j, sub in enumerate(subs):
                if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                    tag = f"{_path}/{name}#{i}.{key}"
                    if len(subs) > 1:
                        tag += f"[{j}]"
                    sched.extend(collective_schedule(sub, tag))
    return sched


def _branch_schedules(program):
    """-> {cond-path: [schedule-per-branch]} for every cond eqn."""
    closed = _closed(program)
    jaxpr = closed.jaxpr if isinstance(closed, ClosedJaxpr) else closed
    out: Dict[str, List[List[Tuple]]] = {}

    def walk(j: Jaxpr, path: str):
        for i, eqn in enumerate(j.eqns):
            name = _prim(eqn)
            if name == "cond":
                branches = eqn.params.get("branches", ())
                out[f"{path}/cond#{i}"] = [collective_schedule(b)
                                           for b in branches]
            for val in eqn.params.values():
                subs = val if isinstance(val, (tuple, list)) else (val,)
                for sub in subs:
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr, f"{path}/{name}#{i}")
                    elif isinstance(sub, Jaxpr):
                        walk(sub, f"{path}/{name}#{i}")

    walk(jaxpr, "")
    return out


def check_collective_order(programs, rank_names: Optional[Sequence[str]] = None
                           ) -> List[Finding]:
    """DF004. Accepts ONE program (checks cond-branch divergence) or a
    sequence of per-rank programs (checks the cross-rank schedule — every
    mesh axis must see the identical collective sequence on all ranks)."""
    if isinstance(programs, (ClosedJaxpr, Jaxpr)) or hasattr(
            programs, "closed"):
        programs = [programs]
    programs = list(programs)
    findings: List[Finding] = []

    # cross-rank: compare (primitive, axes) sequences
    if len(programs) > 1:
        names = list(rank_names or [f"rank{i}"
                                    for i in range(len(programs))])
        scheds = [[(prim, axes) for (_p, prim, axes) in
                   collective_schedule(p)] for p in programs]
        ref = scheds[0]
        for r, sched in enumerate(scheds[1:], start=1):
            if sched == ref:
                continue
            # locate the first divergence for a pointable message
            i = 0
            while i < min(len(ref), len(sched)) and ref[i] == sched[i]:
                i += 1
            a = ref[i] if i < len(ref) else None
            b = sched[i] if i < len(sched) else None
            findings.append(Finding(
                "DF004",
                f"{names[0]} and {names[r]} disagree at collective #{i}: "
                f"{names[0]} issues {a}, {names[r]} issues {b} — mesh "
                "ranks will deadlock waiting on each other",
                line=i,
                extra={"ranks": [names[0], names[r]], "index": i}))

    # intra-program: cond branches must agree (ranks taking different
    # branches otherwise issue different collective sequences)
    for p in programs:
        for path, branch_scheds in _branch_schedules(p).items():
            flat = [[(prim, axes) for (_pp, prim, axes) in s]
                    for s in branch_scheds]
            if any(s != flat[0] for s in flat[1:]):
                findings.append(Finding(
                    "DF004",
                    f"cond at {path or '/'} carries different collective "
                    f"sequences per branch ({flat}) — ranks taking "
                    "different branches deadlock the mesh",
                    extra={"path": path}))
    return findings


# ---------------------------------------------------------------------------
# DF005 — NaN-prone patterns
# ---------------------------------------------------------------------------

_RISKY_UNARY = {"log", "log2", "log10", "sqrt", "rsqrt"}
#: producers that make a subtraction safe-ish (clamped / shifted)
_GUARD_PRIMS = {"max", "clamp", "clip", "abs", "exp", "add",
                "reduce_max", "square"}


def check_nan_prone(program) -> List[Finding]:
    closed = _closed(program)
    jaxpr = closed.jaxpr
    producer: Dict[Var, Tuple[int, object]] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for o in eqn.outvars:
            if not isinstance(o, DropVar):
                producer[o] = (i, eqn)
    findings = []

    def produced_by_sub(v) -> Optional[int]:
        if not isinstance(v, Var) or v not in producer:
            return None
        idx, eqn = producer[v]
        return idx if _prim(eqn) == "sub" else None

    for i, eqn in enumerate(jaxpr.eqns):
        name = _prim(eqn)
        if name in _RISKY_UNARY:
            src = produced_by_sub(eqn.invars[0])
            if src is not None:
                findings.append(Finding(
                    "DF005",
                    f"eqn #{i} ({name}) consumes an unclamped subtraction "
                    f"(eqn #{src}); negative/zero inputs produce NaN/inf "
                    "— clamp or add an epsilon first",
                    line=i))
        elif name == "div" and len(eqn.invars) > 1:
            src = produced_by_sub(eqn.invars[1])
            if src is not None:
                findings.append(Finding(
                    "DF005",
                    f"eqn #{i} (div) divides by an unclamped subtraction "
                    f"(eqn #{src}); a zero difference produces inf/NaN",
                    line=i))
    return findings


# ---------------------------------------------------------------------------
# DF006 — inplace/donation alias audit (registry-level, not per-jaxpr)
# ---------------------------------------------------------------------------

def audit_inplace_aliases(namespace=None) -> List[Finding]:
    """Validate every op exposed as an ``op_`` inplace variant against the
    alias metadata declared in ``ops/registry.py``:

    * the registry entry must declare alias metadata (the donation
      contract is explicit, not implied by appearing in _INPLACE_NAMES);
    * declared ``preserves_shape`` / ``preserves_dtype`` must match the
      op's actual abstract behavior (probed with jax.eval_shape on
      canonical float32 operands where the op's arity allows).

    A wrong declaration is an ERROR: the compiled path donates the input
    buffer based on it, and a shape/dtype-changing op reusing the donated
    buffer corrupts memory on real hardware.
    """
    import jax
    import jax.numpy as jnp
    from ..ops import inplace as _inplace
    from ..ops.registry import OP_REGISTRY
    if namespace is None:
        from .. import ops as _ops
        namespace = vars(_ops)

    findings: List[Finding] = []
    probe = jax.ShapeDtypeStruct((2, 3), jnp.float32)

    for name in _inplace._INPLACE_NAMES:
        fn = namespace.get(name)
        if fn is None or not callable(fn):
            continue
        op_name = getattr(fn, "op_name", name)
        entry = OP_REGISTRY.get(op_name)
        if entry is None:
            continue
        alias = entry.get("alias")
        if alias is None:
            findings.append(Finding(
                "DF006",
                f"op '{op_name}' has an inplace variant '{name}_' but no "
                "alias metadata in the registry — donation contract is "
                "implicit", extra={"op": op_name}))
            continue
        raw = entry["fn"]
        out = None
        import warnings
        for args in ((probe,), (probe, probe)):
            try:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    out = jax.eval_shape(raw, *args)
                break
            except Exception:
                continue
        if out is None:
            continue  # needs special operands; metadata presence checked
        leaves = jax.tree_util.tree_leaves(out)
        if len(leaves) != 1:
            continue
        o = leaves[0]
        actual_shape = tuple(o.shape) == tuple(probe.shape)
        actual_dtype = o.dtype == probe.dtype
        if alias.get("preserves_shape") and not actual_shape:
            findings.append(Finding(
                "DF006",
                f"op '{op_name}' declares preserves_shape but maps "
                f"{probe.shape} -> {tuple(o.shape)}; donating its input "
                "buffer would corrupt memory",
                extra={"op": op_name}))
        if alias.get("preserves_dtype") and not actual_dtype:
            findings.append(Finding(
                "DF006",
                f"op '{op_name}' declares preserves_dtype but maps "
                f"{probe.dtype} -> {o.dtype}; the inplace write-back "
                "silently changes the tensor's dtype",
                extra={"op": op_name}))
    return findings


# ---------------------------------------------------------------------------

_PER_PROGRAM = [check_shapes, check_dead_code, check_unused_inputs,
                check_collective_order, check_nan_prone]


def run_all(program) -> List[Finding]:
    """All per-program DF analyses over one IrProgram/ClosedJaxpr."""
    findings: List[Finding] = []
    for fn in _PER_PROGRAM:
        findings.extend(fn(program))
    return findings
