"""SPMD shard-safety analysis (SH2xx rules).

GSPMD-style systems validate sharding propagation *before* compiling for
the mesh; this module does the static half of that for paddle_tpu so a
``PLAN_7B.json`` variant (or any traced program) is proven shard-feasible
on the CPU-only fallback path before a chip ever runs it.

Two entry layers:

* **plan-level** (stdlib-only, no jax): ``check_plan_sharding`` audits the
  7B plan's declared parameter shardings against a mesh — axis
  divisibility (SH201), FSDP replication waste (SH204) and the analytic
  per-step collective volume vs the interconnect budget derived from
  ``ROOFLINE.json`` (SH203). ``tools/shard_check.py`` imports this module
  straight off the tree (no package, no jax), same as ``tpu_lint`` does
  with ``ast_lint``.
* **jaxpr-level** (lazy jax import): ``propagate_placements`` pushes
  ``Shard``/``Replicate``/``Partial`` placements through a jaxpr's
  equations — contraction over a matched sharded dim yields ``Partial``
  (pending psum), mismatched operand placements flag SH202 (XLA would
  insert an implicit all-gather/reshard on the hot path), collective
  primitives are costed against the mesh so ``check_sharding`` can apply
  the SH203 budget.

Rules:
* SH201 (error)   shard-axis-divisibility — a dim declared ``Shard(axis)``
  must divide by the mesh axis degree; the runtime placement policy
  (``distributed/sharding.py``) replicates instead, so a plan assuming
  the shard is simply wrong.
* SH202 (warning) sharding-mismatch at an equation.
* SH203 (warning) estimated collective bytes over the interconnect budget.
* SH204 (warning) replicated-parameter-under-FSDP.
"""
from __future__ import annotations

import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

try:
    from .findings import ERROR, Finding, WARNING
except ImportError:  # loaded standalone by tools/shard_check.py
    from findings import ERROR, Finding, WARNING  # type: ignore

__all__ = [
    "MeshSpec", "ShardSpec", "PropagationResult", "divisible_dim",
    "dtype_bytes", "nbytes", "check_spec_divisibility",
    "propagate_placements", "check_sharding", "check_fsdp_replication",
    "ici_bytes_per_s", "interconnect_budget", "LLAMA7B_DIMS",
    "plan_param_shapes", "plan_shard_dim", "plan_mesh_size",
    "plan_step_collective_bytes", "plan_step_flops_per_chip",
    "check_plan_sharding",
]

GIB = 1024 ** 3

#: v5e chip: HBM ~819 GB/s vs a single ICI link ~200 GB/s; when
#: ROOFLINE.json carries no explicit ``peak_ici`` we derive it from the
#: recorded HBM roof with this ratio.
ICI_HBM_RATIO = 4.0


def divisible_dim(shape: Sequence[int], degree: int) -> Optional[int]:
    """First dim the axis degree divides (dim0 preferred), else None.

    Single source of truth for the placement policy — the runtime
    (``distributed/sharding.py``) and the static SH201/SH204 checks must
    agree on which dim a parameter shards over.
    """
    for d, size in enumerate(shape):
        if size % degree == 0 and size >= degree:
            return d
    return None


_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8, "complex64": 8,
    "float32": 4, "f32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "bf16": 2, "float16": 2, "f16": 2,
    "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "float8_e4m3fn": 1,
    "float8_e5m2": 1,
}


def dtype_bytes(dtype) -> int:
    itemsize = getattr(dtype, "itemsize", None)
    if itemsize:
        return int(itemsize)
    return _DTYPE_BYTES.get(str(dtype), 4)


def nbytes(shape: Sequence[int], dtype="float32") -> int:
    return math.prod(shape) * dtype_bytes(dtype) if shape is not None else 0


class MeshSpec:
    """Named mesh axes with degrees; the static mirror of ProcessMesh."""

    __slots__ = ("axes",)

    def __init__(self, axes):
        if not isinstance(axes, dict):
            axes = dict(axes)
        self.axes: Dict[str, int] = {str(k): int(v) for k, v in axes.items()}

    @classmethod
    def from_any(cls, mesh) -> "MeshSpec":
        if isinstance(mesh, MeshSpec):
            return mesh
        if isinstance(mesh, dict):
            return cls(mesh)
        if hasattr(mesh, "dim_names") and hasattr(mesh, "get_dim_size"):
            return cls({n: mesh.get_dim_size(n) for n in mesh.dim_names})
        if hasattr(mesh, "axis_names") and hasattr(mesh, "shape"):
            return cls({n: mesh.shape[n] for n in mesh.axis_names})
        raise TypeError(f"cannot interpret {mesh!r} as a mesh")

    def degree(self, axes) -> int:
        """Product of the degrees of the given axis names (unknown: 1)."""
        if isinstance(axes, str):
            axes = (axes,)
        deg = 1
        for a in axes:
            deg *= self.axes.get(str(a), 1)
        return deg

    @property
    def size(self) -> int:
        return math.prod(self.axes.values()) if self.axes else 1

    def __repr__(self):
        body = ",".join(f"{k}={v}" for k, v in self.axes.items())
        return f"MeshSpec({body})"


class ShardSpec:
    """Per-tensor placement: a tuple of mesh-axis tuples per dim, plus a
    ``partial`` set of axes over which the values are pending a psum."""

    __slots__ = ("dims", "partial")

    def __init__(self, dims, partial=()):
        norm = []
        for d in dims:
            if d is None:
                norm.append(())
            elif isinstance(d, str):
                norm.append((d,))
            else:
                norm.append(tuple(d))
        self.dims: Tuple[Tuple[str, ...], ...] = tuple(norm)
        self.partial = frozenset(partial)

    @classmethod
    def replicated(cls, ndim: int) -> "ShardSpec":
        return cls(((),) * ndim)

    @classmethod
    def normalize(cls, spec, ndim: int) -> "ShardSpec":
        if spec is None:
            return cls.replicated(ndim)
        if isinstance(spec, ShardSpec):
            return spec
        return cls(tuple(spec))

    @property
    def is_replicated(self) -> bool:
        return not any(self.dims) and not self.partial

    def shard_fraction(self, mesh: MeshSpec) -> float:
        """1/N of the global bytes held per chip under this placement."""
        deg = 1
        for axes in self.dims:
            deg *= mesh.degree(axes)
        return 1.0 / deg

    def with_partial(self, axes) -> "ShardSpec":
        return ShardSpec(self.dims, self.partial | frozenset(axes))

    def __eq__(self, other):
        return (isinstance(other, ShardSpec) and self.dims == other.dims
                and self.partial == other.partial)

    def __hash__(self):
        return hash((self.dims, self.partial))

    def __repr__(self):
        body = ",".join("+".join(a) if a else "·" for a in self.dims)
        tail = f"|partial={sorted(self.partial)}" if self.partial else ""
        return f"ShardSpec[{body}{tail}]"


# ---------------------------------------------------------------------------
# SH201 — axis divisibility (works on bare shapes; no jax)
# ---------------------------------------------------------------------------

def check_spec_divisibility(name: str, shape: Sequence[int], spec,
                            mesh, file: str = "<plan>",
                            line: int = 0) -> List[Finding]:
    mesh = MeshSpec.from_any(mesh)
    spec = ShardSpec.normalize(spec, len(shape))
    findings = []
    for d, axes in enumerate(spec.dims):
        deg = mesh.degree(axes)
        if deg > 1 and shape[d] % deg:
            findings.append(Finding(
                "SH201",
                f"'{name}' dim {d} (size {shape[d]}) is declared "
                f"Shard({'+'.join(axes)}) but {shape[d]} % {deg} != 0 — "
                "the placement policy would replicate it and the plan's "
                "per-chip math is wrong",
                file=file, line=line, severity=ERROR,
                extra={"param": name, "dim": d, "degree": deg}))
    return findings


# ---------------------------------------------------------------------------
# SH204 — replicated parameter under an FSDP axis (no jax)
# ---------------------------------------------------------------------------

def check_fsdp_replication(params: Dict[str, tuple], mesh, axis: str,
                           min_bytes: int = 1 << 20, dtype="bfloat16",
                           file: str = "<plan>") -> List[Finding]:
    """``params``: name -> (shape, spec-or-None). A param left fully
    replicated over the FSDP axis although a divisible dim exists wastes
    (N-1)/N of its per-chip bytes on every chip."""
    mesh = MeshSpec.from_any(mesh)
    n = mesh.degree(axis)
    findings = []
    if n <= 1:
        return findings
    for name, (shape, spec) in params.items():
        spec = ShardSpec.normalize(spec, len(shape))
        if any(axis in axes for axes in spec.dims):
            continue
        size = nbytes(shape, dtype)
        if size < min_bytes:
            continue
        dim = divisible_dim(shape, n)
        if dim is None:
            continue
        waste = size * (n - 1) // n
        findings.append(Finding(
            "SH204",
            f"'{name}' ({size / GIB:.3f} GiB) stays replicated over FSDP "
            f"axis '{axis}' (degree {n}) although dim {dim} is divisible "
            f"— {waste / GIB:.3f} GiB/chip is redundant",
            file=file, severity=WARNING,
            extra={"param": name, "dim": dim, "waste_bytes": waste}))
    return findings


# ---------------------------------------------------------------------------
# Interconnect budget (ROOFLINE.json; no jax)
# ---------------------------------------------------------------------------

def ici_bytes_per_s(roofline: dict) -> float:
    ici = roofline.get("peak_ici")
    if ici:
        return float(ici)
    return float(roofline.get("peak_hbm", 8.19e11)) / ICI_HBM_RATIO


def interconnect_budget(roofline: dict, step_flops: float,
                        overlap_frac: float = 1.0) -> float:
    """Collective bytes the interconnect can move while the chip computes
    ``step_flops`` at the roofline's peak — beyond this the step is
    ICI-bound (SH203)."""
    t_compute = step_flops / float(roofline["peak_flops"])
    return ici_bytes_per_s(roofline) * t_compute * overlap_frac


# ---------------------------------------------------------------------------
# Jaxpr-level placement propagation (lazy jax import)
# ---------------------------------------------------------------------------

class PropagationResult:
    __slots__ = ("var_specs", "findings", "collective_bytes",
                 "reshard_bytes")

    def __init__(self):
        self.var_specs: Dict = {}
        self.findings: List[Finding] = []
        self.collective_bytes = 0.0   # explicit collectives (psum, ...)
        self.reshard_bytes = 0.0      # implicit gathers from SH202 sites

    @property
    def total_bytes(self) -> float:
        return self.collective_bytes + self.reshard_bytes


def _jax_core():
    from jax._src.core import ClosedJaxpr, DropVar, Jaxpr, Literal, Var
    return ClosedJaxpr, DropVar, Jaxpr, Literal, Var


_ELEMENTWISE_SAFE_PARTIAL = {"add", "sub", "neg", "psum", "convert_element_type",
                             "copy", "transpose", "reshape", "broadcast_in_dim"}


def _gather_cost(aval, spec: ShardSpec, mesh: MeshSpec) -> float:
    """Bytes moved to materialize the replicated form of a sharded value."""
    total = nbytes(tuple(aval.shape), aval.dtype)
    return total * (1.0 - spec.shard_fraction(mesh))


def propagate_placements(program, mesh, in_specs=None) -> PropagationResult:
    """Push placements through a jaxpr. ``in_specs``: one spec per invar
    (None entries = replicated); sizes are read from the avals as-traced
    (global view). Emits SH202 findings at mismatch sites and tallies
    explicit-collective + implicit-reshard bytes for the SH203 budget."""
    ClosedJaxpr, DropVar, Jaxpr, Literal, Var = _jax_core()
    closed = getattr(program, "closed", program)
    jaxpr = closed.jaxpr if isinstance(closed, ClosedJaxpr) else closed
    mesh = MeshSpec.from_any(mesh)
    res = PropagationResult()

    if in_specs is None:
        in_specs = [None] * len(jaxpr.invars)
    env: Dict = {}
    for i, (v, spec) in enumerate(zip(jaxpr.invars, in_specs)):
        ndim = len(getattr(v.aval, "shape", ()))
        s = ShardSpec.normalize(spec, ndim)
        env[v] = s
        res.findings.extend(check_spec_divisibility(
            f"input #{i}", tuple(v.aval.shape), s, mesh, file="<jaxpr>"))
    for v in jaxpr.constvars:
        env[v] = ShardSpec.replicated(len(getattr(v.aval, "shape", ())))

    def spec_of(atom) -> ShardSpec:
        if isinstance(atom, Literal):
            return ShardSpec.replicated(len(getattr(atom.aval, "shape", ())))
        return env.get(atom,
                       ShardSpec.replicated(len(getattr(atom.aval, "shape",
                                                        ()))))

    def mismatch(idx, prim, detail, moved_bytes):
        res.reshard_bytes += moved_bytes
        res.findings.append(Finding(
            "SH202",
            f"eqn #{idx} ({prim}): {detail} — XLA inserts an implicit "
            f"all-gather/reshard (~{moved_bytes / (1 << 20):.1f} MiB) on "
            "the hot path",
            line=idx, severity=WARNING,
            extra={"eqn": idx, "primitive": prim}))

    collective_prims = _collective_prims()

    for idx, eqn in enumerate(jaxpr.eqns):
        prim = str(eqn.primitive)
        specs = [spec_of(a) for a in eqn.invars]
        outs = _infer_eqn(idx, eqn, prim, specs, mesh, res, mismatch,
                          collective_prims, ClosedJaxpr, Jaxpr)
        for o, s in zip(eqn.outvars, outs):
            if not isinstance(o, DropVar):
                env[o] = s

    res.var_specs = env
    return res


def _collective_prims() -> frozenset:
    try:
        from .dataflow import _collective_prims as dfprims
        return dfprims()
    except Exception:  # pragma: no cover - standalone context
        return frozenset({
            "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
            "all_to_all", "psum_scatter", "reduce_scatter", "pbroadcast"})


def _axis_names(params: dict) -> Tuple[str, ...]:
    axes = params.get("axes", params.get("axis_name", ()))
    if axes is None:
        axes = ()
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _infer_eqn(idx, eqn, prim, specs, mesh, res, mismatch,
               collective_prims, ClosedJaxpr, Jaxpr):
    """-> one ShardSpec per outvar; side effects: findings + byte tallies."""
    out_ndims = [len(getattr(o.aval, "shape", ())) for o in eqn.outvars]

    # -- explicit collectives: cost them, resolve Partial on psum --------
    if prim in collective_prims:
        axes = _axis_names(eqn.params)
        n = mesh.degree(axes)
        in_spec = specs[0] if specs else ShardSpec.replicated(0)
        size = nbytes(tuple(eqn.invars[0].aval.shape),
                      eqn.invars[0].aval.dtype) if eqn.invars else 0
        if n > 1:
            if prim in ("psum", "pmax", "pmin"):
                # a psum resolving a Partial is one reduce; a plain
                # all-reduce costs ~2(n-1)/n of the payload
                factor = ((n - 1) / n if set(axes) <= in_spec.partial
                          else 2.0 * (n - 1) / n)
                res.collective_bytes += size * factor * max(
                    in_spec.shard_fraction(mesh), 1.0 / mesh.size)
            elif prim == "all_gather":
                out_size = nbytes(tuple(eqn.outvars[0].aval.shape),
                                  eqn.outvars[0].aval.dtype)
                res.collective_bytes += out_size * (n - 1) / n
            elif prim in ("psum_scatter", "reduce_scatter"):
                res.collective_bytes += size * (n - 1) / n
            else:  # ppermute / all_to_all / broadcasts: payload once
                res.collective_bytes += size
        outs = []
        for s, nd in zip(specs, out_ndims):
            cleared = s.partial - set(axes) if prim == "psum" else s.partial
            outs.append(ShardSpec(s.dims[:nd] if len(s.dims) >= nd
                                  else ((),) * nd, cleared))
        while len(outs) < len(out_ndims):
            outs.append(ShardSpec.replicated(out_ndims[len(outs)]))
        return outs

    # -- dot_general: contraction semantics ------------------------------
    if prim == "dot_general":
        return [_infer_dot(idx, eqn, specs, mesh, mismatch)]

    # -- structural prims -------------------------------------------------
    if prim == "transpose":
        perm = eqn.params.get("permutation", ())
        s = specs[0]
        return [ShardSpec(tuple(s.dims[p] for p in perm), s.partial)]

    if prim == "broadcast_in_dim":
        s = specs[0]
        bdims = eqn.params.get("broadcast_dimensions", ())
        out_shape = tuple(eqn.outvars[0].aval.shape)
        in_shape = tuple(eqn.invars[0].aval.shape)
        dims = [()] * len(out_shape)
        for j, bd in enumerate(bdims):
            if j < len(in_shape) and in_shape[j] == out_shape[bd]:
                dims[bd] = s.dims[j]
        return [ShardSpec(dims, s.partial)]

    if prim == "reshape":
        s = specs[0]
        in_shape = tuple(eqn.invars[0].aval.shape)
        out_shape = tuple(eqn.outvars[0].aval.shape)
        if in_shape == out_shape:
            return [s]
        keep = 0
        while (keep < min(len(in_shape), len(out_shape))
               and in_shape[keep] == out_shape[keep]):
            keep += 1
        dims = list(s.dims[:keep]) + [()] * (len(out_shape) - keep)
        return [ShardSpec(dims, s.partial)]

    # -- call / remat recursion -------------------------------------------
    for key in ("jaxpr", "call_jaxpr"):
        sub = eqn.params.get(key)
        if isinstance(sub, (ClosedJaxpr, Jaxpr)):
            subj = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
            if len(subj.invars) == len(eqn.invars):
                sub_res = propagate_placements(sub, mesh, list(specs))
                for f in sub_res.findings:
                    if f.rule == "SH202":
                        f.extra.setdefault("path", f"{prim}#{idx}")
                        res.findings.append(f)
                res.collective_bytes += sub_res.collective_bytes
                res.reshard_bytes += sub_res.reshard_bytes
                outs = []
                for v, nd in zip(subj.outvars, out_ndims):
                    s = sub_res.var_specs.get(v)
                    outs.append(s if isinstance(s, ShardSpec)
                                else ShardSpec.replicated(nd))
                return outs
            break

    # -- elementwise / same-shape unify -----------------------------------
    if len(eqn.outvars) == 1 and eqn.invars:
        out_shape = tuple(getattr(eqn.outvars[0].aval, "shape", ()))
        arrayish = [(a, s) for a, s in zip(eqn.invars, specs)
                    if tuple(getattr(a.aval, "shape", ())) == out_shape]
        if arrayish and all(
                tuple(getattr(a.aval, "shape", ())) in (out_shape, ())
                for a in eqn.invars):
            dims = []
            for d in range(len(out_shape)):
                cands = []
                for _a, s in arrayish:
                    if d < len(s.dims) and s.dims[d] and \
                            s.dims[d] not in cands:
                        cands.append(s.dims[d])
                if len(cands) > 1:
                    loser_a, loser_s = arrayish[-1]
                    mismatch(idx, prim,
                             f"operands disagree on dim {d} placement "
                             f"({cands[0]} vs {cands[1]})",
                             _gather_cost(loser_a.aval, loser_s, mesh))
                dims.append(cands[0] if cands else ())
            partial = frozenset().union(*(s.partial for _a, s in arrayish))
            return [ShardSpec(dims, partial)]

    # -- conservative fallback -------------------------------------------
    if (len(eqn.outvars) == 1 and len(eqn.invars) >= 1
            and tuple(getattr(eqn.invars[0].aval, "shape", ()))
            == tuple(getattr(eqn.outvars[0].aval, "shape", ()))):
        return [specs[0]]
    return [ShardSpec.replicated(nd) for nd in out_ndims]


def _infer_dot(idx, eqn, specs, mesh, mismatch) -> ShardSpec:
    ls, rs = specs[0], specs[1]
    lhs, rhs = eqn.invars[0], eqn.invars[1]
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    partial = set(ls.partial | rs.partial)
    out_dims: List[Tuple[str, ...]] = []

    for dl, dr in zip(lb, rb):
        al, ar = ls.dims[dl], rs.dims[dr]
        if al != ar and (al or ar):
            mismatch(idx, "dot_general",
                     f"batch dim sharded differently (lhs {al or '·'} vs "
                     f"rhs {ar or '·'})",
                     _gather_cost(rhs.aval, rs, mesh))
        out_dims.append(al or ar)

    for dl, dr in zip(lc, rc):
        al, ar = ls.dims[dl], rs.dims[dr]
        if al and al == ar:
            partial |= set(al)          # matched shard: psum pending
        elif al or ar:
            moved = 0.0
            if al:
                moved += _gather_cost(lhs.aval, ls, mesh)
            if ar:
                moved += _gather_cost(rhs.aval, rs, mesh)
            mismatch(idx, "dot_general",
                     f"contraction dim sharded on one side only "
                     f"(lhs {al or '·'} vs rhs {ar or '·'})", moved)

    lhs_free = [d for d in range(len(ls.dims)) if d not in lc and d not in lb]
    rhs_free = [d for d in range(len(rs.dims)) if d not in rc and d not in rb]
    out_dims += [ls.dims[d] for d in lhs_free] + [rs.dims[d]
                                                  for d in rhs_free]
    return ShardSpec(out_dims, partial)


def check_sharding(program, mesh, in_specs=None,
                   collective_budget_bytes: Optional[float] = None,
                   roofline: Optional[dict] = None,
                   step_flops: Optional[float] = None) -> List[Finding]:
    """SH201/SH202 via propagation, plus SH203 when a budget is known —
    either an explicit byte budget or ``roofline + step_flops``."""
    res = propagate_placements(program, mesh, in_specs)
    findings = list(res.findings)
    budget = collective_budget_bytes
    if budget is None and roofline is not None and step_flops:
        budget = interconnect_budget(roofline, step_flops)
    if budget is not None and res.total_bytes > budget:
        findings.append(Finding(
            "SH203",
            f"estimated collective traffic {res.total_bytes / GIB:.2f} GiB "
            f"exceeds the interconnect budget {budget / GIB:.2f} GiB — "
            "the step is ICI-bound, not compute-bound",
            severity=WARNING,
            extra={"collective_bytes": res.collective_bytes,
                   "reshard_bytes": res.reshard_bytes,
                   "budget_bytes": budget}))
    return findings


# ---------------------------------------------------------------------------
# Plan-level audit (stdlib-only; mirrors tools/plan_7b.py)
# ---------------------------------------------------------------------------

#: LLaMA-7B dims, kept in lockstep with tools/plan_7b.py:_llama7b_dims.
LLAMA7B_DIMS = dict(L=32, H=4096, I=11008, V=32000, heads=32, kv_heads=32)


def plan_param_shapes(dims: Optional[dict] = None) -> Dict[str, tuple]:
    """Parameter shapes of the 7B plan (mirror of plan_7b._param_shapes)."""
    d = dict(LLAMA7B_DIMS, **(dims or {}))
    L, H, I, V = d["L"], d["H"], d["I"], d["V"]
    return {
        "embed": (V, H),
        "wq": (L, H, H), "wk": (L, H, H), "wv": (L, H, H), "wo": (L, H, H),
        "w_gate": (L, H, I), "w_up": (L, H, I), "w_down": (L, I, H),
        "ln1": (L, H), "ln2": (L, H), "ln_f": (H,),
        "lm_head": (H, V),
    }


def plan_shard_dim(name: str, shape: Sequence[int]) -> Optional[int]:
    """The dim the plan declares Shard('z') on (plan_7b._shardings):
    norms replicate, 2D shards dim0, 3D shards dim1 (the per-layer
    leading dim stays whole)."""
    if name.startswith("ln") or len(shape) < 2:
        return None
    return 0 if len(shape) == 2 else 1


def plan_mesh_size(plan: dict, default: int = 16) -> int:
    topo = str(plan.get("topology", ""))
    m = re.search(r"(\d+)\s*-\s*chip", topo)
    return int(m.group(1)) if m else default


#: FLOPs multiplier per remat policy: full recomputes the forward in the
#: backward (4/3 of the base 6·P·tokens), selective recomputes roughly
#: half of it.
REMAT_FLOPS_MULT = {"full": 4.0 / 3.0, "selective": 7.0 / 6.0}


def plan_step_collective_bytes(n_params: int, n_chips: int,
                               stage: str) -> float:
    """Analytic per-chip collective bytes of one ZeRO train step:
    bf16 param all-gather (twice under stage-3: forward + backward
    re-gather) plus the f32 grad reduce-scatter."""
    frac = (n_chips - 1) / n_chips
    ag_params = 2.0 * n_params * frac          # bf16 all-gather
    rs_grads = 4.0 * n_params * frac           # f32 reduce-scatter
    if stage in ("s3", "p_g_os"):
        return 2.0 * ag_params + rs_grads
    return ag_params + rs_grads


def plan_step_flops_per_chip(n_params: int, tokens_per_chip: float,
                             remat: str = "selective") -> float:
    mult = REMAT_FLOPS_MULT.get(remat, 1.0)
    return 6.0 * n_params * tokens_per_chip * mult


def check_plan_sharding(plan: dict, mesh_size: Optional[int] = None,
                        roofline: Optional[dict] = None,
                        dims: Optional[dict] = None,
                        overlap_frac: float = 1.0,
                        file: str = "<plan>") -> List[Finding]:
    """SH201/SH203/SH204 over every training variant of a PLAN_7B dict."""
    findings: List[Finding] = []
    n = mesh_size or plan_mesh_size(plan)
    mesh = MeshSpec({"z": n})
    shapes = plan_param_shapes(dims)

    # SH201: the declared shard dim of every (master-)sharded param must
    # divide; SH204: params with NO divisible dim fall back to replication
    # under the FSDP axis.
    fsdp_tree: Dict[str, tuple] = {}
    for name, shape in shapes.items():
        dim = plan_shard_dim(name, shape)
        if dim is None:
            continue
        spec = [None] * len(shape)
        spec[dim] = "z"
        findings.extend(check_spec_divisibility(
            name, shape, spec, mesh, file=file))
        fallback = divisible_dim(shape, n)
        fsdp_tree[name] = (shape, None if fallback is None else spec)
    findings.extend(check_fsdp_replication(
        fsdp_tree, mesh, "z", file=file))

    # SH203: analytic collective volume vs the roofline-derived budget.
    if roofline is not None:
        for var in plan.get("variants", ()):
            vname = var.get("variant", "?")
            stage = "s3" if vname.startswith("s3") or vname == "p_g_os" \
                else "s2"
            n_params = var.get("n_params") or sum(
                math.prod(s) for s in shapes.values())
            batch = var.get("batch", 16)
            seq = var.get("seq", 2048)
            tokens_per_chip = batch * seq / n
            coll = plan_step_collective_bytes(n_params, n, stage)
            flops = plan_step_flops_per_chip(
                n_params, tokens_per_chip, var.get("remat", "selective"))
            budget = interconnect_budget(roofline, flops, overlap_frac)
            if coll > budget:
                t_ici = coll / ici_bytes_per_s(roofline)
                t_cmp = flops / float(roofline["peak_flops"])
                findings.append(Finding(
                    "SH203",
                    f"variant '{var.get('name', vname)}': "
                    f"{coll / GIB:.1f} GiB of collectives need "
                    f"{t_ici * 1e3:.0f} ms on the interconnect but the "
                    f"step only computes for {t_cmp * 1e3:.0f} ms — "
                    "ICI-bound",
                    file=file, severity=WARNING,
                    extra={"variant": var.get("name", vname),
                           "collective_bytes": coll,
                           "budget_bytes": budget}))
    return findings
