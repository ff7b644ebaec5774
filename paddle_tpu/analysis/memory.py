"""Liveness-based HBM-footprint analysis (MEM3xx rules).

The memory half of the static PLAN_7B gate: roofline analysis bounds a
config's *time* before it runs; this module bounds its *memory*. Two entry
layers, mirroring ``analysis/sharding.py``:

* **jaxpr-level** (lazy jax import): ``peak_hbm_estimate`` walks the
  equations front-to-back tracking live buffer bytes — a var's buffer is
  freed after its last use, an output may reuse a dying same-layout input
  when the producing primitive's op-registry alias metadata permits
  donation (the DF006 contract from ``ops/registry.py``). Program inputs
  are only reusable when explicitly donated; a large input that dies at a
  donation-eligible equation *without* being donated is the MEM302
  missed-donation finding. ``check_hbm`` compares the peak against a
  budget (MEM301).
* **plan-level** (stdlib-only, no jax): ``check_plan_memory`` audits every
  ``PLAN_7B.json`` training variant against ``hbm_per_chip_gib`` —
  recorded per-chip byte categories are trusted at the recorded batch and
  scaled linearly in batch×seq otherwise (optimizer/param state constant,
  activations scale, the f32 grad shard held fixed). A variant already
  recorded infeasible (``fits_v5e_16gib: false``) is an honest documented
  baseline and does NOT error; overriding batch/seq re-opens the check.
  ``serving_bucket_report`` prices the gateway serving buckets (TP-sharded
  weights + per-rung KV cache) against the same budget.

Rules:
* MEM301 (error)   plan-over-hbm-budget.
* MEM302 (warning) missing-donation / remat opportunity.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

try:
    from .findings import ERROR, Finding, WARNING
    from . import sharding as _sharding
except ImportError:  # loaded standalone by tools/shard_check.py
    from findings import ERROR, Finding, WARNING  # type: ignore
    import sharding as _sharding  # type: ignore

__all__ = [
    "peak_hbm_estimate", "check_hbm", "variant_live_gib",
    "check_plan_memory", "serving_bucket_report",
]

GIB = 1024 ** 3

#: lax primitive -> framework op name, where they differ; the registry
#: speaks framework names (multiply), jaxprs speak lax names (mul).
_PRIM_TO_OP = {
    "mul": "multiply", "sub": "subtract", "div": "divide",
    "max": "maximum", "min": "minimum", "integer_pow": "pow",
    "logistic": "sigmoid",
}


def _donation_ops() -> Dict[str, dict]:
    try:
        from ..ops.registry import donatable_aliases
        return donatable_aliases()
    except Exception:  # standalone / partial-import contexts
        return {}


def _alias_for_prim(prim: str, donation_ops: Dict[str, dict]):
    return donation_ops.get(_PRIM_TO_OP.get(prim, prim))


def _aval_bytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()))
    return _sharding.nbytes(shape, getattr(aval, "dtype", "float32"))


# ---------------------------------------------------------------------------
# Jaxpr-level liveness walk
# ---------------------------------------------------------------------------

def peak_hbm_estimate(program, donate: Sequence[int] = (),
                      invar_shards: Optional[Sequence[int]] = None,
                      default_shards: int = 1) -> dict:
    """Estimate peak live HBM bytes of one jaxpr execution.

    Returns ``{"peak_bytes", "input_bytes", "output_bytes", "timeline",
    "missed_donations"}``. ``donate`` lists invar indices whose buffers
    the caller donates (jit ``donate_argnums``); intermediates are always
    reusable. The model charges each equation's transient as
    ``live + out_bytes - reuse_credit`` where the credit applies when a
    same-shape/dtype input dies at that equation and the primitive's
    registry alias metadata marks it donation-safe.

    Sharded per-chip mode (the runtime mesh gate): ``invar_shards`` is a
    per-invar shard degree (parallel to the jaxpr's invars) dividing that
    input's resident bytes, and ``default_shards`` divides every
    equation-produced buffer (the data-parallel degree activations shard
    over). Constvars and unlisted invars stay whole — replicated. The
    defaults reproduce the original whole-program accounting bit-for-bit.
    """
    from .dataflow import _closed  # lazy: pulls in jax
    from jax._src.core import DropVar, Literal, Var

    closed = _closed(program)
    jaxpr = closed.jaxpr
    donation_ops = _donation_ops()
    donate = set(donate)

    n_eqns = len(jaxpr.eqns)
    last_use: Dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, Var):
                last_use[v] = i
    for v in jaxpr.outvars:
        if isinstance(v, Var):
            last_use[v] = n_eqns          # outputs live to the end

    donated_vars = {v for i, v in enumerate(jaxpr.invars) if i in donate}
    invar_index = {v: i for i, v in enumerate(jaxpr.invars)}

    divisor: Dict = {}
    if invar_shards is not None:
        for v, d in zip(jaxpr.invars, invar_shards):
            divisor[v] = max(1, int(d))
    boundary = set(jaxpr.invars) | set(jaxpr.constvars)

    def _vb(v) -> int:
        nb = _aval_bytes(v.aval)
        if v in divisor:
            return nb // divisor[v]
        if v in boundary:
            return nb
        return nb // max(1, int(default_shards))

    live = 0
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        live += _vb(v)
    input_bytes = live

    peak = live
    timeline = [(-1, live)]
    missed: List[dict] = []

    for i, eqn in enumerate(jaxpr.eqns):
        prim = str(eqn.primitive)
        out_bytes = sum(_vb(o) for o in eqn.outvars
                        if not isinstance(o, DropVar))
        dying = [v for v in dict.fromkeys(
                     x for x in eqn.invars if isinstance(x, Var))
                 if last_use.get(v) == i]
        dying_bytes = sum(_vb(v) for v in dying)

        credit = 0
        alias = _alias_for_prim(prim, donation_ops)
        if alias is not None and dying:
            out_layouts = [(tuple(o.aval.shape), str(o.aval.dtype))
                           for o in eqn.outvars
                           if not isinstance(o, DropVar)]
            for v in dying:
                layout = (tuple(v.aval.shape), str(v.aval.dtype))
                if layout not in out_layouts:
                    continue
                reusable = v not in invar_index or v in donated_vars
                if reusable:
                    credit = _vb(v)
                    out_layouts.remove(layout)
                else:
                    missed.append({
                        "invar": invar_index[v], "eqn": i,
                        "primitive": prim,
                        "bytes": _vb(v)})
        peak = max(peak, live + out_bytes - credit)
        live += out_bytes - dying_bytes
        timeline.append((i, live))

    output_bytes = sum(_vb(v) for v in jaxpr.outvars
                       if isinstance(v, Var))
    return {"peak_bytes": peak, "input_bytes": input_bytes,
            "output_bytes": output_bytes, "timeline": timeline,
            "missed_donations": missed}


def check_hbm(program, budget_gib: Optional[float] = None,
              donate: Sequence[int] = (),
              min_donation_bytes: int = 1 << 20) -> List[Finding]:
    """MEM301 (peak over budget) + MEM302 (missed donation) for a jaxpr."""
    est = peak_hbm_estimate(program, donate=donate)
    findings: List[Finding] = []
    if budget_gib is not None and est["peak_bytes"] > budget_gib * GIB:
        findings.append(Finding(
            "MEM301",
            f"estimated peak HBM {est['peak_bytes'] / GIB:.3f} GiB exceeds "
            f"the {budget_gib:.3f} GiB per-chip budget — the program OOMs "
            "on the first step",
            severity=ERROR,
            extra={"peak_bytes": est["peak_bytes"],
                   "budget_gib": budget_gib}))
    for m in est["missed_donations"]:
        if m["bytes"] < min_donation_bytes:
            continue
        findings.append(Finding(
            "MEM302",
            f"input #{m['invar']} ({m['bytes'] / (1 << 20):.1f} MiB) dies "
            f"at eqn #{m['eqn']} ({m['primitive']}) whose alias metadata "
            "permits buffer reuse, but the input is not donated — pass it "
            "in donate_argnums to drop the extra copy",
            line=m["eqn"], severity=WARNING, extra=dict(m)))
    return findings


# ---------------------------------------------------------------------------
# Plan-level audit (stdlib-only; consumes PLAN_7B.json records)
# ---------------------------------------------------------------------------

def _plan_chips(plan: dict) -> int:
    return _sharding.plan_mesh_size(plan)


def variant_live_gib(variant: dict, n_chips: int,
                     batch: Optional[int] = None,
                     seq: Optional[int] = None) -> dict:
    """Estimated per-chip live GiB for a training variant, optionally
    re-scaled to a different batch/seq.

    Trusts the recorded ``per_chip_bytes`` at the recorded shape (the
    recorded categories reproduce ``per_chip_live_gib`` exactly:
    ``args + temp + max(0, out - aliased)``). Under a batch/seq override,
    optimizer/param state (``arguments``) and the f32 grad shard stay
    constant while the remaining activation bytes scale linearly with
    batch×seq — the standard transformer activation model.
    """
    b0 = variant.get("batch", 16)
    s0 = variant.get("seq", 2048)
    b = batch if batch is not None else b0
    s = seq if seq is not None else s0
    ratio = (b * s) / float(b0 * s0)
    n_params = variant.get("n_params", 6738415616)
    grads = 4.0 * n_params / n_chips

    rec = variant.get("per_chip_bytes")
    if rec:
        state = float(rec["arguments"])
        act = float(rec["temp"]) + max(
            0.0, float(rec["outputs"]) - float(rec["aliased"]))
        act_var = max(0.0, act - grads)
        live = state + grads + act_var * ratio
        basis = "recorded" if ratio == 1.0 else "scaled"
    else:
        # analytic fallback: state by stage, activations from dims
        stage = str(variant.get("variant", "s3"))
        if stage.startswith("s2"):
            state = 2.0 * n_params + 12.0 * n_params / n_chips
        else:
            state = 14.0 * n_params / n_chips
        act_var = 6.0 * n_params / n_chips  # coarse: grads-scale workspace
        live = state + grads + act_var * ratio
        basis = "analytic"
    return {"live_gib": live / GIB, "basis": basis, "batch": b, "seq": s,
            "ratio": ratio}


def check_plan_memory(plan: dict, hbm_gib: Optional[float] = None,
                      batch: Optional[int] = None,
                      seq: Optional[int] = None,
                      strict: bool = False,
                      rows: Optional[list] = None,
                      file: str = "<plan>") -> List[Finding]:
    """MEM301/MEM302 over every training variant of a PLAN_7B dict.

    A variant recorded ``fits_v5e_16gib: false`` at its recorded shape is
    a documented-infeasible baseline: reported in ``rows`` but not an
    error (``strict=True`` errors anyway). Overriding batch/seq always
    re-opens the check — that is the "deliberately oversubscribed
    variant" path the gate exists for.
    """
    budget = hbm_gib if hbm_gib is not None else float(
        plan.get("hbm_per_chip_gib", 16.0))
    n_chips = _plan_chips(plan)
    overridden = batch is not None or seq is not None
    variants = list(plan.get("variants", ()))
    findings: List[Finding] = []
    fits_map = {}

    for var in variants:
        name = var.get("name", var.get("variant", "?"))
        est = variant_live_gib(var, n_chips, batch=batch, seq=seq)
        over = est["live_gib"] > budget
        fits_map[name] = (var, est, over)
        if rows is not None:
            rows.append({"variant": name, "batch": est["batch"],
                         "seq": est["seq"], "remat": var.get("remat"),
                         "live_gib": round(est["live_gib"], 3),
                         "basis": est["basis"], "fits": not over})
        if not over:
            continue
        documented = (not overridden
                      and var.get("fits_v5e_16gib") is False)
        if documented and not strict:
            continue
        findings.append(Finding(
            "MEM301",
            f"variant '{name}' ({est['basis']}, batch {est['batch']} x "
            f"seq {est['seq']}) needs {est['live_gib']:.2f} GiB/chip but "
            f"the budget is {budget:.2f} GiB — OOM before step 1",
            file=file, severity=ERROR,
            extra={"variant": name, "live_gib": est["live_gib"],
                   "budget_gib": budget, "basis": est["basis"]}))

    # MEM302: an over-budget variant whose sibling at the same shape fits
    # — the remat/sharding headroom exists and is not taken.
    for name, (var, est, over) in fits_map.items():
        if not over:
            continue
        for other, (ovar, oest, oover) in fits_map.items():
            if other == name or oover:
                continue
            if (oest["batch"], oest["seq"]) != (est["batch"], est["seq"]):
                continue
            findings.append(Finding(
                "MEM302",
                f"variant '{name}' is over budget at "
                f"{est['live_gib']:.2f} GiB but sibling '{other}' "
                f"(remat={ovar.get('remat')}, "
                f"variant={ovar.get('variant')}) fits at "
                f"{oest['live_gib']:.2f} GiB — remat/sharding headroom "
                "exists and is not taken",
                file=file, severity=WARNING,
                extra={"variant": name, "sibling": other}))
            break
    return findings


# ---------------------------------------------------------------------------
# Gateway serving buckets
# ---------------------------------------------------------------------------

def _serving_rungs(seq_max: int, rungs=None) -> List[int]:
    if rungs:
        return sorted(int(r) for r in rungs)
    try:
        from ..perf.buckets import BucketLadder
        return list(BucketLadder.pow2(lo=128, hi=seq_max).buckets)
    except Exception:  # standalone CLI: equivalent pow2 ladder
        out, b = [], 128
        while b < seq_max:
            out.append(b)
            b *= 2
        out.append(seq_max)
        return out


def serving_bucket_report(plan: dict, mesh_size: Optional[int] = None,
                          hbm_gib: Optional[float] = None,
                          dims: Optional[dict] = None,
                          max_batch: int = 8, rungs=None,
                          kv_dtype_bytes: int = 2,
                          file: str = "<plan>") -> dict:
    """Price the gateway serving buckets against the per-chip budget.

    Serving shards tensor-parallel over the mesh: bf16 weights 2P/N per
    chip, attention heads split N-ways (SH201 when the head count does
    not divide), and per-sequence KV cache 2·L·S·H·kv_bytes/N per rung.
    Returns ``{"rows", "findings"}``; over-budget rungs flag MEM301.
    """
    d = dict(_sharding.LLAMA7B_DIMS, **(dims or {}))
    n = mesh_size or _plan_chips(plan)
    budget = hbm_gib if hbm_gib is not None else float(
        plan.get("hbm_per_chip_gib", 16.0))
    n_params = None
    seq_max = 0
    for var in plan.get("variants", ()):
        n_params = n_params or var.get("n_params")
        seq_max = max(seq_max, var.get("seq", 0))
    n_params = n_params or 6738415616
    seq_max = seq_max or 2048

    findings: List[Finding] = []
    for key in ("heads", "kv_heads"):
        if d[key] % n:
            findings.append(Finding(
                "SH201",
                f"serving TP shards attention over {n} chips but "
                f"{key}={d[key]} is not divisible by {n}",
                file=file, severity=ERROR,
                extra={"param": key, "degree": n}))

    weights = 2.0 * n_params / n
    rows = []
    for s in _serving_rungs(seq_max, rungs):
        kv_per_seq = 2.0 * d["L"] * s * d["H"] * kv_dtype_bytes / n
        logits = max_batch * d["V"] * 4.0
        live = weights + max_batch * kv_per_seq + logits
        fits = live <= budget * GIB
        rows.append({"bucket": s, "max_batch": max_batch,
                     "weights_gib": round(weights / GIB, 3),
                     "kv_gib": round(max_batch * kv_per_seq / GIB, 3),
                     "live_gib": round(live / GIB, 3), "fits": fits})
        if not fits:
            findings.append(Finding(
                "MEM301",
                f"serving bucket seq={s} at batch {max_batch} needs "
                f"{live / GIB:.2f} GiB/chip (weights "
                f"{weights / GIB:.2f} + KV "
                f"{max_batch * kv_per_seq / GIB:.2f}) over the "
                f"{budget:.2f} GiB budget",
                file=file, severity=ERROR,
                extra={"bucket": s, "live_gib": live / GIB}))
    return {"rows": rows, "findings": findings}
