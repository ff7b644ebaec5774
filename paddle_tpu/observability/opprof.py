"""Op-level compiled-program observatory (ISSUE 16 tentpole).

``roofline_attr`` explains a step's MFU gap at *phase* granularity
(compute / memory / overhead / comm:axis) but every optimization the
gap is supposed to direct — Pallas decode attention, quantized KV,
remat tuning — is an *op-level* decision. This module closes that
resolution gap without any runtime sampling: it reads the numbers XLA
already computed at compile time.

Three layers, all deterministic on the CPU backend:

  * **Taxonomy** — ``canon_op`` / ``classify_op`` map any op name (an
    optimized-HLO opcode, a fused-computation member, or an xplane
    trace op) into one shared bucket scheme::

        matmul | attention | collective | elementwise | reduce |
        data-movement | other

    A device trace is read by hand with ``python -m chipbench.phases
    <trace>``, which names its rows by executable and model scope.

  * **Capture** — ``maybe_capture(label, jitted, args)`` AOT-lowers an
    already-built ``jax.jit`` callable at its live argument tuple,
    reads ``lowered.compile().cost_analysis()`` (module totals) and the
    optimized HLO text (per-op/per-fusion FLOPs, bytes-accessed and
    output bytes; ``while`` bodies are expanded by their
    ``known_trip_count``), and files an :class:`OpProfile` under the
    label. ``jit.TrainStep`` (single-device and ``mesh_plan=``),
    ``hapi.Model.prepare(jit=True)`` and the serving batchers'
    compiled prefill/decode call the hook at their warm transitions —
    a zero-cost no-op until :func:`enable` (or ``PADDLE_OPPROF=1``).

  * **Attribution + artifacts** — :func:`publish_gap_attribution`
    splits each ``roofline.gap_attribution`` phase across op classes
    (classes tile each phase's fraction exactly);
    :func:`write_artifact` persists ``OPPROF_r*.json`` with
    per-executable fingerprints and recompile counts, and
    :func:`diff` names exactly which ops appeared / disappeared /
    changed cost between two artifacts — a recompile storm or a
    fusion regression becomes a named finding instead of silent
    step-time drift. ``tools/profile_report.py`` is the CLI;
    ``tools/bench_guard.py`` gates the ``opprof:`` lane.

Module-level imports are stdlib-only on purpose: tools load this file
standalone (``importlib`` from path) for the taxonomy and artifact
views without paying the ``paddle_tpu``/jax import. Anything that
needs jax or the metrics registry imports lazily inside the function.
"""
from __future__ import annotations

import glob
import hashlib
import json
import os
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "OP_CLASSES", "canon_op", "classify_op",
    "enable", "disable", "enabled", "reset_captures",
    "maybe_capture", "profile_compiled", "profile_hlo_text",
    "OpProfile", "get_captures", "recompile_counts",
    "op_class_table", "top_op_classes",
    "attribute_gap", "publish_gap_attribution",
    "write_artifact", "load_artifact", "artifact_paths", "diff",
    "bench_summary",
]

# The shared bucket scheme. Order is significant: it is the tie-break
# and display order everywhere (reports, gauges, artifacts).
OP_CLASSES = ("matmul", "attention", "collective", "elementwise",
              "reduce", "data-movement", "quant", "other")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# ---------------------------------------------------------------------------
# Taxonomy
# ---------------------------------------------------------------------------

# HLO opcodes / xplane op names by class. Names are matched after
# canonicalization ('-' and '_' fold to '-', instance ids dropped).
_MATMUL = {"dot", "dot-general", "convolution", "conv", "gemm",
           "cublas-gemm", "einsum", "matmul"}
_COLLECTIVE = {"all-reduce", "all-gather", "reduce-scatter",
               "all-to-all", "collective-permute", "collective-broadcast",
               "all-reduce-start", "all-reduce-done", "all-gather-start",
               "all-gather-done", "collective-permute-start",
               "collective-permute-done", "psum", "ppermute", "pmax",
               "pmin", "send", "send-done", "recv", "recv-done",
               "partition-id", "replica-id"}
_REDUCE = {"reduce", "reduce-window", "argmax", "argmin", "sort",
           "reduce-sum", "reduce-max", "reduce-min", "reduce-and",
           "reduce-or", "reduce-precision", "cumsum", "cumprod",
           "select-and-scatter", "topk", "top-k"}
_DATA_MOVEMENT = {"copy", "copy-start", "copy-done", "transpose",
                  "reshape", "broadcast", "broadcast-in-dim",
                  "concatenate", "slice", "dynamic-slice",
                  "dynamic-update-slice", "gather", "scatter", "pad",
                  "convert", "convert-element-type", "bitcast",
                  "bitcast-convert", "reverse", "infeed", "outfeed",
                  "tuple", "get-tuple-element", "parameter", "constant",
                  "iota", "after-all", "domain", "optimization-barrier"}
_TRANSCENDENTAL = {"tanh", "exp", "expm1", "log", "log1p", "logistic",
                   "sqrt", "rsqrt", "cbrt", "power", "pow", "erf",
                   "erf-inv", "sin", "cos", "tan", "atan2", "sigmoid"}
_ELEMENTWISE = _TRANSCENDENTAL | {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "abs", "negate", "sign", "floor", "ceil", "round",
    "round-nearest-afz", "round-nearest-even", "clamp", "select",
    "compare", "and", "or", "xor", "not", "is-finite", "remainder",
    "shift-left", "shift-right-logical", "shift-right-arithmetic",
    "rem", "real", "imag", "complex", "map", "rng", "rng-bit-generator",
    "rng-get-and-update-state", "clz", "popcnt", "stochastic-convert",
    # jax primitive spellings — fusion classification falls back to the
    # op_name scope tail, which uses these rather than the HLO opcodes
    "mul", "sub", "div", "max", "min", "neg", "pow", "integer-pow",
    "square", "erf", "erfc", "erf-inv", "logistic"}
_ATTENTION_HINTS = ("flash", "attention", "attn", "mha",
                    "scaled-dot-product", "softmax")
# serving-quantization scopes (decode_attention's cachekv_quant /
# cachekv_dequant, _ConvertedLinear's weight_dequant). Checked BEFORE
# the attention hints: the inline cache dequant lives inside the
# attention computation, and "how much am I paying to (de)quantize" is
# exactly the attribution the quant lane needs split out.
_QUANT_HINTS = ("cachekv-quant", "cachekv-dequant", "weight-dequant",
                "quantize", "dequant")


def canon_op(name: str) -> str:
    """Collapse op instances to a stable identity: ``fusion.123`` ->
    ``fusion``, trailing HLO ids dropped, ``_`` folded to ``-`` (HLO
    opcode spelling) for set lookups."""
    name = re.sub(r"\.\d+$", "", name)
    name = re.sub(r"\d+$", "", name) or name
    return name.strip().replace("_", "-")


def classify_op(name: str, path: str = "") -> str:
    """Map one op (HLO opcode, fused-op name, or xplane trace op) into
    the shared class scheme. ``path`` is optional context (an HLO
    ``metadata op_name`` scope or a fusion's member list) — a dot
    inside an attention scope classifies as ``attention``, which is
    the attribution we want (attention matmuls vs projection matmuls
    are different optimization targets)."""
    c = canon_op(name).lower()
    ctx = (path or "").lower().replace("_", "-")
    if any(h in ctx for h in _QUANT_HINTS) \
            or any(h in c for h in _QUANT_HINTS):
        return "quant"
    if any(h in ctx for h in _ATTENTION_HINTS) \
            or any(h in c for h in _ATTENTION_HINTS):
        return "attention"
    if c in _MATMUL or c.startswith(("dot", "conv", "gemm")):
        return "matmul"
    if c in _COLLECTIVE or c.startswith(("all-", "collective-",
                                         "reduce-scatter")):
        return "collective"
    if c in _REDUCE or c.startswith("reduce"):
        return "reduce"
    if c in _DATA_MOVEMENT or c.startswith(("copy", "transpose",
                                            "reshape", "broadcast",
                                            "slice", "dynamic-")):
        return "data-movement"
    if c in _ELEMENTWISE:
        return "elementwise"
    return "other"


# ---------------------------------------------------------------------------
# HLO text parsing: per-op FLOPs / bytes from the optimized module
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1, "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_META_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]+)"')
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_BODY_RE = re.compile(r"body=%([\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[":{\s]+n[":\s]+"?(\d+)')
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_WINDOW_RE = re.compile(r"window=\{[^}]*?size=([0-9x]+)")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')
_OPCODE_RE = re.compile(r"([a-z][a-z0-9\-]*)\(")

_SKIP_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "after-all", "bitcast", "domain"}


def _shape_bytes(dtype: str, dims: str) -> Tuple[int, int]:
    """(elements, bytes) of one ``dtype[d0,d1,...]`` shape literal."""
    n = 1
    for d in dims.split(","):
        d = d.strip()
        if d:
            n *= int(d)
    return n, n * _DTYPE_BYTES.get(dtype, 4)


def _split_result_operands(rest: str) -> Tuple[str, str, str, str]:
    """Split one instruction's RHS into (result_types, opcode,
    operand_segment, attrs). The operand segment is the top-level
    paren group right after the opcode (operand types can nest parens
    for tuple-typed operands)."""
    m = _OPCODE_RE.search(rest)
    if m is None:
        return rest, "", "", ""
    opcode = m.group(1)
    result = rest[:m.start()]
    i = m.end() - 1  # at the '('
    depth = 0
    j = i
    for j in range(i, len(rest)):
        if rest[j] == "(":
            depth += 1
        elif rest[j] == ")":
            depth -= 1
            if depth == 0:
                break
    return result, opcode, rest[i + 1:j], rest[j + 1:]


class _Instr:
    __slots__ = ("name", "opcode", "out_elems", "out_bytes",
                 "operand_bytes", "attrs", "operands", "path")

    def __init__(self, name, opcode, out_elems, out_bytes,
                 operand_bytes, attrs, operands, path):
        self.name = name
        self.opcode = opcode
        self.out_elems = out_elems
        self.out_bytes = out_bytes
        self.operand_bytes = operand_bytes
        self.attrs = attrs
        self.operands = operands
        self.path = path


def _parse_computations(text: str) -> Tuple[Dict[str, List[_Instr]],
                                            Optional[str]]:
    """All computations in an HLO module: name -> instruction list,
    plus the ENTRY computation's name."""
    comps: Dict[str, List[_Instr]] = {}
    entry = None
    current: Optional[List[_Instr]] = None
    for line in text.splitlines():
        stripped = line.strip()
        # A computation header is '%name (params...) -> type {' — the
        # param list can NEST parens (tuple-typed args), so detect by
        # shape (ends with '{', no '=' before the param list) rather
        # than by a paren-balanced regex.
        if stripped.endswith("{") and "=" not in stripped.split("(")[0]:
            cm = _COMP_RE.match(stripped)
            if cm:
                current = comps.setdefault(cm.group(1), [])
                if stripped.startswith("ENTRY"):
                    entry = cm.group(1)
                continue
        im = _INSTR_RE.match(line)
        if im is None or current is None:
            continue
        name, rest = im.group(1), im.group(2)
        result, opcode, operands, attrs = _split_result_operands(rest)
        if not opcode:
            continue
        out_elems = out_bytes = 0
        for dt, dims in _SHAPE_RE.findall(result):
            e, b = _shape_bytes(dt, dims)
            out_elems += e
            out_bytes += b
        operand_bytes = 0
        for dt, dims in _SHAPE_RE.findall(operands):
            operand_bytes += _shape_bytes(dt, dims)[1]
        meta = _META_RE.search(attrs)
        path = meta.group(1) if meta else ""
        current.append(_Instr(name, opcode, out_elems, out_bytes,
                              operand_bytes, attrs, operands, path))
    return comps, entry


def _dot_flops(ins: _Instr) -> float:
    """2 * prod(out) * K for a dot; K from the lhs contracting dims."""
    cm = _CONTRACT_RE.search(ins.attrs)
    shapes = _SHAPE_RE.findall(ins.operands)
    if cm is None or not shapes:
        return 2.0 * ins.out_elems
    lhs_dims = [int(d) for d in shapes[0][1].split(",") if d.strip()]
    k = 1
    for idx in cm.group(1).split(","):
        idx = idx.strip()
        if idx and int(idx) < len(lhs_dims):
            k *= lhs_dims[int(idx)]
    return 2.0 * ins.out_elems * max(k, 1)


def _conv_flops(ins: _Instr) -> float:
    """~2 * prod(out) * prod(window) * C_in (kernel = window x Cin x
    Cout; estimate Cin as kernel_elems / (window * Cout-from-output))."""
    wm = _WINDOW_RE.search(ins.attrs)
    window = 1
    if wm:
        for d in wm.group(1).split("x"):
            window *= int(d)
    shapes = _SHAPE_RE.findall(ins.operands)
    kernel_elems = 1
    if len(shapes) >= 2:
        kernel_elems = _shape_bytes(*shapes[1])[0]
    return 2.0 * ins.out_elems * max(kernel_elems // max(window, 1), 1) \
        * window / max(window, 1) * (window if window > 1 else 1)


def _instr_cost(ins: _Instr, comps: Dict[str, List[_Instr]],
                depth: int = 0) -> Tuple[float, float, float, str]:
    """(flops, bytes_accessed, transcendentals, op_class) of one
    instruction; fusion/while/call expand their called computations."""
    op = canon_op(ins.opcode)
    if op == "fusion" or op == "call":
        called = _CALLS_RE.search(ins.attrs) or _TO_APPLY_RE.search(
            ins.attrs)
        f = b = t = 0.0
        classes: Dict[str, float] = {}
        if called and called.group(1) in comps and depth < 8:
            for m in comps[called.group(1)]:
                if canon_op(m.opcode) in _SKIP_OPS:
                    continue
                mf, _mb, mt, mc = _instr_cost(m, comps, depth + 1)
                f += mf
                t += mt
                classes[mc] = classes.get(mc, 0.0) + (mf or m.out_elems)
        # a fusion's memory traffic is its boundary, not its members
        b = float(ins.operand_bytes + ins.out_bytes)
        # the op_name scope tail ('.../reduce_sum') names the producing
        # jaxpr primitive — better identity than the fusion's own name,
        # which XLA prefixes with the FIRST member's opcode (a
        # 'broadcast_multiply_fusion' is a multiply, not a broadcast)
        cls = "other"
        if ins.path:
            cls = classify_op(ins.path.split("/")[-1], ins.path)
        if cls == "other":
            cls = classify_op(ins.name, ins.path)
        if cls == "other" and classes:
            cls = max(classes.items(),
                      key=lambda kv: (kv[1], -OP_CLASSES.index(kv[0])))[0]
        return f, b, t, cls
    if op == "while":
        body = _BODY_RE.search(ins.attrs)
        trip = 1
        tm = _TRIP_RE.search(ins.attrs)
        if tm:
            trip = max(int(tm.group(1)), 1)
        f = b = t = 0.0
        if body and body.group(1) in comps and depth < 8:
            for m in comps[body.group(1)]:
                if canon_op(m.opcode) in _SKIP_OPS:
                    continue
                mf, mb, mt, _ = _instr_cost(m, comps, depth + 1)
                f += mf
                b += mb
                t += mt
        return f * trip, b * trip, t * trip, "other"
    if op == "conditional":
        return 0.0, float(ins.operand_bytes + ins.out_bytes), 0.0, "other"
    cls = classify_op(ins.opcode, ins.path)
    bytes_acc = float(ins.operand_bytes + ins.out_bytes)
    if op in ("dot", "dot-general"):
        return _dot_flops(ins), bytes_acc, 0.0, cls
    if op in ("convolution", "conv"):
        return _conv_flops(ins), bytes_acc, 0.0, cls
    if op == "custom-call":
        tgt = _TARGET_RE.search(ins.attrs)
        if tgt:
            cls = classify_op(tgt.group(1), ins.path)
        return 2.0 * ins.out_elems, bytes_acc, 0.0, cls
    if op in _TRANSCENDENTAL:
        return float(ins.out_elems), bytes_acc, float(ins.out_elems), cls
    if cls == "reduce":
        # a reduction reads its input once: elements ~ operand elems
        return float(max(ins.operand_bytes // 4, ins.out_elems)), \
            bytes_acc, 0.0, cls
    if cls in ("data-movement", "collective"):
        return 0.0, bytes_acc, 0.0, cls
    return float(ins.out_elems), bytes_acc, 0.0, cls


def _display_name(ins: _Instr) -> str:
    """Stable human identity for diffing: the metadata op_name tail
    (scope path without the jit(...) wrappers), else the canon HLO
    name. ``while``-body members keep their scope so a scan-body dot
    stays distinguishable from a top-level dot."""
    if ins.path:
        parts = [p for p in ins.path.split("/")
                 if p and not p.startswith("jit(")]
        if parts:
            return "/".join(parts[-3:])
    return canon_op(ins.name)


def profile_hlo_text(text: str, label: str = "",
                     xla_totals: Optional[dict] = None) -> "OpProfile":
    """Parse one optimized-HLO module into an :class:`OpProfile`.

    Deterministic: same text -> same profile (the fingerprint is the
    sha1 of the text). ``while`` bodies are expanded by their
    ``known_trip_count`` backend config (1 when absent)."""
    comps, entry = _parse_computations(text)
    rows: Dict[Tuple[str, str], dict] = {}

    def _emit(ins: _Instr, mult: float):
        op = canon_op(ins.opcode)
        if op in _SKIP_OPS:
            return
        if op == "while":
            body = _BODY_RE.search(ins.attrs)
            trip = 1
            tm = _TRIP_RE.search(ins.attrs)
            if tm:
                trip = max(int(tm.group(1)), 1)
            if body and body.group(1) in comps:
                for m in comps[body.group(1)]:
                    _emit(m, mult * trip)
                return
        f, b, t, cls = _instr_cost(ins, comps)
        key = (_display_name(ins), cls)
        row = rows.setdefault(key, {
            "op": key[0], "class": cls, "flops": 0.0, "bytes": 0.0,
            "out_bytes": 0.0, "transcendentals": 0.0, "count": 0})
        row["flops"] += f * mult
        row["bytes"] += b * mult
        row["out_bytes"] += float(ins.out_bytes) * mult
        row["transcendentals"] += t * mult
        row["count"] += int(mult) if mult >= 1 else 1

    for ins in comps.get(entry or "", []):
        _emit(ins, 1.0)
    ops = sorted(rows.values(),
                 key=lambda r: (-r["flops"], -r["bytes"], r["op"]))
    fingerprint = hashlib.sha1(text.encode()).hexdigest()[:16]
    op_paths = {ins.name: ins.path for instrs in comps.values()
                for ins in instrs if ins.path}
    return OpProfile(label=label, fingerprint=fingerprint, ops=ops,
                     xla_totals=dict(xla_totals or {}), op_paths=op_paths)


# ---------------------------------------------------------------------------
# OpProfile
# ---------------------------------------------------------------------------

def _peaks() -> Tuple[float, float]:
    """(peak_flops/s, peak_hbm bytes/s) for the cost-unit time model —
    ROOFLINE.json when present, else v5e-class constants. Only RATIOS
    of cost units matter (shares), so the absolute scale is free."""
    path = os.environ.get("PADDLE_ROOFLINE") or os.path.join(
        _REPO, "ROOFLINE.json")
    try:
        with open(path) as f:
            d = json.load(f)
        return (float(d.get("peak_flops") or 197e12),
                float(d.get("peak_hbm") or 819e9))
    except (OSError, ValueError):
        return 197e12, 819e9


class OpProfile:
    """Per-op cost profile of ONE compiled executable. ``op_paths`` maps
    an instruction's name, which is what a device trace calls its events,
    to the ``op_name`` of its metadata (``jit(serving_paged_decode)/
    paged_attention/kv_gather/gather``): the model's scopes of an event."""

    def __init__(self, label: str, fingerprint: str, ops: List[dict],
                 xla_totals: Optional[dict] = None,
                 op_paths: Optional[Dict[str, str]] = None):
        self.label = label
        self.fingerprint = fingerprint
        self.ops = ops
        self.xla_totals = dict(xla_totals or {})
        self.op_paths = dict(op_paths or {})

    # -- derived views ------------------------------------------------------
    def cost_units(self) -> Dict[str, float]:
        """Roofline time-model cost per op row: max(flops/peak,
        bytes/bw) — the per-op analog of t_ideal. Keyed by op name."""
        pf, pb = _peaks()
        return {r["op"]: max(r["flops"] / pf, r["bytes"] / pb)
                for r in self.ops}

    def op_class_table(self) -> Dict[str, dict]:
        """Aggregate by class: flops, bytes, cost units + shares."""
        pf, pb = _peaks()
        table = {c: {"flops": 0.0, "bytes": 0.0, "cost": 0.0, "n_ops": 0}
                 for c in OP_CLASSES}
        for r in self.ops:
            t = table[r["class"]]
            t["flops"] += r["flops"]
            t["bytes"] += r["bytes"]
            t["cost"] += max(r["flops"] / pf, r["bytes"] / pb)
            t["n_ops"] += 1
        total = sum(t["cost"] for t in table.values()) or 1.0
        for t in table.values():
            t["cost_share"] = t["cost"] / total
        return table

    def top_ops(self, k: int = 10) -> List[dict]:
        cu = self.cost_units()
        return sorted(self.ops, key=lambda r: -cu[r["op"]])[:k]

    def totals(self) -> dict:
        return {
            "flops": sum(r["flops"] for r in self.ops),
            "bytes": sum(r["bytes"] for r in self.ops),
            "n_ops": sum(r["count"] for r in self.ops),
            "xla": self.xla_totals,
        }

    # -- (de)serialization --------------------------------------------------
    def to_dict(self) -> dict:
        return {"label": self.label, "fingerprint": self.fingerprint,
                "ops": self.ops, "xla_totals": self.xla_totals}

    @classmethod
    def from_dict(cls, d: dict) -> "OpProfile":
        return cls(label=d.get("label", ""),
                   fingerprint=d.get("fingerprint", ""),
                   ops=list(d.get("ops") or []),
                   xla_totals=d.get("xla_totals") or {})


def op_class_table(profile: OpProfile) -> Dict[str, dict]:
    return profile.op_class_table()


def top_op_classes(profile: OpProfile, k: int = 5) -> List[Tuple[str,
                                                                 float]]:
    """[(class, cost_share), ...] descending, zero-share classes
    dropped."""
    table = profile.op_class_table()
    pairs = [(c, round(t["cost_share"], 6)) for c, t in table.items()
             if t["cost_share"] > 0]
    return sorted(pairs, key=lambda kv: -kv[1])[:k]


# ---------------------------------------------------------------------------
# Capture registry (process-wide, like the metrics registry)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_ENABLED = [False]
_CAPTURES: Dict[str, List[OpProfile]] = {}
_CAPTURE_FAILURES = [0]


def enabled() -> bool:
    return _ENABLED[0] or os.environ.get("PADDLE_OPPROF", "") not in (
        "", "0")


def enable() -> None:
    _ENABLED[0] = True


def disable() -> None:
    _ENABLED[0] = False


def reset_captures() -> None:
    with _LOCK:
        _CAPTURES.clear()
        _CAPTURE_FAILURES[0] = 0


def get_captures() -> Dict[str, List[OpProfile]]:
    with _LOCK:
        return {k: list(v) for k, v in _CAPTURES.items()}


def recompile_counts() -> Dict[str, int]:
    """Executable builds per label. >1 for a label that should compile
    once is a recompile — the storm detector's raw number."""
    with _LOCK:
        return {k: len(v) for k, v in _CAPTURES.items()}


def profile_compiled(compiled, label: str = "") -> OpProfile:
    """Profile an AOT-compiled jax executable (``lowered.compile()``
    result): module totals from ``cost_analysis()``, per-op rows from
    the optimized HLO text."""
    totals: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)) and ca:
            ca = ca[0]
        if isinstance(ca, dict):
            totals = {k: float(v) for k, v in ca.items()
                      if k in ("flops", "bytes accessed",
                               "transcendentals")}
    except Exception:  # backend without cost analysis: text-only
        totals = {}
    text = compiled.as_text()
    return profile_hlo_text(text, label=label, xla_totals=totals)


def maybe_capture(label: str, jitted, args: tuple,
                  kwargs: Optional[dict] = None) -> Optional[OpProfile]:
    """Capture hook the compiled paths call at their warm transition.

    No-op (and free) unless :func:`enabled`. AOT lowering only traces
    avals — donated live buffers are untouched and nothing executes;
    on TPU the persistent compile cache absorbs the AOT compile.
    Must never take down the caller: any failure increments
    ``opprof.capture_failures`` and returns None."""
    if not enabled():
        return None
    try:
        compiled = jitted.lower(*args, **(kwargs or {})).compile()
        prof = profile_compiled(compiled, label=label)
        with _LOCK:
            _CAPTURES.setdefault(label, []).append(prof)
        try:
            from paddle_tpu.observability.metrics import get_registry
            get_registry().counter(
                "opprof.captures_total",
                "compiled-executable cost profiles captured, by label",
                labelnames=("label",)).labels(label=label).inc()
        except Exception:
            pass
        return prof
    except Exception:
        _CAPTURE_FAILURES[0] += 1
        try:
            from paddle_tpu.observability.metrics import get_registry
            get_registry().counter(
                "opprof.capture_failures",
                "opprof capture attempts that raised (hook is "
                "best-effort by contract)").inc()
        except Exception:
            pass
        return None


def _latest_profile(prefer: str = "train") -> Optional[OpProfile]:
    """Newest capture, preferring labels containing ``prefer``."""
    with _LOCK:
        if not _CAPTURES:
            return None
        for lbl, profs in _CAPTURES.items():
            if prefer in lbl and profs:
                return profs[-1]
        for profs in _CAPTURES.values():
            if profs:
                return profs[-1]
    return None


# ---------------------------------------------------------------------------
# Gap attribution: phase fractions -> per-op-class gauges
# ---------------------------------------------------------------------------

def _tile_exactly(total: float, weights: Dict[str, float]
                  ) -> Dict[str, float]:
    """Split ``total`` over OP_CLASSES proportional to ``weights`` so
    the parts sum to ``total`` EXACTLY (fp residual folded into the
    largest part) — the tiling contract the tests assert."""
    out = {c: 0.0 for c in OP_CLASSES}
    wsum = sum(w for w in weights.values() if w > 0)
    if total <= 0:
        return out
    if wsum <= 0:
        out["other"] = total
        return out
    for c in OP_CLASSES:
        out[c] = total * max(weights.get(c, 0.0), 0.0) / wsum
    largest = max(out, key=lambda c: out[c])
    out[largest] += total - sum(out.values())
    return out


def attribute_gap(attr: dict, profile: OpProfile
                  ) -> Dict[str, Dict[str, float]]:
    """Split each roofline phase fraction across op classes.

    ``attr`` is :func:`roofline_attr.observe_train_step`'s return
    (``compute_frac`` / ``memory_frac`` / ``overhead_frac`` +
    optional ``comm_fracs``). Weighting per phase:

      * compute  — class FLOPs share (MXU time is flops-proportional);
      * memory   — class bytes-accessed share (exposed HBM);
      * overhead — class cost-unit share (dispatch/host cost tracks
        how many op-seconds each class puts on the timeline);
      * comm:axis — entirely ``collective``.

    Classes tile each phase exactly: for every phase,
    ``sum(split[phase].values()) == attr[phase_frac]``."""
    table = profile.op_class_table()
    flops_w = {c: t["flops"] for c, t in table.items()}
    bytes_w = {c: t["bytes"] for c, t in table.items()}
    cost_w = {c: t["cost"] for c, t in table.items()}
    split = {
        "compute": _tile_exactly(float(attr.get("compute_frac", 0.0)),
                                 flops_w),
        "memory": _tile_exactly(float(attr.get("memory_frac", 0.0)),
                                bytes_w),
        "overhead": _tile_exactly(float(attr.get("overhead_frac", 0.0)),
                                  cost_w),
    }
    for axis, frac in (attr.get("comm_fracs") or {}).items():
        part = {c: 0.0 for c in OP_CLASSES}
        part["collective"] = float(frac)
        split[f"comm:{axis}"] = part
    return split


def publish_gap_attribution(attr: dict,
                            profile: Optional[OpProfile] = None
                            ) -> Optional[Dict[str, Dict[str, float]]]:
    """Publish ``roofline.gap_attribution_opclass{phase,op_class}``
    from the newest train-step capture (or an explicit profile).
    Returns the split, or None when no profile is available — callers
    (roofline_attr) treat that as a silent no-op."""
    if profile is None:
        profile = _latest_profile(prefer="train")
    if profile is None:
        return None
    split = attribute_gap(attr, profile)
    try:
        from paddle_tpu.observability.metrics import get_registry
        g = get_registry().gauge(
            "roofline.gap_attribution_opclass",
            "per-phase step-time fractions split by op class (classes "
            "tile each roofline.gap_attribution phase exactly)",
            labelnames=("phase", "op_class"))
        for phase, parts in split.items():
            for cls in OP_CLASSES:
                g.labels(phase=phase, op_class=cls).set(parts[cls])
    except Exception:
        pass
    return split


# ---------------------------------------------------------------------------
# Artifacts: OPPROF_r*.json + diff
# ---------------------------------------------------------------------------

def artifact_paths(dirpath: Optional[str] = None) -> List[str]:
    d = dirpath or _REPO
    rx = re.compile(r"OPPROF_r(\d+)\.json$")
    paths = [p for p in glob.glob(os.path.join(d, "OPPROF_r*.json"))
             if rx.search(os.path.basename(p))]
    return sorted(paths, key=lambda p: int(
        rx.search(os.path.basename(p)).group(1)))


def load_artifact(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return None
    # driver dry-run wrappers ({n, cmd, rc, tail}) are not artifacts
    if not isinstance(d, dict) or "captures" not in d:
        return None
    return d


def write_artifact(dirpath: Optional[str] = None, tpu: bool = False,
                   extra: Optional[dict] = None,
                   gap_attribution: Optional[dict] = None,
                   path: Optional[str] = None) -> Optional[str]:
    """Persist the capture registry as the next ``OPPROF_rNN.json``.

    The artifact is self-contained: latest profile per label (full op
    table), per-label recompile counts and fingerprint history, the
    headline top-op-class share the bench_guard ``opprof:`` lane
    gates, and the newest per-op-class gap split when one was
    published. Returns the path, or None when nothing was captured."""
    caps = get_captures()
    if not caps:
        return None
    d = dirpath or _REPO
    if path is None:
        existing = artifact_paths(d)
        rx = re.compile(r"OPPROF_r(\d+)\.json$")
        nxt = (int(rx.search(os.path.basename(existing[-1])).group(1))
               + 1) if existing else 0
        path = os.path.join(d, f"OPPROF_r{nxt:02d}.json")
    profiles = {lbl: profs[-1] for lbl, profs in caps.items() if profs}
    headline_prof = (_latest_profile(prefer="train")
                     or next(iter(profiles.values())))
    top = top_op_classes(headline_prof, k=len(OP_CLASSES))
    doc = {
        "kind": "opprof",
        "tpu": bool(tpu),
        "captures": {lbl: p.to_dict() for lbl, p in profiles.items()},
        "recompiles": recompile_counts(),
        "fingerprints": {lbl: [p.fingerprint for p in profs]
                         for lbl, profs in caps.items()},
        "capture_failures": _CAPTURE_FAILURES[0],
        "headline": {
            "label": headline_prof.label,
            "fingerprint": headline_prof.fingerprint,
            "top_class": top[0][0] if top else "other",
            "top_share": top[0][1] if top else 0.0,
            "top_op_classes": top,
            "n_recompiles": max(
                sum(recompile_counts().values())
                - len(recompile_counts()), 0),
        },
    }
    if gap_attribution:
        doc["gap_attribution"] = gap_attribution
    if extra:
        doc.update(extra)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    return path


def diff(old: dict, new: dict, share_tol: float = 0.02) -> dict:
    """Name exactly which ops appeared / disappeared / changed cost
    between two OPPROF artifacts (or two ``{label: profile_dict}``
    capture maps). ``changed`` lists ops whose cost share moved more
    than ``share_tol`` absolute. Also reports per-label fingerprint
    flips and recompile-count growth — the named form of a recompile
    storm."""
    old_caps = old.get("captures", old) or {}
    new_caps = new.get("captures", new) or {}

    def _shares(caps) -> Dict[str, float]:
        pf, pb = _peaks()
        cost: Dict[str, float] = {}
        for lbl, pd in caps.items():
            for r in (pd.get("ops") or []):
                key = f"{lbl}:{r['op']}"
                cost[key] = cost.get(key, 0.0) + max(
                    r.get("flops", 0.0) / pf, r.get("bytes", 0.0) / pb)
        total = sum(cost.values()) or 1.0
        return {k: v / total for k, v in cost.items()}

    so, sn = _shares(old_caps), _shares(new_caps)
    appeared = sorted(k for k in sn if k not in so)
    disappeared = sorted(k for k in so if k not in sn)
    changed = []
    for k in sorted(set(so) & set(sn)):
        delta = sn[k] - so[k]
        if abs(delta) > share_tol:
            changed.append({"op": k, "old_share": round(so[k], 6),
                            "new_share": round(sn[k], 6),
                            "delta": round(delta, 6)})
    changed.sort(key=lambda c: -abs(c["delta"]))
    fp_changed = []
    for lbl in set(old_caps) & set(new_caps):
        of = (old_caps[lbl] or {}).get("fingerprint")
        nf = (new_caps[lbl] or {}).get("fingerprint")
        if of and nf and of != nf:
            fp_changed.append(lbl)
    ro = old.get("recompiles") or {}
    rn = new.get("recompiles") or {}
    storms = {lbl: {"old": ro.get(lbl, 0), "new": rn[lbl]}
              for lbl in rn if rn[lbl] > ro.get(lbl, rn[lbl])}
    return {"appeared": appeared, "disappeared": disappeared,
            "changed": changed, "fingerprint_changed": sorted(fp_changed),
            "recompile_growth": storms}


def bench_summary(top_k: int = 5) -> Optional[dict]:
    """The compact block bench.py embeds into ``BENCH_r*.json`` detail:
    top-k op-class cost table + executable fingerprint + recompiles."""
    prof = _latest_profile(prefer="train")
    if prof is None:
        return None
    return {
        "label": prof.label,
        "fingerprint": prof.fingerprint,
        "top_op_classes": top_op_classes(prof, k=top_k),
        "recompiles": recompile_counts(),
        "n_ops": sum(r["count"] for r in prof.ops),
    }
