"""Compiled execution path — the to_static analog.

Reference architecture (SURVEY.md §2.13, §3.4): paddle.jit.to_static captures
Python into a Program via AST transforms or the SOT frame-eval hook
(pybind/eval_frame.c, jit/sot/opcode_translator), appends a grad program, and
runs it on the StandaloneExecutor.

TPU-native redesign: capture-by-execution (core/capture.py) discovers the
function's implicit state in one eager pass, then the whole computation —
forward, tape backward, optimizer update — is staged as ONE pure jax function
and compiled by XLA into a single TPU executable (the CINN/StandaloneExecutor
role collapses into jax.jit + the PJRT executable cache). Guards are shape/
dtype/static-arg keys on the compile cache, the analog of SOT guards
(sot/opcode_translator executor guards).
"""
from __future__ import annotations

import functools
import os
import re
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..autograd import engine as _engine
from ..core import capture as _capture
from ..core import random as _random
from ..core.tensor import Tensor
from ..observability.tracing import span as _span
from ..ops import pallas as _pallas
from ..optimizer.clip import ClipGradByGlobalNorm
from ..perf import compile_cache as _cc
from ..perf.buckets import resolve_ladder as _resolve_ladder

__all__ = ["to_static", "not_to_static", "StaticFunction", "TrainStep",
           "enable_to_static"]

_TO_STATIC_ENABLED = [True]


def enable_to_static(flag: bool):
    _TO_STATIC_ENABLED[0] = flag


def _is_tensor(x):
    return isinstance(x, Tensor)


def _named(pure, label: str):
    """Give the traced function its label (dots to underscores) before
    ``jax.jit`` sees it, so that the device trace's module line and the
    optimized HLO read ``jit_<label>`` and not ``jit_pure`` for every
    executable the program compiles."""
    pure.__name__ = pure.__qualname__ = re.sub(r"\W", "_", label)
    return pure


def _sig_of(args, kwargs):
    """Cache key: tensor shapes/dtypes are dynamic; everything else static."""
    flat, treedef = jax.tree_util.tree_flatten((args, kwargs),
                                               is_leaf=_is_tensor)
    parts = []
    for x in flat:
        if _is_tensor(x):
            parts.append(("T", tuple(x.shape), str(x.dtype)))
        elif isinstance(x, (jax.Array, np.ndarray)):
            parts.append(("A", tuple(x.shape), str(x.dtype)))
        else:
            parts.append(("S", repr(x)))
    return (treedef, tuple(parts))


class StaticFunction:
    """Compiled wrapper (program_translator.py:StaticFunction analog).

    First call per input signature runs eagerly under a CaptureContext
    (the real step still happens — it doubles as warmup), discovering
    state reads/mutations/grad-writes/RNG use; subsequent calls hit a
    jax.jit-compiled pure function with that state threaded through.
    """

    def __init__(self, fn: Callable, input_spec=None, build_strategy=None,
                 backend=None, full_graph=True, batch_buckets=None,
                 seq_buckets=None, seq_axis=1, seq_mask_arg=None,
                 seq_unpad_outputs=True, donate_args=None):
        functools.update_wrapper(self, fn)
        self._fn = fn
        self._cache: Dict[Any, dict] = {}
        self._full_graph = full_graph
        # bucket specs go through the shared perf ladder policy: a list is
        # a custom ladder, "pow2"/"fixed:K" name the standard ones — the
        # trace-cache key then quantizes to O(#buckets) signatures
        self._buckets = _resolve_ladder(batch_buckets)
        self._seq_buckets = _resolve_ladder(seq_buckets)
        self._seq_axis = seq_axis
        self._seq_mask_arg = seq_mask_arg
        self._seq_unpad_outputs = seq_unpad_outputs
        # donate_args: indices of TOP-LEVEL POSITIONAL arguments whose
        # tensor buffers (every leaf, for pytree args) are donated to the
        # executable — XLA reuses them in place (e.g. a decode step's KV
        # caches, halving serving HBM traffic). Inference-only: donated
        # inputs are invalid after the call, so any grad-mode call on a
        # donating function raises up front.
        self._donate_args = tuple(donate_args) if donate_args else ()
        # ``_post(result)``, where a caller sets it: what to make of the
        # function's result, in the eager first call and in the traced
        # step alike. It runs after the function has returned, so no frame
        # of the caller's stands under the function's own call: how long
        # the eager first call takes to lower a Pallas kernel depends on
        # the depth of the Python stack there (PERF.md section 6, PR 39)
        self._post = None

    @property
    def code(self):
        import inspect
        return inspect.getsource(self._fn)

    def concrete_program(self, *args, **kwargs):
        return self._cache.get(_sig_of(args, kwargs))

    def __call__(self, *args, **kwargs):
        if not _TO_STATIC_ENABLED[0]:
            out = self._fn(*args, **kwargs)
            return out if self._post is None else self._post(out)
        if self._donate_args and _engine.is_grad_enabled():
            # fail fast and CONSISTENTLY (not only once compiled): donated
            # buffers die after the call, which would corrupt the tape
            raise RuntimeError(
                "to_static(donate_args=...) is inference-only: run under "
                "paddle.no_grad() (or drop donate_args)")
        if self._seq_buckets:
            return self._call_seq_bucketed(args, kwargs)
        return self._inner_dispatch(args, kwargs)

    def _inner_dispatch(self, args, kwargs):
        if self._buckets:
            return self._call_bucketed(args, kwargs)
        return self._dispatch(args, kwargs)

    def _dispatch(self, args, kwargs):
        key = _sig_of(args, kwargs)
        entry = self._cache.get(key)
        if entry is None:
            with _cc.timed_miss():
                entry = self._trace(args, kwargs)
            self._cache[key] = entry
            # pop so the cache doesn't pin the first call's autograd tape
            return entry.pop("first_out")
        _cc.note_hit()
        return self._run(entry, args, kwargs)

    # -- bucketed dynamic-batch compilation (SURVEY §7 hard part (d)) -------
    def _call_bucketed(self, args, kwargs):
        """Pad the leading (batch) dim of every batch-carrying tensor arg
        up to the next bucket, run the bucket's executable, slice outputs
        back — XLA's static-shape answer to dynamic batch sizes: a BOUNDED
        set of compilations instead of one per observed size. Opt-in and
        only valid for per-sample maps (no cross-batch reductions inside)."""
        leaves = [t for t in jax.tree_util.tree_leaves(
            (args, kwargs), is_leaf=_is_tensor) if _is_tensor(t)]
        batched = [t for t in leaves if t.ndim >= 1]
        if not batched:
            return self._dispatch(args, kwargs)
        b = batched[0].shape[0]
        if any(t.shape[0] != b for t in batched):
            return self._dispatch(args, kwargs)  # mixed leading dims
        bucket = self._buckets.bucket(b)
        if bucket == b:  # exact rung, or above the ladder (identity)
            return self._dispatch(args, kwargs)

        from .. import concat

        def pad(t):
            if _is_tensor(t) and t.ndim >= 1 and t.shape[0] == b:
                reps = [t[-1:]] * (bucket - b)
                return concat([t] + reps, axis=0)
            return t

        p_args, p_kwargs = jax.tree_util.tree_map(
            pad, (args, kwargs), is_leaf=_is_tensor)
        out = self._dispatch(p_args, p_kwargs)

        def unpad(t):
            if _is_tensor(t) and t.ndim >= 1 and t.shape[0] == bucket:
                return t[:b]
            return t

        return jax.tree_util.tree_map(unpad, out, is_leaf=_is_tensor)

    # -- bucketed dynamic-SEQUENCE compilation (SURVEY §7 hard part (d)) ----
    def _call_seq_bucketed(self, args, kwargs):
        """Pad dim `seq_axis` of every sequence-carrying tensor arg up to
        the next bucket and slice outputs back — O(log s_max) executables
        serve any sequence length instead of one trace/compile per length
        (the reference re-traces via SOT guards,
        jit/sot/opcode_translator/executor/function_graph.py:143; XLA's
        static shapes want padding instead).

        Exact for causal models as-is (real positions never attend to the
        right-padded tail). For bidirectional attention pass
        ``seq_mask_arg``: the wrapper synthesizes (or pads a caller's)
        keep-mask blocking the tail keys.

        Limitations (document-level contract, like batch_buckets'
        per-sample-map rule): every arg carrying the sequence must carry
        it at `seq_axis` (attention masks go through seq_mask_arg); an
        output whose `seq_axis` dim coincidentally EQUALS a bucket size
        would be sliced — models whose outputs carry no sequence axis
        (classifier heads) should pass seq_unpad_outputs=False.
        """
        leaves = [t for t in jax.tree_util.tree_leaves(
            (args, kwargs), is_leaf=_is_tensor) if _is_tensor(t)]
        ax = self._seq_axis
        seqful = [t for t in leaves if t.ndim > ax]
        if not seqful:
            return self._inner_dispatch(args, kwargs)
        s = seqful[0].shape[ax]
        bucket = self._seq_buckets.bucket(s)
        if bucket == s:  # exact rung, or above the ladder (identity)
            return self._inner_dispatch(args, kwargs)

        from .. import concat, zeros

        # locate the caller's mask whether it came by keyword OR position
        mask_name = self._seq_mask_arg
        user_mask = None
        mask_pos = None
        if mask_name:
            if mask_name in kwargs:
                user_mask = kwargs[mask_name]
            else:
                import inspect
                try:
                    params = list(
                        inspect.signature(self._fn).parameters)
                    pos = params.index(mask_name)
                    if pos < len(args):
                        mask_pos = pos
                        user_mask = args[pos]
                except ValueError:
                    pass

        def pad_seq(t):
            if not (_is_tensor(t) and t.ndim > ax and t.shape[ax] == s):
                return t
            if t is user_mask:
                return t  # handled below (needs blocking, not zero, fill)
            pshape = list(t.shape)
            pshape[ax] = bucket - s
            return concat([t, zeros(pshape, dtype=t.dtype)], axis=ax)

        p_args, p_kwargs = jax.tree_util.tree_map(
            pad_seq, (args, kwargs), is_leaf=_is_tensor)

        if mask_name:
            padded = self._padded_mask(user_mask, s, bucket)
            if mask_pos is not None:
                p_args = list(p_args)
                p_args[mask_pos] = padded
                p_args = tuple(p_args)
            else:
                p_kwargs = dict(p_kwargs)
                p_kwargs[mask_name] = padded
        out = self._inner_dispatch(p_args, p_kwargs)
        if not self._seq_unpad_outputs:
            return out

        def unpad(t):
            if _is_tensor(t) and t.ndim > ax and t.shape[ax] == bucket:
                idx = [slice(None)] * t.ndim
                idx[ax] = slice(0, s)
                return t[tuple(idx)]
            return t

        return jax.tree_util.tree_map(unpad, out, is_leaf=_is_tensor)

    @staticmethod
    def _padded_mask(user_mask, s, bucket):
        """Tail-blocking attention mask at the bucket size.

        No caller mask: a [1, 1, 1, bucket] bool keep-mask (tail keys
        dropped, broadcast over rows/heads). Caller mask with trailing
        [.., s, s]: padded to [.., bucket, bucket] — tail KEY columns
        blocked (False, or a dtype-safe large negative: -1e9 overflows
        fp16 to -inf and fully-blocked rows then NaN through softmax),
        tail query rows are sliced off the output so their fill is
        irrelevant.
        """
        import numpy as np

        from .. import to_tensor

        if user_mask is None:
            keep = np.zeros((1, 1, 1, bucket), dtype=bool)
            keep[..., :s] = True
            return to_tensor(keep)
        m = user_mask
        is_bool = "bool" in str(m.dtype)
        from .. import concat, full
        qs, ks = m.shape[-2], m.shape[-1]
        if is_bool:
            blocked = False
        else:
            np_dtype = np.dtype(str(m.dtype).replace("paddle.", ""))
            blocked = (float(np.finfo(np_dtype).min) / 2
                       if np.issubdtype(np_dtype, np.floating) else -1e9)
        if ks == s and bucket > s:
            cshape = list(m.shape)
            cshape[-1] = bucket - s
            m = concat([m, full(cshape, blocked, dtype=m.dtype)], axis=-1)
        if qs == s and bucket > s:
            rshape = list(m.shape)
            rshape[-2] = bucket - s
            m = concat([m, full(rshape, blocked, dtype=m.dtype)], axis=-2)
        return m

    # -- pass 1: discovery --------------------------------------------------
    def _trace(self, args, kwargs):
        arg_ids = {id(t) for t in jax.tree_util.tree_leaves(
            (args, kwargs), is_leaf=_is_tensor) if _is_tensor(t)}
        with _capture.CaptureContext() as cap:
            out = self._fn(*args, **kwargs)
            if self._post is not None:
                out = self._post(out)

        state = [t for i, t in cap.reads.items()
                 if i not in arg_ids and not isinstance(t._data, jax.core.Tracer)]
        mutated = [t for i, t in cap.mutated.items() if i not in arg_ids]
        grad_ts = [t for i, t in cap.grad_writes.items() if i not in arg_ids]
        rng_used = cap.rng_used

        fn = self._fn
        gen = _random.default_generator()

        def pure(state_arrays, grads_in, rng_key, *flat_args):
            saved = [(t, t._data, t._grad) for t in state]
            saved_grads = [(t, t._grad) for t in grad_ts]
            saved_key = gen.get_state()
            try:
                for t, a in zip(state, state_arrays):
                    t._data = a
                for t, g in zip(grad_ts, grads_in):
                    t._grad = None if g is None else Tensor(g)
                if rng_used:
                    gen.set_state(rng_key)
                a2, k2 = _rewrap_args(flat_args, self._treedef, self._tensor_pos,
                                      self._static_flat)
                res = fn(*a2, **k2)
                if self._post is not None:
                    res = self._post(res)
                out_arrays = jax.tree_util.tree_map(
                    lambda x: x._data if _is_tensor(x) else x, res,
                    is_leaf=_is_tensor)
                new_state = [t._data for t in mutated]
                new_grads = [None if t._grad is None else t._grad._data
                             for t in grad_ts]
                new_key = gen.get_state()
                return out_arrays, new_state, new_grads, new_key
            finally:
                for t, d, g in saved:
                    t._data = d
                    t._grad = g
                for t, g in saved_grads:
                    t._grad = g
                gen.set_state(saved_key)

        # flatten args once to know tensor positions (static parts baked)
        flat, treedef = jax.tree_util.tree_flatten((args, kwargs),
                                                   is_leaf=_is_tensor)
        self._treedef = treedef
        self._tensor_pos = [i for i, x in enumerate(flat) if _is_tensor(x)]
        self._static_flat = [None if _is_tensor(x) else x for x in flat]

        # donate_args indexes TOP-LEVEL positional args; expand each to
        # its tensor-leaf range in the flat calling convention (a pytree
        # cache arg donates every leaf, and args after a pytree don't
        # silently shift onto the wrong buffer)
        donate_leaves = []
        if self._donate_args:
            ranges = []
            pos = 0
            for a in args:
                n = sum(1 for t in jax.tree_util.tree_leaves(
                    a, is_leaf=_is_tensor) if _is_tensor(t))
                ranges.append((pos, pos + n))
                pos += n
            for i in self._donate_args:
                if i >= len(ranges):
                    raise ValueError(
                        f"donate_args index {i} out of range for "
                        f"{len(args)} positional arguments")
                donate_leaves.extend(range(*ranges[i]))
        donate = tuple(3 + j for j in donate_leaves)
        compiled = jax.jit(_named(pure, self._label()),
                           donate_argnums=donate)
        entry = {"compiled": compiled, "state": state, "mutated": mutated,
                 "grad_ts": grad_ts, "rng_used": rng_used, "first_out": out,
                 "treedef": treedef, "tensor_pos": self._tensor_pos,
                 "static_flat": self._static_flat}
        return entry

    # -- pass 2+: compiled execution ----------------------------------------
    _BREAK_ERRORS = ()  # populated lazily (jax.errors import)

    @classmethod
    def _graph_break_errors(cls):
        if not cls._BREAK_ERRORS:
            import jax.errors
            cls._BREAK_ERRORS = (
                jax.errors.TracerBoolConversionError,
                jax.errors.ConcretizationTypeError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerIntegerConversionError)
        return cls._BREAK_ERRORS

    def _run(self, entry, args, kwargs):
        if entry.get("fallback"):
            # graph broke on a previous call: the SOT segment compiler takes
            # over this signature — compiled sub-graphs between the breaks,
            # guarded on the break values (jit/sot.py)
            sot_cache = entry.get("sot")
            if sot_cache is None:
                from .sot import SOTCache
                sot_cache = SOTCache(self._fn)
                entry["sot"] = sot_cache
            return sot_cache.run(args, kwargs)
        try:
            if not entry.get("warm"):
                # first compiled execution at this signature pays the XLA
                # compile — attribute its wall time to compile.elapsed
                # (the signature's miss was already counted at trace time)
                # opprof hook BEFORE the run: donated input buffers are
                # still live here (AOT lowering only reads avals, but a
                # deleted donated array would refuse even that)
                self._maybe_opprof(entry, args, kwargs)
                import time as _t
                t0 = _t.perf_counter()
                out = self._run_compiled(entry, args, kwargs)
                _cc.observe_elapsed(_t.perf_counter() - t0)
                entry["warm"] = True
                return out
            return self._run_compiled(entry, args, kwargs)
        except self._graph_break_errors() as e:
            # Data-dependent python control flow (bool()/int()/float() of a
            # traced tensor) — the SOT graph-break case
            # (sot/opcode_translator: BreakGraphError -> eager fallback).
            # full_graph=True mirrors the reference: hard error.
            if self._full_graph:
                raise RuntimeError(
                    f"to_static(full_graph=True): {self._fn.__name__} has "
                    f"data-dependent python control flow that cannot be "
                    f"compiled; use lax-style ops (paddle.where, masking) "
                    f"or full_graph=False for eager fallback") from e
            entry["fallback"] = True
            entry.pop("compiled", None)  # free the trace
            return self._fn(*args, **kwargs)

    def _label(self) -> str:
        """What names this function's executables: in the opprof
        observatory as it stands, in the device trace with dots as
        underscores (``jit_serving_paged_decode``)."""
        return (getattr(self, "_opprof_label", None)
                or f"static.{getattr(self._fn, '__name__', 'fn')}")

    def _maybe_opprof(self, entry, args, kwargs):
        """Op-level cost capture of this signature's executable (opprof
        observatory). Free unless ``observability.opprof`` is enabled;
        never raises. Each newly-traced signature captures once — the
        per-label capture COUNT is how recompile storms get named."""
        from ..observability import opprof as _opprof
        if (not _opprof.enabled() or entry.get("opprof_done")
                or "compiled" not in entry):
            return
        entry["opprof_done"] = True
        label = self._label()
        try:
            gen = _random.default_generator()
            flat = jax.tree_util.tree_flatten(
                (args, kwargs), is_leaf=_is_tensor)[0]
            arg_tensors = [flat[i] for i in entry["tensor_pos"]]
            grads_in = [None if t._grad is None else t._grad._data
                        for t in entry["grad_ts"]]
            call = ([t._data for t in entry["state"]], grads_in,
                    gen.get_state(), *[t._data for t in arg_tensors])
            _opprof.maybe_capture(label, entry["compiled"], call)
        except Exception:
            pass

    def _run_compiled(self, entry, args, kwargs):
        gen = _random.default_generator()
        flat = jax.tree_util.tree_flatten((args, kwargs), is_leaf=_is_tensor)[0]
        arg_tensors = [flat[i] for i in entry["tensor_pos"]]
        state = entry["state"]
        grads_in = [None if t._grad is None else t._grad._data
                    for t in entry["grad_ts"]]
        rng_key = gen.get_state()
        self._treedef = entry["treedef"]
        self._tensor_pos = entry["tensor_pos"]
        self._static_flat = entry["static_flat"]

        need_grad = _engine.is_grad_enabled() and (
            any(not t.stop_gradient for t in state)
            or any(not t.stop_gradient for t in arg_tensors))

        if not need_grad:
            out_arrays, new_state, new_grads, new_key = entry["compiled"](
                [t._data for t in state], grads_in, rng_key,
                *[t._data for t in arg_tensors])
            result = jax.tree_util.tree_map(
                lambda x: Tensor(x) if isinstance(x, (jax.Array,)) else x,
                out_arrays)
        else:
            # Differentiable compiled call: route the jitted pure function
            # through op dispatch, so outputs carry a GradNode whose vjp
            # differentiates through the XLA executable (partial-eval keeps
            # forward compiled; the transpose compiles separately). This is
            # the analog of the reference's run_program op carrying the grad
            # program (jit/pir_partial_program.py).
            from ..ops import registry as _registry
            n_state = len(state)
            compiled = entry["compiled"]

            def op_fn(*xs):
                st = list(xs[:n_state])
                ar = list(xs[n_state:])
                return compiled(st, grads_in, rng_key, *ar)

            out_arrays, new_state_t, new_grads_t, new_key_t = \
                _registry.dispatch(op_fn, tuple(state) + tuple(arg_tensors),
                                   {}, op_name="static_fn")
            result = out_arrays  # already Tensors with grad nodes
            new_state = [t._data for t in jax.tree_util.tree_leaves(
                new_state_t, is_leaf=_is_tensor)] if new_state_t else []
            new_grads = [None if g is None else
                         (g._data if _is_tensor(g) else g)
                         for g in (new_grads_t if isinstance(new_grads_t,
                                                             (list, tuple))
                                   else [new_grads_t])] \
                if entry["grad_ts"] else []
            new_key = new_key_t._data if _is_tensor(new_key_t) else new_key_t

        for t, a in zip(entry["mutated"], new_state):
            t._data = a
        for t, g in zip(entry["grad_ts"], new_grads):
            t._grad = None if g is None else Tensor(g)
        if entry["rng_used"]:
            gen.set_state(new_key)
        return result


def _rewrap_args(flat_arrays, treedef, tensor_pos, static_flat):
    buf = list(static_flat)
    for i, a in zip(tensor_pos, flat_arrays):
        buf[i] = Tensor(a)
    return jax.tree_util.tree_unflatten(treedef, buf)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True, batch_buckets=None,
              seq_buckets=None, seq_axis=1, seq_mask_arg=None,
              seq_unpad_outputs=True, donate_args=None):
    """paddle.jit.to_static analog (jit/api.py:171).

    batch_buckets: opt-in dynamic-batch bucketing — inputs pad their
    leading dim up to the next bucket so a BOUNDED set of executables
    serves any batch size (valid only for per-sample maps: cross-batch
    reductions would see the pad rows).

    seq_buckets: opt-in dynamic-SEQUENCE bucketing (e.g. powers of two):
    inputs pad dim `seq_axis` up to the next bucket and outputs slice
    back, so varying lengths reuse O(log s_max) executables. Exact for
    causal models; for bidirectional attention name the mask kwarg via
    `seq_mask_arg` and the wrapper blocks the tail keys."""
    def deco(fn):
        # Layer: compile its forward, keep the layer object semantics
        from ..nn.layer import Layer
        if isinstance(fn, Layer):
            layer = fn
            static = StaticFunction(layer.forward, input_spec,
                                    build_strategy, backend, full_graph,
                                    batch_buckets, seq_buckets, seq_axis,
                                    seq_mask_arg, seq_unpad_outputs,
                                    donate_args)
            layer.forward = static
            return layer
        return StaticFunction(fn, input_spec, build_strategy, backend,
                              full_graph, batch_buckets, seq_buckets,
                              seq_axis, seq_mask_arg, seq_unpad_outputs,
                              donate_args)
    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class TrainStep:
    """Whole-train-step compilation: forward + tape backward + optimizer
    update staged into ONE XLA executable.

    This is the reference's `to_static` training path (partial_program with
    appended backward run by the StandaloneExecutor, SURVEY.md §3.4) rebuilt
    TPU-first: XLA sees the entire step, so it fuses the optimizer update into
    the backward and overlaps everything on-chip.

    train_fn(*batch) -> loss (closes over the model); optimizer supplies the
    pure update (optimizer.py `_update`).
    """

    def __init__(self, train_fn: Callable, optimizer, amp=None, donate=True,
                 mesh_plan=None, opprof_label=None):
        """donate=True donates the param/master/opt-state device buffers to
        each compiled step (XLA updates them in place — halves HBM for the
        update). Tensors aliasing those buffers from BEFORE the step (e.g. a
        `.detach()` snapshot of a weight) become invalid afterwards and raise
        loudly on use; pass donate=False to keep old buffers alive.

        mesh_plan (a ``distributed.mesh.TrainMeshPlan``) compiles the step
        SPMD: params/masters/optimizer state live sharded per the plan's
        ``in_shardings``/``out_shardings``, grads are constrained onto the
        param placement, and the program is refused (SH201/MEM301) by the
        runtime gate before any compile.

        opprof_label names this step's executables in the opprof
        observatory (OPPROF artifacts / gap-attribution gauges);
        mesh-compiled steps get a ``:mesh`` suffix."""
        self._fn = train_fn
        self._opt = optimizer
        self._amp = amp  # optional paddle_tpu.amp.auto_cast factory kwargs
        self._donate = donate
        self._mesh_plan = mesh_plan
        self._opprof_label = ((opprof_label or "train_step")
                              + (":mesh" if mesh_plan is not None else ""))
        self._cache: Dict[Any, dict] = {}

    def __call__(self, *args):
        key = _sig_of(args, {})
        entry = self._cache.get(key)
        if entry is None:
            if self._cache:
                # The pure step re-executes the model under tracing, so it is
                # shape-polymorphic: a new batch shape only needs an XLA
                # retrace (jax.jit does that), NOT a new eager discovery
                # pass. This keeps the expensive unfused eager pass on a
                # tiny warmup batch (TPU memory: the eager pass holds every
                # per-op vjp residual unfused). Caveat: the state/mutation
                # sets discovered at the first shape are reused — a model
                # that lazily creates NEW buffers only at some shapes (e.g.
                # a cached per-seq-len mask) must precompute them (as the
                # model zoo does) or run one eager step per shape first.
                entry = next(iter(self._cache.values()))
                self._cache[key] = entry
                # the shared entry is shape-polymorphic but jax.jit still
                # XLA-retraces at the new signature: a compile miss
                with _cc.timed_miss():
                    out = self._run(entry, args)
                # every retrace is a fresh executable — capture it so the
                # OPPROF diff can NAME the recompile (not just count it)
                self._maybe_opprof(entry, args)
                return out
            else:
                with _cc.timed_miss():
                    entry = self._build(args)
                self._cache[key] = entry
                return entry.pop("first_loss")
        if not entry.get("warm"):
            # first compiled execution after the eager discovery pass pays
            # the XLA compile (the miss itself was counted at build time)
            import time as _t
            t0 = _t.perf_counter()
            out = self._run(entry, args)
            _cc.observe_elapsed(_t.perf_counter() - t0)
            entry["warm"] = True
            self._maybe_opprof(entry, args)
            return out
        _cc.note_hit()
        import time as _t
        t0 = _t.perf_counter()
        out = self._run(entry, args)
        # steady-state (warm-hit) step latency feeds the roofline gap
        tokens = None
        shape = getattr(args[0], "shape", None) if args else None
        if shape:
            tokens = 1
            for d in shape:
                tokens *= int(d)
        _cc.observe_steady_step(_t.perf_counter() - t0, tokens=tokens)
        return out

    def _loss_fn(self, *args):
        if self._amp:
            from .. import amp as amp_mod
            with amp_mod.auto_cast(**self._amp):
                return self._fn(*args)
        return self._fn(*args)

    def _build(self, args):
        opt = self._opt
        params = [p for p in opt._parameter_list if p.trainable]
        arg_ids = {id(t) for t in args if _is_tensor(t)}
        param_ids = {id(p) for p in params}

        # discovery pass (doubles as real step 1, eager)
        with _capture.CaptureContext() as cap:
            loss = self._loss_fn(*args)
            loss.backward()
        # extra state: buffers/constants the model read or mutated
        extra = [t for i, t in cap.reads.items()
                 if i not in arg_ids and i not in param_ids
                 and not isinstance(t._data, jax.core.Tracer)]
        extra_mut = [t for i, t in cap.mutated.items()
                     if i not in arg_ids and i not in param_ids]
        # trainable leaves NOT managed by the optimizer still receive grads —
        # thread them through the compiled step like StaticFunction does
        other_grad_ts = [t for i, t in cap.grad_writes.items()
                         if i not in param_ids and i not in arg_ids]
        rng_used = cap.rng_used
        gen = _random.default_generator()

        # eager optimizer update for step 1
        opt.step()
        for p in params:
            p.clear_grad()
        opt._functional_states(params)  # ensure accumulators exist per param

        use_master = [opt._multi_precision and p.dtype != jnp.float32
                      for p in params]
        if any(use_master):
            for p, um in zip(params, use_master):
                if um:
                    opt._master_weight(p)  # materialize fp32 master

        clip = opt._grad_clip
        fn = self._loss_fn
        mesh_plan = self._mesh_plan
        if mesh_plan is not None:
            mesh_plan.register_params(params)

        def pure(p_arrays, masters, opt_states, extra_arrays, other_grads_in,
                 rng_key, lr, *batch):
            saved_p = [(p, p._data, p._grad) for p in params]
            saved_e = [(t, t._data) for t in extra]
            saved_o = [(t, t._grad) for t in other_grad_ts]
            saved_key = gen.get_state()
            try:
                for i, (p, a) in enumerate(zip(params, p_arrays)):
                    # stage-3 storage sharding: the stored shard gathers
                    # to its compute placement at use
                    p._data = (a if mesh_plan is None
                               else mesh_plan.constrain_param_for_use(i, a))
                    p._grad = None
                for t, a in zip(extra, extra_arrays):
                    t._data = a
                for t, g in zip(other_grad_ts, other_grads_in):
                    t._grad = None if g is None else Tensor(g)
                if rng_used:
                    gen.set_state(rng_key)
                batch_t = [Tensor(b) for b in batch]
                # under a mesh plan this traces one SPMD program, in
                # which a Pallas kernel has to be wrapped to run at all
                with _pallas.whole_on_each_device(
                        None if mesh_plan is None
                        else mesh_plan.runtime.mesh):
                    loss_t = fn(*batch_t)
                    _engine.run_backward([loss_t], [None])
                grads = [None if p._grad is None else p._grad._data
                         for p in params]
                if mesh_plan is not None:
                    # land each grad on its param's placement so XLA
                    # scatters instead of keeping a full copy per chip
                    grads = [g if g is None
                             else mesh_plan.constrain_grad(i, g)
                             for i, g in enumerate(grads)]
                gs = getattr(opt, "_group_sharded", None)
                if gs is not None:
                    # ZeRO stage-2/3: constrain grads Shard(0) over the
                    # sharding axis so XLA reduce-scatters the backward
                    grads = [
                        g if g is None else (
                            jax.lax.with_sharding_constraint(
                                g, gs.grad_sharding(tuple(g.shape)))
                            if gs.grad_sharding(tuple(g.shape)) is not None
                            else g)
                        for g in grads]
                if clip is not None and hasattr(clip, "apply_to_arrays"):
                    grads = clip.apply_to_arrays(grads)
                lr_ = lr
                new_p, new_masters, new_states = [], [], []
                for p, pa, m, um, g, st in zip(params, p_arrays, masters,
                                               use_master, grads, opt_states):
                    if g is None:
                        new_p.append(pa)
                        new_masters.append(m)
                        new_states.append(st)
                        continue
                    base = m if um else pa
                    if g.dtype != base.dtype:
                        g = g.astype(base.dtype)
                    nv, ns = opt._update(base, g, st, lr_)
                    if um:
                        new_masters.append(nv)
                        new_p.append(nv.astype(pa.dtype))
                    else:
                        new_masters.append(m)
                        new_p.append(nv)
                    new_states.append(ns)
                new_extra = [t._data for t in extra_mut]
                new_other_grads = [None if t._grad is None else t._grad._data
                                   for t in other_grad_ts]
                new_key = gen.get_state()
                return (loss_t._data, new_p, new_masters, new_states,
                        new_extra, new_other_grads, new_key)
            finally:
                for p, d, g in saved_p:
                    p._data = d
                    p._grad = g
                for t, d in saved_e:
                    t._data = d
                for t, g in saved_o:
                    t._grad = g
                gen.set_state(saved_key)

        # Donate params/masters/opt-state buffers: every one is fully
        # replaced after the step, so XLA reuses their HBM in place (halves
        # steady-state memory for the update).
        donate_argnums = (0, 1, 2) if self._donate else ()
        _named(pure, self._opprof_label)
        if mesh_plan is None:
            compiled = jax.jit(pure, donate_argnums=donate_argnums)
        else:
            p_arrays = [p._data for p in params]
            masters_l = [opt._master_weights.get(id(p)) if um else None
                         for p, um in zip(params, use_master)]
            opt_states_l = [{n: opt._accumulators[n][id(p)]
                             for n in opt._state_names()} for p in params]
            extra_arrays = [t._data for t in extra]
            other_grads_in = [None if t._grad is None else t._grad._data
                              for t in other_grad_ts]
            batch_arrs = [a._data if _is_tensor(a) else a for a in args]
            lr0 = jnp.asarray(opt.get_lr(), jnp.float32)
            in_sh, out_sh = mesh_plan.step_shardings(
                p_arrays, masters_l, opt_states_l, extra_arrays,
                other_grads_in, batch_arrs, n_extra_out=len(extra_mut))
            # runtime SH201/MEM301 gate over the ACTUAL step jaxpr and the
            # exact specs it will compile with — refuses before any XLA time
            jaxpr = jax.make_jaxpr(pure)(
                p_arrays, masters_l, opt_states_l, extra_arrays,
                other_grads_in, gen.get_state(), lr0, *batch_arrs)
            n_donated = len(jax.tree_util.tree_leaves(
                (p_arrays, masters_l, opt_states_l)))
            mesh_plan.gate(jaxpr=jaxpr,
                           donate=tuple(range(n_donated)) if self._donate
                           else (),
                           invar_specs=mesh_plan.flat_invar_specs(in_sh))
            # commit state to its sharded residence (AFTER the eager
            # discovery step: eager ops cannot touch non-addressable
            # shards in a multi-process world)
            placed_masters, placed_states = mesh_plan.place_state(
                params, masters_l, opt_states_l)
            for p, um, m in zip(params, use_master, placed_masters):
                if um:
                    opt._master_weights[id(p)] = m
            for p, st in zip(params, placed_states):
                for name, v in st.items():
                    opt._accumulators[name][id(p)] = v
            compiled = jax.jit(pure, donate_argnums=donate_argnums,
                               in_shardings=in_sh, out_shardings=out_sh)
        return {"compiled": compiled, "params": params, "extra": extra,
                "extra_mut": extra_mut, "other_grad_ts": other_grad_ts,
                "use_master": use_master, "rng_used": rng_used,
                "first_loss": loss.detach()}

    def _assemble(self, entry, args):
        """The compiled step's live argument tuple, exactly as one
        invocation passes it (shared by ``_run`` and the mesh
        memory-measurement path)."""
        opt = self._opt
        gen = _random.default_generator()
        params = entry["params"]
        use_master = entry["use_master"]
        p_arrays = [p._data for p in params]
        masters = [opt._master_weights.get(id(p)) if um else None
                   for p, um in zip(params, use_master)]
        opt_states = [{name: opt._accumulators[name][id(p)]
                       for name in opt._state_names()} for p in params]
        if getattr(opt, "_sharded_states_offload", False):
            # ZeRO-offload step boundary: prefetch host-resident states to
            # device for the compiled step (the temporary device copies are
            # donated, so HBM holds them only for the step's duration)
            opt_states = [{k: opt._fetch_state_for_update(v)
                           for k, v in st.items()} for st in opt_states]
        extra_arrays = [t._data for t in entry["extra"]]
        other_grads_in = [None if t._grad is None else t._grad._data
                          for t in entry["other_grad_ts"]]
        batch = [a._data if _is_tensor(a) else a for a in args]
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        rng_key = gen.get_state()
        mp = self._mesh_plan
        if mp is not None:
            # commit per-step host inputs to the mesh (a multi-process
            # world cannot auto-commit host arrays to a global sharding;
            # state args are already mesh-resident from _build)
            batch = mp.place_batch(batch)
            place = mp.runtime.place
            extra_arrays = [place(a, ()) for a in extra_arrays]
            other_grads_in = [None if g is None else place(g, ())
                              for g in other_grads_in]
            lr = place(lr, ())
            rng_key = place(rng_key, ())
        return (p_arrays, masters, opt_states, extra_arrays,
                other_grads_in, rng_key, lr, *batch)

    def _maybe_opprof(self, entry, args):
        """Op-level cost capture of the step executable (opprof
        observatory). Called AFTER a run, so donated param/opt-state
        buffers have already been replaced by their fresh outputs and
        ``_assemble`` sees only live arrays. Free unless enabled; never
        raises."""
        from ..observability import opprof as _opprof
        if not _opprof.enabled() or "compiled" not in entry:
            return
        try:
            _opprof.maybe_capture(self._opprof_label, entry["compiled"],
                                  self._assemble(entry, args))
        except Exception:
            pass

    def aot_compile(self, *args):
        """The step's XLA executable for this batch, compiled ahead of
        time at the live state's shapes: nothing runs and no donated
        buffer is touched. ``as_text()`` is the optimized HLO (kernels
        appear as ``tpu_custom_call``, collectives by name) and
        ``memory_analysis()`` XLA's own buffer assignment. Call after a
        compiled step has run at this batch signature; the persistent
        compilation cache absorbs the second compile."""
        entry = self._cache.get(_sig_of(args, {}))
        if entry is None:
            raise RuntimeError("aot_compile needs a step that has already "
                               "run at this batch signature")
        return entry["compiled"].lower(
            *self._assemble(entry, args)).compile()

    def mesh_memory_report(self, *args, tolerance: float = 0.10):
        """Runtime/static memory cross-check for the compiled SPMD step.

        AOT-compiles the cached step at the live state's shapes, reads
        XLA's OWN per-chip buffer assignment, and verifies it against the
        liveness-walk prediction the gate used (gauges
        ``mesh.live_bytes_{measured,predicted,agreement}``). Returns the
        report dict, or None when there is no mesh plan / the backend
        exposes no memory analysis. Call after at least one step."""
        mp = self._mesh_plan
        if mp is None or _sig_of(args, {}) not in self._cache:
            return None
        from ..distributed.mesh import MeshRuntime
        exe = self.aot_compile(*args)
        measured = MeshRuntime.measured_live_bytes(exe)
        predicted = mp.memory_report
        if measured is None or not predicted:
            return None
        return mp.runtime.verify_live_bytes(measured, predicted,
                                            tolerance=tolerance)

    def _run(self, entry, args):
        opt = self._opt
        gen = _random.default_generator()
        params = entry["params"]
        use_master = entry["use_master"]
        with _span("trainstep.assemble"):
            call = self._assemble(entry, args)
        with _span("trainstep.launch"):     # returns when dispatched
            (loss, new_p, new_masters, new_states, new_extra,
             new_other_grads, new_key) = entry["compiled"](*call)
        with _span("trainstep.writeback"):
            for p, a in zip(params, new_p):
                p._data = a
            for p, um, m in zip(params, use_master, new_masters):
                if um:
                    opt._master_weights[id(p)] = m
            for p, st in zip(params, new_states):
                for name, v in st.items():
                    # ZeRO-offload hook: fresh state buffers return to
                    # their sharded host residence (identity when
                    # offload is off)
                    opt._accumulators[name][id(p)] = \
                        opt._restore_state_placement(v)
            for t, a in zip(entry["extra_mut"], new_extra):
                t._data = a
            for t, g in zip(entry["other_grad_ts"], new_other_grads):
                t._grad = None if g is None else Tensor(g)
            if entry["rng_used"]:
                gen.set_state(new_key)
            opt._step_count += 1
        return Tensor(loss)


def _pure_layer_forward(layer):
    """Stage layer.__call__ as a pure fn(param_arrays, *input_arrays):
    the state-threading trick TrainStep uses, for inference export.

    Uses _state_dict_raw(): the LIVE tensors (padded shapes intact) —
    state_dict() returns sliced COPIES for Megatron-padded params, and
    assigning t._data on a copy would bake the live weight into the
    trace as a constant."""
    named = list(layer._state_dict_raw().items())  # params + buffers

    def fn(param_arrays, *input_arrays):
        saved = [(t, t._data) for _, t in named]
        try:
            for (_, t), a in zip(named, param_arrays):
                t._data = a
            with _engine.no_grad():
                out = layer(*[Tensor(a) for a in input_arrays])
            leaves = jax.tree_util.tree_leaves(
                out, is_leaf=lambda x: isinstance(x, Tensor))
            return tuple(l._data if isinstance(l, Tensor) else l
                         for l in leaves)
        finally:
            for t, d in saved:
                t._data = d

    return fn, named


def save(layer, path, input_spec=None, **kwargs):
    """paddle.jit.save analog (jit/api.py save -> TranslatedLayer format).

    Serializes THREE artifacts, the reference's program+params split mapped
    to the XLA world (N25 C++ jit loader / N22 inference input format):
      <path>.pdmodel   — jax.export-serialized StableHLO of the forward
      <path>.pdiparams — the state dict (params + buffers)
      <path>.json      — input specs + metadata
    Layers whose forward can't be staged (data-dependent python) still get
    params saved; load() then requires the original class.
    """
    import json

    from ..framework import io as fio
    from ..static import InputSpec

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    fio.save(layer.state_dict(), path + ".pdiparams")

    specs = None
    if input_spec is not None:
        specs = [s if isinstance(s, InputSpec)
                 else InputSpec.from_tensor(s) if _is_tensor(s)
                 else InputSpec(s) for s in input_spec]
    if specs is None:
        # no spec: params-only save (reference allows this for Layers
        # loaded back as code + state dict)
        with open(path + ".json", "w") as f:
            json.dump({"format": "params_only"}, f)
        return

    was_training = layer.training
    layer.eval()
    try:
        fn, named = _pure_layer_forward(layer)
        param_arrays = [t._data for _, t in named]
        from jax import export as jexport
        # dynamic dims (None/-1 in the spec) export as symbolic sizes so the
        # serialized program serves ANY batch/seq length. Dims at the SAME
        # axis position share one symbol across inputs (paddle semantics:
        # axis 0 is the common batch dim, axis 1 the common seq dim), so
        # multi-input models like (input_ids, attention_mask) export.
        scope = jexport.SymbolicScope()
        sym_by_axis = {}
        arg_shapes = []
        for s in specs:
            dims = []
            for axis, d in enumerate(s.shape):
                if d in (None, -1):
                    if axis not in sym_by_axis:
                        (sym_by_axis[axis],) = jexport.symbolic_shape(
                            f"d{axis}", scope=scope)
                    dims.append(sym_by_axis[axis])
                else:
                    dims.append(int(d))
            arg_shapes.append(jax.ShapeDtypeStruct(tuple(dims),
                                                   s.np_dtype()))
        param_structs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for a in param_arrays]
        exported = jexport.export(jax.jit(fn))(param_structs, *arg_shapes)
        # pdiparams stores LOGICAL shapes (state_dict slices pad tails,
        # so checkpoints interchange across mp degrees); the exported
        # program's param inputs are the live PADDED shapes — record the
        # pad map so load() can zero-fill before binding
        pads = {name: {"dim": pad[0], "logical": pad[1],
                       "padded": int(p.shape[pad[0]])}
                for name, p, pad in layer._named_param_entries()
                if pad is not None and p.shape[pad[0]] != pad[1]}
        with open(path + ".pdmodel", "wb") as f:
            f.write(exported.serialize())
        with open(path + ".json", "w") as f:
            json.dump({"format": "stablehlo",
                       "param_pads": pads,
                       "param_names": [n for n, _ in named],
                       "input_specs": [{"shape": list(s.shape),
                                        "dtype": s.dtype,
                                        "name": s.name} for s in specs]}, f)
    finally:
        if was_training:
            layer.train()


class TranslatedLayer:
    """jit/translated_layer.py analog: a loaded AOT program + params,
    callable like the original Layer (inference only)."""

    def __init__(self, exported, param_arrays, meta):
        self._exported = exported
        self._params = param_arrays
        self._meta = meta

    def __call__(self, *inputs):
        arrs = [i._data if _is_tensor(i) else jnp.asarray(i) for i in inputs]
        outs = self._exported.call(self._params, *arrs)
        wrapped = [Tensor(o) for o in outs]
        return wrapped[0] if len(wrapped) == 1 else wrapped

    forward = __call__

    def eval(self):
        return self

    def input_specs(self):
        return self._meta.get("input_specs", [])


def load(path, **kwargs):
    """paddle.jit.load analog: returns a TranslatedLayer for stablehlo
    saves, or the raw state dict for params-only saves."""
    import json

    from ..framework import io as fio

    meta = {}
    if os.path.exists(path + ".json"):
        with open(path + ".json") as f:
            meta = json.load(f)
    if meta.get("format") == "stablehlo":
        from jax import export as jexport
        with open(path + ".pdmodel", "rb") as f:
            exported = jexport.deserialize(f.read())
        state = fio.load(path + ".pdiparams")
        params = [state[n]._data if _is_tensor(state[n])
                  else jnp.asarray(state[n]) for n in meta["param_names"]]
        # re-pad logical-shape params to the exported program's padded
        # input shapes (zero tails, matching the layers' init contract)
        pads = meta.get("param_pads", {})
        if pads:
            by_name = dict(zip(meta["param_names"], range(len(params))))
            for name, info in pads.items():
                i = by_name[name]
                a = params[i]
                dim, padded = info["dim"], info["padded"]
                if a.shape[dim] < padded:
                    widths = [(0, 0)] * a.ndim
                    widths[dim] = (0, padded - a.shape[dim])
                    params[i] = jnp.pad(a, widths)
        return TranslatedLayer(exported, params, meta)
    # params-only (or legacy .pdparams) save
    for suffix in (".pdiparams", ".pdparams"):
        if os.path.exists(path + suffix):
            return fio.load(path + suffix)
    raise FileNotFoundError(f"no saved model at {path}")


# -- dy2static logging/config shims (ref jit/dy2static/logging_utils.py) -----

_IGNORED_MODULES: list = []


def ignore_module(modules):
    """ref jit.ignore_module: functions from these modules never capture
    (always treated as not_to_static)."""
    _IGNORED_MODULES.extend(modules if isinstance(modules, (list, tuple))
                            else [modules])


def set_code_level(level=100, also_to_stdout=False):
    """ref set_code_level: transformed-code logging — capture-by-execution
    has no transformed source; retained for API parity (sets verbosity)."""
    import logging
    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if level > 0 else logging.WARNING)


def set_verbosity(level=0, also_to_stdout=False):
    import logging
    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if level > 0 else logging.WARNING)


__all__ += ["ignore_module", "set_code_level", "set_verbosity", "save",
            "load", "TranslatedLayer"]
