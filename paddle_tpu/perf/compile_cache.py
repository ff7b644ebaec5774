"""Compilation caching + compile observability.

Two layers, one goal: recompiles become rare AND measurable.

  * **Persistent cache** — ``enable_persistent_cache()`` turns on JAX's
    on-disk compilation cache (XLA executables survive process restarts).
    It is the one place in the repo that configures that cache: the
    entry points that compile at real sizes (``bench.py``,
    ``chip_smoke.py``, the TPU test tier) call it before their first
    compile. The directory is ``JAX_COMPILATION_CACHE_DIR`` where that is
    set (JAX reads it itself) and ``<checkout>/.jax_cache`` otherwise —
    always a fixed path, because a cache that moves never hits.

  * **Dispatch-cache counters** — every program cache the framework keeps
    (``jit.StaticFunction`` signatures, ``jit.TrainStep`` entries, the
    serving prefill/decode wrappers) reports through ``note_hit`` /
    ``note_miss`` here, keyed on the abstractified signature (shapes,
    dtypes, donation mask — ``signature_of``). ``compile.miss`` rising in
    steady state IS the recompile bug, now a regressable number
    (tests/test_perf.py guards it); ``compile.elapsed`` accumulates the
    seconds spent tracing/compiling.
"""
from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Optional, Tuple

__all__ = ["enable_persistent_cache",
           "note_hit", "note_miss", "observe_elapsed",
           "observe_steady_step", "signature_of",
           "compile_metrics", "donation_safe", "timed_miss"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_LOCK = threading.Lock()
_LISTENING = False

# JAX's own monitoring events for its on-disk cache
_PERSISTENT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.persistent_hit",
    "/jax/compilation_cache/cache_misses": "compile.persistent_miss",
}


# -- persistent (on-disk) XLA executable cache -------------------------------

def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    has already read it and no directory is set in code; otherwise the
    cache is ``<checkout>/.jax_cache``. Compiles of any length are cached,
    and JAX's hits and misses are counted into ``compile_metrics()``."""
    global _LISTENING
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with _LOCK:
        if not _LISTENING:
            jax.monitoring.register_event_listener(_on_jax_event)
            _LISTENING = True
    return jax.config.jax_compilation_cache_dir


def _persistent_counter(name: str):
    return _reg().counter(name, "events of JAX's on-disk compilation cache")


def _on_jax_event(event: str, **_kw) -> None:
    name = _PERSISTENT_EVENTS.get(event)
    if name is not None:
        _persistent_counter(name).inc()


# -- in-process dispatch-cache observability ---------------------------------

def _reg():
    from ..observability.metrics import get_registry
    return get_registry()


def _counters():
    reg = _reg()
    return (reg.counter("compile.hit",
                        "dispatches served by an existing compiled program"),
            reg.counter("compile.miss",
                        "dispatches that traced/compiled a new program"),
            reg.histogram("compile.elapsed",
                          "seconds spent in trace/compile work",
                          buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                                   5.0, 10.0, 30.0, 60.0, 300.0, 900.0)))


def note_hit(n: int = 1) -> None:
    _counters()[0].inc(n)


def note_miss(elapsed_s: Optional[float] = None) -> None:
    _, miss, hist = _counters()
    miss.inc()
    if elapsed_s is not None:
        hist.observe(float(elapsed_s))


def observe_elapsed(elapsed_s: float) -> None:
    """Add compile-attributed seconds without counting a new miss (the
    first run of an already-counted signature pays the XLA compile)."""
    _counters()[2].observe(float(elapsed_s))


def observe_steady_step(elapsed_s: float,
                        tokens: Optional[int] = None) -> None:
    """Record one WARM fused-step execution (cache-hit path): the
    steady-state latency the roofline gap is measured against, kept
    separate from ``compile.elapsed`` so compile cost never pollutes the
    steady-state distribution."""
    reg = _reg()
    reg.histogram("train.fused_step_seconds",
                  "warm (cache-hit) fused train-step wall time"
                  ).observe(float(elapsed_s))
    if tokens and elapsed_s > 0:
        reg.gauge("train.fused_tokens_per_sec",
                  "steady-state fused-step token throughput").set(
                      tokens / elapsed_s)


@contextmanager
def timed_miss():
    """Time a miss-path block (trace/build) and record it as one miss."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        note_miss(time.perf_counter() - t0)


def compile_metrics() -> dict:
    """Current counters as plain numbers (bench.py emits these). The
    ``persistent_*`` pair counts JAX's on-disk cache and stays 0 until
    ``enable_persistent_cache()`` has been called."""
    hit, miss, hist = _counters()
    return {"compile_cache_hits": hit.value,
            "compile_cache_misses": miss.value,
            "compile_time_s": round(hist.sum, 3),
            "persistent_cache_hits":
                _persistent_counter("compile.persistent_hit").value,
            "persistent_cache_misses":
                _persistent_counter("compile.persistent_miss").value}


def signature_of(tree, donated: Tuple[int, ...] = ()) -> tuple:
    """Abstractified, hashable dispatch key: tensor/array leaves reduce to
    (shape, dtype), everything else stays by value; the donation mask is
    part of the key (the same shapes with different donation compile
    different executables)."""
    import jax
    import numpy as np

    from ..core.tensor import Tensor

    def is_leaf(x):
        return isinstance(x, Tensor)

    flat, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_leaf)
    parts = []
    for x in flat:
        if isinstance(x, Tensor):
            parts.append(("T", tuple(x.shape), str(x.dtype)))
        elif isinstance(x, (jax.Array, np.ndarray)):
            parts.append(("A", tuple(x.shape), str(x.dtype)))
        else:
            parts.append(("S", repr(x)))
    return (treedef, tuple(parts), tuple(donated))


# -- donation safety (DF006 alias audit) -------------------------------------

_DONATION_AUDIT: Optional[Tuple[bool, tuple]] = None


def donation_safe() -> Tuple[bool, tuple]:
    """Run the DF006 inplace/donation alias audit once per process and
    cache the verdict. Donation-by-default paths (the hapi fused train
    step) consult this before handing XLA the right to overwrite param /
    opt-state buffers: a wrong alias declaration plus donation corrupts
    memory on hardware, so any DF006 finding downgrades to non-donating."""
    global _DONATION_AUDIT
    if _DONATION_AUDIT is None:
        try:
            from ..analysis.dataflow import audit_inplace_aliases
            findings = tuple(audit_inplace_aliases())
        except Exception:
            findings = ()
        _DONATION_AUDIT = (not findings, findings)
    return _DONATION_AUDIT
