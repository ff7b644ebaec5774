"""Block-size autotuner for the Pallas flash-attention kernel.

The reference ships per-arch tuned CUDA kernels (flashattn binaries per SM
generation); on TPU the analogous knob is the (block_q, block_k) tiling of
the Pallas grid — the right choice depends on chip generation (VMEM size,
MXU shape) and on (seq, head_dim, heads). Rather than bake one guess,
`tune_flash_blocks` measures a candidate set ON THE DEVICE and caches the
winner per shape signature; `flash_attention_pallas` consults the cache
when `FLAGS_flash_autotune` is on.

Timing only means something on real hardware, so tuning is a no-op off
TPU (interpret mode would measure the python interpreter). The real-TPU
tier (`pytest -m tpu`) exercises one tuning sweep; `bench.py` can enable
the flag for the headline run.

MULTI-CONTROLLER CAUTION: the cache is process-local. In a multi-process
SPMD world every controller must trace the SAME program — per-host timing
noise could elect different winners and diverge the compiled step. There,
tune on rank 0 only and distribute the winner to every rank via
``set_best`` (e.g. over distributed.broadcast_object_list) before the
first flagged call.
"""
from __future__ import annotations

import math
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax

# (block_q, block_k) candidates: MXU-friendly multiples of 128, biased
# toward tall-K tiles (K/V streaming is the HBM-bound leg).
CANDIDATES: List[Tuple[int, int]] = [
    (128, 128), (128, 256), (256, 128), (256, 256),
    (128, 512), (512, 128),
]

# shape signature -> winning (block_q, block_k)
_BEST: Dict[tuple, Tuple[int, int]] = {}


def _sig(q, k, causal, has_mask, dropout_p):
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    # dtype matters twice over: VMEM footprint (a tiling that fits bf16 can
    # overflow f32) and timing winners differ per dtype
    return (b, s, hq, hkv, d, str(q.dtype), bool(causal), bool(has_mask),
            bool(dropout_p))


def _cache_counter(outcome: str):
    from ...observability.metrics import get_registry
    return get_registry().counter(
        "flash_autotune_cache_total",
        "autotune tiling-cache lookups by outcome (hit/miss)",
        labelnames=("outcome",)).labels(outcome=outcome)


def cached_blocks(q, k, causal, has_mask, dropout_p):
    best = _BEST.get(_sig(q, k, causal, has_mask, dropout_p))
    _cache_counter("hit" if best is not None else "miss").inc()
    return best


def set_best(q, k, causal, has_mask, dropout_p, blocks: Tuple[int, int]):
    """Install a winner without measuring (rank-0-tunes-and-broadcasts
    pattern for multi-controller worlds — see module docstring)."""
    _BEST[_sig(q, k, causal, has_mask, dropout_p)] = tuple(blocks)


def synth_like(q, k, v, attn_mask):
    """Concrete random arrays matching (possibly traced) inputs' avals.

    Tuning only needs shapes/dtypes; this lets the flag work from inside a
    jit/vjp trace (the training path) — the sweep runs on synthesized
    data while the trace is suspended in python."""
    import numpy as np

    import jax.numpy as jnp
    rng = np.random.RandomState(0)

    def mk(t):
        if t is None:
            return None
        return jnp.asarray(rng.randn(*t.shape), jnp.float32).astype(t.dtype)

    return mk(q), mk(k), mk(v), mk(attn_mask)


def candidates_for(q, k, causal, has_mask, dropout_p) -> List[Tuple[int, int]]:
    """CANDIDATES plus the grid tiles `flash_tiling` derives from this
    call's shape (what the kernels run with the flag off): a sweep measures
    the default with the rest, so it can never pick worse than it."""
    from .flash_attention import Tilings, flash_tiling
    _, s, _, d = q.shape
    derived = [flash_tiling(kn, s, d, q.dtype.itemsize, causal, has_mask,
                            bool(dropout_p))[:2] for kn in Tilings._fields]
    return list(dict.fromkeys(CANDIDATES + derived))


def _filter_candidates(s: int, candidates) -> List[Tuple[int, int]]:
    """Keep tilings the kernel will actually run at this length: the
    kernel pads sequences to lcm(block_q, block_k) and SHRINKS blocks
    when s < lcm, so any candidate with lcm > s would be measured as a
    different tiling than the one cached."""
    return [c for c in candidates if math.lcm(*c) <= s]


def tune_flash_blocks(q, k, v, causal: bool = True, attn_mask=None,
                      dropout_p: float = 0.0,
                      candidates: Optional[List[Tuple[int, int]]] = None,
                      iters: int = 5, include_bwd: bool = True):
    """Measure the candidate tilings on-device; cache + return the winner.

    Returns (best, results) where results is {(bq, bk): seconds | None}.
    None means the compiler refused that tiling (VMEM overflow): it is
    warned about once with the compiler's message and the sweep goes on.
    A sweep in which every candidate is refused raises.
    """
    from . import on_tpu
    from .flash_attention import flash_attention_pallas

    if not on_tpu():
        raise RuntimeError("tune_flash_blocks times real kernels; it is "
                           "meaningless off TPU")
    s = q.shape[1]
    cands = _filter_candidates(s, candidates or candidates_for(
        q, k, causal, attn_mask is not None, dropout_p))
    if not cands:
        raise RuntimeError(
            f"sequence length {s} below every candidate tiling's lcm — "
            f"the kernel's short-sequence shrink governs; nothing to tune")
    from ...observability.metrics import get_registry
    get_registry().counter(
        "flash_autotune_tunes_total",
        "on-device flash-attention tuning sweeps run").inc()
    results: Dict[Tuple[int, int], Optional[float]] = {}

    def run(bq, bk):
        def fwd_bwd(q_, k_, v_):
            out = flash_attention_pallas(q_, k_, v_, causal=causal,
                                         attn_mask=attn_mask,
                                         dropout_p=dropout_p,
                                         block_q=bq, block_k=bk)
            return out.sum()
        fn = (jax.jit(jax.grad(fwd_bwd, argnums=(0, 1, 2)))
              if include_bwd else jax.jit(fwd_bwd))
        r = fn(q, k, v)  # compile + warm
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(q, k, v)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / iters

    refusals: Dict[Tuple[int, int], str] = {}
    for c in cands:
        try:
            results[c] = run(*c)
        except jax.errors.JaxRuntimeError as e:
            results[c] = None
            refusals[c] = str(e).splitlines()[0][:300]
            sig = _sig(q, k, causal, attn_mask is not None, dropout_p)
            warnings.warn(f"flash autotune: tiling {c} refused at {sig}: "
                          f"{refusals[c]}")
    timed = {c: t for c, t in results.items() if t is not None}
    if not timed:
        raise RuntimeError(
            f"flash autotune: the compiler refused every tiling: {refusals}")
    best = min(timed, key=timed.get)
    _BEST[_sig(q, k, causal, attn_mask is not None, dropout_p)] = best
    return best, results


def clear_cache():
    _BEST.clear()
