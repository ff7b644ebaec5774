"""Cross-cutting performance layer: kill the recompiles, feed the device.

Three subsystems, adopted by the four hot paths (serving admission, the
dataloader, the ``jit`` trace caches, and the hapi train loop):

  * ``buckets``       — shared shape-bucketing policy (``BucketLadder``,
    ``ShapeBuckets``): pad dynamic extents onto a fixed ladder so XLA
    compiles O(#buckets) programs instead of O(#shapes).
  * ``compile_cache`` — the one function that places JAX's persistent
    compilation cache, plus the ``compile.hit`` /
    ``compile.miss`` / ``compile.elapsed`` counters every framework
    dispatch cache reports through (recompiles are a regressable metric).
  * ``prefetch``      — coalesced single-transfer ``device_put`` for
    batch trees and the double-buffered async ``DevicePrefetcher``
    (``DataLoader(prefetch_to_device=...)``; on by default in
    ``hapi.Model.fit``).
"""
from __future__ import annotations

from . import buckets, compile_cache, prefetch
from .buckets import BucketLadder, ShapeBuckets, resolve_ladder
from .compile_cache import (compile_metrics, donation_safe,
                            enable_persistent_cache)
from .prefetch import DevicePrefetcher, coalesced_device_put

__all__ = [
    "buckets", "compile_cache", "prefetch",
    "BucketLadder", "ShapeBuckets", "resolve_ladder",
    "compile_metrics", "donation_safe", "enable_persistent_cache",
    "DevicePrefetcher", "coalesced_device_put",
]
