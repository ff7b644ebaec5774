"""Flash-attention block autotuner (CPU-side machinery tests).

Timing only means something on real hardware — `pytest -m tpu` runs the
actual sweep (test_tpu_tier.py). Here we pin the pure machinery:
candidate filtering, the cache, and `_resolve_blocks` (explicit blocks
win; cached tilings are adopted; short sequences and interpret mode skip
the consult and get the shape-derived tiling: tests/test_flash_tiling.py)
— without ever running a Mosaic kernel.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import autotune
from paddle_tpu.ops.pallas.flash_attention import (DEFAULT_BLOCK_K,
                                                   Tilings, _resolve_blocks,
                                                   flash_attention_pallas,
                                                   flash_tiling)


def _grid_tiles(tiles):
    """(block_q, block_k) of the three kernels' tilings: one pair where a
    caller or the tuner named it, the shape-derived ones otherwise."""
    return {t[:2] for t in tiles}


def _derived(q):
    _, s, _, d = q.shape
    return Tilings(*(flash_tiling(kn, s, d, q.dtype.itemsize, True, False,
                                  False) for kn in Tilings._fields))


def _rand(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


@pytest.fixture(autouse=True)
def _clean_cache():
    autotune.clear_cache()
    yield
    autotune.clear_cache()


@pytest.fixture
def _flag_on():
    paddle.set_flags({"FLAGS_flash_autotune": True})
    yield
    paddle.set_flags({"FLAGS_flash_autotune": False})


def test_tuning_refuses_off_tpu():
    q = _rand((1, 256, 2, 64))
    with pytest.raises(RuntimeError, match="off TPU"):
        autotune.tune_flash_blocks(q, q, q)


def test_candidate_filter_drops_over_lcm_tilings():
    assert autotune._filter_candidates(64, autotune.CANDIDATES) == []
    got = autotune._filter_candidates(256, autotune.CANDIDATES)
    assert (128, 128) in got and (256, 256) in got
    assert (128, 512) not in got and (512, 128) not in got
    assert autotune._filter_candidates(
        512, autotune.CANDIDATES) == autotune.CANDIDATES


def test_cached_blocks_roundtrip_and_set_best():
    q, k = _rand((1, 256, 4, 64), 1), _rand((1, 256, 2, 64), 2)
    assert autotune.cached_blocks(q, k, True, False, 0.0) is None
    autotune.set_best(q, k, True, False, 0.0, (256, 128))
    assert autotune.cached_blocks(q, k, True, False, 0.0) == (256, 128)
    # a different signature misses
    assert autotune.cached_blocks(q, k, False, False, 0.0) is None


def test_resolve_blocks_explicit_always_wins(_flag_on):
    """A caller forcing the default tiling must GET the default tiling,
    even when the cache prefers another one (review repro)."""
    q, k, v = _rand((1, 512, 2, 64), 3), _rand((1, 512, 2, 64), 4), \
        _rand((1, 512, 2, 64), 5)
    autotune.set_best(q, k, True, False, 0.0, (256, 256))
    assert _grid_tiles(_resolve_blocks(q, k, v, True, None, 0.0, 128, 128,
                                       False)) == {(128, 128)}
    assert _grid_tiles(_resolve_blocks(q, k, v, True, None, 0.0, 256, None,
                                       False)) == {(256, DEFAULT_BLOCK_K)}


def test_resolve_blocks_adopts_cached_tiling(_flag_on):
    q, k, v = _rand((1, 512, 2, 64), 6), _rand((1, 512, 2, 64), 7), \
        _rand((1, 512, 2, 64), 8)
    autotune.set_best(q, k, True, False, 0.0, (256, 128))
    assert _grid_tiles(_resolve_blocks(q, k, v, True, None, 0.0, None, None,
                                       False)) == {(256, 128)}
    # flag off: the tiling computed from the shape
    paddle.set_flags({"FLAGS_flash_autotune": False})
    assert _resolve_blocks(q, k, v, True, None, 0.0, None, None,
                           False) == _derived(q)
    paddle.set_flags({"FLAGS_flash_autotune": True})


def test_resolve_blocks_skips_short_seq_and_interpret(_flag_on):
    """Short sequences (shrink branch governs) and interpret mode never
    consult the cache — no wasted tuning for a discarded answer."""
    q, k, v = _rand((1, 64, 2, 64), 9), _rand((1, 64, 2, 64), 10), \
        _rand((1, 64, 2, 64), 11)
    autotune.set_best(q, k, True, False, 0.0, (256, 128))
    assert _resolve_blocks(q, k, v, True, None, 0.0, None, None,
                           False) == _derived(q)
    q2, k2, v2 = _rand((1, 512, 2, 64), 12), _rand((1, 512, 2, 64), 13), \
        _rand((1, 512, 2, 64), 14)
    autotune.set_best(q2, k2, True, False, 0.0, (256, 128))
    assert _resolve_blocks(q2, k2, v2, True, None, 0.0, None, None,
                           True) == _derived(q2)


def test_block_choice_is_numerics_neutral():
    """Different tilings, identical math (interpret mode, CPU)."""
    b, s, h, d = 1, 256, 2, 64
    q, k, v = _rand((b, s, h, d), 15), _rand((b, s, h, d), 16), \
        _rand((b, s, h, d), 17)
    ref = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    out = flash_attention_pallas(q, k, v, causal=True, block_q=64,
                                 block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
