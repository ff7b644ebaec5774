"""The flash kernels' shape-derived tiling (CPU: nothing here runs a kernel
on a device; the one interpreted call only resolves and counts).

``flash_tiling`` is a pure function of the call's shape: the benchmark's
train cell pins ``FLAGS_flash_autotune`` off so that every run compiles one
program, and a default that depended on a clock, a sweep or the device would
break that. On-chip compiles of what it returns: tests/test_chip_compile.py;
numerics under it: tests/test_pallas_kernels.py.
"""
import math

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.ops.pallas import autotune
from paddle_tpu.ops.pallas.flash_attention import (
    DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, MAX_BLOCK, VMEM_BUDGET, Tile, Tilings,
    _resolve_blocks, flash_attention_pallas, flash_tiling, padded_len,
    vmem_bytes)

KERNELS = Tilings._fields

# (s, d, itemsize, hq, hkv): the train cell (SmolLM2 heads of 64, 2,048
# tokens, bfloat16), Mistral-7B (heads of 128, GQA 32/8, 4,096), GPT-2
# (heads of 64, 1,024, float32 and bfloat16), and the edges: 8k, a length
# that pads, one tile, under one tile
SHAPES = {
    "smollm2_cell": (2048, 64, 2, 32, 32),
    "mistral7b": (4096, 128, 2, 32, 8),
    "gpt2_f32": (1024, 64, 4, 12, 12),
    "gpt2_bf16": (1024, 64, 2, 12, 12),
    "llama_8k": (8192, 128, 2, 32, 32),
    "f32_d128_4k": (4096, 128, 4, 8, 8),
    "pads_2176": (2176, 64, 2, 4, 4),
    "one_tile": (128, 64, 2, 2, 2),
    "short_48": (48, 32, 4, 2, 2),
}


@pytest.mark.parametrize("dropout", [False, True], ids=["nodrop", "drop"])
@pytest.mark.parametrize("has_mask", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_tiling_is_pure_fits_and_divides(shape, has_mask, dropout):
    s, d, itemsize, _, _ = SHAPES[shape]
    s_pad = padded_len(s)
    tiles = [flash_tiling(kn, s, d, itemsize, True, has_mask, dropout)
             for kn in KERNELS]
    again = [flash_tiling(kn, s, d, itemsize, True, has_mask, dropout)
             for kn in KERNELS]
    assert tiles == again                       # same shape, same answer
    for kn, t in zip(KERNELS, tiles):
        assert vmem_bytes(kn, t, d, itemsize, has_mask) <= VMEM_BUDGET, t
        assert s_pad % t.block_q == 0 and s_pad % t.block_k == 0
        assert t.block_q % t.sub_q == 0 and t.block_k % t.sub_k == 0
        assert max(t.block_q, t.block_k) <= MAX_BLOCK
        if s_pad >= 128:                         # whole lanes, whole tiles
            assert t.sub_q % 128 == 0 and t.sub_k % 128 == 0
    if dropout:
        # the hardware mask is drawn per score tile from its coordinates:
        # one tiling for the forward and both backward kernels
        assert len(set(tiles)) == 1


def test_tiling_of_the_train_cell():
    """What `smollm2-train-seq2k` runs: one head's whole sequence a grid
    step (grid (128, 1, 1), no step above the diagonal), walked in score
    tiles inside the body."""
    for kn in KERNELS:
        t = flash_tiling(kn, 2048, 64, 2, True, False, False)
        assert (t.block_q, t.block_k) == (2048, 2048)
        assert t.sub_q * t.sub_k * 4 <= 2 ** 20      # a score tile <= 1 MiB


def test_mask_and_wide_heads_shrink_the_grid_tile():
    for kn in KERNELS:
        plain = flash_tiling(kn, 4096, 64, 2, True, False, False)
        masked = flash_tiling(kn, 4096, 64, 2, True, True, False)
        assert masked.block_q * masked.block_k < plain.block_q * plain.block_k
        # the float32 mask tile, double-buffered, is what had to fit
        assert 2 * masked.block_q * masked.block_k * 4 <= VMEM_BUDGET
    wide = flash_tiling("dkv", 4096, 128, 4, True, False, False)
    slim = flash_tiling("dkv", 4096, 64, 2, True, False, False)
    assert wide.block_q * wide.block_k < slim.block_q * slim.block_k


@pytest.mark.parametrize("s,expect", [
    (2048, 2048), (2176, 2304), (2050, 2176), (1000, 1024), (600, 640),
    (200, 256), (128, 128), (100, 128), (48, 64), (5, 8)])
def test_padded_len_never_doubles_a_sequence(s, expect):
    assert padded_len(s) == expect
    assert padded_len(s) - s < max(s / 4, 128)


def _qkv(s, hq, hkv, d, dtype):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, s, hq, d), jnp.float32).astype(dtype)
    k = jnp.asarray(rng.randn(1, s, hkv, d), jnp.float32).astype(dtype)
    return q, k, k


def test_explicit_blocks_win_for_all_three_kernels():
    q, k, v = _qkv(1024, 2, 2, 64, jnp.bfloat16)
    for blocks in ((256, 512), (512, 256), (1024, 128), (128, 128)):
        tiles = _resolve_blocks(q, k, v, True, None, 0.0, *blocks, False)
        assert len(set(tiles)) == 1
        assert (tiles.fwd.block_q, tiles.fwd.block_k) == blocks
        assert blocks[0] % tiles.fwd.sub_q == 0
        assert blocks[1] % tiles.fwd.sub_k == 0
    half = _resolve_blocks(q, k, v, True, None, 0.0, 256, None, False)
    assert (half.fwd.block_q, half.fwd.block_k) == (256, DEFAULT_BLOCK_K)
    # a sequence shorter than the blocks named: one 128-row tile, as ever
    q, k, v = _qkv(200, 2, 2, 64, jnp.bfloat16)
    short = _resolve_blocks(q, k, v, True, None, 0.0, 256, 512, False)
    assert short.fwd == Tile(DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, 128, 128)


def test_default_is_among_the_autotune_candidates():
    """A sweep, where someone turns the flag on, can never pick worse than
    the shape-derived default: it is measured with the rest."""
    q, k, v = _qkv(2048, 2, 2, 64, jnp.bfloat16)
    cands = autotune.candidates_for(q, k, True, False, 0.0)
    dflt = flash_tiling("fwd", 2048, 64, 2, True, False, False)
    assert (dflt.block_q, dflt.block_k) in cands
    assert all(math.lcm(*c) <= 2048 for c in cands)
    assert set(autotune.CANDIDATES) <= set(cands)


def _tiling_count(**labels):
    entry = get_registry().get("flash_tiling_total")
    return 0.0 if entry is None else entry.labels(**labels).value


def test_flash_tiling_total_counts_one_per_resolved_kernel():
    """The labels the train cell's run should show: every kernel a grid tile
    of 2048 x 2048 with bfloat16 operands (trace time: once an executable)."""
    labels = [dict(kernel=kn, block_q="2048", block_k="2048",
                   operand="bfloat16") for kn in KERNELS]
    before = [_tiling_count(**lb) for lb in labels]
    q, k, v = _qkv(2048, 1, 1, 64, jnp.bfloat16)
    flash_attention_pallas(q, k, v, causal=True, interpret=True)
    assert [_tiling_count(**lb) for lb in labels] == [b + 1 for b in before]
    # float32 inputs are counted as such: they multiply in float32
    f32 = dict(kernel="fwd", block_q="256", block_k="256", operand="float32")
    n = _tiling_count(**f32)
    q, k, v = _qkv(256, 1, 1, 32, jnp.float32)
    flash_attention_pallas(q, k, v, causal=True, interpret=True)
    assert _tiling_count(**f32) == n + 1
