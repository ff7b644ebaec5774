"""Pallas grouped expert product (TPU): a routed-expert layer's tiles, each
through its own expert's SwiGLU, the weights read in place, the rows taken
from the layer's input and added into its result inside the kernel.

``models/routed_experts.py`` groups a step's assignments by expert and pads
every held expert's group to whole tiles of ``tile_rows`` rows; here the
tiles are multiplied. The XLA route (``routed_experts._walk``) walks them in
a ``fori_loop``, one turn a tile: the expert's two matrices taken out of the
stacks, two products that start and end alone, nothing in flight across the
turn's end. Here the walk is the grid of ONE kernel:

  * ``tile_expert [max_tiles]`` and ``n_tiles`` arrive as scalar prefetch
    (SMEM) and address the weight blocks in the ``BlockSpec`` index maps,
    so the pipeline copies tile ``t + 1``'s first weight block while tile
    ``t`` is multiplied, and two consecutive tiles whose index maps name
    the same block (one expert's, where a block is all of ``f``) cost one
    copy;
  * ``exp_w1 [E, d, 2f]`` and ``exp_w2 [E, f, d]`` stay where they are: a
    grid step ``(t, j)`` sees columns ``j`` of the expert's gate half and
    of its up half (two block specs over the one array) and rows ``j`` of
    its down matrix, ``block_f`` wide; no expert's matrix is materialised
    and an expert nobody chose is not read;
  * ``h [N, d]`` is held whole in VMEM, and so is the float32 sum the
    result is made of: a tile's rows are ``pick @ h`` (``pick [tile_rows,
    N]`` holds a one in a row's token's column: a product of ones and
    stored values, exact), and its result rows go back as ``pick^T @ o``
    into their tokens' sums (a token meets an expert once, so a tile adds
    one row at most to a token: the sum over a token's experts is the
    float32 sum it was, in the experts' order). Neither the gathered rows
    nor the tiles' results ever exist in HBM;
  * tiles at and past ``n_tiles`` (the grid is the worst case,
    ``N k / tile_rows + E``) cost a grid step and nothing else: their index
    maps name the last real tile's last blocks (no copy) and the body is
    under ``pl.when``; whatever their rows of ``token`` and ``gate`` hold
    is never read;
  * the roundings are ``routed_experts._swiglu``'s: operands as stored,
    float32 accumulation, ``silu(gate) * up`` in float32 rounded to the
    activations' type before the down product, the down product summed over
    the blocks of ``f`` in float32 (the order of a sum, nothing else), the
    result rounded, times the router's gate in float32, rounded again, a
    token's rows summed in float32 and rounded.

One fused kernel, not two grouped products with the activation between
them: the activations of a tile (``[tile_rows, block_f]``) never leave
VMEM, and one pipeline carries all three matrices of the next expert in
while this one's are multiplied.

A product with a one-hot matrix is a sum over every row of ``h``: a row of
``h`` that is not finite would reach every token of every tile (``0 * nan``).
The caller hands in rows that are finite (``routed_experts`` zeroes a row
that is not: its residual carries the fault on, its neighbours never see
it).

``grouped_experts_tiling`` is a pure function of the shape, as
``flash_tiling`` is: the widest block of ``f`` (a multiple of 128 lanes
that divides it) whose grid step fits ``VMEM_BUDGET``, None where not even
the narrowest does (``h`` too long to hold: the caller keeps the XLA
route). The v5e has 128 MiB of VMEM of which Mosaic scopes a kernel 16 by
default; an expert of the served widths does not fit that twice (12.4 MB at
2,304 x 896, 75.5 MB at 6,144 x 2,048), so the call states ``VMEM_LIMIT``
and the blocks are sized to the budget under it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_call
from .flash_attention import _largest_divisor

_LANES = 128
_SUM_ROWS = 256                # rows of the result a tile is added to at once
VMEM_LIMIT = 100 << 20         # stated to Mosaic (``vmem_limit_bytes``)
VMEM_BUDGET = 80 << 20         # what a grid step's blocks may sum to


def _sum_rows(n: int) -> int:
    """Rows of the result one ``pick^T @ o`` covers: whole sublanes that
    divide ``n``, ``_SUM_ROWS`` at most (all of an ``n`` that is not whole
    sublanes: interpreted only)."""
    return n if n % 8 else _largest_divisor(n, 8, _SUM_ROWS)


def vmem_bytes(n: int, tile_rows: int, d: int, block_f: int,
               itemsize: int) -> int:
    """VMEM one grid step holds with blocks of ``block_f``: what the
    pipeline double-buffers (``h`` and the result, the three weight blocks,
    a tile's tokens and gates a lane wide), the scratch (a tile's rows, its
    float32 accumulator, the result's float32 sum), and the body's
    temporaries (``pick``, the gate and up products and their activation in
    float32, the down product's part and its rounding, one block of rows of
    ``pick^T @ o``)."""
    held = 2 * 2 * n * d * itemsize + n * d * 4
    weights = 2 * 3 * d * block_f * itemsize
    tile = 2 * 3 * tile_rows * _LANES * 4 \
        + tile_rows * d * (itemsize + 4)
    body = 2 * tile_rows * max(n, _LANES) * itemsize \
        + 3 * tile_rows * block_f * 4 + 2 * tile_rows * d * 4 \
        + _sum_rows(n) * d * 4
    return held + weights + tile + body


def grouped_experts_tiling(n: int, tile_rows: int, d: int, f: int,
                           itemsize: int):
    """``block_f`` for a call's shape: the widest whole-lane divisor of
    ``f`` whose grid step fits ``VMEM_BUDGET`` (fewer, longer copies: a
    step of the pipeline costs a third of a microsecond whatever it moves,
    and a block that is all of ``f`` lets an expert's second tile reuse the
    first one's copy); ``f`` itself where ``f`` is not whole lanes
    (interpreted at cut widths); None where nothing fits."""
    if f % _LANES:
        return f
    lanes = f // _LANES
    for m in range(lanes, 0, -1):
        if lanes % m == 0 and vmem_bytes(n, tile_rows, d, m * _LANES,
                                         itemsize) <= VMEM_BUDGET:
            return m * _LANES
    return None


def supported(n: int, tile_rows: int, d: int, f: int, dtype) -> bool:
    """What Mosaic takes as it lies and VMEM holds: whole lanes of ``d``
    and of ``f`` (the up half of ``exp_w1`` starts at column ``f``), whole
    sublanes of rows, float operands, ``h`` and its result resident."""
    dtype = jnp.dtype(dtype)
    return d % _LANES == 0 and f % _LANES == 0 and n % 8 == 0 \
        and jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize <= 4 \
        and grouped_experts_tiling(n, tile_rows, d, f,
                                   dtype.itemsize) is not None


def _tiling_counter(n, d, f, tile_rows, block_f) -> None:
    from ...observability.metrics import get_registry
    get_registry().counter(
        "routed_experts_tiling_total",
        "grouped expert products lowered, by the call's shape and the "
        "blocks it got (trace time: once a shape, whatever the layers)",
        labelnames=("rows", "d", "f", "tile_rows", "block_f"),
    ).labels(rows=str(n), d=str(d), f=str(f), tile_rows=str(tile_rows),
             block_f=str(block_f)).inc()


def _kernel(te_ref, nt_ref, down_ref, across_ref, gate_ref, h_ref, wg_ref,
            wu_ref, w2_ref, y_ref, x_ref, acc_ref, sum_ref, *, f_blocks):
    del te_ref                            # the index maps read it
    t, j = pl.program_id(0), pl.program_id(1)
    n = h_ref.shape[0]
    tile_rows = x_ref.shape[0]
    dtype = x_ref.dtype

    @pl.when((t == 0) & (j == 0))
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    @pl.when(t < nt_ref[0])
    def _():
        @pl.when(j == 0)
        def _():                          # the tile's rows of h
            pick = down_ref[...] == lax.broadcasted_iota(
                jnp.int32, (tile_rows, n), 1)
            x_ref[...] = jnp.dot(pick.astype(dtype), h_ref[...],
                                 preferred_element_type=jnp.float32
                                 ).astype(dtype)

        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        part = jnp.dot((jax.nn.silu(gate) * up).astype(dtype), w2_ref[...],
                       preferred_element_type=jnp.float32)

        def done(y):                      # into the tokens' sums
            y = y.astype(dtype).astype(jnp.float32)
            o = (y * gate_ref[...]).astype(dtype)
            rows = _sum_rows(n)
            for r in range(0, n, rows):
                put = across_ref[...] == r + lax.broadcasted_iota(
                    jnp.int32, (rows, tile_rows), 0)
                sum_ref[r:r + rows, :] += jnp.dot(
                    put.astype(dtype), o, preferred_element_type=jnp.float32)

        if f_blocks == 1:
            done(part)
            return

        @pl.when(j == 0)
        def _():
            acc_ref[...] = part

        @pl.when(j > 0)
        def _():
            acc_ref[...] += part

        @pl.when(j == f_blocks - 1)
        def _():
            done(acc_ref[...])

    @pl.when((t == pl.num_programs(0) - 1) & (j == f_blocks - 1))
    def _():
        y_ref[...] = sum_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_rows", "block_f",
                                             "interpret"))
def _grouped_call(tile_expert, n_tiles, token, gate, h, w1, w2, *, tile_rows,
                  block_f, interpret):
    """The launch, jitted on its own with the tiling static: a stack's
    layers of one shape trace and lower the kernel once, in the eager
    first call of a ``to_static`` step as in the step itself."""
    n, d = h.shape
    f = w2.shape[1]
    max_tiles = token.shape[0] // tile_rows
    f_blocks = f // block_f
    _tiling_counter(n, d, f, tile_rows, block_f)

    def tile(t, nt):
        return jnp.minimum(t, jnp.maximum(nt[0] - 1, 0))

    def block(t, j, nt):              # a dead tile: the last real one's last
        return jnp.where(t < nt[0], j, f_blocks - 1)

    down = pl.BlockSpec((tile_rows, 1), lambda t, j, te, nt: (tile(t, nt), 0))
    whole = pl.BlockSpec((n, d), lambda t, j, te, nt: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, f_blocks=f_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(max_tiles, f_blocks),
            in_specs=[
                down,                                   # a row's token, down
                pl.BlockSpec((None, 1, tile_rows),      # and across
                             lambda t, j, te, nt: (tile(t, nt), 0, 0)),
                down,                                   # a row's gate
                whole,
                pl.BlockSpec((None, d, block_f), lambda t, j, te, nt: (
                    te[tile(t, nt)], 0, block(t, j, nt))),
                pl.BlockSpec((None, d, block_f), lambda t, j, te, nt: (
                    te[tile(t, nt)], 0, f_blocks + block(t, j, nt))),
                pl.BlockSpec((None, block_f, d), lambda t, j, te, nt: (
                    te[tile(t, nt)], block(t, j, nt), 0)),
            ],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((tile_rows, d), h.dtype),
                            pltpu.VMEM((tile_rows, d), jnp.float32),
                            pltpu.VMEM((n, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
        # tiles in order: a tile's weights are fetched under the one before
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="grouped_experts",
    )(tile_expert, n_tiles, token.reshape(-1, 1),
      token.reshape(max_tiles, 1, tile_rows), gate.reshape(-1, 1), h, w1, w1,
      w2)


def grouped_experts(h, token, gate, w1, w2, tile_expert, n_tiles, *,
                    tile_rows, block_f=None, interpret=False):
    """``sum`` over the tiles of ``gate * SwiGLU_e(h[token])`` added to the
    rows' tokens: h ``[N, d]`` (finite) -> ``[N, d]`` in h's type. Row ``r``
    of tile ``t = r // tile_rows`` is token ``token[r]`` (-1: padding, which
    takes no row and adds to none) through expert ``tile_expert[t]`` of
    ``w1 [E, d, 2f]`` / ``w2 [E, f, d]``, times ``gate[r]`` (float32). Only
    the first ``n_tiles`` tiles are computed: what ``token`` and ``gate``
    hold past them is not read. ``block_f`` where given is a caller's (the
    tests'); else ``grouped_experts_tiling``."""
    (n, d), f = h.shape, w2.shape[1]
    if block_f is None:
        block_f = grouped_experts_tiling(n, tile_rows, d, f,
                                         h.dtype.itemsize)
    if not block_f or f % block_f:
        raise ValueError(f"no block of f {f} for h {h.shape} (block_f "
                         f"{block_f})")
    return kernel_call(
        functools.partial(_grouped_call, tile_rows=tile_rows,
                          block_f=block_f, interpret=interpret),
        tile_expert.astype(jnp.int32),
        jnp.asarray(n_tiles, jnp.int32).reshape(1),
        token.astype(jnp.int32).reshape(-1),
        gate.astype(jnp.float32).reshape(-1), h, w1, w2)
