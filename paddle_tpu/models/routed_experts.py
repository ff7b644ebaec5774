"""The routed-expert layer that the families with sparse experts share
(``glm_dsa``, ``mellum``): what a router chose goes in, the held experts'
part of ``sum_e g_e SwiGLU_e(h)`` comes out, with what was done counted.

The router is the family's own (GLM's a sigmoid with a bias that moves the
choice alone, Mellum's a softmax over all experts renormalised over the
chosen); so is what else a layer adds (a shared expert). Here are the
grouped product and its counts: assignments grouped by expert, every held
expert's group padded to whole tiles (``tile_layout``), the tiles that hold
anything multiplied, dropless whatever the skew, a token's rows of the
result added up. On a TPU the tiles are the grid of one Pallas kernel that
reads each touched expert's weights in place, once, the next expert's
copied in while this one's are multiplied, and that takes its rows from
``h`` and adds its results to their tokens itself
(``ops/pallas/grouped_experts.py``); ``_walk``, a ``fori_loop`` with one
turn a tile, is the XLA route: the kernel's reference, what the CPU tier
runs inside the models, and what shapes the kernel does not take get
anywhere. The layer is told which experts it holds (``held`` = (start,
count)): a chosen expert held elsewhere adds nothing (expert parallelism's
share of the layer, without its exchange); a chip that holds them all says
``(0, num_experts)``. Forward only: no expert layer is on a training path.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import pallas as _pallas
from ..ops.pallas.grouped_experts import grouped_experts, supported

F32 = jnp.float32


def _mm(x, w, out=None):
    return jnp.dot(x, w, preferred_element_type=F32).astype(out or x.dtype)


def _swiglu(x, w1, w2):
    gp = _mm(x, w1, F32)
    f = gp.shape[-1] // 2
    return _mm((jax.nn.silu(gp[..., :f]) * gp[..., f:]).astype(x.dtype), w2)


def _tile_rows(n: int) -> int:
    """Rows of one tile of the grouped product: whole groups of a decode
    step, MXU-sized tiles of a chunk."""
    return int(min(128, max(16, 1 << (max(n, 1) - 1).bit_length())))


class Tiles(NamedTuple):
    """Where the grouped product finds its rows, and a token its own."""
    tile_expert: jax.Array      # [max_tiles] the held expert of each tile
    n_tiles: jax.Array          # () tiles that hold anything
    token: jax.Array            # [max_tiles, tm] the row of h; -1: padding
    gate: jax.Array             # [max_tiles, tm] float32, 0 on padding
    at: jax.Array               # [N, k] an assignment's row of the tiles
    local: jax.Array            # [N, k] whether its expert is held here
    counts: jax.Array           # [count] assignments to each held expert


def tile_layout(chosen, gates, held, tm: int) -> Tiles:
    """The N * k assignments grouped by expert in their order, every held
    expert's group padded to whole tiles of ``tm`` rows: ``max_tiles = N k
    / tm + count`` tiles, of which the first ``n_tiles`` hold anything.

    No sort and no gather (XLA's on a TPU cost a microsecond an index or
    so: they were a fifth of the layer): an assignment's place in its
    group is a running count down the one-hot ``[N k, count]``, and the
    rows learn their token and gate by a scatter of the N k assignments."""
    start, count = held
    n, k = chosen.shape
    max_tiles = -(-n * k // tm) + count
    rows = max_tiles * tm
    local = (chosen >= start) & (chosen < start + count)
    expert = jnp.where(local, chosen - start, count).reshape(-1)   # [A]
    one = expert[:, None] == jnp.arange(count)[None, :]            # [A, E]
    ones = one.astype(jnp.int32)
    counts = jnp.sum(ones, 0, dtype=jnp.int32)
    padded = -(-counts // tm) * tm
    pad_end = jnp.cumsum(padded, dtype=jnp.int32)
    tile_expert = jnp.minimum(jnp.sum(
        pad_end[None, :] <= (jnp.arange(max_tiles) * tm)[:, None], -1,
        dtype=jnp.int32), count - 1)                         # [max_tiles]
    # the group's first row + the assignments to the expert before this one
    at = jnp.sum(jnp.where(
        one, (pad_end - padded)[None, :] + jnp.cumsum(ones, 0) - ones, 0),
        -1, dtype=jnp.int32)
    to = jnp.where(local.reshape(-1), at, rows)              # rows: nowhere
    return Tiles(
        tile_expert, pad_end[-1] // tm,
        jnp.full(rows, -1, jnp.int32).at[to].set(
            jnp.arange(n * k, dtype=jnp.int32) // k,
            mode="drop").reshape(max_tiles, tm),
        jnp.zeros(rows, F32).at[to].set(
            gates.reshape(-1).astype(F32), mode="drop").reshape(
                max_tiles, tm),
        at.reshape(n, k), local, counts)


def _walk(p, h, tiles: Tiles):
    """The XLA route of the grouped product, and the kernel's reference:
    the tiles that hold anything one after another in a ``fori_loop``, each
    through its expert's weights; a token then adds up its own rows of the
    result (an assignment to an expert held elsewhere adds nothing)."""
    max_tiles, tm = tiles.token.shape

    def tile(t, out):
        e = tiles.tile_expert[t]
        x = h[jnp.maximum(tiles.token[t], 0)]
        y = _swiglu(x, lax.dynamic_index_in_dim(p["exp_w1"], e, 0, False),
                    lax.dynamic_index_in_dim(p["exp_w2"], e, 0, False))
        y = (y.astype(F32) * tiles.gate[t][:, None]).astype(h.dtype)
        return lax.dynamic_update_slice_in_dim(out, y, t * tm, 0)

    out = lax.fori_loop(0, tiles.n_tiles, tile,
                        jnp.zeros((max_tiles * tm, h.shape[-1]), h.dtype))
    mine = out[jnp.where(tiles.local, tiles.at, 0)].astype(F32)
    mine = jnp.where(tiles.local[..., None], mine, 0.0)
    return jnp.sum(mine, 1).astype(h.dtype)


def routed_experts(p, h, chosen, gates, held):
    """The held experts' part of ``sum_e g_e SwiGLU_e(h)``: h [N, d] ->
    ([N, d], assignments to each held expert [count] int32).

    Dropless, whatever the skew: the N * k assignments are grouped by
    expert, every held expert's group is padded to whole tiles of
    ``_tile_rows(N)`` rows, and the tiles that hold anything are
    multiplied, each through its expert's weights (an expert nobody chose
    costs nothing, its weights are not read); a token's rows of the result
    are added up. On a TPU the tiles are the grid of one Pallas kernel
    (``ops/pallas/grouped_experts.py``), which takes a tile's rows from
    ``h`` and adds its results to their tokens with one-hot products, so a
    row of ``h`` that is not finite goes in as zeros with gates of zero
    (its residual carries the fault, the other rows never see it);
    elsewhere, and for shapes the kernel does not take, ``_walk``."""
    tm = _tile_rows(chosen.shape[0])
    w1, w2 = p["exp_w1"], p["exp_w2"]
    kernel = _pallas.on_tpu() and h.dtype == w1.dtype == w2.dtype and \
        supported(h.shape[0], tm, h.shape[1], w2.shape[1], h.dtype)
    if kernel:
        sound = jnp.all(jnp.isfinite(h), -1, keepdims=True)
        h, gates = jnp.where(sound, h, 0), jnp.where(sound, gates, 0)
    tiles = tile_layout(chosen, gates, held, tm)
    if kernel:
        y = grouped_experts(h, tiles.token, tiles.gate, w1, w2,
                            tiles.tile_expert, tiles.n_tiles, tile_rows=tm)
    else:
        y = _walk(p, h, tiles)
    return y, tiles.counts


def expert_counts(counts, assigned):
    """The [4] int32 a layer leaves in ``step_counts`` (the batcher's
    ``_init_step_counts_series`` says in what order): the assignments to
    experts held here, all the assignments the router made (``assigned``),
    the held experts touched, the fullest one's tokens. ``counts`` is
    ``routed_experts``' second result."""
    return jnp.stack([
        jnp.sum(counts, dtype=jnp.int32), jnp.asarray(assigned, jnp.int32),
        jnp.sum(counts > 0, dtype=jnp.int32), jnp.max(counts)])


def _counts_of_step(counts, *per_layer):
    """``step_counts`` [2, layers, 6] after a decode step: the layers' own
    counts in [0], the chunks' kept."""
    return jnp.stack([jnp.stack(per_layer), counts[1]])


def _counts_of_chunk(counts, *per_layer):
    """``step_counts`` after a chunk: its own added to [1]."""
    return counts.at[1].add(jnp.stack(per_layer))
