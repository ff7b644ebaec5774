"""The routed-expert layer that the families with sparse experts share
(``glm_dsa``, ``mellum``): what a router chose goes in, the held experts'
part of ``sum_e g_e SwiGLU_e(h)`` comes out, with what was done counted.

The router is the family's own (GLM's a sigmoid with a bias that moves the
choice alone, Mellum's a softmax over all experts renormalised over the
chosen); so is what else a layer adds (a shared expert). Here are the
grouped product and its counts: assignments sorted by expert, every held
expert's group padded to whole tiles, the tiles that hold anything
multiplied one after another, dropless whatever the skew. The layer is told
which experts it holds (``held`` = (start, count)): a chosen expert held
elsewhere adds nothing (expert parallelism's share of the layer, without
its exchange); a chip that holds them all says ``(0, num_experts)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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
    return int(min(128, max(8, 1 << (max(n, 1) - 1).bit_length())))


def routed_experts(p, h, chosen, gates, held):
    """The held experts' part of ``sum_e g_e SwiGLU_e(h)``: h [N, d] ->
    ([N, d], assignments to each held expert [count] int32).

    Dropless, whatever the skew: the N * k assignments are sorted by
    expert, every held expert's group is padded to whole tiles of
    ``_tile_rows(N)`` rows, and the tiles that hold anything are
    multiplied one after another, each through its expert's weights (an
    expert nobody chose costs nothing, its weights are not read); a token
    then adds up its own rows of the result."""
    start, count = held
    n, k = chosen.shape
    d = h.shape[-1]
    tm = _tile_rows(n)
    max_tiles = -(-n * k // tm) + count
    local = (chosen >= start) & (chosen < start + count)
    expert = jnp.where(local, chosen - start, count).reshape(-1)   # [A]
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    where_sorted = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    counts = jnp.sum(expert[:, None] == jnp.arange(count)[None, :], 0,
                     dtype=jnp.int32)
    padded = -(-counts // tm) * tm
    pad_end = jnp.cumsum(padded)
    pad_start = pad_end - padded
    src_start = jnp.cumsum(counts) - counts
    n_tiles = pad_end[-1] // tm
    tile_expert = jnp.minimum(jnp.sum(
        pad_end[None, :] <= (jnp.arange(max_tiles) * tm)[:, None], -1,
        dtype=jnp.int32), count - 1)                         # [max_tiles]
    r = jnp.arange(max_tiles * tm, dtype=jnp.int32)
    e_r = jnp.repeat(tile_expert, tm)
    within = r - pad_start[e_r]
    real = within < counts[e_r]
    assign = order[jnp.clip(src_start[e_r] + within, 0, n * k - 1)]
    token = jnp.where(real, assign // k, 0).reshape(max_tiles, tm)
    gate = jnp.where(real, gates.reshape(-1)[assign], 0.0).reshape(
        max_tiles, tm)

    def tile(t, out):
        e = tile_expert[t]
        x = h[token[t]]
        y = _swiglu(x, lax.dynamic_index_in_dim(p["exp_w1"], e, 0, False),
                    lax.dynamic_index_in_dim(p["exp_w2"], e, 0, False))
        y = (y.astype(F32) * gate[t][:, None]).astype(h.dtype)
        return lax.dynamic_update_slice_in_dim(out, y, t * tm, 0)

    out = lax.fori_loop(0, n_tiles, tile,
                        jnp.zeros((max_tiles * tm + 1, d), h.dtype))
    # a token's own rows: an assignment to an expert held elsewhere reads
    # the zero row at the end
    own = jnp.minimum(expert, count - 1)
    at = jnp.where(local.reshape(-1),
                   pad_start[own] + where_sorted - src_start[own],
                   max_tiles * tm)
    mine = out[at.reshape(n, k)].astype(F32)
    return jnp.sum(mine, 1).astype(h.dtype), counts


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
