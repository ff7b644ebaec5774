"""Ring (context-parallel) attention over the device mesh.

The reference snapshot has NO ring/Ulysses context parallelism (verified in
SURVEY.md §2.8.8); long context is served there by SEP + Megatron-SP + fused
flash attention. On TPU the idiomatic equivalent is ring attention: shard the
sequence over a mesh axis, keep Q local, and rotate K/V blocks around the ICI
ring with `ppermute`, accumulating the softmax streamingly (flash-attention
style log-sum-exp), so attention memory is O(s_local^2) and the K/V traffic
rides neighbor-to-neighbor ICI links.

GQA-aware: K/V keep their (fewer) kv heads on the wire — blocks rotate
unexpanded and the group expansion happens in the score einsum (a broadcast,
no materialized copy, h/kv less ICI traffic). Batch and head dims can stay
sharded over dp/mp mesh axes via the spec hints.

Implementation: one shard_map whose body runs the P-step ring. Differentiable
end-to-end (ppermute and the streaming softmax have exact transposes under
jax.vjp); the op integrates with the tape via the standard dispatch path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from .registry import dispatch


def _block_update(q, k, v, o, m, l, q_off, k_off, causal, scale,
                  mask_blk=None, seqlens=None):
    """One streaming-softmax step with the K/V block at seq offset k_off.

    q: [b, g, r, sq, d] (g = kv head groups, r = h // kv);
    k/v: [b, g, sk, d]; o: [b, g, r, sq, d]; m/l: [b, g, r, sq].
    mask_blk: [b, hm, sq, sk] slice of the attention mask for this k block
    (bool = keep, float = additive — flash v2 semantics). seqlens: [b]
    per-batch valid lengths (cols and rows >= len are masked).
    Accumulation in fp32.
    """
    scores = jnp.einsum("bgrqd,bgkd->bgrqk", q, k).astype(jnp.float32) * scale
    sq, sk = q.shape[3], k.shape[2]
    if mask_blk is not None:
        b, hm = mask_blk.shape[0], mask_blk.shape[1]
        g, r = q.shape[1], q.shape[2]
        if hm == 1:
            mb = mask_blk[:, :, None]                     # [b, 1, 1, sq, sk]
        else:
            mb = mask_blk.reshape(b, g, r, sq, sk)
        if mask_blk.dtype == jnp.bool_:
            scores = jnp.where(mb, scores, -jnp.inf)
        else:
            scores = scores + mb.astype(jnp.float32)
    if causal or seqlens is not None:
        rows = q_off + jnp.arange(sq)[:, None]
        cols = k_off + jnp.arange(sk)[None, :]
        if causal:
            scores = jnp.where(cols <= rows, scores, -jnp.inf)
        if seqlens is not None:
            sl = seqlens[:, None, None, None, None]       # [b, 1, 1, 1, 1]
            # rows: [sq, 1], cols: [1, sk] → lifted to [1, 1, 1, sq|1, sk|1]
            valid = ((cols[None, None, None] < sl)
                     & (rows[None, None, None] < sl))
            scores = jnp.where(valid, scores, -jnp.inf)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # fully-masked rows keep m == -inf; guard the exp against inf - inf
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(jnp.where(jnp.isneginf(scores), -jnp.inf,
                          scores - safe_m[..., None]))
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
    l = l * alpha + p.sum(axis=-1)
    o = o * alpha[..., None] + jnp.einsum(
        "bgrqk,bgkd->bgrqd", p, v.astype(jnp.float32))
    return o, m_new, l


def _ring_body(q_blk, k_blk, v_blk, axis_name, num_blocks, causal, scale,
               mask_local=None, seqlens=None):
    """Per-shard ring loop. q_blk [b, h, s_local, d]; k/v [b, kv, s_local, d].

    mask_local: [b, hm, s_local, S_full] — this shard's query rows against
    the FULL key axis; each ring step dynamic-slices the current block's
    columns. seqlens: [b] per-batch valid lengths (replicated).
    """
    i = jax.lax.axis_index(axis_name)
    b, h, sq, d = q_blk.shape
    g = k_blk.shape[1]
    r = h // g
    q = q_blk.reshape(b, g, r, sq, d)
    o = jnp.zeros((b, g, r, sq, d), jnp.float32)
    m = jnp.full((b, g, r, sq), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, g, r, sq), jnp.float32)
    perm = [(j, (j + 1) % num_blocks) for j in range(num_blocks)]
    k_cur, v_cur = k_blk, v_blk
    for t in range(num_blocks):
        src = (i - t) % num_blocks  # owner of the kv block now held locally
        # issue the NEXT block's rotation BEFORE this block's math: the
        # permute depends only on k_cur/v_cur (already live), so XLA's
        # latency-hiding scheduler overlaps the ICI transfer with the MXU
        # work — the double-buffered ring (the whole point of ring
        # attention's comm/compute pipelining)
        if t + 1 < num_blocks:
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_blk = None
        if mask_local is not None:
            mask_blk = jax.lax.dynamic_slice_in_dim(
                mask_local, src * sq, sq, axis=3)
        o, m, l = _block_update(
            q, k_cur, v_cur, o, m, l,
            q_off=i * sq, k_off=src * sq, causal=causal, scale=scale,
            mask_blk=mask_blk, seqlens=seqlens)
        if t + 1 < num_blocks:
            k_cur, v_cur = k_nxt, v_nxt
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(b, h, sq, d).astype(q_blk.dtype)


def _ring_attention_impl(query, key, value, *extras, jax_mesh, axis_name,
                         causal, batch_axis, head_axis, has_mask=False,
                         has_seqlens=False):
    """query [b, s, h, d]; key/value [b, s, kv, d]; s sharded over axis_name.

    extras (in order, as flagged): attn_mask [b, hm, s, s] (bool keep /
    float additive — rows sharded over the ring axis, cols full), then
    kv_seqlens [b] (per-batch valid lengths for packed/padded batches).
    """
    num_blocks = jax_mesh.shape[axis_name]
    s = query.shape[1]
    if s % num_blocks:
        raise ValueError(f"sequence length {s} not divisible by the "
                         f"'{axis_name}' mesh axis size {num_blocks}")
    if key.shape[1] != s or value.shape[1] != s:
        raise ValueError("ring_attention requires equal q/k/v sequence "
                         f"lengths, got q={s}, k={key.shape[1]}, "
                         f"v={value.shape[1]}")
    if query.shape[2] % key.shape[2]:
        raise ValueError("num q heads must be a multiple of kv heads")
    scale = 1.0 / (query.shape[-1] ** 0.5)

    it = iter(extras)
    mask = next(it) if has_mask else None
    seqlens = next(it) if has_seqlens else None
    if mask is not None:
        if mask.ndim != 4 or mask.shape[1] not in (1, query.shape[2]):
            raise ValueError(
                f"ring attn_mask must be [b, 1|{query.shape[2]}, s, s]-"
                f"broadcastable, got {tuple(mask.shape)}")
        if mask.shape[2] not in (1, s) or mask.shape[3] not in (1, s):
            raise ValueError(
                f"ring attn_mask dims 2/3 must be 1 or s={s}, got "
                f"{tuple(mask.shape)}")
        # materialize broadcastable row/col dims ([b,1,1,s] padding masks):
        # the ring shards rows over the sequence axis, so they must be real
        if mask.shape[2] != s or mask.shape[3] != s:
            mask = jnp.broadcast_to(
                mask, (mask.shape[0], mask.shape[1], s, s))

    def local_fn(q, k, v, *loc_extras):
        # shards arrive [b, s_local, (h|kv), d]; compute head-major
        lit = iter(loc_extras)
        m_loc = next(lit) if has_mask else None
        sl_loc = next(lit) if has_seqlens else None
        qt = jnp.einsum("bshd->bhsd", q)
        kt = jnp.einsum("bshd->bhsd", k)
        vt = jnp.einsum("bshd->bhsd", v)
        out = _ring_body(qt, kt, vt, axis_name, num_blocks, causal, scale,
                         mask_local=m_loc, seqlens=sl_loc)
        return jnp.einsum("bhsd->bshd", out)

    # keep batch/head dims sharded over their mesh axes so hybrid dp/mp runs
    # don't all-gather at the attention boundary
    spec = PartitionSpec(batch_axis, axis_name, head_axis, None)
    in_specs = [spec, spec, spec]
    args = [query, key, value]
    if has_mask:
        # query rows ride the ring axis; the key axis stays FULL per shard
        # (each step slices the current block's columns locally). A
        # per-head mask shards its head dim alongside q's heads.
        mask_head = head_axis if mask.shape[1] == query.shape[2] else None
        in_specs.append(PartitionSpec(batch_axis, mask_head, axis_name,
                                      None))
        args.append(mask)
    if has_seqlens:
        in_specs.append(PartitionSpec(batch_axis))
        args.append(seqlens)
    from ..distributed.collective import shard_map as _shard_map
    fn = _shard_map(local_fn, jax_mesh, in_specs=tuple(in_specs),
                    out_specs=spec)
    return fn(*args)


_DP_NAMES = ("dp", "data", "fsdp", "sharding")
_MP_NAMES = ("mp", "model", "tp")


def _pick_axis(mesh_axes, candidates, exclude):
    """ALL matching mesh axes as a tuple (None when none match): hybrid
    dp x fsdp runs shard the batch over BOTH data axes, and omitting one
    from the shard_map spec forces an all-gather at the attention
    boundary (XLA 'involuntary full rematerialization')."""
    names = tuple(n for n in mesh_axes if n in candidates and n != exclude)
    return names or None


def _axes_size(jmesh, axes):
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= jmesh.shape[a]
    return size


def ring_attention(query, key, value, mesh=None, axis_name: str = "sep",
                   causal: bool = True, batch_axis: Optional[str] = None,
                   head_axis: Optional[str] = None, attn_mask=None,
                   kv_seqlens=None):
    """Context-parallel attention (see module docstring).

    query: [b, s, h, d]; key/value: [b, s, kv, d] with h % kv == 0 (GQA kv
    heads stay unexpanded on the ring). mesh: a ProcessMesh containing
    `axis_name` (defaults to the fleet hybrid mesh). batch_axis/head_axis:
    mesh axes the batch/head dims are sharded over (auto-detected from
    conventional names dp/data/fsdp/sharding and mp/model when present).
    attn_mask: [b, 1|h, s, s] — bool keep-mask or float additive mask
    (flash v2 semantics); its query rows ride the ring axis, the key axis
    stays whole per shard and each ring step slices the current block.
    kv_seqlens: [b] int per-batch valid lengths — padded/packed batches can
    use context parallelism (VERDICT r2 #5). Returns the output
    sequence-sharded over `axis_name`.
    """
    from ..distributed.auto_parallel import ProcessMesh, get_default_mesh
    if mesh is None:
        from ..distributed.fleet.topology import get_hybrid_communicate_group
        hcg = get_hybrid_communicate_group()
        mesh = hcg.mesh if hcg is not None else get_default_mesh()
    if mesh is None:
        raise ValueError("ring_attention needs a mesh (or initialized fleet)")
    jmesh = mesh.jax_mesh if isinstance(mesh, ProcessMesh) else mesh
    axes = jmesh.axis_names
    if batch_axis is None:
        batch_axis = _pick_axis(axes, _DP_NAMES, axis_name)
    if head_axis is None:
        head_axis = _pick_axis(axes, _MP_NAMES, axis_name)
    if isinstance(batch_axis, str):
        batch_axis = (batch_axis,)
    if isinstance(head_axis, str):
        head_axis = (head_axis,)
    # auto-detected axes must evenly divide their dims; drop them otherwise
    if batch_axis is not None and \
            query.shape[0] % _axes_size(jmesh, batch_axis):
        batch_axis = None
    if head_axis is not None and (
            query.shape[2] % _axes_size(jmesh, head_axis)
            or key.shape[2] % _axes_size(jmesh, head_axis)):
        head_axis = None

    impl = _cached_impl(jmesh, axis_name, bool(causal), batch_axis, head_axis,
                        attn_mask is not None, kv_seqlens is not None)
    args = [query, key, value]
    if attn_mask is not None:
        args.append(attn_mask)
    if kv_seqlens is not None:
        args.append(kv_seqlens)
    return dispatch(impl, tuple(args), {}, "ring_attention")


@functools.lru_cache(maxsize=16)
def _cached_impl(jax_mesh, axis_name, causal, batch_axis, head_axis,
                 has_mask=False, has_seqlens=False):
    """Bounded cache (a jax Mesh is hashable); avoids re-closing over the
    mesh per call without growing an unbounded registry. Jitted here, so
    that an eager call compiles the ring once per shape: an eager
    shard_map runs its body primitive by primitive, each one a program
    of its own over the whole mesh, at every call and again under vjp."""
    return jax.jit(functools.partial(
        _ring_attention_impl, jax_mesh=jax_mesh, axis_name=axis_name,
        causal=causal, batch_axis=batch_axis, head_axis=head_axis,
        has_mask=has_mask, has_seqlens=has_seqlens))
