"""Pallas kernel tier tests (interpret mode on the CPU mesh).

Reference coverage model: the fused-kernel unit tests under
test/legacy_test/test_flash_attention.py etc. (SURVEY.md §4); kernels run
interpreted off-TPU so the same suite gates both backends. The v2 kernel's
feature matrix (GQA, additive mask, varlen, arbitrary lengths) is pinned
against a dense reference, matching FlashAttnKernel/FlashAttnUnpaddedKernel
(phi/kernels/gpu/flash_attn_kernel.cu:128).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.flash_attention import (flash_attention_pallas,
                                                   supported)


def _dense(q, k, v, causal, mask=None, seqlens=None, neg=-jnp.inf):
    """Dense float32 reference. ``neg``: what a masked score becomes; a
    finite one keeps the gradients of wholly masked rows finite."""
    d = q.shape[-1]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    hq, hkv = q.shape[2], k.shape[2]
    if hkv != hq:  # GQA reference: expand kv heads
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = jnp.einsum("bshd->bhsd", q)
    kt = jnp.einsum("bshd->bhsd", k)
    vt = jnp.einsum("bshd->bhsd", v)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(d)
    if mask is not None:
        s = s + mask
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, neg)
    if seqlens is not None:
        n = q.shape[1]
        cols = jnp.arange(n)[None, None, None, :]
        rows = jnp.arange(n)[None, None, :, None]
        sl = seqlens[:, None, None, None]
        s = jnp.where((cols < sl) & (rows < sl), s, neg)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vt)
    return jnp.einsum("bhsd->bshd", out)


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32).astype(dtype)


BF16 = jnp.bfloat16

# (b, s, hq, hkv, d, dtype, (block_q, block_k) or None for the shape-derived
# tiling, additive mask, kv_seqlens). The float32 rows keep the tolerances
# the kernel has always been held to; the rectangular ones put several K
# tiles under one Q tile and the reverse, which is where the loop bounds at
# the diagonal can go wrong.
CASES = {
    "f32": (2, 256, 2, 2, 64, jnp.float32, None, False, None),
    "f32_d32": (1, 256, 1, 1, 32, jnp.float32, None, False, None),
    "f32_s1024": (1, 1024, 1, 1, 64, jnp.float32, None, False, None),
    "f32_256x512": (1, 1024, 1, 1, 64, jnp.float32, (256, 512), False, None),
    "f32_512x256": (1, 1024, 1, 1, 64, jnp.float32, (512, 256), False, None),
    "f32_1024x128": (1, 1024, 1, 1, 64, jnp.float32, (1024, 128), False,
                     None),
    "f32_mask_seqlens_gqa": (2, 512, 4, 2, 32, jnp.float32, None, True,
                             (500, 130)),
    "bf16_d64": (1, 1024, 2, 2, 64, BF16, None, False, None),
    "bf16_d128": (1, 1024, 1, 1, 128, BF16, None, False, None),
    "bf16_d64_pads": (1, 1100, 1, 1, 64, BF16, None, False, None),
    "bf16_d128_pads": (1, 600, 1, 1, 128, BF16, None, False, None),
    "bf16_256x512": (1, 1024, 1, 1, 64, BF16, (256, 512), False, None),
    "bf16_1024x128": (1, 1024, 1, 1, 64, BF16, (1024, 128), False, None),
    "bf16_mask": (1, 1024, 2, 2, 64, BF16, None, True, None),
    "bf16_seqlens": (2, 1024, 1, 1, 64, BF16, None, False, (1000, 300)),
    "bf16_gqa": (1, 1024, 4, 2, 64, BF16, None, False, None),
}


def _case(name, seed):
    b, s, hq, hkv, d, dtype, blocks, has_mask, lens = CASES[name]
    q = _rand((b, s, hq, d), seed, dtype)
    k, v = _rand((b, s, hkv, d), seed + 1, dtype), \
        _rand((b, s, hkv, d), seed + 2, dtype)
    mask = _rand((b, 1, s, s), seed + 3) * 2 if has_mask else None
    lens = None if lens is None else jnp.asarray(lens, jnp.int32)
    kw = dict(attn_mask=mask, kv_seqlens=lens, interpret=True)
    if blocks:
        kw.update(block_q=blocks[0], block_k=blocks[1])
    # only rows under a sequence's length are defined: weigh the rest by 0
    valid = jnp.ones((b, s, 1, 1), jnp.float32) if lens is None else \
        (jnp.arange(s)[None, :] < lens[:, None]).astype(
            jnp.float32)[:, :, None, None]
    return q, k, v, mask, lens, valid, kw


def _assert_close(got, ref, dtype, f32_tol):
    """float32 inputs: elementwise, at the tolerances the tests always had.
    bfloat16 inputs: the kernel rounds its output, ``p`` and ``ds`` to 8
    bits of mantissa (2^-9 = 2e-3 relative each, as the dense path's
    ``probs.astype`` does), so an element may be off by a few of those
    against a float32 reference on the same inputs, while the error as a
    whole (independent roundings) stays under 2^-7 of the result's norm."""
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, ref, **f32_tol)
        return
    assert np.linalg.norm(got - ref) <= 2.0 ** -7 * np.linalg.norm(ref)
    np.testing.assert_allclose(got, ref, rtol=3e-2, atol=3e-2 * np.abs(
        ref).max())


@pytest.mark.quick
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("case", list(CASES))
def test_flash_forward_matches_dense(case, causal):
    q, k, v, mask, lens, valid, kw = _case(case, 0)
    out = flash_attention_pallas(q, k, v, causal=causal, **kw)
    assert out.dtype == q.dtype and out.shape == q.shape
    ref = _dense(q, k, v, causal, mask=mask, seqlens=lens)
    _assert_close(out.astype(jnp.float32) * valid, ref * valid, q.dtype,
                  dict(rtol=1e-5, atol=1e-5))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_native(causal):
    """K/V stay at kv-head count; the kernel's index map expands the group."""
    b, s, hq, hkv, d = 2, 256, 4, 2, 32
    q = _rand((b, s, hq, d), 6)
    k, v = _rand((b, s, hkv, d), 7), _rand((b, s, hkv, d), 8)
    out = flash_attention_pallas(q, k, v, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense(q, k, v, causal)),
                               rtol=1e-5, atol=1e-5)


def test_flash_additive_mask():
    b, s, h, d = 1, 256, 2, 32
    q, k, v = _rand((b, s, h, d), 12), _rand((b, s, h, d), 13), \
        _rand((b, s, h, d), 14)
    mask = jnp.asarray(
        np.random.RandomState(15).randn(b, 1, s, s) * 2, jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=False, attn_mask=mask,
                                 interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense(q, k, v, False, mask=mask)),
        rtol=1e-5, atol=1e-5)

    def f(q):
        return flash_attention_pallas(q, k, v, causal=False, attn_mask=mask,
                                      interpret=True).sum()

    def g(q):
        return _dense(q, k, v, False, mask=mask).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(f)(q)),
                               np.asarray(jax.grad(g)(q)),
                               rtol=1e-4, atol=1e-5)


def test_flash_varlen_padding_mask():
    """kv_seqlens masks the padded tail (FlashAttnUnpaddedKernel analog)."""
    b, s, h, d = 2, 256, 2, 32
    q, k, v = _rand((b, s, h, d), 16), _rand((b, s, h, d), 17), \
        _rand((b, s, h, d), 18)
    lens = jnp.asarray([200, 128], jnp.int32)
    out = flash_attention_pallas(q, k, v, causal=True, kv_seqlens=lens,
                                 interpret=True)
    ref = _dense(q, k, v, True, seqlens=lens)
    for i, L in enumerate([200, 128]):
        np.testing.assert_allclose(np.asarray(out)[i, :L],
                                   np.asarray(ref)[i, :L],
                                   rtol=1e-5, atol=1e-5)


def test_flash_arbitrary_seq_len():
    """Non-block-multiple lengths pad internally and slice back."""
    b, s, h, d = 1, 200, 2, 32
    q, k, v = _rand((b, s, h, d), 19), _rand((b, s, h, d), 20), \
        _rand((b, s, h, d), 21)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    assert out.shape == (b, s, h, d)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense(q, k, v, True)),
                               rtol=1e-5, atol=1e-5)

    def f(q):
        return flash_attention_pallas(q, k, v, causal=True,
                                      interpret=True).sum()

    def g(q):
        return _dense(q, k, v, True).sum()

    np.testing.assert_allclose(np.asarray(jax.grad(f)(q)),
                               np.asarray(jax.grad(g)(q)),
                               rtol=1e-4, atol=1e-5)


def test_flash_short_seq():
    """Sequences below one default block shrink the block instead of 8x pad."""
    b, s, h, d = 2, 48, 2, 32
    q, k, v = _rand((b, s, h, d), 22), _rand((b, s, h, d), 23), \
        _rand((b, s, h, d), 24)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense(q, k, v, True)),
                               rtol=1e-5, atol=1e-5)


def test_flash_long_seq_blocked_kv():
    """8k tokens: v1 pinned whole-sequence K/V per program (VMEM blowup);
    v2 streams K/V tiles through the grid, so this must run."""
    b, s, h, d = 1, 8192, 1, 64
    q, k, v = _rand((b, s, h, d), 25), _rand((b, s, h, d), 26), \
        _rand((b, s, h, d), 27)
    out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    # spot-check a strip against dense (full 8k dense is slow in interpret)
    ref = _dense(q[:, :1024], k[:, :1024], v[:, :1024], True)
    np.testing.assert_allclose(np.asarray(out)[:, :1024], np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flash_supported_gate():
    assert supported(1024, 64)
    assert supported(1000, 64)   # v2: arbitrary lengths pad internally
    assert supported(64, 64)     # v2: short seqs shrink the block
    assert not supported(1024, 63)   # head dim not 8-aligned


def test_sdpa_routes_by_flag():
    """CPU backend never routes to pallas; the flag gate is honored."""
    from paddle_tpu.nn.functional import _pallas_attention_eligible
    q = paddle.randn([1, 128, 2, 64])
    assert not _pallas_attention_eligible(q, q, None, 0.0)  # cpu backend
    paddle.set_flags({"FLAGS_use_pallas_attention": False})
    try:
        assert not _pallas_attention_eligible(q, q, None, 0.0)
    finally:
        paddle.set_flags({"FLAGS_use_pallas_attention": True})


def test_flash_dropout():
    """In-kernel dropout: deterministic per seed, mean-preserving, bwd
    regenerates the same mask (finite, mask-consistent grads)."""
    b, s, h, d = 1, 256, 2, 32
    q, k, v = _rand((b, s, h, d), 30), _rand((b, s, h, d), 31), \
        _rand((b, s, h, d), 32)
    o1 = flash_attention_pallas(q, k, v, causal=False, dropout_p=0.3,
                                seed=7, interpret=True)
    o2 = flash_attention_pallas(q, k, v, causal=False, dropout_p=0.3,
                                seed=7, interpret=True)
    o3 = flash_attention_pallas(q, k, v, causal=False, dropout_p=0.3,
                                seed=8, interpret=True)
    o0 = flash_attention_pallas(q, k, v, causal=False, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert not np.allclose(np.asarray(o1), np.asarray(o3))
    assert not np.allclose(np.asarray(o1), np.asarray(o0))
    # E[dropout(out)] == out: averages should stay in the same ballpark
    assert abs(float(jnp.mean(o1 - o0))) < 0.05

    g = jax.grad(lambda q: flash_attention_pallas(
        q, k, v, causal=False, dropout_p=0.3, seed=7,
        interpret=True).sum())(q)
    assert bool(jnp.isfinite(g).all())
    # same-seed grads are deterministic too
    g2 = jax.grad(lambda q: flash_attention_pallas(
        q, k, v, causal=False, dropout_p=0.3, seed=7,
        interpret=True).sum())(q)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g2))


def test_block_sparse_attention_matches_dense_masked():
    """Active tiles only: output must equal dense attention under the
    expanded block mask (ref sparse_attention semantics at tile granularity)."""
    from paddle_tpu.ops.pallas.block_sparse_attention import \
        block_sparse_attention_pallas
    b, s, h, d = 1, 512, 2, 32
    q, k, v = _rand((b, s, h, d), 40), _rand((b, s, h, d), 41), \
        _rand((b, s, h, d), 42)
    nb = s // 128
    rng = np.random.RandomState(43)
    bm = (rng.rand(nb, nb) < 0.5)
    bm[:, 0] = True  # every row keeps at least one active tile
    out = block_sparse_attention_pallas(q, k, v, bm, interpret=True)

    mask = np.repeat(np.repeat(bm, 128, 0), 128, 1)
    big = jnp.asarray(np.where(mask, 0.0, -1e30), jnp.float32)
    ref = _dense(q, k, v, False, mask=big[None, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)

    # gradients flow (dense recompute backward)
    g = jax.grad(lambda q: block_sparse_attention_pallas(
        q, k, v, bm, interpret=True).sum())(q)
    gref = jax.grad(lambda q: _dense(q, k, v, False,
                                     mask=big[None, None]).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=1e-4, atol=1e-5)


def test_sparse_attention_csr_block_alignment_probe():
    from paddle_tpu.nn.functional_extras import _csr_masks
    seq, blk = 256, 128
    nb = seq // blk
    # block-aligned: every row attends exactly to block-col 0
    offs = np.zeros((1, 1, seq + 1), np.int64)
    cols_list = []
    for r in range(seq):
        cols_list.append(np.arange(blk))
        offs[0, 0, r + 1] = offs[0, 0, r] + blk
    cols = np.concatenate(cols_list)[None, None]
    mask, bm = _csr_masks(offs, cols, seq, blk)
    assert bm is not None and bm.shape == (nb, nb)
    assert bm[:, 0].all() and not bm[:, 1:].any()
    assert mask.shape == (1, 1, seq, seq)
    # cached: same pattern returns the identical objects
    mask2, bm2 = _csr_masks(offs, cols, seq, blk)
    assert mask2 is mask and bm2 is bm
    # non-aligned pattern (single element) probes to None
    offs2 = np.zeros((1, 1, seq + 1), np.int64)
    offs2[0, 0, 1:] = 1
    cols2 = np.zeros((1, 1, seq), np.int64)
    _, bm3 = _csr_masks(offs2, cols2, seq, blk)
    assert bm3 is None


def test_block_sparse_empty_row_zero_output():
    """A fully-masked block-row outputs ZERO in fwd AND its bwd recompute
    (review repro: softmax-of-all-masked must not become uniform)."""
    from paddle_tpu.ops.pallas.block_sparse_attention import \
        block_sparse_attention_pallas
    b, s, h, d = 1, 256, 1, 16
    q, k, v = _rand((b, s, h, d), 50), _rand((b, s, h, d), 51), \
        _rand((b, s, h, d), 52)
    bm = np.array([[True, False], [False, False]])  # row 1 fully masked
    out = block_sparse_attention_pallas(q, k, v, bm, interpret=True)
    np.testing.assert_allclose(np.asarray(out)[:, 128:], 0.0)
    g = jax.grad(lambda v_: block_sparse_attention_pallas(
        q, k, v_, bm, interpret=True).sum())(v)
    # masked rows contribute nothing to dv's second half either
    np.testing.assert_allclose(np.asarray(g)[:, 128:], 0.0, atol=1e-6)


def test_flash_gqa_grads():
    b, s, hq, hkv, d = 1, 256, 4, 2, 16
    q = _rand((b, s, hq, d), 9)
    k, v = _rand((b, s, hkv, d), 10), _rand((b, s, hkv, d), 11)

    def f(q, k, v):
        return flash_attention_pallas(q, k, v, causal=True,
                                      interpret=True).sum()

    def g(q, k, v):
        return _dense(q, k, v, True).sum()

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("blocks", [None, (256, 128)],
                         ids=["derived", "256x128"])
def test_flash_dropout_backward_redraws_the_forwards_mask(blocks):
    """The mask is drawn per score tile from the tile's coordinates, so the
    three kernels must agree on the score tile: under dropout
    ``_resolve_blocks`` hands all three ONE tiling (the shape-derived one
    that fits the hungriest of them, or the caller's). With the seed fixed
    the masked function is smooth, so a central difference along each
    gradient must give its norm; a backward that drew another mask returns
    a vector the function does not rise along."""
    from paddle_tpu.ops.pallas.flash_attention import _resolve_blocks
    b, s, h, d = 1, 1024, 1, 64
    q, k, v = _rand((b, s, h, d), 33), _rand((b, s, h, d), 34), \
        _rand((b, s, h, d), 35)
    bq, bk = blocks or (None, None)
    tiles = _resolve_blocks(q, k, v, True, None, 0.3, bq, bk, True)
    assert len(set(tiles)) == 1
    assert s // tiles.fwd.sub_q > 1 or s // tiles.fwd.sub_k > 1
    w = _rand((b, s, h, d), 36)

    def f(*qkv):
        return (flash_attention_pallas(
            *qkv, causal=True, dropout_p=0.3, seed=7, block_q=bq,
            block_k=bk, interpret=True) * w).sum()

    grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for i, g in enumerate(grads):
        # along the gradient itself the slope is its norm, if it is the
        # gradient of what the forward computed
        norm = float(jnp.linalg.norm(g.ravel()))
        u = g / norm
        hi, lo = [q, k, v], [q, k, v]
        hi[i], lo[i] = hi[i] + 0.1 * u, lo[i] - 0.1 * u
        fd = float(f(*hi) - f(*lo)) / 0.2
        assert abs(fd - norm) <= 0.02 * norm, (i, fd, norm)
