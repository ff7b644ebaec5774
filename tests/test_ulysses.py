"""Ulysses (all-to-all) sequence parallelism over the sep axis: the attention
alone against dense, with GQA, masks, lengths and gradients, hybrid with the
mp axis, and what it refuses. (The ring is
tests/test_sequence_parallel.py's; Llama over either is
tests/test_llama_sequence_parallel.py's.)
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.auto_parallel import ProcessMesh

from test_sequence_parallel import (_dense_attention,  # noqa: F401 (autouse)
                                    _dense_masked, _reset_topology)


def _ulysses(*args, **kw):
    from paddle_tpu.ops.ulysses_attention import ulysses_attention
    return ulysses_attention(*args, **kw)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(causal):
    """DeepSpeed-Ulysses style all-to-all CP: heads<->sequence exchange,
    full attention per head subset, exchange back — must equal dense."""
    rng = np.random.RandomState(30)
    b, s, h, d = 2, 32, 8, 8
    q = rng.randn(b, s, h, d).astype("float32")
    k = rng.randn(b, s, h, d).astype("float32")
    v = rng.randn(b, s, h, d).astype("float32")
    mesh = ProcessMesh(np.arange(8), ["sep"])
    out = _ulysses(paddle.to_tensor(q), paddle.to_tensor(k),
                   paddle.to_tensor(v), mesh=mesh, causal=causal)
    expected = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(out.numpy(), expected, rtol=2e-4, atol=2e-5)


def test_ulysses_gqa_mask_seqlens_and_grads():
    rng = np.random.RandomState(31)
    b, s, h, kv, d = 2, 24, 8, 4, 8   # GQA rep=2; h, kv divisible by sep=4
    mesh = ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "sep"])
    q = rng.randn(b, s, h, d).astype("float32")
    k = rng.randn(b, s, kv, d).astype("float32")
    v = rng.randn(b, s, kv, d).astype("float32")
    # GQA + causal + per-batch valid lengths on a (dp, sep) grid
    lens = np.array([20, 24], np.int64)
    out = _ulysses(paddle.to_tensor(q), paddle.to_tensor(k),
                   paddle.to_tensor(v), mesh=mesh, axis_name="sep",
                   causal=True, kv_seqlens=paddle.to_tensor(lens)).numpy()
    ref = _dense_masked(q, np.repeat(k, h // kv, 2),
                        np.repeat(v, h // kv, 2), True, seqlens=lens)
    for i, L in enumerate(lens):
        np.testing.assert_allclose(out[i, :L], ref[i, :L],
                                   rtol=2e-4, atol=2e-5)
    # additive mask + backward through both all-to-alls
    mesh1 = ProcessMesh(np.arange(8), ["sep"])
    q8 = rng.randn(1, 16, 8, 8).astype("float32")
    k8 = rng.randn(1, 16, 8, 8).astype("float32")
    v8 = rng.randn(1, 16, 8, 8).astype("float32")
    mask = (rng.randn(1, 1, 16, 16) * 2).astype("float32")

    qt = paddle.to_tensor(q8)
    qt.stop_gradient = False
    out2 = _ulysses(qt, paddle.to_tensor(k8), paddle.to_tensor(v8),
                    mesh=mesh1, causal=False,
                    attn_mask=paddle.to_tensor(mask))
    out2.sum().backward()
    g = qt.grad.numpy()

    # dense reference gradient via jax on the same math
    import jax
    import jax.numpy as jnp

    def dense_sum(qq):
        qt_ = jnp.einsum("bshd->bhsd", qq)
        kt_ = jnp.einsum("bshd->bhsd", jnp.asarray(k8))
        vt_ = jnp.einsum("bshd->bhsd", jnp.asarray(v8))
        sc = jnp.einsum("bhqd,bhkd->bhqk", qt_, kt_) / np.sqrt(8)
        sc = sc + jnp.asarray(mask)
        p = jax.nn.softmax(sc.astype(jnp.float32), -1).astype(qq.dtype)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, vt_)
        return o.sum()

    gd = jax.grad(dense_sum)(jnp.asarray(q8))
    np.testing.assert_allclose(g, np.asarray(gd), rtol=2e-3, atol=2e-4)


def test_ulysses_hybrid_mp_sep_shards_heads_jointly():
    """ADVICE r4: on a hybrid (mp, sep) mesh, heads shard jointly over
    (mp, sep) — the head dim must not replicate over mp. Numerics must
    still match dense, including a per-head additive mask."""
    rng = np.random.RandomState(34)
    b, s, h, d = 2, 16, 8, 8          # h divisible by |mp|*|sep| = 8
    mesh = ProcessMesh(np.arange(8).reshape(2, 4), ["mp", "sep"])
    q = rng.randn(b, s, h, d).astype("float32")
    k = rng.randn(b, s, h, d).astype("float32")
    v = rng.randn(b, s, h, d).astype("float32")
    out = _ulysses(paddle.to_tensor(q), paddle.to_tensor(k),
                   paddle.to_tensor(v), mesh=mesh, axis_name="sep",
                   causal=True).numpy()
    np.testing.assert_allclose(out, _dense_attention(q, k, v, True),
                               rtol=2e-4, atol=2e-5)
    # per-head mask shards over (mp, sep) too
    mask = (rng.randn(b, h, s, s) * 2).astype("float32")
    out2 = _ulysses(paddle.to_tensor(q), paddle.to_tensor(k),
                    paddle.to_tensor(v), mesh=mesh, axis_name="sep",
                    causal=False,
                    attn_mask=paddle.to_tensor(mask)).numpy()
    ref = _dense_masked(q, k, v, False, mask=mask)
    np.testing.assert_allclose(out2, ref, rtol=2e-4, atol=2e-5)
    # h=4 < |mp|*|sep|: joint sharding impossible -> head_axis dropped,
    # still correct (replicated-over-mp fallback)
    q4 = rng.randn(b, s, 4, d).astype("float32")
    k4 = rng.randn(b, s, 4, d).astype("float32")
    v4 = rng.randn(b, s, 4, d).astype("float32")
    out3 = _ulysses(paddle.to_tensor(q4), paddle.to_tensor(k4),
                    paddle.to_tensor(v4), mesh=mesh, axis_name="sep",
                    causal=True).numpy()
    np.testing.assert_allclose(out3, _dense_attention(q4, k4, v4, True),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_hybrid_gqa_headed_mask():
    """GQA (rep=2) with heads jointly sharded over (mp, sep): the
    riskiest layout — kv heads all-to-all split + q/mask head-block
    alignment with rep > 1 on a hybrid mesh — plus a per-head mask."""
    rng = np.random.RandomState(36)
    b, s, h, kv, d = 2, 16, 16, 8, 8  # both divisible by |mp|*|sep|=8
    mesh = ProcessMesh(np.arange(8).reshape(2, 4), ["mp", "sep"])
    q = rng.randn(b, s, h, d).astype("float32")
    k = rng.randn(b, s, kv, d).astype("float32")
    v = rng.randn(b, s, kv, d).astype("float32")
    out = _ulysses(paddle.to_tensor(q), paddle.to_tensor(k),
                   paddle.to_tensor(v), mesh=mesh, axis_name="sep",
                   causal=True).numpy()
    ref = _dense_attention(q, np.repeat(k, h // kv, 2),
                           np.repeat(v, h // kv, 2), True)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
    mask = (rng.randn(b, h, s, s) * 2).astype("float32")
    out2 = _ulysses(paddle.to_tensor(q), paddle.to_tensor(k),
                    paddle.to_tensor(v), mesh=mesh, axis_name="sep",
                    causal=False,
                    attn_mask=paddle.to_tensor(mask)).numpy()
    ref2 = _dense_masked(q, np.repeat(k, h // kv, 2),
                         np.repeat(v, h // kv, 2), False, mask=mask)
    np.testing.assert_allclose(out2, ref2, rtol=2e-4, atol=2e-5)


def test_ulysses_public_impl_seam():
    """VERDICT r4 item 6: ulysses_attention_impl is the scan-safe public
    entry — same cache slots as the wrapper, callable directly."""
    from paddle_tpu.ops.ulysses_attention import (
        _cached_impl, ulysses_attention_impl, validate_ulysses)
    import jax.numpy as jnp
    mesh = ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "sep"])
    jmesh = mesh.jax_mesh
    validate_ulysses(jmesh, "sep", 8, 8, 16)
    impl = ulysses_attention_impl(mesh, "sep", causal=True,
                                  batch_axis=("dp",))
    # identical lru_cache slot as the private constructor
    assert impl is _cached_impl(jmesh, "sep", True, ("dp",), False,
                                False, False, None)
    rng = np.random.RandomState(35)
    q = rng.randn(2, 16, 8, 8).astype("float32")
    k = rng.randn(2, 16, 8, 8).astype("float32")
    v = rng.randn(2, 16, 8, 8).astype("float32")
    out = np.asarray(impl(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    np.testing.assert_allclose(out, _dense_attention(q, k, v, True),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_rejects_ragged_heads():
    mesh = ProcessMesh(np.arange(8), ["sep"])
    rng = np.random.RandomState(32)
    q = paddle.to_tensor(rng.randn(1, 16, 6, 8).astype("float32"))
    with pytest.raises(ValueError, match="divisible by the context axis"):
        _ulysses(q, q, q, mesh=mesh)
