"""Tests for the fused Pallas kernel tier (ops/pallas/fused_ops.py):
RMSNorm fwd/bwd and single-pass AdamW, in interpret mode on CPU, plus the
fused rope functional. Reference: phi/kernels/fusion fused_rms_norm /
fused_adam / fused_rope."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.fused_ops import adamw_pallas, rms_norm_pallas


def _ref_rmsnorm(x, w, eps=1e-6):
    var = np.mean(x.astype(np.float64) ** 2, axis=-1, keepdims=True)
    return (x / np.sqrt(var + eps) * w).astype(np.float32)


def test_rmsnorm_pallas_forward_matches_reference():
    rng = np.random.RandomState(0)
    x = rng.randn(6, 4, 256).astype(np.float32)
    w = rng.rand(256).astype(np.float32) + 0.5
    out = rms_norm_pallas(jnp.asarray(x), jnp.asarray(w), 1e-6, True)
    np.testing.assert_allclose(np.asarray(out), _ref_rmsnorm(x, w),
                               rtol=1e-4, atol=1e-5)


def test_rmsnorm_pallas_gradients_match_autodiff():
    rng = np.random.RandomState(1)
    x = rng.randn(8, 128).astype(np.float32)
    w = rng.rand(128).astype(np.float32) + 0.5

    def ref(x_, w_):
        var = jnp.mean(jnp.square(x_), axis=-1, keepdims=True)
        return jnp.sum(jnp.sin(x_ * jax.lax.rsqrt(var + 1e-6) * w_))

    def fused(x_, w_):
        return jnp.sum(jnp.sin(rms_norm_pallas(x_, w_, 1e-6, True)))

    gx_ref, gw_ref = jax.grad(ref, argnums=(0, 1))(jnp.asarray(x),
                                                   jnp.asarray(w))
    gx, gw = jax.grad(fused, argnums=(0, 1))(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               rtol=1e-3, atol=1e-4)


def test_rmsnorm_pallas_bf16():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4, 128), jnp.bfloat16)
    w = jnp.asarray(rng.rand(128) + 0.5, jnp.bfloat16)
    out = rms_norm_pallas(x, w, 1e-6, True)
    assert out.dtype == jnp.bfloat16
    ref = _ref_rmsnorm(np.asarray(x, np.float32), np.asarray(w, np.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=3e-2, atol=3e-2)


def test_rmsnorm_routing_through_functional():
    # CPU: routing must stay on the XLA path and still be correct
    from paddle_tpu.nn import functional as F
    x = paddle.to_tensor(np.random.RandomState(3).randn(2, 8, 128)
                         .astype(np.float32))
    w = paddle.to_tensor(np.random.RandomState(4).rand(128)
                         .astype(np.float32))
    out = F.rms_norm(x, w)
    ref = _ref_rmsnorm(np.asarray(x._data), np.asarray(w._data))
    np.testing.assert_allclose(np.asarray(out._data), ref, rtol=1e-4,
                               atol=1e-5)


def _ref_adamw(p, m, v, g, lr, b1, b2, eps, wd, t):
    m2 = b1 * m + (1 - b1) * g
    v2 = b2 * v + (1 - b2) * g * g
    mh = m2 / (1 - b1 ** t)
    vh = v2 / (1 - b2 ** t)
    p2 = p * (1 - lr * wd) - lr * mh / (np.sqrt(vh) + eps)
    return p2, m2, v2


@pytest.mark.parametrize("shape", [(1000,), (33, 77), (4, 128, 128)])
def test_adamw_pallas_matches_reference(shape):
    rng = np.random.RandomState(0)
    p = rng.randn(*shape).astype(np.float32)
    m = rng.randn(*shape).astype(np.float32) * 0.1
    v = np.abs(rng.randn(*shape)).astype(np.float32) * 0.01
    g = rng.randn(*shape).astype(np.float32)
    lr, b1, b2, eps, wd, t = 1e-3, 0.9, 0.999, 1e-8, 0.01, 3

    p2, m2, v2 = adamw_pallas(
        jnp.asarray(p), jnp.asarray(m), jnp.asarray(v), jnp.asarray(g),
        lr=lr, beta1=b1, beta2=b2, eps=eps, weight_decay=wd,
        beta1_pow=b1 ** t, beta2_pow=b2 ** t, interpret=True)
    rp, rm, rv = _ref_adamw(p, m, v, g, lr, b1, b2, eps, wd, t)
    np.testing.assert_allclose(np.asarray(p2), rp, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(m2), rm, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), rv, rtol=1e-5, atol=1e-6)


def test_adamw_pallas_multi_step_training_converges():
    # quadratic bowl: p -> 0 under repeated fused updates
    p = jnp.asarray(np.ones(512, np.float32) * 5.0)
    m = jnp.zeros(512, jnp.float32)
    v = jnp.zeros(512, jnp.float32)
    for t in range(1, 60):
        g = 2 * p  # d/dp p^2
        p, m, v = adamw_pallas(p, m, v, g, lr=0.1, beta1=0.9, beta2=0.999,
                               eps=1e-8, weight_decay=0.0,
                               beta1_pow=0.9 ** t, beta2_pow=0.999 ** t,
                               interpret=True)
    assert float(jnp.abs(p).max()) < 1.0


def test_fused_rope_matches_model_rope():
    from paddle_tpu.incubate.nn.functional import (
        fused_rotary_position_embedding)
    from paddle_tpu.models.llama import _rope_cos_sin

    rng = np.random.RandomState(0)
    b, s, h, d = 2, 16, 4, 32
    q = paddle.to_tensor(rng.randn(b, s, h, d).astype(np.float32))
    k = paddle.to_tensor(rng.randn(b, s, h, d).astype(np.float32))
    cos, sin = _rope_cos_sin(s, d, 10000.0, jnp.float32)
    qo, ko, vo = fused_rotary_position_embedding(
        q, k, None, sin=paddle.to_tensor(np.asarray(sin)),
        cos=paddle.to_tensor(np.asarray(cos)))
    assert vo is None
    from paddle_tpu.models.llama import apply_rotary_pos_emb
    ref_q = apply_rotary_pos_emb(q._data, cos, sin)
    np.testing.assert_allclose(np.asarray(qo._data), np.asarray(ref_q),
                               rtol=1e-5, atol=1e-6)
    # rotation preserves norms
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(qo._data), axis=-1),
        np.linalg.norm(np.asarray(q._data), axis=-1), rtol=1e-4)


def test_fused_rope_default_tables_and_position_ids():
    from paddle_tpu.incubate.nn.functional import (
        fused_rotary_position_embedding)
    from paddle_tpu.models.llama import _rope_cos_sin, apply_rotary_pos_emb

    rng = np.random.RandomState(5)
    b, s, h, d = 3, 8, 2, 16
    q = paddle.to_tensor(rng.randn(b, s, h, d).astype(np.float32))
    # no sin/cos: default tables computed internally
    qo, _, _ = fused_rotary_position_embedding(q)
    cos, sin = _rope_cos_sin(s, d, 10000.0, jnp.float32)
    np.testing.assert_allclose(np.asarray(qo._data),
                               np.asarray(apply_rotary_pos_emb(
                                   q._data, cos, sin)),
                               rtol=1e-5, atol=1e-6)
    # batched [B, S] position_ids: reversed positions for one row
    pid = np.tile(np.arange(s), (b, 1))
    pid[1] = pid[1][::-1]
    qp, _, _ = fused_rotary_position_embedding(q, position_ids=pid)
    # row 0 matches normal rope; row 1 matches rope with reversed tables
    np.testing.assert_allclose(np.asarray(qp._data)[0],
                               np.asarray(qo._data)[0], rtol=1e-5,
                               atol=1e-6)
    ref_rev = apply_rotary_pos_emb(q._data[1:2], cos[::-1], sin[::-1])
    np.testing.assert_allclose(np.asarray(qp._data)[1],
                               np.asarray(ref_rev)[0], rtol=1e-5, atol=1e-6)


def test_fused_rope_decode_step_position_ids():
    # kv-cache decode: q of length 1, position beyond the local seq_len
    from paddle_tpu.incubate.nn.functional import (
        fused_rotary_position_embedding)
    from paddle_tpu.models.llama import _rope_cos_sin, apply_rotary_pos_emb

    rng = np.random.RandomState(7)
    q = paddle.to_tensor(rng.randn(1, 1, 2, 16).astype(np.float32))
    qo, _, _ = fused_rotary_position_embedding(
        q, position_ids=np.array([[17]]))
    cos, sin = _rope_cos_sin(18, 16, 10000.0, jnp.float32)
    ref = apply_rotary_pos_emb(q._data, cos[17:18], sin[17:18])
    np.testing.assert_allclose(np.asarray(qo._data), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # and NOT equal to position 0's rotation (the old clamping bug)
    ref0 = apply_rotary_pos_emb(q._data, cos[0:1], sin[0:1])
    assert not np.allclose(np.asarray(qo._data), np.asarray(ref0))


def test_fused_rope_half_style():
    from paddle_tpu.incubate.nn.functional import (
        fused_rotary_position_embedding)
    from paddle_tpu.models.llama import _rope_cos_sin

    rng = np.random.RandomState(6)
    b, s, h, d = 1, 4, 1, 8
    q = paddle.to_tensor(rng.randn(b, s, h, d).astype(np.float32))
    cos, sin = _rope_cos_sin(s, d, 10000.0, jnp.float32)
    qo, _, _ = fused_rotary_position_embedding(
        q, sin=paddle.to_tensor(np.asarray(sin)),
        cos=paddle.to_tensor(np.asarray(cos)), use_neox_rotary_style=False)
    # half-rotation reference
    x = np.asarray(q._data)
    c = np.asarray(cos)[None, :, None, :]
    sn = np.asarray(sin)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    ref = np.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)
    np.testing.assert_allclose(np.asarray(qo._data), ref, rtol=1e-5,
                               atol=1e-6)


def test_bench_composition_flash_selective_scan(monkeypatch):
    """The EXACT bench.py headline composition — Pallas flash attention
    INSIDE a jax.checkpoint(selective)-wrapped lax.scan body with a full
    TrainStep — has to trace/compile/train as one program. The test
    stands in for the chip: it answers ``on_tpu()`` with True and runs
    the kernel interpreted, so a composition break (e.g.
    checkpoint-over-custom_vjp-in-scan) surfaces on the CPU mesh.
    tests/test_chip_compile.py offers the same composition to the chip's
    compiler at 7B widths. (Hidden 64 is below the rmsnorm kernel's
    128-lane gate, so only flash is reached.)"""
    import functools

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import jit, optimizer
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.ops import pallas as _pl
    from paddle_tpu.ops.pallas import flash_attention as fa

    flash_calls = []

    @functools.wraps(fa.flash_attention_pallas)
    def interpreted(*a, **kw):
        flash_calls.append(1)
        return interpreted.__wrapped__(*a, **dict(kw, interpret=True))

    def losses(flash: bool):
        with monkeypatch.context() as mp:
            if flash:
                mp.setattr(_pl, "on_tpu", lambda: True)
                mp.setattr(fa, "flash_attention_pallas", interpreted)
            paddle.seed(0)
            cfg = llama_tiny_config(scan_layers=True, use_recompute=True,
                                    recompute_granularity="selective")
            m = LlamaForCausalLM(cfg)
            opt = optimizer.AdamW(learning_rate=1e-3,
                                  parameters=m.parameters())
            step = jit.TrainStep(lambda i, l: m(i, labels=l)[1], opt)
            rng = np.random.RandomState(0)
            ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 64)))
            lbl = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 64)))
            return [float(step(ids, lbl)) for _ in range(3)]

    flash_losses = losses(True)
    assert flash_calls, "the scan body never reached the flash kernel"
    dense_losses = losses(False)
    assert flash_losses[-1] < flash_losses[0]
    # flash vs dense attention are numerically close, not bit-equal
    np.testing.assert_allclose(flash_losses, dense_losses, rtol=5e-3)
