"""Diffusion UNet family (models/unet.py — the SD kernel mix as a
first-class model: time-conditioned UNet, DDPM objective, DDIM sampler).
Coverage model: the family must be trainable end to end, conditioning
must matter, and the sampler must run off one static-shape forward.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (UNetModel, ddim_sample, ddpm_loss,
                               unet_tiny_config)


def _model(**over):
    paddle.seed(0)
    return UNetModel(unet_tiny_config(**over))


def test_forward_shapes_and_time_conditioning():
    m = _model()
    m.eval()
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(2, 3, 16, 16).astype(np.float32))
    t1 = paddle.to_tensor(np.array([10, 10], np.int64))
    t2 = paddle.to_tensor(np.array([900, 900], np.int64))
    with paddle.no_grad():
        o1 = m(x, t1)
        o2 = m(x, t2)
    assert list(o1.shape) == [2, 3, 16, 16]
    # the timestep embedding must actually steer the prediction
    assert np.abs(o1.numpy() - o2.numpy()).max() > 1e-4


def test_cross_attention_context_matters():
    m = _model(context_dim=24)
    m.eval()
    rng = np.random.RandomState(1)
    x = paddle.to_tensor(rng.randn(2, 3, 16, 16).astype(np.float32))
    t = paddle.to_tensor(np.array([5, 5], np.int64))
    c1 = paddle.to_tensor(rng.randn(2, 7, 24).astype(np.float32))
    c2 = paddle.to_tensor(rng.randn(2, 7, 24).astype(np.float32))
    with paddle.no_grad():
        o1 = m(x, t, c1)
        o2 = m(x, t, c2)
    assert np.abs(o1.numpy() - o2.numpy()).max() > 1e-4


@pytest.mark.smoke  # the diffusion-family smoke representative (light)
def test_ddim_sampler_shapes():
    m = _model()
    m.eval()
    out = ddim_sample(m, (1, 3, 16, 16), num_steps=4)
    assert list(out.shape) == [1, 3, 16, 16]
    assert np.isfinite(out.numpy()).all()


def test_grads_reach_every_parameter():
    """Skip connections + time MLP + attention: one backward touches the
    whole tree (a dead branch would silently undertrain)."""
    m = _model(context_dim=16)
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.randn(1, 3, 16, 16).astype(np.float32))
    t = paddle.to_tensor(np.array([42], np.int64))
    n = paddle.to_tensor(rng.randn(1, 3, 16, 16).astype(np.float32))
    ctx = paddle.to_tensor(rng.randn(1, 4, 16).astype(np.float32))
    loss = ddpm_loss(m, x, t, n, context=ctx)
    loss.backward()
    missing = [name for name, p in m.named_parameters()
               if p.grad is None]
    assert not missing, missing
