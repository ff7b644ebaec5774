"""The diffusion UNet trains: the DDPM objective falls under the fused train
step, and a batch-sharded step runs over the 8-device mesh. (Forward,
conditioning, sampler and gradient reach are tests/test_unet.py's.)
"""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import ddpm_loss

from test_unet import _model


def test_ddpm_training_reduces_loss():
    from paddle_tpu import jit, optimizer
    m = _model()
    opt = optimizer.AdamW(learning_rate=3e-4, parameters=m.parameters())
    step = jit.TrainStep(lambda x, t, n: ddpm_loss(m, x, t, n), opt)
    rng = np.random.RandomState(2)
    x = paddle.to_tensor(rng.randn(2, 3, 16, 16).astype(np.float32))
    t = paddle.to_tensor(rng.randint(0, 1000, (2,)).astype(np.int64))
    n = paddle.to_tensor(rng.randn(2, 3, 16, 16).astype(np.float32))
    losses = [float(step(x, t, n)._data) for _ in range(6)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_data_parallel_unet_step():
    """DP over the 8-device CPU mesh: batch-sharded DDPM step compiles."""
    from paddle_tpu.distributed.auto_parallel import (ProcessMesh, Replicate,
                                                      Shard, shard_tensor)
    mesh = ProcessMesh(np.arange(8), dim_names=["dp"])
    m = _model()
    rng = np.random.RandomState(4)
    x = shard_tensor(
        paddle.to_tensor(rng.randn(8, 3, 16, 16).astype(np.float32)),
        mesh, [Shard(0)])
    t = paddle.to_tensor(rng.randint(0, 1000, (8,)).astype(np.int64))
    n = paddle.to_tensor(rng.randn(8, 3, 16, 16).astype(np.float32))
    loss = ddpm_loss(m, x, t, n)
    loss.backward()
    assert np.isfinite(float(loss))
