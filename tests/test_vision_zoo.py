"""Vision model zoo part 2 (vision/models_extra.py + resnext/wide).

Reference test model: test/legacy_test/test_vision_models.py —每个
architecture gets a forward-shape check; parameter counts pin the
architectures to their published sizes (weights can't be diffed offline).
"""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.vision import models as M


def _x(size=64):
    return paddle.to_tensor(
        np.random.RandomState(0).randn(1, 3, size, size).astype("float32")
        / 10)


def _abstract(build):
    """``build()`` made under ``jax.eval_shape``: the module with its
    layers, attributes and parameter shapes, and no initializer compiled or
    run (a normal draw is a program per shape, a quarter of a second each).
    For the cases that read an architecture and compute nothing."""
    made = []
    try:
        jax.eval_shape(lambda: made.append(build()))
    finally:
        paddle.seed(1234)   # the global generator's key was split in the trace
    return made[0]


def _n_params(ctor):
    return sum(int(np.prod(p.shape)) for p in _abstract(ctor).parameters())


def _forward(build, x):
    """``build()``'s forward of ``x`` in eval mode as one compiled program,
    the building included. Run eagerly, every layer of a network is a
    program of its own to compile (743 of them for densenet121, 37 s; 2 s
    this way) and so is every initializer's draw, and these cases assert
    on the shape the whole forward gives (no buffer is written)."""
    def fwd(a):
        m = build()
        m.eval()
        with paddle.no_grad():
            out = m(paddle.Tensor(a))
        return jax.tree_util.tree_map(lambda t: t._data, out)
    try:
        return jax.jit(fwd)(x._data)
    finally:
        paddle.seed(1234)   # the global generator's key was split in the trace


class TestZooForward:
    @pytest.mark.parametrize("name", [
        "alexnet", "squeezenet1_0", "squeezenet1_1", "densenet121",
        "mobilenet_v3_small", "mobilenet_v3_large", "shufflenet_v2_x0_5",
        "shufflenet_v2_x1_0",
    ])
    def test_forward_shape(self, name):
        out = _forward(lambda: getattr(M, name)(num_classes=4), _x())
        assert list(out.shape) == [1, 4]

    def test_googlenet_aux_heads(self):
        out, aux1, aux2 = _forward(lambda: M.googlenet(num_classes=4),
                                   _x(96))
        assert list(out.shape) == [1, 4]
        assert list(aux1.shape) == [1, 4]
        assert list(aux2.shape) == [1, 4]

    def test_pretrained_raises_offline(self):
        with pytest.raises(Exception):
            _abstract(lambda: M.alexnet(pretrained=True))


class TestZooArchitectures:
    """Parameter counts at num_classes=1000 pin each architecture to its
    published size (strong structural check without pretrained weights)."""

    @pytest.mark.parametrize("ctor,expected_m", [
        (M.alexnet, 61.10),
        (M.squeezenet1_0, 1.25),
        (M.densenet121, 7.98),
        (M.inception_v3, 23.83),
        (M.mobilenet_v3_large, 5.48),
        (M.mobilenet_v3_small, 2.55),
        (M.shufflenet_v2_x1_0, 2.28),
        (M.resnext50_32x4d, 25.03),
        (M.wide_resnet50_2, 68.88),
    ])
    def test_param_count(self, ctor, expected_m):
        n = _n_params(ctor) / 1e6
        assert abs(n - expected_m) / expected_m < 0.03, \
            f"{ctor.__name__}: {n:.2f}M params, expected ~{expected_m}M"

    def test_resnext_grouped_conv(self):
        m = _abstract(lambda: M.resnext50_32x4d(num_classes=4))
        # the 3x3 stage of the first bottleneck must be 32-grouped, width 128
        blk = m.layer1.blocks[0]
        assert blk.conv2.groups == 32
        assert blk.conv2.weight.shape[0] == 128

    def test_wide_resnet_width(self):
        m = _abstract(lambda: M.wide_resnet50_2(num_classes=4))
        blk = m.layer1.blocks[0]
        assert blk.conv2.weight.shape[0] == 128  # 64 * (128/64) = 128

    def test_training_step_on_small_model(self):
        from paddle_tpu import nn, optimizer
        m = M.shufflenet_v2_x0_5(num_classes=4)
        opt = optimizer.SGD(learning_rate=0.01, parameters=m.parameters())
        lf = nn.CrossEntropyLoss()
        x = _x()
        y = paddle.to_tensor(np.array([1], dtype="int64"))
        loss = lf(m(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        assert np.isfinite(float(loss._data))
