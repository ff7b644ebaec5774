"""paddle.utils.flops analog — per-layer FLOPs estimation.

Reference: hapi/model_summary flops + utils/flops.py: walks the network
with forward hooks recording per-layer multiply-accumulate counts.
"""
from __future__ import annotations

import numpy as np

from ..core.tensor import Tensor
from ..nn.layer import Layer


def _layer_flops(layer, ins, outs):
    from ..nn.common import Linear
    from ..nn.conv import _ConvNd
    from ..nn.norm import LayerNorm, _BatchNormBase
    x = ins[0] if isinstance(ins, (list, tuple)) else ins
    out = outs[0] if isinstance(outs, (list, tuple)) else outs
    if isinstance(layer, Linear):
        batch = int(np.prod(x.shape[:-1]))
        return 2 * batch * layer.in_features * layer.out_features
    if isinstance(layer, _ConvNd):
        out_elems = int(np.prod(out.shape))
        k_elems = int(np.prod(layer.weight.shape[1:]))  # cin/groups*k*k
        return 2 * out_elems * k_elems
    if isinstance(layer, (_BatchNormBase, LayerNorm)):
        return 2 * int(np.prod(x.shape))
    return 0


def flops(net: Layer, input_size, custom_ops=None, print_detail=False):
    """Total forward FLOPs for one batch of `input_size`."""
    from ..autograd import no_grad
    from ..static import InputSpec

    sizes = input_size if isinstance(input_size, list) else [input_size]
    if sizes and isinstance(sizes[0], int):
        sizes = [tuple(sizes)]
    inputs = [InputSpec(s, "float32")._zeros(
        batch_size=s[0] if s and s[0] not in (None, -1) else 1)
        for s in sizes]

    total = [0]
    rows = []
    hooks = []
    custom_ops = custom_ops or {}

    def make_hook(lyr):
        def hook(layer, ins, outs):
            fn = custom_ops.get(type(layer))
            n = fn(layer, ins, outs) if fn else _layer_flops(layer, ins, outs)
            total[0] += n
            if n and print_detail:
                rows.append((type(layer).__name__, n))
        return hook

    for _, sub in net.named_sublayers():
        if next(iter(sub.children()), None) is None:
            hooks.append(sub.register_forward_post_hook(make_hook(sub)))
    was_training = net.training
    net.eval()
    try:
        with no_grad():
            net(*inputs)
    finally:
        for h in hooks:
            h.remove()
        if was_training:
            net.train()
    if print_detail:
        for name, n in rows:
            print(f"  {name:<24} {n:,}")
        print(f"Total FLOPs: {total[0]:,}")
    return total[0]


# Peak dense bf16 FLOP/s of one chip, keyed by a substring of
# ``device_kind`` (a v5e reports "TPU v5 lite"). Source: Google Cloud TPU
# documentation, the system-architecture page of each generation.
PEAK_BF16_FLOPS = {
    "v6e": 918e12, "v6 lite": 918e12,
    "v5p": 459e12,
    "v5e": 197e12, "v5 lite": 197e12, "v5lite": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def peak_device_flops(device=None) -> float:
    """Peak bf16 FLOP/s of the accelerator (the MFU denominator).

    A device that is not a TPU, or a TPU whose ``device_kind`` is not in
    ``PEAK_BF16_FLOPS``, raises: a utilization against a guessed peak is
    not a measurement.
    """
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        raise ValueError(
            f"no peak FLOP/s for platform {device.platform!r}: "
            f"utilization is defined against a TPU only")
    kind = device.device_kind.lower()
    for key, val in PEAK_BF16_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"device_kind {device.device_kind!r} is not in PEAK_BF16_FLOPS "
        f"(paddle_tpu/utils/flops.py): add its published peak")


__all__ = ["flops", "peak_device_flops", "PEAK_BF16_FLOPS"]
