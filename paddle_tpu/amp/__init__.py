"""Automatic mixed precision.

Analog of the reference AMP stack: paddle.amp.auto_cast
(python/paddle/amp/auto_cast.py:703, levels O0/OD/O1/O2 at :333), per-op
white/black lists (amp/amp_lists.py), GradScaler with dynamic loss scaling
(amp/grad_scaler.py), and the AMP cast injected into every generated eager
ad_func (eager_gen.py:251). Here the cast policy is applied centrally in the op
dispatch wrapper (ops/registry.py) — on TPU the natural AMP dtype is bfloat16,
which needs no loss scaling, but GradScaler is provided for float16 parity.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax.numpy as jnp

from ..core import dtype as dtype_mod
from ..core.tensor import Tensor

# Per-op lists mirroring python/paddle/amp/amp_lists.py.
WHITE_LIST = {
    "matmul", "mm", "bmm", "einsum", "conv1d", "conv2d", "conv3d",
    "conv2d_transpose", "linear", "addmm", "scaled_dot_product_attention",
    "flash_attention",
    # matmul-dominated fused blocks (fp32-sensitive pieces inside them —
    # rmsnorm reductions, softmax — already accumulate in fp32)
    "llama_scanned_layers",
}
BLACK_LIST = {
    "exp", "log", "log2", "log10", "log1p", "pow", "square", "sqrt", "rsqrt",
    "softmax_with_cross_entropy", "cross_entropy", "log_softmax",
    "mean_all", "reduce_sum_all", "cumsum", "erf", "erfinv",
    "sin", "cos", "tan", "asin", "acos", "atan", "sinh", "cosh", "tanh_shrink",
    "norm", "p_norm", "cos_sim", "layer_norm_fp32",
}

_STATE = threading.local()


def _stack():
    if not hasattr(_STATE, "stack"):
        _STATE.stack = []
    return _STATE.stack


class _AmpState:
    __slots__ = ("enable", "dtype", "level", "white", "black")

    def __init__(self, enable, dtype, level, white, black):
        self.enable = enable
        self.dtype = dtype
        self.level = level
        self.white = white
        self.black = black


class auto_cast:
    """paddle.amp.auto_cast analog (auto_cast.py:703)."""

    def __init__(self, enable=True, custom_white_list=None, custom_black_list=None,
                 level="O1", dtype="bfloat16"):
        if level not in ("O0", "OD", "O1", "O2"):
            raise ValueError(f"bad amp level {level}")
        white = set(WHITE_LIST)
        black = set(BLACK_LIST)
        if custom_white_list:
            white |= set(custom_white_list)
            black -= set(custom_white_list)
        if custom_black_list:
            black |= set(custom_black_list)
            white -= set(custom_black_list)
        self._st = _AmpState(enable and level != "O0", dtype_mod.to_jax_dtype(dtype),
                             level, white, black)

    def __enter__(self):
        _stack().append(self._st)
        return self

    def __exit__(self, *exc):
        _stack().pop()
        return False


amp_guard = auto_cast  # legacy alias (paddle.base.dygraph.amp_guard)


def amp_state() -> Optional[_AmpState]:
    s = _stack()
    return s[-1] if s else None


def autocast_args(op_name, args, kwargs):
    """Apply the active cast policy to Tensor args. Called from op dispatch."""
    st = amp_state()
    if st is None or not st.enable or getattr(_STATE, "in_cast", False):
        return args, kwargs
    if st.level in ("O1", "OD"):
        if op_name in st.white:
            target = st.dtype
        elif op_name in st.black:
            target = jnp.float32
        else:
            return args, kwargs
    else:  # O2: everything low precision except black list
        target = jnp.float32 if op_name in st.black else st.dtype

    def cast_leaf(x):
        if isinstance(x, Tensor) and jnp.issubdtype(x.dtype, jnp.floating) \
                and x.dtype != target:
            return _guarded_cast(x, target)
        return x

    import jax
    flat, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
    flat = [cast_leaf(x) for x in flat]
    return jax.tree_util.tree_unflatten(treedef, flat)


def _guarded_cast(t: Tensor, target):
    from ..ops import cast
    _STATE.in_cast = True
    try:
        return cast(t, target)
    finally:
        _STATE.in_cast = False


def decorate(models, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """paddle.amp.decorate analog (auto_cast.py): casts model params to the amp
    dtype for O2 and enables optimizer master weights."""
    single_model = not isinstance(models, (list, tuple))
    model_list = [models] if single_model else list(models)
    target = dtype_mod.to_jax_dtype(dtype)
    if level == "O2":
        for m in model_list:
            for p in m.parameters():
                if jnp.issubdtype(p.dtype, jnp.floating):
                    p._set_data(p._data.astype(target))
        if optimizers is not None:
            opts = optimizers if isinstance(optimizers, (list, tuple)) else [optimizers]
            for o in opts:
                o._multi_precision = True if master_weight is None else master_weight
    if optimizers is None:
        return models if single_model else model_list
    return (models if single_model else model_list), optimizers


class GradScaler:
    """Dynamic loss scaling (amp/grad_scaler.py analog). bf16 on TPU does not
    need scaling; enable only for float16 experiments."""

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 16,
                 incr_ratio=2.0, decr_ratio=0.5,
                 incr_every_n_steps=2000, decr_every_n_nan_or_inf=1,
                 use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        self._unscaled_opts = set()

    def is_enable(self):
        return self._enable

    def get_loss_scaling(self):
        return self._scale

    def scale(self, loss):
        if not self._enable:
            return loss
        return loss * self._scale

    def unscale_(self, optimizer):
        if not self._enable or id(optimizer) in self._unscaled_opts:
            return
        self._unscaled_opts.add(id(optimizer))
        inv = 1.0 / self._scale
        found = False
        for p in optimizer._parameter_list:
            if p.grad is not None:
                g = p.grad._data * inv
                finite = bool(jnp.all(jnp.isfinite(g)))
                if not finite:
                    found = True
                p.grad = Tensor(g)
        self._found_inf = found

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)  # no-op if user already unscaled this opt
        if not self._found_inf:
            optimizer.step()
        self._unscaled_opts.discard(id(optimizer))

    def update(self):
        if not self._enable or not self._dynamic:
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0

    def minimize(self, optimizer, loss):
        self.step(optimizer)
        self.update()

    def state_dict(self):
        return {"scale": self._scale, "good_steps": self._good_steps,
                "bad_steps": self._bad_steps, "enable": self._enable}

    def load_state_dict(self, state):
        self._scale = state["scale"]
        self._good_steps = state["good_steps"]
        self._bad_steps = state["bad_steps"]


# paddle.amp.debugging (op stats + NaN/Inf checker); imported late so the
# dispatch hook only pays when enabled
from . import debugging  # noqa: E402,F401


def is_float16_supported(device=None):
    """ref amp.is_float16_supported: fp16 compute support. TPUs compute in
    bf16 natively; fp16 works via XLA but without MXU benefit."""
    import jax
    return jax.devices()[0].platform in ("tpu", "gpu")


def is_bfloat16_supported(device=None):
    """ref amp.is_bfloat16_supported: always true on TPU/XLA backends."""
    return True
