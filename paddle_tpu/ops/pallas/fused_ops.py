"""Fused Pallas kernels: RMSNorm (fwd + bwd) and single-pass AdamW.

The reference's fused-op tier (phi/kernels/fusion: fused_rms_norm,
fused_adam / phi/kernels/fusion/gpu fused_adam_kernel) rebuilt as TPU
Pallas kernels:

- ``rms_norm_pallas``: one VMEM-resident pass per row block computes the
  normalized output; backward is a second fused kernel producing dx and
  per-block dw partials (summed outside). Saves only rstd between passes.
- ``adamw_pallas``: the whole AdamW update (moments, bias correction,
  decoupled weight decay, master-weight cast) in ONE elementwise kernel —
  one read and one write of each buffer per step, with hyperparameters in
  SMEM.

Both run in interpret mode on CPU for tests; on TPU the MXU/VPU tiling
follows the (8/16, 128) tile constraints from the Pallas guide.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_call


def _round_up(n, m):
    return (n + m - 1) // m * m


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    w = w_ref[:].astype(jnp.float32)
    o_ref[:] = (x * rstd * w[None, :]).astype(o_ref.dtype)
    rstd_ref[:] = rstd


def _rms_bwd_kernel(x_ref, w_ref, g_ref, rstd_ref, dx_ref, dw_ref):
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]                       # [block_rows, 1]
    h = x.shape[-1]
    gw = g * w[None, :]
    c = jnp.sum(gw * x, axis=-1, keepdims=True) / h
    dx = (gw - x * c * rstd * rstd) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # dw accumulates into ONE (1, h) block revisited by every grid step —
    # TPU grid iterations run sequentially, so read-modify-write is safe,
    # and the single-block output satisfies the (8, 128) tiling rule that a
    # (1, h) slice of a (grid, h) array would violate.
    part = jnp.sum(g * x * rstd, axis=0, keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dw_ref[:] = part

    @pl.when(pl.program_id(0) > 0)
    def _acc():
        dw_ref[:] += part


# Mosaic refuses a kernel whose scoped VMEM passes 16 MiB on a v5e; the
# row block is sized to stay under 12 MiB of it.
_VMEM_BUDGET = 12 << 20


def _pick_block_rows(n_rows: int, h: int, itemsize: int) -> int:
    # Sized for the backward, the larger of the two kernels: the x, g and
    # dx blocks are double-buffered in the input dtype and the compiler
    # keeps about two fp32 temporaries per element (it reported 17.3 B per
    # element for bf16 at 256 x 4096). Callers pad n_rows to a multiple of
    # 8 (TPU sublane tiling), so the 8-row block always divides.
    per_row = h * (6 * itemsize + 8)
    for cand in (256, 128, 64, 32, 16):
        if n_rows % cand == 0 and cand * per_row <= _VMEM_BUDGET:
            return cand
    return 8


def _pad_rows(a, n_pad):
    n = a.shape[0]
    if n_pad == n:
        return a
    return jnp.pad(a, ((0, n_pad - n),) + ((0, 0),) * (a.ndim - 1))


def _rms_fwd_call(x2d, w, eps, interpret):
    n_orig, h = x2d.shape
    n = _round_up(n_orig, 8)
    x2d = _pad_rows(x2d, n)   # zero rows: rstd=rsqrt(eps), sliced off below
    br = _pick_block_rows(n, h, x2d.dtype.itemsize)
    out, rstd = kernel_call(pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(n // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x2d.dtype),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=interpret,
        name="rms_norm_fwd",
    ), x2d, w)
    return out[:n_orig], rstd[:n_orig]


def _rms_bwd_call(x2d, w, g2d, rstd, interpret):
    n_orig, h = x2d.shape
    n = _round_up(n_orig, 8)
    # zero-padded rows contribute g*x*rstd = 0 to dw; their dx rows are
    # sliced off
    x2d = _pad_rows(x2d, n)
    g2d = _pad_rows(g2d, n)
    rstd = _pad_rows(rstd, n)
    br = _pick_block_rows(n, h, x2d.dtype.itemsize)
    grid = n // br
    dx, dw = kernel_call(pl.pallas_call(
        _rms_bwd_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((h,), lambda i: (0,)),
                  pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                   pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h), x2d.dtype),
                   jax.ShapeDtypeStruct((1, h), jnp.float32)],
        interpret=interpret,
        name="rms_norm_bwd",
    ), x2d, w, g2d, rstd)
    return dx[:n_orig], dw[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm_pallas(x, weight, eps: float = 1e-6, interpret: bool = False):
    """Fused RMSNorm: y = x * rsqrt(mean(x^2) + eps) * weight.

    x: [..., hidden]; weight: [hidden]. Arbitrary leading dims.
    """
    lead = x.shape[:-1]
    h = x.shape[-1]
    out, _ = _rms_fwd_call(x.reshape(-1, h), weight, eps, interpret)
    return out.reshape(*lead, h)


def _rms_vjp_fwd(x, weight, eps, interpret):
    lead = x.shape[:-1]
    h = x.shape[-1]
    x2d = x.reshape(-1, h)
    out, rstd = _rms_fwd_call(x2d, weight, eps, interpret)
    return out.reshape(*lead, h), (x2d, weight, rstd, lead)


def _rms_vjp_bwd(eps, interpret, res, g):
    x2d, weight, rstd, lead = res
    h = x2d.shape[-1]
    dx, dw = _rms_bwd_call(x2d, weight, g.reshape(-1, h), rstd, interpret)
    return dx.reshape(*lead, h), dw.astype(weight.dtype)


rms_norm_pallas.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


# ---------------------------------------------------------------------------
# fused AdamW
# ---------------------------------------------------------------------------

def _adamw_kernel(scalars_ref, p_ref, m_ref, v_ref, g_ref,
                  p_out, m_out, v_out):
    lr = scalars_ref[0]
    beta1 = scalars_ref[1]
    beta2 = scalars_ref[2]
    eps = scalars_ref[3]
    wd = scalars_ref[4]
    bc1 = scalars_ref[5]   # 1 - beta1^t
    bc2 = scalars_ref[6]   # 1 - beta2^t
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:]
    v = v_ref[:]
    m_new = beta1 * m + (1.0 - beta1) * g
    v_new = beta2 * v + (1.0 - beta2) * g * g
    m_hat = m_new / bc1
    v_hat = v_new / bc2
    # decoupled weight decay (AdamW): p -= lr*wd*p before the adam step
    p_new = p * (1.0 - lr * wd) - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    p_out[:] = p_new.astype(p_out.dtype)
    m_out[:] = m_new
    v_out[:] = v_new


def adamw_pallas(p, m, v, g, *, lr, beta1, beta2, eps, weight_decay,
                 beta1_pow, beta2_pow, interpret: bool = False):
    """Single-pass fused AdamW update.

    p may be any shape/dtype (master fp32 recommended); m/v are fp32 of the
    same shape; returns (p_new, m_new, v_new). ``beta1_pow``/``beta2_pow``
    are the CURRENT-step beta powers (beta^t, traced ok); hyperparameters
    ride in SMEM so one compiled kernel serves every step and lr value.
    """
    shape = p.shape
    n = p.size
    lane = 128
    sub = 8
    width = lane * sub
    n_pad = _round_up(max(n, width), width)
    rows = n_pad // lane

    def flat(a, dtype):
        a = a.reshape(-1).astype(dtype)
        if n_pad != n:
            a = jnp.pad(a, (0, n_pad - n))
        return a.reshape(rows, lane)

    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        1.0 - jnp.asarray(beta1_pow, jnp.float32),
        1.0 - jnp.asarray(beta2_pow, jnp.float32),
    ])

    block_rows = sub
    while rows % block_rows:
        block_rows //= 2
    grid = rows // block_rows

    p2, m2, v2 = pl.pallas_call(
        _adamw_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, lane), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, lane), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, lane), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, lane), p.dtype),
                   jax.ShapeDtypeStruct((rows, lane), jnp.float32),
                   jax.ShapeDtypeStruct((rows, lane), jnp.float32)],
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
        name="adamw_update",
    )(scalars, flat(p, p.dtype), flat(m, jnp.float32),
      flat(v, jnp.float32), flat(g, jnp.float32))

    unflat = lambda a: a.reshape(-1)[:n].reshape(shape)  # noqa: E731
    return unflat(p2), unflat(m2), unflat(v2)


__all__ = ["rms_norm_pallas", "adamw_pallas"]
