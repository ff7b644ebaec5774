"""The flash kernels' backward (interpret mode on the CPU mesh): the
gradients of tests/test_pallas_kernels.py's case table against the dense
reference (GQA's and the dropout mask's cases are in that file)."""
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas

from test_pallas_kernels import CASES, _assert_close, _case, _dense, _rand


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("case", list(CASES))
def test_flash_grads_match_dense(case, causal):
    q, k, v, mask, lens, valid, kw = _case(case, 3)
    w = _rand(q.shape, 9) * valid       # a cotangent with no structure

    def f(q, k, v):
        return (flash_attention_pallas(q, k, v, causal=causal, **kw
                                       ).astype(jnp.float32) * w).sum()

    def g(q, k, v):
        return (_dense(q, k, v, causal, mask=mask, seqlens=lens,
                       neg=-1e30) * w).sum()

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, ref):
        assert a.dtype == q.dtype
        _assert_close(a, b_, q.dtype, dict(rtol=1e-4, atol=1e-5))
