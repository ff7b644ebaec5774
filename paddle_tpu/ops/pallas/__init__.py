"""Pallas TPU kernel tier.

Reference disposition (SURVEY.md N27): the reference dynloads a vendored
flashattn library (third_party/flashattn, phi/backends/dynload/flashattn.cc)
and carries 66k LoC of fused CUDA kernels (phi/kernels/fusion). Here the
fused tier is a small set of Pallas TPU kernels behind availability gates —
XLA's fusion covers the long tail, Pallas covers what XLA's dataflow fusion
cannot restructure: ``flash_attention`` (blockwise softmax, forward and
backward), ``block_sparse_attention``, ``fused_ops`` (rmsnorm, AdamW),
``paged_attention`` (a decode step's query against the pages a sequence
holds) and ``grouped_experts`` (a routed-expert layer's tiles, each through
its own expert's weights read in place: one pipeline across the tiles where
XLA has a ``while`` with a turn a tile).

Every kernel has an XLA reference; `on_tpu()` picks between them from the
backend JAX reports, so the same code runs on the CPU test mesh and on
real TPUs. A backend that fails to initialise raises here: a kernel never
gives way to its reference because the device could not be reached.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import PartitionSpec


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


_SPMD_MESH = contextvars.ContextVar("pallas_spmd_mesh", default=None)


@contextlib.contextmanager
def whole_on_each_device(mesh):
    """Trace the enclosed code as part of an SPMD program over ``mesh``.

    Mosaic kernels cannot be partitioned automatically: lowering one in a
    multi-device program raises ("wrap the call in a shard_map"). Inside
    this context ``kernel_call`` does that wrapping, with every operand
    and result replicated, so each device runs the kernel whole. That is
    how the gather-at-use SPMD train step computes anyway (parameters are
    gathered, the batch is replicated unless the mesh has a data axis);
    with a data axis it costs a gather of the kernel's operands.
    """
    token = _SPMD_MESH.set(mesh if mesh is not None and mesh.size > 1
                           else None)
    try:
        yield
    finally:
        _SPMD_MESH.reset(token)


def kernel_call(call, *args):
    """Invoke a ``pl.pallas_call(...)`` closure on ``args``; see
    ``whole_on_each_device``."""
    mesh = _SPMD_MESH.get()
    if mesh is None:
        return call(*args)
    whole = PartitionSpec()
    return jax.shard_map(call, mesh=mesh, in_specs=(whole,) * len(args),
                         out_specs=whole, check_vma=False)(*args)


def interpret_mode() -> bool:
    """Pallas kernels run interpreted off-TPU (CPU test mesh)."""
    return not on_tpu()


from .flash_attention import flash_attention_pallas  # noqa: E402

__all__ = ["flash_attention_pallas", "on_tpu", "interpret_mode",
           "kernel_call", "whole_on_each_device"]
