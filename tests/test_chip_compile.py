"""Offer the main path's kernels to the chip's compiler, without a chip.

libtpu compiles for a TPU that is described, not attached
(``jax.experimental.topologies``), so what Mosaic or XLA:TPU would refuse on
a v5e — a kernel over its scoped VMEM, a misaligned tile, a program that
does not fit 16 GB — is refused here, in tier-1, at the widths of
``llama2_7b_config()``. Nothing runs: these say nothing about results or
times. Interpret-mode tests cannot see any of this (the rmsnorm kernel
passed all of them and did not compile at hidden 4096).

Skipped where the topology cannot be described. JAX's persistent cache is
off around the compiles: an executable for a described device cannot be
read back without the device.
"""
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Llama-2-7B widths (models/llama.py: llama2_7b_config)
HIDDEN, HEADS, HEAD_DIM, MLP, SEQ = 4096, 32, 128, 11008, 2048


@pytest.fixture(scope="module")
def host():
    """The four chips of a described v5e 2x2 host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it cannot start
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def chip(host):
    """Sharding on one of them."""
    return SingleDeviceSharding(host[0])


@pytest.fixture(autouse=True)
def _as_on_the_chip():
    """Cache off (see module docstring) and x64 off: tier-1 turns x64 on
    for its numeric-gradient checks, the chip runs without it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _compile(fn, *shapes):
    """Compile for the described chip; raises what its compiler raises."""
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_calls(compiled, name):
    """tpu_custom_call lines of the optimized HLO that carry a kernel's
    ``pallas_call(name=...)``."""
    return [ln for ln in compiled.as_text().splitlines()
            if "tpu_custom_call" in ln and name in ln]


def _loop_reads(compiled, scope, shape):
    """Of the optimized HLO's fusions whose ``op_name`` lies under ``scope``
    inside a ``while`` body, those with an operand of ``shape`` ("512,
    65536"), as (fusion, the operand's own line in the fused computation,
    whole): ``whole`` where anything but a ``dynamic-slice`` reads it."""
    computations, name = {}, None
    for ln in compiled.as_text().splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \((.*)\) -> .* \{$", ln)
        if head:
            name = head.group(1)
            computations[name] = (head.group(2), [])
        elif name and ln.strip() != "}":
            computations[name][1].append(ln)
    reads = []
    for _, lines in computations.values():
        for ln in lines:
            call = re.search(r" fusion\(.*calls=%([\w.\-]+)", ln)
            where = re.search(r'op_name="([^"]*)"', ln)
            if not (call and where
                    and scope + "/while/body" in where.group(1)):
                continue
            params, inner = computations[call.group(1)]
            for p in re.findall(r"([\w.\-]+): \w+\[%s\]" % shape, params):
                used = re.compile(r"%%%s\b" % re.escape(p))
                own, = [u for u in inner if " parameter(" in u
                        and used.search(u.split(" = ")[0])]
                users = [u for u in inner
                         if used.search(u.split(" = ", 1)[-1])]
                reads.append((ln.strip(), own.strip(), any(
                    " dynamic-slice(" not in u for u in users)))
    return reads


@pytest.mark.parametrize("kv_heads", [32, 8], ids=["mha32", "gqa32_8"])
@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles_at_7b_widths(chip, kv_heads, bwd):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas

    def fwd(q, k, v):
        return flash_attention_pallas(q, k, v, causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((2, SEQ, HEADS, HEAD_DIM), jnp.bfloat16,
                             sharding=chip)
    kv = jax.ShapeDtypeStruct((2, SEQ, kv_heads, HEAD_DIM), jnp.bfloat16,
                              sharding=chip)
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)) if bwd else fwd,
                        q, kv, kv)
    assert _kernel_calls(compiled, "flash_fwd")
    if bwd:
        assert _kernel_calls(compiled, "flash_bwd_dq")
        assert _kernel_calls(compiled, "flash_bwd_dkv")


# (b, s, hq, hkv, d, dtype, causal, mask, kv_seqlens, dropout, blocks): what
# the shape-derived tiling (ops/pallas/flash_attention.py::flash_tiling) hands
# Mosaic at real sizes. The interpreter accepts any tiling; only this compile
# refuses one over the kernel's VMEM or with a misaligned slice.
FLASH_VARIANTS = {
    "smollm2_cell": (4, 2048, 32, 32, 64, jnp.bfloat16, True, False, False,
                     0.0, None),
    "mistral_4k": (1, 4096, 32, 8, 128, jnp.bfloat16, True, False, False,
                   0.0, None),
    "f32_d128_4k": (1, 4096, 4, 4, 128, jnp.float32, True, False, False,
                    0.0, None),
    "f32_d64_1k": (2, 1024, 4, 4, 64, jnp.float32, True, False, False, 0.0,
                   None),
    "gpt2_mask": (2, 1024, 12, 12, 64, jnp.bfloat16, False, True, False,
                  0.0, None),
    "mask_f32_8k": (1, 8192, 2, 2, 128, jnp.float32, True, True, False, 0.0,
                    None),
    "seqlens": (2, 2048, 4, 4, 64, jnp.bfloat16, True, False, True, 0.0,
                None),
    "dropout": (2, 1024, 4, 4, 64, jnp.bfloat16, True, False, False, 0.1,
                None),
    "dropout_f32_d128": (1, 2048, 2, 2, 128, jnp.float32, False, False,
                         False, 0.5, None),
    "pads_2176": (1, 2176, 4, 4, 64, jnp.bfloat16, True, False, False, 0.0,
                  None),
    "pads_200": (1, 200, 2, 2, 64, jnp.float32, True, False, False, 0.0,
                 None),
    "short_48": (2, 48, 2, 2, 64, jnp.bfloat16, True, False, False, 0.0,
                 None),
    "8k_d128": (1, 8192, 8, 8, 128, jnp.bfloat16, True, False, False, 0.0,
                None),
    "explicit_1024x128": (1, 2048, 4, 4, 64, jnp.bfloat16, True, False,
                          False, 0.0, (1024, 128)),
    "explicit_256x512": (1, 2048, 4, 4, 64, jnp.bfloat16, True, False,
                         False, 0.0, (256, 512)),
    "explicit_128x128": (1, 2048, 4, 4, 64, jnp.bfloat16, True, False,
                         False, 0.0, (128, 128)),
}


@pytest.mark.parametrize("variant", list(FLASH_VARIANTS))
def test_flash_variants_compile_fwd_bwd(chip, variant):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas
    (b, s, hq, hkv, d, dtype, causal, has_mask, has_lens, dropout,
     blocks) = FLASH_VARIANTS[variant]
    bq, bk = blocks or (None, None)

    def loss(q, k, v, mask, lens):
        return flash_attention_pallas(
            q, k, v, causal=causal, attn_mask=mask, kv_seqlens=lens,
            dropout_p=dropout, seed=3, block_q=bq, block_k=bk,
        ).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((b, s, hq, d), dtype, sharding=chip)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), dtype, sharding=chip)
    mask = jax.ShapeDtypeStruct((b, 1, s, s), jnp.float32,
                                sharding=chip) if has_mask else None
    lens = jax.ShapeDtypeStruct((b,), jnp.int32,
                                sharding=chip) if has_lens else None
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, mask,
                        lens)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert _kernel_calls(compiled, name), name


@pytest.mark.parametrize("hidden", [2048, 4096, 8192])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_rmsnorm_fwd_bwd_compiles(chip, hidden, dtype):
    """Hidden 4096 and 8192 overflowed scoped VMEM (18.13 MiB against a
    16 MiB limit) while the row block was 256 whatever the width."""
    from paddle_tpu.ops.pallas.fused_ops import rms_norm_pallas

    def loss(x, w):
        return rms_norm_pallas(x, w, 1e-5).astype(jnp.float32).sum()

    x = jax.ShapeDtypeStruct((2, SEQ, hidden), dtype, sharding=chip)
    w = jax.ShapeDtypeStruct((hidden,), dtype, sharding=chip)
    compiled = _compile(jax.grad(loss, argnums=(0, 1)), x, w)
    assert _kernel_calls(compiled, "rms_norm_fwd")
    assert _kernel_calls(compiled, "rms_norm_bwd")


def test_kernels_compile_inside_a_mesh_program(host):
    """Mosaic kernels cannot be partitioned automatically: lowered in a
    program over four chips they raise, which is what the SPMD train step
    did on a TPU. ``whole_on_each_device`` wraps them (ops/pallas)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops import pallas as _pl
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas
    from paddle_tpu.ops.pallas.fused_ops import rms_norm_pallas

    mesh = Mesh(np.array(host, dtype=object).reshape(2, 2),
                ("fsdp", "tensor"))

    def loss(x, w):
        b, s, _ = x.shape
        y = rms_norm_pallas(x, w, 1e-5).reshape(b, s, HEADS, HEAD_DIM)
        return flash_attention_pallas(y, y, y, causal=True).astype(
            jnp.float32).sum()

    def spmd_loss(x, w):
        with _pl.whole_on_each_device(mesh):
            return jax.grad(loss, argnums=(0, 1))(x, w)

    x = jax.ShapeDtypeStruct((1, SEQ, HIDDEN), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    w = jax.ShapeDtypeStruct((HIDDEN,), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(jax.grad(loss, argnums=(0, 1)), x, w)
    compiled = _compile(spmd_loss, x, w)
    for kernel in ("rms_norm_fwd", "rms_norm_bwd", "flash_fwd",
                   "flash_bwd_dq", "flash_bwd_dkv"):
        assert _kernel_calls(compiled, kernel), kernel


def test_adamw_kernel_compiles_at_mlp_width(chip):
    """Off by default (FLAGS_use_pallas_adamw); offered at the size of
    one 7B MLP matrix so that ROADMAP S5 starts from a kernel that
    compiles."""
    from paddle_tpu.ops.pallas.fused_ops import adamw_pallas

    def update(p, m, v, g):
        return adamw_pallas(p, m, v, g, lr=1e-4, beta1=0.9, beta2=0.999,
                            eps=1e-8, weight_decay=0.01, beta1_pow=0.9,
                            beta2_pow=0.999)

    a = jax.ShapeDtypeStruct((HIDDEN, MLP), jnp.float32, sharding=chip)
    assert _kernel_calls(_compile(update, a, a, a, a), "adamw_update")


def test_block_sparse_attention_compiles(chip):
    """Causal sliding window of 4 tiles plus a global first column, at
    s 2048, 32 x 128 heads (ROADMAP S5)."""
    import numpy as np

    from paddle_tpu.ops.pallas.block_sparse_attention import \
        block_sparse_attention_pallas

    nb = SEQ // 128
    i, j = np.indices((nb, nb))
    pattern = (j <= i) & ((j >= i - 3) | (j == 0))

    def fwd(q, k, v):
        return block_sparse_attention_pallas(q, k, v, pattern)

    x = jax.ShapeDtypeStruct((1, SEQ, HEADS, HEAD_DIM), jnp.bfloat16,
                             sharding=chip)
    assert _kernel_calls(_compile(fwd, x, x, x), "block_sparse_fwd")


@pytest.mark.parametrize("tokens", [8, 1024], ids=["decode_b8", "prefill_1k"])
def test_paged_attention_step_compiles(chip, tokens):
    """The general serving attention op (an XLA gather over the block
    table; the decode step has a kernel of its own, next test) at 32 x 128
    heads with 16-token pages: one decode step for 8 slots, and a
    1024-token prompt admitted into one slot, which is how the batcher
    admits. (Two packed prompts in one call
    do not fit: the op gathers a copy of the timeline per token, 34 GB at
    these sizes; XLA folds that gather away only for a single sequence.)"""
    from paddle_tpu.incubate.nn.functional.decode_attention import \
        block_gqa_attention

    block, blocks_per_seq = 16, SEQ // 16
    bsz = 8 if tokens == 8 else 1
    n_pages = 8 * blocks_per_seq + 1

    def step(q, k, v, kc, vc, dec, bt, cos, sin):
        if tokens == bsz:        # decode: one token per slot, appended
            enc, this = jnp.zeros_like(dec), jnp.ones_like(dec)
        else:                    # prefill: the whole prompt into slot 0
            enc = this = jnp.full_like(dec, tokens)
        cu_q = jnp.arange(bsz + 1, dtype=jnp.int32) * (tokens // bsz)
        out, kc, vc = block_gqa_attention(
            q, k, v, kc, vc, enc, dec, this, cu_q, bt, block_size=block,
            rope_cos=cos, rope_sin=sin)
        return out._data, kc._data, vc._data

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    qkv = sds((tokens, HEADS, HEAD_DIM))
    pool = sds((n_pages, HEADS, block, HEAD_DIM))
    rope = sds((SEQ, HEAD_DIM // 2))
    compiled = _compile(step, qkv, qkv, qkv, pool, pool,
                        sds((bsz,), jnp.int32),
                        sds((bsz, blocks_per_seq), jnp.int32), rope, rope)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 << 30, mem


def _pool_arrays(compiled, pool):
    """(layouts, copies) of the arrays of ``pool``'s shape and dtype in the
    optimized HLO: every layout such an array is given (its tiling with
    it), and the ``copy`` instructions that produce one."""
    import re
    dims = ",".join(str(d) for d in pool.shape)
    name = {"bfloat16": "bf16", "float32": "f32"}[pool.dtype.name]
    array = re.escape(f"{name}[{dims}]") + r"\{[^}]*\}"
    text = compiled.as_text()
    layouts = {m for m in re.findall(array, text) if ":" in m}
    copies = re.findall(rf"= {array} copy\(", text)
    return layouts, copies


@pytest.mark.parametrize("kv_heads,dtype", [
    (8, jnp.bfloat16), (32, jnp.bfloat16), (8, jnp.float32)],
    ids=["gqa32_8_bf16", "mha32_bf16", "gqa32_8_f32"])
def test_paged_decode_entry_compiles_with_the_kernel(chip, monkeypatch,
                                                     kv_heads, dtype):
    """The decode step's attention entry at the serving cell's shapes
    (mistral7b-*: 32 slots, 32/8 heads of 128, 16-token pages, 256 pages a
    sequence, 3,073 pages; and the same with 32 kv heads, chip_smoke's
    Llama-2 widths, and in float32), two layers chained with their pools
    donated, as the decode executable holds them: RoPE, the page writer and
    the Pallas kernel ``paged_attention_decode`` reading the pool in place.
    The pools keep the layout they enter with: no ``copy`` of a pool array,
    one layout for all of them, nothing their size among the temporaries.
    (A row scatter indexed on axes 0 and 2 had XLA:TPU copy every pool
    array into a layout of its own and back, 64% of the cell's decode step:
    PERF.md, PR 29.) Nor is anything the size of the gathered timelines
    left: at these shapes ``_gather_paged`` made 1 GB of them.
    The entry asks ``on_tpu()``, which sees this sandbox's CPU, so the test
    steers it: the compile is for the described chip."""
    import paddle_tpu.ops.pallas as pallas_tier
    from paddle_tpu.incubate.nn.functional.decode_attention import \
        block_gqa_decode_attention

    monkeypatch.setattr(pallas_tier, "on_tpu", lambda: True)
    slots, block, blocks_per_seq, n_pages = 32, 16, 256, 3073

    def step(q, k, v, pools, dec, bt, cos, sin):
        out = []
        for kc, vc in pools:
            att, kc, vc = block_gqa_decode_attention(
                q, k, v, kc, vc, dec, bt, rope_cos=cos, rope_sin=sin)
            q = att._data.reshape(q.shape)
            out.append((kc._data, vc._data))
        return q, out

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    row = sds((slots, kv_heads, HEAD_DIM))
    pool = sds((n_pages, kv_heads, block, HEAD_DIM))
    rope = sds((block * blocks_per_seq, HEAD_DIM // 2), jnp.float32)
    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        sds((slots, HEADS, HEAD_DIM)), row, row, [(pool, pool)] * 2,
        sds((slots,), jnp.int32), sds((slots, blocks_per_seq), jnp.int32),
        rope, rope).compile()
    assert len(_kernel_calls(compiled, "paged_attention_decode")) == 2
    layouts, copies = _pool_arrays(compiled, pool)
    assert not copies, copies
    assert len(layouts) == 1, layouts
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("tokens,first_row", [(256, "dec"), (1024, 0)],
                         ids=["chunk_256", "whole_prompt_1k"])
def test_paged_chunk_keeps_the_pools_layout(chip, tokens, first_row):
    """A prompt's chunk as the batcher admits it (one sequence, 256 rows at
    a traced ``dec``; and a whole prompt of 1,024 in encoder mode) through
    the general op at the cell's shapes, two layers chained, pools donated:
    the run is laid over the slot's pages (8 MB an array) and the pages
    are scattered back along the first axis, so no pool array is copied and
    all keep one layout. What is left are the transposes of the slot's
    pages to a timeline a head and back."""
    from paddle_tpu.incubate.nn.functional.decode_attention import \
        block_gqa_attention

    kv_heads, block, blocks_per_seq, n_pages = 8, 16, 256, 3073

    def step(q, k, v, pools, dec, bt, cos, sin):
        this = jnp.full_like(dec, tokens)
        enc, dec = (jnp.zeros_like(dec), dec) if first_row == "dec" \
            else (this, jnp.zeros_like(dec))
        out = []
        for kc, vc in pools:
            att, kc, vc = block_gqa_attention(
                q, k, v, kc, vc, enc, dec, this,
                jnp.array([0, tokens], jnp.int32), bt, block_size=block,
                rope_cos=cos, rope_sin=sin)
            q = att._data.reshape(q.shape)
            out.append((kc._data, vc._data))
        return q, out

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    row = sds((tokens, kv_heads, HEAD_DIM))
    pool = sds((n_pages, kv_heads, block, HEAD_DIM))
    rope = sds((block * blocks_per_seq, HEAD_DIM // 2), jnp.float32)
    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        sds((tokens, HEADS, HEAD_DIM)), row, row, [(pool, pool)] * 2,
        sds((1,), jnp.int32), sds((1, blocks_per_seq), jnp.int32),
        rope, rope).compile()
    layouts, copies = _pool_arrays(compiled, pool)
    assert not copies, copies
    assert len(layouts) == 1, layouts
    # the float32 scores of one layer, and less than a pool array (100 MB)
    scores = tokens * HEADS * block * blocks_per_seq * 4
    assert compiled.memory_analysis().temp_size_in_bytes < scores + (64 << 20)


def test_scanned_decoder_layer_fwd_bwd_compiles(chip, monkeypatch):
    """One layer of the scanned stack, forward and backward under
    selective recompute, at 7B widths: flash attention inside
    jax.checkpoint inside lax.scan, as the train step composes them. The
    test answers ``on_tpu()`` for the program, which sees the CPU here."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.llama import ScannedLlamaLayers, llama2_7b_config
    from paddle_tpu.ops import pallas as _pl

    cfg = llama2_7b_config(num_hidden_layers=1, dtype="bfloat16",
                           scan_layers=True, use_recompute=True,
                           recompute_granularity="selective")
    layer = ScannedLlamaLayers(cfg)
    params = list(layer.parameters())
    monkeypatch.setattr(_pl, "on_tpu", lambda: True)

    def loss(arrays, hidden, cos, sin):
        saved = [p._data for p in params]
        try:
            for p, a in zip(params, arrays):
                p._data = a
            with paddle.no_grad():
                out = layer(Tensor(hidden), cos, sin)
            return out._data.astype(jnp.float32).sum()
        finally:
            for p, a in zip(params, saved):
                p._data = a

    def sds(shape):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.bfloat16,
                                    sharding=chip)

    compiled = _compile(jax.grad(loss, argnums=(0, 1)),
                        [sds(p.shape) for p in params],
                        sds((1, SEQ, HIDDEN)),
                        sds((SEQ, HEAD_DIM // 2)), sds((SEQ, HEAD_DIM // 2)))
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert _kernel_calls(compiled, kernel), kernel


def test_sambay_decoder_blocks_compile_with_the_kernel(chip, monkeypatch):
    """The window and the shared-pool decoder blocks of SambaY at the cell
    ``phi4flash-reason-open``'s shapes (64 slots, 40/20 heads of 64 as 10
    key groups of 128, rings of 512 rows = 32 pages a slot, 16,385 pool
    pages of 16 rows, 256 pages a sequence): the row write (whole pages
    read, changed and scattered back along the first axis) and PR 26's
    kernel over the ring and over the pool, with queries zero-padded to the
    group's width. Rings and pool keep their layout: nothing their size is
    left as a temporary."""
    import paddle_tpu.ops.pallas as pallas_tier
    from paddle_tpu.models import sambay

    monkeypatch.setattr(pallas_tier, "on_tpu", lambda: True)
    slots, d, ffn, block, window, pages = 64, 2560, 10240, 16, 512, 16385

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    block_w = {"ln1_g": sds((d,)), "ln1_b": sds((d,)), "ln2_g": sds((d,)),
               "ln2_b": sds((d,)), "mlp_w1": sds((d, 2 * ffn)),
               "mlp_w2": sds((ffn, d)), "o_w": sds((d, d)), "o_b": sds((d,)),
               "lq1": sds((64,)), "lk1": sds((64,)), "lq2": sds((64,)),
               "lk2": sds((64,)), "sub_g": sds((128,)),
               "qkv_w": sds((d, 2 * d)), "qkv_b": sds((2 * d,))}
    x, dec = sds((slots, d)), sds((slots,), jnp.int32)
    lam0 = sds((), jnp.float32)

    ring = sds((slots * window // block, 10, block, 128))
    win = jax.jit(
        lambda p, x, rk, rv, dec, lam: sambay._window_block_tok(
            p, x, rk, rv, dec, lam, 1e-5, window),
        donate_argnums=(2, 3)).lower(block_w, x, ring, ring, dec,
                                     lam0).compile()
    pool = sds((pages, 10, block, 128))
    full = jax.jit(
        lambda p, x, kc, vc, bt, dec, lam: sambay._pool_block_tok(
            p, x, kc, vc, bt, dec, lam, 1e-5, False),
        donate_argnums=(2, 3)).lower(block_w, x, pool, pool,
                                     sds((slots, 256), jnp.int32), dec,
                                     lam0).compile()
    for compiled in (win, full):
        assert len(_kernel_calls(compiled, "paged_attention_decode")) == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("entry", ["decode", "chunk"])
def test_glm_dsa_expert_block_compiles_at_published_widths(chip, monkeypatch,
                                                           entry):
    """One expert block of ``models/glm_dsa.py`` at the cell
    ``glm5-longdoc-sessions``'s shapes (hidden 6144, 64 heads over one
    latent row of 512 + 64, 32 index heads of 128, 2,048 rows kept, 16 of
    256 experts held, 16 slots of 32,768 rows over 16,385 pages of 16): a
    decode step (every held row scored, rows gathered by token, absorbed
    attention, the grouped product over the experts touched: the Pallas
    kernel, its blocks of ``f`` under the VMEM the call states) and a chunk
    of 512 (held rows read 2,048 at a time). Both pools keep their place
    (donated, aliased), and what a block needs beside them stays well
    under a gigabyte."""
    import paddle_tpu.ops.pallas as pallas_tier
    from paddle_tpu.models import glm_dsa

    monkeypatch.setattr(pallas_tier, "on_tpu", lambda: True)
    d, ql, heads, experts, f = 6144, 2048, 64, 16, 2048
    slots, block, s_max, pages = 16, 16, 32768, 16385

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    w = {"ln1_g": sds((d,)), "q_a_w": sds((d, ql)), "q_a_g": sds((ql,)),
         "q_b_w": sds((ql, heads * 256)), "kv_a_w": sds((d, 576)),
         "kv_a_g": sds((512,)), "kv_b_w": sds((512, heads * 448)),
         "o_w": sds((heads * 256, d)), "iq_w": sds((ql, 32 * 128)),
         "ik_w": sds((d, 128)), "ik_g": sds((128,)), "ik_b": sds((128,)),
         "iw_w": sds((d, 32)), "ln2_g": sds((d,)),
         "router_w": sds((d, 256)), "router_b": sds((256,)),
         "exp_w1": sds((experts, d, 2 * f)), "exp_w2": sds((experts, f, d)),
         "sh_w1": sds((d, 2 * f)), "sh_w2": sds((f, d))}
    static = dict(eps=1e-5, ieps=1e-6, heads=heads, topk=2048,
                  held=(0, experts), top_k=8, scaling=2.5)
    # the latent row is held whole lanes wide: declared 576 wide, the
    # runtime gives the pool a page-minor layout and every executable
    # copies all of it in and out (PERF.md section 6, PR 36)
    assert glm_dsa.lane_width(576) == 640
    latent, index = sds((pages, 1, block, 640)), sds((pages, 1, block, 128))
    angles = sds((s_max, 32), jnp.float32)
    if entry == "decode":
        compiled = jax.jit(
            lambda p, x, lat, idx, table, dec, cos, sin: glm_dsa._block_tok(
                p, x, lat, idx, table, dec, cos, sin, **static),
            donate_argnums=(2, 3)).lower(
                w, sds((slots, d)), latent, index,
                sds((slots, s_max // block), jnp.int32),
                sds((slots,), jnp.int32), angles, angles).compile()
    else:
        compiled = jax.jit(
            lambda p, x, lat, idx, table, dec, real, cos, sin:
            glm_dsa._block_chunk(p, x, lat, idx, table, dec, real, cos, sin,
                                 kb=2048, **static),
            donate_argnums=(2, 3)).lower(
                w, sds((512, d)), latent, index,
                sds((s_max // block,), jnp.int32), sds((), jnp.int32),
                sds((), jnp.int32), angles, angles).compile()
    memory = compiled.memory_analysis()
    pools = pages * block * (640 + 128) * 2
    assert len(_kernel_calls(compiled, "grouped_experts")) == 1
    assert memory.alias_size_in_bytes >= pools
    assert memory.temp_size_in_bytes < 512 << 20
    text = compiled.as_text()
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and "bf16[16385,1,16," in ln]
    if entry == "chunk":
        # the thresholds' 32 passes count 512 x 32,768 bits whole, out of
        # VMEM: 64 MiB that ``dsa_select.select_block`` leaves one block
        # because they stay there from the first pass to the last
        reads = _loop_reads(compiled, "index_topk", f"512,{s_max}")
        assert reads and all("S(1)" in own for _, own, _ in reads)


@pytest.mark.parametrize("kind", ["window", "full"])
@pytest.mark.parametrize("entry", ["decode", "chunk"])
def test_mellum_blocks_compile_at_published_widths(chip, monkeypatch, entry,
                                                   kind):
    """One block of ``models/mellum.py`` of each kind at the cell
    ``mellum2-ide-mixed``'s shapes (hidden 2304, 32/4 heads of 128, window
    1,024, all 64 experts of width 896, 16 slots; the full group 24,577
    pages of 16 rows over tables of 2,048, the window group 6,145 pages
    behind rings of 99): a decode step (the row written by the page, PR
    26's kernel over the table or, with a first row, over the 65 pages the
    window lies in, the grouped product over the experts touched as one
    Pallas kernel) and a chunk of 512 (a full layer reads held rows 1,024
    at a time, a window layer the 97 pages before and under it). The pools
    keep their place and a block needs well under a gigabyte beside
    them."""
    import paddle_tpu.ops.pallas as pallas_tier
    from paddle_tpu.models import mellum

    monkeypatch.setattr(pallas_tier, "on_tpu", lambda: True)
    d, heads, kv, hd, experts, f = 2304, 32, 4, 128, 64, 896
    slots, block, s_max = 16, 16, 32768
    window = 1024 if kind == "window" else 0
    pages, table = (6145, 99) if window else (24577, s_max // block)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    w = {"ln1_g": sds((d,)), "q_w": sds((d, heads * hd)),
         "k_w": sds((d, kv * hd)), "v_w": sds((d, kv * hd)),
         "q_g": sds((hd,)), "k_g": sds((hd,)), "o_w": sds((heads * hd, d)),
         "ln2_g": sds((d,)), "router_w": sds((d, experts)),
         "exp_w1": sds((experts, d, 2 * f)), "exp_w2": sds((experts, f, d))}
    static = dict(eps=1e-6, heads=heads, kv_heads=kv, window=window, top_k=8,
                  norm_topk=True)
    pool = sds((pages, kv, block, hd))
    angles = sds((s_max, hd // 2), jnp.float32)
    if entry == "decode":
        compiled = jax.jit(
            lambda p, x, kc, vc, tab, dec, cos, sin: mellum._block_tok(
                p, x, kc, vc, tab, dec, cos, sin, **static),
            donate_argnums=(2, 3)).lower(
                w, sds((slots, d)), pool, pool,
                sds((slots, table), jnp.int32), sds((slots,), jnp.int32),
                angles, angles).compile()
        assert len(_kernel_calls(compiled, "paged_attention_decode")) == 1
    else:
        compiled = jax.jit(
            lambda p, x, kc, vc, tab, dec, real, cos, sin:
            mellum._block_chunk(p, x, kc, vc, tab, dec, real, cos, sin,
                                kb=1024, **static),
            donate_argnums=(2, 3)).lower(
                w, sds((512, d)), pool, pool, sds((table,), jnp.int32),
                sds((), jnp.int32), sds((), jnp.int32), angles,
                angles).compile()
    assert len(_kernel_calls(compiled, "grouped_experts")) == 1
    assert not [ln for ln in compiled.as_text().splitlines()
                if " while(" in ln and "experts_routed" in ln]   # no walk
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * pages * kv * block * hd * 2
    assert memory.temp_size_in_bytes < 768 << 20
    text = compiled.as_text()
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f"bf16[{pages},4,16,128]" in ln]


@pytest.mark.parametrize("entry", ["decode", "chunk"])
def test_keye_block_compiles_at_published_widths(chip, monkeypatch, entry):
    """One block of ``models/keye.py`` at the cell ``keye2-longctx-
    sessions``' shapes (hidden 2048, 32/4 heads of 128, an indexer of 16 x
    64 that keeps 2,048 rows, all 128 experts of width 768, 8 slots; K, V
    and index-key pools of 12,289 pages of 16 rows over tables of 4,096): a
    decode step (rows written by the page; the index keys a slot holds
    scored, 2,048 kept and their K and V gathered by row, whatever a slot
    holds: no second attention path is compiled beside it; the grouped
    product as one Pallas kernel) and a chunk of 512 (held rows scored and read 1,024 at a
    time). The three pools keep their place (no copy of a whole pool) and a
    block needs well under 1.5 GB beside them."""
    import paddle_tpu.ops.pallas as pallas_tier
    from paddle_tpu.models import keye

    monkeypatch.setattr(pallas_tier, "on_tpu", lambda: True)
    d, heads, kv, hd, experts, f = 2048, 32, 4, 128, 128, 768
    n_idx, di, slots, block, s_max, pages = 16, 64, 8, 16, 65536, 12289
    table = s_max // block

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    w = {"ln1_g": sds((d,)), "q_w": sds((d, heads * hd)),
         "k_w": sds((d, kv * hd)), "v_w": sds((d, kv * hd)),
         "q_g": sds((hd,)), "k_g": sds((hd,)), "o_w": sds((heads * hd, d)),
         "iq_w": sds((d, n_idx * di)), "ik_w": sds((d, di)),
         "ik_g": sds((di,)), "ik_b": sds((di,)), "iw_w": sds((d, n_idx)),
         "ln2_g": sds((d,)), "router_w": sds((d, experts)),
         "exp_w1": sds((experts, d, 2 * f)), "exp_w2": sds((experts, f, d))}
    static = dict(eps=1e-6, ieps=1e-6, heads=heads, kv_heads=kv, topk=2048,
                  top_k=8, norm_topk=True)
    pool = sds((pages, kv, block, hd))
    keys = sds((pages, 1, block, keye.key_width(di)))

    def angles(n):
        return tuple(sds((n, w_), jnp.float32)
                     for w_ in (hd // 2, hd // 2, 16, 16))

    if entry == "decode":
        compiled = jax.jit(
            lambda p, x, kc, vc, ic, tab, dec, ang: keye._block_tok(
                p, x, kc, vc, ic, tab, dec, ang, **static),
            donate_argnums=(2, 3, 4)).lower(
                w, sds((slots, d)), pool, pool, keys,
                sds((slots, table), jnp.int32), sds((slots,), jnp.int32),
                angles(slots)).compile()
        assert not _kernel_calls(compiled, "paged_attention_decode")
    else:
        compiled = jax.jit(
            lambda p, x, kc, vc, ic, tab, dec, real, ang:
            keye._block_chunk(p, x, kc, vc, ic, tab, dec, real, ang,
                              kb=1024, **static),
            donate_argnums=(2, 3, 4)).lower(
                w, sds((512, d)), pool, pool, keys, sds((table,), jnp.int32),
                sds((), jnp.int32), sds((), jnp.int32),
                angles(512)).compile()
    assert len(_kernel_calls(compiled, "grouped_experts")) == 1
    memory = compiled.memory_analysis()
    pools = (2 * kv * hd + keye.key_width(di)) * pages * block * 2
    assert memory.alias_size_in_bytes >= pools
    assert memory.temp_size_in_bytes < 1536 << 20
    text = compiled.as_text()
    assert not [ln for ln in text.splitlines() if " copy(" in ln and (
        f"bf16[{pages},4,16,128]" in ln or f"bf16[{pages},1,16,128]" in ln)]
    if entry == "chunk":
        # the thresholds' passes read the 128 MiB of bits out of HBM, and
        # only a block of the rows held at a time
        reads = _loop_reads(compiled, "index_topk", f"512,{s_max}")
        assert reads and not [fusion for fusion, _, whole in reads if whole]


@pytest.mark.parametrize("kind", ["conv", "attn"])
@pytest.mark.parametrize("entry", ["decode", "chunk"])
def test_lfm2_blocks_compile_at_published_widths(chip, monkeypatch, entry,
                                                 kind):
    """One block of ``models/lfm2.py`` of each kind at the cell ``lfm2-
    agent-sessions``' shapes (hidden 2048, 32/8 heads of 64, 3 taps, all 64
    experts of width 1536, 32 slots; K and V pools of 24,577 pages of 16
    rows that hold two key heads a lane row, tables of 2,048): a decode
    step (an attention layer writes its row by the page and reads the
    packed pages through PR 26's kernel, query heads laid into their key
    head's half of the lanes; a convolution layer reads and writes a
    slot's two rows; the grouped product as one Pallas kernel) and a chunk
    of 512 (held rows read 1,024 at a time; the convolution over the two
    carried rows and the chunk, four boundary states cut out of it). The
    pools keep their place and a block needs well under a gigabyte beside
    them; the whole step's state hand-over (slots and the store of 3,073
    snapshots) keeps the store's place too."""
    import paddle_tpu.ops.pallas as pallas_tier
    from paddle_tpu.models import lfm2

    monkeypatch.setattr(pallas_tier, "on_tpu", lambda: True)
    d, heads, kv, hd, experts, f = 2048, 32, 8, 64, 64, 1536
    slots, block, s_max, pages, snaps = 32, 16, 32768, 24577, 3073
    table = s_max // block

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    w = {"ln1_g": sds((d,)), "ln2_g": sds((d,)),
         "router_w": sds((d, experts)), "router_b": sds((experts,)),
         "exp_w1": sds((experts, d, 2 * f)), "exp_w2": sds((experts, f, d))}
    ffn = dict(eps=1e-5, top_k=4, norm_topk=True, scaling=1.0)
    if kind == "conv":
        w.update(in_w=sds((d, 3 * d)), conv_w=sds((d, 3)), out_w=sds((d, d)))
        if entry == "decode":
            compiled = jax.jit(
                lambda p, x, st, dec: lfm2._conv_tok(p, x, st, dec, **ffn)
            ).lower(w, sds((slots, d)), sds((slots, 2, d)),
                    sds((slots,), jnp.int32)).compile()
        else:
            compiled = jax.jit(
                lambda p, x, st, real, at: lfm2._conv_chunk(
                    p, x, st, real, at, **ffn)
            ).lower(w, sds((512, d)), sds((2, d)), sds((), jnp.int32),
                    sds((4,), jnp.int32)).compile()
    else:
        w.update(q_w=sds((d, heads * hd)), k_w=sds((d, kv * hd)),
                 v_w=sds((d, kv * hd)), o_w=sds((heads * hd, d)),
                 q_g=sds((hd,)), k_g=sds((hd,)))
        static = dict(ffn, heads=heads, kv_heads=kv)
        pool = sds((pages, kv // 2, block, 2 * hd))
        angles = sds((s_max, hd // 2), jnp.float32)
        if entry == "decode":
            compiled = jax.jit(
                lambda p, x, kc, vc, tab, dec, cos, sin: lfm2._attn_tok(
                    p, x, kc, vc, tab, dec, cos, sin, **static),
                donate_argnums=(2, 3)).lower(
                    w, sds((slots, d)), pool, pool,
                    sds((slots, table), jnp.int32),
                    sds((slots,), jnp.int32), angles, angles).compile()
            assert len(_kernel_calls(compiled,
                                     "paged_attention_decode")) == 1
        else:
            compiled = jax.jit(
                lambda p, x, kc, vc, tab, dec, real, cos, sin:
                lfm2._attn_chunk(p, x, kc, vc, tab, dec, real, cos, sin,
                                 kb=1024, **static),
                donate_argnums=(2, 3)).lower(
                    w, sds((512, d)), pool, pool, sds((table,), jnp.int32),
                    sds((), jnp.int32), sds((), jnp.int32), angles,
                    angles).compile()
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= 2 * pages * kv * block * hd * 2
        assert not [ln for ln in compiled.as_text().splitlines()
                    if " copy(" in ln and f"bf16[{pages},4,16,128]" in ln]
    assert len(_kernel_calls(compiled, "grouped_experts")) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 768 << 20
    if kind == "conv" and entry == "chunk":
        # where a chunk's states go: four snapshots and the slot, in place
        store = sds((snaps, 7, 2, d))
        moved = jax.jit(lfm2._state_after_chunk, donate_argnums=(0, 1)).lower(
            sds((slots, 7, 2, d)), store, sds((), jnp.int32),
            sds((4,), jnp.int32), sds((7, 2, d)),
            sds((7, 4, 2, d))).compile()
        assert moved.memory_analysis().alias_size_in_bytes \
            >= snaps * 7 * 2 * d * 2
        assert not [ln for ln in moved.as_text().splitlines()
                    if " copy(" in ln and f"bf16[{snaps},7,2,{d}]" in ln]
