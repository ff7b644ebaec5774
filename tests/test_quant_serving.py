"""Weight-only int8 serving through the full decode stack.

Reference surface: nn/quant/quantized_linear.py weight_only_linear powering
the serving predictor's int8 path. The machinery invariant under test:
every serving route (dense KV, paged KV, continuous batchers, compiled
steps) must be TOKEN-EXACT against the quantized model's own solo
generate — quantization changes the logits, never the serving algebra.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (ContinuousBatcher,
                                          PagedContinuousBatcher)
from paddle_tpu.models.gpt import GPT2Config, GPT2ForCausalLM
from paddle_tpu.nn.quant import quantize_linear_layers


def _quantized_gpt2(algo="weight_only_int8"):
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    n = quantize_linear_layers(m, algo)
    assert n > 0
    return m


def test_int8_logits_close_to_fp():
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (1, 6)).astype(np.int64))
    with paddle.no_grad():
        fp = m(ids).numpy()
    quantize_linear_layers(m)
    with paddle.no_grad():
        q8 = m(ids).numpy()
    rel = np.abs(q8 - fp).max() / (np.abs(fp).max() + 1e-9)
    assert rel < 0.05, rel


@pytest.mark.smoke
def test_quantized_paged_matches_quantized_dense():
    m = _quantized_gpt2()
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(0, 128, (2, 6)).astype(np.int64))
    with paddle.no_grad():
        dense = m.generate(ids, max_new_tokens=7).numpy()
        paged = m.generate_paged(ids, max_new_tokens=7, block_size=8).numpy()
    np.testing.assert_array_equal(dense, paged)


def test_quantized_batchers_token_exact():
    m = _quantized_gpt2()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 8)]

    def solo(p, n):
        ids = paddle.to_tensor(np.asarray(p, np.int64)[None])
        with paddle.no_grad():
            return m.generate(ids, max_new_tokens=n).numpy()[0]

    with paddle.no_grad():
        dense_b = ContinuousBatcher(m, max_batch=2, s_max=32, compile=False)
        rids = [dense_b.submit(p, 5) for p in prompts]
        outs = dense_b.run_until_done()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid], solo(p, 5))

    paged_b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                                     policy="ondemand", compile=False)
    rids = [paged_b.submit(p, 5) for p in prompts]
    outs = paged_b.run_until_done()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid], solo(p, 5))


def test_quantized_compiled_decode_matches_eager():
    from paddle_tpu import jit
    m = _quantized_gpt2()
    ids = paddle.to_tensor(
        np.random.RandomState(3).randint(0, 128, (2, 6)).astype(np.int64))
    with paddle.no_grad():
        ref = m.generate_paged(ids, max_new_tokens=6, block_size=8).numpy()
        step = jit.to_static(m.paged_decode_step)
        out = m.generate_paged(ids, max_new_tokens=6, block_size=8,
                               decode_fn=step).numpy()
    np.testing.assert_array_equal(ref, out)


def test_int4_serving_runs():
    m = _quantized_gpt2("weight_only_int4")
    ids = paddle.to_tensor(
        np.random.RandomState(4).randint(0, 128, (1, 5)).astype(np.int64))
    with paddle.no_grad():
        dense = m.generate(ids, max_new_tokens=5).numpy()
        paged = m.generate_paged(ids, max_new_tokens=5, block_size=8).numpy()
    np.testing.assert_array_equal(dense, paged)


# -- cache-KV int8 (reference block_multihead_attention static quant mode) --

def _llama_eval():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny_config(vocab_size=128))
    m.eval()
    return m


def test_cachekv_int8_close_to_fp_cache():
    """Static per-head int8 cache: paged logits track the fp-cache paged
    logits; pools actually hold int8."""
    m = _llama_eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 128, (2, 8)).astype(np.int64))
    with paddle.no_grad():
        fp_logits, _ = m.paged_prefill(ids, block_size=8)
        scales = m.calibrate_cachekv_int8(ids)
        assert len(scales) == m.config.num_hidden_layers
        q_logits, q_state = m.paged_prefill(ids, block_size=8)
    assert str(q_state["layers"][0][0].dtype) in ("paddle.int8", "int8")
    rel = (np.abs(q_logits.numpy() - fp_logits.numpy()).max()
           / (np.abs(fp_logits.numpy()).max() + 1e-9))
    assert rel < 0.05, rel
    m.calibrate_cachekv_int8(None)      # disable restores fp pools
    with paddle.no_grad():
        _, state2 = m.paged_prefill(ids, block_size=8)
    assert "int8" not in str(state2["layers"][0][0].dtype)


@pytest.mark.parametrize("options", [
    dict(compile=False), dict(prefill_chunk=8, compile=True)],
    ids=["eager", "chunked_compiled"])
def test_cachekv_int8_serving_algebra_exact(options):
    """Quantized-cache generate_paged vs the quantized-cache batcher must
    be token-exact (the int8 cache changes logits, never the scheduler):
    static calibrated pages, whole-prompt and chunked admission, slots
    reused."""
    from test_paged_batching import _retry_load_flake
    m = _llama_eval()
    rng = np.random.RandomState(1)
    calib = paddle.to_tensor(rng.randint(0, 128, (2, 10)).astype(np.int64))
    with paddle.no_grad():
        m.calibrate_cachekv_int8(calib)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 11, 8)]

    def solo(p, n):
        ids = paddle.to_tensor(np.asarray(p, np.int64)[None])
        with paddle.no_grad():
            return m.generate_paged(ids, max_new_tokens=n,
                                    block_size=8).numpy()[0]

    def body():
        b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                                   **options)
        assert str(b._state["layers"][0][0].dtype).endswith("int8")
        rids = [b.submit(p, 5) for p in prompts]
        outs = b.run_until_done()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid], solo(p, 5))
        assert b.audit_pages() == 0

    _retry_load_flake(body, attempts=3 if options["compile"] else 1)


def test_cachekv_int8_mha_functional():
    """block_multihead_attention's static cachekv-int8 mode: int8 pools +
    per-head scales reproduce the fp-cache output within quant noise."""
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.functional.decode_attention import \
        block_multihead_attention
    rng = np.random.RandomState(2)
    b, h, d, bs, bps, s = 2, 4, 16, 8, 2, 6
    n_blocks = b * bps
    qkv = paddle.to_tensor(rng.randn(b * s, 3 * h * d).astype(np.float32))
    bt = paddle.to_tensor(
        np.arange(n_blocks, dtype=np.int32).reshape(b, bps))
    enc = paddle.to_tensor(np.full((b,), s, np.int32))
    dec = paddle.to_tensor(np.zeros((b,), np.int32))
    cu = paddle.to_tensor(np.arange(b + 1, dtype=np.int32) * s)

    kc = paddle.zeros([n_blocks, h, bs, d], dtype="float32")
    vc = paddle.zeros([n_blocks, h, bs, d], dtype="float32")
    fp_out, _, fkc, fvc = block_multihead_attention(
        qkv, kc, vc, enc, dec, enc, None, None, cu, cu, bt, block_size=bs)

    amax_k = np.abs(np.asarray(fkc._data)).max(axis=(0, 2, 3)) + 1e-6
    amax_v = np.abs(np.asarray(fvc._data)).max(axis=(0, 2, 3)) + 1e-6
    kq = paddle.to_tensor((127.0 / amax_k).astype(np.float32))
    vq = paddle.to_tensor((127.0 / amax_v).astype(np.float32))
    kdq = paddle.to_tensor((amax_k / 127.0).astype(np.float32))
    vdq = paddle.to_tensor((amax_v / 127.0).astype(np.float32))
    kc8 = paddle.zeros([n_blocks, h, bs, d], dtype="int8")
    vc8 = paddle.zeros([n_blocks, h, bs, d], dtype="int8")
    q_out, _, qkc, qvc = block_multihead_attention(
        qkv, kc8, vc8, enc, dec, enc, None, None, cu, cu, bt,
        cache_k_quant_scales=kq, cache_v_quant_scales=vq,
        cache_k_dequant_scales=kdq, cache_v_dequant_scales=vdq,
        block_size=bs)
    assert str(qkc.dtype).endswith("int8")
    rel = (np.abs(q_out.numpy() - fp_out.numpy()).max()
           / (np.abs(fp_out.numpy()).max() + 1e-9))
    assert rel < 0.05, rel


def test_cachekv_scale_contract_errors():
    """Partial scale sets and int8-pool-without-scales are loud errors,
    never silent truncation (review finding)."""
    from paddle_tpu.incubate.nn.functional.decode_attention import \
        block_gqa_attention
    rng = np.random.RandomState(3)
    b, h, kvh, d, bs, bps, s = 1, 4, 2, 8, 4, 2, 3
    q = paddle.to_tensor(rng.randn(b * s, h, d).astype(np.float32))
    k = paddle.to_tensor(rng.randn(b * s, kvh, d).astype(np.float32))
    v = paddle.to_tensor(rng.randn(b * s, kvh, d).astype(np.float32))
    bt = paddle.to_tensor(np.arange(b * bps, dtype=np.int32).reshape(b, bps))
    enc = paddle.to_tensor(np.full((b,), s, np.int32))
    dec = paddle.to_tensor(np.zeros((b,), np.int32))
    cu = paddle.to_tensor(np.arange(b + 1, dtype=np.int32) * s)
    sc = paddle.to_tensor(np.ones((kvh,), np.float32))
    kc8 = paddle.zeros([b * bps, kvh, bs, d], dtype="int8")
    vc8 = paddle.zeros([b * bps, kvh, bs, d], dtype="int8")
    kcf = paddle.zeros([b * bps, kvh, bs, d], dtype="float32")
    vcf = paddle.zeros([b * bps, kvh, bs, d], dtype="float32")
    # int8 pool, no scales
    with pytest.raises(ValueError, match="int8 cache pool"):
        block_gqa_attention(q, k, v, kc8, vc8, enc, dec, enc, cu, bt,
                            block_size=bs)
    # partial scales
    with pytest.raises(ValueError, match="all four"):
        block_gqa_attention(q, k, v, kc8, vc8, enc, dec, enc, cu, bt,
                            block_size=bs, cache_k_dequant_scales=sc)
    # scales against an fp pool
    with pytest.raises(ValueError, match="allocate int8"):
        block_gqa_attention(q, k, v, kcf, vcf, enc, dec, enc, cu, bt,
                            block_size=bs, cache_k_quant_scales=sc,
                            cache_v_quant_scales=sc,
                            cache_k_dequant_scales=sc,
                            cache_v_dequant_scales=sc)


def test_cachekv_int8_gpt2_paged():
    """The MHA family gets the same cache-int8 wiring: calibrated GPT-2
    paged decode runs on int8 pools and the serving algebra stays exact."""
    paddle.seed(0)
    cfg = GPT2Config(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     dropout=0.0)
    m = GPT2ForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(5)
    calib = paddle.to_tensor(rng.randint(0, 128, (2, 10)).astype(np.int64))
    ids = paddle.to_tensor(rng.randint(0, 128, (2, 6)).astype(np.int64))
    with paddle.no_grad():
        fp = m.generate_paged(ids, max_new_tokens=6, block_size=8).numpy()
        m.calibrate_cachekv_int8(calib)
        _, state = m.paged_prefill(ids, block_size=8)
        assert str(state["layers"][0][0].dtype).endswith("int8")
        q8 = m.generate_paged(ids, max_new_tokens=6, block_size=8).numpy()
    # int8 cache tracks fp decode on a tiny model: compare only the
    # GENERATED suffix (the echoed prompt always matches)
    assert (fp[:, 6:] == q8[:, 6:]).mean() > 0.8
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               compile=False)
    rid = b.submit(np.asarray(ids.numpy()[0]), 5)
    outs = b.run_until_done()
    with paddle.no_grad():
        solo = m.generate_paged(paddle.to_tensor(ids.numpy()[:1]),
                                max_new_tokens=5, block_size=8).numpy()[0]
    np.testing.assert_array_equal(outs[rid], solo)
    m.calibrate_cachekv_int8(None)
