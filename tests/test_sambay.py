"""SambaY (Phi-4-mini-flash-reasoning's architecture) against the repo's plain
reference for it, ``chipbench/families/sambay.py``: the whole forward, and
the served path (``PagedContinuousBatcher``: chunked prefill, decode past
the window, slots reused, a preemption) at small sizes on seeded weights.

Tolerances, and why:

* float32 model against the float32 reference: 5e-4 on logits of a few
  units. Both compute the same sums in another order (chunks, a ring in
  ring order, grouped heads); nothing else differs.
* bfloat16 model against the float32 reference: 0.12. Weights are the same
  bfloat16 values on both sides; the program rounds every activation to
  bfloat16 (2 ** -9 relative), which over 8 blocks reads 0.02 to 0.06 on
  these logits. The same reference computed in float8 (e4m3, the nearest
  precision below) lies 0.3 or more from the float32 one, so a served path
  in a precision below bfloat16 fails this tolerance; the tests assert that
  the control does.
"""
import functools
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:       # chipbench lies beside tests/, at the root
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from chipbench import families, reference as R, weights as W  # noqa: E402
from paddle_tpu.inference.serving import PagedContinuousBatcher  # noqa: E402
from paddle_tpu.models import (SambaYConfig, SambaYForCausalLM,  # noqa: E402
                               sambay_tiny_config)
from paddle_tpu.models import sambay  # noqa: E402

CFG = dict(family="sambay", hidden_size=64, intermediate_size=96,
           num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
           vocab_size=128, sliding_window=8, layer_norm_eps=1e-5,
           tie_word_embeddings=True, initializer_range=0.1,
           max_position_embeddings=512)
FAMILY = families.of(CFG)
F32_TOL, BF16_TOL = 5e-4, 0.12


@functools.lru_cache(maxsize=None)
def build(dtype="float32", seed=3):
    """One model a dtype for the whole file: serving changes nothing of
    it."""
    paddle.seed(0)
    model = FAMILY.program_model(CFG, dtype=dtype)
    model.eval()
    W.install(model, CFG, seed, scanned=False)
    return model


class Tap:
    """Keeps, for every request, the logits row each of its tokens was
    picked from: admission picks from [1, V], a decode step from [B, V].
    Logits reach the host only where the batcher samples (a greedy one
    fetches the ids its executables chose), so ``SERVER`` samples from the
    one best row: the tokens greedy would serve."""

    def __init__(self, batcher):
        self.rows, self.last = {}, None
        pick, on_token = batcher._pick, batcher._tele.on_token

        def tapped_pick(logits):
            self.last = np.asarray(logits, np.float32)
            return pick(logits)

        def tapped_token(req):
            row = self.last[0] if len(self.last) == 1 \
                else self.last[req.slot]
            self.rows.setdefault(req.rid, []).append(row)
            return on_token(req)

        batcher._pick, batcher._tele.on_token = tapped_pick, tapped_token


def serve(model, prompts, news, **server):
    batcher = PagedContinuousBatcher(model, **server)
    tap = Tap(batcher)
    rids = [batcher.submit(p, n) for p, n in zip(prompts, news)]
    with paddle.no_grad():
        out = batcher.run_until_done()
    assert batcher.audit_pages() == 0
    stats = batcher.stats()
    batcher.close()
    return [out[r] for r in rids], [np.stack(tap.rows[r]) for r in rids], \
        stats


def reference_rows(seqs, prompts, seed=3, precision="f32"):
    width = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), width), np.int64)
    rows = []
    for i, (seq, prompt) in enumerate(zip(seqs, prompts)):
        ids[i, :len(seq)] = seq
        rows.append(list(range(len(prompt) - 1, len(seq) - 1)))
    return R.served_logits(CFG, seed, ids, rows, precision=precision)


def prompts_of(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG["vocab_size"], n) for n in lengths]


# -- the whole forward --------------------------------------------------------

def test_forward_matches_the_reference_logits():
    model = build()
    ids = np.random.default_rng(0).integers(0, 128, (2, 24))
    ref = R.served_logits(CFG, 3, ids, [list(range(24))] * 2)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids))._data)
    np.testing.assert_allclose(got, np.stack(ref), atol=F32_TOL, rtol=0)


def test_forward_gives_a_loss_and_gradients():
    model = build()
    ids = np.random.default_rng(0).integers(0, 128, (2, 12))
    _, loss = model(paddle.to_tensor(ids[:, :-1]),
                    labels=paddle.to_tensor(ids[:, 1:]))
    loss.backward()
    assert np.isfinite(float(loss))
    for name, p in model.named_parameters():
        assert p.grad is not None, name
        assert float(jnp.abs(p.grad._data).max()) > 0, name


def test_parameters_are_created_in_the_requested_dtype():
    paddle.seed(0)
    model = SambaYForCausalLM(sambay_tiny_config(dtype="bfloat16"))
    assert {str(p.dtype) for p in model.parameters()} == {"bfloat16"}
    kinds = [layer.kind for layer in model.model.layers]
    assert kinds == ["ssm", "window", "ssm", "window", "ssm_mem", "full",
                     "gmu", "cross"]


def test_published_sizes_count_3_85_billion_parameters():
    cfg = SambaYConfig()
    kinds = [cfg.mixer_kind(i) for i in range(32)]
    assert [kinds.count(k) for k in ("ssm", "ssm_mem", "window", "full",
                                     "gmu", "cross")] == [8, 1, 8, 1, 7, 7]
    assert cfg.d_inner == 5120 and cfg.dt_rank == 160 and cfg.head_dim == 64
    published = dict(CFG, hidden_size=2560, intermediate_size=10240,
                     num_hidden_layers=32, num_attention_heads=40,
                     num_key_value_heads=20, vocab_size=200064,
                     sliding_window=512)
    total = sum(int(np.prod(s)) for i in range(32)
                for s in FAMILY.layer_shapes(published, i).values()) \
        + sum(int(np.prod(s))
              for s in FAMILY.top_shapes(published).values())
    assert round(total / 1e9, 2) == 3.85


# -- the served path ----------------------------------------------------------

SERVER = dict(max_batch=2, s_max=64, block_size=4, n_pages=32,
              prefill_chunk=8, compile=True, do_sample=True, top_k=1)
SCENARIOS = {
    # prompts of 19 and 5 (three chunks and one), answers that run past
    # the window of 8 so that every ring wraps, two more requests than
    # slots so that both slots are used again
    "chunks_wrap_reuse": (dict(SERVER), (19, 5, 11, 9), (14, 20, 6, 9)),
    # the whole prompt in one eager call, no chunk executable
    "unchunked_eager": (dict(SERVER, prefill_chunk=None, compile=False),
                        (13, 6, 21), (12, 5, 9)),
    # 14 pages for two sequences that want 18: the later one is preempted
    # and prefilled again from prompt + generated; chunks of 12 rows, wider
    # than the window's ring of 8
    "ondemand_preemption": (dict(SERVER, n_pages=14, policy="ondemand",
                                 prefill_chunk=12), (17, 15), (20, 18)),
}


@functools.lru_cache(maxsize=None)
def served(name):
    """(prompts, sequences, logits, stats, what the state series counted)
    of one scenario, served once for the tests that read it."""
    from paddle_tpu.observability.metrics import get_registry
    server, lengths, news = SCENARIOS[name]
    prompts = prompts_of(lengths)
    PagedContinuousBatcher(build(), **server).close()   # makes the series
    names = ("serving.state_resets", "serving.window_rows_overwritten",
             "serving.window_rows_read")
    before = {n: get_registry().get(n).value for n in names}
    seqs, logits, stats = serve(build(), prompts, news, **server)
    counted = {n: get_registry().get(n).value - before[n] for n in names}
    return prompts, seqs, logits, stats, counted


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_served_logits_match_the_reference(name):
    _, lengths, news = SCENARIOS[name]
    prompts, seqs, logits, stats, _ = served(name)
    if name == "ondemand_preemption":
        assert stats["preemptions"] >= 1
    ref = reference_rows(seqs, prompts)
    for got, want, seq, prompt, n in zip(logits, ref, seqs, prompts, news):
        assert len(seq) == len(prompt) + n
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def test_bfloat16_serving_is_inside_a_tolerance_float8_is_outside():
    server, lengths, news = SCENARIOS["chunks_wrap_reuse"]
    prompts = prompts_of(lengths)
    model = build("bfloat16")
    assert {str(p.dtype) for p in model.parameters()} == {"bfloat16"}
    seqs, logits, _ = serve(model, prompts, news, **server)
    ref = reference_rows(seqs, prompts)
    low = reference_rows(seqs, prompts, precision="fp8")
    worst = max(np.abs(g - w).max() for g, w in zip(logits, ref))
    control = max(np.abs(lo - w).max() for lo, w in zip(low, ref))
    assert worst < BF16_TOL < control, (worst, control)


def test_a_reused_slot_starts_from_zero_state():
    """The third request lands in a slot whose rings and recurrent state
    an earlier one left full; its logits are those of a fresh sequence:
    served alone they are the same."""
    server, _, news = SCENARIOS["chunks_wrap_reuse"]
    prompts, _, together, _, _ = served("chunks_wrap_reuse")
    _, alone, _ = serve(build(), prompts[2:3], news[2:3], **server)
    np.testing.assert_allclose(together[2], alone[0], atol=F32_TOL, rtol=0)


def test_kernel_route_matches_the_gather_route(monkeypatch):
    """What the chip runs (the Pallas paged kernel over key groups of 2h
    with zero-padded queries, here interpreted) against what the CPU runs
    (the gather), through the batcher's compiled decode step."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    server, _, news = SCENARIOS["chunks_wrap_reuse"]
    prompts, seqs, want, stats, _ = served("chunks_wrap_reuse")
    assert stats["decode_attention_path"] == \
        "window=gather,full=gather,cross=gather"
    monkeypatch.setattr(sambay, "decode_route", lambda pool: "kernel")
    monkeypatch.setattr(pa, "paged_attention_decode", functools.partial(
        pa.paged_attention_decode, interpret=True))
    # new jitted blocks: the old ones were traced over the gather route
    monkeypatch.setattr(sambay, "_BLOCKS", {
        name: sambay._jitted(fn.__wrapped__)
        for name, fn in sambay._BLOCKS.items()})
    got_seqs, got, stats = serve(build(), prompts, news, **server)
    assert stats["decode_attention_path"] == \
        "window=kernel,full=kernel,cross=kernel"
    for a, b, s, t in zip(got, want, got_seqs, seqs):
        np.testing.assert_array_equal(s, t)
        np.testing.assert_allclose(a, b, atol=F32_TOL, rtol=0)


# -- the scan -----------------------------------------------------------------

def _ssm_weights(seed=5):
    layer = {k: np.asarray(v, np.float32)
             for k, v in W.make_layer(CFG, seed, 0).items()}
    return {k: jnp.asarray(v) for k, v in layer.items()}


@pytest.mark.parametrize("cut,pad", [(7, 0), (10, 5)])
def test_chunked_scan_from_a_carried_state_matches_token_by_token(cut, pad):
    """Two chunks, the second from the state the first left (its last
    ``pad`` rows padding that must leave the state alone), against the
    reference's scan over the whole sequence from zero."""
    w = _ssm_weights()
    sizes = FAMILY.sizes(CFG)
    rows = 20
    u = jnp.asarray(np.random.default_rng(2).standard_normal(
        (rows, sizes["d"])), jnp.float32)
    es = functools.partial(R.einsum, "f32")
    want, want_y = FAMILY.state_space(es, u, w, sizes)

    h = jnp.zeros((sizes["di"], sizes["n"]), jnp.float32)
    conv = jnp.zeros((sizes["conv"] - 1, sizes["di"]), jnp.float32)
    out1, y1, h, conv = sambay._ssm_seq(w, u[:cut], h, conv, cut)
    tail = jnp.concatenate([u[cut:], jnp.ones((pad, sizes["d"]))])
    out2, y2, h2, conv2 = sambay._ssm_seq(w, tail, h, conv, rows - cut)
    np.testing.assert_allclose(
        np.concatenate([out1, out2[:rows - cut]]), want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(
        np.concatenate([y1, y2[:rows - cut]]), want_y, atol=2e-5, rtol=0)
    # and one token more, by the decode step's update, from that state
    nxt = jnp.asarray(np.random.default_rng(3).standard_normal(
        (1, sizes["d"])), jnp.float32)
    full, _ = FAMILY.state_space(es, jnp.concatenate([u, nxt]), w, sizes)
    step, _, _, _ = sambay._ssm_tok(w, nxt, h2[None], conv2[None])
    np.testing.assert_allclose(step[0], full[-1], atol=2e-5, rtol=0)


# -- the batcher's side -------------------------------------------------------

REFUSED = {"prefix_cache": True, "kv_quant": "int8",
           "cache_quant": "dynamic_int8", "tier_quant": "int8",
           "draft_model": "a model", "session_store": "/tmp/never-made"}


@pytest.mark.parametrize("option", list(REFUSED))
def test_options_that_assume_pages_of_kv_are_refused_by_name(option):
    with pytest.raises(ValueError, match=f"^{option} is not supported for "
                                         f"SambaYForCausalLM"):
        PagedContinuousBatcher(build(), **dict(
            SERVER, **{option: REFUSED[option]}))


def test_state_series_count_resets_and_ring_rows():
    from paddle_tpu.observability.metrics import get_registry
    server, lengths, news = SCENARIOS["chunks_wrap_reuse"]
    counted = served("chunks_wrap_reuse")[4]
    PagedContinuousBatcher(build(), **server).close()   # sets the gauge
    # per slot: 2 rings of 8 rows x 2 (K, V) x 2 groups x 16 wide floats,
    # 3 states of 128 x 16 float32 and 3 convolution rows of 128
    per_slot = 2 * 8 * 2 * 2 * 16 * 4 + 3 * (128 * 16 * 4 + 3 * 128 * 4)
    assert get_registry().get("serving.recurrent_state_bytes").value \
        == 2 * per_slot
    assert counted["serving.state_resets"] == 4
    # every row past the eighth of a sequence drops one from each of the
    # two rings; the last token of an answer is picked, never written
    rows = [p + n - 1 for p, n in zip(lengths, news)]
    assert counted["serving.window_rows_overwritten"] \
        == 2 * sum(r - 8 for r in rows)
    # a decode step at row dec reads min(dec + 1, 8) rows of a ring
    assert counted["serving.window_rows_read"] \
        == sum(min(dec + 1, 8) for p, n in zip(lengths, news)
               for dec in range(p, p + n - 1))


def test_a_window_that_is_not_whole_pages_is_refused():
    with pytest.raises(ValueError, match="not whole pages"):
        PagedContinuousBatcher(build(), **dict(SERVER, block_size=3,
                                               s_max=63))
