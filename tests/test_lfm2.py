"""``models/lfm2.py`` against its plain reference ``chipbench/families/
lfm2.py`` at the rehearsal size, float32: the whole forward, prefill in
chunks of several widths and decode through the cache, the convolution
state carried across chunks, a request resumed from a state snapshot
against the same request prefilled cold, what becomes of a snapshot that is
reclaimed or whose node is evicted, the router's bias, heads of 64 packed
two a lane row, and the experts' shares. What becomes of snapshots on the
served path is in ``test_lfm2_snapshots.py`` (a file of its own: tier-1 deals
files to workers whole), the snapshot owner itself in
``test_page_groups.py``."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle  # noqa: E402
from chipbench import families, harness as H  # noqa: E402
from chipbench import reference as R, weights as W  # noqa: E402
from paddle_tpu.inference.serving import PagedContinuousBatcher  # noqa: E402
from paddle_tpu.models import (Lfm2Config, Lfm2ForCausalLM,  # noqa: E402
                               lfm2_tiny_config)
from paddle_tpu.models import lfm2 as M  # noqa: E402
from paddle_tpu.models.routed_experts import routed_experts  # noqa: E402
from paddle_tpu.observability.metrics import get_registry  # noqa: E402

CFG = H.load_config("lfm2-24b-a2b-serve-d9", True)     # the rehearsal's
FAMILY = families.of(CFG)
TOL = 1e-4
SEED = 5
ES = functools.partial(R.einsum, "f32")


@functools.lru_cache(maxsize=None)
def build():
    paddle.seed(0)
    model = FAMILY.program_model(CFG, dtype="float32")
    model.eval()
    W.install(model, CFG, SEED, scanned=False)
    return model


class Tap:
    """Keeps, for every request, the logits row each of its tokens was
    picked from (``SERVER`` samples from the one best row, so logits reach
    the host: the tokens greedy would serve)."""

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


SERVER = dict(max_batch=3, s_max=128, block_size=4, n_pages=96,
              prefill_chunk=16, prefix_cache=True, compile=False,
              do_sample=True, top_k=1)


def serve(prompts, news, batcher=None, **server):
    b = batcher or PagedContinuousBatcher(build(), **dict(SERVER, **server))
    tap = Tap(b)
    rids = [b.submit(p, n) for p, n in zip(prompts, news)]
    with paddle.no_grad():
        out = b.run_until_done()
    assert b.audit_pages() == 0
    return [out[r] for r in rids], [np.stack(tap.rows[r]) for r in rids], b


def reference_rows(seqs, prompts):
    width = max(len(s) for s in seqs)
    ids = np.zeros((len(seqs), width), np.int64)
    rows = []
    for i, (seq, prompt) in enumerate(zip(seqs, prompts)):
        ids[i, :len(seq)] = seq
        rows.append(list(range(len(prompt) - 1, len(seq) - 1)))
    return R.served_logits(CFG, SEED, ids, rows)


# -- the whole forward --------------------------------------------------------

def test_forward_matches_the_reference_in_logits():
    ids = np.random.default_rng(0).integers(0, 256, (2, 70))
    ref = R.served_logits(CFG, SEED, ids, [list(range(70))] * 2)
    with paddle.no_grad():
        got = np.asarray(build()(paddle.to_tensor(ids))._data)
    np.testing.assert_allclose(got, np.stack(ref), atol=TOL, rtol=0)


def test_the_tiny_config_is_the_tested_shape_and_forward_gives_a_loss():
    c = lfm2_tiny_config()
    assert c.layer_types == tuple(CFG["layer_types"])
    assert (c.hidden_size, c.head_dim, c.num_experts, c.num_experts_per_tok,
            c.num_dense_layers, c.snapshot_rows, c.state_rows) \
        == (256, 64, 8, 2, 1, 8, 2)
    ids = paddle.to_tensor(np.random.default_rng(2).integers(0, 128, (2, 9)))
    with paddle.no_grad():
        logits, loss = Lfm2ForCausalLM(c)(ids, labels=ids)
    assert logits.shape == [2, 9, 128] and np.isfinite(float(loss))
    # the published pattern: attention at layers 2, 6, ..., 38
    assert [i for i, k in enumerate(Lfm2Config().layer_types)
            if k == M.FULL] == list(range(2, 40, 4))
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2ForCausalLM(lfm2_tiny_config(layer_types=(M.CONV,)))
    with pytest.raises(ValueError, match="two a lane row"):
        Lfm2ForCausalLM(lfm2_tiny_config(num_attention_heads=1,
                                         num_key_value_heads=1))


# -- the router ---------------------------------------------------------------

def router_inputs(layer=1, rows=400):
    w = R._f32(W.make_layer(CFG, SEED, layer))
    u = jnp.asarray(np.random.default_rng(1).normal(size=(rows, 256)),
                    jnp.float32)
    return w, u


def test_the_routers_choose_the_same_experts_at_the_same_gates():
    w, u = router_inputs()
    chosen, gates = M.route(w, u, 2, True, 1.0)
    want, want_gates = FAMILY.router(ES, u, w, FAMILY.sizes(CFG), CFG)
    assert np.array_equal(np.asarray(chosen), np.asarray(want))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want_gates),
                               atol=1e-6)
    # the chosen sigmoids over their sum + 1e-6: just under one
    total = np.asarray(gates).sum(-1)
    assert (total < 1.0).all() and (total > 1.0 - 1e-5).all()


def test_the_bias_changes_the_choice_and_never_the_gates():
    """At the configuration's ``expert_bias_std`` the bias changes the
    chosen set for between a tenth and a half of the tokens, the expert
    layers together (the scale was set by this count), and a chosen
    expert's gate is its sigmoid over the chosen sigmoids' sum, whatever
    the bias."""
    s = FAMILY.sizes(CFG)
    changed = []
    for layer in range(8, 0, -1):
        w, u = router_inputs(layer)
        chosen, gates = FAMILY.router(ES, u, w, s, CFG)
        plain, _ = FAMILY.router(ES, u, w, s, CFG, bias="none")
        changed.append(np.mean([set(a) != set(b) for a, b in zip(
            np.asarray(chosen).tolist(), np.asarray(plain).tolist())]))
    assert 0.1 < np.mean(changed) < 0.5 and min(changed) > 0, changed
    score = np.asarray(jax.nn.sigmoid(u @ w["router_w"]))
    picked = np.take_along_axis(score, np.asarray(chosen), -1)
    np.testing.assert_allclose(
        np.asarray(gates), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        atol=1e-6)
    leaked = FAMILY.router(ES, u, w, s, CFG, bias="gates")[1]
    assert np.abs(np.asarray(leaked) - np.asarray(gates)).max() > 1e-3
    # the program: the same choice with the bias, another without it
    got, got_gates = M.route(w, u, 2, True, 1.0)
    assert np.array_equal(np.asarray(got), np.asarray(chosen))
    without = {k: v for k, v in w.items() if k != "router_b"}
    assert np.array_equal(np.asarray(M.route(without, u, 2, True, 1.0)[0]),
                          np.asarray(plain))


def test_the_held_shares_of_the_experts_add_up_to_the_uncut_layer():
    """``model-configs`` section 4 at this router: the two halves of the
    experts, each told which it holds, give parts that add up to what the
    uncut layer gives, in the reference and in the program."""
    w, u = router_inputs(rows=64)
    s = FAMILY.sizes(CFG)
    whole = FAMILY.experts_by_rows(ES, u, w, s, CFG)
    parts = [FAMILY.experts_by_rows(ES, u, w, s, CFG, held=h)
             for h in ((0, 4), (4, 4))]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(whole), atol=1e-5)
    assert np.abs(np.asarray(parts[0])).max() > 1e-3
    chosen, gates = M.route(w, u, 2, True, 1.0)
    full, counts = routed_experts(w, u, chosen, gates, (0, 8))
    halves = []
    for start in (0, 4):
        held = {"exp_w1": w["exp_w1"][start:start + 4],
                "exp_w2": w["exp_w2"][start:start + 4]}
        halves.append(routed_experts(held, u, chosen, gates, (start, 4)))
    np.testing.assert_allclose(
        np.asarray(halves[0][0] + halves[1][0]), np.asarray(full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(full), np.asarray(whole),
                               atol=1e-5)
    assert int(counts.sum()) == 128 == int(halves[0][1].sum()
                                           + halves[1][1].sum())


# -- the convolution's state --------------------------------------------------

@pytest.mark.parametrize("widths", [(40,), (16, 16, 8), (7, 1, 20, 12),
                                    (2, 38)])
def test_the_state_carried_across_chunks_equals_one_pass(widths):
    """The operator over 40 rows in chunks of any widths, each starting
    from the state the last one left, is the operator over the 40 rows at
    once (to 1e-5: a product's rounding depends on how many rows it is
    given); the state handed on is the last two rows of z, and a boundary's
    state is the two rows before it."""
    w, _ = router_inputs(layer=2)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(40, 256)),
                    jnp.float32)
    statics = dict(eps=1e-5, top_k=2, norm_topk=True, scaling=1.0)
    whole, _, _, _ = M._conv_chunk(w, x, jnp.zeros((2, 256)), 40,
                                   jnp.array([8, 40]), **statics)
    state, at, outs, marks = jnp.zeros((2, 256)), 0, [], {}
    for n in widths:
        bounds = jnp.array([min(max(8 - at, 0), n), n])
        out, state, mk, _ = M._conv_chunk(w, x[at:at + n], state, n, bounds,
                                          **statics)
        if at < 8 <= at + n:
            marks[8] = mk[0]
        outs.append(out)
        at += n
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs)),
                               np.asarray(whole), atol=1e-5)
    z = (x * 0 + M._rms(x, w["ln1_g"], 1e-5)) @ w["in_w"]
    z = z[:, :256] * z[:, 512:]
    np.testing.assert_allclose(np.asarray(state), np.asarray(z[38:40]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(marks[8]), np.asarray(z[6:8]),
                               atol=1e-5)


def test_pad_rows_behind_the_real_ones_leave_the_state_alone():
    w, _ = router_inputs(layer=2)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(16, 256)),
                    jnp.float32)
    statics = dict(eps=1e-5, top_k=2, norm_topk=True, scaling=1.0)
    before = jnp.ones((2, 256))
    _, state, _, _ = M._conv_chunk(w, x[:5], before, 5, jnp.array([5]),
                                   **statics)
    padded = x.at[5:].set(7.0)
    _, got, _, counts = M._conv_chunk(w, padded, before, 5, jnp.array([5]),
                                      **statics)
    assert np.array_equal(np.asarray(got), np.asarray(state))
    assert int(counts[1]) == 5 * 2          # the real rows' assignments


# -- heads of 64, two a lane row ----------------------------------------------

def test_packed_heads_of_64_read_what_unpacked_attention_reads():
    """8 query heads over 4 key heads of 64 in a pool of 2 lane rows: a
    decode step's scores out of the packed pages against plain attention
    over the same rows."""
    rng = np.random.default_rng(5)
    b, heads, kvh, d, block, pages = 3, 8, 4, 64, 4, 5
    k = rng.normal(size=(b, pages * block, kvh, d)).astype(np.float32)
    v = rng.normal(size=(b, pages * block, kvh, d)).astype(np.float32)
    q = rng.normal(size=(b, heads, d)).astype(np.float32)
    kv_len = np.array([20, 7, 13])
    table = 1 + np.arange(b * pages).reshape(b, pages)
    pool_k = np.zeros((1 + b * pages, kvh // 2, block, 2 * d), np.float32)
    pool_v = np.zeros_like(pool_k)
    for pool, rows in ((pool_k, k), (pool_v, v)):
        packed = rows.reshape(b, pages, block, kvh // 2, 2 * d)
        pool[table] = np.moveaxis(packed, 3, 2)
    got = M._decode_scores(jnp.asarray(q), jnp.asarray(pool_k),
                           jnp.asarray(pool_v), jnp.asarray(table),
                           jnp.asarray(kv_len))
    for i in range(b):
        n = kv_len[i]
        for h in range(heads):
            g = h // (heads // kvh)
            sc = k[i, :n, g] @ q[i, h] / np.sqrt(d)
            p = np.exp(sc - sc.max())
            want = (p / p.sum()) @ v[i, :n, g]
            np.testing.assert_allclose(np.asarray(got[i, h]), want,
                                       atol=1e-5)


# -- the served path ----------------------------------------------------------

def prompts_by_length():
    rng = np.random.default_rng(6)
    return [rng.integers(0, 256, n) for n in (10, 16, 53)]


@pytest.mark.parametrize("chunk,compiled", [(8, False), (12, False),
                                            (16, True), (None, False)])
def test_chunks_and_decode_match_the_reference_at_every_served_row(
        chunk, compiled):
    """Chunks of 8, 12 (no multiple of the 8 rows between snapshots) and
    16 rows and the whole prompt at once, three slots side by side, then
    decode through the cache: logits, not tokens."""
    prompts, news = prompts_by_length(), [9, 12, 20]
    seqs, rows, b = serve(prompts, news, prefill_chunk=chunk,
                          compile=compiled)
    assert b.stats()["kv_writer"] == "page"
    assert b._snapshots.taken_total == 1 + 2 + 6    # boundaries of 10/16/53
    b.close()
    for got, ref in zip(rows, reference_rows(seqs, prompts)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)


def series(name, **labels):
    entry = get_registry().get(name)
    if entry is None:
        return 0
    return (entry.labels(**labels) if labels else entry).value


@pytest.mark.parametrize("option,value", [
    ("kv_quant", "int8"), ("cache_quant", "dynamic_int8"),
    ("tier_quant", "int8"), ("host_kv_gib", 1.0), ("disk_kv_dir", "/tmp/x"),
    ("session_store", "/tmp/x"), ("draft_model", object())])
def test_the_contract_refuses_by_name(option, value):
    with pytest.raises(ValueError, match=f"{option} is not supported for "
                                         f"Lfm2ForCausalLM"):
        PagedContinuousBatcher(build(), **dict(SERVER, **{option: value}))


def test_the_contract_declares_state_and_snapshots_and_keeps_the_prefix_cache():
    contract = build().paged_serving_contract()
    assert contract["slot_state"] is True and contract["step_counts"] is True
    assert contract["state_snapshots"] == {"rows": 8}
    assert "prefix_cache" not in contract["unsupported"]
    # without the prefix cache no snapshot is owned or written
    seqs, rows, b = serve(prompts_by_length()[:1], [4], prefix_cache=False)
    assert b._snapshots is None and b.prefix_cache is None
    b.close()
    np.testing.assert_allclose(
        rows[0], reference_rows(seqs, prompts_by_length()[:1])[0], atol=TOL,
        rtol=0)


def test_the_steps_count_what_their_routers_did():
    """The batcher's series, from ``step_counts``: the dense layer routes
    nothing, every assignment is local (all 8 experts held)."""
    names = ("serving.moe_assignments_total",
             "serving.moe_assignments_local_total")
    before = [series(n, phase="prefill") for n in names]
    taken0 = series("serving.state_snapshots_taken_total")
    serve([np.random.default_rng(10).integers(0, 256, 21)], [4], max_batch=1)
    made, local = (series(n, phase="prefill") - b
                   for n, b in zip(names, before))
    assert made == local == 21 * 2 * 8         # rows x top-2 x 8 layers
    assert series("serving.state_snapshots_taken_total") - taken0 == 2


def test_the_cache_gauges_read_the_pools_as_allocated():
    _, _, b = serve([np.arange(5)], [2])
    # K and V, one lane row x 4 rows x 128 lanes float32 a page, the scratch
    # page too, 2 attention layers; a snapshot is 7 layers x 2 rows x 256
    page = 2 * 1 * 4 * 128 * 4
    assert series("serving.kv_cache_bytes", group="full") == 2 * 97 * page
    snapshot = 7 * 2 * 256 * 4
    assert b._snapshots.n == 96 * 4 // 8 == 48
    assert series("serving.state_snapshot_bytes") == 49 * snapshot
    assert series("serving.recurrent_state_bytes") == 3 * snapshot
    b.close()
