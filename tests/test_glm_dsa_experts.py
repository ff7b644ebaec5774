"""GLM-5's routed experts and what the reference leaves out: the bias moves the
choice and not the gate, the parts all shares give add up to the uncut
layer, a share of the experts is served like the reference given it, and
each thing left out moves the logits. (Attention, selection and the paged
server are tests/test_glm_dsa.py's.)
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# first: it puts the checkout, where chipbench is, on the path
from test_glm_dsa import (CFG, FAMILY, SEED, TOL, build, reference_rows, serve,
                          walk)

from chipbench import reference as R  # noqa: E402
from chipbench import weights as W  # noqa: E402
from paddle_tpu.models import glm_dsa as G  # noqa: E402

from test_routed_experts import _expert_layer  # noqa: E402


def test_the_bias_moves_the_choice_and_not_the_gate():
    p, h = _expert_layer()
    chosen, gates = map(np.asarray, G.route(p, h, 4, 2.5))
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-5)
    score = np.asarray(jax.nn.sigmoid(h @ p["router_w"]))
    assert all(set(c) == set(np.argsort(-s)[:4])
               for c, s in zip(chosen, score))
    biased = dict(p, router_b=jnp.zeros(16).at[11].set(10.0))
    chosen_b, gates_b = map(np.asarray, G.route(biased, h, 4, 2.5))
    assert (chosen_b == 11).any(-1).all() and not (chosen == 11).any(-1).all()
    picked = np.take_along_axis(score, chosen_b, -1)     # without the bias
    np.testing.assert_allclose(
        gates_b, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)


def test_the_parts_all_shares_give_add_up_to_the_uncut_layer():
    """16 chips of one expert each, or 4 of four: what the held experts of
    every share give, with the shared expert (which every chip computes
    alike) counted once, is the uncut layer: in the program and in the
    reference."""
    p, h = _expert_layer(2)
    x = h
    whole, _ = G._ffn(p, x, 1e-5, (0, 16), 4, 2.5)
    u = G._rms(x, p["ln2_g"], 1e-5)
    chosen, gates = G.route(p, u, 4, 2.5)
    shared = G._swiglu(u, p["sh_w1"], p["sh_w2"])
    for count in (1, 4):
        parts = sum(G.routed_experts(
            dict(p, exp_w1=p["exp_w1"][s:s + count],
                 exp_w2=p["exp_w2"][s:s + count]), u, chosen, gates,
            (s, count))[0] for s in range(0, 16, count))
        np.testing.assert_allclose(np.asarray(x + parts + shared),
                                   np.asarray(whole), atol=1e-4, rtol=1e-5)
    # the reference, on a layer of the model's own (bfloat16 values)
    s = FAMILY.sizes(CFG)
    w = R._f32(W.make_layer(CFG, SEED, 1))
    es = functools.partial(R.einsum, "f32")
    u = jnp.asarray(np.random.default_rng(3).normal(size=(20, 64)),
                    jnp.float32)
    whole = FAMILY.routed(es, u, w, s, CFG)
    parts = sum(FAMILY.routed(
        es, u, dict(w, exp_w1=w["exp_w1"][e:e + 4],
                    exp_w2=w["exp_w2"][e:e + 4]), s, CFG, held=(e, 4))
        for e in range(0, 16, 4))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-5, rtol=1e-5)


def test_a_share_of_the_experts_is_served_like_the_reference_given_it():
    """Experts 4 .. 8 of 16 held: a chosen expert held elsewhere adds
    nothing, in program and reference alike."""
    cfg = dict(CFG, experts_held_start=4, n_routed_experts=4)
    model = build(held=(4, 4))
    prompts = [np.random.default_rng(6).integers(0, 128, n)
               for n in (45, 12)]
    seqs, rows, b = serve(model, prompts, [10, 10], prefix_cache=False)
    b.close()
    for got, ref in zip(rows, reference_rows(seqs, prompts, cfg)):
        np.testing.assert_allclose(got, ref, atol=TOL, rtol=0)
    uncut = reference_rows(seqs, prompts)
    assert max(np.abs(a - c).max() for a, c in zip(uncut, rows)) > 100 * TOL


@pytest.mark.parametrize("left_out", [dict(selection="newest"),
                                      dict(selection="all"),
                                      dict(with_routed=False)])
def test_what_is_left_out_moves_the_logits(left_out):
    """The newest ``index_topk`` rows in the indexer's place, every row, or
    no routed branch: 100 times the tolerance or more on the logits of rows
    past ``index_topk``."""
    ids = np.random.default_rng(4).integers(0, 128, (2, 96))
    whole, _ = walk(ids)
    cut, _ = walk(ids, **left_out)
    assert np.abs(whole - cut)[:, 32:].max() > 100 * TOL
    if "selection" in left_out:      # the first 16 rows keep every row
        np.testing.assert_allclose(cut[:, :16], whole[:, :16], atol=1e-5)
