"""The routed-expert layer that ``models/glm_dsa.py`` and ``models/mellum.py``
share (``models/routed_experts.py``): the grouped product is dropless
whatever the skew, a share of the experts gives its part of the sum, and
the counts say what was done. The routers are the families' own: GLM's
makes the skew here, Mellum's is held in ``test_mellum.py``."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.models import glm_dsa as G  # noqa: E402
from paddle_tpu.models import routed_experts as E  # noqa: E402


def _expert_layer(seed=0, d=16, f=8, experts=16):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return {"router_w": draw(d, experts), "router_b": jnp.zeros(experts),
            "exp_w1": draw(experts, d, 2 * f), "exp_w2": draw(experts, f, d),
            "sh_w1": draw(d, 2 * f), "sh_w2": draw(f, d),
            "ln2_g": jnp.ones(d)}, draw(24, d)


def _by_hand(p, h, chosen, gates, held):
    out = np.zeros(h.shape, np.float32)
    for t in range(h.shape[0]):
        for j in range(chosen.shape[1]):
            e = int(chosen[t, j])
            if held[0] <= e < held[0] + held[1]:
                out[t] += float(gates[t, j]) * np.asarray(E._swiglu(
                    h[t:t + 1], p["exp_w1"][e], p["exp_w2"][e]))[0]
    return out


@pytest.mark.parametrize("rows", [1, 3, 24])
def test_nothing_is_dropped_when_every_token_picks_one_expert(rows):
    p, h = _expert_layer(1)
    h = h[:rows]
    p = dict(p, router_b=jnp.zeros(16).at[5].set(10.0))
    chosen, gates = G.route(p, h, 4, 2.5)
    assert (np.asarray(chosen) == 5).any(-1).all()
    for held in ((0, 16), (4, 4), (5, 1)):
        share = dict(p, exp_w1=p["exp_w1"][held[0]:held[0] + held[1]],
                     exp_w2=p["exp_w2"][held[0]:held[0] + held[1]])
        got, counts = E.routed_experts(share, h, chosen, gates, held)
        np.testing.assert_allclose(np.asarray(got),
                                   _by_hand(p, h, chosen, gates, held),
                                   atol=1e-4, rtol=1e-5)
        assert int(counts[5 - held[0]]) == rows      # all of them, in one
        assert int(counts.sum()) == int(np.sum(
            (np.asarray(chosen) >= held[0])
            & (np.asarray(chosen) < held[0] + held[1])))



@pytest.mark.parametrize("rows,tile", [(1, 16), (8, 16), (9, 16), (16, 16),
                                       (100, 128), (512, 128)])
def test_a_decode_step_is_whole_groups_and_a_chunk_mxu_tiles(rows, tile):
    assert E._tile_rows(rows) == tile


def test_both_families_call_the_one_grouped_product():
    from paddle_tpu.models import mellum
    assert G.routed_experts is E.routed_experts is mellum.routed_experts
    assert G._counts_of_step is mellum._counts_of_step


@pytest.mark.parametrize("held", [(0, 16), (4, 4)])
def test_the_counts_say_what_was_done(held):
    """[4]: assignments to experts held here, all the router made, the
    held experts touched, the fullest one's tokens."""
    p, h = _expert_layer(3)
    chosen, gates = G.route(p, h, 4, 2.5)
    share = dict(p, exp_w1=p["exp_w1"][held[0]:held[0] + held[1]],
                 exp_w2=p["exp_w2"][held[0]:held[0] + held[1]])
    _, counts = E.routed_experts(share, h, chosen, gates, held)
    got = np.asarray(E.expert_counts(counts, chosen.size))
    local = (np.asarray(chosen) >= held[0]) \
        & (np.asarray(chosen) < held[0] + held[1])
    per = np.bincount(np.asarray(chosen)[local] - held[0],
                      minlength=held[1])
    assert got.tolist() == [int(local.sum()), chosen.size,
                            int((per > 0).sum()), int(per.max())]


def test_rows_routed_nowhere_add_nothing_and_count_nowhere():
    p, h = _expert_layer(4)
    chosen, gates = G.route(p, h, 4, 2.5)
    parked = jnp.arange(h.shape[0]) % 2 == 0
    got, counts = E.routed_experts(
        p, h, jnp.where(parked[:, None], -1, chosen), gates, (0, 16))
    assert np.abs(np.asarray(got)[np.asarray(parked)]).max() == 0
    assert int(counts.sum()) == int((~np.asarray(parked)).sum()) * 4
    want = _by_hand(p, h, chosen, gates, (0, 16))
    np.testing.assert_allclose(np.asarray(got)[~np.asarray(parked)],
                               want[~np.asarray(parked)], atol=1e-4,
                               rtol=1e-5)
