"""The grouped expert product as a Pallas kernel
(``ops/pallas/grouped_experts.py``), interpreted on the CPU at cut widths:
equal to the XLA walk it replaces on the chip (``routed_experts._walk``),
whatever the routing; what it does not read never reaches a result; the
tiling a pure function of the shape that fits the VMEM the call states; one
count a lowering. The real widths are offered to
the chip's compiler in ``tests/test_chip_compile.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import routed_experts as E
from paddle_tpu.observability.metrics import get_registry
from paddle_tpu.ops.pallas import grouped_experts as GE

BF16, F32 = jnp.bfloat16, jnp.float32

# d : f as the two families have them: mellum 2,304 : 896 (an expert's f is
# one block), glm_dsa 6,144 : 2,048 (walked in blocks of f)
RATIOS = {"mellum": (384, 128, None), "glm_dsa": (384, 256, 128)}


def _layer(seed, held, d, f, dtype=BF16):
    rng = np.random.default_rng(seed)
    return {"exp_w1": jnp.asarray(rng.normal(size=(held, d, 2 * f))
                                  * d ** -0.5, dtype),
            "exp_w2": jnp.asarray(rng.normal(size=(held, f, d))
                                  * f ** -0.5, dtype)}, rng


def _routing(rng, n, k, width, p=None):
    chosen = np.stack([rng.choice(width, k, replace=False, p=p)
                       for _ in range(n)])
    return jnp.asarray(chosen, jnp.int32), \
        jnp.asarray(rng.uniform(0.05, 1.0, size=(n, k)), F32)


def _kernel_route(p, h, chosen, gates, held, block_f=None, spoil=None):
    """``routed_experts`` as a TPU runs it, the kernel interpreted."""
    tm = E._tile_rows(chosen.shape[0])
    tiles = E.tile_layout(chosen, gates, held, tm)
    token, gate = (tiles.token, tiles.gate) if spoil is None \
        else spoil(tiles)
    return GE.grouped_experts(h, token, gate, p["exp_w1"], p["exp_w2"],
                              tiles.tile_expert, tiles.n_tiles, tile_rows=tm,
                              block_f=block_f, interpret=True), tiles


def _same(got, want):
    """Within bfloat16 rounding (interpreted, the products are XLA's own in
    both routes: in practice not a bit differs)."""
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 ** -7, atol=2 ** -7)


@pytest.mark.parametrize("ratio", list(RATIOS))
def test_a_decode_step_equals_the_walk(ratio):
    """16 rows, tiles of 16: every touched expert one tile."""
    d, f, block_f = RATIOS[ratio]
    p, rng = _layer(1, 32, d, f)
    h = jnp.asarray(rng.normal(size=(16, d)), BF16)
    chosen, gates = _routing(rng, 16, 4, 32)
    want, counts = E.routed_experts(p, h, chosen, gates, (0, 32))
    got, tiles = _kernel_route(p, h, chosen, gates, (0, 32), block_f)
    assert tiles.token.shape == (4 + 32, 16)
    assert int(tiles.n_tiles) == int(jnp.sum(counts > 0))
    _same(got, want)


@pytest.mark.parametrize("ratio", list(RATIOS))
def test_a_chunk_equals_the_walk_where_an_expert_has_two_tiles(ratio):
    d, f, block_f = RATIOS[ratio]
    p, rng = _layer(2, 8, d, f)
    h = jnp.asarray(rng.normal(size=(256, d)), BF16)
    skew = np.arange(1, 9, dtype=np.float64) ** -1.5
    chosen, gates = _routing(rng, 256, 2, 8, skew / skew.sum())
    want, counts = E.routed_experts(p, h, chosen, gates, (0, 8))
    got, tiles = _kernel_route(p, h, chosen, gates, (0, 8), block_f)
    assert tiles.token.shape[1] == 128 and int(counts.max()) > 128
    assert int(tiles.n_tiles) > int(jnp.sum(counts > 0))
    _same(got, want)


def test_every_assignment_on_one_expert():
    p, rng = _layer(3, 8, 256, 128)
    h = jnp.asarray(rng.normal(size=(16, 256)), BF16)
    chosen = jnp.full((16, 1), 5, jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(16, 1)), F32)
    want, counts = E.routed_experts(p, h, chosen, gates, (0, 8))
    got, tiles = _kernel_route(p, h, chosen, gates, (0, 8))
    assert np.asarray(counts).tolist() == [0] * 5 + [16, 0, 0]
    assert int(tiles.n_tiles) == 1
    _same(got, want)


def test_no_local_assignment_gives_zeros():
    """``n_tiles`` 0: no tile is computed, nothing of the result is read."""
    p, rng = _layer(4, 4, 256, 128)
    h = jnp.asarray(rng.normal(size=(16, 256)), BF16)
    chosen, gates = _routing(rng, 16, 2, 8)
    chosen = jnp.where(chosen < 4, chosen + 4, chosen)     # all held elsewhere
    got, tiles = _kernel_route(p, h, chosen, gates, (0, 4))
    assert int(tiles.n_tiles) == 0 and int(tiles.counts.sum()) == 0
    assert np.abs(np.asarray(got, np.float32)).max() == 0
    parked, _ = _kernel_route(p, h, jnp.full((16, 2), -1, jnp.int32), gates,
                              (0, 4))
    assert np.abs(np.asarray(parked, np.float32)).max() == 0


@pytest.mark.parametrize("held", [(4, 8), (13, 3)])
def test_a_share_of_the_routers_width(held):
    p, rng = _layer(5, held[1], 256, 128)
    h = jnp.asarray(rng.normal(size=(16, 256)), BF16)
    chosen, gates = _routing(rng, 16, 4, 16)
    want, counts = E.routed_experts(p, h, chosen, gates, held)
    got, _ = _kernel_route(p, h, chosen, gates, held)
    local = (np.asarray(chosen) >= held[0]) \
        & (np.asarray(chosen) < held[0] + held[1])
    assert 0 < int(counts.sum()) == int(local.sum()) < chosen.size
    _same(got, want)


def test_rows_past_the_last_tile_never_reach_a_result():
    """Every row of the tiles past ``n_tiles`` names a token and holds a
    NaN for a gate: the result holds none, and nothing of theirs."""
    p, rng = _layer(6, 16, 256, 128)
    h = jnp.asarray(rng.normal(size=(16, 256)), BF16)
    chosen, gates = _routing(rng, 16, 2, 16)
    want, _ = E.routed_experts(p, h, chosen, gates, (0, 16))

    def spoil(tiles):
        dead = (jnp.arange(tiles.token.shape[0]) >= tiles.n_tiles)[:, None]
        return jnp.where(dead, 3, tiles.token), \
            jnp.where(dead, jnp.nan, tiles.gate)

    got, tiles = _kernel_route(p, h, chosen, gates, (0, 16), spoil=spoil)
    assert int(tiles.n_tiles) < tiles.token.shape[0]
    assert np.isfinite(np.asarray(got, np.float32)).all()
    _same(got, want)


def test_padding_takes_no_row_and_adds_to_none():
    """A tile's pad rows name no token (-1): they read nothing of ``h`` and
    no token's sum has them, whatever their gate."""
    p, rng = _layer(9, 8, 256, 128)
    h = jnp.asarray(rng.normal(size=(16, 256)), BF16)
    chosen, gates = _routing(rng, 16, 2, 8)
    want, _ = E.routed_experts(p, h, chosen, gates, (0, 8))
    got, tiles = _kernel_route(
        p, h, chosen, gates, (0, 8),
        spoil=lambda t: (t.token, jnp.where(t.token < 0, 7.0, t.gate)))
    assert int(jnp.sum(tiles.token < 0)) > 0
    _same(got, want)


def test_a_row_that_is_not_finite_stays_alone(monkeypatch):
    """``routed_experts`` hands the kernel finite rows: a token whose row
    of ``h`` holds a NaN (and whose gates the router then made of it) gets
    the experts of a zero row, the other tokens what they got without it."""
    import paddle_tpu.ops.pallas as pallas_tier
    p, rng = _layer(12, 8, 256, 128)
    h = jnp.asarray(rng.normal(size=(16, 256)), BF16)
    chosen, gates = _routing(rng, 16, 2, 8)
    want, _ = E.routed_experts(p, h, chosen, gates, (0, 8))
    monkeypatch.setattr(pallas_tier, "on_tpu", lambda: True)
    monkeypatch.setattr(E, "grouped_experts", lambda *a, **k:
                        GE.grouped_experts(*a, **k, interpret=True))
    same, _ = E.routed_experts(p, h, chosen, gates, (0, 8))
    _same(same, want)
    got, _ = E.routed_experts(p, h.at[5, 17].set(jnp.nan), chosen,
                              gates.at[5].set(jnp.nan), (0, 8))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    keep = np.arange(16) != 5
    _same(np.asarray(got, np.float32)[keep],
          np.asarray(want, np.float32)[keep])


def test_float32_operands_multiply_in_float32():
    p, rng = _layer(7, 8, 256, 128, F32)
    h = jnp.asarray(rng.normal(size=(16, 256)), F32)
    chosen, gates = _routing(rng, 16, 2, 8)
    want, _ = E.routed_experts(p, h, chosen, gates, (0, 8))
    got, _ = _kernel_route(p, h, chosen, gates, (0, 8))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# (N, tile_rows, d, f, itemsize): the two cells' decode step and chunk
CELLS = {"mellum2_decode": ((16, 16, 2304, 896, 2), 896),
         "mellum2_chunk": ((512, 128, 2304, 896, 2), 896),
         "glm5_decode": ((16, 16, 6144, 2048, 2), 1024),
         "glm5_chunk": ((512, 128, 6144, 2048, 2), 256)}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_tiling_is_pure_and_fits_the_stated_vmem(cell):
    shape, block_f = CELLS[cell]
    assert GE.grouped_experts_tiling(*shape) == block_f
    assert GE.grouped_experts_tiling(*shape) == block_f   # the same answer
    n, tm, d, f, itemsize = shape
    assert f % block_f == 0 and block_f % 128 == 0
    assert GE.vmem_bytes(n, tm, d, block_f, itemsize) <= GE.VMEM_BUDGET \
        < GE.VMEM_LIMIT
    wider = [b for b in range(block_f + 128, f + 1, 128) if f % b == 0]
    assert all(GE.vmem_bytes(n, tm, d, b, itemsize) > GE.VMEM_BUDGET
               for b in wider)                    # the widest that fits
    assert GE.supported(n, tm, d, f, BF16)


def test_shapes_the_kernel_takes():
    assert GE.supported(16, 16, 2304, 896, BF16)
    assert GE.supported(16, 16, 6144, 2048, F32)
    assert not GE.supported(24, 16, 16, 8, F32)   # the CPU tests' widths
    assert not GE.supported(16, 16, 2304, 896, jnp.int8)
    assert not GE.supported(3, 16, 2304, 896, BF16)
    # h and its sum no longer fit beside the narrowest blocks: the XLA route
    assert GE.grouped_experts_tiling(8192, 128, 6144, 2048, 2) is None
    assert not GE.supported(8192, 128, 6144, 2048, BF16)
    assert GE.grouped_experts_tiling(16, 16, 16, 8, 4) == 8
    with pytest.raises(ValueError, match="no block of f"):
        GE.grouped_experts(jnp.zeros((16, 256), BF16),
                           jnp.zeros(16, jnp.int32), jnp.zeros(16),
                           jnp.zeros((1, 256, 512), BF16),
                           jnp.zeros((1, 256, 256), BF16),
                           jnp.zeros(1, jnp.int32), 0, tile_rows=16,
                           block_f=96, interpret=True)


def _count(**labels):
    entry = get_registry().get("routed_experts_tiling_total")
    return 0.0 if entry is None else entry.labels(**labels).value


def test_the_series_counts_one_a_lowering_whatever_the_layers():
    """A stack's layers of one shape trace the kernel once: the eager first
    call of a step, then the step traced, count one between them."""
    d, f, layers = 128, 128, 3
    labels = dict(rows="16", d=str(d), f=str(f), tile_rows="16",
                  block_f=str(f))
    stack = [_layer(10 + i, 6, d, f)[0] for i in range(layers)]
    rng = np.random.default_rng(8)
    h = jnp.asarray(rng.normal(size=(16, d)), BF16)
    chosen, gates = _routing(rng, 16, 4, 6)

    def step(h):
        for p in stack:
            h = h + _kernel_route(p, h, chosen, gates, (0, 6))[0]
        return h

    before = _count(**labels)
    eager = step(h)
    assert _count(**labels) == before + 1
    traced = jax.jit(step)(h)
    assert _count(**labels) == before + 1
    _same(traced, eager)
