"""``ops.latent_block_attention`` (interpret mode) against a float32
NumPy softmax over each row's own positions and against the XLA loop it
replaced, kept here as the reference: the latent, no-indexer branch
``generate._live_block_attention`` had before PR 36 (a tile's blocks
gathered, scored against their owner's queries, folded into float32
running sums)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ptype_tpu.models import generate as gen
from ptype_tpu.ops.latent_block_attention import latent_block_attention

LANES, H, D, DV, BT, NB, NBLK, LAYER = 4, 4, 128, 64, 4, 12, 48, 1
SCALE = 0.3


def loop_reference(q, bank, base, blocks, limits, scale, v_dim):
    """The parent's loop: q (B, H, D), bank (rows, bt, D)."""
    lst, n_tiles = blocks
    B, Hq, Dh = q.shape
    bt = bank.shape[1]
    S = lst.shape[2] * bt
    f32 = jnp.float32
    limits = jnp.asarray(limits, jnp.int32)
    offs = jnp.arange(bt, dtype=jnp.int32)

    def fold(t, carry):
        ids, owner, first = lst[0, t], lst[1, t], lst[2, t]
        ks = bank[base + ids].reshape(S, Dh)
        mine = jnp.minimum(owner[0], B - 1)
        m, l, acc = (lax.dynamic_index_in_dim(a, mine, 0) for a in carry)
        s = jnp.einsum("bhd,sd->bhs",
                       lax.dynamic_index_in_dim(q, mine, 0), ks).astype(f32)
        s = s * f32(scale)
        at = (first[:, None] + offs[None, :]).reshape(S)
        mask = ((jnp.repeat(owner, bt) == mine)
                & (at < limits[mine]))[None, None, :]
        s = jnp.where(mask, s, f32(-1e30))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), f32(0))
        alpha = jnp.exp(m - m_new)
        pv = jnp.einsum("bhs,sd->bhd", p.astype(q.dtype), ks[:, :v_dim],
                        preferred_element_type=f32)
        new = (m_new, l * alpha + jnp.sum(p, axis=-1),
               acc * alpha[..., None] + pv)
        return tuple(lax.dynamic_update_index_in_dim(a, n, mine, 0)
                     for a, n in zip(carry, new))

    m, l, acc = lax.fori_loop(
        0, n_tiles, fold,
        (jnp.full((B, Hq), -1e30, f32), jnp.zeros((B, Hq), f32),
         jnp.zeros((B, Hq, v_dim), f32)))
    return (acc / jnp.where(l > 0, l, f32(1))[..., None]).astype(q.dtype)


def numpy_reference(q, bank, base, tables, ctx):
    """float64 softmax over each live row's own positions."""
    out = np.zeros((LANES, H, DV))
    q, bank = np.asarray(q, np.float64), np.asarray(bank, np.float64)
    for b in range(LANES):
        if ctx[b]:
            n = -(-ctx[b] // BT)
            rows = bank[base + tables[b, :n]].reshape(-1, D)[:ctx[b]]
            s = q[b] @ rows.T * SCALE
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b] = (p / p.sum(-1, keepdims=True)) @ rows[:, :DV]
    return out


#: name: (context a lane (0 = the lane holds no request), list tile,
#: sub-tile, blocks allocated past the limit, the leading blocks lanes
#: 0 and 2 share, dtype)
CASES = {
    "one_lane_live_mid_block": ([0, 9, 0, 0], 4, None, 0, 0, "float32"),
    "three_lanes_live": ([9, 0, 33, 16], 4, None, 0, 0, "float32"),
    "all_lanes_live": ([5, 18, 33, 48], 4, None, 0, 0, "float32"),
    "no_lane_live": ([0, 0, 0, 0], 4, None, 0, 0, "float32"),
    "ends_on_a_block_edge": ([8, 0, 12, 0], 4, None, 0, 0, "float32"),
    "ends_on_a_tile_edge": ([16, 32, 0, 0], 4, None, 0, 0, "float32"),
    "one_key_past_a_tile_edge": ([17, 33, 0, 0], 4, None, 0, 0, "float32"),
    "last_tile_mostly_padding": ([33, 0, 34, 0], 8, None, 0, 0, "float32"),
    "inactive_lane_between_live_ones": ([12, 0, 20, 0], 4, None, 0, 0,
                                        "float32"),
    "two_rows_share_a_prefix": ([21, 0, 30, 7], 4, None, 0, 4, "float32"),
    "a_block_allocated_ahead": ([16, 0, 32, 5], 4, None, 1, 0, "float32"),
    "sub_tiles_of_one_block": ([9, 40, 33, 16], 4, 1, 0, 0, "float32"),
    "four_sub_tiles_a_tile": ([47, 0, 33, 16], 8, 2, 0, 0, "float32"),
    "whole_reach_one_tile": ([48, 48, 0, 1], 12, None, 0, 0, "float32"),
    "bfloat16": ([9, 40, 33, 16], 4, 2, 0, 3, "bfloat16"),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_numpy_and_the_loop(name):
    ctx, tile, sub, ahead, shared, dtype = CASES[name]
    ctx = np.asarray(ctx)
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(7)
    bank = rng.normal(size=(2 * NBLK, BT, D)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(LANES, H, D)), dt)
    active = ctx > 0
    nalloc = np.where(active, -(-ctx // BT) + ahead, 0)
    tables = np.zeros((LANES, NB), np.int32)
    nxt = 1
    for b in range(LANES):
        for c in range(min(nalloc[b], NB)):
            if b == 2 and c < shared and active[0]:
                tables[b, c] = tables[0, c]
            else:
                tables[b, c], nxt = nxt, nxt + 1
    nalloc = np.minimum(nalloc, NB)
    # The engine's limits: an inactive lane sits at position 0.
    limits = jnp.asarray(np.where(active, ctx, 1), jnp.int32)
    lst, n = gen.live_block_list(tables, nalloc, active, BT, tile=tile,
                                 own_tiles=True)
    blocks = (jnp.asarray(lst), jnp.asarray(n))
    base = LAYER * NBLK
    bank = jnp.asarray(bank, dt)
    # What must not be read reads NaN: each layer's trash block (the
    # padding of a row's last tile names it) and every block allocated
    # past a row's limit.
    poisoned = bank.at[0].set(jnp.nan).at[base].set(jnp.nan)
    for b in np.flatnonzero(active):
        for c in range(-(-ctx[b] // BT), nalloc[b]):
            poisoned = poisoned.at[base + tables[b, c]].set(jnp.nan)

    got = latent_block_attention(q, poisoned, base, blocks, limits,
                                 scale=SCALE, v_dim=DV, sub_blocks=sub)
    assert got.shape == (LANES, H, DV) and got.dtype == dt
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    assert (got[~active] == 0).all()  # a lane with no listed block

    want = numpy_reference(q, bank, base, tables, ctx)
    loop = np.asarray(loop_reference(q, bank, base, blocks, limits, SCALE,
                                     DV), np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
        # The loop's sums in another order: float32 rounding.
        np.testing.assert_allclose(got, loop, atol=2e-6, rtol=0)
    else:
        # bfloat16 products as the loop makes them (the scores rounded
        # to the query's type): one rounding of the result apart.
        np.testing.assert_allclose(got, want, atol=3e-2, rtol=0)
        np.testing.assert_allclose(got, loop, atol=2 ** -8, rtol=2 ** -7)


def test_one_program_whatever_the_load():
    """The trip count is data: two loads, one trace of the kernel."""
    rng = np.random.default_rng(1)
    bank = jnp.asarray(rng.normal(size=(NBLK, BT, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(LANES, H, D)), jnp.float32)
    traces = []

    @jax.jit
    def run(blocks, limits):
        traces.append(1)
        return latent_block_attention(q, bank, 0, blocks, limits,
                                      scale=SCALE, v_dim=DV)

    outs = []
    for ctx in ([9, 0, 0, 0], [9, 40, 33, 16]):
        ctx = np.asarray(ctx)
        nalloc = -(-ctx // BT)
        tables = np.zeros((LANES, NB), np.int32)
        tables[:, :] = 1 + np.arange(LANES * NB).reshape(LANES, NB)
        lst, n = gen.live_block_list(tables, nalloc, ctx > 0, BT, tile=4,
                                     own_tiles=True)
        outs.append(np.asarray(run((jnp.asarray(lst), jnp.asarray(n)),
                                   jnp.asarray(np.maximum(ctx, 1)))))
        np.testing.assert_allclose(
            outs[-1], numpy_reference(q, bank, 0, tables, ctx), atol=2e-5)
    assert len(traces) == 1
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
