"""GLM-5's prefill chunk walks its block table once (ISSUE 38): the
indexer's top-k is a mask on ``generate._table_attention``'s walk, not
a gather of every query's selected latent rows. At test size, in
float32: the walked chunk against the gather form the decode step keeps
(``attend_paged`` without ``whole_context``), the mask against
``lax.top_k``'s set position for position, one compiled chunk for every
context, and the chunk program's temporaries."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu.models import generate as gen
from ptype_tpu.models import sparse_mla
from ptype_tpu.models import transformer as tfm

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
#: The tiny preset behind latent attention with the indexer, top 8.
CFG = dataclasses.replace(
    tfm.preset("tiny", n_kv_heads=2, **F32),
    latent=tfm.LatentAttention(
        q_rank=16, kv_rank=8, nope_dim=6, rope_dim=2, v_dim=8,
        index_heads=2, index_dim=4, index_topk=8, index_rope_dim=2))
LA = CFG.latent
ROWS = 40

#: name -> (block tokens, table blocks, blocks a walk's tile, first
#: position, queries, real queries, planted ties, queries a block).
CASES = {
    # Every visible key is selected: the contexts are under the top 8.
    "context-under-topk": (4, 24, 64, 0, 6, 6, False, 128),
    # Contexts of 38-53 over tiles of 8 keys: seven trips.
    "several-tiles": (4, 24, 2, 37, 16, 16, False, 128),
    # The last five queries are pads (limit 0).
    "pad-queries": (4, 24, 2, 20, 16, 11, False, 128),
    # Tiles of 20 keys over a table of 96: the scores are padded.
    "table-not-whole-tiles": (4, 24, 5, 60, 16, 16, False, 128),
    # Three distinct indexer keys and integer queries: exact ties all
    # along the k-th score.
    "planted-ties": (4, 24, 2, 37, 16, 16, True, 128),
    "ties-across-tiles": (4, 24, 3, 50, 16, 16, True, 128),
    # Two blocks of eight queries, pads in the second.
    "query-blocks": (4, 24, 2, 30, 16, 13, True, 8),
    "query-blocks-blocks-of-16": (16, 8, 1, 70, 16, 16, False, 8),
}


def _inputs(bt, nb, start, Q, n_real, ties, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    H = CFG.n_heads
    q_nope = jax.random.normal(ks[0], (1, Q, H, LA.nope_dim))
    q_rope = jax.random.normal(ks[1], (1, Q, H, LA.rope_dim))
    ckv = jax.random.normal(ks[2], (ROWS, bt, LA.cache_dim))
    if ties:
        # Small integers: every score is exact, and equal keys score
        # equal at every query.
        qi = jax.random.randint(ks[3], (1, Q, LA.index_heads,
                                        LA.index_dim), -2, 3)
        wi = jnp.ones((1, Q, LA.index_heads))
        three = jax.random.randint(ks[4], (3, LA.index_dim), -1, 2)
        ki = three[jax.random.randint(ks[5], (ROWS, bt), 0, 3)]
        qi, ki = qi.astype(jnp.float32), ki.astype(jnp.float32)
    else:
        qi = jax.random.normal(ks[3], (1, Q, LA.index_heads, LA.index_dim))
        wi = jax.random.normal(ks[6], (1, Q, LA.index_heads))
        ki = jax.random.normal(ks[4], (ROWS, bt, LA.index_dim))
    layer = {"w_uk": 0.3 * jax.random.normal(
        ks[7], (LA.kv_rank, H, LA.nope_dim)),
             "w_uv": 0.3 * jax.random.normal(
        ks[8], (LA.kv_rank, H, LA.v_dim))}
    tables = jnp.asarray(np.random.RandomState(seed).permutation(
        np.arange(1, ROWS))[:nb][None], jnp.int32)
    pos = start + np.arange(Q)
    limits = jnp.asarray(np.where(np.arange(Q) < n_real, pos + 1, 0)[None],
                         jnp.int32)
    return q_nope, q_rope, qi, wi, ckv, ki, tables, limits, layer


@pytest.mark.parametrize("case", list(CASES))
def test_walked_chunk_equals_the_gather_form(case, monkeypatch):
    """The chunk's walk against the gather of each query's selected
    rows (the parent's form, which the decode step keeps): equal to
    2e-5 on every real query, and a pad query reads zeros."""
    bt, nb, tile, start, Q, n_real, ties, qb = CASES[case]
    monkeypatch.setattr(gen, "TABLE_TILE_BLOCKS", tile)
    monkeypatch.setattr(sparse_mla, "QUERY_BLOCK", qb)
    args = _inputs(bt, nb, start, Q, n_real, ties) + (CFG,)
    if case == "context-under-topk":
        assert start + Q < LA.index_topk
    want = np.asarray(sparse_mla.attend_paged(*args))
    got = np.asarray(sparse_mla.attend_paged(*args, whole_context=True))
    np.testing.assert_allclose(got[:, :n_real], want[:, :n_real],
                               atol=2e-5)
    assert (got[:, n_real:] == 0).all()
    assert np.abs(got[:, :n_real]).min() > 0


@pytest.mark.parametrize("span", [16, 24, 96])
@pytest.mark.parametrize("k", [1, 5, 16])
def test_the_mask_is_top_ks_set_position_for_position(span, k):
    """:func:`sparse_mla.kept_by_top_k` read over tiles of ``span``
    against a scatter of ``lax.top_k``'s positions: equal, on scores
    with ties at every value and masked positions past each limit
    (fewer visible than ``k`` for the first rows)."""
    rng = np.random.RandomState(k * 100 + span)
    B, Q, T = 2, 6, 80
    I = rng.randint(0, 4, (B, Q, T)).astype(np.float32)
    limits = np.arange(1, B * Q + 1).reshape(B, Q) * 6
    I = np.where(np.arange(T) < limits[..., None], I,
                 np.float32(sparse_mla._NEG))
    vals, idx = jax.lax.top_k(jnp.asarray(I).reshape(B * Q, T), k)
    want = np.zeros((B * Q, T), bool)
    want[np.arange(B * Q)[:, None], np.asarray(idx)] = True
    keep = sparse_mla.kept_by_top_k(
        jnp.asarray(I), vals[:, -1].reshape(B, Q),
        idx[:, -1].reshape(B, Q), span)
    got = np.concatenate(
        [np.asarray(keep(t0, jnp.broadcast_to(
            t0 + jnp.arange(span), (B, span))))
         for t0 in range(0, T, span)], axis=-1)[..., :T]
    np.testing.assert_array_equal(got.reshape(B * Q, T), want)


def test_one_compiled_chunk_serves_every_context(monkeypatch):
    """The walk's trip count is data: chunks from the first position to
    the reach's last run through one compiled program."""
    monkeypatch.setattr(gen, "TABLE_TILE_BLOCKS", 2)
    cfg = dataclasses.replace(CFG, max_seq=128)
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    bt, C = 16, 16
    nb = cfg.max_seq // bt
    banks = {n: jnp.zeros((cfg.n_layers, nb + 1, bt) + w, jnp.float32)
             for n, w in tfm.cache_spec(cfg).items()}
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)
    chunk = jax.jit(lambda banks, tok, start, n: gen.prefill_chunk_banks(
        params, tok, start, n, cfg, banks, table)[:2])
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, cfg.max_seq), 1,
                              cfg.vocab_size, jnp.int32)
    for start, n in ((0, 1), (0, C), (C, C), (64, 9), (cfg.max_seq - C, C)):
        lg, banks = chunk(banks, jax.lax.dynamic_slice_in_dim(
            toks, start, C, axis=1), jnp.int32(start), jnp.int32(n))
        assert np.isfinite(np.asarray(lg)).all()
    assert chunk._cache_size() == 1
