"""The paged layer loop (ISSUE 26): ``generate._paged_layers`` serves
the decode step, the prefill chunk and the two speculative programs
with the K,V banks as the scan's carry and the layer in the scatter's
and gather's indices. Greedy rows through it equal the untouched
contiguous path token for token and logit for logit, and a program
changes exactly its own rows of each layer's bank. (A file of its own:
test_serve_engine.py and test_spec_decode.py are the fast tier's two
longest already.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu.models import generate as gen
from ptype_tpu.models import transformer as tfm

# Three layers, banks pre-filled with noise: a layer that wrote into
# (or read from) another layer's rows cannot pass.

CFG3 = tfm.preset("tiny", dtype=jnp.float32, n_layers=3)
BT, N_BLOCKS = 16, 12


def _noise_banks(seed):
    shape = (CFG3.n_layers, N_BLOCKS, BT, CFG3.kv_heads, CFG3.head_dim)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, shape, jnp.float32),
            jax.random.normal(k2, shape, jnp.float32))


@pytest.mark.parametrize("attend", ["tables", "blocks"])
def test_paged_programs_match_contiguous_greedy(attend):
    """Two rows that SHARE their first block and a third, inactive
    lane routed to the trash block, through ``prefill_chunk_banks``
    and ``decode_step_banks``: each live row equals the untouched
    contiguous ``generate()`` token for token and the contiguous
    ``prefill`` + ``decode_step`` logit for logit (a random-init model
    mostly echoes its input, so tokens alone would forgive a layer
    that read another layer's rows), the shared block is written once
    and never again, and no block outside the rows' tables (and the
    trash block) changes in any layer. ``"blocks"``: the decode step
    attends over the live rows' block list (ISSUE 29), rebuilt as the
    engine rebuilds it, in tiles of two blocks: rows A and B come to
    three tiles once both hold two blocks."""
    params = tfm.init_params(jax.random.PRNGKey(0), CFG3)
    rng = np.random.default_rng(3)
    shared = rng.integers(1, CFG3.vocab_size, BT)
    prompts = [np.concatenate([shared,
                               rng.integers(1, CFG3.vocab_size, n)])
               for n in (5, 9)]
    new = 6
    tables = jnp.asarray([[3, 5, 0, 0], [3, 7, 0, 0], [0, 0, 0, 0]],
                         jnp.int32)
    kb0, vb0 = _noise_banks(4)

    chunk = jax.jit(lambda kb, vb, toks, start, length, table:
                    gen.prefill_chunk_banks(params, toks, start, length,
                                            CFG3, {"k": kb, "v": vb},
                                            table)[:2])

    def prefill(kb, vb, row, start):
        toks = np.zeros((1, 32), np.int32)
        n = len(prompts[row]) - start
        toks[0, :n] = prompts[row][start:]
        lg, banks = chunk(kb, vb, jnp.asarray(toks), jnp.int32(start),
                          jnp.int32(n), tables[row])
        return np.asarray(lg[0]), banks["k"], banks["v"]

    lg_a, kb, vb = prefill(kb0, vb0, 0, 0)
    shared_k, shared_v = np.asarray(kb[:, 3]), np.asarray(vb[:, 3])
    # Row B's first block is row A's: a prefix hit prefills from BT on.
    lg_b, kb, vb = prefill(kb, vb, 1, BT)

    @jax.jit
    def step(kb, vb, tok, pos, active, blocks=None):
        wr_b = jnp.where(active, tables[jnp.arange(3), pos // BT], 0)
        lg, banks, _ = gen.decode_step_banks(
            params, tok, pos, CFG3, {"k": kb, "v": vb}, tables, wr_b,
            pos % BT, live_list=blocks)
        kb, vb = banks["k"], banks["v"]
        nxt = jnp.where(active, jnp.argmax(lg, -1).astype(jnp.int32), 0)
        return kb, vb, lg, nxt, jnp.where(active, pos + 1, pos)

    if attend == "blocks":
        plain_step = step

        def step(kb, vb, tok, pos, active):
            blocks = gen.live_block_list(
                np.asarray(tables), np.asarray(pos) // BT + 1,
                np.asarray(active), BT, tile=2)
            return plain_step(kb, vb, tok, pos, active, blocks)

    active = jnp.asarray([True, True, False])
    logits = [np.stack([lg_a, lg_b, lg_b])]
    tok = jnp.asarray([lg_a.argmax(), lg_b.argmax(), 0], jnp.int32)
    pos = jnp.asarray([len(prompts[0]), len(prompts[1]), 0], jnp.int32)
    outs = [np.asarray(tok)]
    for _ in range(new - 1):
        kb, vb, lg, tok, pos = step(kb, vb, tok, pos, active)
        logits.append(np.asarray(lg))
        outs.append(np.asarray(tok))
    outs, logits = np.stack(outs, axis=1), np.stack(logits, axis=1)
    ref_prefill = jax.jit(lambda p: gen.prefill(
        params, p, CFG3, gen.init_cache(CFG3, 1, max_seq=64)))
    ref_step = jax.jit(lambda tok, pos, cache: gen.decode_step(
        params, tok, pos, CFG3, cache))
    for row, p in enumerate(prompts):
        p = jnp.asarray(p, jnp.int32)[None]
        want = np.asarray(gen.generate(params, CFG3, p, new))[0]
        np.testing.assert_array_equal(outs[row], want,
                                      err_msg=f"row {row}")
        lg, cache = ref_prefill(p)
        for j in range(new):
            np.testing.assert_allclose(
                logits[row, j], np.asarray(lg[0]), rtol=1e-5, atol=1e-5,
                err_msg=f"row {row} step {j}")
            lg, cache = ref_step(jnp.asarray(want[j:j + 1]),
                                 jnp.int32(p.shape[1] + j), cache)
    assert (outs[2] == 0).all()
    for got, was, shared_rows in ((kb, kb0, shared_k),
                                  (vb, vb0, shared_v)):
        got = np.asarray(got)
        np.testing.assert_array_equal(got[:, 3], shared_rows)
        untouched = [b for b in range(N_BLOCKS) if b not in (0, 3, 5, 7)]
        np.testing.assert_array_equal(got[:, untouched],
                                      np.asarray(was)[:, untouched])


def _rows(lengths, nb, shared=0, seed=0):
    """Tables for rows of ``lengths`` tokens (0: an inactive lane) over
    distinct blocks in shuffled order, the first ``shared`` blocks of
    every live row being row 0's."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths)
    nalloc = -(-lengths // BT)
    free = list(rng.permutation(np.arange(1, 1 + int(nalloc.sum()))))
    tables = np.zeros((len(lengths), nb), np.int32)
    for b, n in enumerate(nalloc):
        tables[b, :n] = [free.pop() for _ in range(n)]
        tables[b, :min(shared, n)] = tables[0, :min(shared, n)]
    return tables, nalloc.astype(np.int32), lengths > 0


LIVE_CASES = {
    # name: (row lengths, blocks of reach, shared blocks, tile)
    "ragged-len1-fullreach-inactive": ((1, 6 * BT, 37, 0, 50), 6, 0, 4),
    "shared-first-blocks": ((3 * BT + 5, 2 * BT + 1, 2 * BT), 6, 2, 4),
    "ends-on-a-tile-boundary": ((2 * BT, BT + 3, 4 * BT), 4, 0, 4),
    "spans-three-tiles": ((3 * BT + 1, 2 * BT, BT + 9, 7), 4, 0, 4),
    "one-block-a-tile": ((BT + 1, 5), 2, 0, 1),
    "one-tile-holds-all": ((40, 0, 17), 4, 0, None),
}


@pytest.mark.parametrize("extra_tiles", [0, 2])
@pytest.mark.parametrize("case", list(LIVE_CASES))
def test_live_block_attention_matches_the_table_gather(case,
                                                       extra_tiles):
    """``_live_block_attention`` over ``live_block_list``'s list
    against ``_paged_attention_gather`` through the tables, float32 to
    1e-5, in one compiled program whatever the trip count. The list
    names each live row's allocated blocks once, in row order, and
    nothing else; tiles asked beyond those filled (``extra_tiles``:
    all padding, the trash block under an owner no lane has) change
    nothing; an inactive lane reads zeros."""
    lengths, nb, shared, tile = LIVE_CASES[case]
    tables, nalloc, active = _rows(lengths, nb, shared)
    B, Kh, G, Dh = len(lengths), 2, 2, 8
    n_blocks = 1 + int(nalloc.sum())
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    kc = jax.random.normal(k1, (n_blocks, BT, Kh, Dh), jnp.float32)
    vc = jax.random.normal(k2, (n_blocks, BT, Kh, Dh), jnp.float32)
    q = jax.random.normal(k3, (B, 1, Kh * G, Dh), jnp.float32)
    limits = jnp.asarray(np.maximum(lengths, 1), jnp.int32)

    lst, n_tiles = gen.live_block_list(tables, nalloc, active, BT,
                                       tile=tile)
    width = lst.shape[2]
    n = int(nalloc[active].sum())
    assert lst.shape[1] * width >= B * nb
    assert int(n_tiles) == -(-n // width)
    if case == "ends-on-a-tile-boundary":
        assert n == int(n_tiles) * width
    if case == "spans-three-tiles":
        assert int(n_tiles) == 3
    ids, owner, first = lst.reshape(3, -1)
    want = [(int(tables[b, j]), b, j * BT) for b in range(B)
            if active[b] for j in range(nalloc[b])]
    assert list(zip(ids[:n], owner[:n], first[:n])) == want
    assert (ids[n:] == 0).all() and (owner[n:] == B).all()

    attend = jax.jit(lambda q, kc, vc, lst, n_tiles, limits:
                     gen._live_block_attention(q, kc, vc, 0,
                                               (lst, n_tiles), limits))
    asked = jnp.int32(min(int(n_tiles) + extra_tiles, lst.shape[1]))
    got = np.asarray(attend(q, kc, vc, jnp.asarray(lst), asked, limits))
    ref = np.asarray(gen._paged_attention_gather(
        q, kc, vc, jnp.asarray(tables), limits, None))
    np.testing.assert_allclose(got[active], ref[active], rtol=1e-5,
                               atol=1e-5)
    assert (got[~active] == 0).all()
    assert attend._cache_size() == 1


@pytest.mark.parametrize("program", ["decode", "chunk", "verify",
                                     "draft"])
def test_paged_write_lands_in_its_own_layer_rows_only(program):
    """Each paged program changes exactly the rows ``[l, wr_b, wr_o]``
    of every layer ``l`` — every other row of every layer's bank stays
    bit-identical — and a layer writes its OWN K/V there (the rows
    differ from layer to layer)."""
    params = tfm.init_params(jax.random.PRNGKey(0), CFG3)
    kb0, vb0 = _noise_banks(5)
    i32 = jnp.int32
    tables = jnp.asarray([[2, 4, 0, 0], [6, 9, 0, 0]], i32)
    pos0 = jnp.asarray([3, 20], i32)
    tok = jnp.asarray([11, 12], i32)
    W = 3
    ap = pos0[:, None] + jnp.arange(W)[None, :]
    wr_b = jnp.take_along_axis(tables, ap // BT, axis=1)  # (2, W)
    wr_o = ap % BT
    if program == "decode":
        wr_b, wr_o = wr_b[:, :1], wr_o[:, :1]
        _, banks, _ = gen.decode_step_banks(
            params, tok, pos0, CFG3, {"k": kb0, "v": vb0}, tables,
            wr_b[:, 0], wr_o[:, 0])
        kb, vb = banks["k"], banks["v"]
    elif program == "chunk":
        # One sequence: positions 20..24 of table 1, 3 pads to trash.
        n = 5
        wr_b = jnp.concatenate([jnp.full((n,), 9, i32),
                                jnp.zeros((3,), i32)])[None]
        wr_o = ((20 + jnp.arange(8)) % BT)[None]
        _, banks, _ = gen.prefill_chunk_banks(
            params, jnp.ones((1, 8), i32), i32(20), i32(n), CFG3,
            {"k": kb0, "v": vb0}, tables[1])
        kb, vb = banks["k"], banks["v"]
    elif program == "verify":
        toks = jnp.asarray([[11, 5, 6], [12, 7, 8]], i32)
        _, kb, vb = gen.verify_step_paged(params, toks, pos0, CFG3, kb0,
                                          vb0, tables, wr_b, wr_o)
    else:
        keys = jnp.zeros((2, 2), jnp.uint32)
        z = jnp.zeros((2,), i32)
        _, _, kb, vb = gen.draft_propose_paged(
            params, tok, pos0, CFG3, kb0, vb0, tables, wr_b, wr_o, keys,
            z, jnp.zeros((2,), jnp.float32), z,
            jnp.ones((2,), jnp.float32), n_steps=W, sampled=False)
    written = np.zeros((N_BLOCKS, BT), bool)
    written[np.asarray(wr_b), np.asarray(wr_o)] = True
    for got, was in ((kb, kb0), (vb, vb0)):
        got, was = np.asarray(got), np.asarray(was)
        np.testing.assert_array_equal(got[:, ~written], was[:, ~written])
        rows = got[:, written]            # (L, n_written, Kh, Dh)
        assert (rows != was[:, written]).any(axis=(2, 3)).all()
        for a in range(CFG3.n_layers):
            for b in range(a + 1, CFG3.n_layers):
                assert (rows[a] != rows[b]).any(axis=(1, 2)).all()
