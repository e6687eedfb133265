"""Paged KV-cache serving engine (ISSUE 9): block pool invariants,
paged-vs-contiguous greedy parity, prefix reuse skipping prefill,
chunked-prefill stall bounds, continuous-path sampling parity, typed
admission sheds + the serve.admit chaos seam, and the gateway's
pool-exhaustion / prefix-affinity load signals."""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu import chaos
from ptype_tpu.chaos import FaultPlan, FaultSpec
from ptype_tpu.errors import ShedError
from ptype_tpu.models import generate as gen
from ptype_tpu.models import transformer as tfm
from ptype_tpu.serve_engine import (BlockPool, PagedGeneratorActor,
                                    block_hashes, prefix_affinity_key)

CFG = tfm.preset("tiny", dtype=jnp.float32)
RNG = np.random.default_rng(7)


def _prompt(n, rng=RNG):
    return jnp.asarray(rng.integers(1, CFG.vocab_size, n),
                       jnp.int32)[None]


# ------------------------------------------------------- pool (unit)


def test_block_pool_refcount_reuse_eviction_invariants():
    pool = BlockPool(CFG, n_blocks=5, block_tokens=16)  # 4 usable
    assert pool.capacity == 4 and pool.free_blocks() == 4
    # Reservation gates admission; acquisitions consume it.
    assert pool.try_reserve(3)
    assert pool.free_blocks() == 1
    assert not pool.try_reserve(2)  # over-commit refused
    a, b = pool.alloc(), pool.alloc()
    toks = list(range(16))
    h = block_hashes(toks, 16)[0]
    pool.seal(a, h, toks)
    assert pool.lookup(h, toks) == a
    # Content verified: a colliding hash with different tokens misses.
    assert pool.lookup(h, list(range(1, 17))) is None
    # Deref a hashed block → cached (still reusable), unhashed → free.
    pool.deref(a)
    pool.deref(b)
    assert pool.lookup(h, toks) == a  # cached, still addressable
    pool.unreserve(1)
    assert pool.check_invariants() == []
    # Re-ref from cache consumes a reservation, leaves the LRU.
    assert pool.try_reserve(1)
    pool.ref(a)
    st = pool.stats()
    assert st["kv_used_blocks"] == 1 and st["kv_cached_blocks"] == 0
    pool.deref(a)
    # Exhaust the free list: the next allocs evict LRU cached blocks
    # and their hashes leave the index.
    assert pool.try_reserve(4)
    got = [pool.alloc() for _ in range(4)]
    assert a in got  # the cached block was reclaimed
    assert pool.lookup(h, toks) is None
    assert pool.evictions >= 1
    for bid in got:
        pool.deref(bid)
    assert pool.check_invariants() == []
    assert pool.free_blocks() == 4


def test_block_pool_rejects_misaligned_block_tokens():
    with pytest.raises(ValueError, match="divide"):
        BlockPool(CFG, n_blocks=4, block_tokens=12)


def test_block_hash_chain_commits_to_whole_prefix():
    t1 = list(RNG.integers(1, 200, 48))
    h1 = block_hashes(t1, 16)
    assert len(h1) == 3
    # Same prefix → same chain; a flip in block 0 changes EVERY hash.
    assert block_hashes(t1 + [5, 6], 16) == h1  # partial tail ignored
    t2 = list(t1)
    t2[0] ^= 1
    h2 = block_hashes(t2, 16)
    assert all(x != y for x, y in zip(h1, h2))
    # A flip in block 1 keeps h[0], changes h[1:] (chain property).
    t3 = list(t1)
    t3[20] ^= 1
    h3 = block_hashes(t3, 16)
    assert h3[0] == h1[0] and h3[1] != h1[1] and h3[2] != h1[2]
    # The gateway affinity key is the FIRST block's chain hash.
    assert prefix_affinity_key(t1, 16) == f"kv:{h1[0]:08x}"
    assert prefix_affinity_key(t1[:15], 16) is None


# ---------------------------------------------- parity (acceptance)


def test_paged_engine_matches_contiguous_greedy_token_for_token():
    """THE parity bar: concurrent mixed-length greedy requests through
    the paged engine — including mid-decode joins — each match the
    contiguous compiled decode (gen.generate) exactly."""
    actor = PagedGeneratorActor(CFG, n_slots=4, block_tokens=16,
                                prefill_chunk=24)
    try:
        lens = (3, 17, 5, 33, 4, 21)
        news = (6, 12, 9, 5, 10, 7)
        prompts = [_prompt(n) for n in lens]
        outs = [None] * len(prompts)

        def call(i, delay):
            time.sleep(delay)  # staggered joins: mid-flight admission
            outs[i] = actor.Generate(prompts[i], news[i])

        threads = [threading.Thread(target=call,
                                    args=(i, 0.05 * (i % 3)))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, p in enumerate(prompts):
            want = gen.generate(actor.params, CFG, p, news[i])
            np.testing.assert_array_equal(np.asarray(outs[i]),
                                          np.asarray(want),
                                          err_msg=f"req {i}")
        info = actor.Info()
        assert info["max_live_slots"] >= 2, info
        assert actor.pool.check_invariants() == []
        # Everything retired: pool fully reclaimable again.
        assert info["kv_used_blocks"] == 0
    finally:
        actor.close()


def test_decode_attends_over_the_live_rows_blocks(monkeypatch,
                                                  jitwatch_watchdog):
    """ISSUE 29: rows are admitted, cross block boundaries and retire
    while others decode (tiles of two blocks, so the step's trip count
    walks from 1 up). Greedy tokens equal solo ``generate()`` token for
    token; after EVERY rebuild the list names exactly the live rows'
    allocated blocks, row after row, under their lanes and first
    positions; and the step compiled once for all the trip counts."""
    jw = jitwatch_watchdog
    monkeypatch.setattr(gen, "LIVE_TILE_BLOCKS", 2)
    build, seen, wrong = gen.live_block_list, [], []
    holder = {}

    def checked(tables, nalloc, active, bt, tile=None, own_tiles=False):
        assert not own_tiles  # a GQA engine's rows lie end to end
        lst, n_tiles = build(tables, nalloc, active, bt, tile)
        eng = holder["actor"]
        want = [(bid, slot, j * bt)
                for slot in sorted(eng._slot_state) if active[slot]
                for j, bid in enumerate(eng._slot_state[slot].table)]
        ids, owner, first = lst.reshape(3, -1)
        got = list(zip(ids[:len(want)], owner[:len(want)],
                       first[:len(want)]))
        if (got != want or (owner[len(want):] != eng.n_slots).any()
                or (ids[len(want):] != 0).any()
                or int(n_tiles) != -(-len(want) // lst.shape[2])):
            wrong.append((want, lst, n_tiles))
        seen.append(int(n_tiles))
        return lst, n_tiles

    monkeypatch.setattr(gen, "live_block_list", checked)
    before = jw.compiles().get("engine_step", 0)
    actor = holder["actor"] = PagedGeneratorActor(
        CFG, n_slots=4, block_tokens=16, prefill_chunk=24)
    try:
        solo = _prompt(4)
        np.testing.assert_array_equal(       # one row, one block: 1 tile
            np.asarray(actor.Generate(solo, 5)),
            np.asarray(gen.generate(actor.params, CFG, solo, 5)))
        lens = (3, 14, 30, 33, 12, 47)   # 14+9, 30+7, 12+10, 47+6 cross
        news = (6, 9, 7, 16, 10, 6)      # a block boundary decoding
        prompts = [_prompt(n) for n in lens]
        outs = [None] * len(prompts)

        def call(i):
            time.sleep(0.04 * (i % 3))
            outs[i] = actor.Generate(prompts[i], news[i])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(
                np.asarray(outs[i]),
                np.asarray(gen.generate(actor.params, CFG, p, news[i])),
                err_msg=f"req {i}")
        assert not wrong, wrong[0]
        assert actor.Info()["max_live_slots"] >= 3
        assert 1 in seen and max(seen) >= 4, sorted(set(seen))
        assert jw.compiles()["engine_step"] - before == 1, jw.compiles()
        assert actor.pool.check_invariants() == []
    finally:
        actor.close()


def test_ledger_counts_the_block_lists_tiles():
    """One short row: every decode step runs one tile over one listed
    block, and ``kv_tile_fill`` is the tokens attended over the tokens
    the tiles covered. The dispatch span carries the two counts."""
    from ptype_tpu import trace

    rec = trace.enable("kv-list-test")
    actor = PagedGeneratorActor(CFG, n_slots=2, block_tokens=16)
    try:
        actor.Generate(_prompt(5), 8)    # 7 decode steps at pos 5..11
        summ = actor.ledger.summary()
        covered = 2 * actor.nb * 16      # the one tile: every lane's reach
        assert summ["kv_blocks"] == 1.0 and summ["kv_tiles"] == 1.0
        assert summ["kv_tile_fill"] == round(
            sum(range(6, 13)) / (7 * covered), 4)
        spans = [s for s in rec.spans()
                 if s.name == "serve.step/dispatch"]
        assert len(spans) == 7
        assert all(s.attrs == {"kv_blocks": 1, "kv_tiles": 1}
                   for s in spans)
    finally:
        trace.disable()
        actor.close()


LATENT = dataclasses.replace(
    tfm.preset("tiny", n_kv_heads=2, dtype=jnp.float32),
    latent=tfm.LatentAttention(
        q_rank=16, kv_rank=8, nope_dim=6, rope_dim=2, v_dim=8,
        index_heads=2, index_dim=4, index_topk=8, index_rope_dim=2))


def _solo_latent(params, prompt, new):
    """One prompt alone through the paged programs, no list: one chunk,
    then ``new - 1`` decode steps over a one-lane table."""
    n, bt = prompt.shape[1], 16
    nb = LATENT.max_seq // bt
    banks = {name: jnp.zeros((LATENT.n_layers, nb + 1, bt) + w,
                             jnp.float32)
             for name, w in tfm.cache_spec(LATENT).items()}
    table = jnp.arange(1, nb + 1, dtype=jnp.int32)
    toks = jnp.zeros((1, 64), jnp.int32).at[:, :n].set(prompt)
    lg, banks, _ = gen.prefill_chunk_banks(
        params, toks, jnp.int32(0), jnp.int32(n), LATENT, banks, table)
    out = [int(jnp.argmax(lg[0]))]
    for pos in range(n, n + new - 1):
        lg, banks, _ = gen.decode_step_banks(
            params, jnp.asarray(out[-1:], jnp.int32), jnp.asarray([pos]),
            LATENT, banks, table[None], table[None][:, pos // bt],
            jnp.asarray([pos % bt]))
        out.append(int(jnp.argmax(lg[0])))
    return out


def test_latent_decode_runs_over_the_live_lanes(monkeypatch,
                                                jitwatch_watchdog):
    """ISSUE 32, the block-list test on a latent engine: rows are
    admitted and retire while others decode (tiles of two lanes over
    four slots, so the trip count walks 0..2). Greedy tokens equal each
    prompt's solo run through the paged programs; after EVERY rebuild
    the list names exactly the live slots, in slot order, padded with
    ``n_slots``; the step compiled once for all the trip counts; and
    the ledger and the dispatch span carry the list's counts."""
    from ptype_tpu import trace

    jw = jitwatch_watchdog
    monkeypatch.setattr(gen, "LIVE_TILE_LANES", 2)
    build, seen, wrong = gen.live_lane_list, [], []
    holder = {}

    def checked(active, tile=None):
        lst, n_tiles = build(active, tile)
        eng = holder["actor"]
        want = [s for s in sorted(eng._slot_state) if active[s]]
        flat = lst.reshape(-1)
        if (lst.shape != (2, 2) or flat[:len(want)].tolist() != want
                or (flat[len(want):] != eng.n_slots).any()
                or int(n_tiles) != -(-len(want) // 2)):
            wrong.append((want, lst, n_tiles))
        seen.append(int(n_tiles))
        return lst, n_tiles

    monkeypatch.setattr(gen, "live_lane_list", checked)
    before = jw.compiles().get("engine_step", 0)
    rec = trace.enable("lane-list-test")
    actor = holder["actor"] = PagedGeneratorActor(
        LATENT, n_slots=4, block_tokens=16, prefill_chunk=32)
    try:
        lens = (5, 20, 33, 9, 41, 12)
        news = (6, 9, 5, 12, 7, 10)
        rng = np.random.default_rng(11)
        prompts = [_prompt(n, rng) for n in lens]
        outs = [None] * len(prompts)

        def call(i):
            time.sleep(0.04 * (i % 3))
            outs[i] = actor.Generate(prompts[i], news[i])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, p in enumerate(prompts):
            assert np.asarray(outs[i])[0].tolist() == _solo_latent(
                actor.params, p, news[i]), f"req {i}"
        assert not wrong, wrong[0]
        assert actor.Info()["max_live_slots"] >= 3
        assert {1, 2} <= set(seen), sorted(set(seen))
        assert jw.compiles()["engine_step"] - before == 1, jw.compiles()
        assert actor.pool.check_invariants() == []
        summ = actor.ledger.summary()
        assert 1.0 <= summ["lane_tiles"] <= 2.0
        assert 0.5 <= summ["lane_tile_fill"] <= 1.0
        assert "kv_tiles" not in summ
        spans = [s for s in rec.spans()
                 if s.name == "serve.step/dispatch"]
        assert spans and all(
            set(s.attrs) == {"live_lanes", "lane_tiles"}
            and s.attrs["lane_tiles"] == -(-s.attrs["live_lanes"] // 2)
            for s in spans)
        steps = len(spans)
        assert summ["lane_tiles"] == round(
            sum(s.attrs["lane_tiles"] for s in spans) / steps, 3)
        assert summ["lane_tile_fill"] == round(
            sum(s.attrs["live_lanes"] for s in spans)
            / (2 * sum(s.attrs["lane_tiles"] for s in spans)), 4)
    finally:
        trace.disable()
        actor.close()


def test_sampled_single_row_rides_engine_with_exact_solo_parity():
    """The sampling satellite: temperature/top-k/top-p single-row
    requests ride the CONTINUOUS path (per-slot RNG keys folded into
    the engine step) and still match the solo path draw-for-draw —
    two run CONCURRENTLY to prove they co-batch without perturbing
    each other's streams."""
    actor = PagedGeneratorActor(CFG, n_slots=4, block_tokens=16)
    try:
        p1, p2 = _prompt(5), _prompt(9)
        kw1 = dict(temperature=0.7, seed=11, top_k=5, top_p=0.9)
        kw2 = dict(temperature=1.1, seed=3, top_k=0, top_p=0.8)
        steps0 = actor.Info()["engine_steps"]
        outs = [None, None]
        ts = [threading.Thread(
                 target=lambda: outs.__setitem__(
                     0, actor.Generate(p1, 8, **kw1))),
              threading.Thread(
                 target=lambda: outs.__setitem__(
                     1, actor.Generate(p2, 8, **kw2)))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        w1 = gen.generate(actor.params, CFG, p1, 8, 0.7,
                          jax.random.PRNGKey(11), top_k=5, top_p=0.9)
        w2 = gen.generate(actor.params, CFG, p2, 8, 1.1,
                          jax.random.PRNGKey(3), top_p=0.8)
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(w1))
        np.testing.assert_array_equal(np.asarray(outs[1]),
                                      np.asarray(w2))
        # They actually rode the engine, not the solo fallback.
        assert actor.Info()["engine_steps"] > steps0
    finally:
        actor.close()


def test_categorical_equals_gumbel_argmax_contract():
    """The RNG equivalence sample_token_rows' solo parity stands on:
    categorical(key, (1, V)) == argmax(logits + gumbel(key, (1, V))).
    If a jax upgrade changes categorical's internals, this fails
    before the engine's parity does."""
    key = jax.random.fold_in(jax.random.PRNGKey(11), 3)
    logits = jax.random.normal(jax.random.PRNGKey(0), (1, 64))
    want = jax.random.categorical(key, logits, axis=-1)
    got = jnp.argmax(logits + jax.random.gumbel(key, (1, 64)), axis=-1)
    assert int(want[0]) == int(got[0])


def test_stop_token_frees_slot_and_blocks_early():
    actor = PagedGeneratorActor(CFG, n_slots=2, block_tokens=16)
    try:
        prompt = jnp.zeros((1, 4), jnp.int32)
        max_new = 24
        solo = gen.generate(actor.params, CFG, prompt, max_new)
        stop = int(np.asarray(solo)[0, 2])
        out = actor.Generate(prompt, max_new, stop_token=stop,
                             pad_token=7)
        want = gen.generate(actor.params, CFG, prompt, max_new,
                            stop_token=stop, pad_token=7)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(want))
        info = actor.Info()
        assert info["engine_steps"] < max_new, (
            "stop token did not retire the slot early")
        assert info["kv_used_blocks"] == 0  # blocks came back
    finally:
        actor.close()


# --------------------------------------------------- prefix reuse


def test_prefix_hit_skips_prefill_engine_work_asserted():
    """An affinity-landed request whose prefix blocks are resident
    skips their prefill: hits > 0, and the second request's prefill
    token/chunk counts shrink to just its divergent tail — with exact
    greedy parity throughout (reused blocks ARE the same K/V)."""
    actor = PagedGeneratorActor(CFG, n_slots=4, block_tokens=16,
                                prefill_chunk=16)
    try:
        shared = np.asarray(RNG.integers(1, CFG.vocab_size, 48),
                            np.int32)
        p1 = jnp.asarray(np.concatenate(
            [shared, RNG.integers(1, CFG.vocab_size, 7)]).astype(
                np.int32))[None]
        p2 = jnp.asarray(np.concatenate(
            [shared, RNG.integers(1, CFG.vocab_size, 5)]).astype(
                np.int32))[None]
        o1 = actor.Generate(p1, 8)
        i1 = actor.Info()
        assert i1["prefix_hits"] == 0  # cold: nothing resident
        o2 = actor.Generate(p2, 8)
        i2 = actor.Info()
        # 48 shared tokens = 3 full blocks reused.
        assert i2["prefix_hits"] == 3, i2
        assert i2["prefix_hit_rate"] > 0
        # Prefill work asserted: request 2 prefilled ONLY its 5-token
        # tail (one chunk), not the 53-token prompt.
        assert i2["prefill_tokens"] - i1["prefill_tokens"] == 5
        assert i2["prefill_chunks"] - i1["prefill_chunks"] == 1
        for p, o in ((p1, o1), (p2, o2)):
            want = gen.generate(actor.params, CFG, p, 8)
            np.testing.assert_array_equal(np.asarray(o),
                                          np.asarray(want))
        assert actor.pool.check_invariants() == []
    finally:
        actor.close()


def test_prefix_cache_evicts_under_pressure_and_stays_sound():
    """A pool smaller than the working set: cached prefix blocks are
    evicted LRU to make room, counters tick, invariants hold, and
    every request still matches solo."""
    actor = PagedGeneratorActor(CFG, n_slots=2, block_tokens=16,
                                n_blocks=9, max_len=64)  # 8 usable
    try:
        prompts = [_prompt(33) for _ in range(4)]  # 3 blocks each
        for p in prompts:
            want = gen.generate(actor.params, CFG, p, 4)
            np.testing.assert_array_equal(
                np.asarray(actor.Generate(p, 4)), np.asarray(want))
        st = actor.pool.stats()
        assert st["kv_evictions"] > 0, st
        assert actor.pool.check_invariants() == []
        assert st["kv_used_blocks"] == 0
    finally:
        actor.close()


# ------------------------------------------- chunked prefill stall


def test_chunked_prefill_bounds_co_batched_decode_stall():
    """The interference bar: one long prompt admitted while a decode
    is live. Whole-prompt admission stalls the co-batched decode for
    the full prefill; chunked admission bounds the per-step stall to
    one chunk — measured by the engine's own stall meter, with the
    goodput ledger's serve-side prefill leg cross-checking."""
    from ptype_tpu.health.goodput import GoodputLedger

    # Big enough that per-chunk COMPUTE dominates dispatch (the tiny
    # preset is dispatch-bound on CPU — 96- vs 16-token prefills cost
    # the same there and the comparison measures scheduler noise).
    cfg = tfm.preset("tiny", d_model=256, n_layers=4, d_ff=512,
                     dtype=jnp.float32)
    long_p = jnp.asarray(RNG.integers(1, cfg.vocab_size, 96),
                         jnp.int32)[None]
    # Same length (same compiled shapes), DIFFERENT content: warming
    # with long_p itself would seal its blocks and the measured pass
    # would prefix-hit its way down to one tail chunk in both drives,
    # reducing the comparison to scheduler noise.
    warm_p = jnp.asarray(RNG.integers(1, cfg.vocab_size, 96),
                         jnp.int32)[None]
    short = jnp.zeros((1, 4), jnp.int32)

    def drive(prefill_chunk):
        actor = PagedGeneratorActor(cfg, n_slots=2, block_tokens=16,
                                    prefill_chunk=prefill_chunk)
        ledger = GoodputLedger(step_name="serve.step").install()
        stalls: list[float] = []
        rec0 = actor._record_stall
        actor._record_stall = lambda ms: (stalls.append(ms),
                                          rec0(ms))[-1]
        try:
            # Warm every chunk-bucket compile OFF the measured pass.
            actor.Generate(warm_p, 2)
            actor.Generate(short, 2)
            actor._max_stall_ms = 0.0
            stalls.clear()
            done = threading.Event()
            t = threading.Thread(target=lambda: (
                actor.Generate(short, 48), done.set()))
            t.start()
            while actor.Info()["live_slots"] < 1 and not done.is_set():
                time.sleep(0.002)
            out = actor.Generate(long_p, 4)
            t.join(timeout=120)
            meter = actor.Info()["prefill_stall_ms"]
            recs = ledger.records()
            return out, [s for s in stalls if s > 0.05], meter, recs
        finally:
            ledger.uninstall()
            actor.close()

    out_c, stalls_c, meter_c, recs = drive(16)
    out_w, stalls_w, meter_w, _ = drive(None)  # None → whole prompt
    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_w))
    # The acceptance inequality: bounded chunks beat the whole-prompt
    # stall with real margin (96 tokens vs 16-token chunks). The
    # chunked side is judged by its MEDIAN per-step stall — the
    # typical decode step's wait, robust to one OS-scheduler spike
    # poisoning the max on a shared CPU — against the whole-prompt
    # drive's biggest recorded stall (its long prefill; noise only
    # inflates it, which tightens the bar). The two drives run seconds
    # apart, so a sustained load shift between them can still invert
    # the comparison: re-drive BOTH sides (up to twice) only when the
    # bar is unmet rather than trusting one poisoned pair.
    for _ in range(2):
        if (len(stalls_c) >= 6
                and float(np.median(stalls_c)) < 0.75 * max(stalls_w)):
            break
        out_c, stalls_c, meter_c, recs = drive(16)
        out_w, stalls_w, meter_w, _ = drive(None)
        np.testing.assert_array_equal(np.asarray(out_c),
                                      np.asarray(out_w))
    stall_whole = max(stalls_w)
    stall_chunked = float(np.median(stalls_c))
    # Chunked admission interleaved: ≥ 96/16 bounded stalls, not one.
    assert len(stalls_c) >= 6, stalls_c
    assert stall_chunked < 0.75 * stall_whole, (stalls_c, stalls_w)
    # The engine's own meter carries the signal the bench exports.
    assert meter_w >= stall_whole - 0.01 and meter_c > 0
    # The ledger saw serve-side steps with a prefill leg.
    assert any(r["prefill_ms"] > 0 for r in recs), recs[-5:]


# ------------------------------------------------ admission sheds


def test_backlog_sheds_typed_with_retry_hint():
    actor = PagedGeneratorActor(CFG, n_slots=1, block_tokens=16,
                                max_queue=1)
    try:
        first_done = threading.Event()
        t = threading.Thread(target=lambda: (
            actor.Generate(jnp.zeros((1, 4), jnp.int32), 48),
            first_done.set()))
        t.start()
        deadline = time.monotonic() + 30
        while (actor.Info()["live_slots"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.002)
        # Slot busy: the next request QUEUES (cap 1)...
        t2 = threading.Thread(target=lambda: actor.Generate(
            jnp.zeros((1, 5), jnp.int32), 4))
        t2.start()
        deadline = time.monotonic() + 30
        while (actor.Info()["queue_depth"] < 1
               and not first_done.is_set()
               and time.monotonic() < deadline):
            time.sleep(0.002)
        # ... and the one after sheds TYPED with a retry hint. The
        # first request finishing between the check and the call drains
        # the queue and admits this one instead — a benign interleaving
        # on a loaded host, tolerated; anything else must shed typed.
        if not first_done.is_set():
            try:
                actor.Generate(jnp.zeros((1, 6), jnp.int32), 4)
                assert first_done.is_set(), \
                    "admitted with the backlog still full (expected ShedError)"
            except ShedError as e:
                assert e.retry_after_s > 0
        t.join(timeout=120)
        t2.join(timeout=120)
    finally:
        actor.close()

    # A request that can NEVER fit rejects loudly up front.
    tiny = PagedGeneratorActor(CFG, n_slots=1, block_tokens=16,
                               n_blocks=2, max_len=32)  # capacity 1
    try:
        with pytest.raises(ValueError, match="blocks"):
            tiny.Generate(jnp.zeros((1, 30), jnp.int32), 2)
    finally:
        tiny.close()


def test_pool_exhaustion_sheds_typed_after_admit_timeout():
    """A reserve-refused head-of-line request waits at most
    admit_timeout_s, then sheds TYPED (the frontdoor re-routes on
    that) — and admits normally once headroom returns."""
    actor = PagedGeneratorActor(CFG, n_slots=1, block_tokens=16,
                                admit_timeout_s=0.2)
    try:
        # Exhaust the pool from outside: every real reservation is
        # now refused, exactly the oversubscribed-pool regime.
        grabbed = actor.pool.free_blocks()
        assert actor.pool.try_reserve(grabbed)
        t0 = time.monotonic()
        with pytest.raises(ShedError, match="exhausted") as ei:
            actor.Generate(jnp.zeros((1, 4), jnp.int32), 4)
        assert ei.value.retry_after_s > 0
        assert time.monotonic() - t0 < 10  # bounded, not deadline-burn
        # Headroom back -> the same request admits and completes.
        actor.pool.unreserve(grabbed)
        out = actor.Generate(jnp.zeros((1, 4), jnp.int32), 4)
        assert out.shape == (1, 4)
        assert actor.Info()["admit_timeout_s"] == 0.2
    finally:
        actor.close()


def test_multirow_shed_leaves_no_orphaned_work():
    """When a multi-row request raises (one row shed at the admit
    timeout), its sibling rows are withdrawn: nothing keeps queuing or
    decoding output the caller will never read, and the pool drains."""
    # capacity 8 covers exactly ONE row's worst case (4 + 120 tokens
    # -> 8 blocks): row 0 admits, rows 1-2 queue and shed.
    actor = PagedGeneratorActor(CFG, n_slots=2, block_tokens=16,
                                n_blocks=9, admit_timeout_s=0.2)
    try:
        with pytest.raises(ShedError):
            actor.Generate(jnp.zeros((3, 4), jnp.int32), 120)
        s0 = actor.Info()["engine_steps"]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            info = actor.Info()
            if (info["live_slots"] == 0 and info["queue_depth"] == 0
                    and actor.pool.used_blocks() == 0):
                break
            time.sleep(0.01)
        info = actor.Info()
        assert info["live_slots"] == 0
        assert info["queue_depth"] == 0
        assert actor.pool.used_blocks() == 0
        # No withdrawn sibling decoded its 120 steps after the raise.
        assert actor.Info()["engine_steps"] - s0 < 60
    finally:
        actor.close()


def test_cancel_rows_retires_active_row_and_frees_blocks():
    """White-box: flagging a LIVE row via _cancel_rows makes the
    engine retire it at the next boundary and free its blocks."""
    actor = PagedGeneratorActor(CFG, n_slots=1, block_tokens=16)
    try:
        t = threading.Thread(target=lambda: np.asarray(
            actor.Generate(jnp.zeros((1, 4), jnp.int32), 120)))
        t.start()
        deadline = time.monotonic() + 30
        while (actor.Info()["live_slots"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.002)
        slot = int(np.flatnonzero(actor._active)[0])
        row = actor._slot_state[slot]
        actor._cancel_rows([row])
        deadline = time.monotonic() + 30
        while (actor.pool.used_blocks() > 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert actor.pool.used_blocks() == 0
        assert len(row.emitted) < 120  # retired early, not run out
        t.join(timeout=120)
    finally:
        actor.close()


def test_serve_admit_chaos_seam_sheds_and_pairs():
    """The serve.admit seam: a planned fault forces a typed shed with
    a retry hint; the next successful admission beacons recovery
    (unrecovered() drains to empty)."""
    actor = PagedGeneratorActor(CFG, n_slots=2, block_tokens=16)
    plan = chaos.arm(FaultPlan([
        FaultSpec("serve.admit", "shed", times=1),
        FaultSpec("serve.admit", "delay", after=1, times=1,
                  delay_s=0.01),
    ], seed=1, name="serve-admit"))
    try:
        with pytest.raises(ShedError) as ei:
            actor.Generate(jnp.zeros((1, 4), jnp.int32), 4)
        assert ei.value.retry_after_s > 0
        out = actor.Generate(jnp.zeros((1, 4), jnp.int32), 4)
        assert np.asarray(out).shape == (1, 4)
        # Pairing is one success per outstanding fault: the delayed
        # call's own beacon paired the delay; one more clean admission
        # pairs the shed.
        actor.Generate(jnp.zeros((1, 4), jnp.int32), 2)
        assert [e.site for e in plan.fired()] == ["serve.admit",
                                                  "serve.admit"]
        assert chaos.unrecovered() == {}, plan.trace()
    finally:
        chaos.disarm()
        actor.close()


# ------------------------------------------------ gateway signals


def test_gateway_affinity_yields_when_replica_pool_exhausted(coord):
    """The load-signal satellite: probes pick up kv_free_blocks /
    prefix_hit_rate from Info(), and prefix affinity YIELDS when the
    pinned replica's pool is exhausted (an affinity hit that sheds is
    worse than a cold miss elsewhere)."""
    import test_gateway as tg
    from ptype_tpu.registry import CoordRegistry

    registry = CoordRegistry(coord, lease_ttl=1.0)
    actors, servers, regs = tg._fleet(registry, "llm-kv", [0.0, 0.0])
    gw = tg._gateway(registry, "llm-kv")
    try:
        assert tg._wait_healthy(gw, 2)
        # Freeze probing: a probe RTT spike under full-suite CPU load
        # would overwrite the pinned latency signals and make affinity
        # yield for the wrong reason. (Monkeypatch, then let any
        # in-flight round drain.)
        gw.pool.probe_now = lambda: None
        time.sleep(0.3)
        # Fake paged-engine load reports (the fleet is fake actors;
        # the pool only sees Info payloads either way).
        key = "kv:deadbeef"
        stable = sorted(gw.pool.healthy(), key=lambda r: r.key)
        from ptype_tpu.rpc import fnv32a

        pinned = stable[fnv32a(key) % len(stable)]
        other = next(r for r in stable if r is not pinned)
        for r, free in ((pinned, 17), (other, 9)):
            with r.lock:
                r.reported = dict(r.reported, kv_free_blocks=free,
                                  prefix_hit_rate=0.5)
                r.ewma_ms = r.probe_ms = 1.0  # equal latency signals
        assert gw.pool.pick(affinity_key=key) is pinned
        snap = pinned.snapshot()
        assert snap["kv_free_blocks"] == 17
        assert snap["prefix_hit_rate"] == 0.5
        # Exhaust the pinned replica's pool: affinity yields.
        with pinned.lock:
            pinned.reported = dict(pinned.reported, kv_free_blocks=0)
        assert gw.pool.pick(affinity_key=key) is other
        # Headroom back → affinity pins again.
        with pinned.lock:
            pinned.reported = dict(pinned.reported, kv_free_blocks=3)
        assert gw.pool.pick(affinity_key=key) is pinned
    finally:
        gw.close()
        for r in regs:
            r.close()
        for s in servers:
            s.close()


def test_gateway_shared_prefix_workload_earns_hits_on_affinity_replica(
        coord):
    """Acceptance shape: a shared-prefix workload routed with
    prefix_affinity_key through the gateway lands every request on
    ONE replica, whose prefix-cache hit counters move — the OTHER
    replica stays cold (affinity is what turns routing into cache
    hits)."""
    import test_gateway as tg
    from ptype_tpu.actor import ActorServer
    from ptype_tpu.registry import CoordRegistry

    registry = CoordRegistry(coord, lease_ttl=1.0)
    base = PagedGeneratorActor(CFG, n_slots=4, block_tokens=16)
    twin = PagedGeneratorActor(CFG, params=base.params, n_slots=4,
                               block_tokens=16)
    actors, servers, regs = [base, twin], [], []
    for i, a in enumerate(actors):
        s = ActorServer("127.0.0.1", 0)
        s.register(a, "Generator")
        s.serve()
        servers.append(s)
        regs.append(registry.register("llm-paged", f"r{i}",
                                      "127.0.0.1", s.port))
    gw = tg._gateway(registry, "llm-paged", per_replica_inflight=4)
    try:
        assert tg._wait_healthy(gw, 2)
        shared = np.asarray(RNG.integers(1, CFG.vocab_size, 48),
                            np.int32)
        key = prefix_affinity_key(shared, 16)
        assert key is not None
        for i in range(3):
            tail = RNG.integers(1, CFG.vocab_size, 3 + i)
            p = jnp.asarray(np.concatenate([shared, tail]).astype(
                np.int32))[None]
            out = gw.generate(p, 4, affinity_key=key)
            assert np.asarray(out).shape == (1, 4)
        hits = [a.Info()["prefix_hits"] for a in actors]
        # One replica took the whole affinity stream and HIT; the
        # other never saw the prefix.
        assert sorted(hits)[-1] > 0, hits
        assert sorted(hits)[0] == 0, hits
    finally:
        gw.close()
        for r in regs:
            r.close()
        for s in servers:
            s.close()
        for a in actors:
            a.close()


def test_gateway_reroutes_replica_shed_without_evicting(coord):
    """A replica-side typed shed (serve.admit / pool exhausted) is a
    ROUTING signal, not a failure: the gateway re-routes to a sibling
    with headroom, answers the request, and the shedding replica is
    neither evicted nor error-counted."""
    import test_gateway as tg
    from ptype_tpu.actor import ActorServer
    from ptype_tpu.registry import CoordRegistry

    class _Shedder:
        calls = 0

        def Generate(self, prompt, max_new_tokens=8, *a):
            type(self).calls += 1
            raise ShedError("pool exhausted", retry_after_s=0.25)

        def Info(self):
            return {"in_flight": 0, "queue_depth": 0,
                    "kv_free_blocks": 0}

    registry = CoordRegistry(coord, lease_ttl=1.0)
    healthy = tg._FakeGen(name="ok")
    actors = [_Shedder(), healthy]
    servers, regs = [], []
    for i, a in enumerate(actors):
        s = ActorServer("127.0.0.1", 0)
        s.register(a, "Generator")
        s.serve()
        servers.append(s)
        regs.append(registry.register("llm-shed", f"r{i}",
                                      "127.0.0.1", s.port))
    gw = tg._gateway(registry, "llm-shed")
    try:
        assert tg._wait_healthy(gw, 2)
        served = 0
        for _ in range(6):
            out = gw.generate(tg.PROMPT, 8)
            assert np.asarray(out).shape == (1, 8)
            served += 1
        assert served == 6
        # The shedder answered typed at least once and is still a
        # healthy, routable member (no eviction pressure).
        assert gw.pool.n_healthy() == 2
    finally:
        gw.close()
        for r in regs:
            r.close()
        for s in servers:
            s.close()


# -------------------------------------------------- goodput leg


def test_goodput_ledger_attributes_serve_prefill_leg():
    from ptype_tpu.health.goodput import GoodputLedger

    led = GoodputLedger(step_name="serve.step")
    with led.region("serve.step"):
        time.sleep(0.005)
    with led.region("serve.prefill"):
        time.sleep(0.02)
    with led.region("serve.step"):
        time.sleep(0.005)
    rec = led.records()[-1]
    # The chunk is attributed to the prefill leg AND deducted from
    # stall — bounded-stall is a measured number, not a vibe.
    assert rec["prefill_ms"] >= 15, rec
    assert rec["stall_ms"] < rec["prefill_ms"], rec
    assert led.summary()["step_breakdown"]["prefill_ms"] > 0


# ------------------------------------- dispatch discipline (ISSUE 15)


def test_steady_state_decode_compiles_nothing_armed(jitwatch_watchdog):
    """The armed serve tier: after one full warmup request (prefill
    chunks + decode steps + sampling), a steady stream of same-shaped
    requests compiles NOTHING — the engine's device mirrors and
    cached programs re-dispatch, never re-trace — and the hot region's
    transfer guard held (an unsanctioned implicit transfer inside the
    decode step would have raised, failing the drive)."""
    jw = jitwatch_watchdog
    actor = PagedGeneratorActor(CFG, n_slots=2, block_tokens=16)
    try:
        p = _prompt(5)
        warm = np.asarray(actor.Generate(p, 8))
        jw.mark_steady()
        for _ in range(3):
            out = np.asarray(actor.Generate(p, 8))
            np.testing.assert_array_equal(out, warm)
        assert jw.recompiles_since_steady() == {}, \
            jw.recompiles_since_steady()
        assert jw.report()["hot_regions"] > 0  # the guard was LIVE
        assert jw.recompiles() == {} and jw.storms() == []
    finally:
        actor.close()


def test_placed_replica_lives_on_its_device_and_compiles_its_step_once(
        jitwatch_watchdog):
    """``device=``: params and banks are committed there, output
    matches a default-placed replica token for token, and the decode
    step compiles ONCE — its own committed outputs feed the next step,
    so the first upload must carry the same commitment (on the chip an
    uncommitted first upload cost every replica a second compile in the
    middle of its first request)."""
    jw = jitwatch_watchdog
    dev = jax.devices()[3]
    p = _prompt(5)
    plain = PagedGeneratorActor(CFG, n_slots=2)
    try:
        want = np.asarray(plain.Generate(p, 6))
    finally:
        plain.close()
    before = jw.compiles().get("engine_step", 0)
    actor = PagedGeneratorActor(CFG, n_slots=2, device=dev)
    try:
        got = np.asarray(actor.Generate(p, 6))
        homes = actor.pool.k.devices() | actor.pool.v.devices()
        for leaf in jax.tree.leaves(actor.params):
            homes |= leaf.devices()
        assert homes == {dev}
        np.testing.assert_array_equal(got, want)
        assert jw.compiles()["engine_step"] - before == 1, jw.compiles()
    finally:
        actor.close()
