"""Speculative decoding on the paged engine (ISSUE 12): greedy
speculative output bit-identical to the non-speculative engine
(co-batched ragged accept lengths, mid-decode joins, stop-token early
retire mid-window), the exact-distribution acceptance-sampling
contract (statistical, vs jax.random.categorical from the target —
the PR 9 solo-parity family extended), the BlockPool reservation
audit covering the worst-case k-token advance under pool pressure,
adaptive-k backoff, the serve.spec chaos seam, and the gateway /
`obs serve` accept-rate plumbing."""

import threading
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu import chaos
from ptype_tpu.chaos import FaultPlan, FaultSpec
from ptype_tpu.models import generate as gen
from ptype_tpu.models import transformer as tfm
from ptype_tpu.serve_engine import (BlockPool, PagedGeneratorActor,
                                    SpecConfig)

CFG = tfm.preset("tiny", dtype=jnp.float32)
RNG = np.random.default_rng(11)


def _prompt(n, rng=RNG):
    return jnp.asarray(rng.integers(1, CFG.vocab_size, n),
                       jnp.int32)[None]


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda r: tfm.init_params(r, CFG))(
        jax.random.PRNGKey(0))


def _hostile_draft(params):
    """A draft that NEVER agrees with the target: untied head rolled
    one vocab slot, so it systematically proposes (target pick − 1).
    (A random-init tied-embedding model echoes its input token —
    embed·embed self-similarity — so any same-embedding draft would
    trivially agree; the roll breaks that.) Greedy speculation must
    stay bit-identical even against this — every window commits one
    corrected token."""
    emb = np.asarray(params["embed"])
    dp = dict(params, lm_head=jnp.asarray(np.roll(emb, -1, axis=0).T))
    return dp, replace(CFG, tie_embeddings=False)


def _friendly_draft(params):
    """The layer-truncated variant: agrees with the random-init
    target nearly always (residual blocks barely move the embed→head
    logits), so windows commit full accepted prefixes."""
    return gen.truncated_draft_params(params, CFG, n_layers=1)


# -------------------------------------------------- greedy bit-parity


@pytest.mark.parametrize("draft", ["friendly", "hostile"])
def test_spec_greedy_co_batched_bit_identical(params, draft):
    """THE acceptance bar: concurrent mixed-length greedy requests
    through the SPECULATIVE engine — staggered mid-decode joins, so
    per-slot accept lengths make iterations ragged — each match the
    compiled solo decode token-for-token, with a draft that accepts
    nearly everything AND one that rejects everything."""
    dp, dcfg = (_friendly_draft(params) if draft == "friendly"
                else _hostile_draft(params))
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=4, block_tokens=16,
        prefill_chunk=24,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=3,
                        adaptive=False))
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
            want = gen.generate(params, CFG, p, news[i])
            np.testing.assert_array_equal(np.asarray(outs[i]),
                                          np.asarray(want),
                                          err_msg=f"req {i}")
        info = actor.Info()
        assert info["max_live_slots"] >= 2, info
        assert info["spec_windows"] > 0
        if draft == "friendly":
            assert info["spec_accept_rate"] > 0.9, info
        else:
            assert info["spec_accept_rate"] == 0.0, info
        assert actor.pool.check_invariants() == []
        assert actor._dpool.check_invariants() == []
        assert info["kv_used_blocks"] == 0  # both pools drained
        assert actor._dpool.used_blocks() == 0
    finally:
        actor.close()


def test_spec_windows_beat_per_token_iterations(params):
    """Speculation's whole point: N tokens commit in far fewer engine
    iterations than N (the latency lever batching can't touch), and
    the ledger's decode-token counter carries the REAL ragged totals,
    not one-per-iteration."""
    dp, dcfg = _friendly_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=2, block_tokens=16,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=4,
                        adaptive=False))
    try:
        out = actor.Generate(_prompt(9), 40)
        assert np.asarray(out).shape == (1, 40)
        info = actor.Info()
        # 39 decode tokens (the first came from prefill) in ≤ ~9
        # windows of up to 5 — a hard structural bound, not a timing.
        assert info["engine_steps"] <= 12, info
        assert info["spec_tokens"] >= 30, info
        iters = actor.ledger.summary()
        recs = actor.ledger.records()
        assert recs[-1]["tokens_out"] == 40
        assert iters["iterations"] < 20
    finally:
        actor.close()


def test_spec_stop_token_retires_mid_window(params):
    """A stop token landing MID-speculation-window truncates the
    commit at the stop, retires the row early, and still matches the
    solo decode's stop semantics token-for-token; both pools drain."""
    dp, dcfg = _friendly_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=2, block_tokens=16,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=4,
                        adaptive=False))
    try:
        prompt = jnp.zeros((1, 4), jnp.int32)
        max_new = 24
        solo = gen.generate(params, CFG, prompt, max_new)
        stop = int(np.asarray(solo)[0, 2])  # stops 2 tokens in
        out = actor.Generate(prompt, max_new, stop_token=stop,
                             pad_token=7)
        want = gen.generate(params, CFG, prompt, max_new,
                            stop_token=stop, pad_token=7)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(want))
        info = actor.Info()
        assert info["engine_steps"] < max_new, (
            "stop mid-window did not retire early")
        assert info["kv_used_blocks"] == 0
        assert actor._dpool.used_blocks() == 0
    finally:
        actor.close()


def test_spec_composes_with_prefix_reuse(params):
    """Speculation + prefix reuse + chunked prefill in one engine: a
    shared-prefix second request still skips its resident blocks'
    prefill (target pool only — draft KV is draft-specific) and both
    requests decode bit-identically through speculative windows."""
    dp, dcfg = _friendly_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=4, block_tokens=16,
        prefill_chunk=16,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=3,
                        adaptive=False))
    try:
        shared = np.asarray(RNG.integers(1, CFG.vocab_size, 48),
                            np.int32)
        mk = lambda tail: jnp.asarray(np.concatenate(  # noqa: E731
            [shared, RNG.integers(1, CFG.vocab_size, tail)]).astype(
                np.int32))[None]
        p1, p2 = mk(7), mk(5)
        o1 = actor.Generate(p1, 8)
        o2 = actor.Generate(p2, 8)
        info = actor.Info()
        assert info["prefix_hits"] == 3, info  # 48 shared = 3 blocks
        assert info["spec_windows"] > 0
        for p, o in ((p1, o1), (p2, o2)):
            want = gen.generate(params, CFG, p, 8)
            np.testing.assert_array_equal(np.asarray(o),
                                          np.asarray(want))
        assert actor.pool.check_invariants() == []
        assert actor._dpool.check_invariants() == []
    finally:
        actor.close()


# -------------------------------- acceptance-sampling contract (unit)


def test_accept_greedy_chain_matches_reference():
    """The greedy acceptance chain: longest draft prefix matching the
    target argmax chain, then the target argmax at the mismatch —
    checked against a plain Python reference over random cases."""
    rng = np.random.default_rng(3)
    k, V, B = 4, 13, 8
    tlg = rng.normal(size=(B, k + 1, V)).astype(np.float32)
    draft = rng.integers(0, V, (B, k)).astype(np.int32)
    # Plant exact matches in some rows to hit every accept length.
    gt = tlg.argmax(-1)
    for b in range(B):
        draft[b, :b % (k + 1)] = gt[b, :b % (k + 1)]
    out, acc = gen.spec_accept_rows(
        jnp.asarray(draft), jnp.zeros((B, k, V), jnp.float32),
        jnp.asarray(tlg), jnp.zeros((B, 2), jnp.uint32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32),
        sampled=False)
    out, acc = np.asarray(out), np.asarray(acc)
    for b in range(B):
        a = 0
        while a < k and draft[b, a] == gt[b, a]:
            a += 1
        assert acc[b] == a, (b, acc[b], a)
        want = list(draft[b, :a]) + [gt[b, a]]
        assert list(out[b, :a + 1]) == want, (b, out[b], want)


def test_accept_sampled_matches_categorical_distribution():
    """THE exact-distribution contract (the PR 9 draw-for-draw family
    extended to residual acceptance): over many independent windows,
    the first emitted token's empirical distribution matches the
    target's filtered softmax as closely as a same-size direct
    ``jax.random.categorical`` sample does — acceptance + residual
    resampling is statistically indistinguishable from sampling the
    target. Deterministic keys: no flake."""
    V, k, N = 16, 2, 4000
    rng = np.random.default_rng(0)
    t_lg = jnp.asarray(rng.normal(size=(k + 1, V)) * 2.0, jnp.float32)
    d_lg = jnp.asarray(rng.normal(size=(k, V)) * 2.0, jnp.float32)
    temps = jnp.ones((N,), jnp.float32)
    topk = jnp.zeros((N,), jnp.int32)
    topp = jnp.ones((N,), jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(N))
    steps = jnp.zeros((N,), jnp.int32)
    # The draft proposes from q through the SAME helper the engine
    # uses (domain-separated key, fold at steps + j).
    dkeys = jax.vmap(
        lambda kk: jax.random.fold_in(kk, gen._DRAFT_FOLD))(keys)
    d0 = gen.sample_token_rows(jnp.broadcast_to(d_lg[0], (N, V)),
                               dkeys, steps, temps, topk, topp)
    d1 = gen.sample_token_rows(jnp.broadcast_to(d_lg[1], (N, V)),
                               dkeys, steps + 1, temps, topk, topp)
    draft = jnp.stack([d0, d1], axis=1)
    out, acc = jax.jit(
        lambda *a: gen.spec_accept_rows(*a, sampled=True))(
        draft, jnp.broadcast_to(d_lg, (N, k, V)),
        jnp.broadcast_to(t_lg, (N, k + 1, V)), keys, steps, temps,
        topk, topp)
    out, acc = np.asarray(out), np.asarray(acc)
    p0 = np.asarray(jax.nn.softmax(t_lg[0]))
    emp = np.bincount(out[:, 0], minlength=V) / N
    tv_spec = 0.5 * np.abs(emp - p0).sum()
    ref = np.asarray(jax.vmap(
        lambda kk: jax.random.categorical(kk, t_lg[0]))(keys))
    tv_ref = 0.5 * np.abs(np.bincount(ref, minlength=V) / N - p0).sum()
    # Margin: the speculative stream may not be meaningfully farther
    # from p than a direct categorical sample of the same size.
    assert tv_spec < max(2.5 * tv_ref, 0.05), (tv_spec, tv_ref)
    # Both branches exercised: some windows rejected, some accepted.
    assert 0 < acc.mean() < k, acc.mean()


def test_accept_sampled_full_accept_draws_bonus_from_target():
    """q == p: every proposal accepts (the ratio is 1), and the bonus
    token draws from the bare target distribution at the last
    position — the all-accepted leg of the identity."""
    V, N = 12, 3000
    rng = np.random.default_rng(1)
    t_lg = jnp.asarray(rng.normal(size=(2, V)) * 2.0, jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(N))
    temps = jnp.ones((N,), jnp.float32)
    topk = jnp.zeros((N,), jnp.int32)
    topp = jnp.ones((N,), jnp.float32)
    steps = jnp.zeros((N,), jnp.int32)
    dkeys = jax.vmap(
        lambda kk: jax.random.fold_in(kk, gen._DRAFT_FOLD))(keys)
    d0 = gen.sample_token_rows(jnp.broadcast_to(t_lg[0], (N, V)),
                               dkeys, steps, temps, topk, topp)
    out, acc = gen.spec_accept_rows(
        d0[:, None], jnp.broadcast_to(t_lg[:1], (N, 1, V)),
        jnp.broadcast_to(t_lg, (N, 2, V)), keys, steps, temps, topk,
        topp, sampled=True)
    out, acc = np.asarray(out), np.asarray(acc)
    assert (acc == 1).all()  # identical dists: nothing rejects
    p1 = np.asarray(jax.nn.softmax(t_lg[1]))
    emp = np.bincount(out[:, 1], minlength=V) / N
    assert 0.5 * np.abs(emp - p1).sum() < 0.06
    # And the accepted first token is exactly the draft's draw.
    np.testing.assert_array_equal(out[:, 0], np.asarray(d0))


def test_spec_sampled_engine_smoke(params):
    """Sampled rows ride speculative windows end to end (shape +
    determinism for a fixed seed; the distribution contract has its
    own unit tier — under speculation the sampled path is
    distribution-exact, not draw-for-draw)."""
    dp, dcfg = _friendly_draft(params)
    mk = lambda: PagedGeneratorActor(  # noqa: E731
        CFG, params=params, n_slots=2, block_tokens=16,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=3,
                        adaptive=False))
    a, b = mk(), mk()
    try:
        p = _prompt(9)
        kw = dict(temperature=0.8, seed=5, top_k=12)
        o1 = np.asarray(a.Generate(p, 12, **kw))
        o2 = np.asarray(b.Generate(p, 12, **kw))
        assert o1.shape == (1, 12)
        np.testing.assert_array_equal(o1, o2)  # same seed, same toks
        assert a.Info()["spec_windows"] > 0
    finally:
        a.close()
        b.close()


# -------------------------------------------- reservation discipline


def test_block_pool_spec_rows_audit_catches_undercover():
    pool = BlockPool(CFG, n_blocks=9, block_tokens=16)
    # Covered: pos 30, 2 blocks allocated, window of 4 → needs
    # ceil(34/16)=3 blocks, 1 new — 1 reserved unit suffices.
    assert pool.check_invariants(
        spec_rows=[(30, 2, 1, 4)]) == []
    # Not covered: same advance with nothing reserved.
    bad = pool.check_invariants(spec_rows=[(30, 2, 0, 4)])
    assert bad and "advance" in bad[0], bad
    # Boundary crossing mid-window: pos 15, window 4 spans blocks
    # 0 and 1 — one allocated block + zero reserve does not cover.
    assert pool.check_invariants(spec_rows=[(15, 1, 0, 4)])


def test_spec_reservations_cover_worst_case_under_pool_pressure(
        params):
    """Every committed window leaves every live row's remaining
    reservation covering its next worst-case k-advance, on BOTH
    pools, with the pool sized tight enough that cached blocks churn
    — audited from the engine thread after each window (the ISSUE 12
    check_invariants extension, exercised under pressure)."""
    dp, dcfg = _friendly_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=2, block_tokens=16, n_blocks=13,
        max_len=96,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=4,
                        adaptive=False))
    bad: list[str] = []
    windows = [0]
    orig = actor._spec_step

    def audited(k_eff, meter=None):
        orig(k_eff, meter)
        windows[0] += 1
        bad.extend(actor.check_spec_reservations())

    actor._spec_step = audited
    try:
        outs = [None, None]
        prompts = [_prompt(33), _prompt(17)]

        def call(i):
            outs[i] = actor.Generate(prompts[i], 40)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert windows[0] > 0
        assert bad == [], bad[:5]
        for i, p in enumerate(prompts):
            want = gen.generate(params, CFG, p, 40)
            np.testing.assert_array_equal(np.asarray(outs[i]),
                                          np.asarray(want))
        assert actor.pool.check_invariants() == []
        assert actor._dpool.check_invariants() == []
    finally:
        actor.close()


def test_spec_admission_reserves_both_pools(params):
    """Admission is both-pools-or-neither: exhausting the DRAFT pool
    alone sheds typed after the admit timeout and releases the target
    reservation (no leak), then admits once headroom returns."""
    from ptype_tpu.errors import ShedError

    dp, dcfg = _friendly_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=1, block_tokens=16,
        admit_timeout_s=0.2,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=2))
    try:
        grabbed = actor._dpool.free_blocks()
        assert actor._dpool.try_reserve(grabbed)
        free_t = actor.pool.free_blocks()
        with pytest.raises(ShedError, match="exhausted"):
            actor.Generate(jnp.zeros((1, 4), jnp.int32), 4)
        # The refused admission did not leak a target reservation.
        assert actor.pool.free_blocks() == free_t
        actor._dpool.unreserve(grabbed)
        out = actor.Generate(jnp.zeros((1, 4), jnp.int32), 4)
        assert np.asarray(out).shape == (1, 4)
    finally:
        actor.close()


# ------------------------------------------------------- adaptive k


def test_adaptive_k_backs_off_and_reprobes(params):
    """A draft that never agrees drives the accept EWMA to 0: the
    depth sheds to 0 (plain decode — speculation priced as a loss),
    k=1 probe windows keep re-testing every probe_every iterations,
    and the output stays bit-identical throughout."""
    dp, dcfg = _hostile_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=2, block_tokens=16,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=4,
                        probe_every=10))
    try:
        p = _prompt(9)
        out = actor.Generate(p, 60)
        want = gen.generate(params, CFG, p, 60)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(want))
        info = actor.Info()
        assert info["spec_k_cur"] == 0, info  # backed off to plain
        assert info["spec_windows"] < 40, info  # not one per token
        assert info["spec_accept_rate"] == 0.0
    finally:
        actor.close()


def test_adaptive_k_holds_depth_for_good_draft(params):
    dp, dcfg = _friendly_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=2, block_tokens=16,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=4))
    try:
        out = actor.Generate(_prompt(9), 40)
        assert np.asarray(out).shape == (1, 40)
        info = actor.Info()
        assert info["spec_k_cur"] == 4, info
        assert info["spec_accept_rate"] > 0.9
    finally:
        actor.close()


# ------------------------------------------------------- chaos seam


def test_serve_spec_chaos_seam_poisons_window_and_pairs(params):
    """The serve.spec seam: "reject" poisons speculation windows (the
    iteration falls back to the plain step — tokens still EXACT, just
    slower), "delay" stalls the draft forward; committed windows
    beacon the paired recoveries (unrecovered drains to {})."""
    dp, dcfg = _friendly_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=2, block_tokens=16,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=3,
                        adaptive=False))
    plan = chaos.arm(FaultPlan([
        FaultSpec("serve.spec", "reject", times=2),
        FaultSpec("serve.spec", "delay", after=4, times=1,
                  delay_s=0.01),
    ], seed=1, name="serve-spec"))
    catch_ups: list[int] = []
    orig_cu = actor._draft_catch_up

    def spying_catch_up(slot, row):
        span = int(actor._pos[slot]) - int(actor._dpos[slot])
        if span > 0:
            catch_ups.append(span)
        orig_cu(slot, row)

    actor._draft_catch_up = spying_catch_up
    try:
        p = _prompt(9)
        out = actor.Generate(p, 24)
        want = gen.generate(params, CFG, p, 24)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(want))
        fired = [e.site for e in plan.fired()]
        assert fired.count("serve.spec") == 3, plan.trace()
        assert chaos.unrecovered() == {}, plan.trace()
        info = actor.Info()
        # Rejected windows decoded plainly: steps > pure-window count.
        assert info["engine_steps"] > info["spec_windows"]
        # The plain fallbacks left draft-KV holes, and the next
        # window BACKFILLED them before drafting — without the
        # catch-up, every later window attends through garbage and
        # the accept rate (incl. the adaptive re-probe) silently
        # rots. Two rejects, back to back → one 2-position catch-up.
        assert catch_ups and sum(catch_ups) == 2, catch_ups
        assert info["spec_accept_rate"] > 0.9, info
    finally:
        chaos.disarm()
        actor.close()


# ------------------------------------------------- fleet visibility


def test_replica_snapshot_carries_spec_accept_rate():
    """The gateway probe plumbing (same family as kv_free_blocks /
    prefix_hit_rate): a replica reporting spec_accept_rate carries it
    into the pool snapshot; one that never speculated stays
    spec-free (collapse is distinguishable from absence)."""
    from ptype_tpu.gateway.pool import Replica
    from ptype_tpu.registry import Node

    r = Replica(Node("llm", "r0", "127.0.0.1", 1))
    with r.lock:
        r.reported = {"kv_free_blocks": 5, "prefix_hit_rate": 0.5,
                      "spec_accept_rate": 0.83}
    snap = r.snapshot()
    assert snap["spec_accept_rate"] == 0.83
    with r.lock:
        r.reported = {"kv_free_blocks": 5}
    assert "spec_accept_rate" not in r.snapshot()


def test_obs_serve_renders_spec_column(params):
    """`obs serve` gains the spec% column, fed by the ledger's
    serve.spec_accept_rate gauge from a real spec engine's registry."""
    from ptype_tpu import metrics as metrics_mod
    from ptype_tpu.health.top import render_serve

    reg = metrics_mod.MetricsRegistry()
    dp, dcfg = _friendly_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=2, block_tokens=16,
        metrics_registry=reg,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=3,
                        adaptive=False))
    try:
        actor.Generate(_prompt(9), 16)
        snap = {"ts": "t", "nodes": {"llm/r0:1": {
            "metrics": reg.snapshot()}}, "errors": {}}
        view = render_serve(snap)
        assert "spec%" in view
        row = [ln for ln in view.splitlines() if "llm/r0:1" in ln][0]
        rate = reg.gauge("serve.spec_accept_rate").value
        assert rate > 0.9
        assert f"{rate * 100:.1f}" in row, row
        # Info carries the same number the probes drain.
        assert actor.Info()["spec_accept_rate"] == pytest.approx(
            rate, abs=0.2)
    finally:
        actor.close()


def test_spec_info_and_ledger_accounting(params):
    """Info()/ledger spec surface: windows/proposed/accepted/tokens
    move together, summary() includes spec fields only once
    speculation ran, and counters land in the engine's registry."""
    from ptype_tpu import metrics as metrics_mod

    reg = metrics_mod.MetricsRegistry()
    dp, dcfg = _friendly_draft(params)
    actor = PagedGeneratorActor(
        CFG, params=params, n_slots=2, block_tokens=16,
        metrics_registry=reg,
        spec=SpecConfig(draft_params=dp, draft_cfg=dcfg, k=3,
                        adaptive=False))
    plain = PagedGeneratorActor(CFG, params=params, n_slots=1,
                                block_tokens=16)
    try:
        actor.Generate(_prompt(9), 20)
        info = actor.Info()
        assert info["spec_windows"] > 0
        assert info["spec_proposed"] >= info["spec_accepted"] > 0
        assert info["spec_tokens"] == 19  # all decode tokens via spec
        assert reg.counter("serve.spec_windows").value == \
            info["spec_windows"]
        assert reg.counter("serve.spec_tokens").value == 19
        # serve.decode_tokens carries the ragged totals too. The
        # caller unblocks at retire, BEFORE the engine thread closes
        # the final iteration's meter — poll briefly.
        deadline = time.monotonic() + 5
        while (reg.counter("serve.decode_tokens").value < 19
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert reg.counter("serve.decode_tokens").value == 19
        # A plain engine's Info stays spec-free.
        plain.Generate(_prompt(5), 4)
        assert "spec_accept_rate" not in plain.Info()
    finally:
        actor.close()
        plain.close()
