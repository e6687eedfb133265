"""Disaggregated prefill/decode serving (ISSUE 16): the quantized KV
wire (pack/unpack roundtrips, residual LRU), the engine migration
protocol (exact-wire greedy parity vs solo decode, chain-hash dedup
never re-sending resident blocks, truncated-wire refusal), speculation
surviving migration with its accept rate intact, the registered
``serve.kv_pack``/``serve.kv_unpack`` program contracts, and the
gateway's two-stage router end-to-end over real engines (parity,
migration counters, prefix-directory publish, chaos fallback)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu import chaos, progaudit
from ptype_tpu.chaos import FaultPlan, FaultSpec
from ptype_tpu.models import generate as gen
from ptype_tpu.models import transformer as tfm
from ptype_tpu.serve_engine import (KVMigrator, PagedGeneratorActor,
                                    SpecConfig, WIRE_MODES)

CFG = tfm.preset("tiny", dtype=jnp.float32)
RNG = np.random.default_rng(16)
BT = 16


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda r: tfm.init_params(r, CFG))(
        jax.random.PRNGKey(0))


def _prompt(n, rng=RNG):
    return jnp.asarray(rng.integers(1, CFG.vocab_size, n),
                       jnp.int32)[None]


def _engine(params, serve_class="unified", spec=None, **over):
    from ptype_tpu.metrics import MetricsRegistry

    kw = dict(params=params, n_slots=2, block_tokens=BT,
              prefill_chunk=32, serve_class=serve_class, spec=spec,
              metrics_registry=MetricsRegistry())
    kw.update(over)
    return PagedGeneratorActor(CFG, **kw)


def _migrate(pre, dec, prompt, max_new, kv_wire="exact"):
    """Drive the full protocol directly (no RPC): Prefill →
    MigratePlan → ExportBlocks → ImportBlocks → ReleaseExport →
    MigrateDecode. Returns (tokens, prefill_reply, plan)."""
    rep = pre.Prefill(prompt, max_new)
    plan = dec.MigratePlan(prompt, max_new)
    wire = pre.ExportBlocks(rep["export_id"], plan["need"], kv_wire)
    dec.ImportBlocks(plan["ticket"], wire)
    assert pre.ReleaseExport(rep["export_id"])
    toks = dec.MigrateDecode(plan["ticket"], rep["first_token"])
    return toks, rep, plan


# ------------------------------------------------------- wire (unit)


def _unpack(mig, kb, vb, payload, bid, mode):
    """Into empty banks of a GQA model's two names."""
    out = mig.unpack_block({"k": jnp.zeros_like(kb),
                            "v": jnp.zeros_like(vb)}, payload, bid, mode)
    return out["k"], out["v"]


def test_kv_migrator_roundtrip_and_residual_lru():
    shape = (2, BT, 2, 8)
    rng = np.random.default_rng(3)
    kb = jnp.asarray(rng.normal(size=(2, 4) + shape[1:]), jnp.float32)
    vb = jnp.asarray(rng.normal(size=(2, 4) + shape[1:]), jnp.float32)
    mig = KVMigrator({"k": shape, "v": shape}, jnp.float32,
                     max_residuals=3)
    # Exact mode: bit-identical through the wire.
    payload, nb = mig.pack_block({"k": kb, "v": vb}, 1, None, "exact")
    assert nb == 2 * int(np.prod(shape)) * 4
    k2, v2 = _unpack(mig, kb, vb, payload, 2, "exact")
    np.testing.assert_array_equal(np.asarray(k2[:, 2]),
                                  np.asarray(kb[:, 1]))
    np.testing.assert_array_equal(np.asarray(v2[:, 2]),
                                  np.asarray(vb[:, 1]))
    # q8: close, and the wire is ~4x smaller than raw f32.
    payload, nbq = mig.pack_block({"k": kb, "v": vb}, 1, 7, "q8")
    assert nbq < nb / 2
    k3, v3 = _unpack(mig, kb, vb, payload, 0, "q8")
    np.testing.assert_allclose(np.asarray(k3[:, 0]),
                               np.asarray(kb[:, 1]), atol=0.05)
    # Residuals: keyed by hash, LRU-bounded.
    assert mig.residual_count() == 1
    for h in range(20, 26):
        mig.pack_block({"k": kb, "v": vb}, 0, h, "q8")
    assert mig.residual_count() == 3
    with pytest.raises(ValueError, match="kv_wire"):
        mig.pack_block({"k": kb, "v": vb}, 0, None, "zstd")
    assert WIRE_MODES == ("q8", "exact")


def test_exact_wire_bf16_banks_survive_the_socket_codec():
    """The exact wire in the model's NATIVE bank dtype (bf16): the RPC
    codec buffer-encodes standard dtypes only, so the pack ships raw
    bits + dtype name and the unpack views them back — round-tripped
    through the real ``codec.encode``/``decode`` pair, because the
    in-process ``lookup_local`` fast path never exercises it."""
    from ptype_tpu import codec

    shape = (2, BT, 2, 8)
    rng = np.random.default_rng(5)
    kb = jnp.asarray(rng.normal(size=(2, 4) + shape[1:]), jnp.bfloat16)
    vb = jnp.asarray(rng.normal(size=(2, 4) + shape[1:]), jnp.bfloat16)
    mig = KVMigrator({"k": shape, "v": shape}, jnp.bfloat16)
    payload, nb = mig.pack_block({"k": kb, "v": vb}, 1, None, "exact")
    assert nb == 2 * int(np.prod(shape)) * 2
    wired = codec.decode(codec.encode(payload))  # the socket hop
    k2, v2 = _unpack(mig, kb, vb, wired, 3, "exact")
    np.testing.assert_array_equal(np.asarray(k2[:, 3]),
                                  np.asarray(kb[:, 1]))
    np.testing.assert_array_equal(np.asarray(v2[:, 3]),
                                  np.asarray(vb[:, 1]))
    # q8 leaves (int8 q, f32 s) are codec-native even off bf16 banks.
    payload, _ = mig.pack_block({"k": kb, "v": vb}, 0, 9, "q8")
    codec.decode(codec.encode(payload))


def test_kv_pack_unpack_programs_audit_clean():
    """The dispatch-discipline contract: both wire programs trace
    with consumed donations, no collectives, no callbacks, no f64."""
    progaudit.register_default_programs()
    for name in ("serve.kv_pack", "serve.kv_unpack"):
        progaudit.audit_registered(name).raise_if_failed()


# ------------------------------------------- engine protocol (parity)


def test_migration_exact_wire_matches_solo_decode_and_dedups(params):
    """THE parity bar: a migrated request's tokens are bit-equal to
    the same request served solo (exact wire, greedy); a second
    request sharing the prefix ships NOTHING but the tail (chain-hash
    dedup), counted, never re-sent."""
    pre = _engine(params, "prefill")
    dec = _engine(params, "decode")
    try:
        prompt = _prompt(40)  # 2 full blocks + 8-token tail
        max_new = 8
        ref = np.asarray(pre.Generate(prompt, max_new))

        toks, rep, plan = _migrate(pre, dec, prompt, max_new)
        assert rep["first_token"] == int(ref[0, 0])
        assert toks == [int(x) for x in ref[0, :len(toks)]]
        assert all(int(x) == 0 for x in ref[0, len(toks):])
        assert plan["need"] == [0, 1] and plan["resident"] == 0
        assert plan["tail"] == 8

        # Same prefix again: the decode side already holds both full
        # blocks — the plan refs them (dedup), the wire carries only
        # the unsealed tail.
        toks2, rep2, plan2 = _migrate(pre, dec, prompt, max_new)
        assert toks2 == toks
        assert plan2["need"] == [] and plan2["resident"] == 2
        info = dec.Info()
        assert info["serve_class"] == "decode"
        assert info["migrations"] == 2
        assert info["migrate_dedup_hits"] == 2
        assert info["migrate_bytes"] > 0
        assert pre.Info()["serve_class"] == "prefill"
        # Both pools come out clean: nothing parked, nothing leaked.
        assert pre.pool.check_invariants() == []
        assert dec.pool.check_invariants() == []
    finally:
        pre.close()
        dec.close()


def test_q8_wire_decodes_and_costs_a_quarter_of_exact(params):
    """The default wire: int8+EF payloads land, decode completes, and
    the bytes-on-wire are ~4x under exact mode for the same blocks."""
    pre = _engine(params, "prefill")
    dec = _engine(params, "decode")
    try:
        prompt = _prompt(40)
        rep = pre.Prefill(prompt, 6)
        plan = dec.MigratePlan(prompt, 6)
        exact = pre.ExportBlocks(rep["export_id"], plan["need"],
                                 "exact")
        q8 = pre.ExportBlocks(rep["export_id"], plan["need"], "q8")
        assert q8["nbytes"] < exact["nbytes"] / 2
        dec.ImportBlocks(plan["ticket"], q8)
        pre.ReleaseExport(rep["export_id"])
        toks = dec.MigrateDecode(plan["ticket"], rep["first_token"])
        assert 1 <= len(toks) <= 6
        assert toks[0] == rep["first_token"]
        assert pre._migrator.residual_count() > 0  # EF state stayed
    finally:
        pre.close()
        dec.close()


def test_truncated_wire_refused_and_abort_unwinds(params):
    """A wire missing planned blocks raises on import (the gateway's
    fallback leg owns recovery); AbortMigration returns every ref and
    reservation — the pool is as if the request never arrived."""
    pre = _engine(params, "prefill")
    dec = _engine(params, "decode")
    try:
        prompt = _prompt(40)
        free0 = dec.pool.free_blocks()
        rep = pre.Prefill(prompt, 6)
        plan = dec.MigratePlan(prompt, 6)
        wire = pre.ExportBlocks(rep["export_id"], plan["need"],
                                "exact")
        short = dict(wire)
        short["blocks"] = wire["blocks"][:-1]
        with pytest.raises(RuntimeError, match="truncated"):
            dec.ImportBlocks(plan["ticket"], short)
        with pytest.raises(RuntimeError, match="not"):
            dec.MigrateDecode(plan["ticket"], rep["first_token"])
        assert dec.AbortMigration(plan["ticket"])
        assert not dec.AbortMigration(plan["ticket"])  # idempotent
        assert pre.ReleaseExport(rep["export_id"])
        assert dec.pool.free_blocks() == free0
        assert dec.pool.check_invariants() == []
        assert dec.Info()["migrations"] == 0  # nothing completed
    finally:
        pre.close()
        dec.close()


def test_speculation_survives_migration_with_accept_rate_intact(
        params):
    """Spec decoding is per-replica state: the decode side runs its
    LOCAL draft prefill on activation, so a migrated greedy request
    emits the same tokens as solo spec decode AND the same accept
    rate (the draft sees the identical token stream)."""
    dp, dcfg = gen.truncated_draft_params(params, CFG, n_layers=1)

    def spec():
        return SpecConfig(draft_params=dp, draft_cfg=dcfg, k=3,
                          adaptive=False)

    solo = _engine(params, spec=spec())
    pre = _engine(params, "prefill", spec=spec())
    dec = _engine(params, "decode", spec=spec())
    try:
        prompt = _prompt(40)
        max_new = 10
        ref = np.asarray(solo.Generate(prompt, max_new))
        toks, _, _ = _migrate(pre, dec, prompt, max_new)
        assert toks == [int(x) for x in ref[0, :len(toks)]]
        r_solo = solo.Info().get("spec_accept_rate")
        r_mig = dec.Info().get("spec_accept_rate")
        assert r_solo is not None and r_mig is not None
        assert r_mig == pytest.approx(r_solo)
        assert r_mig > 0
    finally:
        solo.close()
        pre.close()
        dec.close()


def test_migration_interleaves_with_inflight_decode(params):
    """A migration landing mid-decode must not corrupt the co-batched
    request: imports run under the dispatch lock between iterations,
    and both requests finish with their solo-parity tokens."""
    pre = _engine(params, "prefill")
    dec = _engine(params, "decode", n_slots=2)
    try:
        p_bg, p_mig = _prompt(24), _prompt(40)
        ref_bg = np.asarray(pre.Generate(p_bg, 12))
        ref_mig = np.asarray(pre.Generate(p_mig, 6))
        out = {}

        def bg():
            out["bg"] = np.asarray(dec.Generate(p_bg, 12))

        t = threading.Thread(target=bg)
        t.start()
        time.sleep(0.05)  # let the background decode get in flight
        toks, _, _ = _migrate(pre, dec, p_mig, 6)
        t.join()
        np.testing.assert_array_equal(out["bg"], ref_bg)
        assert toks == [int(x) for x in ref_mig[0, :len(toks)]]
    finally:
        pre.close()
        dec.close()


# ------------------------------------------ gateway (end-to-end RPC)


def _fleet(params):
    """Two REAL paged engines (prefill-class + decode-class) sharing
    params, served over RPC and registered; returns (gw, actors,
    servers, closers)."""
    from ptype_tpu.actor import ActorServer
    from ptype_tpu.coord.core import CoordState
    from ptype_tpu.coord.local import LocalCoord
    from ptype_tpu.gateway import GatewayConfig, InferenceGateway
    from ptype_tpu.metrics import MetricsRegistry
    from ptype_tpu.registry import CoordRegistry

    state = CoordState(sweep_interval=0.1)
    registry = CoordRegistry(LocalCoord(state), lease_ttl=2.0)
    actors, servers, regs = [], [], []
    for name, cls in (("pre0", "prefill"), ("dec0", "decode")):
        a = _engine(params, cls)
        s = ActorServer("127.0.0.1", 0)
        s.register(a, "Generator")
        s.serve()
        # Hold the registration: it carries the lease heartbeat.
        regs.append(registry.register("llm-disagg", name,
                                      "127.0.0.1", s.port))
        actors.append(a)
        servers.append(s)
    cfg = GatewayConfig(probe_interval_s=0.1, probe_timeout_s=2.0,
                        default_deadline_s=60.0, disagg=True,
                        kv_wire="exact")
    gw = InferenceGateway(registry, "llm-disagg", cfg,
                          metrics_registry=MetricsRegistry())

    def close():
        gw.close()
        for r in regs:
            r.close()
        for s in servers:
            s.close()
        for a in actors:
            a.close()
        state.close()

    return gw, actors, close


def _wait_classes(gw, timeout=8.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        classes = {r.serve_class() for r in gw.pool.healthy()}
        if {"prefill", "decode"} <= classes:
            return True
        time.sleep(0.05)
    return False


def test_gateway_disagg_routes_migrates_and_matches_solo(params):
    """The tentpole end-to-end: the gateway's two-stage router picks
    the prefill replica, migrates the block set over the exact wire,
    and the decode replica's tokens are bit-equal to solo decode;
    counters, snapshot class column, and the prefix directory all
    reflect the transfer."""
    gw, (pre, dec), close = _fleet(params)
    try:
        assert _wait_classes(gw)
        prompt = _prompt(40)
        ref = np.asarray(pre.Generate(prompt, 8))  # local, no RPC
        out = np.asarray(gw.generate(prompt, max_new_tokens=8))
        np.testing.assert_array_equal(out, ref)
        assert dec.Info()["migrations"] == 1
        assert pre.Info()["migrations"] == 0
        # The directory learned where the prefix landed...
        dec_key = next(r.key for r in gw.pool.healthy()
                       if r.serve_class() == "decode")
        assert gw.directory.n_blocks(dec_key) >= 2
        # ...so a sibling request sharing it dedups on the wire.
        out2 = np.asarray(gw.generate(prompt, max_new_tokens=8))
        np.testing.assert_array_equal(out2, ref)
        assert dec.Info()["migrate_dedup_hits"] >= 2
        # The pool snapshot carries the class + migration columns
        # (probe-reported, so give the 0.1s probe loop a beat).
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            snaps = {s.get("serve_class"): s
                     for s in gw.pool.status()["replicas"]}
            if snaps.get("decode", {}).get("migrations") == 2:
                break
            time.sleep(0.05)
        assert snaps["prefill"] and snaps["decode"]
        assert snaps["decode"]["migrations"] == 2
        # Migration legs carry their own TTFT attribution.
        summ = dec.ledger.summary()
        assert summ["migrated_requests"] == 2
        assert "migrate_p99_ms" in summ
    finally:
        close()


def test_gateway_disagg_chaos_falls_back_to_local_prefill(params):
    """The chaos seam: drop and truncate mid-transfer both land the
    request on the decode replica's LOCAL prefill — correct tokens,
    never lost, and the injected faults pair with recovery beacons."""
    gw, (pre, dec), close = _fleet(params)
    try:
        assert _wait_classes(gw)
        prompt = _prompt(40)
        ref = np.asarray(pre.Generate(prompt, 8))
        plan = FaultPlan([
            FaultSpec(site="serve.migrate", action="drop", times=1),
            FaultSpec(site="serve.migrate", action="truncate",
                      after=1, times=1),
        ])
        with chaos.armed(plan):
            for _ in range(2):  # one drop, one truncate
                out = np.asarray(gw.generate(prompt,
                                             max_new_tokens=8))
                np.testing.assert_array_equal(out, ref)
            assert chaos.unrecovered() == {}, plan.trace()
        assert dec.Info()["migrations"] == 0  # no transfer completed
        assert len([e for e in plan.fired()
                    if e.site == "serve.migrate"]) == 2
        # Both engines unwound clean: nothing parked, nothing leaked.
        assert pre.pool.check_invariants() == []
        assert dec.pool.check_invariants() == []
    finally:
        close()
