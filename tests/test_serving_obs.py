"""Serving observability plane (ISSUE 10): the ServingLedger unit
tier (lifecycle records, histograms, iteration composition, KV
pressure, span synthesis), the serving alert rules on synthetic
series, the gateway plumbing satellites (real token counts, probe-fed
TTFT, hint ordering), the cross-process stitching acceptance (one
trace: gateway.request → … → serve.admit / prefill chunks /
serve.decode with the first-token event, ledger-vs-span TTFT
agreement), and the seeded KV-pressure drill (names the afflicted
replica, triggers the PR 8 profile-capture hook; the identical clean
run fires nothing)."""

import threading
import time
from unittest import mock

import numpy as np
import pytest

from ptype_tpu import metrics as metrics_mod
from ptype_tpu import trace
from ptype_tpu.health import (AlertCapture, AlertEngine,
                              KvPressureRule, PrefixHitCollapseRule,
                              Sampler, ServeStallRule, ServingLedger,
                              TtftRule, default_rules,
                              measure_seam_cost_us, render_serve,
                              telemetry_endpoint)
from ptype_tpu.health.rules import ClusterView

# -------------------------------------------------- ledger (unit tier)


def _ledger():
    reg = metrics_mod.MetricsRegistry()
    return ServingLedger(registry=reg), reg


def test_request_record_lifecycle_math():
    led, reg = _ledger()
    rec = led.enqueued(prompt_tokens=40, max_new=4)
    assert reg.counter("serve.requests").value == 1
    time.sleep(0.01)                    # waiting behind the queue
    w0 = led.head_refused(rec)          # first refusal stamps t_head
    time.sleep(0.005)                   # reservation still refused
    w1 = led.head_refused(rec)          # later refusals measure wait
    assert w0 == 0.0 and w1 >= 0.004
    led.admitted(rec)
    with led.chunk(rec, 32):
        time.sleep(0.002)
    with led.chunk(rec, 8):
        pass
    led.first_token(rec)
    time.sleep(0.002)
    led.tokens_emitted((rec,))
    led.tokens_emitted((rec,))
    led.tokens_emitted((rec,))
    led.retired(rec, "complete")
    d = led.records()[-1]
    assert d["prompt_tokens"] == 40 and d["prefill_chunks"] == 2
    assert d["prefill_tokens"] == 40
    assert d["queue_wait_ms"] >= 9.0        # enqueue → head of line
    assert d["reserve_wait_ms"] >= 4.0      # head → reservation
    assert d["tokens_out"] == 4 and d["reason"] == "complete"
    assert d["ttft_ms"] > 0 and d["e2e_ms"] >= d["ttft_ms"]
    # TPOT = mean inter-token gap AFTER the first token.
    assert d["tpot_ms"] == pytest.approx(
        sum(d["decode_deltas_ms"]) / 3, rel=0.01)
    assert len(d["decode_deltas_ms"]) == 3
    for h in ("serve.ttft_ms", "serve.tpot_ms", "serve.e2e_ms",
              "serve.queue_wait_ms"):
        assert reg.histogram(h).count == 1, h
    # The gateway-probe surface: sequence-tagged real samples.
    assert led.ttft_recent() == [[1, d["ttft_ms"]]]
    assert led.summary()["requests_retired"] == 1
    assert led.summary()["retire_reasons"] == {"complete": 1}


def test_retire_reasons_shed_and_idempotence():
    led, reg = _ledger()
    rec = led.enqueued(8, 4)
    led.retired(rec, "shed")
    assert reg.counter("serve.sheds").value == 1
    assert reg.counter("serve.retired.shed").value == 1
    # Sheds never pollute the latency histograms or the TTFT feed.
    assert reg.histogram("serve.e2e_ms").count == 0
    assert led.ttft_recent() == []
    # Idempotent: teardown sweeping an already-shed row is a no-op.
    led.retired(rec, "error")
    assert reg.counter("serve.retired").value == 1
    # Unknown reasons clamp to "error"; None records are tolerated.
    rec2 = led.enqueued(8, 4)
    led.retired(rec2, "exploded")
    assert reg.counter("serve.retired.error").value == 1
    led.retired(None, "complete")
    led.shed_untracked()
    assert reg.counter("serve.sheds").value == 2


def test_iteration_meter_folds_batch_composition():
    led, reg = _ledger()
    rec = led.enqueued(32, 4)
    with led.iteration(active=3, stall_ms=1.5):
        with led.chunk(rec, 32):    # mixed prefill+decode iteration
            pass
    with led.iteration(active=3):
        pass
    assert reg.counter("serve.steps").value == 2
    assert reg.counter("serve.decode_tokens").value == 6
    assert reg.counter("serve.prefill_tokens").value == 32
    assert reg.gauge("serve.active_slots").value == 3
    s = led.summary()
    assert s["iterations"] == 2 and s["rows_live_mean"] == 3.0
    assert s["stall_ms_max"] == 1.5
    assert s["prefill_token_share"] == pytest.approx(32 / 38,
                                                     abs=1e-4)


def test_kv_sample_gauges_and_eviction_delta():
    led, reg = _ledger()
    stats = {"kv_free_blocks": 3, "kv_cached_blocks": 5,
             "kv_used_blocks": 8, "kv_total_blocks": 16,
             "kv_util_pct": 50.0, "kv_evictions": 4}
    led.kv_sample(stats, prefix_hit_rate=0.25)
    assert reg.gauge("kv.free_blocks").value == 3
    assert reg.gauge("kv.total_blocks").value == 16
    assert reg.gauge("kv.prefix_hit_rate").value == 0.25
    assert reg.counter("kv.evictions").value == 4
    # The counter carries DELTAS: a re-sample of the same cumulative
    # count adds nothing; growth adds the difference.
    led.kv_sample(stats, 0.25)
    assert reg.counter("kv.evictions").value == 4
    led.kv_sample({**stats, "kv_evictions": 9}, 0.25)
    assert reg.counter("kv.evictions").value == 9


def test_ledger_synthesizes_span_tree_under_traceparent():
    led, _ = _ledger()
    rec_store = trace.enable("serve-test")
    try:
        with trace.span("actor/Generator.Generate") as handler:
            tp = trace.traceparent()
            rec = led.enqueued(24, 3, tp=tp)
            led.admitted(rec)
            with led.chunk(rec, 16):
                time.sleep(0.001)
            with led.chunk(rec, 8):
                pass
            led.first_token(rec)
            led.tokens_emitted((rec,))
            led.tokens_emitted((rec,))
            led.retired(rec, "complete")
        spans = {s.name: s for s in rec_store.spans()}
        for name in ("serve.admit", "serve.prefill.chunk[0]",
                     "serve.prefill.chunk[1]", "serve.decode"):
            assert name in spans, sorted(spans)
            assert spans[name].parent_id == handler.span_id
            assert spans[name].trace_id == handler.trace_id
        dec = spans["serve.decode"]
        assert [e["name"] for e in dec.events] == ["first_token"]
        assert dec.attrs["tokens"] == 3
        # Ledger TTFT and the span-derived one come from stamps taken
        # at the same instants (monotonic + wall twins): they agree.
        span_ttft_ms = (dec.start_s
                        - spans["serve.admit"].start_s) * 1e3
        assert led.records()[-1]["ttft_ms"] == pytest.approx(
            span_ttft_ms, abs=25.0)
    finally:
        trace.disable()


def test_ledger_emits_no_spans_without_traceparent_or_tracing():
    led, _ = _ledger()
    # Tracing off: nothing to record into, retire is clean.
    rec = led.enqueued(8, 2, tp=None)
    led.retired(rec, "complete")
    rec_store = trace.enable("serve-test")
    try:
        # Tracing on but the request carried no traceparent (a direct
        # in-process call): no orphan spans are synthesized.
        rec = led.enqueued(8, 2, tp=None)
        led.admitted(rec)
        led.first_token(rec)
        led.retired(rec, "complete")
        assert rec_store.spans() == []
    finally:
        trace.disable()


def test_seam_cost_probe_prices_one_iteration():
    out = measure_seam_cost_us(iters=500)
    assert out["iters"] == 500
    # Microseconds, not milliseconds: the <1%-per-iteration bar
    # divides this by a multi-ms engine step.
    assert 0.0 < out["seam_cost_us"] < 1000.0


# ------------------------------------------------- rules (unit tier)


def _snap(nodes: dict, ts: float = 1000.0) -> dict:
    return {"ts": ts, "nodes": nodes, "errors": {}}


def test_ttft_rule_fires_over_slo_with_count_floor():
    rule = TtftRule(slo_ttft_ms=2000.0, min_count=8)
    hot = _snap({"serve/a:1": {"series": {
        "serve.ttft_ms.p99": [[999.0, 3500.0]],
        "serve.ttft_ms.count": [[999.0, 20.0]]}}})
    alerts = rule.evaluate(ClusterView(hot))
    assert len(alerts) == 1 and alerts[0].node == "serve/a:1"
    assert alerts[0].value == 3500.0 and alerts[0].severity == "page"
    # Below the count floor a bad tail of 3 requests is noise.
    few = _snap({"serve/a:1": {"series": {
        "serve.ttft_ms.p99": [[999.0, 3500.0]],
        "serve.ttft_ms.count": [[999.0, 3.0]]}}})
    assert rule.evaluate(ClusterView(few)) == []
    ok = _snap({"serve/a:1": {"series": {
        "serve.ttft_ms.p99": [[999.0, 900.0]],
        "serve.ttft_ms.count": [[999.0, 50.0]]}}})
    assert rule.evaluate(ClusterView(ok)) == []


def test_kv_pressure_rule_requires_both_gates():
    rule = KvPressureRule(free_frac=0.15, evict_rate_floor=0.2,
                          window_s=120.0, min_points=3)

    def node(free_pts, evict_rate):
        return {"series": {
            "kv.total_blocks": [[999.0, 100.0]],
            "kv.free_blocks": free_pts,
            "kv.evictions.rate": [[999.0, evict_rate]]}}

    low = [[t, 5.0] for t in (960.0, 970.0, 980.0, 990.0)]
    # Pinned low AND actively evicting: the thrash signature.
    alerts = rule.evaluate(ClusterView(_snap(
        {"serve/b:2": node(low, 3.0)})))
    assert len(alerts) == 1 and alerts[0].node == "serve/b:2"
    assert "evictions" in alerts[0].message
    # Low headroom alone: a well-sized busy pool, not a page.
    assert rule.evaluate(ClusterView(_snap(
        {"serve/b:2": node(low, 0.0)}))) == []
    # Evicting with plenty of headroom: a healthy LRU turning over.
    high = [[t, 60.0] for t in (960.0, 970.0, 980.0, 990.0)]
    assert rule.evaluate(ClusterView(_snap(
        {"serve/b:2": node(high, 3.0)}))) == []
    # One momentary dip must not fake sustained pressure (majority).
    mixed = [[960.0, 60.0], [970.0, 60.0], [980.0, 60.0], [990.0, 5.0]]
    assert rule.evaluate(ClusterView(_snap(
        {"serve/b:2": node(mixed, 3.0)}))) == []


def test_prefix_hit_collapse_rule():
    rule = PrefixHitCollapseRule(healthy_frac=0.3, collapsed_frac=0.1,
                                 min_points=4)
    collapse = _snap({"serve/c:3": {"series": {"kv.prefix_hit_rate": [
        [910.0, 0.55], [940.0, 0.6], [970.0, 0.4], [999.0, 0.02]]}}})
    alerts = rule.evaluate(ClusterView(collapse))
    assert len(alerts) == 1 and alerts[0].node == "serve/c:3"
    assert alerts[0].severity == "warn"
    # Never-healthy (cold start ramping up) and still-healthy stay
    # quiet; so does a quiet replica with too few points.
    ramp = _snap({"serve/c:3": {"series": {"kv.prefix_hit_rate": [
        [910.0, 0.0], [940.0, 0.02], [970.0, 0.05], [999.0, 0.08]]}}})
    assert rule.evaluate(ClusterView(ramp)) == []
    healthy = _snap({"serve/c:3": {"series": {"kv.prefix_hit_rate": [
        [910.0, 0.5], [940.0, 0.55], [970.0, 0.5], [999.0, 0.45]]}}})
    assert rule.evaluate(ClusterView(healthy)) == []


def test_serve_stall_rule_queue_gate_and_threshold():
    rule = ServeStallRule(factor=8.0, min_gap_s=5.0, min_steps=3)
    nodes = {"serve/d:4": {"series": {
        "serve.steps": [[900.0, 50.0], [940.0, 80.0]],
        "serve.step_ms": [[940.0, 100.0]],
        "serve.queue_depth": [[999.0, 4.0]]}}}
    # Last iteration at t=940, queue non-empty, gap 60 s > floor 5 s.
    alerts = rule.evaluate(ClusterView(_snap(nodes)))
    assert len(alerts) == 1 and alerts[0].node == "serve/d:4"
    assert alerts[0].severity == "page"
    # The queue gate: an idle engine (nothing waiting) is healthy.
    idle = {"serve/d:4": {"series": {
        **nodes["serve/d:4"]["series"],
        "serve.queue_depth": [[999.0, 0.0]]}}}
    assert rule.evaluate(ClusterView(_snap(idle))) == []
    # Recent progress inside the threshold: quiet.
    assert rule.evaluate(
        ClusterView(_snap(nodes, ts=942.0))) == []


def test_migration_stall_rule_requires_sustained_inflight():
    from ptype_tpu.health import MigrationStallRule

    rule = MigrationStallRule(window_s=60.0)

    def node(inflight_pts, done_pts):
        return {"series": {"serve.migrate_inflight": inflight_pts,
                           "serve.migrations": done_pts}}

    held = [[t, 2.0] for t in (950.0, 970.0, 990.0)]
    flat = [[950.0, 5.0], [990.0, 5.0]]
    # In flight the whole window, completions flat: the wedge.
    alerts = rule.evaluate(ClusterView(_snap(
        {"serve/a:1": node(held, flat)})))
    assert len(alerts) == 1 and alerts[0].node == "serve/a:1"
    assert alerts[0].severity == "page"
    assert "obs serve" in alerts[0].message
    # Completions advancing: busy, not wedged.
    moving = [[950.0, 5.0], [990.0, 7.0]]
    assert rule.evaluate(ClusterView(_snap(
        {"serve/a:1": node(held, moving)}))) == []
    # Drained mid-window (gauge touched zero): the abort landed.
    drained = [[950.0, 2.0], [970.0, 0.0], [990.0, 1.0]]
    assert rule.evaluate(ClusterView(_snap(
        {"serve/a:1": node(drained, flat)}))) == []
    # A unified fleet (no gauge at all) never pays a false page.
    assert rule.evaluate(ClusterView(_snap(
        {"serve/a:1": {"series": {}}}))) == []


def test_reshard_stall_rule_requires_sustained_inflight():
    """Elastic training (ISSUE 17): the reshard-stall page mirrors
    migration-stall — gauge held high across the window with the
    completion counter flat is a wedged live reshard (training parked
    on the survivor set)."""
    from ptype_tpu.health import ReshardStallRule, default_rules

    rule = ReshardStallRule(window_s=60.0)

    def node(inflight_pts, done_pts):
        return {"series": {"train.reshard_inflight": inflight_pts,
                           "train.reshards": done_pts}}

    held = [[t, 1.0] for t in (950.0, 970.0, 990.0)]
    flat = [[950.0, 3.0], [990.0, 3.0]]
    alerts = rule.evaluate(ClusterView(_snap(
        {"train/a:1": node(held, flat)})))
    assert len(alerts) == 1 and alerts[0].severity == "page"
    assert "obs scale" in alerts[0].message
    # A reshard completing inside the window: progress, not a wedge.
    moving = [[950.0, 3.0], [990.0, 4.0]]
    assert rule.evaluate(ClusterView(_snap(
        {"train/a:1": node(held, moving)}))) == []
    # Gauge touched zero mid-window: the swap (or abort) landed.
    drained = [[950.0, 1.0], [970.0, 0.0], [990.0, 1.0]]
    assert rule.evaluate(ClusterView(_snap(
        {"train/a:1": node(drained, flat)}))) == []
    # Non-elastic trainers (no gauge) never pay a false page.
    assert rule.evaluate(ClusterView(_snap(
        {"train/a:1": {"series": {}}}))) == []
    # Structural: armed by default.
    assert "reshard-stall" in {r.name for r in default_rules()}


def test_default_rules_include_serving_set():
    # Structural serving rules are always armed; the TTFT page is an
    # SLO target only the operator can pick, so like P99Rule it is
    # opt-in — a healthy prompt-heavy fleet must not page (and
    # auto-capture profiles) against an arbitrary default.
    names = {r.name for r in default_rules()}
    assert {"kv-pressure", "prefix-hit-collapse",
            "serve-stall", "migration-stall"} <= names
    assert "ttft-p99" not in names
    armed = {r.name for r in default_rules(slo_ttft_ms=2000.0)}
    assert "ttft-p99" in armed


# -------------------------------------------- gateway plumbing (unit)


def test_count_generated_truncates_at_stop_token():
    from ptype_tpu.gateway.frontdoor import _count_generated

    out = np.array([[5, 7, 2, 0, 0, 0],     # stopped at token 3
                    [1, 4, 6, 8, 9, 3]])    # ran the full width
    assert _count_generated(out, stop_token=2) == 3 + 6
    # No stop token: every cell was generated.
    assert _count_generated(out, stop_token=-1) == 12
    # Pad value colliding with real tokens never under-counts: only
    # the stop token truncates.
    assert _count_generated(np.zeros((2, 4)), stop_token=-1) == 8


def test_slo_tracker_ttft_feed_and_hint_ordering():
    from ptype_tpu.gateway.slo import SLOTracker

    reg = metrics_mod.MetricsRegistry()
    slo = SLOTracker("t", registry=reg, slo_p99_ms=10_000.0,
                     slo_ttft_p99_ms=500.0)
    for _ in range(25):
        slo.answered(50.0, tokens=8)
        slo.record_ttft(900.0)          # TTFT blown, e2e healthy
    p = slo.percentiles()
    assert p["ttft_p99_ms"] == pytest.approx(900.0, rel=0.05)
    hint = slo.scale_hint(queue_depth=0, max_depth=64, n_replicas=2,
                          inflight=2, capacity=4)
    assert hint.delta == 1 and "ttft" in hint.reason
    assert hint.signals["ttft_p99_ms"] > 500.0
    # Shedding still outranks a TTFT breach (capacity actively short).
    slo.shed()
    hint = slo.scale_hint(queue_depth=3, max_depth=64, n_replicas=2,
                          inflight=2, capacity=4)
    assert hint.delta >= 1 and hint.reason == "shedding load"
    # Real token counts flow into the throughput readout.
    assert slo.tokens_per_sec() > 0.0


def test_pool_probe_drains_only_new_ttft_samples():
    from ptype_tpu.gateway.pool import Replica, ReplicaPool
    from ptype_tpu.registry import Node

    r = Replica(Node(address="127.0.0.1", port=1))
    drain = ReplicaPool._drain_ttft_locked
    pool = object.__new__(ReplicaPool)  # the drain touches no state

    r.reported = {"ttft_recent": [[1, 10.0], [2, 12.0]]}
    with r.lock:
        fresh = drain(pool, r)
    assert fresh == [10.0, 12.0] and r.ttft_seen == 2
    # Overlapping window on the next probe: only seq 3 is new.
    r.reported = {"ttft_recent": [[2, 12.0], [3, 31.0]]}
    with r.lock:
        fresh = drain(pool, r)
    assert fresh == [31.0] and r.ttft_seen == 3
    # Malformed payloads never poison the probe — wrong container,
    # wrong item shape, wrong value types all skip cleanly.
    for bad in ("garbage", [{"seq": 4, "ttft": 5.0}], [[4]],
                [["x", "y"]], [None]):
        r.reported = {"ttft_recent": bad}
        with r.lock:
            assert drain(pool, r) == [], bad
    # A replica restart (fresh ledger, seq back at 1, same registry
    # key) resets the high-water mark instead of dropping every
    # post-restart sample.
    r.reported = {"ttft_recent": [[1, 7.0], [2, 8.0]]}
    with r.lock:
        fresh = drain(pool, r)
    assert fresh == [7.0, 8.0] and r.ttft_seen == 2


# ------------------------------------- cross-process stitching (E2E)


def _registry():
    from ptype_tpu.coord.core import CoordState
    from ptype_tpu.coord.local import LocalCoord
    from ptype_tpu.registry import CoordRegistry

    state = CoordState(sweep_interval=0.1)
    return state, CoordRegistry(LocalCoord(state), lease_ttl=5.0)


@pytest.mark.slow
def test_stitched_request_trace_and_ledger_span_agreement():
    """ISSUE 10 acceptance: one affinity-routed request through a
    GatewayActor over real sockets yields ONE trace — gateway.request
    parenting (through the dispatch rpc.call) the paged engine's
    serve.admit / every prefill chunk / serve.decode spans, with the
    first-token event present — and the ledger's TTFT agrees with the
    span-derived value. The same run proves the probe-fed gateway
    TTFT satellite: fleet percentiles fill from real replica samples.
    """
    import jax.numpy as jnp

    from ptype_tpu import actor as actor_mod
    from ptype_tpu.actor import ActorServer
    from ptype_tpu.gateway import (GatewayActor, GatewayConfig,
                                   InferenceGateway)
    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.rpc import Client, ConnConfig
    from ptype_tpu.serve_engine import (PagedGeneratorActor,
                                        prefix_affinity_key)

    cfg = tfm.preset("tiny", dtype=jnp.float32)
    state, registry = _registry()
    rec_store = trace.enable("t")
    servers, regs = [], []
    gw = client = None
    engine = PagedGeneratorActor(cfg, n_slots=2, block_tokens=16,
                                 prefill_chunk=8)
    prompt = np.arange(1, 21, dtype=np.int32)[None]  # 3 chunks: 8+8+4
    MAX_NEW = 6
    with mock.patch.object(actor_mod, "lookup_local",
                           lambda a, p: None):
        try:
            s = ActorServer("127.0.0.1", 0)
            s.register(engine, "Generator")
            s.serve()
            servers.append(s)
            regs.append(registry.register("llm-o", "r0", "127.0.0.1",
                                          s.port))
            gw = InferenceGateway(
                registry, "llm-o",
                GatewayConfig(probe_interval_s=0.1,
                              default_deadline_s=60.0))
            deadline = time.monotonic() + 10
            while (gw.pool.n_healthy() < 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            gws = ActorServer("127.0.0.1", 0)
            gws.register(GatewayActor(gw), "Gateway")
            gws.serve()
            servers.append(gws)
            regs.append(registry.register("llm-o-gw", "gw0",
                                          "127.0.0.1", gws.port))
            client = Client("test", "llm-o-gw", registry,
                            ConnConfig(initial_node_timeout=10.0))
            # Affinity-routed, end to end: the key rides the actor RPC
            # (positional tail) into InferenceGateway.generate.
            key = prefix_affinity_key(prompt[0], 16)
            out = client.call("Gateway.Generate", prompt, MAX_NEW,
                              0.0, 0, 0, 1.0, -1, 0, 1.0, key)
            assert np.asarray(out).shape == (1, MAX_NEW)
            # The probe loop drains the replica's ttft_recent into the
            # gateway SLO tracker (the satellite): wait one round.
            deadline = time.monotonic() + 10
            while (gw.slo.h_ttft.count < 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert gw.slo.h_ttft.count >= 1
            assert gw.stats()["latency"]["ttft_p99_ms"] > 0.0
        finally:
            if client is not None:
                client.close()
            if gw is not None:
                gw.close()
            for r in regs:
                r.close()
            for s in servers:
                s.close()
            engine.close()
            state.close()
            trace.disable()

    # ---- one stitched trace, client root to engine decode ----
    roots = [s for s in rec_store.spans()
             if s.name == "rpc.call" and s.parent_id is None]
    assert len(roots) == 1, [(s.name, s.parent_id)
                             for s in rec_store.spans()]
    tid = roots[0].trace_id
    chain = {s.name: s for s in rec_store.spans(trace_id=tid)}
    for name in ("gateway.request", "actor/Generator.Generate",
                 "serve.admit", "serve.prefill.chunk[0]",
                 "serve.prefill.chunk[1]", "serve.prefill.chunk[2]",
                 "serve.decode"):
        assert name in chain, (name, sorted(chain))
    handler = chain["actor/Generator.Generate"]
    # Engine spans parent under the replica handler span, which
    # parents (through the gateway's dispatch rpc.call) under
    # gateway.request — one connected tree across three processes'
    # worth of hops.
    for name in ("serve.admit", "serve.prefill.chunk[0]",
                 "serve.prefill.chunk[1]", "serve.prefill.chunk[2]",
                 "serve.decode"):
        assert chain[name].parent_id == handler.span_id, name
    dispatch = [s for s in rec_store.spans(trace_id=tid)
                if s.name == "rpc.call"
                and s.parent_id == chain["gateway.request"].span_id]
    assert len(dispatch) == 1
    assert handler.parent_id == dispatch[0].span_id
    # Every prefill chunk is present and accounts the whole prompt.
    chunks = [s for s in rec_store.spans(trace_id=tid)
              if s.name.startswith("serve.prefill.chunk")]
    assert sum(s.attrs["tokens"] for s in chunks) == 20
    # First-token event, stamped where the token materialized.
    dec = chain["serve.decode"]
    assert [e["name"] for e in dec.events] == ["first_token"]
    assert dec.attrs["tokens"] == MAX_NEW
    # ---- ledger vs span agreement ----
    led_rec = engine.ledger.records()[-1]
    span_ttft_ms = (dec.start_s - chain["serve.admit"].start_s) * 1e3
    assert led_rec["ttft_ms"] == pytest.approx(span_ttft_ms, abs=25.0)
    assert dec.attrs["ttft_ms"] == led_rec["ttft_ms"]


# ------------------------------------- seeded KV-pressure drill (E2E)


class _ServeNode:
    """One simulated serving replica: its own registry, paged engine,
    sampler, and an actor server exposing Generator + ptype.Telemetry
    (and the built-in ptype.Profile the capture hook dials)."""

    def __init__(self, name, cfg, registry, n_blocks):
        from ptype_tpu.serve_engine import PagedGeneratorActor

        self.reg = metrics_mod.MetricsRegistry()
        self.engine = PagedGeneratorActor(
            cfg, n_slots=8, block_tokens=16, n_blocks=n_blocks,
            max_len=128, prefill_chunk=32, metrics_registry=self.reg)
        self.sampler = Sampler(registry=self.reg, cadence_s=0.02,
                               memory=False)
        from ptype_tpu.actor import ActorServer

        self.server = ActorServer("127.0.0.1", 0)
        self.server.register(self.engine, "Generator")
        self.server.register_function(
            "ptype.Telemetry",
            telemetry_endpoint(self.reg, self.sampler.store, name))
        self.server.serve()
        self.registration = registry.register(
            "serve", name, "127.0.0.1", self.server.port)
        self.key = f"serve/127.0.0.1:{self.server.port}"

    def close(self):
        self.sampler.close()
        self.registration.close()
        self.server.close()
        self.engine.close()


def run_kv_pressure_drill(pressure: bool, coord, out_dir):
    """Two paged replicas serve concurrent 4-way traffic; under
    ``pressure`` one replica's block pool is sized so the live load
    pins its admission headroom near zero while unique prompts churn
    its cached blocks out (real evictions, not injected numbers). The
    clean twin gives both replicas ample pools. Returns
    (alerts, afflicted_key, snapshot, capture_hook)."""
    import jax.numpy as jnp

    from ptype_tpu import telemetry
    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.registry import CoordRegistry

    cfg = tfm.preset("tiny", dtype=jnp.float32)
    registry = CoordRegistry(coord, lease_ttl=5.0)
    # 8 slots × 5 blocks/request = 40 blocks live at full batch; 42
    # total (1 held back) pins free at 1/41 (2%) while driven, and —
    # the part that matters for the majority gate under the sampler's
    # CHANGE-driven stamping — even the transient retire spike
    # (1 + 5 released = 6/41 = 14.6%) sits under the rule's 15%
    # floor, so every mid-drive sample reads "pinned low".
    nodes = [_ServeNode("r0", cfg, registry, n_blocks=80),
             _ServeNode("r1", cfg, registry,
                        n_blocks=42 if pressure else 80)]
    afflicted = nodes[1]
    # timeout_s lifted above the default 20 s: the capture RPC is
    # in-process here and can queue behind a loaded host's scheduler
    # (observed once under a concurrent full-suite run).
    cap = AlertCapture(out_dir=str(out_dir), duration_s=0.05,
                       min_interval_s=300.0, background=False,
                       timeout_s=120.0)
    # The TTFT rule is ARMED (the opt-in path runs) but with its SLO
    # lifted out of the way: BOTH replicas queue deep behind their
    # slots, so a host-load-dependent ttft-p99 would flake the clean
    # run; this drill is the kv-pressure acceptance and the TTFT rule
    # has its own deterministic unit tier above.
    engine = AlertEngine(default_rules(slo_ttft_ms=60_000.0),
                         cooldown_s=0.0,
                         registry=metrics_mod.MetricsRegistry(),
                         capture=cap)
    rng = np.random.default_rng(7)

    def prompt():
        return jnp.asarray(
            rng.integers(1, cfg.vocab_size, 48, dtype=np.int64
                         ).astype(np.int32))[None]

    def drive(node, n=40, max_new=24):
        """One saturated stream: all ``n`` unique-prefix requests
        submitted at once, so the 8 slots stay occupied (admission
        headroom pinned) and every admission evicts cached blocks."""
        outs = []

        def one(p):
            outs.append(np.asarray(node.engine.Generate(p, max_new)))

        threads = [threading.Thread(target=one, args=(prompt(),))
                   for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert len(outs) == n

    try:
        for node in nodes:          # compile off the clock; also
            drive(node, n=1, max_new=2)  # seeds cached blocks the
        #                                pressure stream must evict
        for node in nodes:
            node.sampler.start()
        for node in nodes:
            drive(node)
        for node in nodes:
            node.engine._export_gauges()  # final kv sample
            node.sampler.sample_once()
        snap = telemetry.cluster_snapshot(registry,
                                          include_local=False)
        alerts = engine.evaluate(snap)
        return alerts, afflicted.key, snap, cap
    finally:
        for node in nodes:
            node.close()


@pytest.mark.slow
def test_seeded_kv_pressure_drill_names_replica_and_captures(
        tmp_path, coord):
    """Acceptance: pool-exhaustion pressure on one replica → the
    ``kv-pressure`` page NAMES that replica within the sampling
    window and the PR 8 capture hook lands a profile artifact for it;
    re-firing inside the rate limit captures nothing new."""
    alerts, key, snap, cap = run_kv_pressure_drill(
        True, coord, tmp_path)
    assert "kv-pressure" in [a.rule for a in alerts], alerts
    kv = [a for a in alerts if a.rule == "kv-pressure"]
    assert [a.node for a in kv] == [key]
    # Whatever else fired under pressure fired on the afflicted
    # replica, not its healthy sibling.
    assert {a.node for a in alerts} == {key}, alerts
    # The snapshot carries the pressure series the rule read.
    telem = snap["nodes"][key]
    assert telem["series"]["kv.evictions.rate"], telem["series"].keys()
    # The capture hook dialed the NAMED node and wrote artifacts.
    caps = [c for c in cap.captures if c["rule"] == "kv-pressure"]
    assert len(caps) == 1, (cap.captures, cap.errors)
    assert caps[0]["node"] == key and caps[0]["files"] >= 1
    # ... and `obs serve` renders the replica and the page.
    view = render_serve(snap, alerts)
    assert key[:28] in view and "kv-pressure" in view
    n_caps = len(cap.captures)
    # Inside the capture rate limit a repeat firing adds no capture.
    engine2 = AlertEngine(default_rules(), cooldown_s=0.0,
                          registry=metrics_mod.MetricsRegistry(),
                          capture=cap)
    again = engine2.evaluate(snap, now=snap["ts"] + 1.0)
    assert "kv-pressure" in [a.rule for a in again]
    assert len(cap.captures) == n_caps


@pytest.mark.slow
def test_clean_kv_drill_fires_nothing(tmp_path, coord):
    """False-positive guard: the identical drill with well-sized
    pools raises zero alerts and captures zero profiles."""
    alerts, _, snap, cap = run_kv_pressure_drill(False, coord,
                                                tmp_path)
    assert alerts == [], alerts
    assert cap.captures == [] and cap.errors == []
    view = render_serve(snap)
    assert "no alerts" in view and "2 serving replicas" in view


# ----------------------------------------------------- obs serve view


def test_render_serve_rows_and_skips_non_serving_nodes():
    snap = {"ts": 123.0, "nodes": {
        "serve/a:1": {"metrics": {
            "histograms": {"serve.ttft_ms": {"p99": 140.0},
                           "serve.tpot_ms": {"p50": 9.0},
                           "serve.e2e_ms": {"p99": 300.0}},
            "gauges": {"serve.queue_depth": 2.0,
                       "serve.active_slots": 3.0,
                       "kv.free_blocks": 12.0, "kv.util_pct": 62.5,
                       "kv.prefix_hit_rate": 0.4,
                       "serve.stall_ms": 1.2},
            "counters": {"kv.evictions": 5.0}}},
        "train/w0": {"metrics": {"gauges": {"goodput.step_ms": 9.0}}},
    }, "errors": {"serve/dead:9": "refused"}}
    view = render_serve(snap)
    assert "1 serving replicas" in view
    assert "serve/a:1" in view and "train/w0" not in view
    assert "140" in view and "UNREACHABLE" in view
    empty = render_serve({"ts": 0.0, "nodes": {}, "errors": {}})
    assert "no serving replicas" in empty


def test_render_serve_class_column_and_migration_counters():
    """The disaggregated columns (ISSUE 16): `obs serve` names each
    replica's serving class and its migration counters; also pins
    top.py's inline class-name copy to ``SERVE_CLASSES`` /
    ``SERVE_CLASS_CODES`` (the docstring's sync contract)."""
    from ptype_tpu.health.top import _SERVE_CLASS_NAMES
    from ptype_tpu.serve_engine import (SERVE_CLASS_CODES,
                                        SERVE_CLASSES)

    assert _SERVE_CLASS_NAMES == SERVE_CLASSES
    assert SERVE_CLASS_CODES == {
        n: i for i, n in enumerate(_SERVE_CLASS_NAMES)}
    snap = {"ts": 123.0, "nodes": {
        "serve/dec:1": {"metrics": {
            "histograms": {"serve.ttft_ms": {"p99": 40.0}},
            "gauges": {"serve.queue_depth": 0.0,
                       "serve.class":
                           float(SERVE_CLASS_CODES["decode"])},
            "counters": {"serve.migrations": 7.0,
                         "serve.migrate_bytes": 2_500_000.0,
                         "serve.migrate_dedup_hits": 12.0}}},
        "serve/uni:2": {"metrics": {
            "histograms": {"serve.ttft_ms": {"p99": 55.0}},
            "gauges": {"serve.queue_depth": 1.0}, "counters": {}}},
    }, "errors": {}}
    view = render_serve(snap)
    assert "class" in view and "decode" in view
    assert "7" in view and "2.50" in view and "12" in view
    # A unified replica (no class gauge, no counters) renders dashes,
    # not zeros — "never migrated" is not "migrated nothing".
    uni_row = next(ln for ln in view.splitlines() if "uni" in ln)
    assert "-" in uni_row and "decode" not in uni_row


def test_run_serve_loop_renders_and_returns_engine(coord):
    from ptype_tpu.health import run_serve
    from ptype_tpu.registry import CoordRegistry

    out: list[str] = []
    engine = run_serve(CoordRegistry(coord, lease_ttl=5.0), iters=1,
                       interval_s=0.0, out=out.append, clear=False)
    assert out and "ptype serving @" in out[0]
    assert isinstance(engine, AlertEngine)


# ------------------------------------------------------ obs topo view


def test_render_topo_domains_legs_and_migration_split():
    """The topology one-pager (ISSUE 18): replicas group by the
    ``serve.domain`` gauge, hierarchical-launch nodes show per-leg
    wire bytes with the slow-leg share, and the gateway's migration
    counters fold into the local/cross locality split."""
    from ptype_tpu.health import render_topo

    snap = {"ts": 5.0, "nodes": {
        "llm/a:1": {"metrics": {
            "gauges": {"serve.domain": 0.0, "serve.lifecycle": 3.0,
                       "serve.queue_depth": 2.0,
                       "serve.active_slots": 1.0}, "counters": {}}},
        "llm/b:2": {"metrics": {
            "gauges": {"serve.domain": 0.0, "serve.lifecycle": 3.0},
            "counters": {}}},
        "llm/c:3": {"metrics": {
            "gauges": {"serve.domain": 1.0, "serve.lifecycle": 4.0},
            "counters": {}}},
        "train/w0": {"metrics": {"gauges": {}, "counters": {
            "collectives.hier_launches": 6.0,
            "collectives.leg_bytes.inner": 24e6,
            "collectives.leg_bytes.outer": 4e6,
            "collectives.leg_bytes.flat_outer": 28e6}}},
        "local": {"metrics": {"gauges": {}, "counters": {
            "serve.migrate.local_domain": 9.0,
            "serve.migrate.cross_domain": 1.0}}},
    }, "errors": {"llm/dead:9": "refused"}}
    view = render_topo(snap)
    assert "3 placed replicas in 2 domains" in view
    d0 = next(ln for ln in view.splitlines() if ln.startswith("0 "))
    assert " 2 " in d0          # two replicas, both active, in d0
    d1 = next(ln for ln in view.splitlines() if ln.startswith("1 "))
    assert "llm/c:3"[:24] in d1
    assert "train/w0" in view and "14.3" in view   # slow-leg share
    assert "9 local-domain, 1 cross-domain" in view
    assert "10.0% crossing the slow leg" in view
    assert "UNREACHABLE" in view


def test_render_topo_flat_fleet_renders_placeholders():
    from ptype_tpu.health import render_topo

    view = render_topo({"ts": 0.0, "nodes": {}, "errors": {}})
    assert "no node exports serve.domain" in view
    assert "no hierarchical collective launches" in view
    assert "0 local-domain, 0 cross-domain" in view
    assert "no alerts" in view


def test_run_topo_loop_renders_and_returns_engine(coord):
    from ptype_tpu.health import run_topo
    from ptype_tpu.registry import CoordRegistry

    out: list[str] = []
    engine = run_topo(CoordRegistry(coord, lease_ttl=5.0), iters=1,
                      interval_s=0.0, out=out.append, clear=False)
    assert out and "ptype topology @" in out[0]
    assert isinstance(engine, AlertEngine)


def test_replica_host_exports_domain_gauge(coord):
    """ReplicaHost stamps its placement on the ``serve.domain``
    gauge (the telemetry mirror of the registration metadata the
    gateway routes on) so ``obs topo`` sees domains without a
    probe."""
    from ptype_tpu import metrics as metrics_mod
    from ptype_tpu.reconciler.replica import ReplicaHost
    from ptype_tpu.registry import CoordRegistry

    class _Idle:
        def Info(self):
            return {}

    reg = metrics_mod.MetricsRegistry()
    host = ReplicaHost(CoordRegistry(coord, lease_ttl=5.0), "llm-dom",
                       "r0", _Idle, warm_hold=True,
                       metrics_registry=reg, domain=2)
    try:
        assert reg.gauge("serve.domain").value == 2.0
    finally:
        host.close()
