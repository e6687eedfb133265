"""Elastic replica lifecycle (ISSUE 13): hysteresis-policy units, the
reconciler's spawn/drain/replace drills over in-process replica hosts
(real sockets, real registry — the LocalLauncher fleet the reconciler
cannot tell apart from OS processes), the paged engine's drain seam,
the gateway pool's lifecycle column + draining-last routing, the
scale.* chaos seams, and the `obs scale` / `obs serve` renders.

Fast tier on purpose: replicas are FakeGeneratorActors (numpy, no
XLA) except the one engine drain-seam test; the OS-process worker
path rides tests/test_reconciler_mp.py (slow tier).
"""

import threading
import time

import numpy as np
import pytest

from ptype_tpu import chaos
from ptype_tpu.chaos import FaultPlan, FaultSpec
from ptype_tpu.errors import ShedError
from ptype_tpu.gateway import GatewayConfig, InferenceGateway
from ptype_tpu.metrics import MetricsRegistry
from ptype_tpu.reconciler import (FakeGeneratorActor, HysteresisPolicy,
                                  LocalLauncher, Reconciler,
                                  ReconcilerConfig)
from ptype_tpu.registry import CoordRegistry

PROMPT = np.zeros((1, 4), np.int32)


@pytest.fixture(autouse=True)
def _lock_order_watchdog(lock_order_watchdog):
    """Every test in this concurrency tier runs under the runtime
    lock-order watchdog (the shared ``lock_order_watchdog`` fixture in
    conftest.py — zero cycles is the teardown invariant)."""
    yield


class _Hint:
    def __init__(self, delta, reason="steady"):
        self.delta = delta
        self.reason = reason


# ------------------------------------------------- policy (pure units)


def test_policy_symmetric_flap_holds_steady():
    """A perfectly flapping hint stream (+1/-1/+1/-1...) never reaches
    a majority: the count holds — the thrash acceptance drill's pure
    core."""
    p = HysteresisPolicy(min_replicas=1, max_replicas=8,
                         cooldown_s=10.0, window=4, quorum=2)
    t = 0.0
    for i in range(40):
        d = p.observe(_Hint(1 if i % 2 == 0 else -1,
                            "queue" if i % 2 == 0 else "idle"),
                      n_replicas=2, now=t)
        assert d is None, (i, d)
        t += 1.0


def test_policy_biased_flap_one_transition_per_cooldown():
    """An up-BIASED flapping stream transitions — but exactly once per
    cooldown window, however many hints arrive inside it."""
    p = HysteresisPolicy(min_replicas=1, max_replicas=8,
                         cooldown_s=5.0, window=5, quorum=3)
    decisions = []
    t = 0.0
    seq = [1, 1, -1, 1, 1]  # 4 up / 1 down per burst: a real margin
    for i in range(100):  # 100 hints over 10s = two cooldown windows
        d = p.observe(_Hint(seq[i % 5], "queue depth"),
                      n_replicas=2, now=t)
        if d is not None:
            decisions.append((t, d))
        t += 0.1
    assert len(decisions) == 2, decisions  # 10s / 5s cooldown
    assert all(d.delta > 0 for _, d in decisions)
    # ... and the transitions are one cooldown apart, not back-to-back.
    assert decisions[1][0] - decisions[0][0] >= 5.0


def test_policy_shed_burst_outranks_idle_shrink():
    """A window full of idle-shrink votes is overruled by ONE
    shed-class hint: provably-short capacity beats a utilization
    reading, and it doesn't wait for quorum."""
    p = HysteresisPolicy(min_replicas=1, max_replicas=8,
                         cooldown_s=10.0, window=5, quorum=5)
    for i in range(3):
        assert p.observe(_Hint(-1, "fleet under a third utilized"),
                         n_replicas=4, now=float(i)) is None
    d = p.observe(_Hint(2, "shedding load"), n_replicas=4, now=3.0)
    assert d is not None and d.delta == 2 and d.urgent, d
    assert d.votes["down"] == 3 and d.votes["urgent"] == 1


def test_policy_cooldown_binds_urgent_votes_too():
    p = HysteresisPolicy(min_replicas=1, max_replicas=8,
                         cooldown_s=5.0, window=3, quorum=1)
    assert p.observe(_Hint(1, "shedding load"), 2, now=0.0) is not None
    # Still shedding — but inside the cooldown nothing moves.
    for t in (0.5, 2.0, 4.9):
        assert p.observe(_Hint(1, "shedding load"), 3, now=t) is None
    assert p.observe(_Hint(1, "shedding load"), 3, now=5.1) is not None


def test_policy_bounds_clamp_and_swallow():
    p = HysteresisPolicy(min_replicas=2, max_replicas=4,
                         cooldown_s=0.0, window=3, quorum=1)
    # At the ceiling an up-decision clamps to nothing (no phantom
    # transition, no cooldown consumed).
    assert p.observe(_Hint(3, "shedding load"), 4, now=0.0) is None
    # Below the ceiling the step clamps to the remaining headroom.
    d = p.observe(_Hint(5, "shedding load"), 3, now=1.0)
    assert d is not None and d.delta == 1
    # At the floor a down-majority swallows.
    p2 = HysteresisPolicy(min_replicas=2, max_replicas=4,
                          cooldown_s=0.0, window=3, quorum=3)
    for t in range(2):
        assert p2.observe(_Hint(-1, "idle"), 2, now=float(t)) is None
    assert p2.observe(_Hint(-1, "idle"), 2, now=2.0) is None


def test_policy_shrinks_one_replica_at_a_time():
    p = HysteresisPolicy(min_replicas=1, max_replicas=8,
                         cooldown_s=0.0, window=3, quorum=3)
    for t in range(2):
        assert p.observe(_Hint(-3, "idle"), 6, now=float(t)) is None
    d = p.observe(_Hint(-3, "idle"), 6, now=2.0)
    assert d is not None and d.delta == -1, d


def test_policy_quorum_gates_non_urgent():
    p = HysteresisPolicy(min_replicas=1, max_replicas=8,
                         cooldown_s=0.0, window=5, quorum=3)
    assert p.observe(_Hint(1, "queue"), 2, now=0.0) is None
    assert p.observe(_Hint(1, "queue"), 2, now=1.0) is None
    assert p.observe(_Hint(1, "queue"), 2, now=2.0) is not None


def test_policy_stale_votes_expire():
    """Votes older than the TTL can't combine with one fresh hint
    into a phantom majority after a quiet stretch."""
    p = HysteresisPolicy(min_replicas=1, max_replicas=8,
                         cooldown_s=2.0, window=5, quorum=3,
                         vote_ttl_s=2.0)
    assert p.observe(_Hint(1, "queue"), 2, now=0.0) is None
    assert p.observe(_Hint(1, "queue"), 2, now=0.5) is None
    # 10s of silence; the two old up-votes are stale now.
    assert p.observe(_Hint(1, "queue"), 2, now=10.0) is None


# ----------------------------------------------------- fleet fixtures


def _fleet(coord, service="llm", delay_s=0.02, warm_pool=0,
           min_replicas=1, max_replicas=4, cooldown_s=0.3,
           drain_deadline_s=10.0, hints=None, quorum=1, window=3):
    registry = CoordRegistry(coord, lease_ttl=2.0)
    mreg = MetricsRegistry()
    launcher = LocalLauncher(
        registry, lambda: FakeGeneratorActor(delay_s=delay_s),
        service=service)
    cfg = ReconcilerConfig(
        min_replicas=min_replicas, max_replicas=max_replicas,
        warm_pool=warm_pool, cooldown_s=cooldown_s,
        vote_window=window, vote_quorum=quorum,
        tick_interval_s=0.05, drain_deadline_s=drain_deadline_s)
    rec = Reconciler(registry, service, launcher, hints=hints,
                     cfg=cfg, metrics_registry=mreg)
    return registry, launcher, rec, mreg


def _settle(rec, n, timeout=8.0):
    """Tick until the fleet holds ``n`` ACTIVE replicas."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rec.tick()
        st = rec.status()
        active = sum(1 for r in st["replicas"].values()
                     if r["lifecycle"] == "active")
        if active == n and not st["pending_spawns"]:
            return True
        time.sleep(0.03)
    return False


def _gateway(registry, service, **over):
    cfg = GatewayConfig(probe_interval_s=0.1, probe_timeout_s=1.0,
                        eviction_threshold=3, default_deadline_s=10.0)
    for k, v in over.items():
        setattr(cfg, k, v)
    return InferenceGateway(registry, service, cfg,
                            metrics_registry=MetricsRegistry())


# ------------------------------------------------- reconciler (drills)


def test_bootstrap_to_min_replicas(coord):
    registry, launcher, rec, mreg = _fleet(coord, min_replicas=2)
    try:
        assert _settle(rec, 2)
        assert mreg.counter("scale.spawns").value == 2
        assert rec.desired == 2
        # Both registered: the gateway-visible fleet matches.
        assert len(registry.nodes("llm")) == 2
    finally:
        rec.close(stop_fleet=True)
        launcher.close()


def test_traffic_spike_scales_up_before_slo_burn(coord):
    """Acceptance (a): a burst a 1-replica fleet sheds on triggers an
    URGENT scale-up from the gateway's own hint stream; the burst is
    fully answered (retries ride the typed retry_after) and the final
    burn rate is under the fast-burn page threshold."""
    registry, launcher, rec, mreg = _fleet(
        coord, delay_s=0.08, min_replicas=1, max_replicas=3,
        cooldown_s=0.2)
    gw = None
    try:
        assert _settle(rec, 1)
        gw = _gateway(registry, "llm", max_queue_depth=4,
                      per_replica_inflight=1)
        assert gw.pool.n_healthy() >= 1
        rec._hints = gw.scale_hint
        rec.start()
        results, errors, lock = [], [], threading.Lock()

        def worker():
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                try:
                    out = gw.generate(PROMPT, 4, deadline_s=5.0)
                    with lock:
                        results.append(np.asarray(out))
                    return
                except ShedError as e:
                    time.sleep(min(0.2, e.retry_after_s))
            with lock:
                errors.append("deadline")

        threads = [threading.Thread(target=worker) for _ in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors and len(results) == 10
        assert all((r == 7).all() for r in results)
        # The hint stream actually drove a scale-up...
        assert mreg.counter("scale.up").value >= 1
        assert gw.pool.n_healthy() >= 2
        # ... and it landed BEFORE the SLO budget burned: the spike's
        # shed burst was transient — a second wave at the same
        # concurrency now fits the grown fleet and sheds NOTHING
        # (the burn stopped the moment capacity caught up).
        sheds_before = int(gw.slo.c_shed.value)
        wave2 = [threading.Thread(target=worker) for _ in range(6)]
        results.clear()
        for t in wave2:
            t.start()
        for t in wave2:
            t.join(timeout=30)
        assert not errors and len(results) == 6
        assert int(gw.slo.c_shed.value) == sheds_before
    finally:
        if gw is not None:
            gw.close()
        rec.close(stop_fleet=True)
        launcher.close()


def test_replica_kill_replaced_with_zero_lost_on_survivors(coord):
    """Acceptance (b): kill one replica mid-traffic — every request
    is still answered (the frontdoor re-routes the victim's in-flight
    to survivors) and the reconciler registers a replacement."""
    registry, launcher, rec, mreg = _fleet(
        coord, delay_s=0.05, min_replicas=2, max_replicas=4)
    gw = None
    try:
        assert _settle(rec, 2)
        gw = _gateway(registry, "llm", max_queue_depth=32,
                      per_replica_inflight=2, max_reroutes=3)
        deadline = time.monotonic() + 5
        while gw.pool.n_healthy() < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert gw.pool.n_healthy() == 2
        rec.start()
        results, errors, lock = [], [], threading.Lock()
        stop = threading.Event()

        def worker():
            while not stop.is_set():
                try:
                    out = gw.generate(PROMPT, 4, deadline_s=8.0)
                    with lock:
                        results.append(np.asarray(out))
                except ShedError as e:
                    time.sleep(min(0.2, e.retry_after_s))
                except Exception as e:  # noqa: BLE001 — the drill's
                    with lock:          # zero-lost assertion target
                        errors.append(repr(e))

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.4)  # traffic flowing on both replicas
        victim = rec._pick_victim()
        assert victim is not None
        victim.kill()
        # Replacement: the reconciler notices the death and respawns.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            st = rec.status()
            active = sum(1 for r in st["replicas"].values()
                         if r["lifecycle"] == "active")
            if active == 2 and mreg.counter(
                    "scale.replacements").value >= 1:
                break
            time.sleep(0.05)
        time.sleep(0.4)  # traffic through the replacement too
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
        assert len(results) > 10
        assert mreg.counter("scale.deaths").value == 1
        assert mreg.counter("scale.replacements").value == 1
        st = rec.status()
        assert sum(1 for r in st["replicas"].values()
                   if r["lifecycle"] == "active") == 2
    finally:
        if gw is not None:
            gw.close()
        rec.close(stop_fleet=True)
        launcher.close()


def test_flapping_hint_stream_holds_count_steady(coord):
    """Acceptance (c): a symmetric flapping hint stream produces ZERO
    transitions — the voting window never reaches majority."""
    flip = [0]

    def hints():
        flip[0] += 1
        return _Hint(1 if flip[0] % 2 else -1,
                     "queue depth" if flip[0] % 2 else
                     "fleet under a third utilized")

    registry, launcher, rec, mreg = _fleet(
        coord, min_replicas=2, cooldown_s=0.1, hints=hints,
        quorum=2, window=4)
    try:
        assert _settle(rec, 2)
        for _ in range(40):
            rec.tick()
            time.sleep(0.01)
        assert mreg.counter("scale.decisions").value == 0
        st = rec.status()
        assert sum(1 for r in st["replicas"].values()
                   if r["lifecycle"] == "active") == 2
        assert rec.desired == 2
    finally:
        rec.close(stop_fleet=True)
        launcher.close()


def test_graceful_drain_finishes_in_flight_zero_lost(coord):
    """Acceptance (d): scale-down drains the victim — in-flight
    requests FINISH (drain_lost_requests == 0), new work sheds typed
    and lands on the survivor, and the victim deregisters only after
    its last request completed."""
    registry, launcher, rec, mreg = _fleet(
        coord, delay_s=0.25, min_replicas=2, drain_deadline_s=10.0)
    try:
        assert _settle(rec, 2)
        victim = rec._pick_victim()
        host = next(h for h in launcher.hosts
                    if h.node_name == victim.name)
        results, errors, lock = [], [], threading.Lock()

        def inflight():
            try:
                out = host.actor.Generate(PROMPT, 4)
                with lock:
                    results.append(np.asarray(out))
            except Exception as e:  # noqa: BLE001 — the zero-lost bar
                with lock:
                    errors.append(repr(e))

        threads = [threading.Thread(target=inflight)
                   for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.05)  # all three inside Generate now
        rec.desired = 1
        rec.tick()
        # While draining: still registered (in-flight must finish
        # first), but NEW work on the victim sheds typed.
        assert victim.name in rec.status()["replicas"]
        with pytest.raises(ShedError):
            host.actor.Generate(PROMPT, 4)
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
        assert len(results) == 3
        assert all((r == 7).all() for r in results)
        # Drain completes: deregistered, handle reaped.
        deadline = time.monotonic() + 8
        while time.monotonic() < deadline:
            rec.tick()
            if victim.name not in rec.status()["replicas"] \
                    and len(registry.nodes("llm")) == 1:
                break
            time.sleep(0.05)
        assert victim.name not in rec.status()["replicas"]
        assert len(registry.nodes("llm")) == 1
        assert mreg.counter("scale.drains").value == 1
        assert mreg.counter("scale.drain_escalations").value == 0
        # The departure was ORDERED: no death, no replacement.
        rec.tick()
        assert mreg.counter("scale.deaths").value == 0
    finally:
        rec.close(stop_fleet=True)
        launcher.close()


def test_warm_pool_activates_instead_of_spawning(coord):
    """Scale-up consumes the warm standby first: the replica was
    already up with params loaded, so activation is registration-only
    — the fast path a spike needs."""
    registry, launcher, rec, mreg = _fleet(
        coord, min_replicas=1, warm_pool=1, cooldown_s=0.1, quorum=1)
    try:
        assert _settle(rec, 1)
        # Warm standby exists but is NOT registered.
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            rec.tick()
            if any(r["lifecycle"] == "warm"
                   for r in rec.status()["replicas"].values()):
                break
            time.sleep(0.03)
        st = rec.status()
        assert any(r["lifecycle"] == "warm"
                   for r in st["replicas"].values())
        assert len(registry.nodes("llm")) == 1
        spawns_before = mreg.counter("scale.spawns").value
        rec._alert_votes.append(_Hint(1, "shedding load"))
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            rec.tick()
            if mreg.counter("scale.activations").value >= 1:
                break
            time.sleep(0.03)
        assert mreg.counter("scale.activations").value == 1
        assert len(registry.nodes("llm")) == 2
        # The new ACTIVE capacity cost zero fresh spawns (the warm
        # pool refill spawns in the background, but the activation
        # itself consumed the standby).
        st = rec.status()
        active = [r for r in st["replicas"].values()
                  if r["lifecycle"] == "active"]
        assert len(active) == 2
        del spawns_before
    finally:
        rec.close(stop_fleet=True)
        launcher.close()


def test_alert_firing_votes_for_scale_up(coord):
    """health rules → actions: an AlertEngine-shaped firing on a
    serving rule lands as a policy vote (urgent for the shed-driven
    burn-rate rule) and scales the fleet."""

    class _Alert:
        rule = "slo-burn-rate"
        node = "w1"

    registry, launcher, rec, mreg = _fleet(
        coord, min_replicas=1, cooldown_s=0.1)
    try:
        assert _settle(rec, 1)
        rec.observe_alert(_Alert())

        class _Other:
            rule = "loss"  # not a serving-capacity rule: ignored
            node = "w1"

        rec.observe_alert(_Other())
        assert _settle(rec, 2)
        assert mreg.counter("scale.up").value == 1
    finally:
        rec.close(stop_fleet=True)
        launcher.close()


def test_drain_deadline_escalation_kills_wedged_victim(coord):
    """A drain wedged past its deadline (scale.drain chaos) is
    escalated: the victim is killed, the fleet reaches the desired
    size, and the wedge pairs with the escalation's recovery beacon."""
    registry, launcher, rec, mreg = _fleet(
        coord, min_replicas=2, drain_deadline_s=0.4)
    try:
        assert _settle(rec, 2)
        plan = chaos.arm(FaultPlan([
            FaultSpec("scale.drain", "wedge", delay_s=30.0)],
            name="wedged-drain"))
        rec.desired = 1
        rec.tick()
        deadline = time.monotonic() + 8
        while time.monotonic() < deadline:
            rec.tick()
            if mreg.counter("scale.drain_escalations").value >= 1:
                break
            time.sleep(0.05)
        assert mreg.counter("scale.drain_escalations").value == 1
        assert len(plan.fired()) == 1
        assert plan.unrecovered() == {}
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            rec.tick()
            if len(registry.nodes("llm")) == 1:
                break
            time.sleep(0.05)
        assert len(registry.nodes("llm")) == 1
    finally:
        chaos.disarm()
        rec.close(stop_fleet=True)
        launcher.close()


def test_scale_spawn_chaos_fails_then_retries_and_pairs(coord):
    """scale.spawn 'fail' kills the first spawn; the next tick
    retries, succeeds, and the success beacon pairs the fault —
    unrecovered() == {} is the soak invariant."""
    registry = CoordRegistry(coord, lease_ttl=2.0)
    mreg = MetricsRegistry()
    launcher = LocalLauncher(registry, FakeGeneratorActor,
                             service="llm")
    rec = Reconciler(registry, "llm", launcher,
                     cfg=ReconcilerConfig(min_replicas=2,
                                          tick_interval_s=0.05),
                     metrics_registry=mreg)
    plan = chaos.arm(FaultPlan([
        FaultSpec("scale.spawn", "fail", times=1),
        FaultSpec("scale.spawn", "delay", after=1, delay_s=0.05)],
        name="spawn-chaos"))
    try:
        deadline = time.monotonic() + 8
        while time.monotonic() < deadline:
            rec.tick()
            st = rec.status()
            if sum(1 for r in st["replicas"].values()
                   if r["lifecycle"] == "active") == 2:
                break
            time.sleep(0.05)
        assert mreg.counter("scale.spawn_failures").value == 1
        assert sum(1 for r in rec.status()["replicas"].values()
                   if r["lifecycle"] == "active") == 2
        fired = [(e.site, e.action) for e in plan.fired()]
        assert ("scale.spawn", "fail") in fired
        assert ("scale.spawn", "delay") in fired
        assert plan.unrecovered() == {}
    finally:
        chaos.disarm()
        rec.close(stop_fleet=True)
        launcher.close()


# ------------------------------------ lifecycle surfaces (satellite 1)


def test_pool_snapshot_lifecycle_column_and_draining_routing(coord):
    """Replica.snapshot() carries the lifecycle; pick() sorts a
    draining replica LAST and prefix affinity yields past it."""
    registry, launcher, rec, _mreg = _fleet(
        coord, min_replicas=2, drain_deadline_s=10.0)
    gw = None
    try:
        assert _settle(rec, 2)
        gw = _gateway(registry, "llm")
        deadline = time.monotonic() + 5
        while gw.pool.n_healthy() < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        gw.pool.probe_now()
        snaps = gw.pool.status()["replicas"]
        assert all(s.get("lifecycle") == "active" for s in snaps)
        victim = rec._pick_victim()
        host = next(h for h in launcher.hosts
                    if h.node_name == victim.name)
        host.actor.begin_drain()
        gw.pool.probe_now()
        snaps = {s["key"]: s for s in gw.pool.status()["replicas"]}
        assert snaps[victim.addr]["lifecycle"] == "draining"
        # Routing: every pick lands on the survivor now...
        survivor = next(k for k in snaps if k != victim.addr)
        for _ in range(8):
            assert gw.pool.pick().key == survivor
        # ... including affinity keys that hash onto the victim.
        for i in range(8):
            assert gw.pool.pick(affinity_key=f"k{i}").key == survivor
    finally:
        if gw is not None:
            gw.close()
        rec.close(stop_fleet=True)
        launcher.close()


def test_replica_ctl_endpoints_over_the_wire(coord):
    """The Replica.* control face works over a real socket — what the
    reconciler drives for OS-process workers."""
    from ptype_tpu import rpc as rpc_mod
    from ptype_tpu.registry import Node

    registry = CoordRegistry(coord, lease_ttl=2.0)
    launcher = LocalLauncher(registry, FakeGeneratorActor,
                             service="llm")
    handle = launcher.spawn("wire-r0", warm_hold=True)
    conn = None
    try:
        host, port = handle.addr.split(":")
        conn = rpc_mod._dial(Node(address=host, port=int(port)), 2.0)

        def call(method, *args):
            return conn.call_async(method, args).result(timeout=5)

        st = call("Replica.Status")
        assert st["lifecycle"] == "warm" and not st["registered"]
        assert len(registry.nodes("llm")) == 0
        st = call("Replica.Activate")
        assert st["lifecycle"] == "active" and st["registered"]
        assert len(registry.nodes("llm")) == 1
        st = call("Replica.Drain", 5.0)
        # An idle replica drains instantly: the reply may already
        # carry the terminal state.
        assert st["lifecycle"] in ("draining", "drained")
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if not handle.alive():
                break
            time.sleep(0.02)
        assert not handle.alive()  # drained → deregistered → exited
        assert len(registry.nodes("llm")) == 0
    finally:
        if conn is not None:
            conn.close()
        launcher.close()


def test_paged_engine_drain_seam():
    """The real engine's drain seam: begin_drain sheds NEW work typed
    while an in-flight request decodes to completion; drained() flips
    only after the last row retired; Info carries the lifecycle."""
    import jax.numpy as jnp

    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.serve_engine import PagedGeneratorActor

    cfg = tfm.preset("tiny", dtype=jnp.float32)
    eng = PagedGeneratorActor(cfg, n_slots=2, max_len=128,
                              block_tokens=16)
    try:
        assert eng.Info()["lifecycle"] == "active"
        prompt = jnp.ones((1, 8), jnp.int32)
        out_box = {}

        def inflight():
            out_box["out"] = np.asarray(eng.Generate(prompt, 24))

        t = threading.Thread(target=inflight)
        t.start()
        deadline = time.monotonic() + 20
        while not eng._active.any() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng._active.any(), "request never reached a slot"
        eng.begin_drain()
        assert not eng.drained()  # one row still live
        with pytest.raises(ShedError):
            eng.Generate(prompt, 4)
        t.join(timeout=30)
        assert out_box["out"].shape == (1, 24)
        deadline = time.monotonic() + 10
        while not eng.drained() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.drained()
        info = eng.Info()
        assert info["lifecycle"] == "draining"
        # The gauge twin obs serve renders.
        from ptype_tpu.serve import LIFECYCLE_CODES
        assert (eng._reg.gauge("serve.lifecycle").value
                == LIFECYCLE_CODES["draining"])
    finally:
        eng.close()


# -------------------------------------------------- obs renders (CLI)


def test_local_launcher_hands_out_the_least_loaded_device(coord):
    """``devices=``: each spawn goes to the device holding the fewest
    live replicas, so an on-chip fleet lands one replica per chip; a
    killed replica's device is the next one handed out."""
    placed = []

    def factory(device=None):
        placed.append(device)
        actor = FakeGeneratorActor()
        actor.device = device  # what a placed actor reports
        return actor

    launcher = LocalLauncher(CoordRegistry(coord, lease_ttl=2.0), factory,
                             devices=["chip0", "chip1", "chip2"])
    try:
        handles = [launcher.spawn(f"r{i}") for i in range(4)]
        assert placed == ["chip0", "chip1", "chip2", "chip0"]
        handles[1].kill()
        launcher.spawn("r4")
        assert placed[-1] == "chip1"
    finally:
        launcher.close()


def test_process_launcher_refuses_paged_workers_on_a_tpu_host(
        monkeypatch):
    """A chip belongs to one process: spawned paged workers would fail
    or hang at backend init when the parent already holds the TPU."""
    import jax

    from ptype_tpu.errors import ClusterError
    from ptype_tpu.reconciler import ProcessLauncher

    ProcessLauncher("127.0.0.1:1", kind="paged")  # CPU host: fine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ClusterError, match="one process"):
        ProcessLauncher("127.0.0.1:1", kind="paged")
    ProcessLauncher("127.0.0.1:1", kind="fake")  # control plane: fine


def test_lifecycle_names_pinned_in_sync():
    from ptype_tpu.health import top as top_mod
    from ptype_tpu.serve import LIFECYCLES

    assert tuple(top_mod._LIFECYCLE_NAMES) == tuple(LIFECYCLES)


def _snapshot(nodes):
    return {"ts": "t", "nodes": nodes, "errors": {}}


def test_render_serve_lifecycle_column():
    from ptype_tpu.health import render_serve

    node = {"metrics": {"gauges": {"serve.step_ms": 5.0,
                                   "serve.lifecycle": 3.0,
                                   "serve.queue_depth": 1.0},
                        "histograms": {}, "counters": {}}}
    out = render_serve(_snapshot({"w1/1:1": node}))
    assert "draining" in out and "state" in out


def test_render_scale_shows_reconciler_and_fleet():
    from ptype_tpu.health import render_scale

    rec_node = {"metrics": {"gauges": {"scale.desired": 3.0,
                                       "scale.actual": 2.0,
                                       "scale.warm": 1.0,
                                       "scale.draining": 0.0,
                                       "scale.pending_spawns": 1.0},
                            "counters": {"scale.decisions": 4,
                                         "scale.spawns": 3,
                                         "scale.drains": 1,
                                         "scale.drain_escalations": 0,
                                         "scale.deaths": 1,
                                         "scale.spawn_failures": 0},
                            "histograms": {}}}
    rep_node = {"metrics": {"gauges": {"serve.lifecycle": 2.0,
                                       "serve.queue_depth": 0.0},
                            "histograms": {}, "counters": {}}}
    out = render_scale(_snapshot({"ctl/1:1": rec_node,
                                  "w1/2:2": rep_node}))
    assert "1 reconcilers" in out
    assert "active" in out
    # desired vs actual visible on the reconciler row
    assert " 3 " in out and " 2 " in out


def test_render_scale_empty_fleet_message():
    from ptype_tpu.health import render_scale

    out = render_scale(_snapshot({}))
    assert "no reconciler" in out
