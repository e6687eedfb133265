"""Profiling plane (ISSUE 8): capture sessions + the host-side
summary parser, the built-in ``ptype.Profile`` actor endpoint over
real sockets (including the dead-node and double-start error paths),
cluster-wide simultaneous capture, alert-triggered capture with its
rate limit, compiled-cost accounting (``mfu_compiled`` next to the
analytic MFU, gap reported), and the end-to-end seeded chaos drill:
a delayed ``store.push`` on one worker fires the straggler alert AND
an XPlane profile artifact appears for the named node — rate-limited
on repeat firings."""

import os

import pytest

from ptype_tpu import chaos
from ptype_tpu import metrics as metrics_mod
from ptype_tpu.health import (AlertCapture, AlertEngine, ClusterView,
                              GoodputLedger, MfuGapRule, default_rules)
from ptype_tpu.health import profiling

# ------------------------------------------------------ capture session


def test_start_stop_capture_manifest_and_summary(tmp_path):
    import jax
    import jax.numpy as jnp

    profiling.start(label="unit", base=str(tmp_path))
    with metrics_mod.annotate("train.step"):
        jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()
    out = profiling.stop()
    assert out["files"], out
    names = [f["path"] for f in out["files"]]
    assert any(p.endswith(".xplane.pb") for p in names)
    assert any(p.endswith(".trace.json.gz") for p in names)
    # The host-side parser (stdlib gzip+json, CPU run): the annotate
    # region shows up as a top op.
    s = profiling.summarize(out["dir"])
    assert s["events"] > 0
    assert any(op["name"] == "train.step" for op in s["top_ops"])
    # HBM/host snapshot rides along (RSS fallback always present).
    assert out["memory"]["host"]["rss_bytes"] > 0
    assert profiling.render_hbm_table(out["memory"])


def test_double_start_is_typed_error_and_stop_without_start(tmp_path):
    profiling.start(base=str(tmp_path))
    try:
        with pytest.raises(profiling.ProfileError):
            profiling.start(base=str(tmp_path))
    finally:
        profiling.stop()
    with pytest.raises(profiling.ProfileError):
        profiling.stop()


def test_capture_ships_data_and_fetch_blocks_traversal(tmp_path):
    out = profiling.capture(duration_s=0.01, base=str(tmp_path),
                            include_data=True)
    assert out["data"] and all(isinstance(b, bytes)
                               for b in out["data"].values())
    rel = out["files"][0]["path"]
    assert profiling.fetch(out["dir"], rel) == out["data"][rel]
    with pytest.raises(profiling.ProfileError):
        profiling.fetch(out["dir"], "../../etc/passwd")
    # write_artifacts round-trips the shipped bytes.
    dest = tmp_path / "shipped"
    written = profiling.write_artifacts(str(dest), out)
    assert len(written) == len(out["data"])
    s = profiling.summarize(str(dest))
    assert s["files"]


# ------------------------------------------ the ptype.Profile endpoint


def _dial(server):
    from ptype_tpu import rpc as rpc_mod
    from ptype_tpu.registry import Node

    return rpc_mod._dial(Node("127.0.0.1", server.port),
                         dial_timeout=5.0)


def _call(conn, *args, timeout=20.0):
    return conn.call_async("ptype.Profile", args).result(timeout=timeout)


def test_profile_endpoint_over_real_sockets(tmp_path, monkeypatch):
    """Remote start/stop through the built-in endpoint every
    ActorServer registers — status, capture-with-shipping, memory,
    fetch, and the double-start error marshalled as RemoteError."""
    monkeypatch.setenv(profiling.PROFILE_DIR_ENV, str(tmp_path))
    from ptype_tpu.actor import ActorServer
    from ptype_tpu.errors import RemoteError

    server = ActorServer("127.0.0.1", 0).serve()
    assert "ptype.Profile" in server.methods
    conn = _dial(server)
    try:
        st = _call(conn, "status")
        assert st["active"] is False and st["devices"] >= 1
        started = _call(conn, "start", {"label": "remote"})
        assert str(tmp_path) in started["dir"]
        assert _call(conn, "status")["active"] is True
        with pytest.raises(RemoteError):
            _call(conn, "start", {"label": "again"})
        out = _call(conn, "stop", {"include_data": True})
        assert out["files"] and out["data"]
        rel = out["files"][0]["path"]
        blob = _call(conn, "fetch", {"dir": out["dir"], "path": rel})
        assert blob == out["data"][rel]
        mem = _call(conn, "memory")
        assert mem["host"]["rss_bytes"] > 0
    finally:
        conn.close()
        server.close()


def test_cluster_profile_partial_on_dead_node(tmp_path, monkeypatch,
                                              coord):
    """Simultaneous capture across the registry: the live node ships
    artifacts into its per-node directory, the registered-but-dead
    node lands in errors — a partial capture of a degraded fleet, not
    a crash."""
    monkeypatch.setenv(profiling.PROFILE_DIR_ENV,
                       str(tmp_path / "node"))
    from ptype_tpu import telemetry
    from ptype_tpu.actor import ActorServer
    from ptype_tpu.registry import CoordRegistry

    registry = CoordRegistry(coord, lease_ttl=5.0)
    live = ActorServer("127.0.0.1", 0).serve()
    dead = ActorServer("127.0.0.1", 0).serve()
    dead_port = dead.port
    regs = [registry.register("work", "w0", "127.0.0.1", live.port),
            registry.register("work", "w1", "127.0.0.1", dead_port)]
    dead.close()
    try:
        res = telemetry.cluster_profile(
            registry, duration_s=0.02, out_dir=str(tmp_path / "out"))
        live_key = f"work/127.0.0.1:{live.port}"
        dead_key = f"work/127.0.0.1:{dead_port}"
        assert live_key in res["nodes"], res
        assert dead_key in res["errors"], res
        node = res["nodes"][live_key]
        assert node["files"]
        assert os.path.isdir(node["dir"])
        assert profiling.summarize(node["dir"])["files"]
        assert node["memory"]["host"]["rss_bytes"] > 0
    finally:
        for r in regs:
            r.close()
        live.close()


# ------------------------------------------------ alert-driven capture


def _alert(rule="straggler", node="local"):
    from ptype_tpu.health.rules import Alert

    return Alert(rule=rule, severity="warn", node=node,
                 message="test", ts=1.0)


def test_alert_capture_rate_limit_dedup(tmp_path, monkeypatch):
    monkeypatch.setenv(profiling.PROFILE_DIR_ENV,
                       str(tmp_path / "base"))
    cap = AlertCapture(out_dir=str(tmp_path / "alerts"),
                       duration_s=0.01, min_interval_s=60.0,
                       background=False)
    cap(_alert())                      # local fallback capture
    cap(_alert())                      # same (rule, node): deduped
    assert len(cap.captures) == 1, (cap.captures, cap.errors)
    # A different rule on the same node is its own budget.
    cap(_alert(rule="train-stall"))
    assert len(cap.captures) == 2
    # Non-profile rules never capture.
    cap(_alert(rule="loss"))
    assert len(cap.captures) == 2
    d = cap.captures[0]["dir"]
    assert os.path.isfile(os.path.join(d, "capture.json"))
    assert profiling.summarize(d)["files"]


def test_alert_capture_survives_dead_node(tmp_path):
    cap = AlertCapture(out_dir=str(tmp_path), duration_s=0.01,
                       timeout_s=2.0, background=False)
    cap(_alert(node="work/127.0.0.1:1"))  # nothing listens there
    assert cap.captures == []
    assert cap.errors and cap.errors[0]["node"] == "work/127.0.0.1:1"


# --------------------------------------------- compiled-cost accounting


def test_compiled_cost_and_mfu_compiled_in_ledger():
    """StoreDPTrainer.compiled_cost() yields XLA-counted FLOPs; fed to
    a ledger via set_compiled_flops, every step records mfu_compiled
    next to the analytic mfu with the gap REPORTED, and publishes the
    gauges the mfu-divergence rule watches."""
    import jax

    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.parallel.mesh import build_mesh
    from ptype_tpu.parallel.tensorstore import TensorStore
    from ptype_tpu.train.data import synthetic_batches
    from ptype_tpu.train.store_dp import StoreDPTrainer

    cfg = tfm.preset("tiny")
    mesh = build_mesh({"data": jax.device_count()})
    trainer = StoreDPTrainer(cfg, TensorStore(mesh))
    with pytest.raises(ValueError):
        trainer.compiled_cost()        # needs one step's shapes
    stream = synthetic_batches(cfg.vocab_size, 8, 32)
    trainer.step(next(stream))
    cost = trainer.compiled_cost()
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
    assert cost["tokens_per_step"] == 8 * 32
    assert cost["programs"]["grads"]["flops"] > \
        cost["programs"]["optimizer"]["flops"]
    # The unrolled lowering counts every layer: compiled flops must be
    # at least the matmul floor the analytic formula counts per layer.
    analytic = tfm.flops_per_token(cfg, 32)
    assert 0.5 < cost["flops_per_token"] / analytic < 2.0

    reg = metrics_mod.MetricsRegistry()
    led = GoodputLedger(registry=reg, tokens_per_step=8 * 32,
                        flops_per_token=analytic)
    led.set_compiled_flops(cost["flops"])
    end = 10.0
    for _ in range(2):
        end += 0.1
        led.observe("train.step", 0.1, end=end)
    rec = led.records()[-1]
    assert rec["mfu"] > 0 and rec["mfu_compiled"] > 0
    assert "mfu_gap_pct" in rec
    gauges = reg.snapshot()["gauges"]
    assert gauges["goodput.mfu_compiled"] == rec["mfu_compiled"]
    assert gauges["goodput.mfu_gap_pct"] == rec["mfu_gap_pct"]
    s = led.summary()
    assert "mfu_compiled" in s and "mfu_gap_pct" in s


def test_mfu_gap_rule_fires_on_divergence():
    rule = MfuGapRule(gap_frac=0.25)

    def snap(compiled):
        return {"ts": 1000.0, "errors": {}, "nodes": {"w": {"series": {
            "goodput.mfu": [[999.0, 0.40]],
            "goodput.mfu_compiled": [[999.0, compiled]]}}}}

    alerts = rule.evaluate(ClusterView(snap(0.55)))
    assert len(alerts) == 1 and alerts[0].rule == "mfu-divergence"
    assert rule.evaluate(ClusterView(snap(0.42))) == []
    # A node without the compiled series (no set_compiled_flops) is
    # silent — the rule needs both sides.
    lone = {"ts": 1.0, "errors": {}, "nodes": {"w": {"series": {
        "goodput.mfu": [[0.5, 0.4]]}}}}
    assert rule.evaluate(ClusterView(lone)) == []


@pytest.mark.slow
def test_zero_compiled_cost_counts_sharded_apply():
    import jax

    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.parallel.mesh import build_mesh
    from ptype_tpu.parallel.tensorstore import TensorStore
    from ptype_tpu.train.data import synthetic_batches
    from ptype_tpu.train.store_dp import StoreDPTrainer

    cfg = tfm.preset("tiny")
    mesh = build_mesh({"data": jax.device_count()})
    trainer = StoreDPTrainer(cfg, TensorStore(mesh), zero=True)
    stream = synthetic_batches(cfg.vocab_size, 8, 32)
    trainer.step(next(stream))
    cost = trainer.compiled_cost()
    opt = cost["programs"]["optimizer"]
    assert opt["flops"] > 0 and opt["n_buckets"] >= 1
    assert cost["flops"] > cost["programs"]["grads"]["flops"]


@pytest.mark.slow
def test_pipeline_step_compiled_cost():
    """The generic compiled_cost helper covers the pipeline step
    program too (ISSUE 8: store_dp, zero, pipeline)."""
    import jax
    import jax.numpy as jnp

    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.parallel.mesh import build_mesh
    from ptype_tpu.parallel.pipeline import make_pipeline_train_step
    from ptype_tpu.train.trainer import TrainState, default_optimizer

    mesh = build_mesh({"stage": 4})
    cfg = tfm.preset("tiny", n_layers=4)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = default_optimizer()
    state = TrainState(params, opt.init(params),
                       jnp.zeros((), jnp.int32))
    step = make_pipeline_train_step(cfg, mesh, n_microbatches=4,
                                    optimizer=opt)
    batch = {"tokens": jnp.ones((8, 16), jnp.int32),
             "targets": jnp.ones((8, 16), jnp.int32)}
    cost = profiling.compiled_cost(
        step, profiling.tree_avals(state), profiling.tree_avals(batch))
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0


@pytest.mark.slow
def test_measure_compiled_cost_gap_within_10pct_on_125m():
    """The ISSUE 8 acceptance check: on the 125M CPU-mesh config the
    compiled-cost MFU lands within 10% of the analytic MFU — and the
    gap is reported either way, never hidden."""
    out = profiling.measure_compiled_cost(preset="optimus-125m",
                                          batch=8, seq=128)
    assert out["compiled_flops_per_token"] > 0
    assert out["analytic_flops_per_token"] > 0
    assert "mfu_gap_pct" in out
    assert abs(out["mfu_gap_pct"]) <= 10.0, out


# ---------------------------------------------------- peak-TFLOPS table


def test_device_peak_tflops_is_keyed_by_exact_device_kind():
    class _Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    assert metrics_mod.device_peak_tflops(_Dev("TPU v5 lite")) == 197.0
    # A v5p reports "TPU v5": a substring table priced it as a v5e.
    for kind in ("TPU v5", "tpu v5 lite", "TPU v99 weirdchip"):
        with pytest.raises(ValueError, match="device_kind"):
            metrics_mod.device_peak_tflops(_Dev(kind))
    # The CPU backend keeps a nominal figure (host-mesh test runs).
    assert metrics_mod.device_peak_tflops() == \
        metrics_mod.CPU_NOMINAL_TFLOPS


# ------------------------------------------- end-to-end chaos drill


def test_straggler_alert_auto_captures_profile_on_named_node(
        tmp_path, coord):
    """Acceptance drill: seeded chaos delays one worker's store.push →
    the straggler alert fires naming that node AND an XPlane profile
    artifact appears for it (captured over the real socket to that
    node's ptype.Profile endpoint, dropped next to the flight-recorder
    dump) — and a repeat firing within the rate-limit window captures
    nothing new."""
    import jax
    from test_health import (DRILL_STEPS, N_WORKERS, SLOW_PUSH_S,
                             _SimWorker)

    from ptype_tpu import telemetry
    from ptype_tpu.chaos import FaultPlan, FaultSpec
    from ptype_tpu.parallel.mesh import build_mesh
    from ptype_tpu.registry import CoordRegistry

    registry = CoordRegistry(coord, lease_ttl=5.0)
    mesh = build_mesh({"data": 1}, devices=jax.devices()[:1])
    workers = [_SimWorker(f"w{i}", mesh, registry)
               for i in range(N_WORKERS)]
    cap = AlertCapture(out_dir=str(tmp_path), duration_s=0.05,
                       min_interval_s=300.0, background=False)
    engine = AlertEngine(default_rules(), cooldown_s=0.0,
                         registry=metrics_mod.MetricsRegistry(),
                         capture=cap)
    try:
        for w in workers:
            w.step(0)               # compile before the clock runs
        for w in workers:
            w.sampler.start()
        chaos.arm(FaultPlan([FaultSpec(
            "store.push", "delay", match="w2",
            times=DRILL_STEPS + 1, delay_s=SLOW_PUSH_S)]))
        for i in range(1, DRILL_STEPS + 1):
            for w in workers:
                w.step(i)
        chaos.disarm()
        for w in workers:
            w.sampler.sample_once()
        snap = telemetry.cluster_snapshot(registry,
                                          include_local=False)
        alerts = engine.evaluate(snap)
        slow_key = workers[2].key
        assert [a.rule for a in alerts] == ["straggler"], alerts
        assert alerts[0].node == slow_key
        # The capture hit the NAMED node's endpoint and landed an
        # XPlane artifact next to the flight dumps.
        assert len(cap.captures) == 1, (cap.captures, cap.errors)
        rec = cap.captures[0]
        assert rec["node"] == slow_key and rec["files"] >= 1
        files = profiling.summarize(rec["dir"])["files"]
        assert any(f["path"].endswith(".xplane.pb") for f in files)
        # Re-firing past the engine cooldown (0 s) but inside the
        # capture rate limit: the alert repeats, the capture does not.
        # (+1 s, not +60: a minute of fake idleness would legitimately
        # fire train-stall on every node.)
        alerts2 = engine.evaluate(snap, now=snap["ts"] + 1.0)
        assert [a.rule for a in alerts2] == ["straggler"]
        assert len(cap.captures) == 1
    finally:
        chaos.disarm()
        for w in workers:
            w.close()


def test_clean_drill_captures_nothing(tmp_path, coord):
    """False-positive guard: the identical clean run raises no alert
    and writes no profile artifact."""
    from test_health import run_straggler_drill

    cap = AlertCapture(out_dir=str(tmp_path), duration_s=0.05,
                       background=False)
    alerts, _, snap, _ = run_straggler_drill(False, coord)
    engine = AlertEngine(default_rules(),
                         registry=metrics_mod.MetricsRegistry(),
                         capture=cap)
    assert engine.evaluate(snap) == []
    assert cap.captures == [] and cap.errors == []
    assert list(os.listdir(tmp_path)) == []
