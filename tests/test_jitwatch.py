"""Jitwatch unit tier (ISSUE 15): the seeded forced-retrace fixture
the watchdog must catch, shape-specialization vs recompile
accounting, the eager-wrapper exclusion, hot-region transfer
discipline + the sanctioned seam, steady-state marking, the
flight-recorder dump, env arming, and the recompile-storm health
rule on synthetic series."""

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu import jitwatch, trace


@pytest.fixture
def watch():
    jw = jitwatch.enable(storm_threshold=3)
    yield jw
    jitwatch.disable()


def test_disarmed_is_inert():
    jitwatch.disable()
    assert jitwatch.active() is None
    # Guards are free no-ops disarmed.
    with jitwatch.hot_region("x"):
        jax.jit(lambda v: v + 1)(np.ones(3))  # implicit transfer: fine
    with jitwatch.sanctioned_transfer("x"):
        pass


def test_forced_retrace_is_detected(watch):
    """THE fixture: a fresh jit object per call re-keys the trace
    cache — same function name, same signature, compiled again and
    again. The watchdog books every one as a recompile and raises a
    storm at the threshold."""
    x = jnp.ones(9)
    for _ in range(4):
        jax.jit(lambda v: v * 3)(x).block_until_ready()
    rec = watch.recompiles()
    assert rec.get("<lambda>", 0) >= 2, rec
    storms = watch.storms()
    assert storms and storms[0]["fn"] == "<lambda>", storms
    assert storms[0]["compiles"] == watch.storm_threshold


def test_shape_specialization_is_not_a_recompile(watch):
    """Distinct signatures are legit specializations (the engine's
    per-chunk-width programs): compiles counted, recompiles zero."""
    f = jax.jit(lambda v: v * 2)

    f(jnp.ones(3)).block_until_ready()
    f(jnp.ones(4)).block_until_ready()
    f(jnp.ones(5)).block_until_ready()
    f(jnp.ones(5)).block_until_ready()  # cache hit: no compile
    assert watch.compiles().get("<lambda>", 0) == 3
    assert watch.recompiles() == {} and watch.storms() == []


def test_eager_wrapper_static_param_churn_is_excluded(watch):
    """jax's eager op dispatch (jit(broadcast_in_dim) ...) compiles
    the same INPUT signature under different static params — the log
    line can't tell those apart, so wrapper names stay out of the
    recompile/storm books (the false-positive-free charter)."""
    for n in (2, 3, 4, 5):
        jnp.broadcast_to(jnp.float32(1.0), (n,)).block_until_ready()
    assert "broadcast_to" in watch.ignored_fns
    assert watch.recompiles() == {} and watch.storms() == [], (
        watch.recompiles(), watch.storms())


def test_hot_region_blocks_unsanctioned_implicit_transfer(watch):
    """Armed, a hot region disallows implicit transfers: a numpy
    array (or python scalar) smuggled into a jitted call raises AT
    the call; explicit uploads (jnp.asarray) and the sanctioned seam
    stay legal."""
    f = jax.jit(lambda v: v * 2)
    dev = jnp.ones(4)
    f(dev).block_until_ready()  # compile outside the guard
    with jitwatch.hot_region("test.hot"):
        f(dev)                        # device-resident: fine
        f(jnp.asarray(np.ones(4, np.float32)))  # explicit: fine
        with pytest.raises(Exception, match="[Tt]ransfer"):
            f(np.ones(4, np.float32))  # implicit: the leak, caught
        with jitwatch.sanctioned_transfer("test.meter"):
            f(np.ones(4, np.float32))  # exempted AND counted
    assert watch.sanctioned() == {"test.meter": 1}
    assert watch.report()["hot_regions"] == 1


def test_mark_steady_books_every_later_compile(watch):
    f = jax.jit(lambda v: v + 1)
    x3, x6 = jnp.ones(3), jnp.ones(6)  # arrays built pre-steady: the
    #                                    books must show OUR program
    f(x3).block_until_ready()
    watch.mark_steady()
    assert watch.recompiles_since_steady() == {}
    f(x3).block_until_ready()           # cache hit: still zero
    assert watch.recompiles_since_steady() == {}
    f(x6).block_until_ready()  # NEW shape post-steady: booked
    assert watch.recompiles_since_steady() == {"<lambda>": 1}


def test_storm_dumps_through_flight_recorder(watch, tmp_path):
    """A storm lands in the span ring and the rate-limited
    flight-*.jsonl dump — the post-mortem artifact the runbook row
    points at."""
    rec = trace.enable("jitwatch-test", dump_dir=str(tmp_path))
    trace._dump_last = 0.0  # an earlier test's dump must not eat the
    #                         one-per-interval rate limit
    try:
        with trace.span("drive"):
            x = jnp.ones(11)
            for _ in range(3):
                jax.jit(lambda v: v - 1)(x).block_until_ready()
        assert watch.storms()
        dumps = list(tmp_path.glob("flight-*.jsonl"))
        assert dumps, "no flight-recorder dump for the storm"
    finally:
        trace.disable()


def test_enable_from_env(monkeypatch):
    monkeypatch.setenv(jitwatch.ENV_VAR, "1")
    jitwatch.disable()
    jitwatch._maybe_enable_from_env()
    try:
        assert jitwatch.active() is not None
    finally:
        jitwatch.disable()


def test_disable_restores_compile_log_config():
    prior = bool(jax.config.jax_log_compiles)
    jitwatch.enable()
    assert bool(jax.config.jax_log_compiles) is True
    jitwatch.disable()
    assert bool(jax.config.jax_log_compiles) is prior
    # No leftover filters on the hooked loggers.
    for name in jitwatch._NOISY_LOGGERS:
        assert not any(isinstance(f, jitwatch._CompileFilter)
                       for f in logging.getLogger(name).filters)


def test_armed_logs_are_swallowed_not_printed(watch, capsys):
    """We armed jax_log_compiles for the hook, not the console: the
    compile WARNINGs must not reach the root handlers."""
    jax.jit(lambda v: v * 7)(jnp.ones(13)).block_until_ready()
    err = capsys.readouterr().err
    assert "Compiling" not in err and "Finished XLA" not in err


def test_recompile_storm_rule_names_the_function():
    """The health rule on synthetic series: counter delta over the
    window trips the page, and the per-function books name the worst
    offender; a flat series stays silent."""
    from ptype_tpu.health.rules import ClusterView, RecompileStormRule

    now = 1000.0
    stormy = {
        "nodes": {
            "workers/w0": {"series": {
                "jit.recompiles": [(now - 90, 1.0), (now - 30, 3.0),
                                   (now - 5, 6.0)],
                "jit.fn.engine_step": [(now - 5, 5.0)],
                "jit.fn.apply": [(now - 5, 1.0)],
            }},
            "workers/w1": {"series": {
                "jit.recompiles": [(now - 90, 2.0), (now - 5, 2.0)],
            }},
        },
        "ts": now,
    }
    rule = RecompileStormRule(threshold=3, window_s=120.0)
    alerts = rule.evaluate(ClusterView(stormy, now))
    assert len(alerts) == 1 and alerts[0].node == "workers/w0"
    assert alerts[0].rule == "recompile-storm"
    assert "engine_step" in alerts[0].message
    assert alerts[0].labels.get("fn") == "engine_step"


def test_recompile_storm_rule_in_default_set():
    from ptype_tpu.health.rules import (RecompileStormRule,
                                        default_rules)

    assert any(isinstance(r, RecompileStormRule)
               for r in default_rules())


def test_obs_jit_render_names_functions_and_disarmed_fleet():
    from ptype_tpu.health.top import render_jit

    snap = {
        "ts": "2026-08-04T00:00:00",
        "nodes": {
            "workers/w0": {
                "metrics": {
                    "counters": {"jit.compiles": 42.0,
                                 "jit.recompiles": 7.0,
                                 "jit.sanctioned_transfers": 5.0},
                    "gauges": {"jit.fn.engine_step": 6.0,
                               "jit.fn.apply": 1.0},
                },
                "series": {},
            },
            "workers/w1": {"metrics": {"counters": {}}, "series": {}},
        },
        "errors": {},
    }
    out = render_jit(snap)
    assert "engine_step (6x)" in out and "42" in out and "7" in out
    assert "1 armed" in out
    empty = render_jit({"ts": "t", "nodes": {}, "errors": {}})
    assert "PTYPE_JITWATCH=1" in empty


def measure_jitwatch_overhead(iters: int = 1500,
                              repeats: int = 5) -> dict:
    """The armed watchdog's price against the step it wraps (ISSUE 15:
    armed < 5% vs disarmed); the test below is its one reader.

    The armed watchdog costs per STEP, not per compile: the compile
    hook only fires on a cache miss (zero in steady state), so the
    recurring price is ONE hot-region transfer-guard entry around
    each dispatch. Price the machinery directly (a bare-dispatch A/B microloop —
    the region costs single-digit microseconds), then charge it
    against the step it actually wraps — an engine-shaped step with
    its one host sync per iteration, measured in the same process.
    A wall A/B of the bare microloop would report the guard at 100%
    duty cycle, a workload no armed engine runs (its step IS the
    model forward). Best-of-``repeats`` per side;
    ``jitwatch_region_us`` carries the raw per-region price, and the
    probe asserts its own steady-state recompiles are zero."""
    # The region-cost microloop: bare async dispatch vs dispatch
    # under the guard — the difference IS the per-step armed price.
    f = jax.jit(lambda v: v * 2.0 + 1.0)
    x = jnp.ones((256,), jnp.float32)
    f(x).block_until_ready()  # compile outside the measurement

    # The engine-shaped step the guard wraps in production: a real
    # forward-sized program with the one-per-step host sync the
    # engine pays (np.array(nxt) / the loss readback).
    w = jnp.ones((256, 256), jnp.float32) * 0.01
    step = jax.jit(lambda v, m: jnp.tanh(v @ m) @ m)
    sx = jnp.ones((64, 256), jnp.float32)
    np.asarray(step(sx, w))  # compile + settle

    def drive(armed: bool) -> float:
        t0 = time.perf_counter()
        if armed:
            for _ in range(iters):
                with jitwatch.hot_region("bench.step"):
                    f(x)
        else:
            for _ in range(iters):
                f(x)
        f(x).block_until_ready()
        return time.perf_counter() - t0

    def drive_step(n: int = 60) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            np.asarray(step(sx, w))
        return (time.perf_counter() - t0) / n

    was = jitwatch.active()
    jitwatch.disable()
    try:
        drive(False)  # warm the loop path
        t_off = min(drive(False) for _ in range(repeats))
        step_s = min(drive_step() for _ in range(3))
        jw = jitwatch.enable()
        jw.mark_steady()
        drive(True)
        t_on = min(drive(True) for _ in range(repeats))
        steady = jw.recompiles_since_steady()
    finally:
        jitwatch.disable()
        if was is not None:
            # Re-ARM (fresh books) rather than reinstalling the old
            # watch object: disable() tore down the compile-log
            # filters and jax_log_compiles, so a reinstalled watch
            # would report armed while counting nothing.
            jitwatch.enable(was.storm_threshold, was.transfer_level)
    region_s = max(0.0, (t_on - t_off) / iters)
    return {
        "jitwatch_overhead_pct": round(
            100.0 * region_s / max(step_s, 1e-12), 3),
        "jitwatch_region_us": round(region_s * 1e6, 3),
        "jitwatch_dispatch_us": round(t_off / iters * 1e6, 2),
        "jitwatch_step_ms": round(step_s * 1e3, 3),
        "jitwatch_steady_recompiles": sum(steady.values()),
    }


def test_overhead_probe_rearms_an_armed_watchdog():
    """Review regression: measure_jitwatch_overhead in an armed
    process must leave a LIVE watchdog behind (filters + compile-log
    config re-armed), not a zombie that reports armed while counting
    nothing."""
    jitwatch.enable()
    try:
        measure_jitwatch_overhead(iters=50, repeats=1)
        jw = jitwatch.active()
        assert jw is not None and bool(jax.config.jax_log_compiles)
        x = jnp.ones(17)
        for _ in range(4):
            jax.jit(lambda v: v * 2)(x).block_until_ready()
        assert jw.recompiles().get("<lambda>", 0) >= 2, \
            jw.recompiles()
    finally:
        jitwatch.disable()
