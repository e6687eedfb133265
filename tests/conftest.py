"""Test fixtures.

Environment must be pinned before the first ``import jax`` anywhere in the
test process: tests run on a virtual 8-device CPU mesh (SURVEY.md §4 — the
reference's embedded-etcd tier becomes a single-process multi-device
fixture), so every sharding/collective test runs without a TPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys  # noqa: E402

import pytest  # noqa: E402

# The benchmark's tiny copy (tests/benchmark/perfbench_tiny.py, a shipped
# benchmark file) takes in the cells shipped after it was written from
# here: a conftest.py beside it would shadow this module's name, which
# test_examples.py imports.
_BENCH_TESTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "benchmark")
if _BENCH_TESTS not in sys.path:
    sys.path.insert(0, _BENCH_TESTS)
import axk1_tiny  # noqa: E402
import exaone_tiny  # noqa: E402
import glm_tiny  # noqa: E402

glm_tiny.install()
exaone_tiny.install()
axk1_tiny.install()

#: Modules auto-marked ``slow`` (excluded from `make test`, run by
#: `make test-all`). Per-module, not per-test: the cost in these files
#: is jit compilation / subprocess drills, which every test in the file
#: pays. The fast tier — everything else — is the control-plane +
#: unit surface, mirroring the reference's 35 s whole-suite contract
#: (its suite WAS control-plane only; the ML surface is this repo's
#: addition and pays real XLA compiles).
SLOW_FILES = {
    "test_actor_pipeline.py", "test_chaos_soak.py", "test_checkpoint.py",
    "test_data.py",
    "test_elastic.py", "test_elastic_mp.py", "test_examples.py",
    "test_failover.py",
    "test_flash_attention.py", "test_fsdp_8b.py",
    "test_loadgen_drills.py",
    "test_models.py", "test_moe.py", "test_mp_train.py",
    "test_multihost_walkthrough.py",
    "test_overlap.py", "test_param_server.py", "test_pipeline.py",
    "test_quantized_train.py", "test_reconciler_mp.py",
    "test_race.py", "test_resnet.py", "test_ring_attention.py",
    "test_scale.py", "test_store_bench.py",
    "test_train.py", "test_zero_train.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in SLOW_FILES:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _reset_local_coords():
    """Isolate the process-local coordination states between tests."""
    yield
    from ptype_tpu.coord.local import reset_local_coords

    reset_local_coords()


@pytest.fixture(autouse=True)
def _disarm_chaos():
    """A test that armed a fault plan must never leak it into the next
    test's seams."""
    yield
    from ptype_tpu import chaos

    chaos.disarm()


@pytest.fixture(autouse=True)
def _disarm_health():
    """A test that installed a goodput ledger or armed the default
    health sampler must not leak either into later tests' metrics."""
    yield
    import sys as _sys

    trace_mod = _sys.modules.get("ptype_tpu.trace")
    if trace_mod is not None:
        trace_mod.set_region_observer(None)
    series_mod = _sys.modules.get("ptype_tpu.health.series")
    if series_mod is not None:
        series_mod.stop()
    goodput_mod = _sys.modules.get("ptype_tpu.health.goodput")
    if goodput_mod is not None:
        goodput_mod.uninstall()


@pytest.fixture
def coord():
    """A fresh in-process coordination backend (fast lease sweep)."""
    from ptype_tpu.coord.core import CoordState
    from ptype_tpu.coord.local import LocalCoord

    state = CoordState(sweep_interval=0.05)
    backend = LocalCoord(state)
    yield backend
    state.close()


@pytest.fixture
def coord_server():
    """A TCP coordination service on an ephemeral port."""
    from ptype_tpu.coord.core import CoordState
    from ptype_tpu.coord.service import CoordServer

    server = CoordServer("127.0.0.1:0", CoordState(sweep_interval=0.05))
    yield server
    server.close()


def wait_output(proc, needle: str, timeout: float):
    """Wait until ``proc`` prints a line containing ``needle``.
    Select-based so a live-but-silent child fails at the deadline
    instead of blocking readline forever; returns the lines seen."""
    import os
    import select
    import time

    deadline = time.time() + timeout
    lines = []
    buf = ""
    fd = proc.stdout.fileno()
    while time.time() < deadline:
        ready, _, _ = select.select([fd], [], [], 0.25)
        if not ready:
            if proc.poll() is not None:
                break
            continue
        chunk = os.read(fd, 4096).decode(errors="replace")
        if not chunk:
            if proc.poll() is not None:
                break
            continue
        buf += chunk
        while "\n" in buf:
            line, buf = buf.split("\n", 1)
            lines.append(line + "\n")
            if needle in line:
                return lines
    raise AssertionError(
        f"did not see {needle!r} within {timeout}s; got: {''.join(lines)}"
    )


@pytest.fixture
def jitwatch_watchdog():
    """ISSUE 15: arm the runtime recompile watchdog for one test —
    every backend compile the stack under test pays is booked per
    (function, signature), hot regions disallow unsanctioned implicit
    transfers (they RAISE at the call), and a recompile storm (the
    same signature compiled ≥3 times — a hot program re-tracing per
    call) fails the test at teardown. The dispatch tiers
    (test_chaos_soak / test_serve_engine) alias this as an autouse
    fixture; steady-state drills additionally ``mark_steady()`` and
    assert ``recompiles_since_steady() == {}``."""
    from ptype_tpu import jitwatch

    was = jitwatch.active()
    jw = jitwatch.enable()
    yield jw
    storms = jw.storms()
    if was is not None:
        # PTYPE_JITWATCH=1 session: re-arm rather than silently
        # disarming the rest of the run.
        jitwatch.enable(was.storm_threshold, was.transfer_level)
    else:
        jitwatch.disable()
    assert not storms, f"recompile storms detected: {storms}"


@pytest.fixture
def lock_order_watchdog():
    """ISSUE 14: arm the runtime lock-order watchdog for one test —
    every lock the stack under test creates is tracked, and a cycle
    in the acquisition graph (a latent deadlock, hung or not) fails
    the test at teardown. Hold-budget findings are informational;
    cycles are the invariant. The concurrency tiers
    (test_chaos_soak / test_gateway / test_reconciler) alias this as
    an autouse fixture so every drill runs under it for free."""
    from ptype_tpu import lockcheck

    was = lockcheck.active()
    wd = lockcheck.enable()
    yield wd
    cycles = wd.cycles()
    if was is not None:
        # PTYPE_LOCKCHECK=1 session: hand the env-armed watchdog
        # back instead of silently disarming the rest of the run.
        lockcheck._watchdog = was
    else:
        lockcheck.disable()
    assert not cycles, f"lock-order cycles detected: {cycles}"
