"""What every cell shares: the look for a chip, the compile cache, the
cluster, counting compilations, the traced window, the result line."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import socket
import sys

from benchmark.xplane import WINDOW_SPAN

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def require_tpu(chips: int) -> list:
    """The cell's devices, or exit non-zero with no result line."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"needs {chips} TPU chip(s); JAX shows {len(devs)} "
            f"{devs[0].platform} device(s). Nothing was measured.")
        raise SystemExit(3)
    return devs[:chips]


def configure_compile_cache() -> str:
    """The program's own rule: $JAX_COMPILATION_CACHE_DIR if set, else
    <checkout>/.jax_cache — a fixed path inside the checkout."""
    import jax

    from ptype_tpu import compile_cache

    # Every program, however small, is found in the cache by the next
    # run: JAX's default keeps out what compiled in under a second,
    # which is most of what a server's set-up compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return compile_cache.configure()


def join_cluster(chips: int):
    """A one-process cluster, joined the way the examples join; its mesh
    is the cell's chips on the "data" axis (on a machine that holds just
    those, what the examples' axis-less platform file gives)."""
    from ptype_tpu import join
    from ptype_tpu.config import config_from_file

    cfg = config_from_file(os.path.join(HERE, "cluster", "member.yaml"))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cfg.platform.coordinator_address = f"127.0.0.1:{port}"
    cfg.platform.mesh_axes = {"data": int(chips)}
    return join(cfg)


class CompileCounter:
    """Backend compilations, counted by JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1


@contextlib.contextmanager
def traced_window(trace_dir: str | None):
    """The measured window. With a directory, the profiler runs around
    it, and the window itself is a host span the reduction finds."""
    import jax

    if trace_dir is None:
        yield
        return
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def trace_dir_for(workload: str) -> str:
    base = os.environ.get("TMPDIR") or os.path.join(ROOT, ".bench_tmp")
    return os.path.join(base, f"bench-trace-{workload}")


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest chip: what the runtime's allocator held
    for buffers at its highest (``peak_bytes_in_use``: arguments,
    results, state) plus what it reserved for compiled programs'
    temporaries (``peak_bytes_reserved``), which it counts apart: a
    train step's 10 GB of saved activations show only there."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0))
                     + int(st.get("peak_bytes_reserved", 0)))
    log(f"memory_stats of device 0: {devices[0].memory_stats()}")
    return max(peaks)


def device_block(devices, extra: dict | None = None) -> dict:
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices)}
    out.update(extra or {})
    return out


def quantile(xs, q: float) -> float:
    """Linear-interpolated quantile of a non-empty list."""
    xs = sorted(xs)
    if not xs:
        raise ValueError("quantile of nothing")
    k = (len(xs) - 1) * q
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict, dict]:
    """The cell's limits file decides what is compared: each of its
    names against the number the run computed under it (a name the run
    did not compute fails). Correct when every compared value is a
    number at or under its limit. → (correct, compared, the rest)."""
    if not limits:
        raise SystemExit("benchmark: the cell has no limits file; "
                         "nothing would be compared")
    out, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, float("nan"))
        good = value == value and value <= limit  # NaN fails
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    rest = {k: v for k, v in numbers.items() if k not in limits}
    return ok, out, rest


def emit(result: dict) -> None:
    """The compared numbers as the last lines of stderr, then the one
    result line as the last line of stdout."""
    for name, c in result.get("checks", {}).items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["checks"] = result.get("checks", {})
    print(json.dumps(line), flush=True)
