"""Metrics / observability.

The reference had logging only — zap globals, no metrics surface
(SURVEY.md §5 "Metrics": `Client.ConnectionErrs` was the entire
observability API, cluster/rpc.go:122-124). The BASELINE.json metrics
(tokens/sec/chip, MFU, collective GB/s) need a real counter/timing
module; this is it.
"""

from __future__ import annotations

import collections
import json
import threading
import time
from dataclasses import dataclass, field

from ptype_tpu import lockcheck

import jax

from ptype_tpu import trace as trace_mod

#: Peak dense bf16 matmul TFLOP/s per chip, keyed by the EXACT
#: ``device_kind`` string JAX reports. A v5e chip reports
#: "TPU v5 lite" (chip run, PR 21); 197 is the published figure
#: (Google Cloud documentation, "TPU v5e"). Matching is exact on
#: purpose: a v5p reports "TPU v5", which a substring table would
#: price at the v5e peak. A kind that is not here is an error — add it
#: together with its source.
PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,
}

#: Nominal figure for the CPU backend so MFU stays defined (and
#: obviously tiny) in host-mesh test runs. Not a peak of anything.
CPU_NOMINAL_TFLOPS = 0.5


def device_peak_tflops(device=None) -> float:
    """Peak bf16 TFLOP/s of ``device`` (default: ``devices()[0]``)
    from :data:`PEAK_TFLOPS`. An accelerator whose ``device_kind`` is
    not in the table raises: MFU is never computed against another
    chip's peak."""
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return CPU_NOMINAL_TFLOPS
    kind = device.device_kind
    if kind not in PEAK_TFLOPS:
        raise ValueError(
            f"no peak TFLOP/s on record for device_kind {kind!r} "
            f"(have {sorted(PEAK_TFLOPS)}); add it to "
            "metrics.PEAK_TFLOPS with its source")
    return PEAK_TFLOPS[kind]


def mfu(tokens_per_sec: float, flops_per_token: float,
        n_chips: int, peak_tflops: float | None = None) -> float:
    """Model FLOPs utilization in [0, 1]: achieved / peak."""
    peak = (peak_tflops or device_peak_tflops()) * 1e12 * n_chips
    return tokens_per_sec * flops_per_token / peak


#: Samples a Counter keeps for its windowed rate() — filled by the
#: health sampler's cadence (one sample per tick), sized so a minute
#: of 1 Hz sampling fits.
COUNTER_RATE_WINDOW = 64


@dataclass
class Counter:
    name: str
    value: float = 0.0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)
    #: (t, cumulative value) samples behind the windowed rate() — the
    #: hot-path add() never touches this; the health Sampler (or an
    #: explicit sample() call) stamps it at its cadence.
    _samples: collections.deque = field(
        default_factory=lambda: collections.deque(
            maxlen=COUNTER_RATE_WINDOW),
        repr=False, compare=False)

    def add(self, delta: float = 1.0) -> None:
        with self._lock:
            self.value += delta

    def sample(self, now: float | None = None) -> None:
        """Stamp (t, value) into the rate window — called by the health
        sampler at its cadence (time.monotonic clock)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            self._samples.append((now, self.value))

    def rate(self, window_s: float | None = None,
             now: float | None = None) -> float:
        """Events/sec over the sampled window (the sampler cadence).

        Computed from the stamped samples only — deterministic under
        explicit sample(now=...) calls. With a single sample the live
        value at ``now`` closes the interval; with none, 0.0."""
        now = time.monotonic() if now is None else now
        with self._lock:
            pts = list(self._samples)
            cur = self.value
        if window_s is not None:
            pts = [p for p in pts if p[0] >= now - window_s]
        if not pts:
            return 0.0
        t0, v0 = pts[0]
        t1, v1 = pts[-1] if len(pts) > 1 else (now, cur)
        if t1 <= t0:
            return 0.0
        return max(0.0, (v1 - v0) / (t1 - t0))


#: Recent observations a Timing keeps for its percentile window —
#: enough to be distribution-aware on hot paths, small enough that the
#: per-observe cost stays one deque append.
TIMING_WINDOW = 256


@dataclass
class Timing:
    name: str
    total: float = 0.0
    count: int = 0
    #: Most recent observation — what a bench tail or debugger wants
    #: from a warm path (the mean is polluted by the compile-pass
    #: first observation).
    last: float = 0.0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)
    #: Ring of the most recent observations, powering percentile() —
    #: hot-path timings (rpc calls, store pushes) are long-tailed, and
    #: a mean hides exactly the tail an SLO check needs.
    _recent: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=TIMING_WINDOW),
        repr=False, compare=False)

    def observe(self, seconds: float) -> None:
        with self._lock:
            self.total += seconds
            self.count += 1
            self.last = seconds
            self._recent.append(seconds)

    @property
    def mean(self) -> float:
        with self._lock:
            return self.total / self.count if self.count else 0.0

    @staticmethod
    def _rank(data: list, p: float) -> float:
        if not data:
            return 0.0
        rank = max(0, min(len(data) - 1,
                          int(round(p / 100.0 * (len(data) - 1)))))
        return data[rank]

    def percentile(self, p: float) -> float:
        """Nearest-rank percentile over the recent window (seconds);
        0.0 before any observation."""
        with self._lock:
            data = sorted(self._recent)
        return self._rank(data, p)

    def summary(self) -> dict:
        # One lock round-trip + one sort for all three percentiles:
        # snapshot() calls this per timing on every ptype.Telemetry
        # pull, and observe() contends the same lock on hot paths.
        with self._lock:
            data = sorted(self._recent)
            total, count, last = self.total, self.count, self.last
        return {"mean_s": total / count if count else 0.0,
                "count": count, "last_s": last,
                "p50_s": self._rank(data, 50.0),
                "p95_s": self._rank(data, 95.0),
                "p99_s": self._rank(data, 99.0)}


@dataclass
class Gauge:
    """A last-write-wins level (queue depth, live replicas, scale
    hint) — the counter/timing pair can't express 'current value'."""

    name: str
    value: float = 0.0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def set(self, value: float) -> None:
        with self._lock:
            self.value = float(value)

    def add(self, delta: float = 1.0) -> None:
        with self._lock:
            self.value += delta


#: Exemplar slots kept per histogram: the K worst observations that
#: arrived with a trace id attached. Small and fixed — the point is a
#: handful of replayable links off the p99, not a second reservoir.
EXEMPLAR_SLOTS = 8


class Histogram:
    """Windowed reservoir with exact percentiles over the last
    ``window`` observations — the tail-latency surface (p50/p95/p99)
    the gateway's SLO accounting and autoscale signals read. A ring
    buffer, not a sketch: serving windows are small (thousands), and
    exact tails are what an SLO check needs.

    **Exemplars** (ISSUE 20): when an observation happens inside an
    active trace (or the caller passes ``trace_id``), the value keeps
    its trace id in one of :data:`EXEMPLAR_SLOTS` worst-value slots —
    so the p99 a dashboard shows links to a real replayable trace in
    the flight recorder, not an anonymous number. Free when tracing
    is disabled (one global load in :func:`trace.current_trace_id`)."""

    __slots__ = ("name", "window", "_ring", "_idx", "_count", "_lock",
                 "_exemplars")

    def __init__(self, name: str, window: int = 2048):
        self.name = name
        self.window = int(window)
        self._ring: list[float] = []
        self._idx = 0
        self._count = 0
        self._exemplars: list[tuple[float, str, float]] = []
        self._lock = lockcheck.lock("metrics.histogram")

    def observe(self, value: float, trace_id: str | None = None) -> None:
        v = float(value)
        if trace_id is None:
            trace_id = trace_mod.current_trace_id()
        with self._lock:
            if len(self._ring) < self.window:
                self._ring.append(v)
            else:
                self._ring[self._idx] = v
                self._idx = (self._idx + 1) % self.window
            self._count += 1
            if trace_id:
                ex = self._exemplars
                if len(ex) < EXEMPLAR_SLOTS:
                    ex.append((v, trace_id, time.time()))
                else:
                    i = min(range(len(ex)), key=lambda j: ex[j][0])
                    if v > ex[i][0]:
                        ex[i] = (v, trace_id, time.time())

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    def percentile(self, p: float) -> float:
        """Nearest-rank percentile over the window; 0.0 when empty."""
        with self._lock:
            data = sorted(self._ring)
        if not data:
            return 0.0
        rank = max(0, min(len(data) - 1,
                          int(round(p / 100.0 * (len(data) - 1)))))
        return data[rank]

    def exemplars(self) -> list[dict]:
        """Worst-first ``{value, trace_id, ts}`` exemplar slots —
        what ``obs tail`` and the OpenMetrics exporter surface."""
        with self._lock:
            ex = list(self._exemplars)
        ex.sort(key=lambda e: -e[0])
        return [{"value": round(v, 3), "trace_id": tid,
                 "ts": round(ts, 3)} for v, tid, ts in ex]

    def summary(self) -> dict:
        out = {"count": self.count,
               "p50": self.percentile(50.0),
               "p95": self.percentile(95.0),
               "p99": self.percentile(99.0)}
        ex = self.exemplars()
        if ex:  # key present only when real links exist — snapshot
            out["exemplars"] = ex  # shape is pinned by older tests
        return out


class MetricsRegistry:
    """Process-local named counters/timings with a JSON dump — the
    metrics surface the reference never had (SURVEY.md §5)."""

    def __init__(self):
        self._counters: dict[str, Counter] = {}
        self._timings: dict[str, Timing] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}
        self._lock = lockcheck.lock("metrics.registry")
        self._version = 0

    def _family(self, fam: dict, name: str, make):
        with self._lock:
            obj = fam.get(name)
            if obj is None:
                obj = fam[name] = make()
                # Version bumps let the health Sampler cache its walk
                # list and stay allocation-free between new families.
                self._version += 1
            return obj

    def counter(self, name: str) -> Counter:
        return self._family(self._counters, name, lambda: Counter(name))

    def timing(self, name: str) -> Timing:
        return self._family(self._timings, name, lambda: Timing(name))

    def gauge(self, name: str) -> Gauge:
        return self._family(self._gauges, name, lambda: Gauge(name))

    def histogram(self, name: str, window: int = 2048) -> Histogram:
        return self._family(self._histograms, name,
                            lambda: Histogram(name, window))

    @property
    def version(self) -> int:
        """Bumped once per family creation — the sampler's cheap
        'did the registry grow since my cached walk list' check."""
        with self._lock:
            return self._version

    def families(self) -> tuple:
        """(version, counters, timings, gauges, histograms) — shallow
        copies of the live family maps, for consumers (the health
        sampler) that need values-and-counts without the full summary
        construction :meth:`snapshot` pays."""
        with self._lock:
            return (self._version, dict(self._counters),
                    dict(self._timings), dict(self._gauges),
                    dict(self._histograms))

    def timed(self, name: str):
        """Context manager recording wall time into a Timing."""
        registry = self

        class _Ctx:
            def __enter__(self):
                self._t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                registry.timing(name).observe(time.perf_counter() - self._t0)
                return False

        return _Ctx()

    def snapshot(self) -> dict:
        with self._lock:
            counters = dict(self._counters)
            timings = dict(self._timings)
            gauges = dict(self._gauges)
            histograms = dict(self._histograms)
        # Every family dumps uniformly: counters/gauges as values,
        # timings and histograms as distribution summaries (count +
        # p50/p95/p99) — the gateway's SLO tail and a hot path's
        # Timing read the same way in one dump.
        return {
            "counters": {n: c.value for n, c in counters.items()},
            "timings": {n: t.summary() for n, t in timings.items()},
            "gauges": {n: g.value for n, g in gauges.items()},
            "histograms": {n: h.summary() for n, h in histograms.items()},
        }

    def dump_json(self) -> str:
        return json.dumps(self.snapshot(), separators=(",", ":"))


#: Default process-global registry.
metrics = MetricsRegistry()


def flatten_snapshot(snap: dict) -> dict:
    """One flat ``{name: scalar}`` view of a registry snapshot — what
    :meth:`MetricsWriter.emit` merges so the training scalar log and
    the health-plane series read the same values: counters and gauges
    as-is, timings as ``<name>.last_s`` (what the sampler stamps into
    its series) plus ``<name>.mean_s``, histograms as ``<name>.p99``.
    """
    flat: dict = {}
    flat.update(snap.get("counters", {}))
    flat.update(snap.get("gauges", {}))
    for name, s in snap.get("timings", {}).items():
        flat[f"{name}.last_s"] = s.get("last_s", 0.0)
        flat[f"{name}.mean_s"] = s.get("mean_s", 0.0)
    for name, s in snap.get("histograms", {}).items():
        flat[f"{name}.p99"] = s.get("p99", 0.0)
    return flat


# --------------------------------------------------------- memory gauges


def memory_watermarks(device=None) -> dict:
    """Device HBM watermarks where the backend reports them
    (``device.memory_stats()``: bytes_in_use / peak_bytes_in_use, the
    PJRT allocator's numbers), plus the process peak RSS fallback via
    ``resource.getrusage`` — always present, so the health plane can
    watch memory growth even on backends with no allocator stats."""
    out: dict = {}
    try:
        dev = device if device is not None else jax.devices()[0]
        stats = dev.memory_stats() or {}
    except Exception:  # noqa: BLE001 — stats are best-effort per backend
        stats = {}
    for src, dst in (("bytes_in_use", "device_bytes_in_use"),
                     ("peak_bytes_in_use", "device_peak_bytes"),
                     ("bytes_limit", "device_bytes_limit")):
        if src in stats:
            out[dst] = int(stats[src])
    try:
        import resource

        # Linux reports ru_maxrss in KiB; it is a peak, i.e. already a
        # watermark.
        out["rss_bytes"] = int(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
    except Exception:  # noqa: BLE001 — resource is POSIX-only
        pass
    return out


def record_memory_gauges(registry: MetricsRegistry | None = None,
                         device=None) -> dict:
    """Refresh the ``mem.*`` gauges from :func:`memory_watermarks` in
    ``registry`` (default: the process-global one) and return the raw
    dict — the seam serve.Info(), the telemetry endpoint, and the
    health sampler share."""
    reg = registry if registry is not None else metrics
    wm = memory_watermarks(device)
    for key, value in wm.items():
        reg.gauge(f"mem.{key}").set(value)
    return wm


class MetricsWriter:
    """Append-only JSONL metrics sink for training runs.

    One ``{"ts": ..., "step": ..., **scalars}`` line per emit —
    tail-able during a run, trivially loadable after (pandas/jq); the
    file-based observability tier beneath profiler traces. Flushed per
    line so a SIGKILLed run keeps everything emitted before the kill.
    """

    def __init__(self, path: str):
        import os

        os.makedirs(os.path.dirname(os.path.abspath(path)),
                    exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")
        self._lock = lockcheck.lock("metrics.kvlogger")

    def emit(self, step: int, snapshot: dict | None = None,
             **scalars) -> None:
        """Emit one line. ``snapshot`` (a :meth:`MetricsRegistry
        .snapshot` dict, or a registry to snapshot) merges flattened
        via :func:`flatten_snapshot` UNDER the explicit scalars — the
        training log and the health series then agree on one source of
        truth instead of call sites recomputing rates by hand."""
        import math

        if snapshot is not None:
            if isinstance(snapshot, MetricsRegistry):
                snapshot = snapshot.snapshot()
            merged = flatten_snapshot(snapshot)
            merged.update(scalars)
            scalars = merged
        rec = {"ts": round(time.time(), 3), "step": int(step)}
        for k, v in scalars.items():
            try:
                f = float(v)
            except (TypeError, ValueError):
                rec[k] = str(v)
                continue
            # json.dumps would emit the invalid-JSON token `NaN` and
            # break jq/strict parsers on exactly the diverging runs
            # where the file matters most — stringify non-finite.
            rec[k] = f if math.isfinite(f) else str(f)
        line = json.dumps(rec, separators=(",", ":")) + "\n"
        with self._lock:
            self._f.write(line)
            self._f.flush()

    def close(self) -> None:
        with self._lock:
            try:
                self._f.close()
            except OSError:
                pass


# ------------------------------------------------------------- profiling
# The reference had zap logging only (SURVEY.md §5 "Tracing/profiling:
# Absent"); the TPU build owes JAX profiler traces (XPlane/TensorBoard)
# with annotated steps so Store collective time is attributable.
# Captures are taken by ``health/profiling.start``/``stop``.

#: A named region in profiler traces (host timeline, on the device
#: trace's clock), in the flight recorder and for the goodput ledger:
#: :func:`ptype_tpu.trace.span`, the one function that opens a region,
#: under the name the train/store side has always imported.
#:
#: >>> with metrics.annotate("store.push/grads"):
#: ...     store.push_tree("grads", grads)
annotate = trace_mod.span


def step_annotation(step: int):
    """Mark one training step in the trace (XProf groups by these)."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


@dataclass
class StepStats:
    """Rolling per-step throughput tracker for training loops."""

    flops_per_token: float
    n_chips: int
    peak_tflops: float | None = None
    tokens: int = 0
    seconds: float = 0.0
    steps: int = 0
    _t0: float = field(default=0.0, repr=False)

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def step(self, n_tokens: int, n_steps: int = 1) -> None:
        """Fold ``n_tokens`` of COMPLETED work (``n_steps`` train steps)
        into the rolling rates. Callers that dispatch asynchronously must
        only call this at drain boundaries — crediting tokens at dispatch
        time measures queueing rate, not compute (VERDICT r2 weak #5)."""
        now = time.perf_counter()
        self.seconds += now - self._t0
        self._t0 = now
        self.tokens += n_tokens
        self.steps += n_steps

    @property
    def tokens_per_sec(self) -> float:
        return self.tokens / self.seconds if self.seconds else 0.0

    @property
    def tokens_per_sec_per_chip(self) -> float:
        return self.tokens_per_sec / max(self.n_chips, 1)

    @property
    def mfu(self) -> float:
        return mfu(self.tokens_per_sec, self.flops_per_token,
                   self.n_chips, self.peak_tflops)
