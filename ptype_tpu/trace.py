"""Distributed tracing — spans, wire context, and the flight recorder.

The reference's entire observability API was one counter
(``Client.ConnectionErrs``, SURVEY.md §5); the repo since grew
per-process metrics (metrics.py) and KV logs (logs.py), but a request
crossing gateway → actor RPC → coordinator → TensorStore left no
connected record — every soak failure was debugged by grepping five
processes' logs. This module is the missing trace plane:

- **Spans** carry W3C-style context (``trace_id`` / ``span_id`` /
  parent) through a per-thread contextvar; :func:`span` opens a child
  of whatever is current, so nesting needs no plumbing.
- **Wire propagation**: the active span's ``traceparent`` rides actor
  RPC frames (rpc.py injects ``tp``, actor.py re-attaches it around
  dispatch) and coord wire frames (coord/wire.py injects ``_tp``,
  coord/service.py re-attaches) — one request is ONE trace across
  every process it touches.
- **Flight recorder**: each process keeps finished spans in a bounded
  ring (:class:`FlightRecorder`), dumpable on demand
  (:meth:`FlightRecorder.dump_jsonl`) or on unhandled error/shed
  (:func:`maybe_dump`, armed by ``PTYPE_TRACE_DUMP_DIR`` or
  ``enable(dump_dir=...)``).
- **Chaos correlation**: fault firings and recovery beacons
  (:mod:`ptype_tpu.chaos`) land as events on the span they hit, so a
  soak failure shows *which request* a fault landed in.

- **One seam**: :func:`span` is the one function that opens a region,
  for every layer (``metrics.annotate`` is this function under the name
  the train/store side imports). A region has three sinks, each taken
  only when it is armed: the flight recorder (:func:`enable`), a live
  ``jax.profiler`` capture (the region becomes a ``TraceAnnotation`` of
  the same name, its attributes the annotation's metadata — so a device
  profile holds the gateway's, the rpc's and the engine's spans on the
  device trace's clock), and the region observer
  (:func:`set_region_observer` — the goodput ledger).

Zero-cost contract (same shape as chaos.py): with no sink armed,
:func:`span` / :func:`span_from` / :func:`attach` return a module
singleton no-op context manager — two global loads and the profiler's
one atomic "is a capture live" check, no allocation;
:func:`traceparent` returns ``None`` before touching the contextvar.
Tracing is enabled per process with :func:`enable` (tests, the obs
demo, bench probes) or the ``PTYPE_TRACE`` env var.

This module imports only the stdlib plus :mod:`ptype_tpu.chaos`
(itself stdlib-only) at module level — it sits under logs/metrics/rpc
and must never create an import cycle. ``jax.profiler`` is looked up
once, and only in a process that has already imported jax: a process
without jax cannot have a capture running.
"""

from __future__ import annotations

import collections
import contextvars
import json
import os
import random
import sys
import threading
import time

from ptype_tpu import chaos

__all__ = [
    "Span", "FlightRecorder",
    "enable", "disable", "enabled", "capturing", "recorder",
    "set_region_observer", "span", "span_from", "attach", "current",
    "traceparent",
    "parse_traceparent", "add_event", "maybe_dump", "telemetry",
]

#: Env var: truthy value arms tracing at import (multiprocess workers
#: join a traced run without code changes, like PTYPE_CHAOS_PLAN).
TRACE_ENV = "PTYPE_TRACE"
#: Env var: directory for on-error flight-recorder dumps.
DUMP_ENV = "PTYPE_TRACE_DUMP_DIR"

_ids = random.Random()


def _new_trace_id() -> str:
    return f"{_ids.getrandbits(128):032x}"


def _new_span_id() -> str:
    return f"{_ids.getrandbits(64):016x}"


class Span:
    """One timed operation. Created only while tracing is enabled;
    finished spans are frozen into the process flight recorder."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "start_s",
                 "dur_s", "attrs", "events", "status", "tid", "remote")

    def __init__(self, name: str, trace_id: str, parent_id: str | None,
                 remote: bool = False):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _new_span_id()
        self.parent_id = parent_id
        #: Wall clock, NOT monotonic: cross-process spans must land on
        #: one shared timeline for the stitched Perfetto view.
        self.start_s = time.time()
        self.dur_s = 0.0
        self.attrs: dict = {}
        self.events: list[dict] = []
        self.status = "ok"
        self.tid = threading.get_ident()
        #: True for the placeholder parent re-created from a wire
        #: traceparent by :func:`attach` — context only, never recorded.
        self.remote = remote

    def set_attr(self, key: str, value) -> "Span":
        self.attrs[key] = value
        return self

    def set_status(self, status: str) -> "Span":
        """Mark the span's outcome explicitly — for failures the code
        CATCHES (a retried attempt, an absorbed transport error) that
        the context-manager exit therefore never sees."""
        self.status = status
        return self

    def add_event(self, name: str, **attrs) -> None:
        self.events.append({"name": name,
                            "t": round(time.time() - self.start_s, 6),
                            **({"attrs": attrs} if attrs else {})})

    def to_dict(self) -> dict:
        d = {"name": self.name, "trace_id": self.trace_id,
             "span_id": self.span_id, "parent_id": self.parent_id,
             "start_s": round(self.start_s, 6),
             "dur_s": round(self.dur_s, 6), "status": self.status,
             "tid": self.tid}
        if self.attrs:
            d["attrs"] = self.attrs
        if self.events:
            d["events"] = self.events
        return d

    def __repr__(self) -> str:
        return (f"Span({self.name!r} trace={self.trace_id[:8]} "
                f"span={self.span_id[:8]} {self.status})")


class FlightRecorder:
    """Bounded ring of finished spans — the per-process black box.

    A ring, not a file: tracing must be cheap enough to leave on in a
    soak, and the interesting spans are always the most recent ones.
    Pull the ring over RPC (:func:`telemetry` via ``ptype.Telemetry``)
    or dump it to JSONL when something goes wrong.
    """

    def __init__(self, service: str = "", capacity: int = 4096):
        self.service = service or f"pid-{os.getpid()}"
        self.pid = os.getpid()
        self.capacity = int(capacity)
        self._ring: "collections.deque[Span]" = collections.deque(
            maxlen=self.capacity)
        self._lock = threading.Lock()
        self._finished = 0

    def record(self, sp: Span) -> None:
        with self._lock:
            self._ring.append(sp)
            self._finished += 1

    @property
    def finished(self) -> int:
        with self._lock:
            return self._finished

    def spans(self, trace_id: str | None = None,
              limit: int | None = None) -> list[Span]:
        with self._lock:
            out = list(self._ring)
        if trace_id is not None:
            out = [s for s in out if s.trace_id == trace_id]
        if limit is not None and len(out) > limit:
            out = out[-limit:]
        return out

    def to_dicts(self, limit: int | None = None,
                 trace_id: str | None = None) -> list[dict]:
        return [s.to_dict() for s in self.spans(trace_id, limit)]

    def trace_ids(self) -> list[str]:
        """Distinct trace ids in the ring, oldest first."""
        seen: dict[str, None] = {}
        for s in self.spans():
            seen.setdefault(s.trace_id, None)
        return list(seen)

    def dump_jsonl(self, path: str) -> int:
        """Write the ring (one span dict per line); returns the count."""
        spans = self.to_dicts()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            for s in spans:
                f.write(json.dumps(s, separators=(",", ":")) + "\n")
        return len(spans)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


# -------------------------------------------------------------- module API

_recorder: FlightRecorder | None = None
_current: "contextvars.ContextVar[Span | None]" = contextvars.ContextVar(
    "ptype_trace_span", default=None)
_dump_dir: str | None = None
_dump_last = 0.0
_dump_lock = threading.Lock()
#: Minimum seconds between on-error dumps — an error storm must not
#: turn the flight recorder into a disk-filling loop.
DUMP_MIN_INTERVAL_S = 5.0


def enable(service: str = "", capacity: int = 4096,
           dump_dir: str | None = None) -> FlightRecorder:
    """Arm tracing process-wide; returns the fresh flight recorder.
    Also registers the chaos observer so fault firings / recovery
    beacons land as events on the span they hit."""
    global _recorder, _dump_dir, _dump_last
    rec = FlightRecorder(service, capacity)
    _recorder = rec
    if dump_dir is not None:
        _dump_dir = dump_dir
    # A fresh recorder owes nothing to the last one's dumps: the rate
    # limit would otherwise silence this session's first post-mortem
    # for up to DUMP_MIN_INTERVAL_S after another session's.
    with _dump_lock:
        _dump_last = 0.0
    chaos.set_observer(_chaos_observer)
    return rec


def disable() -> None:
    global _recorder, _dump_dir
    _recorder = None
    _dump_dir = None
    chaos.set_observer(None)


def _restore(rec: FlightRecorder | None, dump_dir: str | None) -> None:
    """Re-arm a previously captured (recorder, dump_dir) pair — how the
    bench overhead probe hands back the host process's tracing state
    (ring, service name, dump config) after toggling around its own
    measurement."""
    global _recorder, _dump_dir
    _recorder = rec
    _dump_dir = dump_dir
    chaos.set_observer(_chaos_observer if rec is not None else None)


def enabled() -> bool:
    return _recorder is not None


#: Observer of finished regions — ``fn(name, dur_s)``. The health
#: plane's goodput ledger installs itself here, so every train.step /
#: store.push_tree / checkpoint region feeds the per-step breakdown
#: through the one seam.
_observer = None


def set_region_observer(fn) -> None:
    """Install (or clear, with ``None``) the region observer. One per
    process — the goodput ledger; tests that need several ledgers drive
    them directly via ``GoodputLedger.region``."""
    global _observer
    _observer = fn


#: ``jax.profiler.TraceAnnotation``, once this process has imported jax.
_trace_me = None


def capturing() -> bool:
    """Is a jax profiler capture running? The profiler's own atomic
    check; False in a process that never imported jax."""
    global _trace_me
    tm = _trace_me
    if tm is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation as tm

        _trace_me = tm
    return tm.is_enabled()


def recorder() -> FlightRecorder | None:
    return _recorder


def dump_dir() -> str | None:
    """The on-error dump directory, if armed (``enable(dump_dir=...)``
    or ``PTYPE_TRACE_DUMP_DIR``) — where :func:`maybe_dump` writes,
    and where the health plane's alert-triggered profile captures
    land so a page's span ring and device timeline sit side by side."""
    return _dump_dir or os.environ.get(DUMP_ENV) or None


def current() -> Span | None:
    """The active span on this thread, or None (always None when
    tracing is disabled — stale contextvars from a disable() mid-span
    must not leak ids into logs)."""
    if _recorder is None:
        return None
    return _current.get()


def traceparent() -> str | None:
    """W3C-style ``00-<trace_id>-<span_id>-01`` for the active span —
    what the rpc/coord transports inject into outbound frames."""
    if _recorder is None:
        return None
    sp = _current.get()
    if sp is None:
        return None
    return f"00-{sp.trace_id}-{sp.span_id}-01"


def current_trace_id() -> str | None:
    """The active trace id on this thread, or None — the exemplar
    seam (:meth:`ptype_tpu.metrics.Histogram.observe` attaches it to
    tail observations). One global load when tracing is disabled."""
    if _recorder is None:
        return None
    sp = _current.get()
    return sp.trace_id if sp is not None else None


def parse_traceparent(tp) -> tuple[str, str] | None:
    """(trace_id, span_id) from a traceparent, or None if malformed —
    a peer's garbage must degrade to 'start a fresh trace', not raise."""
    if not isinstance(tp, str):
        return None
    parts = tp.split("-")
    if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    try:
        int(parts[1], 16), int(parts[2], 16)
    except ValueError:
        return None
    return parts[1], parts[2]


class _Noop:
    """The disabled-path singleton: a context manager that allocates
    nothing and absorbs the whole Span surface."""

    __slots__ = ()

    def __enter__(self) -> "_Noop":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_attr(self, key: str, value) -> "_Noop":
        return self

    def set_status(self, status: str) -> "_Noop":
        return self

    def add_event(self, name: str, **attrs) -> None:
        pass


_NOOP = _Noop()


class _SpanCtx:
    """One region, into whichever sinks are armed. Ring: a span opened
    as a child of the current (or an explicit remote) context, current
    for the scope, frozen into the recorder on exit. Capture: a
    profiler ``TraceAnnotation`` around the same scope. Observer: the
    region's name and wall seconds on exit."""

    __slots__ = ("_rec", "_name", "_attrs", "_parent", "_span", "_token",
                 "_ann", "_obs", "_t0")

    def __init__(self, rec: FlightRecorder | None, name: str,
                 parent: tuple[str, str] | None, attrs: dict,
                 live: bool, obs):
        self._rec = rec
        self._name = name
        self._attrs = attrs
        self._parent = parent  # (trace_id, span_id) | None
        self._span: Span | None = None
        self._token = None
        self._ann = _trace_me(name, **attrs) if live else None
        self._obs = obs

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        # Monotonic duration clock alongside the wall-clock start.
        self._t0 = time.perf_counter()
        if self._rec is None:
            return _NOOP
        if self._parent is not None:
            trace_id, parent_id = self._parent
        else:
            cur = _current.get()
            if cur is not None:
                trace_id, parent_id = cur.trace_id, cur.span_id
            else:
                trace_id, parent_id = _new_trace_id(), None
        sp = Span(self._name, trace_id, parent_id)
        if self._attrs:
            sp.attrs.update(self._attrs)
        self._span = sp
        self._token = _current.set(sp)
        return sp

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = time.perf_counter() - self._t0
        sp = self._span
        if sp is not None:
            sp.dur_s = dt
            if exc is not None:
                # ShedError is a typed refusal, not a failure — checked
                # by name so this module stays import-light.
                sp.status = ("shed" if type(exc).__name__ == "ShedError"
                             else "error")
                sp.add_event("exception", type=type(exc).__name__,
                             message=str(exc)[:200])
            _current.reset(self._token)
            self._rec.record(sp)
        if self._obs is not None:
            try:
                self._obs(self._name, dt)
            except Exception:  # noqa: BLE001 — telemetry must never
                pass           # kill the step it observes, nor leak
                #                the annotation's scope.
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        return False


def span(name: str, **attrs):
    """Open a region for a ``with`` scope: a span (child of the current
    one) in the flight recorder when tracing is armed, a profiler
    annotation of the same name — ``attrs`` its metadata — when a
    capture is live, a report to the region observer when one is
    installed. The no-op singleton otherwise — no allocation.

    >>> with trace.span("store.push/grads"):
    ...     store.push_tree("grads", grads)
    """
    return span_from(None, name, **attrs)


def span_from(tp, name: str, **attrs):
    """:func:`span` whose ring parent is a wire ``traceparent`` (the
    server side of a propagated call; absent or malformed, the current
    span as usual)."""
    rec, obs, live = _recorder, _observer, capturing()
    if rec is None and obs is None and not live:
        return _NOOP
    parent = parse_traceparent(tp) if rec is not None else None
    return _SpanCtx(rec, name, parent, attrs, live, obs)


class _AttachCtx:
    """Make a remote traceparent the current context WITHOUT opening a
    recorded span — the seam for dispatch paths that already open
    their own span (ActorServer.dispatch) one frame below."""

    __slots__ = ("_parent", "_token")

    def __init__(self, parent: tuple[str, str]):
        self._parent = parent
        self._token = None

    def __enter__(self):
        trace_id, span_id = self._parent
        ph = Span("", trace_id, None, remote=True)
        ph.span_id = span_id  # impersonate the remote caller's span
        self._token = _current.set(ph)
        return ph

    def __exit__(self, *exc) -> bool:
        _current.reset(self._token)
        return False


def attach(tp):
    """Context manager adopting a wire traceparent as the current
    context (no span recorded). No-op when disabled or ``tp`` is
    absent/malformed."""
    if _recorder is None:
        return _NOOP
    parent = parse_traceparent(tp)
    if parent is None:
        return _NOOP
    return _AttachCtx(parent)


def add_event(name: str, **attrs) -> None:
    """Attach an event to the active span; free no-op otherwise."""
    if _recorder is None:
        return
    sp = _current.get()
    if sp is not None and not sp.remote:
        sp.add_event(name, **attrs)


def _chaos_observer(kind: str, site: str, action: str, key: str) -> None:
    """chaos.py observer: fault firings and recovery beacons become
    events on whatever span the afflicted thread is inside."""
    if _recorder is None:
        return
    sp = _current.get()
    if sp is not None and not sp.remote:
        sp.add_event(f"chaos.{kind}", site=site, action=action, key=key)


# ------------------------------------------------------- on-error dumping


def maybe_dump(reason: str = "") -> str | None:
    """Dump the flight recorder to ``<dump_dir>/flight-<pid>-<ns>.jsonl``
    if a dump dir is configured (``enable(dump_dir=...)`` or
    ``PTYPE_TRACE_DUMP_DIR``), rate-limited to one dump per
    :data:`DUMP_MIN_INTERVAL_S`. Returns the path or None.

    Called from the unhandled-error path of actor dispatch and the
    gateway's shed path — the moments a post-mortem wants the ring."""
    global _dump_last
    rec = _recorder
    d = _dump_dir or os.environ.get(DUMP_ENV)
    if rec is None or not d:
        return None
    now = time.monotonic()
    with _dump_lock:
        if now - _dump_last < DUMP_MIN_INTERVAL_S:
            return None
        _dump_last = now
    path = os.path.join(
        d, f"flight-{rec.pid}-{time.monotonic_ns()}.jsonl")
    try:
        rec.dump_jsonl(path)
    except OSError:
        return None
    if reason:
        try:
            with open(path, "a", encoding="utf-8") as f:
                f.write(json.dumps({"flight_dump_reason": reason}) + "\n")
        except OSError:
            pass
    return path


# ------------------------------------------------------ telemetry surface


def telemetry(span_limit: int = 256) -> dict:
    """One node's observability snapshot — what the built-in
    ``ptype.Telemetry`` actor endpoint serves and
    :func:`ptype_tpu.telemetry.cluster_snapshot` aggregates: process
    identity, the metrics registry snapshot (memory watermark gauges
    refreshed per pull), recent series when the health sampler is
    armed (:func:`ptype_tpu.health.series.start` — the history the
    alert rules evaluate), and the most recent spans from the flight
    recorder."""
    from ptype_tpu import metrics as metrics_mod  # lazy: jax import
    from ptype_tpu.health import series as series_mod

    metrics_mod.record_memory_gauges()
    rec = _recorder
    return {
        "pid": os.getpid(),
        "service": rec.service if rec is not None else "",
        "tracing": rec is not None,
        "ts": round(time.time(), 3),
        "metrics": metrics_mod.metrics.snapshot(),
        "series": series_mod.default_snapshot(),
        "spans": rec.to_dicts(limit=span_limit) if rec is not None else [],
        "spans_finished": rec.finished if rec is not None else 0,
    }


def _maybe_enable_from_env() -> None:
    raw = os.environ.get(TRACE_ENV, "")
    if raw and raw not in ("0", "false", "off") and _recorder is None:
        enable(service=raw if raw not in ("1", "true", "on") else "")


_maybe_enable_from_env()
