"""Profiling plane: on-demand device profiling + compiled-cost MFU.

ROADMAP items 1 and 2 both gate their remaining headroom on "if a
profile shows the reduce still exposed on real ICI" — and until ISSUE 8
the stack had no way to take that profile: ``metrics.trace`` was a
local-only ``jax.profiler`` wrapper nobody could reach from the
cluster, and the goodput ledger's MFU denominator was the analytic
``models.flops_per_token`` formula, never checked against what XLA
actually compiled. This module is the missing plane, four seams:

- **Capture sessions** (:func:`start` / :func:`stop` /
  :func:`capture`): a managed ``jax.profiler`` XPlane capture into an
  artifact directory (``$PTYPE_PROFILE_DIR`` or a tempdir), returning
  a file manifest — and, on request, the artifact BYTES, so a capture
  can ship over the actor wire. ``jax.profiler`` is process-global
  (one capture at a time); the session lock makes a concurrent start a
  typed :class:`ProfileError`, not a crash. HBM snapshots
  (:func:`memory_snapshot` — ``device.memory_stats()`` plus the pprof
  ``device_memory_profile``) ride along with every capture.
- **The ``ptype.Profile`` actor endpoint** (:func:`endpoint`): every
  :class:`~ptype_tpu.actor.ActorServer` serves it built-in (sibling of
  ``ptype.Telemetry``), so any node's device timeline is one RPC away
  — :func:`ptype_tpu.telemetry.cluster_profile` fans a simultaneous
  capture across the whole registry. Regions already line up across
  the stitched span view and the device timeline because
  ``metrics.annotate`` emits BOTH a profiler ``TraceAnnotation`` and a
  distributed-trace span through the one seam.
- **Alert-triggered capture** (:class:`AlertCapture`): an
  :class:`~ptype_tpu.health.rules.AlertEngine` hook that, when
  ``straggler`` / ``train-stall`` / ``slo-p99`` fires, captures a
  short profile on the NAMED node over its actor surface and drops
  the artifacts next to the flight-recorder dump — rate-limited like
  ``trace.maybe_dump``, so an alert storm cannot turn the profiler
  into a disk-filling loop. Every page becomes a post-mortem with the
  device evidence already attached.
- **Compiled-cost accounting** (:func:`compiled_cost` /
  :func:`measure_compiled_cost`): FLOPs/bytes from XLA's
  ``cost_analysis()`` on the jitted step programs, feeding the goodput
  ledger an ``mfu_compiled`` alongside the analytic MFU
  (:meth:`~ptype_tpu.health.goodput.GoodputLedger.set_compiled_flops`)
  and the ``mfu-divergence`` alert rule — a silent remat or dtype
  change shifts real FLOPs, and today somebody notices. One caveat
  XLA imposes: ``cost_analysis`` counts a while-loop (``lax.scan``)
  body ONCE, so cost lowerings of the transformer step unroll the
  layer scan (``scan_unroll=n_layers``, same math, trip count 1);
  :func:`compiled_cost` on an un-unrolled scan program is a lower
  bound and says so.

The host-side parser (:func:`summarize`) reads the ``*.trace.json.gz``
Chrome-trace artifact jax writes next to the ``.xplane.pb`` — stdlib
gzip+json, so top-op tables work on CPU test runs with no TensorBoard.

Lint rule PT008 (tools/ptlint) closes the side door: raw
``jax.profiler.start_trace`` / ``stop_trace`` calls are forbidden in
``ptype_tpu/`` outside metrics.py and this module — every capture goes
through the rate-limited, artifact-managed seam.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import os
import threading
import time

import jax

from ptype_tpu import logs

log = logs.get_logger("profiling")

#: Env var: base directory for capture artifacts (default: a
#: process-qualified tempdir subdirectory).
PROFILE_DIR_ENV = "PTYPE_PROFILE_DIR"
#: Default on-demand capture length.
DEFAULT_CAPTURE_S = 0.5
#: Hard cap on a single capture's duration — a fat-fingered
#: ``duration=300`` from an operator (or a buggy alert hook) must not
#: pin the process-global profiler for minutes.
MAX_CAPTURE_S = 30.0
#: Byte budget for shipping artifact data over the wire in one reply.
MAX_SHIP_BYTES = 32 * 2**20
#: Minimum seconds between alert-triggered captures per (rule, node) —
#: the ``trace.maybe_dump`` contract, applied to device profiles.
CAPTURE_MIN_INTERVAL_S = 60.0


class ProfileError(RuntimeError):
    """Typed misuse of the process-global profiler (double start, stop
    without start, capture path escape)."""


# -------------------------------------------------------- capture session

_lock = threading.Lock()
#: The active session: {"dir", "label", "t0"} — jax.profiler is
#: process-global, so there is at most one.
_active: dict | None = None


def base_dir() -> str:
    """Artifact root: ``$PTYPE_PROFILE_DIR`` or a tempdir subdir."""
    d = os.environ.get(PROFILE_DIR_ENV)
    if d:
        return d
    import tempfile

    return os.path.join(tempfile.gettempdir(),
                        f"ptype-profile-{os.getpid()}")


def active() -> bool:
    with _lock:
        return _active is not None


def start(label: str = "", base: str | None = None) -> dict:
    """Begin an XPlane capture into a fresh artifact directory.

    Returns ``{"dir", "label", "ts"}``. Raises :class:`ProfileError`
    if a capture is already running (the profiler is process-global).
    """
    global _active
    d = os.path.join(base or base_dir(),
                     f"{label or 'capture'}-{time.monotonic_ns()}")
    with _lock:
        if _active is not None:
            raise ProfileError(
                f"profile capture already active in {_active['dir']!r}")
        os.makedirs(d, exist_ok=True)
        jax.profiler.start_trace(d)
        _active = {"dir": d, "label": label,
                   "t0": time.perf_counter()}
    log.info("profile capture started", kv={"dir": d, "label": label})
    return {"dir": d, "label": label, "ts": round(time.time(), 3)}


def stop(include_data: bool = False,
         max_bytes: int = MAX_SHIP_BYTES) -> dict:
    """End the active capture. Returns the artifact manifest::

        {"dir", "label", "duration_s", "files": [{"path", "size"}],
         "memory": <memory_snapshot()>, "data": {relpath: bytes}?}

    ``data`` (with ``include_data``) carries artifact bytes up to
    ``max_bytes`` total — the wire-shipping path; oversize files are
    listed in the manifest but skipped from ``data`` (``truncated``
    names them). Raises :class:`ProfileError` without an active
    capture.
    """
    global _active
    with _lock:
        if _active is None:
            raise ProfileError("no profile capture active")
        sess, _active = _active, None
        jax.profiler.stop_trace()
    dur = time.perf_counter() - sess["t0"]
    out = {"dir": sess["dir"], "label": sess["label"],
           "duration_s": round(dur, 4),
           "files": artifact_files(sess["dir"]),
           "memory": memory_snapshot()}
    if include_data:
        data: dict[str, bytes] = {}
        truncated: list[str] = []
        budget = int(max_bytes)
        for f in out["files"]:
            if f["size"] > budget:
                truncated.append(f["path"])
                continue
            try:
                with open(os.path.join(sess["dir"], f["path"]),
                          "rb") as fp:
                    data[f["path"]] = fp.read()
            except OSError:
                truncated.append(f["path"])
                continue
            budget -= f["size"]
        out["data"] = data
        if truncated:
            out["truncated"] = truncated
    log.info("profile capture stopped",
             kv={"dir": sess["dir"], "files": len(out["files"]),
                 "duration_s": out["duration_s"]})
    return out


def capture(duration_s: float = DEFAULT_CAPTURE_S, label: str = "",
            include_data: bool = False,
            max_bytes: int = MAX_SHIP_BYTES,
            base: str | None = None) -> dict:
    """One-shot: start, run for ``duration_s`` (capped at
    :data:`MAX_CAPTURE_S`), stop. The remote-capture verb behind the
    ``ptype.Profile`` endpoint and every alert-triggered capture."""
    duration_s = min(max(float(duration_s), 0.0), MAX_CAPTURE_S)
    start(label=label, base=base)
    try:
        threading.Event().wait(duration_s)
    finally:
        result = stop(include_data=include_data, max_bytes=max_bytes)
    return result


def artifact_files(d: str) -> list[dict]:
    """Relative-path manifest of every file under ``d`` (sorted)."""
    out: list[dict] = []
    for dirpath, dirnames, filenames in os.walk(d):
        dirnames.sort()
        for f in sorted(filenames):
            p = os.path.join(dirpath, f)
            out.append({"path": os.path.relpath(p, d),
                        "size": os.path.getsize(p)})
    return out


def fetch(dir_path: str, relpath: str) -> bytes:
    """One artifact file's bytes — the follow-up verb for files the
    capture reply truncated. The resolved path must stay under
    ``dir_path`` (no traversal from the wire)."""
    root = os.path.realpath(dir_path)
    p = os.path.realpath(os.path.join(root, relpath))
    if not p.startswith(root + os.sep):
        raise ProfileError(f"artifact path escapes capture dir: "
                           f"{relpath!r}")
    with open(p, "rb") as fp:
        return fp.read()


def memory_snapshot(include_profile: bool = False) -> dict:
    """Per-device HBM snapshot + host watermarks.

    ``devices``: one row per local device with whatever the backend's
    ``memory_stats()`` reports (PJRT allocator bytes_in_use /
    peak_bytes_in_use / bytes_limit; ``{}`` on backends without stats
    — CPU). ``host`` is :func:`ptype_tpu.metrics.memory_watermarks`
    (always has the RSS fallback). With ``include_profile`` the pprof
    ``device_memory_profile()`` gzip bytes ride along for offline
    ``pprof`` analysis; its size is always reported.
    """
    from ptype_tpu import metrics as metrics_mod

    devices = []
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats() or {}
        except Exception:  # noqa: BLE001 — per-backend best effort
            stats = {}
        devices.append({
            "id": dev.id, "platform": dev.platform,
            "kind": getattr(dev, "device_kind", ""),
            "stats": {k: int(v) for k, v in stats.items()
                      if isinstance(v, (int, float))},
        })
    out = {"devices": devices,
           "host": metrics_mod.memory_watermarks()}
    try:
        prof = jax.profiler.device_memory_profile()
        out["memory_profile_size"] = len(prof)
        if include_profile:
            out["memory_profile"] = prof
    except Exception as e:  # noqa: BLE001 — optional, per-backend
        out["memory_profile_note"] = f"{type(e).__name__}: {e}"
    return out


# ----------------------------------------------------- the actor endpoint


def endpoint(cmd: str, options: dict | None = None):
    """The built-in ``ptype.Profile`` actor endpoint (registered by
    every :class:`~ptype_tpu.actor.ActorServer`, sibling of
    ``ptype.Telemetry``). Verbs::

        ("status",)                       -> platform + active session
        ("start",   {"label"})            -> begin a capture
        ("stop",    {"include_data", "max_bytes"})
        ("capture", {"duration_s", "label", "include_data", ...})
        ("memory",  {"include_profile"})  -> HBM snapshot
        ("fetch",   {"dir", "path"})      -> one artifact's bytes

    Errors (double start, unknown verb) surface as typed exceptions —
    the actor layer marshals them to the caller as ``RemoteError``.
    """
    opts = dict(options or {})
    if cmd == "status":
        with _lock:
            sess = dict(_active) if _active is not None else None
        dev = jax.local_devices()[0]
        return {"pid": os.getpid(), "platform": dev.platform,
                "device_kind": getattr(dev, "device_kind", ""),
                "devices": jax.local_device_count(),
                "active": sess is not None,
                "dir": sess["dir"] if sess else None}
    if cmd == "start":
        return start(label=opts.get("label", ""))
    if cmd == "stop":
        return stop(include_data=opts.get("include_data", False),
                    max_bytes=opts.get("max_bytes", MAX_SHIP_BYTES))
    if cmd == "capture":
        return capture(
            duration_s=opts.get("duration_s", DEFAULT_CAPTURE_S),
            label=opts.get("label", ""),
            include_data=opts.get("include_data", True),
            max_bytes=opts.get("max_bytes", MAX_SHIP_BYTES))
    if cmd == "memory":
        return memory_snapshot(
            include_profile=opts.get("include_profile", False))
    if cmd == "fetch":
        return fetch(opts["dir"], opts["path"])
    raise ProfileError(f"ptype.Profile: unknown command {cmd!r}")


def write_artifacts(out_dir: str, result: dict) -> list[str]:
    """Persist a shipped capture reply (the ``data`` bytes from
    :func:`stop`/:func:`capture` over the wire) under ``out_dir``;
    returns the written paths. Relative paths are sanitized the same
    way :func:`fetch` guards reads."""
    root = os.path.realpath(out_dir)
    os.makedirs(root, exist_ok=True)
    written: list[str] = []
    for rel, blob in (result.get("data") or {}).items():
        p = os.path.realpath(os.path.join(root, rel))
        if not p.startswith(root + os.sep):
            continue
        os.makedirs(os.path.dirname(p), exist_ok=True)
        with open(p, "wb") as fp:
            fp.write(blob)
        written.append(p)
    return written


# --------------------------------------------------- alert-driven capture

#: Alerts whose firing auto-captures a profile on the named node:
#: the rules whose runbook first question is "what is that node's
#: device timeline doing" (docs/OPERATIONS.md). The serving rules
#: (ISSUE 10) ride the same hook — a TTFT blowup or a thrashing KV
#: pool is diagnosed from the afflicted REPLICA's engine timeline
#: (prefill chunks vs decode steps vs admission waits), and the
#: replica is exactly what the alert names.
PROFILE_ALERT_RULES = ("straggler", "train-stall", "slo-p99",
                       "ttft-p99", "kv-pressure", "serve-stall")


class AlertCapture:
    """``AlertEngine`` hook: alert → short profile on the NAMED node.

    Install as ``AlertEngine(rules, capture=AlertCapture(...))``. On a
    matching firing it dials the node from the alert's node key
    (``service/addr:port`` — the cluster-snapshot key shape), runs the
    ``ptype.Profile`` ``capture`` verb with artifact shipping on, and
    writes the artifacts next to the flight-recorder dump
    (``out_dir``, defaulting to the trace plane's dump dir) — the page
    and its device evidence land side by side. Rate-limited per
    (rule, node) to one capture per ``min_interval_s``, mirroring
    ``trace.maybe_dump``; unresolvable node keys (the aggregator's own
    ``local`` row) degrade to a local capture. Capture runs on a
    background thread by default so ``evaluate()`` never blocks on a
    slow node; ``background=False`` is the deterministic test mode.
    """

    def __init__(self, out_dir: str | None = None,
                 duration_s: float = 0.25,
                 rules: tuple = PROFILE_ALERT_RULES,
                 min_interval_s: float = CAPTURE_MIN_INTERVAL_S,
                 timeout_s: float = 20.0,
                 background: bool = True):
        from ptype_tpu import trace as trace_mod

        self.out_dir = (out_dir or trace_mod.dump_dir()
                        or os.path.join(base_dir(), "alerts"))
        self.duration_s = float(duration_s)
        self.rules = tuple(rules)
        self.min_interval_s = float(min_interval_s)
        self.timeout_s = float(timeout_s)
        self.background = background
        #: Completed captures: {"rule", "node", "dir", "files"} — the
        #: post-mortem inventory (and the test surface).
        self.captures: list[dict] = []
        self.errors: list[dict] = []
        self._last: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def __call__(self, alert) -> None:
        if alert.rule not in self.rules:
            return
        key = (alert.rule, alert.node)
        now = time.monotonic()
        with self._lock:
            last = self._last.get(key)
            if last is not None and now - last < self.min_interval_s:
                return
            self._last[key] = now
        if self.background:
            threading.Thread(target=self._capture, args=(alert,),
                             name="alert-profile", daemon=True).start()
        else:
            self._capture(alert)

    @staticmethod
    def _parse_node(node_key: str) -> tuple[str, int] | None:
        """``service/addr:port`` → (addr, port); None when the key has
        no dialable endpoint (the aggregator's ``local`` row)."""
        tail = node_key.rsplit("/", 1)[-1]
        addr, sep, port = tail.rpartition(":")
        if not sep or not addr:
            return None
        try:
            return addr, int(port)
        except ValueError:
            return None

    def _capture(self, alert) -> None:
        dest = os.path.join(
            self.out_dir,
            f"profile-{alert.rule}-"
            f"{alert.node.replace('/', '_').replace(':', '_')}-"
            f"{time.monotonic_ns()}")
        try:
            target = self._parse_node(alert.node)
            if target is None:
                result = capture(duration_s=self.duration_s,
                                 label=f"alert-{alert.rule}",
                                 include_data=True)
            else:
                result = self._remote_capture(*target)
            files = write_artifacts(dest, result)
            meta = {"rule": alert.rule, "node": alert.node,
                    "message": alert.message,
                    "ts": round(time.time(), 3),
                    "duration_s": result.get("duration_s"),
                    "remote_dir": result.get("dir"),
                    "memory": result.get("memory"),
                    "files": [os.path.relpath(p, dest) for p in files]}
            os.makedirs(dest, exist_ok=True)
            with open(os.path.join(dest, "capture.json"), "w",
                      encoding="utf-8") as fp:
                json.dump(meta, fp, indent=1, default=str)
            rec = {"rule": alert.rule, "node": alert.node,
                   "dir": dest, "files": len(files)}
            with self._lock:
                self.captures.append(rec)
            log.warning("alert-triggered profile captured", kv=rec)
        except Exception as e:  # noqa: BLE001 — the watchdog hosting
            # this hook must survive any capture failure (dead node,
            # disk full, profiler already busy on the target).
            with self._lock:
                self.errors.append({"rule": alert.rule,
                                    "node": alert.node,
                                    "error": f"{type(e).__name__}: {e}"})
            log.warning("alert-triggered profile capture failed",
                        kv={"rule": alert.rule, "node": alert.node,
                            "err": repr(e)})

    def _remote_capture(self, addr: str, port: int) -> dict:
        from ptype_tpu import telemetry
        from ptype_tpu.registry import Node

        return telemetry.node_profile(
            Node(addr, port), duration_s=self.duration_s,
            timeout=self.timeout_s, label="alert", dial_timeout=5.0)


# ------------------------------------------------- compiled-cost analysis


def tree_avals(tree):
    """Shape/dtype skeleton of a pytree — what :func:`compiled_cost`
    lowers against (no device data, no transfer)."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def compiled_cost(fn, *args, **kwargs) -> dict:
    """FLOPs/bytes XLA reports for ``fn`` compiled on ``args`` (arrays
    or :class:`~jax.ShapeDtypeStruct` avals) — the MFU denominator as
    the compiler sees it, not as a formula hopes.

    Returns ``{"flops", "bytes_accessed"}``. Caveat (XLA's, not
    ours): ``cost_analysis`` counts a while-loop (``lax.scan``) body
    once regardless of trip count, so a program with a rolled loop
    reports a LOWER BOUND — cost lowerings of the transformer step
    unroll the layer scan (trip count 1) to make the count exact.
    Raises :class:`ProfileError` when the backend reports no cost
    analysis at all.
    """
    compiled = fn.lower(*args, **kwargs).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not ca:
        raise ProfileError(
            "backend reported no cost_analysis for this program")
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def transformer_grads_cost(cfg, batch: int, seq: int,
                           stacked: int | None = None) -> dict:
    """Compiled cost of one fwd+bwd over a ``(batch, seq)`` token
    block for ``cfg`` — the dominant term of every trainer's step.

    Lowers ``value_and_grad(loss_fn)`` with the layer scan fully
    unrolled (``scan_unroll=n_layers`` — identical math, trip count 1,
    so ``cost_analysis`` counts every layer; see :func:`compiled_cost`).
    With ``stacked`` the program is vmapped over that many worker
    shards (the store-DP layout; ``batch`` is then per shard). Returns
    flops/bytes plus ``flops_per_token`` / ``tokens_per_step``.
    """
    import jax.numpy as jnp

    from ptype_tpu.models import transformer as tfm

    cost_cfg = dataclasses.replace(
        cfg, scan_unroll=max(1, int(cfg.n_layers)))
    params_avals = jax.eval_shape(
        lambda r: tfm.init_params(r, cfg), jax.random.PRNGKey(0))

    def local_grads(p, b):
        return jax.value_and_grad(tfm.loss_fn)(p, b, cost_cfg)

    shape = (batch, seq) if stacked is None else (stacked, batch, seq)
    batch_avals = {"tokens": jax.ShapeDtypeStruct(shape, jnp.int32),
                   "targets": jax.ShapeDtypeStruct(shape, jnp.int32)}
    fn = (jax.jit(local_grads) if stacked is None  # ptlint: disable=PT019 -- one-shot cost probe: the jit is lowered for cost_analysis only, never dispatched hot
          else jax.jit(jax.vmap(local_grads, in_axes=(None, 0))))
    cost = compiled_cost(fn, params_avals, batch_avals)
    tokens = batch * seq * (stacked or 1)
    cost["tokens_per_step"] = tokens
    cost["flops_per_token"] = cost["flops"] / tokens
    return cost


def measure_compiled_cost(preset: str = "optimus-125m", batch: int = 8,
                          seq: int = 128) -> dict:
    """Compiled-vs-analytic FLOPs on one config — the ISSUE 8
    acceptance check (``mfu_compiled`` within 10% of analytic MFU on
    the 125M CPU-mesh config, gap REPORTED either way). MFU shares the
    wall-clock and peak factors, so the MFU gap IS the FLOPs gap."""
    from ptype_tpu.models import transformer as tfm

    cfg = tfm.preset(preset)
    t0 = time.perf_counter()
    cost = transformer_grads_cost(cfg, batch, seq)
    analytic = tfm.flops_per_token(cfg, seq)
    compiled = cost["flops_per_token"]
    return {
        "preset": preset, "batch": batch, "seq": seq,
        "compiled_flops_per_token": round(compiled, 1),
        "analytic_flops_per_token": round(analytic, 1),
        "mfu_gap_pct": round(100.0 * (compiled - analytic) / analytic,
                             2),
        "bytes_per_token": round(
            cost["bytes_accessed"] / cost["tokens_per_step"], 1),
        "compile_s": round(time.perf_counter() - t0, 2),
    }


# --------------------------------------------------- host-side summaries


def summarize(profile_dir: str, top: int = 12) -> dict:
    """Host-side artifact summary — stdlib-only (gzip+json over the
    ``*.trace.json.gz`` Chrome trace jax writes beside the
    ``.xplane.pb``), so it works on CPU test runs with no TensorBoard.

    Returns ``{"dir", "files", "events", "top_ops":
    [{"name", "total_us", "count"}, ...]}`` — top ops by total
    duration. Directories with only an ``.xplane.pb`` (some backends)
    still get the file inventory."""
    files = artifact_files(profile_dir)
    totals: dict[str, list] = {}
    n_events = 0
    for f in files:
        if not f["path"].endswith(".trace.json.gz"):
            continue
        try:
            with gzip.open(os.path.join(profile_dir, f["path"]),
                           "rt", encoding="utf-8") as fp:
                doc = json.load(fp)
        except (OSError, ValueError):
            continue
        for ev in doc.get("traceEvents", ()):
            if ev.get("ph") != "X":
                continue
            n_events += 1
            name = str(ev.get("name", "?"))
            acc = totals.setdefault(name, [0.0, 0])
            acc[0] += float(ev.get("dur", 0.0))
            acc[1] += 1
    top_ops = [{"name": name, "total_us": round(us, 1), "count": n}
               for name, (us, n) in sorted(
                   totals.items(), key=lambda kv: -kv[1][0])[:top]]
    return {"dir": profile_dir, "files": files, "events": n_events,
            "top_ops": top_ops}


def render_hbm_table(memory: dict) -> str:
    """One-line-per-device HBM table from a :func:`memory_snapshot`
    dict (the ``obs profile`` CLI's printer feeds this to stdout)."""
    lines = []
    for dev in memory.get("devices", ()):
        stats = dev.get("stats", {})
        if stats:
            used = stats.get("bytes_in_use", 0) / 2**20
            peak = stats.get("peak_bytes_in_use", 0) / 2**20
            limit = stats.get("bytes_limit", 0) / 2**20
            lines.append(
                f"  dev{dev['id']} {dev.get('kind') or dev['platform']}:"
                f" {used:.1f} MiB in use (peak {peak:.1f}"
                + (f" / limit {limit:.0f})" if limit else ")"))
        else:
            lines.append(
                f"  dev{dev['id']} {dev.get('kind') or dev['platform']}:"
                f" no allocator stats (host RSS below)")
    host = memory.get("host", {})
    if host.get("rss_bytes"):
        lines.append(f"  host rss: {host['rss_bytes'] / 2**20:.1f} MiB")
    return "\n".join(lines)
