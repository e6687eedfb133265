"""Cluster telemetry pull plane: aggregate every node's observability.

One process's view lives in :func:`ptype_tpu.trace.telemetry` (metrics
snapshot + recent spans), served by every :class:`ActorServer` as the
built-in ``ptype.Telemetry`` endpoint. This module is the fleet-wide
half:

- :func:`cluster_snapshot` walks the registry and pulls every node's
  telemetry over its existing actor RPC surface — the observability
  plane needs no new server, no sidecar, no push pipeline;
- :func:`stitch_traces` merges the per-node span lists into connected
  traces keyed by ``trace_id`` (the cross-process record the wire
  propagation in rpc.py / coord/wire.py exists to produce);
- :func:`chrome_trace` / :func:`write_chrome_trace` emit Chrome
  trace-event JSON — load the file in Perfetto (ui.perfetto.dev) or
  ``chrome://tracing`` and every process's spans land on one
  wall-clock timeline, stitched by trace id;
- :func:`write_spans_jsonl` is the grep/jq tier (one span per line);
- :func:`render_summary` is the operator one-pager behind
  ``python -m ptype_tpu obs`` and ``make obs-demo``;
- :func:`cluster_profile` is the profiling-plane sibling (ISSUE 8): a
  simultaneous ``jax.profiler`` XPlane capture across every node via
  the built-in ``ptype.Profile`` endpoint, artifacts shipped back and
  written per node — ``python -m ptype_tpu obs profile``.
"""

from __future__ import annotations

import json
import os
import time

from ptype_tpu import logs
from ptype_tpu.registry import Node, Registry

log = logs.get_logger("telemetry")

#: Per-node budget for the telemetry pull (dial + one Info-sized RPC).
DEFAULT_NODE_TIMEOUT_S = 3.0


def node_telemetry(node: Node, timeout: float = DEFAULT_NODE_TIMEOUT_S,
                   span_limit: int = 256) -> dict:
    """Pull one node's telemetry over its actor RPC surface."""
    from ptype_tpu import rpc as rpc_mod

    conn = rpc_mod._dial(node, dial_timeout=timeout)
    try:
        fut = conn.call_async("ptype.Telemetry", (span_limit,))
        return fut.result(timeout=timeout)
    finally:
        conn.close()


def cluster_snapshot(registry: Registry, services: list[str] | None = None,
                     timeout: float = DEFAULT_NODE_TIMEOUT_S,
                     span_limit: int = 256,
                     include_local: bool = True) -> dict:
    """Walk the registry and merge every node's telemetry.

    Returns ``{"ts", "nodes": {service/addr: telemetry},
    "errors": {service/addr: why}, "traces": {trace_id: [span, ...]}}``.
    Nodes that are registered but not actor servers (bare mesh members)
    land in ``errors`` — a partial snapshot of a degraded fleet is the
    point, so per-node failures never fail the walk. With
    ``include_local`` the calling process contributes its own telemetry
    under the key ``local`` (the aggregator is usually also the
    interesting client — its gateway/client spans stitch the fleet's
    server spans together).
    """
    from concurrent.futures import ThreadPoolExecutor

    out: dict = {"ts": round(time.time(), 3), "nodes": {}, "errors": {}}
    svc_map = registry.services()
    targets: list[tuple[str, Node]] = []
    for service in sorted(svc_map):
        if services is not None and service not in services:
            continue
        for node in svc_map[service]:
            targets.append((f"{service}/{node.address}:{node.port}", node))
    if targets:
        # Concurrent pulls (same reason the gateway's probe rounds are
        # concurrent): a degraded fleet is exactly when obs runs, and a
        # serial walk pays every blackholed node's dial timeout
        # additively instead of ~once.
        with ThreadPoolExecutor(
                max_workers=min(16, len(targets))) as pool:
            futs = {key: pool.submit(node_telemetry, node,
                                     timeout=timeout,
                                     span_limit=span_limit)
                    for key, node in targets}
        for key, fut in futs.items():
            try:
                out["nodes"][key] = fut.result()
            except Exception as e:  # noqa: BLE001 — partial is the point
                out["errors"][key] = f"{type(e).__name__}: {e}"
    if include_local:
        from ptype_tpu import trace

        out["nodes"]["local"] = trace.telemetry(span_limit=span_limit)
    out["traces"] = stitch_traces(all_spans(out))
    return out


def all_spans(snapshot: dict) -> list[dict]:
    """Every span in a snapshot, tagged with its node key and deduped
    by span id — several registry endpoints can share one process (and
    therefore one flight recorder), and a span must appear once per
    trace no matter how many service names its process serves under.
    The node key is ``<pid>``-qualified so one process is one Perfetto
    row, not one row per service alias."""
    spans: list[dict] = []
    seen: set[str] = set()
    #: pid → first node key seen for it: one process, one label.
    labels: dict = {}
    for key, telem in snapshot.get("nodes", {}).items():
        pid = telem.get("pid")
        label = labels.setdefault(pid, key) if pid else key
        for sp in telem.get("spans", ()):
            sid = sp.get("span_id", "")
            if sid in seen:
                continue
            seen.add(sid)
            spans.append({**sp, "node": label})
    return spans


def stitch_traces(spans: list[dict]) -> dict[str, list[dict]]:
    """Group spans into traces by ``trace_id``, each sorted by start
    time — the cross-process request record, reassembled."""
    traces: dict[str, list[dict]] = {}
    for sp in spans:
        traces.setdefault(sp.get("trace_id", "?"), []).append(sp)
    for tid in traces:
        traces[tid].sort(key=lambda s: s.get("start_s", 0.0))
    return traces


# ---------------------------------------------------- cluster profiling


def node_profile(node: Node, duration_s: float = 0.5,
                 timeout: float | None = None,
                 include_data: bool = True, label: str = "cluster",
                 dial_timeout: float = DEFAULT_NODE_TIMEOUT_S) -> dict:
    """One node's ``ptype.Profile`` capture over its actor surface:
    start an XPlane capture, run ``duration_s``, stop, and ship the
    artifact bytes + HBM snapshot back in the reply. Shared by
    :func:`cluster_profile` and the health plane's alert-triggered
    capture (``label="alert"``) — one dial/capture/ship sequence."""
    from ptype_tpu import rpc as rpc_mod

    timeout = (duration_s + 15.0) if timeout is None else timeout
    conn = rpc_mod._dial(node, dial_timeout=dial_timeout)
    try:
        fut = conn.call_async(
            "ptype.Profile",
            ("capture", {"duration_s": duration_s, "label": label,
                         "include_data": include_data}))
        return fut.result(timeout=timeout)
    finally:
        conn.close()


def cluster_profile(registry: Registry, duration_s: float = 0.5,
                    out_dir: str = ".",
                    services: list[str] | None = None,
                    timeout: float | None = None) -> dict:
    """Simultaneous device-profile capture across every registered
    node (ISSUE 8): every node's ``ptype.Profile`` endpoint starts its
    capture concurrently, so the per-node XPlane timelines cover ONE
    overlapping wall-clock window — and because ``metrics.annotate``
    feeds both the profiler and the distributed-trace plane, the
    ``train.step`` / ``store.push*`` regions in each device timeline
    line up with the same regions in the stitched span view
    (:func:`cluster_snapshot`).

    Artifacts land under ``out_dir/<service_addr_port>/`` per node
    (XPlane ``.pb`` + the host-parseable ``.trace.json.gz`` —
    :func:`ptype_tpu.health.profiling.summarize` reads the latter with
    no TensorBoard). Returns ``{"ts", "duration_s", "nodes":
    {key: {"dir", "files", "memory"}}, "errors": {key: why}}`` — like
    the telemetry pull, a partial capture of a degraded fleet is the
    point, so per-node failures (dead node, profiler already busy)
    never fail the walk.
    """
    from concurrent.futures import ThreadPoolExecutor

    from ptype_tpu.health import profiling

    out: dict = {"ts": round(time.time(), 3),
                 "duration_s": float(duration_s),
                 "nodes": {}, "errors": {}}
    svc_map = registry.services()
    targets: list[tuple[str, Node]] = []
    for service in sorted(svc_map):
        if services is not None and service not in services:
            continue
        for node in svc_map[service]:
            targets.append((f"{service}/{node.address}:{node.port}", node))
    if targets:
        # Concurrent on purpose — simultaneity IS the feature: the
        # fleet's captures must cover one shared window or cross-node
        # comparisons (who stalls while whose reduce runs) mean
        # nothing. One thread per node (they are I/O-bound waiters):
        # a 16-worker cap would queue the overflow into a LATER,
        # non-overlapping window and silently void that contract.
        with ThreadPoolExecutor(max_workers=len(targets)) as pool:
            futs = {key: pool.submit(node_profile, node,
                                     duration_s=duration_s,
                                     timeout=timeout)
                    for key, node in targets}
    else:
        futs = {}
    for key, fut in futs.items():
        try:
            result = fut.result()
        except Exception as e:  # noqa: BLE001 — partial is the point
            out["errors"][key] = f"{type(e).__name__}: {e}"
            continue
        node_dir = os.path.join(
            out_dir, key.replace("/", "_").replace(":", "_"))
        files = profiling.write_artifacts(node_dir, result)
        out["nodes"][key] = {
            "dir": node_dir,
            "files": [os.path.relpath(p, node_dir) for p in files],
            "remote_dir": result.get("dir"),
            "capture_s": result.get("duration_s"),
            "memory": result.get("memory"),
        }
    return out


# ------------------------------------------------------------- exporters


def chrome_trace(spans: list[dict]) -> dict:
    """Chrome trace-event JSON (the ``traceEvents`` array format) from
    span dicts — loadable in Perfetto / chrome://tracing.

    Spans become complete (``ph: X``) events on their process's row
    (grouped by the originating pid — several registry service names
    can alias one process); span events become instants (``ph: i``);
    every event
    carries ``trace_id``/``span_id``/``parent_id`` in ``args`` so a
    request can be followed across process rows by its trace id.
    Timestamps are the spans' wall-clock microseconds: processes share
    one timeline, which is what makes the stitched view readable.
    """
    events: list[dict] = []
    pids: dict[str, int] = {}
    for sp in spans:
        node = str(sp.get("node", sp.get("pid", "local")))
        pid = pids.setdefault(node, len(pids) + 1)
        tid = int(sp.get("tid", 0)) % 1_000_000
        ts_us = sp.get("start_s", 0.0) * 1e6
        args = {"trace_id": sp.get("trace_id"),
                "span_id": sp.get("span_id"),
                "parent_id": sp.get("parent_id"),
                "status": sp.get("status", "ok")}
        args.update(sp.get("attrs", {}))
        events.append({
            "ph": "X", "name": sp.get("name", "?"),
            "cat": sp.get("status", "ok"),
            "ts": ts_us, "dur": max(sp.get("dur_s", 0.0) * 1e6, 1.0),
            "pid": pid, "tid": tid, "args": args,
        })
        for ev in sp.get("events", ()):
            events.append({
                "ph": "i", "s": "t",
                "name": ev.get("name", "event"),
                "ts": ts_us + ev.get("t", 0.0) * 1e6,
                "pid": pid, "tid": tid,
                "args": {**ev.get("attrs", {}),
                         "trace_id": sp.get("trace_id"),
                         "span_id": sp.get("span_id")},
            })
    for node, pid in pids.items():
        events.append({"ph": "M", "name": "process_name", "pid": pid,
                       "tid": 0, "args": {"name": node}})
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, snapshot_or_spans) -> str:
    """Write a snapshot's (or bare span list's) Chrome trace to
    ``path``; returns the path."""
    spans = (all_spans(snapshot_or_spans)
             if isinstance(snapshot_or_spans, dict) else snapshot_or_spans)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(chrome_trace(spans), f, separators=(",", ":"))
    return path


def write_spans_jsonl(path: str, snapshot_or_spans) -> str:
    """One span dict per line — the grep/jq tier."""
    spans = (all_spans(snapshot_or_spans)
             if isinstance(snapshot_or_spans, dict) else snapshot_or_spans)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        for sp in spans:
            f.write(json.dumps(sp, separators=(",", ":")) + "\n")
    return path


def render_summary(snapshot: dict) -> str:
    """Operator one-pager: per-node span/metric counts and the stitched
    trace inventory (what ``python -m ptype_tpu obs`` prints)."""
    lines = [f"cluster telemetry @ {snapshot.get('ts')}"]
    nodes = snapshot.get("nodes", {})
    lines.append(f"nodes: {len(nodes)}  "
                 f"unreachable: {len(snapshot.get('errors', {}))}")
    for key in sorted(nodes):
        t = nodes[key]
        m = t.get("metrics", {})
        lines.append(
            f"  {key}: pid={t.get('pid')} tracing={t.get('tracing')} "
            f"spans={len(t.get('spans', ()))} "
            f"(finished {t.get('spans_finished', 0)}) "
            f"counters={len(m.get('counters', {}))} "
            f"timings={len(m.get('timings', {}))} "
            f"gauges={len(m.get('gauges', {}))} "
            f"histograms={len(m.get('histograms', {}))}")
    for key in sorted(snapshot.get("errors", {})):
        lines.append(f"  {key}: UNREACHABLE "
                     f"({snapshot['errors'][key]})")
    traces = snapshot.get("traces", {})
    multi = {tid: sp for tid, sp in traces.items()
             if len({s.get("node") for s in sp}) > 1}
    lines.append(f"traces: {len(traces)} "
                 f"({len(multi)} spanning multiple nodes)")
    for tid, spans in sorted(traces.items(),
                             key=lambda kv: -len(kv[1]))[:8]:
        names = " → ".join(s.get("name", "?") for s in spans[:6])
        more = f" (+{len(spans) - 6})" if len(spans) > 6 else ""
        lines.append(f"  {tid[:16]}…: {len(spans)} spans: {names}{more}")
    return "\n".join(lines)


# ------------------------------------------------------- OpenMetrics


def _om_name(name: str) -> str:
    """Metric-name sanitization: ``gateway.llm.stage_ms.queue-wait``
    → ``gateway_llm_stage_ms_queue_wait`` (OpenMetrics charset)."""
    out = []
    for ch in name:
        out.append(ch if (ch.isalnum() or ch == "_") else "_")
    s = "".join(out)
    if s and s[0].isdigit():
        s = "_" + s
    return s


def _om_labels(labels: dict | None, extra: str = "") -> str:
    parts = [f'{_om_name(k)}="{v}"' for k, v in (labels or {}).items()]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def openmetrics(source, labels: dict | None = None) -> str:
    """Render metrics as OpenMetrics text — the scrape format every
    standard collector speaks, so a long soak needs no bespoke reader.

    ``source`` is a :class:`~ptype_tpu.metrics.MetricsRegistry`, one
    process's ``snapshot()`` dict, or a full :func:`cluster_snapshot`
    (each node rendered with a ``node`` label). Counters render as
    ``_total`` samples, gauges as gauges, timings and histograms as
    quantile-labelled summaries; a histogram's worst trace-linked
    exemplar rides its ``quantile="0.99"`` sample in OpenMetrics
    exemplar syntax (``# {trace_id="..."} value``) — the p99 line
    literally names the trace to pull with ``obs request``."""
    snap = source.snapshot() if hasattr(source, "snapshot") else source
    lines: list[str] = []
    if "nodes" in snap and "counters" not in snap:
        for key in sorted(snap["nodes"]):
            m = snap["nodes"][key].get("metrics", {})
            node_labels = dict(labels or {})
            node_labels["node"] = key
            _om_family(lines, m, node_labels)
    else:
        _om_family(lines, snap, labels)
    lines.append("# EOF")
    return "\n".join(lines) + "\n"


def _om_family(lines: list, snap: dict, labels: dict | None) -> None:
    lab = _om_labels(labels)
    for name, v in sorted((snap.get("counters") or {}).items()):
        om = _om_name(name)
        lines.append(f"# TYPE {om} counter")
        lines.append(f"{om}_total{lab} {v}")
    for name, v in sorted((snap.get("gauges") or {}).items()):
        om = _om_name(name)
        lines.append(f"# TYPE {om} gauge")
        lines.append(f"{om}{lab} {v}")
    for name, s in sorted((snap.get("timings") or {}).items()):
        om = _om_name(name)
        lines.append(f"# TYPE {om} summary")
        for q, key in (("0.5", "p50_s"), ("0.95", "p95_s"),
                       ("0.99", "p99_s")):
            qlab = _om_labels(labels, 'quantile="%s"' % q)
            lines.append(f"{om}{qlab} {s.get(key, 0.0)}")
        lines.append(f"{om}_count{lab} {s.get('count', 0)}")
    for name, s in sorted((snap.get("histograms") or {}).items()):
        om = _om_name(name)
        lines.append(f"# TYPE {om} summary")
        exemplars = s.get("exemplars") or []
        for q, key in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
            qlab = _om_labels(labels, 'quantile="%s"' % q)
            line = f"{om}{qlab} {s.get(key, 0.0)}"
            if q == "0.99" and exemplars:
                ex = exemplars[0]  # worst-first
                line += (' # {trace_id="%s"} %s %s'
                         % (ex["trace_id"], ex["value"],
                            ex.get("ts", 0.0)))
            lines.append(line)
        lines.append(f"{om}_count{lab} {s.get('count', 0)}")
