"""From a profiler trace to numbers: one reduction, kept with the
benchmark, checked on a small recorded trace (tests/).

``read`` turns an ``.xplane.pb`` into a plain structure —
``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}`` — and everything else works on that, so the
recorded trace in the tests is the same structure as JSON."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: A collective by the instruction's own name, at the start of the event's
#: text: the text goes on with the operands, and an operand *named*
#: %all-reduce.3 makes its consumer no collective.
COLLECTIVE = re.compile(
    r"^%(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)")
WINDOW_SPAN = "bench.window"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                   for ev in line.events]
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ----------------------------------------------------------- intervals


def union(iv: list) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out: list = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(iv: list) -> int:
    return sum(b - a for a, b in iv)


def clip(iv: list, lo: int, hi: int) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in iv
            if min(b, hi) > max(a, lo)]


def subtract(iv: list, cover: list) -> list:
    """The parts of ``iv`` (disjoint, sorted) outside ``cover`` (same)."""
    out = []
    j = 0
    for a, b in iv:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append([cur, cover[k][0]])
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def gaps(busy: list, lo: int, hi: int) -> list:
    return subtract([[lo, hi]], busy)


# ------------------------------------------------------------- planes


def device_planes(tr: dict) -> list[dict]:
    return sorted((p for p in tr["planes"] if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))


def host_events(tr: dict) -> list:
    """[name, start, dur] of every event on a host plane."""
    out = []
    for p in tr["planes"]:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                out.extend(ln["events"])
    return out


def line_events(plane: dict, line_name: str) -> list:
    out = []
    for ln in plane["lines"]:
        if ln["name"] == line_name:
            out.extend(ln["events"])
    return out


def window(tr: dict) -> tuple[int, int]:
    """[start, end) of the measured window on the trace's clock: the
    benchmark's own ``bench.window`` annotation on the host."""
    spans = [e for e in host_events(tr) if e[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    _, s, d = max(spans, key=lambda e: e[2])
    return s, s + d


def intervals(events: list, lo: int, hi: int, match=None) -> list:
    return clip([[s, s + d] for n, s, d in events
                 if match is None or match(n)], lo, hi)


# ----------------------------------------------------------- reductions


def busy_by_device(tr: dict, lo: int, hi: int) -> list[list]:
    """Per device plane, the union of the intervals in which an
    operation ran, inside the window."""
    return [union(intervals(line_events(p, OPS_LINE), lo, hi))
            for p in device_planes(tr)]


def busy_and_idle(tr: dict) -> dict:
    lo, hi = window(tr)
    per = [total(b) for b in busy_by_device(tr, lo, hi)]
    if not per:
        raise ValueError("trace holds no device plane")
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(per) / len(per) / 1e9,
            "busy_s_by_device": [b / 1e9 for b in per],
            "idle_pct_fullest_idle": 100.0 * (1 - min(per) / (hi - lo))}


def op_seconds(tr: dict, pattern: str, line: str = OPS_LINE,
               device: int | None = None) -> dict:
    """Device seconds and calls of the events on ``line`` whose name
    matches ``pattern``, inside the window; summed over the devices, or
    on one of them."""
    lo, hi = window(tr)
    rx = re.compile(pattern)
    planes = device_planes(tr)
    if device is not None:
        planes = planes[device:device + 1]
    secs, calls = 0, 0
    for p in planes:
        iv = intervals(line_events(p, line), lo, hi, rx.search)
        secs += total(iv)
        calls += len(iv)
    return {"seconds": secs / 1e9, "calls": calls, "devices": len(planes)}


def op_starts(tr: dict, pattern: str, line: str = MODULES_LINE,
              device: int = 0) -> list[float]:
    """Start times (s) of the matching events on one device, in order."""
    lo, hi = window(tr)
    rx = re.compile(pattern)
    planes = device_planes(tr)
    evs = line_events(planes[device], line) if planes else []
    return sorted(s / 1e9 for n, s, d in evs
                  if rx.search(n) and lo <= s < hi)


def collectives(tr: dict, device: int = 0) -> dict:
    """Seconds the device spent in collective operations, and the part
    of them during which no other operation ran on that device."""
    lo, hi = window(tr)
    planes = device_planes(tr)
    if not planes:
        raise ValueError("trace holds no device plane")
    evs = line_events(planes[device], OPS_LINE)
    coll = union(intervals(evs, lo, hi, COLLECTIVE.search))
    rest = union(intervals(evs, lo, hi,
                           lambda n: not COLLECTIVE.search(n)))
    return {"seconds": total(coll) / 1e9,
            "exposed_seconds": total(subtract(coll, rest)) / 1e9,
            "calls": len(intervals(evs, lo, hi, COLLECTIVE.search))}


def span_seconds(tr: dict, name: str) -> dict:
    """Host seconds inside spans called ``name`` (window-clipped), and
    the part of them with no device operation running on any device."""
    lo, hi = window(tr)
    spans = union(intervals(host_events(tr), lo, hi, lambda n: n == name))
    busy = union([iv for b in busy_by_device(tr, lo, hi) for iv in b])
    return {"seconds": total(spans) / 1e9,
            "no_device_seconds": total(subtract(spans, busy)) / 1e9,
            "spans": len(spans)}


#: Operations that only hold others (a scan over layers is a while): the
#: time inside them is their bodies', which the line lists too.
CONTAINER = re.compile(r"^%(while|conditional|call)[.0-9]* =")


def top_device_ops(tr: dict, n: int = 10, name_chars: int = 160) -> list:
    """The operations that took most device time in the window, mean
    over the chips; [name, seconds], names cut to ``name_chars``."""
    lo, hi = window(tr)
    acc: dict[str, int] = {}
    planes = device_planes(tr)
    for p in planes:
        for name, s, d in line_events(p, OPS_LINE):
            if CONTAINER.match(name):
                continue
            name = name[:name_chars]
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                acc[name] = acc.get(name, 0) + (b - a)
    k = max(1, len(planes))
    return [[name, ns / k / 1e9] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps_by_host_span(tr: dict, n: int = 10,
                           ignore=(WINDOW_SPAN,)) -> list:
    """The device's idle time on device 0, attributed to what the host
    was doing: each gap goes to the host span that overlaps it most (the
    shorter span on a tie, so the innermost wins); [name, seconds]."""
    lo, hi = window(tr)
    busy = busy_by_device(tr, lo, hi)
    if not busy:
        return []
    host = sorted(([s, s + d, name] for name, s, d in host_events(tr)
                   if name not in ignore and d > 0 and s < hi
                   and s + d > lo), key=lambda e: e[0])
    acc: dict[str, int] = {}
    # Gaps come in order of time: a span that ended before one gap has
    # ended before every later one, so only the spans still open (in
    # their order of start) are looked at, not all of them for each gap.
    nxt, open_spans = 0, []
    for a, b in gaps(busy[0], lo, hi):
        while nxt < len(host) and host[nxt][0] < b:
            open_spans.append(host[nxt])
            nxt += 1
        open_spans = [h for h in open_spans if h[1] > a]
        best, best_key = "no host span", (0, 0)
        for s, e, name in open_spans:
            ov = min(e, b) - max(s, a)
            if ov > 0 and (ov, -(e - s)) > best_key:
                best, best_key = name, (ov, -(e - s))
        acc[best] = acc.get(best, 0) + (b - a)
    return [[name, ns / 1e9] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(tr: dict) -> dict:
    return {"device_ops": top_device_ops(tr),
            "idle_gaps": idle_gaps_by_host_span(tr)}


def outline(tr: dict, per_line: int = 12) -> dict:
    """Planes, lines and their commonest event names: what to look at by
    hand before trusting a pattern."""
    out = {}
    for p in tr["planes"]:
        lines = {}
        for ln in p["lines"]:
            acc: dict[str, list] = {}
            for name, s, d in ln["events"]:
                a = acc.setdefault(name, [0, 0])
                a[0] += 1
                a[1] += d
            top = sorted(acc.items(), key=lambda kv: -kv[1][1])[:per_line]
            lines[ln["name"]] = {"events": len(ln["events"]),
                                 "top": [[k, v[0], v[1] / 1e9]
                                         for k, v in top]}
        out[p["name"]] = lines
    return out
