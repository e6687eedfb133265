"""What ``xplane.read`` drops: an event's stats.

``jax.profiler.ProfileData`` hands out the stats stored on an event (a
``TraceAnnotation``'s metadata, ``device_offset_ps``) but not the ones the
TPU's profiler stores once per *kind* of event, on the event's metadata:
``tf_op`` (the HLO instruction's ``op_name``, which holds the program's
``jax.named_scope`` path), ``bytes_accessed``, ``flops``, ``program_id``.
So this module reads the ``.xplane.pb`` itself: the few fields of the
XSpace message it needs, straight off the protobuf wire format (varints
and length-delimited fields; no generated class is installed without
TensorFlow). Times are on the clock ``xplane.read`` uses: the line's
``timestamp_ns`` plus the event's ``offset_ps``.

``read`` gives a plain structure, so the tests build one by hand::

    {"planes": [{"name", "lines": [{"name", "events":
        [[name, start_ns, dur_ns, stats], ...]}]}]}

where ``stats`` holds the event's own stats over its metadata's."""

from __future__ import annotations

import re
import struct


def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes):
    """(field number, wire type, value) of one message's top level; a
    length-delimited value is a memoryview-free bytes slice."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wt == 1:
            v = b[i:i + 8]
            i += 8
        elif wt == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")
        yield num, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(b: bytes, stat_names: dict) -> tuple[str, object]:
    """One XStat -> (its name, its value); a ``ref_value`` names a string
    kept in the plane's stat metadata."""
    key, value = 0, None
    for num, wt, v in _fields(b):
        if num == 1:
            key = v
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = v.decode("utf-8", "replace")
        elif num == 6:
            value = bytes(v)
        elif num == 7:
            value = stat_names.get(v, "")
    return stat_names.get(key, str(key)), value


def _map_entry(b: bytes) -> bytes:
    """The value of one map<int64, message> entry."""
    for num, _wt, v in _fields(b):
        if num == 2:
            return v
    return b""


def _plane(b: bytes, want_line) -> dict:
    name, lines, raw_meta, stat_names = "", [], [], {}
    for num, _wt, v in _fields(b):
        if num == 2:
            name = v.decode("utf-8", "replace")
        elif num == 3:
            lines.append(v)
        elif num == 4:
            raw_meta.append(_map_entry(v))
        elif num == 5:
            sid, sname = 0, ""
            for n2, _w2, v2 in _fields(_map_entry(v)):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = v2.decode("utf-8", "replace")
            stat_names[sid] = sname
    meta: dict[int, tuple[str, dict]] = {}
    for m in raw_meta:
        mid, mname, stats = 0, "", {}
        for n2, _w2, v2 in _fields(m):
            if n2 == 1:
                mid = v2
            elif n2 == 2:
                mname = v2.decode("utf-8", "replace")
            elif n2 == 5:
                k, val = _stat(v2, stat_names)
                stats[k] = val
        meta[mid] = (mname, stats)
    out_lines = []
    for ln in lines:
        lname, t0, evs = "", 0, []
        for n2, _w2, v2 in _fields(ln):
            if n2 == 2:
                lname = v2.decode("utf-8", "replace")
            elif n2 == 3:
                t0 = v2
            elif n2 == 4:
                evs.append(v2)
        if not want_line(name, lname):
            continue
        events = []
        for e in evs:
            mid = off = dur = 0
            own = None
            for n3, _w3, v3 in _fields(e):
                if n3 == 1:
                    mid = v3
                elif n3 == 2:
                    off = v3
                elif n3 == 3:
                    dur = v3
                elif n3 == 4:
                    k, val = _stat(v3, stat_names)
                    if own is None:
                        own = {}
                    own[k] = val
            mname, mstats = meta.get(mid, ("", {}))
            stats = mstats if own is None else {**mstats, **own}
            events.append([mname, t0 + off // 1000, dur // 1000, stats])
        out_lines.append({"name": lname, "events": events})
    return {"name": name, "lines": out_lines}


def read(path: str, want_line=lambda plane, line: True) -> dict:
    """The planes of an ``.xplane.pb`` with every event's stats.
    ``want_line(plane name, line name)`` leaves lines out unread (a
    device's "XLA Ops" line holds hundreds of thousands of events)."""
    with open(path, "rb") as f:
        b = f.read()
    return {"planes": [_plane(v, want_line)
                       for num, _wt, v in _fields(b) if num == 1]}


# ------------------------------------------------------------ reductions

#: The program's ``jax.named_scope`` names (ptype_tpu/models/transformer.py
#: lists them): the parts a step's device time is split into.
SCOPES = ("embed", "qkv", "kv_write", "kv_gather", "attn", "attn_out",
          "mlp", "head", "sample", "loss", "optimizer")
#: One component of an op_name path, with the transformations JAX wraps
#: around a scope on the backward pass: ``transpose(jvp(mlp))`` -> mlp.
_COMPONENT = re.compile(r"^(?:\w+\()*([\w.\-]*)\)*$")
_MODULE = re.compile(r"^(\S+)\((\d+)\)$")


def for_cell(ctx: dict, cell: str) -> dict:
    """The traced run's planes with stats, read once a run (the device's
    "XLA Ops" and "XLA Modules" lines and every host line) and kept in
    ``ctx``; a test puts a hand-made structure there. No planes where
    ``cell`` left no trace (a metric file that names another cell)."""
    if "xstats" not in ctx:
        from benchmark import harness, xplane

        try:
            path = xplane.find_xplane(harness.trace_dir_for(cell))
        except FileNotFoundError as e:
            harness.log(f"no stats: {e}")
            ctx["xstats"] = {"planes": []}
            return ctx["xstats"]
        ctx["xstats"] = read(
            path, lambda plane, line: plane.startswith("/host:")
            or (xplane.DEVICE_PLANE.match(plane) is not None
                and line in (xplane.OPS_LINE, xplane.MODULES_LINE)))
    return ctx["xstats"]


def scope_of(op_name: str) -> str | None:
    """The innermost of SCOPES on an operation's path, or None."""
    for part in reversed(op_name.rstrip(":").split("/")):
        m = _COMPONENT.match(part)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


def program_ops(xs: dict, lo: int, hi: int, program: str) -> dict | None:
    """Device time of one program inside [lo, hi), over all devices:
    ``{"module_ns", "runs", "by_scope": {scope or None: ns}, "bytes"}``.
    ``program`` is a pattern for the name on the "XLA Modules" line; an
    operation belongs to the program whose ``program_id`` it carries;
    containers (a scan over layers is a ``while``) are left out, as
    their bodies are on the line too. None where the program never ran."""
    from benchmark import xplane

    # Several metrics read one program: one pass over the ops for all.
    memo = xs.setdefault("program_ops", {})
    if (lo, hi, program) not in memo:
        memo[lo, hi, program] = _program_ops(xs, lo, hi, program, xplane)
    return memo[lo, hi, program]


def _program_ops(xs, lo, hi, program, xplane):
    rx = re.compile(program)
    out = {"module_ns": 0, "runs": 0, "by_scope": {}, "bytes": 0}
    for plane in xs["planes"]:
        if not xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        ids = set()
        for name, s, d, _st in xplane.line_events(plane,
                                                  xplane.MODULES_LINE):
            m = _MODULE.match(name)
            if not (m and rx.search(name)):
                continue
            ids.add(int(m.group(2)))
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                out["module_ns"] += b - a
                out["runs"] += 1
        for name, s, d, st in xplane.line_events(plane, xplane.OPS_LINE):
            pid = st.get("program_id")
            if (pid is None or int(pid) % (1 << 64) not in ids
                    or xplane.CONTAINER.match(name)):
                continue
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            sc = scope_of(str(st.get("tf_op", "")))
            out["by_scope"][sc] = out["by_scope"].get(sc, 0) + (b - a)
            out["bytes"] += int(st.get("bytes_accessed", 0) or 0)
    return out if out["runs"] else None


def host_events(xs: dict, lo: int, hi: int, name: str) -> list:
    """The host events called ``name`` that start inside [lo, hi)."""
    return [e for p in xs["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] for e in ln["events"]
            if e[0] == name and lo <= e[1] < hi]
