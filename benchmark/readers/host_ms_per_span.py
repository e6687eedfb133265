from benchmark import harness, xplane


def innermost_segments(events: list) -> list:
    """One thread's spans ([name, start, dur], properly nested) as
    disjoint [start, end, name] pieces, each named by the innermost
    span covering it."""
    out, stack = [], []  # stack of [end, name]
    cur = None

    def emit(upto):
        nonlocal cur
        if stack and cur is not None and upto > cur:
            out.append([cur, upto, stack[-1][1]])
        cur = upto

    for name, s, d in sorted((e for e in events if e[2] > 0),
                             key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append([s + d, name])
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def idle_by_innermost(tr: dict, per: str) -> dict:
    """Device 0's idle nanoseconds inside the window by the innermost
    of the program's own spans (those that share ``per``'s first name
    part, ``serve.``) on the thread that opens ``per`` spans, the
    engine loop: what the host was doing in each of the device's gaps."""
    lo, hi = xplane.window(tr)
    busy = xplane.busy_by_device(tr, lo, hi)
    acc: dict[str, int] = {}
    if not busy:
        return acc
    idle = xplane.gaps(busy[0], lo, hi)
    family = per.split(".")[0] + "."
    for p in tr["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            if not any(e[0] == per for e in ln["events"]):
                continue
            segs = innermost_segments(
                [e for e in ln["events"] if e[0].startswith(family)])
            j = 0
            for a, b, name in segs:  # both sorted and disjoint
                while j < len(idle) and idle[j][1] <= a:
                    j += 1
                k = j
                while k < len(idle) and idle[k][0] < b:
                    ns = min(b, idle[k][1]) - max(a, idle[k][0])
                    if ns > 0:
                        acc[name] = acc.get(name, 0) + ns
                    k += 1
    return acc


def read(ctx, spans: list, per: str):
    """Host milliseconds inside any of ``spans`` with no device
    operation running, per span called ``per`` (an engine iteration):
    absolute, so it reads the same when the device step shrinks.
    Nothing unless every one of ``spans`` is in the trace. The split of
    the device's idle time by innermost span goes to the log."""
    if ctx["trace"] is None:
        return None
    tr = ctx["trace"]
    lo, hi = xplane.window(tr)
    host = xplane.host_events(tr)
    named = {s: xplane.intervals(host, lo, hi, lambda n, s=s: n == s)
             for s in {*spans, per}}
    if not all(named.values()):
        return None
    inside = xplane.union([iv for s in spans for iv in named[s]])
    busy = xplane.union([iv for b in xplane.busy_by_device(tr, lo, hi)
                         for iv in b])
    n = len(named[per])
    split = {k: round(v / n / 1e6, 4) for k, v in sorted(
        idle_by_innermost(tr, per).items(), key=lambda kv: -kv[1])}
    ctx["notes"]["idle_ms_per_iter_by_innermost_span"] = split
    harness.log(f"device idle, ms per {per}, by innermost host span: "
                f"{split}")
    return xplane.total(xplane.subtract(inside, busy)) / n / 1e6
