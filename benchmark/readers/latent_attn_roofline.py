import itertools
import re

from benchmark import family, harness, xplane, xstats
from benchmark.readers.named_scope_time_pct import innermost
from benchmark.xstats import _MODULE

SPAN = "serve.step/dispatch"


def scoped_extent_ns(xs: dict, lo: int, hi: int, program: str, known,
                     scopes) -> dict | None:
    """Device time of one program's stretches inside ``scopes``, gaps
    included. The program's operations in start order, each under the
    innermost of ``known`` on its path: a stretch is a run of
    operations inside ``scopes`` that no operation under another known
    name interrupts (one under none of them, a compiler's copy or a
    loop's counter, neither opens nor closes a stretch), and it lasts
    from its first operation's start to its last one's end. So a
    loop's trips count with the time between their operations, which
    the sum of the operations' own times leaves out. →
    ``{"extent_ns", "ops_ns", "stretches", "runs"}`` inside [lo, hi)
    (``runs``: the program's executions that start there and whose
    operations the line holds), or None where it holds none."""
    known, scopes = frozenset(known), frozenset(scopes)
    rx = re.compile(program)
    out = {"extent_ns": 0, "ops_ns": 0, "stretches": 0, "runs": 0}
    for plane in xs["planes"]:
        if not xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        ids, starts, held_to = set(), [], lo
        for name, s, d, _st in xplane.line_events(plane,
                                                  xplane.MODULES_LINE):
            m = _MODULE.match(name)
            if m and rx.search(name):
                ids.add(int(m.group(2)))
                starts.append(s)
        scope_of, ops = {}, []
        for name, s, d, st in xplane.line_events(plane, xplane.OPS_LINE):
            pid = st.get("program_id")
            if (pid is None or int(pid) % (1 << 64) not in ids
                    or xplane.CONTAINER.match(name)):
                continue
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            held_to = max(held_to, b)
            path = str(st.get("tf_op", ""))
            if path not in scope_of:
                scope_of[path] = innermost(path, known)
            if scope_of[path] is not None:
                ops.append((a, b, scope_of[path] in scopes))
        out["runs"] += sum(lo <= s < held_to for s in starts)
        ops.sort()
        first = last = None
        for a, b, inside in itertools.chain(ops, [(hi, hi, False)]):
            if inside:
                out["ops_ns"] += b - a
                first = a if first is None else first
                last = b if last is None else max(last, b)
            elif first is not None:
                out["extent_ns"] += last - first
                out["stretches"] += 1
                first = last = None
    return out if out["runs"] else None


def read(ctx, cell: str, program: str, known, scopes):
    """The floor of the decode steps' latent attention ÷ the device
    time it took. The floor is the larger of bytes ÷ HBM rate and
    FLOPs ÷ peak, from the family's own count
    (``latent_attention_work``: every live row's every cached row read
    once a layer, scored and summed by every head in the absorbed
    form) over the contexts the window's steps saw: each step's
    ``serve.step/dispatch`` span says how many blocks its live rows
    held (``kv_blocks``; a context counts to the end of its last
    block). A long window's trace can end before the window does (the
    profiler keeps so many operations and no more: a 40-s window of
    this cell holds some twenty seconds' worth), so the steps counted
    are the first as many as the device's line holds executions of
    ``program`` with their operations, and the log says how many of
    how many. The time is the device's from the first to the last
    operation of each stretch of ``program`` inside ``scopes`` (the
    innermost of ``known`` takes an operation): ``scoped_extent_ns``,
    so the time between a loop's operations is in it, and a kernel that
    takes the loop's place is held to what the loop really cost. A
    kernel in the attention's place is read by the same metric: it is
    its roofline. The operations' own time goes to the log. Nothing
    where the family counts no latent attention, the spans carry no
    blocks, or the program did not run."""
    if ctx["trace"] is None or not ctx.get("peaks"):
        return None
    count = getattr(family.of(ctx["cfg"]), "latent_attention_work", None)
    if count is None:
        return None
    lo, hi = xplane.window(ctx["trace"])
    xs = xstats.for_cell(ctx, cell)
    got = scoped_extent_ns(xs, lo, hi, program, known, scopes)
    if got is None or not got["extent_ns"]:
        return None
    steps = sorted(xstats.host_events(xs, lo, hi, SPAN), key=lambda e: e[1])
    blocks = sum(int(e[3].get("kv_blocks", 0))
                 for e in steps[:got["runs"]])
    if not blocks:
        return None
    tokens = blocks * int(ctx["mix"]["engine"]["block_tokens"])
    nbytes, flops = count(ctx["cfg"], [tokens])
    floor_s = max(nbytes / ctx["peaks"]["hbm_bytes_per_s"],
                  flops / ctx["peaks"]["bf16_flops"])
    harness.log(f"latent attention: the trace holds {got['runs']} of "
                f"{len(steps)} dispatched steps; floor {floor_s:.4f} s "
                f"against {got['extent_ns'] * 1e-9:.4f} s in "
                f"{got['stretches']} stretches, of which operations "
                f"{got['ops_ns'] * 1e-9:.4f}")
    return 100.0 * floor_s / (got["extent_ns"] * 1e-9)
