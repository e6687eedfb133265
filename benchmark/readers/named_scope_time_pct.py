import re

from benchmark import xplane, xstats
from benchmark.xstats import _COMPONENT, _MODULE


def innermost(op_name: str, names) -> str | None:
    """The innermost of ``names`` on an operation's path, or None."""
    for part in reversed(op_name.rstrip(":").split("/")):
        m = _COMPONENT.match(part)
        if m and m.group(1) in names:
            return m.group(1)
    return None


def program_by_scope(xs: dict, lo: int, hi: int, program: str, names
                     ) -> dict | None:
    """Device time of one program inside [lo, hi) split by the
    innermost of ``names`` on each operation's path: ``{"module_ns",
    "by_scope": {name or None: ns}}``. ``xstats.program_ops`` does the
    same for the closed list ``xstats.SCOPES``; this one takes its
    names from the caller, so that a model's new scopes need no edit
    to a shipped file. Containers are left out, as their bodies are on
    the line too. None where the program never ran."""
    names = frozenset(names)
    memo = xs.setdefault("program_by_scope", {})
    key = (lo, hi, program, names)
    if key in memo:
        return memo[key]
    rx = re.compile(program)
    out = {"module_ns": 0, "by_scope": {}}
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
        for name, s, d, st in xplane.line_events(plane, xplane.OPS_LINE):
            pid = st.get("program_id")
            if (pid is None or int(pid) % (1 << 64) not in ids
                    or xplane.CONTAINER.match(name)):
                continue
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                sc = innermost(str(st.get("tf_op", "")), names)
                out["by_scope"][sc] = out["by_scope"].get(sc, 0) + (b - a)
    memo[key] = out if out["module_ns"] else None
    return memo[key]


def read(ctx, cell: str, program: str, known, scopes=(),
         unscoped: bool = False):
    """Device time of the program's operations inside the named scopes
    (``jax.named_scope``; of the names in ``known``, the innermost on
    an operation's path counts) ÷ the program's device time in the
    window. ``known`` is every name the program's time is split by: the
    metric file states it, so the reader knows no model. ``unscoped``:
    the operations inside none of them. Nothing where the program did
    not run or carries none of the names."""
    if ctx["trace"] is None:
        return None
    lo, hi = xplane.window(ctx["trace"])
    got = program_by_scope(xstats.for_cell(ctx, cell), lo, hi, program,
                           known)
    if got is None or not any(got["by_scope"].get(s) for s in known):
        return None
    ns = (got["by_scope"].get(None, 0) if unscoped
          else sum(got["by_scope"].get(s, 0) for s in scopes))
    return 100.0 * ns / got["module_ns"]
