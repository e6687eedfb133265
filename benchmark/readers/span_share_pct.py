from benchmark import xplane


def read(ctx, span: str, inside: str):
    """Share of the wall time of host spans called ``inside`` that is
    spent in spans called ``span`` (its children)."""
    if ctx["trace"] is None:
        return None
    tr = ctx["trace"]
    lo, hi = xplane.window(tr)
    host = xplane.host_events(tr)
    part = xplane.total(xplane.union(
        xplane.intervals(host, lo, hi, lambda n: n == span)))
    whole = xplane.total(xplane.union(
        xplane.intervals(host, lo, hi, lambda n: n == inside)))
    if not part or not whole:
        return None
    return 100.0 * part / whole
