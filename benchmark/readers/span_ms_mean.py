from benchmark import xplane


def read(ctx, span: str):
    """Mean duration (ms) of the host spans called ``span`` that start
    inside the window."""
    if ctx["trace"] is None:
        return None
    lo, hi = xplane.window(ctx["trace"])
    durs = [d for n, s, d in xplane.host_events(ctx["trace"])
            if n == span and lo <= s < hi]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e6
