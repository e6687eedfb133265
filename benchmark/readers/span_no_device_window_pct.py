from benchmark import xplane


def read(ctx, span: str, beside: str):
    """Time inside the host spans called ``span`` with no device
    operation running, as a share of the window: with ``span`` the
    engine's wait for work (``serve.idle``), the part of
    ``device_idle_pct`` that is the load's; the rest is the host's.
    0 where the program never opened the span in the window; nothing
    where it cannot have (it left no ``beside`` event either, which a
    program that has the span leaves whenever it works)."""
    if ctx["trace"] is None:
        return None
    tr = ctx["trace"]
    if not any(n in (span, beside) for n, _, _ in xplane.host_events(tr)):
        return None
    lo, hi = xplane.window(tr)
    return (100.0 * xplane.span_seconds(tr, span)["no_device_seconds"]
            / ((hi - lo) / 1e9))
