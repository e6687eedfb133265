from benchmark import xplane


def read(ctx, pattern: str):
    """Device time of the matching programs ("XLA Modules" line) ÷
    window, mean over chips."""
    if ctx["trace"] is None:
        return None
    tr = ctx["trace"]
    got = xplane.op_seconds(tr, pattern, xplane.MODULES_LINE)
    if not got["calls"]:
        return None
    w = xplane.busy_and_idle(tr)["window_s"]
    return 100.0 * got["seconds"] / got["devices"] / w
