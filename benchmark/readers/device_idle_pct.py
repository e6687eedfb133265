from benchmark import xplane


def read(ctx):
    """1 − union of device-op intervals ÷ window, on the device that
    idles most."""
    if ctx["trace"] is None:
        return None
    return xplane.busy_and_idle(ctx["trace"])["idle_pct_fullest_idle"]
