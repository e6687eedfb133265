from benchmark import xplane


def read(ctx, flops_key: str, basis: str = "window"):
    """Model FLOPs of the work done in the traced window ÷ (seconds ×
    chips × peak). ``basis``: the whole window, or only the seconds in
    which the device ran something."""
    if ctx["trace"] is None:
        return None
    flops = ctx["counters"].get(flops_key)
    if not flops:
        return None
    bi = xplane.busy_and_idle(ctx["trace"])
    secs = bi["window_s"] if basis == "window" else bi["busy_s"]
    return 100.0 * flops / (secs * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
