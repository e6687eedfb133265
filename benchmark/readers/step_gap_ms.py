from benchmark import harness, xplane


def read(ctx, pattern: str, line: str = "XLA Modules"):
    """Median gap between the device starts of consecutive executions of
    the step's program (device 0)."""
    if ctx["trace"] is None:
        return None
    starts = xplane.op_starts(ctx["trace"], pattern, line)
    if len(starts) < 3:
        return None
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    return harness.quantile(gaps, 0.5) * 1e3
