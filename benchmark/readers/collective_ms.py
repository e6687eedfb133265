from benchmark import xplane


def read(ctx, step_pattern: str, exposed: bool = False):
    """Device time of collective operations per step on device 0; with
    ``exposed``, only the part with no other operation running there."""
    if ctx["trace"] is None:
        return None
    tr = ctx["trace"]
    got = xplane.collectives(tr, device=0)
    steps = xplane.op_seconds(tr, step_pattern, xplane.MODULES_LINE,
                              device=0)["calls"]
    if not got["calls"] or not steps:
        return None
    secs = got["exposed_seconds"] if exposed else got["seconds"]
    return secs / steps * 1e3
