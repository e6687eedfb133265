from benchmark import family, xplane


def read(ctx, pattern: str, step_pattern: str):
    """Least time the chip could take for the flash kernels' work (from
    shapes: the larger of FLOPs ÷ peak and bytes ÷ HBM rate) ÷ their
    device time. Steps are counted from the step program's executions in
    the window, so a kernel cut by the window's edge costs both sides."""
    if ctx["trace"] is None:
        return None
    tr, c = ctx["trace"], ctx["counters"]
    got = xplane.op_seconds(tr, pattern)
    if not got["calls"]:
        return None
    steps = xplane.op_seconds(tr, step_pattern, xplane.MODULES_LINE,
                              device=0)["calls"]
    if not steps:
        return None
    floor = family.of(ctx["cfg"]).flash_train_floor_s(
        ctx["cfg"], c["per_chip_batch"], c["seq"], ctx["peaks"])
    ctx["notes"]["flash_bound"] = floor["bound"]
    return (100.0 * floor["floor_s"] * steps * got["devices"]
            / got["seconds"])
