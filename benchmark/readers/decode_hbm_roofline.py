from benchmark import xplane


def read(ctx, pattern: str, bytes_key: str = "decode_needed_bytes"):
    """Bytes the decode iterations of the window had to read (weights
    once each, and the K, V their rows hold, from the benchmark's own
    count) ÷ HBM rate ÷ the device time of the decode program."""
    if ctx["trace"] is None:
        return None
    need = ctx["counters"].get(bytes_key)
    got = xplane.op_seconds(ctx["trace"], pattern, xplane.MODULES_LINE)
    if not need or not got["calls"]:
        return None
    return (100.0 * need / ctx["peaks"]["hbm_bytes_per_s"]
            / got["seconds"])
