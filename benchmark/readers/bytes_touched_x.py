from benchmark import xplane, xstats


def read(ctx, cell: str, program: str,
         bytes_key: str = "decode_needed_bytes"):
    """Bytes the program's operations access in the window, as the
    compiler reckons them (the profiler's ``bytes_accessed`` of each
    operation: an upper estimate, a whole operand counts where a slice
    of it is touched) ÷ the bytes the same iterations needed."""
    if ctx["trace"] is None:
        return None
    need = ctx["counters"].get(bytes_key)
    lo, hi = xplane.window(ctx["trace"])
    got = xstats.program_ops(xstats.for_cell(ctx, cell), lo, hi, program)
    if not need or got is None or not got["bytes"]:
        return None
    return got["bytes"] / need
