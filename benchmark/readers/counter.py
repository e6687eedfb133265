def read(ctx, key: str, scale: float = 1.0):
    """A number the cell counted itself (``ctx['counters'][key]``)."""
    v = ctx["counters"].get(key)
    return None if v is None else float(v) * scale
