from benchmark import xplane


def read(ctx, span: str):
    """Share of the host span's time with no device operation running."""
    if ctx["trace"] is None:
        return None
    got = xplane.span_seconds(ctx["trace"], span)
    if not got["spans"]:
        return None
    return 100.0 * got["no_device_seconds"] / got["seconds"]
