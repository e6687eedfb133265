from benchmark import xplane, xstats

RECORD = "serve.cache"


def read(ctx, cell: str):
    """From the ``serve.cache`` records of the window (health/
    serving.py ``ServingLedger.cache``: one a decode iteration of an
    engine with two kinds of cache): the bytes its live rows hold,
    blocks in the full layers' pool and in the window layers' each
    weighted by the layers of its kind (the configuration's
    ``sliding_windows``: 0 is a full layer), ÷ the bytes of
    ``uniform_blocks``, what a cache of one kind, every layer keeping
    every token, would hold for the same rows. 100 would mean that
    nothing is given back. Nothing where the program left no such
    record or the configuration states no layer kinds."""
    if ctx["trace"] is None:
        return None
    windows = ctx["cfg"].get("sliding_windows")
    if not windows:
        return None
    n_window = sum(1 for w in windows if w)
    n_full = len(windows) - n_window
    lo, hi = xplane.window(ctx["trace"])
    held = uniform = 0
    for e in xstats.host_events(xstats.for_cell(ctx, cell), lo, hi,
                                RECORD):
        r = e[3]
        held += (n_full * int(r.get("full_blocks", 0))
                 + n_window * int(r.get("window_blocks", 0)))
        uniform += len(windows) * int(r.get("uniform_blocks", 0))
    if not uniform:
        return None
    return 100.0 * held / uniform
