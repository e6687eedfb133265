from benchmark import xplane, xstats


def read(ctx, cell: str, program: str, scopes=(), unscoped: bool = False):
    """Device time of the program's operations inside the named scopes
    (``jax.named_scope``; the innermost scope on an operation's path
    counts) ÷ the program's device time in the window. ``unscoped``:
    the operations inside no scope at all (what the compiler put in).
    Nothing where the program did not run or carries no scope anywhere
    (a commit from before the model named its parts)."""
    if ctx["trace"] is None:
        return None
    lo, hi = xplane.window(ctx["trace"])
    got = xstats.program_ops(xstats.for_cell(ctx, cell), lo, hi, program)
    if got is None or not any(got["by_scope"].get(s) for s in xstats.SCOPES):
        return None
    ns = (got["by_scope"].get(None, 0) if unscoped
          else sum(got["by_scope"].get(s, 0) for s in scopes))
    return 100.0 * ns / got["module_ns"]
