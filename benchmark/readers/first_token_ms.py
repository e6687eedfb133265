from benchmark import xplane, xstats

RECORD = "serve.first_token"


def read(ctx, cell: str, what: str, program: str = ""):
    """Where first-token time went, mean ms over the requests whose
    ``serve.first_token`` record (health/serving.py: the request's own
    split, ``queue_ms + reserve_ms + admitted_ms`` = its TTFT) was
    stamped inside the window. ``what``: ``queue`` (waiting to be
    admitted), ``prefill`` (device time of the prefill ``program`` in
    the window ÷ those requests: a request's own prefill, which the
    host's chunk meters cannot give under async dispatch) or ``stall``
    (admitted and not prefilling: ``admitted_ms`` less ``prefill``)."""
    if ctx["trace"] is None:
        return None
    lo, hi = xplane.window(ctx["trace"])
    xs = xstats.for_cell(ctx, cell)
    recs = [e[3] for e in xstats.host_events(xs, lo, hi, RECORD)]
    if not recs:
        return None
    n = len(recs)
    if what == "queue":
        return sum(float(r["queue_ms"]) + float(r["reserve_ms"])
                   for r in recs) / n
    got = xstats.program_ops(xs, lo, hi, program)
    if got is None:
        return None
    prefill = got["module_ns"] / 1e6 / n
    if what == "prefill":
        return prefill
    return sum(float(r["admitted_ms"]) for r in recs) / n - prefill
