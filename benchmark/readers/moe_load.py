from benchmark import xplane, xstats

RECORD = "serve.moe_load"


def read(ctx, cell: str):
    """From the ``serve.moe_load`` records of the window (health/
    serving.py ``ServingLedger.moe_load``: one a decode iteration, the
    assignments each held expert took, ``held`` = "n0:n1:...", summed
    over the expert layers on the device, and those that fell on experts
    held elsewhere): the busiest held expert's assignments over the
    mean held expert's, over the window (1 = even). Nothing where the
    program left no such record."""
    if ctx["trace"] is None:
        return None
    lo, hi = xplane.window(ctx["trace"])
    recs = [e[3] for e in xstats.host_events(xstats.for_cell(ctx, cell),
                                             lo, hi, RECORD)]
    held: list[int] = []
    for r in recs:
        counts = [int(c) for c in str(r.get("held", "")).split(":") if c]
        if len(counts) != len(held):
            held = [0] * len(counts)
        held = [a + b for a, b in zip(held, counts)]
    total = sum(held)
    if not total:
        return None
    return max(held) / (total / len(held))
