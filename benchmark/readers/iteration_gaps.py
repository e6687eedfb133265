from benchmark import harness, xplane, xstats

RECORD = "serve.iteration"


def quantile(pairs: list, q: float) -> float:
    """``harness.quantile`` of the values of ``pairs`` (value, weight),
    each counted as many times as its weight."""
    pairs = sorted(p for p in pairs if p[1] > 0)
    n = sum(w for _, w in pairs)
    if not n:
        raise ValueError("quantile of nothing")

    def at(i):
        for v, w in pairs:
            i -= w
            if i < 0:
                return v

    k = (n - 1) * q
    lo = int(k)
    a, b = at(lo), at(min(lo + 1, n - 1))
    return a + (b - a) * (k - lo)


def record_gaps(r: dict) -> list:
    """The inter-token gaps one record stands for, as (ms, rows): the
    gap the rows shared, the new rows' own, and a speculative window's
    further tokens, which share the stamp (gaps of zero)."""
    new = [float(g) for g in str(r.get("new_gaps_ms", "")).split(":") if g]
    rows = int(r.get("gap_rows", 0))
    out = [(g, 1) for g in new]
    if rows:
        out.append((float(r.get("gap_ms", 0.0)), rows))
    burst = int(r.get("decode_tokens", 0)) - rows - len(new)
    if burst > 0:
        out.append((0.0, burst))
    return out


def window_gaps(ctx, cell: str) -> dict | None:
    """The window's ``serve.iteration`` records (health/serving.py
    ``_IterMeter``: one a pass of the engine loop, stamped as the pass
    closes), split once a run: the gaps of the passes that carried a
    prefill chunk, those of the passes that carried none, and the rows
    live in each pass that ran a step. Logs the count of gaps, the 95th
    percentile of all of them (what ``itl_p95_ms`` reads from outside,
    over the drain too) and the requests whose chunks stalled most
    row-milliseconds (rows x the gap over the decode-only median).
    None where the program left no such record."""
    if "iteration_gaps" in ctx:
        return ctx["iteration_gaps"]
    lo, hi = xplane.window(ctx["trace"])
    recs = [e[3] for e in xstats.host_events(xstats.for_cell(ctx, cell),
                                             lo, hi, RECORD)]
    out = None
    if recs:
        out = {"chunk": [], "plain": [], "live": []}
        carried = []
        for r in recs:
            gaps = record_gaps(r)
            if int(r.get("chunks", 0)):
                out["chunk"] += gaps
                carried.append((str(r.get("chunk_rids", "")), gaps))
            else:
                out["plain"] += gaps
            if int(r.get("active", 0)):
                out["live"].append(int(r["active"]))
        every = out["chunk"] + out["plain"]
        if every:
            base = quantile(out["plain"], 0.5) if out["plain"] else 0.0
            behind: dict[str, float] = {}
            for rids, gaps in carried:
                rids = [x for x in rids.split(":") if x]
                stalled = sum(w * max(0.0, g - base) for g, w in gaps)
                for rid in rids:
                    behind[rid] = behind.get(rid, 0.0) + stalled / len(rids)
            top = sorted(behind.items(), key=lambda kv: -kv[1])[:5]
            note = {"records": len(recs),
                    "gaps": sum(w for _, w in every),
                    "p95_all_ms": quantile(every, 0.95),
                    "stalled_row_ms_by_rid": [[k, round(v, 1)]
                                              for k, v in top]}
            ctx.setdefault("notes", {})["iteration_gaps"] = note
            harness.log(f"serve.iteration in the window: {note}")
    ctx["iteration_gaps"] = out
    return out


def read(ctx, cell: str, what: str):
    """From the window's ``serve.iteration`` records (``window_gaps``).
    ``what``: ``chunk_share_pct`` (gaps whose pass carried a prefill
    chunk ÷ all gaps), ``decode_only_p95_ms`` (95th percentile of the
    gaps whose pass carried none), ``chunk_p50_ms`` (median of the gaps
    whose pass carried one) or ``rows_live_mean`` (mean rows live over
    the passes that ran a step). Nothing where the program left no
    record, or none of the kind asked for."""
    if ctx["trace"] is None:
        return None
    got = window_gaps(ctx, cell)
    if got is None:
        return None
    chunk, plain, live = got["chunk"], got["plain"], got["live"]
    if what == "rows_live_mean":
        return sum(live) / len(live) if live else None
    if what == "chunk_share_pct":
        total = sum(w for _, w in chunk + plain)
        return (100.0 * sum(w for _, w in chunk) / total
                if total else None)
    if what == "decode_only_p95_ms":
        return quantile(plain, 0.95) if plain else None
    if what == "chunk_p50_ms":
        return quantile(chunk, 0.5) if chunk else None
    raise ValueError(f"iteration_gaps: what={what!r}")
