"""Find an open-loop cell's knee, once, when the cell is defined.

    python benchmark/sweep.py --workload <name> --rates 1,2,3 \
        --seconds 40 --seed 7 --out chiprun_out/sweep.json

One server and one seeded population; each rate in turn. The knee is the
highest rate at which no request fails and the requests in flight at the
close are no more than the engine's slots (beyond that the queue grows all
through the window and the first-token tail with it). The cell's traffic
file then fixes 0.8 x knee."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, manifest, run, serve_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=2_100_000_011)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    m = manifest.load()
    cell = manifest.cell(m, args.workload)
    devices = harness.require_tpu(int(cell["chips"]))
    loaded = run.load_cell(m, args.workload, ROOT)
    harness.configure_compile_cache()
    cluster = harness.join_cluster(len(devices))
    ctx = {**loaded, "seed": args.seed, "seconds": args.seconds,
           "devices": devices, "chips": len(devices), "cluster": cluster,
           "compiles": harness.CompileCounter(), "t0": t0,
           "trace_dir": None}
    try:
        points = serve_cell.sweep(
            ctx, [float(r) for r in args.rates.split(",")])
    finally:
        cluster.close()
    slots = int(loaded["mix"]["engine"]["n_slots"])
    for p in points:
        p["keeps_up"] = bool(p["backlog_at_close"] <= slots
                             and p["failed"] == 0)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(points, f, indent=1)
    keys = ("rate_rps", "attempted", "completed_in_window",
            "backlog_at_close", "drain_s", "ttft_p50_ms", "ttft_p90_ms",
            "itl_p50_ms", "itl_p95_ms", "engine_iter_ms_p50",
            "issue_lag_p95_ms", "compiles_in_window", "keeps_up")
    for p in points:
        print(json.dumps({k: p.get(k) for k in keys}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
