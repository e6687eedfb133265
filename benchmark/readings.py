"""The readings a cell's limits are set from (How `correct` is decided,
steps 3-5). Not part of a run: the builder of a cell calls it on the chip.

    python benchmark/readings.py --workload <name> --seeds 12 \
        --controls 3 --seconds 2 --out chiprun_out/readings.json

One process drives the harness's own ``execute`` once per seed, with a
short window, so the lower readings are of the timed path itself. On the
first ``--controls`` seeds it also puts the plain reference in the
program's place, computed in the nearest precision below the one the
configuration states (float8 for bfloat16), and, for a training cell,
with each fault planted that the cell can have; those are the upper
readings. The limits under ``limits/`` are off while it reads."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, manifest, run  # noqa: E402


def others(mix: dict, chips: int, with_controls: bool):
    """What stands in the program's place besides the reference."""
    if not with_controls:
        return None
    if mix["kind"] != "train":
        return ("fp8", "bf16")
    batch = int(mix["per_chip_batch"]) * chips
    out = {"control_fp8": {"mode": "fp8"}, "witness_bf16": {"mode": "bf16"},
           "fault_half_batch": {"rows": list(range(batch // 2))}}
    if chips > 1:
        out["fault_no_exchange"] = {
            "rows": list(range(int(mix["per_chip_batch"])))}
    return out


def reference_only(args) -> int:
    import jax

    from benchmark import train_cell

    m = manifest.load()
    cell = manifest.cell(m, args.workload)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("benchmark: readings are taken on the chip")
    harness.configure_compile_cache()
    loaded = run.load_cell(m, args.workload, ROOT)
    mix, chips = loaded["mix"], int(cell["chips"])
    batch = int(mix["per_chip_batch"]) * chips
    n_check = int(mix.get("check_steps", 3))
    rows = []
    for i in range(args.seeds):
        ctx = {**loaded, "seed": args.first_seed + 7919 * i}
        ref = train_cell.follow(ctx, batch, n_check)
        row = {"seed": ctx["seed"]}
        for name, kw in others(mix, chips, True).items():
            row[name] = train_cell.compare(
                train_cell.follow(ctx, batch, n_check, **kw), ref)
        rows.append(row)
        harness.log("reading " + json.dumps(row))
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f,
                      indent=1)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--reference-only", action="store_true",
                    help="a training cell's upper readings alone: the "
                    "reference against itself in a lower precision or "
                    "with a fault planted, at the cell's batch, on "
                    "whatever one chip is here")
    args = ap.parse_args(argv)
    if args.reference_only:
        return reference_only(args)
    m = manifest.load()
    cell = manifest.cell(m, args.workload)
    devices = harness.require_tpu(int(cell["chips"]))
    harness.configure_compile_cache()
    mix = run.load_cell(m, args.workload, ROOT)["mix"]
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        res = run.execute(
            args.workload, seed, args.seconds, False, devices, t0=t0,
            readings=others(mix, len(devices), i < args.controls))
        row = {"seed": seed,
               "program": {**{k: v["value"]
                              for k, v in res["checks"].items()},
                           **res["counters"].get("not_compared", {})},
               **res["readings"],
               "attempted": res["attempted"], "failed": res["failed"],
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        harness.log("reading " + json.dumps(row))
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f,
                      indent=1)
    names = sorted(rows[0]["program"])
    summary = {}
    for n in names:
        lower = max(r["program"][n] for r in rows)
        summary[n] = {"lower": lower}
        for kind in sorted({k for r in rows for k in r
                            if isinstance(r[k], dict) and k != "program"}):
            vals = [r[kind][n] for r in rows if kind in r and n in r[kind]]
            if vals:
                summary[n][kind + "_min"] = min(vals)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "summary": summary,
                   "rows": rows}, f, indent=1)
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
