"""One cell, once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python benchmark/run.py --check        # the manifest's own rules; no chip

A new process: finds its chip(s) or exits non-zero, joins a one-process
cluster the way the examples do, makes weights on the device from
``--seed``, warms the cell's own shapes (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line last. Which cell does what is data:
``BENCHMARK.json`` names a configuration file and a traffic file; the
configuration's ``family`` names the module under ``families/`` that
knows its architecture (``benchmark/family.py``); the traffic file's
``kind`` picks the driver; each per-layer metric is a file under
``metrics/`` naming a reader under ``readers/``."""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

KINDS = {"train": "benchmark.train_cell", "open": "benchmark.serve_cell",
         "closed": "benchmark.serve_cell"}


def load_cell(m: dict, name: str, root: str) -> dict:
    w = manifest.cell(m, name)
    tf = manifest.traffic_file(w["traffic"], m["paths"], root)
    if tf is None:
        raise SystemExit(f"benchmark: no traffic file for {w['traffic']!r}")
    with open(tf) as f:
        mix = json.load(f)
    limits = {}
    lf = os.path.join(root, m["paths"][0], "limits", name + ".json")
    if os.path.isfile(lf):
        with open(lf) as f:
            limits = json.load(f)
    return {"cell": w, "mix": mix, "limits": limits,
            "cfg": manifest.config_of(m, w["config"], root)}


def per_layer(m: dict, cell_name: str, root: str, rctx: dict) -> dict:
    """Run each of the cell's per-layer readers; leave out the silent."""
    out = {}
    for x in manifest.metrics_for(m, cell_name, "per_layer"):
        with open(os.path.join(root, m["paths"][0], "metrics",
                               x["name"] + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(rctx, **spec.get("params", {}))
        if value is not None:
            out[x["name"]] = {"value": value, "unit": x["unit"]}
    return out


def execute(workload: str, seed: int, seconds: float, trace: bool,
            devices, root: str = ROOT, fault: str | None = None,
            t0: float | None = None, readings: dict | None = None) -> dict:
    """Everything after the look for a chip. → the result (not printed)."""
    from benchmark import harness, peaks, xplane

    m = manifest.load(root)
    loaded = load_cell(m, workload, root)
    counter = harness.CompileCounter()
    cluster = harness.join_cluster(len(devices))
    ctx = {**loaded, "seed": int(seed), "seconds": float(seconds),
           "devices": list(devices), "chips": len(devices),
           "cluster": cluster, "compiles": counter, "fault": fault,
           "readings": readings,
           "t0": T0 if t0 is None else t0,
           "trace_dir": harness.trace_dir_for(workload) if trace else None}
    try:
        cellmod = importlib.import_module(KINDS[loaded["mix"]["kind"]])
        res = cellmod.run(ctx)
    finally:
        cluster.close()
    result = {k: res[k] for k in ("correct", "attempted", "failed",
                                  "checks")}
    device = {"memory_peak_bytes": res["memory_peak_bytes"]}
    if trace:
        tr = xplane.read(xplane.find_xplane(ctx["trace_dir"]))
        bi = xplane.busy_and_idle(tr)
        device.update(busy_s=bi["busy_s"], window_s=bi["window_s"])
        rctx = {"trace": tr, "counters": res["counters"],
                "cfg": loaded["cfg"], "mix": loaded["mix"],
                "chips": len(devices), "notes": {},
                "peaks": peaks.peaks_for(devices[0].device_kind)
                if devices[0].platform == "tpu" else None}
        result["metrics"] = per_layer(m, workload, root, rctx)
        result["breakdown"] = xplane.breakdown(tr)
        result["notes"] = rctx["notes"]
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    else:
        result["metrics"] = {
            x["name"]: {"value": res["e2e"][x["name"]], "unit": x["unit"]}
            for x in manifest.metrics_for(m, workload, "end_to_end")}
    result["device"] = harness.device_block(devices, device)
    result["counters"] = res["counters"]
    result["readings"] = res.get("readings", {})
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    args = ap.parse_args(argv)
    m = manifest.load()
    errs = manifest.check(m)
    if args.check:
        for e in errs:
            print(f"BENCHMARK.json: {e}", file=sys.stderr)
        print("BENCHMARK.json: " + ("ok" if not errs else
                                    f"{len(errs)} fault(s)"))
        return 1 if errs else 0
    if errs:
        print(f"BENCHMARK.json: {errs[0]}", file=sys.stderr)
        return 2
    if not args.workload:
        ap.error("--workload is required")
    from benchmark import harness

    cell = manifest.cell(m, args.workload)
    devices = harness.require_tpu(int(cell["chips"]))
    harness.configure_compile_cache()
    seconds = args.seconds if args.seconds else float(m["run_seconds"])
    result = execute(args.workload, args.seed, seconds, bool(args.trace),
                     devices)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
