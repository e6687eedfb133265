"""BENCHMARK.json: loading it, and refusing one that the driver would
refuse (``python benchmark/run.py --check``; needs no chip)."""

from __future__ import annotations

import json
import os
import re

from benchmark import family

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s, what: str, errs: list) -> None:
    if not (isinstance(s, str) and 1 <= len(s) <= 200
            and "\n" not in s and "\t" not in s):
        errs.append(f"{what}: want 1-200 characters on one line")


def reporting_cells(metric: dict, cells: list[str]) -> list[str]:
    """The cells a metric is reported in: its ``workloads``, or all."""
    return list(metric.get("workloads", cells))


def check(m: dict, root: str = ROOT) -> list[str]:
    """Every rule broken, as sentences; empty when the manifest holds."""
    errs: list[str] = []
    if set(m) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return errs
    paths = m["paths"]
    if not 1 <= len(paths) <= 16:
        errs.append("paths: want 1 to 16 directories")
    for p in paths:
        if (not re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
                or p.startswith("/") or ".." in p.split("/")):
            errs.append(f"path {p!r}: bad characters or leaves the repo")
        elif not os.path.isdir(os.path.join(root, p)):
            errs.append(f"path {p!r} is not a directory")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in paths)

    if not 1 <= len(m["command"]) <= 32:
        errs.append("command: want 1 to 32 words")
    for w in m["command"]:
        _line(w, f"command word {w!r}", errs)
        if isinstance(w, str) and (w.startswith("/") or ".." in w.split("/")):
            errs.append(f"command word {w!r} leaves the repo")
        if (isinstance(w, str) and os.path.exists(os.path.join(root, w))
                and not under_paths(w)):
            errs.append(f"command names {w!r}, a file outside paths")
    if not (isinstance(m["run_seconds"], int)
            and 1 <= m["run_seconds"] <= 51):
        errs.append("run_seconds: want a whole number from 1 to 51")

    names: set[str] = set()

    def name_ok(n, what: str) -> None:
        if not (isinstance(n, str) and NAME.match(n)):
            errs.append(f"{what} {n!r}: letters, digits, _ . - only, "
                        f"at most 64")
        elif (what, n) in names:
            errs.append(f"{what} {n!r} appears twice")
        names.add((what, n))

    files = set()
    if not 1 <= len(m["configs"]) <= 24:
        errs.append("configs: want 1 to 24")
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errs.append(f"config {c.get('name')!r}: keys {sorted(c)}")
            continue
        name_ok(c["name"], "config")
        _line(c["source"], f"config {c['name']} source", errs)
        _line(c["why"], f"config {c['name']} why", errs)
        if len(c["reduced"]) > 16:
            errs.append(f"config {c['name']}: more than 16 reduced keys")
        for k in c["reduced"]:
            if not NAME.match(k):
                errs.append(f"config {c['name']}: reduced key {k!r}")
        f = c["file"]
        if not under_paths(f) or not os.path.isfile(os.path.join(root, f)):
            errs.append(f"config {c['name']}: file {f!r} is missing or "
                        f"outside paths")
        else:
            with open(os.path.join(root, f)) as fh:
                errs.extend(f"config {c['name']}: {e}"
                            for e in family.faults(json.load(fh)))
        if f in files:
            errs.append(f"config file {f!r} serves two configurations")
        files.add(f)

    cfg_names = {c.get("name") for c in m["configs"]}
    cells: list[str] = []
    pairs = set()
    if not 1 <= len(m["workloads"]) <= 24:
        errs.append("workloads: want 1 to 24 cells")
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errs.append(f"cell {w.get('name')!r}: keys {sorted(w)}")
            continue
        name_ok(w["name"], "cell")
        cells.append(w["name"])
        if not NAME.match(w["traffic"]):
            errs.append(f"cell {w['name']}: traffic {w['traffic']!r}")
        _line(w["why"], f"cell {w['name']} why", errs)
        if w["config"] not in cfg_names:
            errs.append(f"cell {w['name']}: no configuration "
                        f"{w['config']!r}")
        if w["chips"] not in (1, 4):
            errs.append(f"cell {w['name']}: chips must be 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            errs.append(f"cell {w['name']}: its configuration and "
                        f"traffic already make a cell")
        pairs.add((w["config"], w["traffic"]))
        tf = traffic_file(w["traffic"], paths, root)
        if tf is None:
            errs.append(f"cell {w['name']}: no traffic file for "
                        f"{w['traffic']!r} under paths")
    for c in m["configs"]:
        if not any(w.get("config") == c.get("name")
                   for w in m["workloads"]):
            errs.append(f"config {c.get('name')!r} has no cell")
    four = sum(1 for w in m["workloads"] if w.get("chips") == 4)
    if four > max(1, len(m["workloads"]) // 4):
        errs.append(f"{four} cells take four chips; at most "
                    f"{max(1, len(m['workloads']) // 4)} may")

    e2e: dict[str, list[str]] = {}
    if not 1 <= len(m["end_to_end"]) <= 16:
        errs.append("end_to_end: want 1 to 16 metrics")
    for x in m["end_to_end"]:
        extra = set(x) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra or not {"name", "unit", "better", "bound",
                         "source"} <= set(x):
            errs.append(f"end-to-end metric {x.get('name')!r}: keys "
                        f"{sorted(x)}")
            continue
        name_ok(x["name"], "metric")
        if not UNIT.match(x["unit"]):
            errs.append(f"metric {x['name']}: unit {x['unit']!r}")
        if x["better"] not in ("lower", "higher"):
            errs.append(f"metric {x['name']}: better {x['better']!r}")
        if x["source"] not in ("host_clock", "device_trace"):
            errs.append(f"end-to-end metric {x['name']}: source "
                        f"{x['source']!r}")
        if not (isinstance(x["bound"], (int, float))
                and 0 < x["bound"] <= 0.1):
            errs.append(f"metric {x['name']}: bound {x['bound']!r} is "
                        f"not in (0, 0.1]")
        e2e[x["name"]] = reporting_cells(x, cells)
        for c in e2e[x["name"]]:
            if c not in cells:
                errs.append(f"metric {x['name']}: no cell {c!r}")
    if "setup_s" not in e2e:
        errs.append("end_to_end lacks setup_s")
    elif sorted(e2e["setup_s"]) != sorted(cells):
        errs.append("setup_s is not reported in every cell")

    if not 1 <= len(m["per_layer"]) <= 128:
        errs.append("per_layer: want 1 to 128 metrics")
    layered = set()
    for x in m["per_layer"]:
        extra = set(x) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra or not {"name", "unit", "better", "source", "layer",
                         "moves"} <= set(x):
            errs.append(f"per-layer metric {x.get('name')!r}: keys "
                        f"{sorted(x)}")
            continue
        name_ok(x["name"], "metric")
        if not UNIT.match(x["unit"]):
            errs.append(f"metric {x['name']}: unit {x['unit']!r}")
        if x["better"] not in ("lower", "higher"):
            errs.append(f"metric {x['name']}: better {x['better']!r}")
        if x["source"] not in SOURCES:
            errs.append(f"metric {x['name']}: source {x['source']!r}")
        _line(x["layer"], f"metric {x['name']} layer", errs)
        if x["moves"] not in e2e or x["moves"] == "setup_s":
            errs.append(f"metric {x['name']}: moves {x['moves']!r}, "
                        f"which is no end-to-end metric of a window")
            continue
        for c in reporting_cells(x, cells):
            if c not in cells:
                errs.append(f"metric {x['name']}: no cell {c!r}")
            elif c not in e2e[x["moves"]]:
                errs.append(
                    f"per_layer metric {x['name']} is reported on "
                    f"workload {c}, where {x['moves']}, which it should "
                    f"move, is not")
            layered.add(c)
        mf = os.path.join(root, paths[0], "metrics", x["name"] + ".json")
        if not os.path.isfile(mf):
            errs.append(f"metric {x['name']}: no file {mf}")
    for c in cells:
        if not any(c in v for k, v in e2e.items() if k != "setup_s"):
            errs.append(f"cell {c} reports no end-to-end metric besides "
                        f"setup_s")
        if c not in layered:
            errs.append(f"cell {c} reports no per-layer metric")
    if len(json.dumps(m)) > 64 * 1024:
        errs.append("BENCHMARK.json is over 64 KiB")
    return errs


def traffic_file(name: str, paths: list[str], root: str = ROOT):
    for p in paths:
        for ext in (".json", ".jsonl", ".toml", ".txt", ".csv"):
            f = os.path.join(root, p, "traffic", name + ext)
            if os.path.isfile(f):
                return f
    return None


def cell(m: dict, name: str) -> dict:
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json; "
                     f"have {[w['name'] for w in m['workloads']]}")


def config_of(m: dict, name: str, root: str = ROOT) -> dict:
    for c in m["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise SystemExit(f"benchmark: no configuration {name!r}")


def metrics_for(m: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries reported in a cell."""
    cells = [w["name"] for w in m["workloads"]]
    return [x for x in m[kind]
            if cell_name in reporting_cells(x, cells)]
