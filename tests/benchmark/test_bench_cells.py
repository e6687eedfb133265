"""Every cell's set-up -> window -> last line, on the CPU at tiny sizes
(four virtual devices for the four-chip cell), through the harness's own
``execute`` and ``emit``; and the command itself, which never prints a
device metric from a CPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny  # noqa: E402
from benchmark import harness, manifest, run  # noqa: E402

M = perfbench_tiny.manifest()      # the shipped cells + the Store cell
CELLS = [(w["name"], w["chips"]) for w in M["workloads"]]
BIG = 2 ** 31 + 4242


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return perfbench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,chips", CELLS, ids=[c for c, _ in CELLS])
def test_cell_runs_to_its_last_line(cell, chips, tiny_root, capsys):
    import jax

    res = run.execute(cell, BIG, 1.0, False, jax.devices()[:chips],
                      root=tiny_root)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["counters"]["compiles_in_window"] == 0
    want = {x["name"] for x in manifest.metrics_for(M, cell, "end_to_end")}
    assert set(res["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    # Every number compared comes with its limit, and only those.
    with open(os.path.join(tiny_root, "benchmark", "limits",
                           cell + ".json")) as f:
        assert set(res["checks"]) == set(json.load(f))
    harness.emit(res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["device"]["count"] == chips
    assert {"platform", "kind", "memory_peak_bytes"} <= set(line["device"])
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(t.startswith("check ") and "limit" in t for t in tail)


def test_per_layer_names_match_their_cells():
    """Every cell reports at least one per-layer metric, each bound to
    an end-to-end metric the cell reports."""
    for cell, _ in CELLS:
        e2e = {x["name"] for x in manifest.metrics_for(M, cell, "end_to_end")}
        layer = manifest.metrics_for(M, cell, "per_layer")
        assert layer and all(x["moves"] in e2e for x in layer)
        assert any("mfu" in x["name"] for x in layer)


def run_cli(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_chip_no_number():
    got = run_cli(ROOT, "--workload", CELLS[0][0], "--seed", str(BIG),
                  "--seconds", "1", "--trace", "0")
    assert got.returncode not in (0, None)
    assert got.stdout.strip() == ""
    assert "Nothing was measured" in got.stderr


def test_alone_in_a_directory_it_prints_no_result(tmp_path):
    perfbench_tiny.copy_shipped(tmp_path)
    got = run_cli(str(tmp_path), "--workload", CELLS[0][0], "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert got.returncode != 0 and got.stdout.strip() == ""


def test_unknown_workload_is_refused():
    got = run_cli(ROOT, "--workload", "no.such-cell", "--seed", "1",
                  "--seconds", "1")
    assert got.returncode != 0 and got.stdout.strip() == ""
