"""The one reduction from a profiler trace to numbers: interval
arithmetic on hand-made traces, then every reader on a small recorded
trace of the one-chip train cell (three steps cut from a v5e run; names
cut to 100 characters)."""

import gzip
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_tiny  # noqa: E402
from benchmark import family, peaks, xplane  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def made(dev_ops, host=(), window=(0, 100 * MS), modules=(), ndev=1):
    """A trace with the same ops on ``ndev`` devices."""
    planes = [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": [list(e) for e in dev_ops]},
        {"name": "XLA Modules", "events": [list(e) for e in modules]}]}
        for i in range(ndev)]
    planes.append({"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench.window", window[0], window[1] - window[0]],
        *[list(e) for e in host]]}]})
    planes.append({"name": "/host:metadata", "lines": []})
    return {"planes": planes}


def test_interval_arithmetic():
    assert xplane.union([[5, 9], [0, 3], [2, 4], [9, 9]]) == [[0, 4], [5, 9]]
    assert xplane.total([[0, 4], [5, 9]]) == 8
    assert xplane.clip([[0, 4], [5, 9]], 3, 6) == [[3, 4], [5, 6]]
    assert xplane.subtract([[0, 10], [20, 30]], [[2, 3], [8, 22], [29, 40]]
                           ) == [[0, 2], [3, 8], [22, 29]]
    assert xplane.gaps([[2, 3]], 0, 5) == [[0, 2], [3, 5]]


def test_busy_is_the_union_not_the_sum():
    # A while that holds two ops: 40 ms busy, not 80.
    tr = made([("%while.1 = x", 10 * MS, 40 * MS),
               ("%fusion.1 = x", 10 * MS, 15 * MS),
               ("%fusion.2 = x", 25 * MS, 25 * MS)])
    got = xplane.busy_and_idle(tr)
    assert got["window_s"] == pytest.approx(0.1)
    assert got["busy_s"] == pytest.approx(0.04)
    assert got["idle_pct_fullest_idle"] == pytest.approx(60.0)


def test_events_are_clipped_to_the_window():
    tr = made([("%fusion.1 = x", -10 * MS, 30 * MS),
               ("%fusion.2 = x", 90 * MS, 30 * MS)])
    assert xplane.busy_and_idle(tr)["busy_s"] == pytest.approx(0.03)
    assert xplane.op_seconds(tr, r"^%fusion")["seconds"] == pytest.approx(0.03)


def test_idle_share_is_of_the_device_that_idles_most():
    tr = made([("%fusion.1 = x", 0, 50 * MS)], ndev=2)
    tr["planes"][1]["lines"][0]["events"] = [["%fusion.1 = x", 0, 20 * MS]]
    got = xplane.busy_and_idle(tr)
    assert got["busy_s"] == pytest.approx(0.035)          # the mean
    assert got["idle_pct_fullest_idle"] == pytest.approx(80.0)


def test_a_pattern_anchored_at_the_name_misses_operands():
    ops = [("%ptype_flash_fwd.3 = bf16[] custom-call()", 0, 5 * MS),
           ("%fusion.9 = bf16[] fusion(%ptype_flash_fwd.3)", 5 * MS, 7 * MS)]
    got = xplane.op_seconds(made(ops), r"^%ptype_flash_(fwd|dq|dkv)[.0-9]* =")
    assert got == {"seconds": pytest.approx(0.005), "calls": 1, "devices": 1}


def test_collective_time_and_its_exposed_part():
    # 20 ms of all-reduce, 8 of them under a fusion; 5 ms of all-gather
    # with nothing beside it.
    ops = [("%all-reduce.1 = f32[] all-reduce()", 10 * MS, 20 * MS),
           ("%fusion.1 = x", 22 * MS, 8 * MS),
           ("%all-gather.2 = f32[] all-gather()", 50 * MS, 5 * MS),
           ("%fusion.2 = x", 60 * MS, 10 * MS)]
    got = xplane.collectives(made(ops))
    assert got["calls"] == 2
    assert got["seconds"] == pytest.approx(0.025)
    assert got["exposed_seconds"] == pytest.approx(0.017)
    reader = importlib.import_module("benchmark.readers.collective_ms")
    ctx = {"trace": made(ops, modules=[("jit_step(1)", 0, 40 * MS),
                                       ("jit_step(1)", 40 * MS, 40 * MS)])}
    assert reader.read(ctx, step_pattern=r"^jit_step\(") == pytest.approx(12.5)
    assert reader.read(ctx, step_pattern=r"^jit_step\(", exposed=True
                       ) == pytest.approx(8.5)


def test_a_trace_without_collectives_gives_no_collective_metric():
    reader = importlib.import_module("benchmark.readers.collective_ms")
    ctx = {"trace": made([("%fusion.1 = x", 0, MS)],
                         modules=[("jit_step(1)", 0, MS)])}
    assert reader.read(ctx, step_pattern=r"^jit_step\(") is None


def test_idle_gaps_go_to_what_the_host_was_doing():
    ops = [("%fusion.1 = x", 0, 20 * MS), ("%fusion.2 = x", 50 * MS, 50 * MS)]
    host = [("serve.step", 15 * MS, 30 * MS),     # covers 25 of the 30 idle
            ("np.asarray", 20 * MS, 4 * MS),      # inside it, shorter
            ("rpc.recv", 44 * MS, 6 * MS)]
    got = dict(xplane.idle_gaps_by_host_span(made(ops, host)))
    assert got == {"serve.step": pytest.approx(0.03)}
    ops2 = [("%fusion.1 = x", 0, 20 * MS), ("%fusion.2 = x", 24 * MS, 20 * MS),
            ("%fusion.3 = x", 50 * MS, 50 * MS)]
    got = dict(xplane.idle_gaps_by_host_span(made(ops2, host)))
    assert got["np.asarray"] == pytest.approx(0.004)     # the innermost
    assert got["rpc.recv"] == pytest.approx(0.006)


def gaps_every_span_for_every_gap(tr, ignore=("bench.window",)):
    """The attribution as first written: every host span looked at for
    every gap (minutes on a 40-s capture of the chat cell). Kept as the
    reference for the pass over the spans still open."""
    lo, hi = xplane.window(tr)
    busy = xplane.busy_by_device(tr, lo, hi)
    host = sorted(([s, s + d, name] for name, s, d in xplane.host_events(tr)
                   if name not in ignore and d > 0 and s < hi
                   and s + d > lo), key=lambda e: e[0])
    acc = {}
    for a, b in xplane.gaps(busy[0], lo, hi):
        best, best_key = "no host span", (0, 0)
        for s, e, name in host:
            if s >= b:
                break
            ov = min(e, b) - max(s, a)
            if ov > 0 and (ov, -(e - s)) > best_key:
                best, best_key = name, (ov, -(e - s))
        acc[best] = acc.get(best, 0) + (b - a)
    return [[name, ns / 1e9] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:10]]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_idle_gaps_are_those_of_the_plain_double_loop(seed):
    """Nested, overlapping and request-long spans, ties included."""
    import random

    rng = random.Random(seed)
    for _ in range(60):
        t, ops = 0, []
        for _ in range(rng.randint(1, 60)):
            t += rng.randint(0, 3) * MS // 2
            ops.append(("%op = x", t, rng.randint(1, 5) * MS))
            t += ops[-1][2]
        host = [(rng.choice(["serve.step", "serve.step/fetch", "rpc.call",
                             "actor/Generator.Generate"]),
                 rng.randint(0, t),
                 rng.choice([0, 1, 1, 2, 5, 50, 500]) * MS
                 + rng.randint(0, 3))
                for _ in range(rng.randint(0, 80))]
        tr = made(ops, host, window=(0, t))
        assert xplane.idle_gaps_by_host_span(tr) == \
            gaps_every_span_for_every_gap(tr)


def test_span_time_with_no_device_op():
    ops = [("%fusion.1 = x", 10 * MS, 10 * MS)]
    host = [("serve.step", 5 * MS, 20 * MS), ("serve.step", 40 * MS, 10 * MS)]
    got = xplane.span_seconds(made(ops, host), "serve.step")
    assert got["spans"] == 2
    assert got["seconds"] == pytest.approx(0.03)
    assert got["no_device_seconds"] == pytest.approx(0.02)


def test_containers_stay_out_of_the_breakdown():
    tr = made([("%while.19 = (s32[]) while()", 0, 90 * MS),
               ("%fusion.1 = x", 0, 60 * MS), ("%copy.1 = x", 60 * MS, 30 * MS)])
    assert [n for n, _ in xplane.top_device_ops(tr)] == [
        "%fusion.1 = x", "%copy.1 = x"]


def test_no_window_span_is_an_error():
    tr = made([("%fusion.1 = x", 0, MS)])
    tr["planes"][1]["lines"][0]["events"] = []
    with pytest.raises(ValueError):
        xplane.window(tr)


# ------------------------------------------------- the recorded trace


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "train-1chip.trace.json.gz"), "rt") as f:
        return json.load(f)


FLASH = r"^%ptype_flash_(fwd|dq|dkv)[.0-9]* ="
STEP = r"^jit_(step|local_grads)\("


def test_recorded_busy_idle_and_steps(recorded):
    bi = xplane.busy_and_idle(recorded)
    assert bi["window_s"] == pytest.approx(0.4171, rel=1e-3)   # 3 x 139 ms
    assert 0.0 <= bi["idle_pct_fullest_idle"] < 1.0
    steps = xplane.op_seconds(recorded, STEP, xplane.MODULES_LINE, device=0)
    assert steps["calls"] == 3
    starts = xplane.op_starts(recorded, STEP)
    assert [round((b - a) * 1e3, 1) for a, b in zip(starts, starts[1:])
            ] == [139.0, 139.0]


def test_recorded_flash_kernels(recorded):
    got = xplane.op_seconds(recorded, FLASH)
    assert got["calls"] == 3 * 12 * 3          # steps x layers x kernels
    assert got["seconds"] == pytest.approx(0.04855, rel=1e-3)
    per_kernel = {k: xplane.op_seconds(
        recorded, rf"^%ptype_flash_{k}[.0-9]* =")["seconds"]
        for k in ("fwd", "dq", "dkv")}
    assert sum(per_kernel.values()) == pytest.approx(got["seconds"])
    assert per_kernel["dkv"] > per_kernel["dq"] > per_kernel["fwd"]


def test_recorded_readers(recorded, tmp_path):
    tiny_root = perfbench_tiny.make_root(str(tmp_path))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "optimus-125m.json")) as f:
        cfg = json.load(f)
    tokens = 3 * 16 * 1024
    ctx = {"trace": recorded, "cfg": cfg, "chips": 1, "notes": {},
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "counters": {"per_chip_batch": 16, "seq": 1024,
                        "compiles_in_window": 0,
                        "model_flops_traced": tokens
                        * family.of(cfg).train_flops_per_token(cfg, 1024)}}

    def read(metric):
        with open(os.path.join(tiny_root, "benchmark", "metrics",
                               metric + ".json")) as f:
            spec = json.load(f)
        mod = importlib.import_module("benchmark.readers." + spec["reader"])
        return mod.read(ctx, **spec.get("params", {}))

    # 3 steps of 16,384 tokens x 773.8 MFLOP in 0.4171 s of a 197 TFLOP/s chip.
    assert read("step_mfu.train") == pytest.approx(
        100 * tokens * 773_849_088 / (0.41708 * 197e12), rel=1e-3)
    assert 45.0 < read("step_mfu.train") < 47.0
    assert read("step_ms_p50.train") == pytest.approx(139.0, abs=0.1)
    assert read("flash_time_pct.train") == pytest.approx(
        100 * 0.04855 / 0.41708, rel=2e-3)
    # Needed: 3 steps x 5.494 ms; took 48.55 ms.
    assert read("flash_roofline.train") == pytest.approx(
        100 * 3 * 5.494e-3 / 0.04855, rel=2e-3)
    assert ctx["notes"]["flash_bound"] == "flops"
    assert read("device_idle_pct.train") < 1.0
    assert read("compiles_in_window.train") == 0.0
    # Nothing to read: no collective ran on one chip, and a reader
    # without a trace is silent, never 0.
    assert read("collective_ms.train4") is None
    ctx["trace"] = None
    assert read("flash_roofline.train") is None
    assert read("device_idle_pct.train") is None


def test_recorded_breakdown(recorded):
    b = xplane.breakdown(recorded)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert not any(n.startswith("%while") for n, _ in b["device_ops"])
    assert all(isinstance(s, float) and s > 0 for _, s in b["device_ops"])
