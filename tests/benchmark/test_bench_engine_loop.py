"""The readers of the engine loop's own account (ISSUE 37):
``iteration_gaps`` over ``serve.iteration`` records and
``span_no_device_window_pct`` over ``serve.idle``, each on hand-made
structures (weighted percentiles, the window's clipping, nothing where
the program left no record), then on a real CPU capture of a tiny engine,
where the records' gaps are the requests' own token stamps'; and the
manifest's new entries."""

import importlib
import inspect
import json
import os
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, manifest, xplane, xstats  # noqa: E402
from benchmark.readers import (iteration_gaps,  # noqa: E402
                               span_ms_mean, span_no_device_window_pct)

MS = 1_000_000
CELLS = {"chat": "mistral-7b.serve-chat", "docqa": "glm-5.serve-docqa",
         "mixed": "k-exaone-236b-a23b.serve-mixed",
         "hotdocs": "a.x-k1.serve-hotdocs"}
CELL = CELLS["chat"]
NAMES = ("itl_chunk_gap_share_pct", "itl_decode_only_p95_ms",
         "itl_chunk_gap_p50_ms", "rows_live_mean",
         "device_idle_no_work_pct", "ttft_ingress_ms_mean")


# ------------------------------------------------ hand-made structures


def made(host, ops=(), window=(0, 100)):
    """(stats structure, plain structure) of one device and one thread;
    ``host``: (name, start ms, ms, stats), ``ops``: (start ms, ms)."""
    host = [[xplane.WINDOW_SPAN, window[0] * MS,
             (window[1] - window[0]) * MS, {}],
            *[[n, int(s * MS), int(d * MS), st] for n, s, d, st in host]]
    xs = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%fusion = x", int(s * MS), int(d * MS), {}]
            for s, d in ops]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}
    tr = {"planes": [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
        for ln in p["lines"]]} for p in xs["planes"]]}
    return {"trace": tr, "xstats": xs, "notes": {}, "counters": {}}


def iteration(at, active=0, gap=(0.0, 0), new="", chunks="", tokens=None):
    """A ``serve.iteration`` record as the profiler hands it back: what
    the annotation's metadata held, numbers parsed, lists as text."""
    rows = gap[1] + len([g for g in new.split(":") if g])
    return ("serve.iteration", at, 0, {
        "seq": int(at), "active": active,
        "decode_tokens": rows if tokens is None else tokens,
        "chunks": len([c for c in chunks.split(":") if c]),
        "chunk_rids": chunks, "chunk_ctx": 0, "gap_ms": gap[0],
        "gap_rows": gap[1], "new_gaps_ms": new})


#: Nine decode-only passes of 4 rows at 10..18 ms, one pass that
#: carried request 7's chunk while 4 rows waited 50 ms and a new row saw
#: 12.5, one that carried 7's and 9's while 2 rows waited 30 ms, and a
#: prefill-only pass; one record before the window and one after it.
RECORDS = [iteration(-5, 4, (99.0, 4)),
           *[iteration(5 + 5 * i, 4, (10.0 + i, 4)) for i in range(9)],
           iteration(60, 5, (50.0, 4), new="12.5", chunks="7"),
           iteration(70, 2, (30.0, 2), chunks="7:9"),
           iteration(80, 0, chunks="9"),
           iteration(105, 4, (99.0, 4))]


def read_gaps(ctx, what):
    return iteration_gaps.read(ctx, cell=CELL, what=what)


def test_quantile_is_harness_quantile_of_the_weighted_values():
    pairs = [(10.0, 3), (50.0, 1), (12.0, 4), (0.0, 2), (30.0, 0)]
    flat = [v for v, w in pairs for _ in range(w)]
    for q in (0.0, 0.05, 0.5, 0.9, 0.95, 1.0):
        assert iteration_gaps.quantile(pairs, q) == pytest.approx(
            harness.quantile(flat, q))
    with pytest.raises(ValueError):
        iteration_gaps.quantile([(1.0, 0)], 0.5)


def test_gap_readers_split_the_window_by_what_the_pass_carried():
    ctx = made(RECORDS)
    # 36 decode-only gaps; 4 + 1 + 2 behind a chunk; none from outside.
    assert read_gaps(ctx, "chunk_share_pct") == pytest.approx(
        100.0 * 7 / 43)
    plain = [10.0 + i for i in range(9) for _ in range(4)]
    assert read_gaps(ctx, "decode_only_p95_ms") == pytest.approx(
        harness.quantile(plain, 0.95))
    assert read_gaps(ctx, "chunk_p50_ms") == pytest.approx(
        harness.quantile([50.0] * 4 + [12.5] + [30.0] * 2, 0.5))
    # Mean over the passes that ran a step: the prefill-only one is out.
    assert read_gaps(ctx, "rows_live_mean") == pytest.approx(
        (9 * 4 + 5 + 2) / 11)
    note = ctx["notes"]["iteration_gaps"]
    assert note["records"] == 12 and note["gaps"] == 43
    assert note["p95_all_ms"] == pytest.approx(harness.quantile(
        plain + [50.0] * 4 + [12.5] + [30.0] * 2, 0.95))
    # Row-milliseconds over the decode-only median (14): request 7 had
    # 4 x 36 behind its own chunk and half of 2 x 16 behind the shared.
    assert note["stalled_row_ms_by_rid"] == [["7", 160.0], ["9", 16.0]]


def test_a_speculative_windows_further_tokens_are_gaps_of_zero():
    ctx = made([iteration(10, 2, (8.0, 2), tokens=7)])
    assert read_gaps(ctx, "decode_only_p95_ms") == pytest.approx(
        harness.quantile([0.0] * 5 + [8.0] * 2, 0.95))
    assert ctx["notes"]["iteration_gaps"]["gaps"] == 7


def test_gap_readers_find_nothing_where_there_is_nothing():
    # No trace; a program that leaves no such record (the parent).
    assert read_gaps({"trace": None}, "chunk_share_pct") is None
    parent = made([("serve.step", 10, 5, {})])
    for what in ("chunk_share_pct", "decode_only_p95_ms", "chunk_p50_ms",
                 "rows_live_mean"):
        assert read_gaps(parent, what) is None
    # Records, and none of the kind asked for.
    only_plain = made(RECORDS[1:4])
    assert read_gaps(only_plain, "chunk_share_pct") == 0.0
    assert read_gaps(only_plain, "chunk_p50_ms") is None
    only_prefill = made([iteration(80, 0, chunks="9")])
    assert read_gaps(only_prefill, "rows_live_mean") is None
    assert read_gaps(only_prefill, "chunk_share_pct") is None
    with pytest.raises(ValueError):
        read_gaps(made(RECORDS), "p99")


def idle_pct(ctx):
    return span_no_device_window_pct.read(ctx, span="serve.idle",
                                          beside="serve.iteration")


def test_idle_inside_the_wait_for_work_is_a_share_of_the_window():
    # The device runs 10-30 and 50-60; the engine waited for work over
    # -20..12 (clipped to 0..12, 10 of it with the device idle), 28..50
    # (20 idle) and 95..130 (clipped to 95..100): 35 of 100 ms. The 10
    # ms from 60 with no work span are not the load's.
    ctx = made([("serve.idle", -20, 32, {}), ("serve.idle", 28, 22, {}),
                ("serve.idle", 95, 35, {}), RECORDS[1]],
               ops=[(10, 20), (50, 10)])
    assert idle_pct(ctx) == pytest.approx(35.0)
    assert idle_pct(ctx) <= 100.0 * (1 - 30 / 100)  # device_idle_pct


def test_an_engine_that_never_waited_reads_zero_and_the_parent_nothing():
    busy = made([RECORDS[1]], ops=[(0, 100)])
    assert idle_pct(busy) == 0.0
    parent = made([("serve.step", 10, 5, {})], ops=[(10, 5)])
    assert idle_pct(parent) is None
    assert idle_pct({"trace": None}) is None


def test_ingress_mean_is_of_the_spans_that_start_in_the_window():
    ctx = made([("serve.ingress", -3, 50, {}), ("serve.ingress", 10, 2, {}),
                ("serve.ingress", 40, 6, {}), ("serve.ingress", 99, 40, {})])
    assert span_ms_mean.read(ctx, span="serve.ingress") == pytest.approx(
        (2 + 6 + 40) / 3)
    assert span_ms_mean.read(made([]), span="serve.ingress") is None


# --------------------------------------------------- a real CPU capture


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """A capture around two overlapping requests on a tiny engine, the
    second sent once the first decodes: → (reader context, the
    requests' records)."""
    import jax
    import jax.numpy as jnp

    from ptype_tpu import trace
    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.serve_engine import PagedGeneratorActor
    from ptype_tpu.serve_engine import engine as engine_mod

    trace.disable()
    logdir = str(tmp_path_factory.mktemp("engine-loop-capture"))
    engine = PagedGeneratorActor(tfm.preset("tiny", dtype=jnp.float32),
                                 n_slots=2, block_tokens=16,
                                 prefill_chunk=16)
    recs = []
    enq = engine.ledger.enqueued

    def enqueued(*a, **kw):
        recs.append(enq(*a, **kw))
        return recs[-1]

    engine.ledger.enqueued = enqueued
    try:
        engine.Generate(np.arange(1, 41, dtype=np.int32)[None], 3)
        del recs[:]
        # A caller leaves from inside its last pass, whose record is
        # stamped as the pass closes: let that be before the capture.
        threading.Event().wait(0.1)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                # The engine waits, and looks up to find a listener.
                threading.Event().wait(3 * engine_mod.IDLE_LOOK_S)
                first = threading.Thread(target=engine.Generate, args=(
                    np.arange(3, 63, dtype=np.int32)[None], 40))
                first.start()
                deadline = threading.Event()
                while not (recs and len(recs[0].tok_t) >= 2):
                    assert not deadline.wait(0.002)
                engine.Generate(np.arange(5, 55, dtype=np.int32)[None], 8)
                first.join(timeout=120)
                assert not first.is_alive()
                # A caller leaves from inside its last pass: with the
                # thread gone every record is in the capture.
                engine.close()
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.close()
    path = xplane.find_xplane(logdir)
    return ({"trace": xplane.read(path), "xstats": xstats.read(path),
             "notes": {}, "counters": {}}, recs)


def test_capture_holds_the_records_and_their_gaps_are_the_requests(capture):
    ctx, recs = capture
    want = sorted((b - a) * 1e3 for r in recs
                  for a, b in zip(r.tok_t, r.tok_t[1:]))
    assert len(want) == 39 + 7
    assert read_gaps(ctx, "chunk_share_pct") is not None
    note = ctx["notes"]["iteration_gaps"]
    assert note["gaps"] == len(want)
    assert note["p95_all_ms"] == pytest.approx(
        harness.quantile(want, 0.95), abs=2e-3)
    # The second request's chunks (50 tokens by 16) rode passes that
    # carried the first one's steps; it is the one rows stalled behind.
    assert 0 < read_gaps(ctx, "chunk_share_pct") < 50
    assert note["stalled_row_ms_by_rid"][0][0] == str(recs[1].rid)
    assert read_gaps(ctx, "chunk_p50_ms") > 0
    assert read_gaps(ctx, "decode_only_p95_ms") > 0
    assert 1.0 <= read_gaps(ctx, "rows_live_mean") <= 2.0


def test_capture_holds_the_wait_for_work_and_the_ingress(capture):
    from ptype_tpu.serve_engine import engine as engine_mod

    ctx, recs = capture
    lo, hi = xplane.window(ctx["trace"])
    # No device plane on a CPU: all of the wait is without the device.
    got = idle_pct(ctx)
    assert got >= 100.0 * (2 * engine_mod.IDLE_LOOK_S * 1e9) / (hi - lo)
    assert got < 100.0
    ingress = xstats.host_events(ctx["xstats"], lo, hi, "serve.ingress")
    assert sorted(int(e[3]["prompt_tokens"]) for e in ingress) == [50, 60]
    assert span_ms_mean.read(ctx, span="serve.ingress") == pytest.approx(
        sum((r.t_enqueue - r.t_call) * 1e3 for r in recs) / 2, abs=1.0)
    firsts = xstats.host_events(ctx["xstats"], lo, hi, "serve.first_token")
    assert all(float(e[3]["ingress_ms"]) > 0 for e in firsts)
    assert all("prefill_host_ms" not in e[3] for e in firsts)


# ------------------------------------------------------- the manifest


def test_manifest_holds_with_the_new_entries():
    m = manifest.load()
    assert manifest.check(m) == []
    new = m["per_layer"][-len(NAMES):]
    assert [x["name"] for x in new] == [n + ".chat" for n in NAMES]
    for x in new:
        assert x["source"] == "program_span"
        assert x["layer"] == "engine loop"
        assert x["workloads"] == [CELL]
    assert {x["name"]: x["moves"] for x in new}[
        "ttft_ingress_ms_mean.chat"] == "ttft_mean_ms"


@pytest.mark.parametrize("suffix", sorted(CELLS))
@pytest.mark.parametrize("name", NAMES)
def test_every_new_metric_file_binds_its_reader(name, suffix):
    """One file a metric a serving cell, the cell its own where the
    reader takes one; ``BENCHMARK.json`` lists the chat cell's (PERF.md
    §7 says what keeps the others out of it)."""
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           f"{name}.{suffix}.json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    inspect.signature(reader.read).bind({}, **spec["params"])
    if "cell" in spec["params"]:
        assert spec["params"]["cell"] == CELLS[suffix]
    assert reader.read({"trace": None}, **spec["params"]) is None
