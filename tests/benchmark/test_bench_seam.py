"""The readers that read what the program writes into a capture (ISSUE
25): the stats decoder against ``ProfileData`` on a real CPU capture of a
tiny engine behind its gateway (every span of the seam, nested, on one
clock, one first-token record a request), then each new reader on
hand-made structures, the case where it finds nothing included."""

import importlib
import inspect
import json
import os
import sys
import time
from unittest import mock

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest, xplane, xstats  # noqa: E402
from benchmark.readers import (bytes_touched_x, first_token_ms,  # noqa: E402
                               host_ms_per_span, program_time_pct,
                               scope_time_pct, span_ms_mean, span_share_pct)

MS = 1_000_000
CELL = "mistral-7b.serve-chat"
STEP = r"^jit_engine_step\("
PREFILL = r"^jit_prefill_chunk\("
N_REQ = 3


# --------------------------------------------------- a real CPU capture


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """(stats structure, plain structure) of a capture around three
    requests through gateway -> socket -> paged engine, tracing off."""
    import jax
    import jax.numpy as jnp

    from ptype_tpu import actor as actor_mod
    from ptype_tpu import trace
    from ptype_tpu.actor import ActorServer
    from ptype_tpu.coord.core import CoordState
    from ptype_tpu.coord.local import LocalCoord
    from ptype_tpu.gateway import GatewayConfig, InferenceGateway
    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.registry import CoordRegistry
    from ptype_tpu.serve_engine import PagedGeneratorActor

    trace.disable()
    logdir = str(tmp_path_factory.mktemp("seam-capture"))
    state = CoordState(sweep_interval=0.1)
    registry = CoordRegistry(LocalCoord(state), lease_ttl=5.0)
    engine = PagedGeneratorActor(tfm.preset("tiny", dtype=jnp.float32),
                                 n_slots=2, block_tokens=16,
                                 prefill_chunk=8)
    server = ActorServer("127.0.0.1", 0)
    gw = reg = None
    with mock.patch.object(actor_mod, "lookup_local", lambda a, p: None):
        try:
            server.register(engine, "Generator")
            server.serve()
            reg = registry.register("llm-seam", "r0", "127.0.0.1",
                                    server.port)
            gw = InferenceGateway(registry, "llm-seam", GatewayConfig(
                probe_interval_s=0.1, default_deadline_s=60.0))
            deadline = time.monotonic() + 10
            while gw.pool.n_healthy() < 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            prompts = [np.arange(1 + i, 21 + i, dtype=np.int32)[None]
                       for i in range(N_REQ)]  # 20 tokens: chunks 8+8+4
            gw.generate(prompts[0], 4)  # compile outside the capture
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(logdir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                    for p in prompts:
                        assert gw.generate(p, 4).shape[-1] == 4
                # The last answer leaves from inside the iteration that
                # retires it: a capture stopped now can hold that
                # ``serve.step``'s phases and not the step. With its
                # work done the loop thread ends at once, and every
                # region it opened has closed.
                engine.close()
            finally:
                jax.profiler.stop_trace()
        finally:
            if gw is not None:
                gw.close()
            if reg is not None:
                reg.close()
            server.close()
            engine.close()
            state.close()
    path = xplane.find_xplane(logdir)
    return xstats.read(path), xplane.read(path)


def _events(xs, name):
    """[(line index, start, end, stats)] of the events called ``name``."""
    return [(i, e[1], e[1] + e[2], e[3] if len(e) > 3 else {})
            for p in xs["planes"] if p["name"].startswith("/host:")
            for i, ln in enumerate(p["lines"]) for e in ln["events"]
            if e[0] == name]


def _inside(xs, child, parent):
    """Every ``child`` lies inside some ``parent`` on its own thread."""
    parents = _events(xs, parent)
    kids = _events(xs, child)
    assert kids, child
    return all(any(pl == kl and ps <= ks and ke <= pe
                   for pl, ps, pe, _ in parents)
               for kl, ks, ke, _ in kids)


def test_decoder_agrees_with_profile_data(capture):
    xs, tr = capture
    assert [p["name"] for p in xs["planes"]] == \
        [p["name"] for p in tr["planes"]]
    for a, b in zip(xs["planes"], tr["planes"]):
        assert [ln["name"] for ln in a["lines"]] == \
            [ln["name"] for ln in b["lines"]]
        for la, lb in zip(a["lines"], b["lines"]):
            assert [e[:3] for e in la["events"]] == lb["events"]


@pytest.mark.parametrize("child,parent", [
    ("serve.step/blocks", "serve.step"),
    ("serve.step/dispatch", "serve.step"),
    ("serve.step/fetch", "serve.step"),
    ("serve.step/emit", "serve.step"),
    ("serve.prefill/host", "serve.prefill"),
    ("serve.prefill/chunk", "serve.prefill"),
    ("serve.prefill/fetch", "serve.prefill/chunk"),
    ("gateway.admit", "gateway.request"),
    ("gateway.route", "gateway.request"),
    ("rpc.call", "gateway.request"),
    ("serve.first_token", xplane.WINDOW_SPAN),
    ("serve.retire", xplane.WINDOW_SPAN),
])
def test_capture_holds_the_seams_spans_nested(capture, child, parent):
    xs, _ = capture
    if parent == xplane.WINDOW_SPAN:  # another thread: by time alone
        (_, lo, hi, _), = _events(xs, parent)
        assert all(lo <= s <= hi for _, s, _, _ in _events(xs, child))
    else:
        assert _inside(xs, child, parent)


def test_capture_holds_upload_admit_and_the_handler(capture):
    xs, _ = capture
    # An upload only follows a change of the slots' state; admission
    # runs outside serve.prefill; the actor's handler is on the path.
    assert _inside(xs, "serve.step/upload", "serve.step")
    assert _events(xs, "serve.admit")
    assert _events(xs, "actor/Generator.Generate")
    step = _events(xs, "serve.step")
    assert all(not (s < e2 and s2 < e) for _, s, e, _ in step
               for _, s2, e2, _ in _events(xs, "serve.admit"))
    assert all(not st for _, _, _, st in step)  # bare: no metadata


def test_one_first_token_record_a_request(capture):
    xs, _ = capture
    recs = [st for _, _, _, st in _events(xs, "serve.first_token")]
    assert len(recs) == N_REQ
    assert len({r["rid"] for r in recs}) == N_REQ
    for r in recs:
        assert r["prompt_tokens"] == 20 and r["chunks"] >= 1
        assert r["queue_ms"] >= 0 and r["admitted_ms"] > 0
    chunks = _events(xs, "serve.prefill/chunk")
    assert {c[3]["rid"] for c in chunks} == {r["rid"] for r in recs}
    retired = [st for _, _, _, st in _events(xs, "serve.retire")]
    assert [r["reason"] for r in retired] == ["complete"] * N_REQ
    assert all(r["tokens_out"] == 4 for r in retired)


def test_span_readers_on_the_capture(capture):
    xs, tr = capture
    ctx = {"trace": tr, "xstats": xs, "notes": {}, "counters": {}}
    share = span_share_pct.read(ctx, span="serve.step/fetch",
                                inside="serve.step")
    assert 0 < share < 100
    assert span_ms_mean.read(ctx, span="gateway.admit") > 0
    queue = first_token_ms.read(ctx, cell=CELL, what="queue")
    assert queue is not None and queue >= 0
    # No device plane on a CPU: the device's part is not to be had.
    assert first_token_ms.read(ctx, cell=CELL, what="prefill",
                               program=PREFILL) is None


# ------------------------------------------------ hand-made structures


def op(name, start_ms, dur_ms, pid, tf_op="", nbytes=0):
    return [f"%{name} = x", int(start_ms * MS), int(dur_ms * MS),
            {"program_id": pid, "tf_op": tf_op, "bytes_accessed": nbytes}]


def made(ops, modules, host=(), window=(0, 100)):
    """(stats structure, plain structure) of one device and one thread."""
    host = [[xplane.WINDOW_SPAN, window[0] * MS,
             (window[1] - window[0]) * MS, {}],
            *[[n, int(s * MS), int(d * MS), st] for n, s, d, st in host]]
    xs = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                [n, int(s * MS), int(d * MS), {}] for n, s, d in modules]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}
    tr = {"planes": [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
        for ln in p["lines"]]} for p in xs["planes"]]}
    return xs, tr


def ctx_of(xs, tr, **counters):
    return {"trace": tr, "xstats": xs, "notes": {}, "counters": counters}


DECODE = "jit(engine_step)/while/body/closed_call/"


def decode_trace(scoped=True):
    """Two 20-ms runs of the decode program (id 7) and one prefill
    chunk (id 9); each decode run: gather 6, write 2, attention 4,
    matmuls 5, embed 1, a compiler's copy 2."""
    def path(scope, prim):
        return DECODE + (f"{scope}/" if scoped else "") + prim + ":"

    ops = []
    for t0 in (10, 40):
        ops += [
            op("while.2", t0, 20, 7),  # the layer scan: a container
            op("fusion.1", t0, 6, 7, path("kv_gather", "gather"), 600),
            op("fusion.2", t0 + 6, 2, 7, path("kv_write", "scatter"), 200),
            op("fusion.3", t0 + 8, 4, 7, path("attn", "dot_general"), 400),
            op("fusion.4", t0 + 12, 3, 7, path("mlp", "dot_general"), 300),
            op("fusion.5", t0 + 15, 2, 7,
               path("qkv", "bsd,dhk->bshk/dot_general"), 200),
            op("fusion.6", t0 + 17, 1, 7,
               "jit(engine_step)/" + ("embed/" if scoped else "")
               + "gather:", 100),
            op("copy.9", t0 + 18, 2, 7, "", 200),
        ]
    ops.append(op("fusion.1", 70, 10, 9, "jit(prefill_chunk)/"
                  + ("mlp/" if scoped else "") + "dot_general:"))
    modules = [("jit_engine_step(7)", 10, 20), ("jit_engine_step(7)", 40, 20),
               ("jit_prefill_chunk(9)", 70, 10)]
    return ops, modules


@pytest.mark.parametrize("path,scope", [
    ("jit(engine_step)/while/body/closed_call/kv_gather/gather:",
     "kv_gather"),
    ("jit(step)/transpose(jvp(mlp))/bsd,df->bsf/dot_general:", "mlp"),
    ("jit(step)/transpose(jvp(loss))/while/body/closed_call/checkpoint/"
     "head/...d,dv->...v/dot_general:", "head"),
    ("jit(step)/jvp(loss)/while/body/closed_call/jit(take_along_axis)/x:",
     "loss"),
    ("jit(step)/optimizer/jit(clip)/mul:", "optimizer"),
    ("jit(engine_step)/while/body/closed_call/gather:", None),
    ("jit(sample_first)/jit(argmax):", None),
    ("", None),
])
def test_scope_of_is_the_innermost_scope_on_the_path(path, scope):
    assert xstats.scope_of(path) == scope


def test_every_op_of_a_program_goes_to_one_bucket():
    xs, _ = made(*decode_trace())
    got = xstats.program_ops(xs, 0, 100 * MS, STEP)
    assert got["runs"] == 2 and got["module_ns"] == 40 * MS
    assert sum(got["by_scope"].values()) == 40 * MS  # the while left out
    assert got["by_scope"][None] == 4 * MS
    assert got["bytes"] == 4000
    assert xstats.program_ops(xs, 0, 100 * MS, r"^jit_nothing\(") is None
    # Clipped to the window, ops and runs alike.
    half = xstats.program_ops(xs, 20 * MS, 45 * MS, STEP)
    assert half["module_ns"] == 15 * MS
    assert sum(half["by_scope"].values()) == 15 * MS


@pytest.mark.parametrize("params,want", [
    ({"scopes": ["kv_gather"]}, 30.0),
    ({"scopes": ["kv_write"]}, 10.0),
    ({"scopes": ["attn"]}, 20.0),
    ({"scopes": ["qkv", "attn_out", "mlp", "head"]}, 25.0),
    ({"unscoped": True}, 10.0),
])
def test_scope_time_pct(params, want):
    xs, tr = made(*decode_trace())
    got = scope_time_pct.read(ctx_of(xs, tr), cell=CELL, program=STEP,
                              **params)
    assert got == pytest.approx(want)


def test_scope_time_pct_finds_nothing_in_an_unscoped_program():
    """A commit from before the model named its parts: every share is
    left out, the unscoped one too (it would read 100)."""
    xs, tr = made(*decode_trace(scoped=False))
    for params in ({"scopes": ["kv_gather"]}, {"unscoped": True}):
        assert scope_time_pct.read(ctx_of(xs, tr), cell=CELL,
                                   program=STEP, **params) is None
    xs, tr = made(*decode_trace())
    assert scope_time_pct.read(ctx_of(xs, tr), cell=CELL,
                               program=r"^jit_step\(",
                               scopes=["mlp"]) is None
    assert scope_time_pct.read({"trace": None}, cell=CELL, program=STEP,
                               scopes=["mlp"]) is None


def test_program_time_pct_and_bytes_touched():
    xs, tr = made(*decode_trace())
    ctx = ctx_of(xs, tr, decode_needed_bytes=1000.0)
    assert program_time_pct.read(ctx, pattern=PREFILL) == pytest.approx(10.0)
    assert program_time_pct.read(ctx, pattern=r"^jit_run\(") is None
    assert bytes_touched_x.read(ctx, cell=CELL, program=STEP) == \
        pytest.approx(4.0)
    assert bytes_touched_x.read(ctx_of(xs, tr), cell=CELL,
                                program=STEP) is None
    assert bytes_touched_x.read(ctx, cell=CELL,
                                program=r"^jit_nothing\(") is None


HOST = [
    # Two iterations: the device runs 10-30 and 40-60.
    ("serve.admit", 5, 1, {}),
    ("serve.step", 6, 26, {}),
    ("serve.step/blocks", 6, 1, {}),
    ("serve.step/dispatch", 7, 3, {}),
    ("serve.step/fetch", 10, 21, {}),
    ("serve.step/emit", 31, 1, {}),
    ("serve.admit", 33, 2, {}),
    ("serve.step", 36, 26, {}),
    ("serve.step/dispatch", 37, 3, {}),
    ("serve.step/fetch", 40, 21, {}),
    ("serve.step/emit", 61, 1, {}),
    ("gateway.admit", 2, 1, {}),
    ("gateway.admit", 34, 3, {}),
    ("serve.first_token", 62, 0, {"rid": 1, "queue_ms": 3.0,
                                  "reserve_ms": 1.0, "admitted_ms": 30.0}),
    ("serve.first_token", 63, 0, {"rid": 2, "queue_ms": 5.0,
                                  "reserve_ms": 0.0, "admitted_ms": 10.0}),
]


def test_engine_loop_readers():
    xs, tr = made(*decode_trace(), host=HOST)
    ctx = ctx_of(xs, tr)
    # Host time in serve.step + serve.admit with the device idle:
    # admit 1 + 2, step 6-10, 30-32, 36-40, 60-62 = 15 ms, 2 iterations.
    got = host_ms_per_span.read(ctx, spans=["serve.step", "serve.admit"],
                                per="serve.step")
    assert got == pytest.approx(7.5)
    split = ctx["notes"]["idle_ms_per_iter_by_innermost_span"]
    assert split["serve.step/dispatch"] == pytest.approx(3.0)
    assert split["serve.step/emit"] == pytest.approx(1.0)
    assert "serve.step/fetch" in split  # the tail behind the device
    assert span_share_pct.read(ctx, span="serve.step/fetch",
                               inside="serve.step") == \
        pytest.approx(100 * 42 / 52)
    assert span_ms_mean.read(ctx, span="gateway.admit") == pytest.approx(2.0)


def test_engine_loop_readers_find_nothing_on_a_parent():
    """No phase spans, no ``serve.admit``: left out, not raised."""
    bare = [h for h in HOST if h[0] == "serve.step"]
    xs, tr = made(*decode_trace(), host=bare)
    ctx = ctx_of(xs, tr)
    assert host_ms_per_span.read(ctx, spans=["serve.step", "serve.admit"],
                                 per="serve.step") is None
    assert span_share_pct.read(ctx, span="serve.step/fetch",
                               inside="serve.step") is None
    assert span_ms_mean.read(ctx, span="gateway.admit") is None
    for what in ("queue", "prefill", "stall"):
        assert first_token_ms.read(ctx, cell=CELL, what=what,
                                   program=PREFILL) is None


def test_first_token_readers():
    xs, tr = made(*decode_trace(), host=HOST)
    ctx = ctx_of(xs, tr)
    read = first_token_ms.read
    assert read(ctx, cell=CELL, what="queue") == pytest.approx(4.5)
    # One 10-ms prefill program over two records.
    assert read(ctx, cell=CELL, what="prefill",
                program=PREFILL) == pytest.approx(5.0)
    assert read(ctx, cell=CELL, what="stall",
                program=PREFILL) == pytest.approx(15.0)
    assert read(ctx, cell=CELL, what="stall",
                program=r"^jit_run\(") is None
    # A record stamped outside the window is not of this window.
    xs, tr = made(*decode_trace(), host=HOST, window=(0, 62.5))
    assert read(ctx_of(xs, tr), cell=CELL, what="queue") == \
        pytest.approx(4.0)


def test_innermost_segments_of_nested_spans():
    segs = host_ms_per_span.innermost_segments(
        [["a", 0, 100], ["b", 10, 20], ["c", 12, 5], ["d", 50, 10],
         ["z", 200, 10]])
    assert segs == [[0, 10, "a"], [10, 12, "b"], [12, 17, "c"],
                    [17, 30, "b"], [30, 50, "a"], [50, 60, "d"],
                    [60, 100, "a"], [200, 210, "z"]]


# ------------------------------------------------------- the manifest


def test_new_entries_hold_and_name_their_readers():
    m = manifest.load()
    assert manifest.check(m) == []
    new = [x for x in m["per_layer"] if x["source"] == "program_span"
           or x["name"].startswith(("decode_", "prefill_time", "mlp_time",
                                    "loss_time", "optimizer_time",
                                    "ttft_prefill"))]
    assert len(new) == 17  # 16 of ISSUE 25, and decode_hbm_roofline.chat
    cells = {w["name"] for w in m["workloads"]}
    for x in new:
        with open(os.path.join(ROOT, "benchmark", "metrics",
                               x["name"] + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module("benchmark.readers."
                                         + spec["reader"])
        inspect.signature(reader.read).bind({}, **spec["params"])
        if "cell" in spec["params"]:
            assert [spec["params"]["cell"]] == x["workloads"]
            assert spec["params"]["cell"] in cells
