"""The one seam into the device trace (ISSUE 25): named scopes in every
program of the model, one function that opens a region for every layer,
a request id and a first-token record with tracing off, stable names
for the Store trainer's programs, and a throughput clock that starts
behind the first step's compile."""

import re
import time

import jax
import jax.numpy as jnp
import pytest

from ptype_tpu import metrics as metrics_mod
from ptype_tpu import trace
from ptype_tpu.health.serving import ServingLedger
from ptype_tpu.models import generate as gen
from ptype_tpu.models import transformer as tfm
from ptype_tpu.parallel.mesh import build_mesh

BLOCK = ("embed", "qkv", "kv_write", "kv_gather", "attn", "attn_out",
         "mlp", "head")


def _scoped(text: str, scope: str) -> bool:
    """Is ``scope`` a component of some op's name: ``"qkv/dot_general"``,
    ``.../closed_call/mlp/...``, ``transpose(jvp(loss))/...``?"""
    return re.search(rf'["/(]{scope}[/)]', text) is not None


def _lowered(program: str) -> str:
    """The program as the engine or the trainer builds it, lowered."""
    cfg = tfm.preset("tiny")
    if program == "train":
        from ptype_tpu.train.trainer import (default_optimizer, init_state,
                                             make_train_step)

        mesh = build_mesh({"data": 1})
        opt = default_optimizer()
        state, _ = init_state(jax.random.PRNGKey(0), cfg, mesh, opt)
        toks = jnp.zeros((2, 32), jnp.int32)
        low = make_train_step(cfg, mesh, opt).lower(
            state, {"tokens": toks, "targets": toks})
        return low.as_text(debug_info=True)
    from ptype_tpu.serve_engine import PagedGeneratorActor

    eng = PagedGeneratorActor(cfg, n_slots=2, block_tokens=16,
                              prefill_chunk=16)
    try:
        if program == "decode":
            low = eng._engine_step.lower(
                False, eng.params, eng.pool.banks, eng._tok, eng._pos,
                eng._tables, eng._active, eng._keys, eng._eidx,
                eng._temps, eng._topk, eng._topp,
                gen.live_block_list(eng._tables, eng._nalloc,
                                    eng._active, eng.block_tokens))
        else:
            low = eng._chunk_prog(16).lower(
                eng.params, eng.pool.banks,
                jnp.zeros((1, 16), jnp.int32), jnp.int32(0),
                jnp.int32(16), jnp.zeros(eng.nb, jnp.int32))
    finally:
        eng.close()
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("program,scopes", [
    ("decode", BLOCK + ("sample",)),
    ("prefill_chunk", BLOCK),
    ("train", ("embed", "qkv", "attn", "attn_out", "mlp", "head", "loss",
               "optimizer")),
])
def test_every_scope_is_in_the_lowered_program(program, scopes):
    """Each part of the step tells the compiler its name (the op_name
    path a device trace carries), the same names in all three."""
    text = _lowered(program)
    for s in scopes:
        assert _scoped(text, s), (program, s)


def test_the_engines_programs_have_their_own_names():
    assert "jit_engine_step" in _lowered("decode")
    assert "jit_prefill_chunk" in _lowered("prefill_chunk")


# ------------------------------------------------------------ the seam


def _capture(tmp_path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    from benchmark import xplane, xstats

    return xstats.read(xplane.find_xplane(str(tmp_path)),
                       lambda plane, line: plane.startswith("/host:"))


def _named(xs, name):
    return [e for p in xs["planes"] for ln in p["lines"]
            for e in ln["events"] if e[0] == name]


def test_span_reaches_a_live_capture_with_tracing_off(tmp_path):
    """One function opens a region: with no flight recorder armed it is
    the no-op until a capture is live, then a profiler annotation of
    the same name whose attributes arrive as the event's stats."""
    trace.disable()
    assert trace.span("seam.probe") is trace._NOOP
    assert metrics_mod.annotate is trace.span

    def body():
        assert trace.capturing()
        with trace.span("seam.outer", rid=7, where="here") as sp:
            sp.set_attr("ignored", 1)  # no recorder: absorbed
            with metrics_mod.annotate("seam.inner"):
                time.sleep(0.002)

    xs = _capture(tmp_path, body)
    assert not trace.capturing()
    (outer,), (inner,) = _named(xs, "seam.outer"), _named(xs, "seam.inner")
    assert outer[3]["rid"] == 7 and outer[3]["where"] == "here"
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]
    assert inner[2] >= 2_000_000


def test_span_feeds_ring_capture_and_observer_at_once(tmp_path):
    seen = []
    rec = trace.enable("seam-test")
    trace.set_region_observer(lambda name, dur: seen.append(name))
    try:
        def body():
            with trace.span("seam.all", k=1) as sp:
                sp.set_attr("late", 2)

        xs = _capture(tmp_path, body)
    finally:
        trace.set_region_observer(None)
        trace.disable()
    assert len(_named(xs, "seam.all")) == 1
    (sp,) = [s for s in rec.spans() if s.name == "seam.all"]
    assert sp.attrs == {"k": 1, "late": 2} and sp.dur_s > 0
    assert seen == ["seam.all"]


def test_enable_resets_the_dump_rate_limit(tmp_path):
    """Two enable/dump rounds back to back: the second session's first
    post-mortem is not silenced by the first session's dump."""
    for i in range(2):
        d = tmp_path / f"round{i}"
        trace.enable(f"dump-{i}", dump_dir=str(d))
        try:
            with trace.span("work"):
                pass
            assert trace.maybe_dump("first of its session") is not None
            assert trace.maybe_dump("rate limited") is None
        finally:
            trace.disable()
        assert len(list(d.glob("flight-*.jsonl"))) == 1


# ------------------------------------------------ the first-token record


def test_rid_is_unique_per_ledger_with_tracing_off():
    trace.disable()
    reg = metrics_mod.MetricsRegistry()
    led, other = ServingLedger(registry=reg), ServingLedger(registry=reg)
    recs = [led.enqueued(8, 2) for _ in range(5)]
    assert [r.rid for r in recs] == [1, 2, 3, 4, 5]
    assert other.enqueued(8, 2).rid == 1
    led.admitted(recs[0])
    led.first_token(recs[0])
    led.retired(recs[0], "complete")
    assert led.records()[-1]["rid"] == 1


@pytest.mark.parametrize("refused", [False, True],
                         ids=["admitted-at-once", "waited-at-the-head"])
def test_first_token_split_adds_up_to_ttft(refused):
    led = ServingLedger(registry=metrics_mod.MetricsRegistry())
    with led.ingress((1, 40)) as ing:
        time.sleep(0.003)
        rec = led.enqueued(40, 4, t_call=ing.t_call)
    time.sleep(0.004)
    if refused:
        led.head_refused(rec)
        time.sleep(0.003)
    led.admitted(rec)
    with led.chunk(rec, 32):
        time.sleep(0.002)
    with led.chunk(rec, 8):
        time.sleep(0.002)
    led.first_token(rec)
    sp = rec.first_token_split()
    assert sp["rid"] == rec.rid and sp["chunks"] == 2
    assert sp["prompt_tokens"] == 40
    total = sp["queue_ms"] + sp["reserve_ms"] + sp["admitted_ms"]
    assert total == pytest.approx(rec.ttft_s() * 1e3, abs=1.0)
    assert (sp["reserve_ms"] > 2.0) is refused
    assert sp["queue_ms"] >= 3.0 and sp["admitted_ms"] >= 3.0
    assert "prefill_host_ms" not in sp
    # With the way in before them, the first-token time as the
    # caller's handler saw it.
    assert sp["ingress_ms"] >= 3.0
    assert sp["ingress_ms"] + total == pytest.approx(
        (rec.t_first - ing.t_call) * 1e3, abs=1.0)


def test_synthesized_spans_carry_the_rid():
    rec_store = trace.enable("rid-test")
    try:
        led = ServingLedger(registry=metrics_mod.MetricsRegistry())
        with trace.span("handler"):
            tp = trace.traceparent()
        rec = led.enqueued(8, 2, tp=tp)
        led.admitted(rec)
        with led.chunk(rec, 8):
            pass
        led.first_token(rec)
        led.retired(rec, "complete")
    finally:
        trace.disable()
    mine = {s.name: s for s in rec_store.spans() if s.name != "handler"}
    assert {"serve.admit", "serve.prefill.chunk[0]", "serve.decode",
            "serve.prefill/chunk", "serve.first_token",
            "serve.retire"} <= set(mine)
    assert all(s.attrs["rid"] == rec.rid for s in mine.values())
    assert mine["serve.retire"].attrs["reason"] == "complete"
    assert len({s.trace_id for s in mine.values()}) == 1


# ------------------------------------------------------ program names


def test_store_trainer_programs_have_their_own_names():
    """One StoreDPTrainer step on four virtual devices: the gradient
    program, the push and the apply are told apart by name (the
    four-chip cell's readers match them)."""
    from ptype_tpu.parallel.tensorstore import TensorStore
    from ptype_tpu.train.store_dp import StoreDPTrainer

    cfg = tfm.preset("tiny")
    mesh = build_mesh({"data": 4})
    tr = StoreDPTrainer(cfg, TensorStore(mesh))
    toks = jnp.zeros((8, 32), jnp.int32)
    stacked = toks.reshape(4, 2, 32)
    text = tr.grads_step.lower(
        tr.params(), {"tokens": stacked, "targets": stacked}
    ).as_text(debug_info=True)
    assert "jit_local_grads" in text
    for s in ("qkv", "mlp", "loss"):
        assert _scoped(text, s), s
    from ptype_tpu.parallel import collectives
    from ptype_tpu.train.trainer import default_optimizer, make_apply_fn

    assert make_apply_fn(default_optimizer()).__name__ == "optimizer_apply"
    push = collectives._bucket_all_reduce_fn(
        mesh, "data", "mean", ((4,),), "float32", 0, None, None, False,
        False)
    assert push.__name__ == "store_push"
    tr.step({"tokens": toks, "targets": toks})  # the names run together


def test_trainer_rates_start_behind_the_first_steps_compile():
    """``Trainer.step()``'s and ``throughput()``'s rates count steady
    steps only: the clock starts at the drain behind the first step."""
    from ptype_tpu.train.trainer import Trainer

    cfg = tfm.preset("tiny")
    tr = Trainer(cfg, build_mesh({"data": 1}), sync_every=0)
    toks = jnp.zeros((2, 32), jnp.int32)
    batch = {"tokens": toks, "targets": toks}
    real = tr._step_for(batch)
    slow_first = {"left": 1}

    def step(state, b):
        if slow_first["left"]:
            slow_first["left"] = 0
            time.sleep(0.6)  # a compile's worth
        return real(state, b)

    tr._steps[("tokens", "targets")] = step
    tr.step(batch)
    assert tr.throughput()["tokens_per_sec"] == 0.0
    for _ in range(3):
        tr.step(batch)
    tr.sync()
    assert tr._stats.steps == 3 and tr._stats.tokens == 3 * toks.size
    assert tr._stats.seconds < 0.5
    assert tr.throughput()["mfu"] > 0

