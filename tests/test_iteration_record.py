"""The engine loop's own account (ISSUE 37): one iteration record a
pass whose gaps are, one for one, the differences of the requests' token
stamps; ``serve.idle`` around the wait for work; a request's ingress
before its enqueue stamp; nothing built when nothing listens."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu import metrics as metrics_mod
from ptype_tpu import trace
from ptype_tpu.health.serving import ServingLedger, record_gaps
from ptype_tpu.models import transformer as tfm
from ptype_tpu.serve_engine import PagedGeneratorActor
from ptype_tpu.serve_engine import engine as engine_mod

CFG = tfm.preset("tiny", dtype=jnp.float32)
#: (prompt tokens, new tokens): one alone, under which the engine's
#: programs compile, then three that overlap, each sent once the one
#: before it decodes, so that its chunks ride passes that carry steps.
ASKS = ((40, 3), (70, 50), (45, 40), (90, 12))


def _actor(**kw):
    return PagedGeneratorActor(CFG, n_slots=4, block_tokens=16,
                               prefill_chunk=32, n_blocks=48, **kw)


def _wait_for(cond, timeout=30.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end, "timed out"
        time.sleep(0.005)


@pytest.fixture(scope="module")
def drove():
    """One dense engine driven through ASKS under the flight recorder:
    → (iteration records, request records by rid, spans, summary)."""
    store = trace.enable("iteration-test")
    recs = {}
    try:
        actor = _actor()
        enq = actor.ledger.enqueued

        def enqueued(*a, **kw):
            rec = enq(*a, **kw)
            recs[rec.rid] = rec
            return rec

        actor.ledger.enqueued = enqueued
        try:
            rng = np.random.default_rng(37)
            errs = []

            def ask(n, new, after=None):
                try:
                    if after is not None:
                        _wait_for(lambda: after in recs
                                  and len(recs[after].tok_t) >= 2)
                    with trace.span("handler"):
                        out = actor.Generate(
                            rng.integers(1, CFG.vocab_size, n)[None], new)
                    assert np.asarray(out).shape == (1, new)
                except Exception as e:  # noqa: BLE001 — shown below
                    errs.append(e)

            ask(*ASKS[0])
            threads = [threading.Thread(target=ask, args=(*a, after))
                       for a, after in zip(ASKS[1:], (None, 2, 3))]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=300)
            assert not errs and not any(t.is_alive() for t in threads)
        finally:
            # A caller is released before its last pass's scope closes:
            # the ring is whole once the engine thread is gone.
            actor.close()
        with actor.ledger._lock:
            iters = list(actor.ledger._iters)
        summary = actor.ledger.summary()
    finally:
        trace.disable()
    return iters, recs, store.spans(), summary


def test_seq_has_no_holes_and_every_pass_did_something(drove):
    iters = drove[0]
    seqs = [r["seq"] for r in iters]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert all(r["chunks"] or r["active"] for r in iters)
    assert all(r["iter_ms"] >= r["step_ms"] for r in iters)


def test_gaps_are_the_differences_of_the_token_stamps(drove):
    iters, recs = drove[0], drove[1]
    want = sorted((b - a) * 1e3 for r in recs.values()
                  for a, b in zip(r.tok_t, r.tok_t[1:]))
    got = sorted(g for it in iters for g, rows in record_gaps(it)
                 for _ in range(rows))
    assert len(got) == len(want) == sum(new - 1 for _, new in ASKS)
    assert got == pytest.approx(want, abs=2e-3)
    # A row's first gap is its own: one a request, none shared.
    assert sum(len(it["new_gaps_ms"]) for it in iters) == len(ASKS)
    # The requests overlapped: some pass carried a chunk while rows
    # waited on it, and its gap holds the chunk's time.
    carried = [it for it in iters if it["chunks"] and it["gap_rows"]]
    assert carried
    assert all(it["gap_ms"] >= it["prefill_ms"] for it in carried)


def test_chunk_rids_name_the_requests_whose_prefill_a_pass_carried(drove):
    iters, recs = drove[0], drove[1]
    for rid, rec in recs.items():
        mine = [it for it in iters if rid in it["chunk_rids"]]
        assert sum(it["chunk_rids"].count(rid) for it in mine) \
            == len(rec.chunks)
        # The request's last chunk ends at its whole prompt.
        assert max(it["chunk_ctx"] for it in mine) == rec.prompt_tokens
    assert sum(it["chunks"] for it in iters) \
        == sum(len(r.chunks) for r in recs.values())
    assert all(len(it["chunk_rids"]) == it["chunks"] for it in iters)
    assert sum(it["prefill_tokens"] for it in iters) \
        == sum(n for n, _ in ASKS)


def test_the_records_went_through_the_seam_as_they_are_in_the_ring(drove):
    iters, spans = drove[0], drove[2]
    sent = {s.attrs["seq"]: s for s in spans if s.name == "serve.iteration"}
    for it in iters:
        a = sent[it["seq"]].attrs
        assert a["chunk_rids"] == ":".join(map(str, it["chunk_rids"]))
        assert a["new_gaps_ms"] == ":".join(map(str, it["new_gaps_ms"]))
        for k in ("step_ms", "active", "decode_tokens", "prefill_tokens",
                  "prefill_ms", "stall_ms", "iter_ms", "chunks",
                  "chunk_ctx", "gap_ms", "gap_rows"):
            assert a[k] == it[k], k
        assert sent[it["seq"]].dur_s < 1e-3


def test_info_reduces_the_ring(drove):
    iters, summary = drove[0], drove[3]
    live = [it["active"] for it in iters if it["active"]]
    assert summary["iterations"] == len(iters)
    assert summary["rows_live_mean"] == pytest.approx(
        sum(live) / len(live), abs=0.01)
    gaps = [(g, rows, bool(it["chunks"])) for it in iters
            for g, rows in record_gaps(it)]
    share = (sum(rows for _, rows, c in gaps if c)
             / sum(rows for _, rows, _ in gaps))
    assert 0 < share < 1
    assert summary["chunk_gap_share"] == pytest.approx(share, abs=1e-4)
    plain = sorted(g for g, rows, c in gaps if not c for _ in range(rows))
    chunked = sorted(g for g, rows, c in gaps if c for _ in range(rows))

    def rank(xs, q):  # the value under which a share q of them lies
        return next(x for i, x in enumerate(xs) if i + 1 >= q * len(xs))

    assert summary["gap_p95_decode_only_ms"] == rank(plain, 0.95)
    assert summary["gap_p50_chunk_ms"] == rank(chunked, 0.5)
    assert "active_mean" not in summary


def test_first_token_account_starts_at_the_callers_entry(drove):
    recs, spans = drove[1], drove[2]
    handlers = {s.span_id: s for s in spans if s.name == "handler"}
    ingress = [s for s in spans if s.name == "serve.ingress"
               and s.parent_id in handlers]
    assert len(ingress) == len(ASKS)
    assert sorted(s.attrs["prompt_tokens"] for s in ingress) \
        == sorted(n for n, _ in ASKS)
    assert all(s.attrs["rows"] == 1 for s in ingress)
    # The request's own spans stay the handler's children, beside it.
    admits = [s for s in spans if s.name == "serve.admit"
              and s.parent_id in handlers]
    assert len(admits) == len(ASKS)
    firsts = {s.attrs["rid"]: s.attrs for s in spans
              if s.name == "serve.first_token"}
    for rid, rec in recs.items():
        sp = firsts[rid]
        assert "prefill_host_ms" not in sp
        assert sp["ingress_ms"] > 0
        assert rec.t_call < rec.t_enqueue
        assert (sp["ingress_ms"] + sp["queue_ms"] + sp["reserve_ms"]
                + sp["admitted_ms"]) == pytest.approx(
            (rec.t_first - rec.t_call) * 1e3, abs=0.01)
    # The span closes at the enqueue stamp, not at the handler's return.
    by_tokens = {s.attrs["prompt_tokens"]: s for s in ingress}
    for rec in recs.values():
        assert by_tokens[rec.prompt_tokens].dur_s * 1e3 == pytest.approx(
            (rec.t_enqueue - rec.t_call) * 1e3, abs=5.0)


# ----------------------------------------------------------- serve.idle


def test_idle_span_covers_the_wait_for_work_and_nothing_else():
    store = trace.enable("idle-test")
    try:
        actor = _actor()
        try:
            idle = lambda: [s for s in store.spans()  # noqa: E731
                            if s.name == "serve.idle"]
            time.sleep(0.05)
            # Open while the engine waits: nothing recorded yet.
            assert idle() == []
            t_ask = time.time()
            actor.Generate(np.arange(1, 41, dtype=np.int32)[None], 4)
            t_done = time.time()
            # Closed by the enqueue's notify, before any of the work.
            first, = idle()
            assert first.dur_s >= 0.05
            assert first.start_s + first.dur_s <= t_ask + 0.5
            work = [s for s in store.spans() if s.name in (
                "serve.prefill/chunk", "serve.iteration")]
            assert work and all(
                s.start_s >= first.start_s + first.dur_s - 1e-3
                for s in work)
            time.sleep(0.05)
            assert len(idle()) == 1  # the second is open again
        finally:
            actor.close()
        _wait_for(lambda: len(idle()) == 2)
        second = idle()[1]
        # It opened only once no row was live: after the last record.
        assert second.start_s >= max(s.start_s for s in work) - 1e-3
        assert second.start_s <= t_done + 0.5
    finally:
        trace.disable()


def test_a_listener_that_starts_on_an_idle_engine_sees_it_idle():
    trace.disable()
    actor = _actor()
    try:
        time.sleep(0.05)  # in its wait, with nobody listening
        store = trace.enable("late-listener")
        t_on = time.time()
        try:
            time.sleep(3 * engine_mod.IDLE_LOOK_S)
            actor.Generate(np.arange(1, 41, dtype=np.int32)[None], 2)
            idle, = [s for s in store.spans() if s.name == "serve.idle"]
            assert t_on <= idle.start_s <= t_on + 2 * engine_mod.IDLE_LOOK_S
            assert idle.dur_s >= engine_mod.IDLE_LOOK_S
        finally:
            trace.disable()
    finally:
        actor.close()


# ------------------------------------------------------- nothing armed


def test_with_nothing_armed_a_pass_builds_no_annotation_and_no_string(
        monkeypatch):
    """The pass pays its counters and the ring's record; the seam is not
    touched, so no attribute string is joined."""
    trace.disable()
    assert not trace.capturing()
    opened = []
    real = trace.span_from

    def span_from(tp, name, **attrs):
        opened.append(name)
        return real(tp, name, **attrs)

    monkeypatch.setattr(trace, "span_from", span_from)
    led = ServingLedger(registry=metrics_mod.MetricsRegistry())
    rec = led.enqueued(32, 4)
    led.admitted(rec)
    with led.iteration() as it:
        cm = led.chunk(rec, 32)
        cm.ctx = 32
        with cm:
            pass
        led.first_token(rec)
        it.step(1, 0.0)
        led.tokens_emitted((rec,))
    with led.iteration() as it:
        it.step(1, 0.0)
        led.tokens_emitted((rec,))
    assert opened == []
    with led._lock:
        a, b = led._iters
    assert (a["seq"], b["seq"]) == (1, 2)
    assert a["chunk_rids"] == (rec.rid,) and a["chunk_ctx"] == 32
    assert len(a["new_gaps_ms"]) == 1 and a["gap_rows"] == 0
    assert b["new_gaps_ms"] == () and b["gap_rows"] == 1
    assert b["gap_ms"] == pytest.approx(
        (rec.tok_t[2] - rec.tok_t[1]) * 1e3, abs=2e-3)
    # An entry stamp costs no span either.
    with led.ingress((1, 8)) as ing:
        assert ing._sp is trace._NOOP
    assert led.enqueued(8, 2, t_call=ing.t_call).t_call == ing.t_call


def test_a_pass_with_no_chunk_and_no_step_leaves_no_record():
    led = ServingLedger(registry=metrics_mod.MetricsRegistry())
    with led.iteration():
        pass
    assert led.registry.counter("serve.steps").value == 1
    assert "iterations" not in led.summary()
    with led.iteration(active=2):
        pass
    assert led.summary()["iterations"] == 1


def test_speculative_windows_further_tokens_are_gaps_of_zero():
    led = ServingLedger(registry=metrics_mod.MetricsRegistry())
    recs = [led.enqueued(8, 8) for _ in range(2)]
    for rec in recs:
        led.admitted(rec)
        led.first_token(rec)
    for counts in ((3, 1), (2, 4)):
        with led.iteration() as it:
            it.step(2, 0.0)
            led.tokens_emitted(recs, counts)
            it.decode_tokens = sum(counts)
    with led._lock:
        iters = list(led._iters)
    want = sorted((b - a) * 1e3 for r in recs
                  for a, b in zip(r.tok_t, r.tok_t[1:]))
    got = sorted(g for it in iters for g, rows in record_gaps(it)
                 for _ in range(rows))
    assert len(got) == len(want) == 10
    assert got == pytest.approx(want, abs=2e-3)
