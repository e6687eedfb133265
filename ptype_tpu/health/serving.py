"""Serving observability plane: the request-lifecycle ledger (ISSUE 10).

The training side has a goodput ledger (health/goodput.py) that turns
``metrics.annotate`` regions into per-step attribution; the paged
serving engine (serve_engine/engine.py) had only an end-to-end latency
number at the gateway. This module is the serving analogue — a
:class:`ServingLedger` fed from metering seams inside the engine:

- **Request lifecycle**: every prompt row gets a
  :class:`RequestRecord` — queue wait (enqueue → head of line),
  reservation wait (head of line → pool reservation), every prefill
  chunk (wall start + duration + tokens), the first-token stamp, a
  per-token decode delta trail, and the retire reason (``complete`` /
  ``stop`` / ``cancelled`` / ``shed`` / ``error``). Retired records
  fold into **TTFT / TPOT / e2e histograms** (``serve.ttft_ms``,
  ``serve.tpot_ms``, ``serve.e2e_ms`` — the health
  :class:`~ptype_tpu.health.series.Sampler` stamps their ``.p99`` /
  ``.count`` series, which the ``ttft-p99`` alert rule reads).
- **Engine-iteration composition**: one record per pass of the engine
  loop that had work (:class:`_IterMeter`) — active slots,
  decode-vs-prefill token split, the step's wall and the co-batched
  stall, whose prefill chunks the pass carried and the inter-token gap
  its rows saw — published as ``serve.step_ms`` /
  ``serve.active_slots`` / ``serve.stall_ms`` gauges and
  ``serve.steps`` / ``serve.decode_tokens`` / ``serve.prefill_tokens``
  counters (the ``serve-stall`` rule watches ``serve.steps`` progress
  against ``serve.queue_depth``), kept in a ring that ``summary()``
  reduces for ``Info()``, and sent through the one seam as a
  ``serve.iteration`` record when something listens.
- **KV-pool pressure**: :meth:`ServingLedger.kv_sample` turns
  :meth:`~ptype_tpu.serve_engine.blocks.BlockPool.stats` into the
  ``kv.free_blocks`` / ``kv.cached_blocks`` / ``kv.total_blocks`` /
  ``kv.prefix_hit_rate`` gauges and the ``kv.evictions`` counter
  (whose sampler-stamped ``kv.evictions.rate`` series gates the
  ``kv-pressure`` rule's eviction floor).
- **Span tree**: when tracing is armed and the request carried a
  traceparent (the engine captures it inside the actor handler span),
  :meth:`ServingLedger.retired` synthesizes the request's span tree
  into the flight recorder — ``serve.admit`` (queue + reservation
  wait), one ``serve.prefill.chunk[i]`` per chunk, and
  ``serve.decode`` carrying the ``first_token`` event and the retire
  reason — all children of the handler span, so the stitched Perfetto
  view reads gateway.request → rpc.call → actor/Generator.Generate →
  admit/chunks/decode for one request across processes. Spans are
  synthesized from the record's own stamps at retire (the lifecycle
  crosses the caller thread and the engine thread, so no single
  ``with`` scope could cover it); their wall-clock starts are the
  stamps the ledger's TTFT is computed from, which is what lets tests
  assert ledger-vs-span agreement.

Timer discipline: lint rule PT010 bars raw ``time.perf_counter()`` /
``time.time()`` calls inside ``serve_engine/`` — every stamp the
engine needs comes from a seam on this ledger (``ingress`` /
``enqueued`` / ``head_refused`` / ``admitted`` / ``chunk`` /
``first_token`` / ``tokens_emitted`` / ``iteration`` / ``retired``), so
latency math has exactly one home and a probe can cost it
(:func:`measure_seam_cost_us`, against the <1%-per-engine-iteration
bar).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import time

from ptype_tpu import lockcheck

from ptype_tpu import metrics as metrics_mod
from ptype_tpu import trace

#: Retired request records a ledger keeps.
REQUEST_WINDOW = 256
#: Engine-iteration records a ledger keeps.
ITER_WINDOW = 512
#: Recent per-request (seq, ttft_ms) samples served in ``Info()`` —
#: the gateway's probe drains new ones into its own SLO tracker.
TTFT_RECENT = 32

#: Retire reasons a record can close with.
RETIRE_REASONS = ("complete", "stop", "cancelled", "shed", "error")


class RequestRecord:
    """One prompt row's lifecycle stamps, engine-thread owned.

    Monotonic (``t_*``) stamps drive every duration; wall-clock
    (``w_*``) twins, taken at the same instants, anchor the
    synthesized spans on the cluster's shared timeline.
    """

    __slots__ = ("rid", "tp", "prompt_tokens", "max_new", "reused_blocks",
                 "t_call", "t_enqueue", "w_enqueue", "t_head", "t_admit",
                 "chunks", "t_first", "w_first", "tok_t",
                 "t_done", "reason", "closed", "t_mig0", "w_mig0",
                 "t_mig1", "migrate_blocks", "migrate_bytes")

    def __init__(self, prompt_tokens: int, max_new: int,
                 tp: str | None, rid: int = 0,
                 t_call: float | None = None):
        #: The request's id on this ledger: in every region and span
        #: the request leaves, with tracing on or off.
        self.rid = rid
        self.tp = tp
        self.prompt_tokens = int(prompt_tokens)
        self.max_new = int(max_new)
        self.reused_blocks = 0
        self.t_enqueue = time.perf_counter()
        self.w_enqueue = time.time()
        #: When the caller's handler entered the engine
        #: (``ServingLedger.ingress``), before the prompt crossed to
        #: the device and back; the enqueue stamp where no entry was
        #: stamped.
        self.t_call = self.t_enqueue if t_call is None else t_call
        self.t_head: float | None = None
        self.t_admit: float | None = None
        #: [(wall_start, dur_s, tokens), ...] — one per prefill chunk.
        self.chunks: list[tuple[float, float, int]] = []
        self.t_first: float | None = None
        self.w_first: float | None = None
        #: Monotonic stamp per emitted token (first token included).
        self.tok_t: list[float] = []
        self.t_done: float | None = None
        self.reason: str | None = None
        self.closed = False
        #: Migration leg (ISSUE 16, decode-side records only): plan →
        #: import-complete stamps plus the transfer's block/byte
        #: totals — its own TTFT attribution inside the request.
        self.t_mig0: float | None = None
        self.w_mig0: float | None = None
        self.t_mig1: float | None = None
        self.migrate_blocks = 0
        self.migrate_bytes = 0

    # ------------------------------------------------------- durations

    def queue_wait_s(self) -> float:
        """Enqueue → head of line (or admission, when the reservation
        never refused)."""
        anchor = (self.t_head if self.t_head is not None
                  else self.t_admit)
        return max(0.0, (anchor - self.t_enqueue)
                   if anchor is not None else 0.0)

    def reserve_wait_s(self) -> float:
        """Head-of-line reservation wait (0 when the pool covered the
        worst case on the first try)."""
        if self.t_head is None or self.t_admit is None:
            return 0.0
        return max(0.0, self.t_admit - self.t_head)

    def ttft_s(self) -> float | None:
        if self.t_first is None:
            return None
        return max(0.0, self.t_first - self.t_enqueue)

    def first_token_split(self) -> dict:
        """The ``serve.first_token`` record: where this request's
        first-token time went, in ms. ``queue_ms + reserve_ms +
        admitted_ms`` is its ``ttft_s``; with ``ingress_ms`` (the
        caller's entry -> the enqueue stamp) before them, its
        first-token time as its caller's handler saw it."""
        queue_s, reserve_s = self.queue_wait_s(), self.reserve_wait_s()
        return {
            "rid": self.rid,
            "prompt_tokens": self.prompt_tokens,
            "reused_blocks": self.reused_blocks,
            "chunks": len(self.chunks),
            "ingress_ms": round(
                (self.t_enqueue - self.t_call) * 1e3, 3),
            "queue_ms": round(queue_s * 1e3, 3),
            "reserve_ms": round(reserve_s * 1e3, 3),
            "admitted_ms": round(
                (self.ttft_s() - queue_s - reserve_s) * 1e3, 3),
        }

    def tpot_s(self) -> float | None:
        """Mean inter-token time after the first token."""
        if self.t_first is None or self.t_done is None:
            return None
        n = len(self.tok_t)
        if n < 2:
            return None
        return max(0.0, (self.tok_t[-1] - self.t_first) / (n - 1))

    def decode_deltas_ms(self) -> list[float]:
        """Per-token decode gaps (ms) — the raw TPOT trail."""
        return [round((b - a) * 1e3, 3)
                for a, b in zip(self.tok_t, self.tok_t[1:])]

    def migrate_s(self) -> float | None:
        """Migration-leg wall (plan → import complete); None when the
        request never migrated or the transfer never finished."""
        if self.t_mig0 is None or self.t_mig1 is None:
            return None
        return max(0.0, self.t_mig1 - self.t_mig0)

    def to_dict(self) -> dict:
        ttft = self.ttft_s()
        tpot = self.tpot_s()
        d = {
            "rid": self.rid,
            "t": round(self.w_enqueue, 3),
            "prompt_tokens": self.prompt_tokens,
            "max_new": self.max_new,
            "reused_blocks": self.reused_blocks,
            "queue_wait_ms": round(self.queue_wait_s() * 1e3, 3),
            "reserve_wait_ms": round(self.reserve_wait_s() * 1e3, 3),
            "prefill_chunks": len(self.chunks),
            "prefill_tokens": sum(c[2] for c in self.chunks),
            "prefill_ms": round(
                sum(c[1] for c in self.chunks) * 1e3, 3),
            "tokens_out": len(self.tok_t),
            "reason": self.reason,
        }
        if ttft is not None:
            d["ttft_ms"] = round(ttft * 1e3, 3)
        mig = self.migrate_s()
        if mig is not None:
            d["migrate_ms"] = round(mig * 1e3, 3)
            d["migrate_blocks"] = self.migrate_blocks
            d["migrate_bytes"] = self.migrate_bytes
        if tpot is not None:
            d["tpot_ms"] = round(tpot * 1e3, 3)
            d["decode_deltas_ms"] = self.decode_deltas_ms()
        if self.t_done is not None:
            d["e2e_ms"] = round(
                max(0.0, self.t_done - self.t_enqueue) * 1e3, 3)
        return d


def _region(rec: RequestRecord, name: str, **attrs):
    """One of a request's own regions (its chunks, its
    ``serve.first_token`` and ``serve.retire`` records), through the
    one seam. A live profiler capture always takes it; the flight
    recorder takes it under the request's own trace, so a request that
    carried no traceparent leaves no orphan spans there."""
    if trace.capturing() or (rec.tp is not None and trace.enabled()):
        return trace.span_from(rec.tp, name, **attrs)
    return contextlib.nullcontext()


class _ChunkMeter:
    """Times one prefill chunk into its record + the ledger's
    per-iteration prefill accumulators."""

    __slots__ = ("_led", "_rec", "tokens", "ctx", "dur_s", "_t0", "_w0",
                 "_sp")

    def __init__(self, led: "ServingLedger", rec: RequestRecord,
                 tokens: int):
        self._led = led
        self._rec = rec
        self.tokens = int(tokens)
        #: The context, in tokens, the chunk ends at (what its cost
        #: follows); the engine sets it before the scope opens.
        self.ctx = 0
        self.dur_s = 0.0

    def __enter__(self) -> "_ChunkMeter":
        self._sp = _region(self._rec, "serve.prefill/chunk",
                           rid=self._rec.rid, tokens=self.tokens)
        self._sp.__enter__()
        self._w0 = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_s = time.perf_counter() - self._t0
        self._rec.chunks.append((self._w0, self.dur_s, self.tokens))
        led = self._led
        with led._lock:
            led._iter_prefill_s += self.dur_s
            led._iter_prefill_tokens += self.tokens
            led._iter_chunks.append((self._rec.rid, int(self.ctx)))
        self._sp.__exit__(*exc)
        return False


class _Ingress:
    """A request's way in on its caller's thread, from the handler's
    entry to its last row's enqueue stamp: the entry stamp its records
    keep (``RequestRecord.t_call``) and, where something listens, a
    ``serve.ingress`` span. ``close`` is the scope's end, and may come
    before the ``with`` block's (the handler goes on to wait for its
    rows inside it)."""

    __slots__ = ("t_call", "_sp")

    def __init__(self, rows: int, prompt_tokens: int):
        self._sp = trace.span("serve.ingress", rows=rows,
                              prompt_tokens=prompt_tokens)

    def __enter__(self) -> "_Ingress":
        self._sp.__enter__()
        self.t_call = time.perf_counter()
        return self

    def close(self, *exc) -> None:
        sp, self._sp = self._sp, None
        if sp is not None:
            sp.__exit__(*(exc or (None, None, None)))

    def __exit__(self, *exc) -> bool:
        self.close(*exc)
        return False


class _IterMeter:
    """One pass of the engine loop that had work: the cancel sweep, the
    admission round with the prefill chunks it ran and, where rows are
    live, the batched decode step (``step`` marks its start). Folds
    the iteration record:

    - ``seq``: the ledger's count of records, tracing on or off;
    - ``step_ms`` (the step's wall; of a pass that ran none, the
      pass's), ``active`` (the rows the pass emitted tokens for: the
      step it fetched, or the window it ran), ``decode_tokens``,
      ``prefill_tokens``, ``prefill_ms``, ``stall_ms`` (the co-batched
      stall the engine charged to this step) and ``iter_ms`` (the
      whole pass);
    - ``ahead``: 1 where the pass dispatched its decode step while the
      previous step's tokens were still on the device, else 0 (behind
      ``summary()``'s ``steps_ahead_share`` of the passes that
      dispatched one);
    - ``chunks``, ``chunk_rids``, ``chunk_ctx``: whose prefill the pass
      carried, and the largest context a chunk of it ended at;
    - ``gap_ms``, ``gap_rows``: the time since the previous
      ``tokens_emitted`` stamp and the rows whose previous token bore
      it, which is what each of them saw as its inter-token gap;
      ``new_gaps_ms``: the gaps of the rows whose previous token was
      their first (``first_token``'s stamp), and those of an earlier
      emit in the same pass (a step drained before a speculative
      window). A speculative window's
      further tokens share the commit stamp: ``decode_tokens -
      gap_rows - len(new_gaps_ms)`` gaps of zero (:func:`record_gaps`).

    Kept in the ledger's ring, and one zero-length ``serve.iteration``
    record through the seam where a capture or the recorder listens. A
    pass that ran neither a chunk nor a step ticks the counters and
    leaves no record."""

    __slots__ = ("_led", "active", "stall_ms", "decode_tokens", "ahead",
                 "_t0", "_t_step")

    def __init__(self, led: "ServingLedger", active: int,
                 stall_ms: float):
        self._led = led
        self.active = int(active)
        self.stall_ms = float(stall_ms)
        #: Tokens this iteration actually decoded. Defaults to the
        #: active-slot count (one token per live row); a speculative
        #: window overwrites it with its emitted total before the
        #: scope closes, so ``serve.decode_tokens`` stays the real
        #: throughput counter either way.
        self.decode_tokens: int | None = None
        #: Set by the engine where the pass dispatched a decode step:
        #: 1 with the previous step in flight, 0 without one.
        self.ahead: int | None = None
        self._t_step: float | None = None

    def __enter__(self) -> "_IterMeter":
        self._t0 = time.perf_counter()
        return self

    def step(self, active: int, stall_ms: float) -> None:
        """The pass reaches its decode step over ``active`` rows."""
        self.active = int(active)
        self.stall_ms = float(stall_ms)
        self._t_step = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        led = self._led
        now = time.perf_counter()
        iter_ms = round((now - self._t0) * 1e3, 3)
        step_ms = (iter_ms if self._t_step is None
                   else round((now - self._t_step) * 1e3, 3))
        stall_ms = round(self.stall_ms, 3)
        dtoks = (self.active if self.decode_tokens is None
                 else int(self.decode_tokens))
        rec = None
        with led._lock:
            prefill_s, led._iter_prefill_s = led._iter_prefill_s, 0.0
            ptoks, led._iter_prefill_tokens = \
                led._iter_prefill_tokens, 0
            chunks, led._iter_chunks = led._iter_chunks, []
            (gap_s, gap_rows), led._iter_gap = led._iter_gap, (0.0, 0)
            new_gaps, led._iter_new_gaps = led._iter_new_gaps, []
            if self.ahead is not None:
                led._dispatched[0] += 1
                led._dispatched[1] += int(self.ahead)
            if chunks or self.active:
                led._seq += 1
                rec = {"seq": led._seq,
                       "step_ms": step_ms,
                       "active": self.active,
                       "decode_tokens": dtoks,
                       "prefill_tokens": ptoks,
                       "prefill_ms": round(prefill_s * 1e3, 3),
                       "stall_ms": stall_ms,
                       "iter_ms": iter_ms,
                       "chunks": len(chunks),
                       "chunk_rids": tuple([r for r, _ in chunks]),
                       "chunk_ctx": max([c for _, c in chunks],
                                        default=0),
                       "gap_ms": round(gap_s * 1e3, 3),
                       "gap_rows": gap_rows,
                       "new_gaps_ms": tuple([round(g * 1e3, 3)
                                             for g in new_gaps]),
                       "ahead": int(bool(self.ahead))}
                led._iters.append(rec)
        led.c_steps.add(1)
        led.c_decode_tokens.add(dtoks)
        if ptoks:
            led.c_prefill_tokens.add(ptoks)
        led.g_step_ms.set(step_ms)
        led.g_active.set(self.active)
        led.g_stall.set(stall_ms)
        if rec is not None and (trace.capturing() or trace.enabled()):
            # ":" inside a list: a profiler annotation's metadata is
            # itself a comma-separated list.
            with trace.span("serve.iteration", **{
                    **rec,
                    "chunk_rids": ":".join(map(str, rec["chunk_rids"])),
                    "new_gaps_ms": ":".join(
                        map(str, rec["new_gaps_ms"]))}):
                pass
        return False


def record_gaps(rec: dict) -> list[tuple[float, int]]:
    """The inter-token gaps one iteration record stands for, as
    ``(gap_ms, rows that saw it)``."""
    new = rec["new_gaps_ms"]
    out = [(g, 1) for g in new]
    if rec["gap_rows"]:
        out.append((rec["gap_ms"], rec["gap_rows"]))
    burst = rec["decode_tokens"] - rec["gap_rows"] - len(new)
    if burst > 0:
        out.append((0.0, burst))
    return out


def _ring_summary(iters: list[dict]) -> dict:
    """What the ring of iteration records says, for ``summary()``:
    whether the tail of the inter-token gaps is a prefill's or a
    step's (``chunk_gap_share`` of the gaps came from a pass that
    carried a chunk, median ``gap_p50_chunk_ms``; the rest reach
    ``gap_p95_decode_only_ms``), and how many rows a step serves."""
    n = len(iters)
    dtoks = sum(r["decode_tokens"] for r in iters)
    ptoks = sum(r["prefill_tokens"] for r in iters)
    live = [r["active"] for r in iters if r["active"]]
    out = {
        "iterations": n,
        "step_ms_mean": round(sum(r["step_ms"] for r in iters) / n, 3),
        "stall_ms_max": round(max(r["stall_ms"] for r in iters), 3),
        "prefill_token_share": round(
            ptoks / (ptoks + dtoks), 4) if ptoks + dtoks else 0.0,
    }
    if live:
        out["rows_live_mean"] = round(sum(live) / len(live), 2)
    chunked = [g for r in iters if r["chunks"] for g in record_gaps(r)]
    plain = [g for r in iters if not r["chunks"] for g in record_gaps(r)]
    if chunked or plain:
        out["chunk_gap_share"] = round(
            sum(w for _, w in chunked)
            / sum(w for _, w in chunked + plain), 4)
    if plain:
        out["gap_p95_decode_only_ms"] = _wquantile(plain, 0.95)
    if chunked:
        out["gap_p50_chunk_ms"] = _wquantile(chunked, 0.5)
    return out


def _wquantile(pairs: list[tuple[float, int]], q: float) -> float:
    """The value under which a share ``q`` of the weight lies."""
    pairs = sorted(pairs)
    need = q * sum(w for _, w in pairs)
    acc = 0
    for v, w in pairs:
        acc += w
        if acc >= need:
            break
    return v


class ServingLedger:
    """Per-engine request-lifecycle + iteration + KV-pressure ledger.

    One per :class:`~ptype_tpu.serve_engine.engine
    .PagedGeneratorActor`; publishes into that engine's metrics
    registry (the process default, or a per-node registry in drills /
    simulated fleets), which the health sampler turns into the series
    the serving alert rules evaluate.
    """

    def __init__(self,
                 registry: metrics_mod.MetricsRegistry | None = None,
                 window: int = REQUEST_WINDOW):
        self.registry = (registry if registry is not None
                         else metrics_mod.metrics)
        reg = self.registry
        self.h_ttft = reg.histogram("serve.ttft_ms")
        self.h_tpot = reg.histogram("serve.tpot_ms")
        self.h_e2e = reg.histogram("serve.e2e_ms")
        self.h_queue_wait = reg.histogram("serve.queue_wait_ms")
        # Per-iteration families resolved once: the iteration meter
        # runs on the hot decode path, and six locked registry name
        # lookups per engine step is exactly the kind of avoidable
        # cost the seam-cost probe would price into the overhead bar.
        self.c_steps = reg.counter("serve.steps")
        self.c_decode_tokens = reg.counter("serve.decode_tokens")
        self.c_prefill_tokens = reg.counter("serve.prefill_tokens")
        self.g_step_ms = reg.gauge("serve.step_ms")
        self.g_active = reg.gauge("serve.active_slots")
        self.g_stall = reg.gauge("serve.stall_ms")
        self._lock = lockcheck.lock("health.serving.ledger")
        self._rids = itertools.count(1)
        self._records: collections.deque = collections.deque(
            maxlen=int(window))
        self._iters: collections.deque = collections.deque(
            maxlen=ITER_WINDOW)
        self._reasons: dict[str, int] = {}
        self._retired = 0
        self._svc_ewma_s = 0.0
        self._ttft_seq = 0
        self._ttft_recent: collections.deque = collections.deque(
            maxlen=TTFT_RECENT)
        #: What the open pass has gathered, folded and cleared when its
        #: iteration scope closes (engine-thread owned): its chunks'
        #: seconds, tokens and ``(rid, ctx)``, the gap its rows saw
        #: ``(seconds, rows)`` and the new rows' own gaps.
        self._iter_prefill_s = 0.0
        self._iter_prefill_tokens = 0
        self._iter_chunks: list[tuple[int, int]] = []
        self._iter_gap = (0.0, 0)
        self._iter_new_gaps: list[float] = []
        #: Passes that dispatched a decode step, and of them those that
        #: did so with the previous step in flight.
        self._dispatched = [0, 0]
        #: Iteration records so far, and the last ``tokens_emitted``
        #: stamp.
        self._seq = 0
        self._emit_t: float | None = None
        self._evictions_last = 0.0
        # Speculative decoding (ISSUE 12): cumulative window totals
        # behind the summary's spec_accept_rate / spec_tokens; the
        # counter/gauge families resolved lazily in spec_window so a
        # non-speculative engine's registry stays spec-free.
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_tokens = 0
        #: Requests whose KV arrived by migration (ISSUE 16); the
        #: migrate histogram/summary keys stay absent until > 0, so
        #: a non-disaggregated replica's Info() is migration-free.
        self._migrated = 0
        #: A dropless router's load (moe_load): running totals per
        #: held expert, last the assignments held elsewhere; absent
        #: from the summary until an iteration reported some.
        self._moe_load: list[int] = []
        self._moe_iters = 0
        #: The dropless layers' tile loop (moe_load): running totals of
        #: tiles visited, held experts hit and rows the tiles covered,
        #: and the number of expert layers a step.
        self._moe_tiles = [0, 0, 0]
        self._moe_layers = 1
        #: Which decode attention the engine's step compiled, as the
        #: engine states it: ``"list"`` (GQA: the live rows' block
        #: list, ``generate._live_block_attention``), ``"lanes"``
        #: (latent behind an indexer: the live lanes' list,
        #: ``sparse_mla.attend_paged``), ``"latent_kernel"`` (latent
        #: with no indexer: ``ops.latent_block_attention`` over the
        #: block list). In ``summary()`` once set.
        self.decode_attn: str | None = None
        #: The decode step's live-block list (kv_list): running totals
        #: of steps, listed blocks, tiles run, tokens attended and
        #: tokens the tiles covered; absent from the summary until a
        #: step ran over a list.
        self._kv_list = [0, 0, 0, 0, 0]
        #: A latent step's live-lane list (lane_list): steps, tiles
        #: run, lanes live and lanes the tiles covered; absent likewise.
        self._lane_list = [0, 0, 0, 0]
        #: Two kinds of cache in one engine (cache): steps, blocks the
        #: live rows hold in the full and in the window pool, the
        #: blocks one kind of cache would hold for them, and window
        #: blocks given back; absent likewise.
        self._cache = [0, 0, 0, 0, 0]

    # --------------------------------------------------- request seams

    def ingress(self, shape) -> _Ingress:
        """Open a request's way in (``with``; ``close`` at its last
        row's ``enqueued``); ``shape`` is the prompt's as it arrived,
        ``(tokens,)`` or ``(rows, tokens)``."""
        rows = int(shape[0]) if len(shape) > 1 else 1
        return _Ingress(rows, rows * int(shape[-1]))

    def enqueued(self, prompt_tokens: int, max_new: int,
                 tp: str | None = None,
                 t_call: float | None = None) -> RequestRecord:
        """A row entered the waiting room; ``tp`` is the caller's
        traceparent (captured inside the actor handler span) the
        synthesized span tree will parent under, ``t_call`` its
        handler's entry stamp (``ingress``)."""
        self.registry.counter("serve.requests").add(1)
        return RequestRecord(prompt_tokens, max_new, tp,
                             rid=next(self._rids), t_call=t_call)

    def head_refused(self, rec: RequestRecord) -> float:
        """The head-of-line reservation was refused; returns seconds
        spent AT THE HEAD so far (the engine's admit-timeout input).
        First refusal stamps the head arrival."""
        now = time.perf_counter()
        if rec.t_head is None:
            rec.t_head = now
        return now - rec.t_head

    def admitted(self, rec: RequestRecord) -> None:
        rec.t_admit = time.perf_counter()

    def chunk(self, rec: RequestRecord, tokens: int) -> _ChunkMeter:
        """Meter one prefill chunk (wrap exactly the chunk compute)."""
        return _ChunkMeter(self, rec, tokens)

    def first_token(self, rec: RequestRecord) -> None:
        """The TTFT stamp; and, into whatever is listening (a live
        profiler capture, the flight recorder), one ``serve.first_token``
        region carrying the record's split of its first-token time."""
        rec.w_first = time.time()
        rec.t_first = time.perf_counter()
        rec.tok_t.append(rec.t_first)
        with _region(rec, "serve.first_token", **rec.first_token_split()):
            pass

    def migrate_begin(self, rec: RequestRecord) -> None:
        """Decode-side migration plan accepted (blocks reserved,
        resident refs taken): the migration leg opens here."""
        rec.w_mig0 = time.time()
        rec.t_mig0 = time.perf_counter()

    def migrate_done(self, rec: RequestRecord, blocks: int,
                     nbytes: int) -> None:
        """The migration wire landed (imported + sealed): close the
        leg, fold ``serve.migrate_ms`` — the histogram behind the
        migration leg's own TTFT attribution (a slow transfer shows
        up HERE before it shows up in ttft_p99)."""
        rec.t_mig1 = time.perf_counter()
        rec.migrate_blocks = int(blocks)
        rec.migrate_bytes = int(nbytes)
        mig = rec.migrate_s()
        if mig is not None:
            self.registry.histogram("serve.migrate_ms").observe(
                mig * 1e3)
        with self._lock:
            self._migrated += 1

    def tokens_emitted(self, recs, counts=None) -> None:
        """One decode step emitted a token on each of ``recs`` — one
        shared stamp (the step boundary), appended per row.
        ``counts`` (speculative windows): per-rec emitted-token counts
        — the window's tokens share the commit stamp, so TPOT stays
        the mean inter-token time of what the caller actually saw.
        Once a pass: what the rows saw as their gap goes to the open
        iteration's record (the rows whose previous token bore the
        previous stamp share one gap; a row fresh from ``first_token``
        has its own; a second emit in one pass turns the first one's
        shared gap into gaps of its rows' own)."""
        now = time.perf_counter()
        last, self._emit_t = self._emit_t, now
        rows, new = 0, self._iter_new_gaps
        gap, shared = self._iter_gap
        new.extend([gap] * shared)
        for i, rec in enumerate(recs):
            tok_t = rec.tok_t
            if tok_t[-1] == last:
                rows += 1
            else:
                new.append(now - tok_t[-1])
            if counts is None:
                tok_t.append(now)
            else:
                tok_t.extend([now] * int(counts[i]))
        self._iter_gap = (now - last if rows else 0.0, rows)

    def moe_load(self, counts, tiles: int = 0, hit: int = 0,
                 tile: int = 0, layers: int = 1) -> None:
        """One decode iteration's router load, as the step counted it
        on the device: assignments per held expert, summed over the
        expert layers, and last those that fell on no held expert.
        Kept as running totals (``summary()["moe_load"]``) and emitted
        as a ``serve.moe_load`` record through the one seam. With them
        what the layers' tile loop did (``transformer._moe_dropless``):
        ``tiles`` of ``tile`` rows visited and held experts ``hit``,
        each summed over the ``layers`` expert layers; behind
        ``summary()``'s ``expert_tiles`` (mean a step), ``experts_hit``
        (mean distinct held experts a layer a step) and
        ``expert_tile_fill`` (routed live rows ÷ rows the tiles in use
        covered)."""
        counts = [int(c) for c in counts]
        tiles, hit = int(tiles), int(hit)
        with self._lock:
            if len(self._moe_load) != len(counts):
                self._moe_load = [0] * len(counts)
            self._moe_load = [a + b for a, b in
                              zip(self._moe_load, counts)]
            self._moe_iters += 1
            for i, v in enumerate((tiles, hit, tiles * int(tile))):
                self._moe_tiles[i] += v
            self._moe_layers = int(layers)
        # ":" between the counts: a profiler annotation's metadata is
        # itself a comma-separated list.
        with trace.span("serve.moe_load",
                        held=":".join(str(c) for c in counts[:-1]),
                        elsewhere=counts[-1], tiles=tiles):
            pass

    def kv_list(self, blocks: int, tiles: int, live_tokens: int,
                tile_tokens: int) -> None:
        """One decode step over the live rows' block list
        (``generate.live_block_list``): ``blocks`` listed, ``tiles`` of
        ``tile_tokens`` tokens run, ``live_tokens`` attended (Σ live
        context). Running totals behind ``summary()``'s ``kv_blocks``,
        ``kv_tiles`` (means a step) and ``kv_tile_fill`` (tokens
        attended ÷ tokens the tiles covered: what of the step's
        attention work was not padding or duplication)."""
        with self._lock:
            for i, v in enumerate((1, blocks, tiles, live_tokens,
                                   tiles * tile_tokens)):
                self._kv_list[i] += v

    def lane_list(self, live: int, tiles: int, tile: int) -> None:
        """One latent decode step over the live lanes' list
        (``generate.live_lane_list``): ``live`` lanes listed, ``tiles``
        of ``tile`` lanes run. Running totals behind ``summary()``'s
        ``lane_tiles`` (mean a step) and ``lane_tile_fill`` (live lanes
        ÷ lanes the tiles in use covered)."""
        with self._lock:
            for i, v in enumerate((1, tiles, live, tiles * tile)):
                self._lane_list[i] += v

    def cache(self, full_blocks: int, window_blocks: int,
              window_freed: int, uniform_blocks: int) -> None:
        """One decode iteration of an engine with two kinds of cache:
        the blocks its live rows hold in the full layers' pool and in
        the window layers', the window blocks given back since the
        last record, and the blocks a cache of one kind (every layer
        keeping every token) would hold for the same rows. Running
        totals behind ``summary()``'s four of the same names (means a
        step; ``window_freed`` the total) and a ``serve.cache`` record
        through the one seam."""
        with self._lock:
            for i, v in enumerate((1, full_blocks, window_blocks,
                                   uniform_blocks, window_freed)):
                self._cache[i] += v
        with trace.span("serve.cache", full_blocks=int(full_blocks),
                        window_blocks=int(window_blocks),
                        window_freed=int(window_freed),
                        uniform_blocks=int(uniform_blocks)):
            pass

    def shed_untracked(self) -> None:
        """A shed before any record existed (the chaos admit seam)."""
        self.registry.counter("serve.sheds").add(1)

    def retired(self, rec: RequestRecord | None, reason: str) -> None:
        """Close a row's lifecycle: fold histograms/counters, update
        the service-time EWMA, emit the span tree. Idempotent — engine
        teardown may sweep rows whose shed path already closed them."""
        if rec is None or rec.closed:
            return
        rec.closed = True
        rec.t_done = time.perf_counter()
        rec.reason = reason if reason in RETIRE_REASONS else "error"
        reg = self.registry
        reg.counter("serve.retired").add(1)
        reg.counter(f"serve.retired.{rec.reason}").add(1)
        if rec.reason == "shed":
            reg.counter("serve.sheds").add(1)
        ttft = rec.ttft_s()
        tpot = rec.tpot_s()
        if rec.reason in ("complete", "stop"):
            e2e = rec.t_done - rec.t_enqueue
            self.h_e2e.observe(e2e * 1e3)
            self.h_queue_wait.observe(rec.queue_wait_s() * 1e3)
            if ttft is not None:
                self.h_ttft.observe(ttft * 1e3)
            if tpot is not None:
                self.h_tpot.observe(tpot * 1e3)
            with self._lock:
                self._svc_ewma_s = (
                    e2e if self._svc_ewma_s == 0.0
                    else 0.3 * e2e + 0.7 * self._svc_ewma_s)
                if ttft is not None:
                    self._ttft_seq += 1
                    self._ttft_recent.append(
                        (self._ttft_seq, round(ttft * 1e3, 3)))
        with self._lock:
            self._retired += 1
            self._reasons[rec.reason] = \
                self._reasons.get(rec.reason, 0) + 1
            self._records.append(rec.to_dict())
        with _region(rec, "serve.retire", rid=rec.rid, reason=rec.reason,
                     tokens_out=len(rec.tok_t)):
            pass
        self._emit_spans(rec)

    # ------------------------------------------------- iteration seams

    def iteration(self, active: int = 0,
                  stall_ms: float = 0.0) -> _IterMeter:
        """Meter one pass of the engine loop (wrap all of it; the
        meter's ``step`` marks where its decode step starts)."""
        return _IterMeter(self, active, stall_ms)

    def spec_window(self, proposed: int, accepted: int, emitted: int,
                    rate: float) -> None:
        """One committed speculative-decoding window (ISSUE 12):
        ``proposed`` draft tokens scored, ``accepted`` of them kept,
        ``emitted`` tokens committed (accepted prefixes + one
        corrected/bonus token per live row). ``rate`` is the engine's
        accept-rate EWMA — published as the ``serve.spec_accept_rate``
        gauge the gateway probes, ``obs serve``, and a fleet-wide
        collapse diagnosis all read; the counters
        (``serve.spec_windows`` / ``spec_proposed`` / ``spec_accepted``
        / ``spec_tokens``) carry the cumulative totals behind the
        summary's measured speedup accounting."""
        reg = self.registry
        reg.counter("serve.spec_windows").add(1)
        if proposed:
            reg.counter("serve.spec_proposed").add(int(proposed))
        if accepted:
            reg.counter("serve.spec_accepted").add(int(accepted))
        if emitted:
            reg.counter("serve.spec_tokens").add(int(emitted))
        reg.gauge("serve.spec_accept_rate").set(round(float(rate), 4))
        with self._lock:
            self._spec_proposed += int(proposed)
            self._spec_accepted += int(accepted)
            self._spec_tokens += int(emitted)

    def kv_sample(self, stats: dict, prefix_hit_rate: float) -> None:
        """Publish one KV-pool pressure sample from
        ``BlockPool.stats()`` — the ``kv.*`` names the serving alert
        rules key on; the eviction counter carries deltas so the
        sampler's ``kv.evictions.rate`` series is a real rate."""
        reg = self.registry
        reg.gauge("kv.free_blocks").set(stats["kv_free_blocks"])
        reg.gauge("kv.cached_blocks").set(stats["kv_cached_blocks"])
        reg.gauge("kv.used_blocks").set(stats["kv_used_blocks"])
        reg.gauge("kv.total_blocks").set(stats["kv_total_blocks"])
        reg.gauge("kv.util_pct").set(stats["kv_util_pct"])
        reg.gauge("kv.prefix_hit_rate").set(float(prefix_hit_rate))
        ev = float(stats.get("kv_evictions", 0))
        with self._lock:
            delta, self._evictions_last = \
                ev - self._evictions_last, ev
        if delta > 0:
            reg.counter("kv.evictions").add(delta)

    # ------------------------------------------------------- readouts

    def spec_totals(self) -> tuple[int, int, int]:
        """Cumulative (proposed, accepted, emitted) speculative
        totals — the ONE accumulation home (the engine derives its
        Info() surface from this; a second engine-side copy would be
        a drift surface)."""
        with self._lock:
            return (self._spec_proposed, self._spec_accepted,
                    self._spec_tokens)

    def svc_ewma_s(self) -> float:
        """EWMA of completed-request service seconds — the engine's
        backlog-proportional retry-after hint."""
        with self._lock:
            return self._svc_ewma_s

    def ttft_recent(self) -> list[list[float]]:
        """Recent (seq, ttft_ms) samples for ``Info()`` — the gateway
        probe feeds NEW ones (seq above its high-water mark) into the
        fleet-level SLO tracker, so its ttft percentiles are fed from
        real per-request samples, never percentile-of-percentile."""
        with self._lock:
            return [[s, ms] for s, ms in self._ttft_recent]

    def records(self, limit: int | None = None) -> list[dict]:
        with self._lock:
            out = list(self._records)
        if limit is not None and len(out) > limit:
            out = out[-limit:]
        return out

    def summary(self) -> dict:
        with self._lock:
            retired = self._retired
            reasons = dict(self._reasons)
            spec_prop = self._spec_proposed
            spec_acc = self._spec_accepted
            spec_toks = self._spec_tokens
            migrated = self._migrated
            moe_load, moe_iters = list(self._moe_load), self._moe_iters
            moe_tiles, moe_hit, moe_covered = self._moe_tiles
            moe_layers = self._moe_layers
            kv_steps, kv_blocks, kv_tiles, kv_live, kv_covered = \
                self._kv_list
            lane_steps, lane_tiles, lanes_live, lanes_covered = \
                self._lane_list
            c_steps, c_full, c_window, c_uniform, c_freed = self._cache
            dispatched, ahead = self._dispatched
            iters = list(self._iters)
        out = {}
        if iters:
            out.update(_ring_summary(iters))
        if dispatched:
            out["steps_ahead_share"] = round(ahead / dispatched, 4)
        if self.decode_attn:
            out["decode_attn"] = self.decode_attn
        if c_steps:
            out["full_blocks"] = round(c_full / c_steps, 2)
            out["window_blocks"] = round(c_window / c_steps, 2)
            out["uniform_blocks"] = round(c_uniform / c_steps, 2)
            out["window_freed"] = c_freed
        if kv_steps:
            out["kv_blocks"] = round(kv_blocks / kv_steps, 2)
            out["kv_tiles"] = round(kv_tiles / kv_steps, 3)
            out["kv_tile_fill"] = round(kv_live / max(kv_covered, 1), 4)
        if lane_steps:
            out["lane_tiles"] = round(lane_tiles / lane_steps, 3)
            out["lane_tile_fill"] = round(
                lanes_live / max(lanes_covered, 1), 4)
        if moe_iters:
            out["moe_load"] = {"iterations": moe_iters,
                               "held": moe_load[:-1],
                               "elsewhere": moe_load[-1]}
            out["expert_tiles"] = round(moe_tiles / moe_iters, 3)
            out["experts_hit"] = round(
                moe_hit / (moe_iters * moe_layers), 3)
            out["expert_tile_fill"] = round(
                sum(moe_load[:-1]) / max(moe_covered, 1), 4)
        if spec_prop:
            # Only once speculation actually ran: a non-speculative
            # replica's Info() stays spec-free, so fleet views can
            # tell "no speculation" from "accept rate 0".
            out["spec_accept_rate"] = round(spec_acc / spec_prop, 4)
            out["spec_tokens"] = spec_toks
        if migrated:
            # Same contract for migration: only once a wire actually
            # landed here.
            out["migrated_requests"] = migrated
            out["migrate_p99_ms"] = round(
                self.registry.histogram("serve.migrate_ms")
                .percentile(99), 3)
        return {
            **out,
            "requests_retired": retired,
            "retire_reasons": reasons,
            "ttft_p50_ms": round(self.h_ttft.percentile(50), 3),
            "ttft_p99_ms": round(self.h_ttft.percentile(99), 3),
            "tpot_p50_ms": round(self.h_tpot.percentile(50), 3),
            "tpot_p99_ms": round(self.h_tpot.percentile(99), 3),
            "e2e_p50_ms": round(self.h_e2e.percentile(50), 3),
            "e2e_p99_ms": round(self.h_e2e.percentile(99), 3),
            "queue_wait_p99_ms": round(
                self.h_queue_wait.percentile(99), 3),
        }

    # ----------------------------------------------------- span trees

    def _emit_spans(self, rec: RequestRecord) -> None:
        """Synthesize the request's span tree into the flight recorder
        (no-op unless tracing is armed AND the request carried a
        traceparent). Children of the actor handler span that carried
        the request, anchored at the record's own wall stamps."""
        recd = trace.recorder()
        if recd is None or rec.tp is None:
            return
        parent = trace.parse_traceparent(rec.tp)
        if parent is None:
            return
        trace_id, parent_id = parent
        admit = trace.Span("serve.admit", trace_id, parent_id)
        admit.start_s = rec.w_enqueue
        anchor = (rec.t_admit if rec.t_admit is not None
                  else rec.t_done)
        admit.dur_s = max(0.0, (anchor or rec.t_enqueue)
                          - rec.t_enqueue)
        admit.attrs = {
            "rid": rec.rid,
            "queue_wait_ms": round(rec.queue_wait_s() * 1e3, 3),
            "reserve_wait_ms": round(rec.reserve_wait_s() * 1e3, 3),
            "prompt_tokens": rec.prompt_tokens,
            "reused_blocks": rec.reused_blocks,
            # Forensics stage tag: an admit wait on a decode-class
            # engine (KV arrived over the wire) is decode-queue time,
            # not front-door queue-wait.
            "stage": ("decode-queue" if rec.t_mig0 is not None
                      else "queue-wait"),
        }
        if rec.reason == "shed":
            admit.status = "shed"
        elif rec.reason not in ("complete", "stop"):
            admit.status = rec.reason or "error"
        recd.record(admit)
        mig = rec.migrate_s()
        if mig is not None:
            sp = trace.Span("serve.migrate", trace_id, parent_id)
            sp.start_s = rec.w_mig0
            sp.dur_s = mig
            sp.attrs = {"rid": rec.rid, "blocks": rec.migrate_blocks,
                        "bytes": rec.migrate_bytes,
                        "dedup_blocks": rec.reused_blocks,
                        "stage": "migrate"}
            recd.record(sp)
        for i, (w0, dur, tokens) in enumerate(rec.chunks):
            sp = trace.Span(f"serve.prefill.chunk[{i}]", trace_id,
                            parent_id)
            sp.start_s = w0
            sp.dur_s = dur
            sp.attrs = {"rid": rec.rid, "tokens": tokens,
                        "stage": "prefill"}
            recd.record(sp)
        if rec.t_first is not None:
            dec = trace.Span("serve.decode", trace_id, parent_id)
            dec.start_s = rec.w_first
            dec.dur_s = max(0.0, rec.t_done - rec.t_first)
            dec.attrs = {"rid": rec.rid, "tokens": len(rec.tok_t),
                         "reason": rec.reason,
                         "stage": "decode",
                         "ttft_ms": round(rec.ttft_s() * 1e3, 3)}
            tpot = rec.tpot_s()
            if tpot is not None:
                dec.attrs["tpot_ms"] = round(tpot * 1e3, 3)
            # The acceptance event: where the request's first token
            # materialized on the shared timeline.
            dec.events.append({"name": "first_token", "t": 0.0})
            recd.record(dec)


# --------------------------------------------------------- bench probe


def measure_seam_cost_us(iters: int = 5000) -> dict:
    """Direct cost of the ledger seams one engine iteration pays (one
    ``iteration`` scope + one shared ``tokens_emitted`` stamp) —
    measured the same way PR 8 costs the profiling plane
    (``profile_overhead_pct``): a tight loop over the real calls,
    because the signal is microseconds against a multi-millisecond
    engine step and a wall-clock A/B on a shared host reports
    scheduler jitter, not the seam. Divided by an engine-iteration
    time it is the ledger's overhead (<1% bar);
    tests/test_serving_obs.py reads it.
    """
    led = ServingLedger(registry=metrics_mod.MetricsRegistry())
    rec = led.enqueued(8, 8)
    led.admitted(rec)
    led.first_token(rec)
    t0 = time.perf_counter()
    for _ in range(iters):
        with led.iteration(active=1, stall_ms=0.0):
            led.tokens_emitted((rec,))
    cost_s = (time.perf_counter() - t0) / iters
    return {"seam_cost_us": round(cost_s * 1e6, 3), "iters": iters}
