"""The paged continuous-batching engine.

:class:`PagedGeneratorActor` rebases serve.py's continuous engine onto
the :class:`~ptype_tpu.serve_engine.blocks.BlockPool`:

- **Paged decode**: one engine step decodes every live slot through
  per-sequence block tables (``models/generate.decode_step_banks``) —
  resident KV memory tracks actual token counts (pool blocks), not
  ``n_slots × reach`` contiguous banks. A GQA step's attention reads
  the blocks its live rows hold and nothing else: the engine keeps,
  beside the tables, a flat list of those blocks
  (``generate.live_block_list``, rebuilt with the device mirrors on
  admission, retire and block boundary) and the step loops over the
  list's tiles in use, so a step costs the weights plus Σ live
  context, whatever ``n_slots`` and the reach are — one compiled
  program for every trip count. A latent cache with no indexer is
  read from the same list (each row's run of it on tiles of its own)
  by one kernel a layer that copies a row's blocks into VMEM once
  (``ops.latent_block_attention``); a latent step whose indexer
  selects each row's keys is handed the list of live LANES instead
  (``generate.live_lane_list``, rebuilt at the same moments): index,
  selection, gather and attention run over those lanes, a tile of
  them a trip, and cost what is live whatever ``n_slots`` is. Greedy
  rows still match their solo decode token for token; logits agree to
  the float32 rounding of a softmax accumulated tile by tile.
- **Two kinds of cache** (a stack with attention kinds,
  ``transformer.cache_layers``): full-attention layers keep every
  token, window layers the last ``window``. Each kind has its own
  :class:`BlockPool`, banks and table a row; a row is admitted only
  when BOTH pools can hold it (the window pool's share is the most a
  row ever holds there at once: ``window + prefill_chunk`` tokens);
  the window table's blocks wholly behind ``pos - window`` are given
  back as the row advances, in each prefill chunk and each decode
  step, so a 32k-token row holds 32k tokens in the full layers alone.
  A prefix hit is the longest chained prefix whose full-layer blocks
  AND whose last window's window-layer blocks are still resident.
- **One step in flight**: a pass of the engine loop dispatches decode
  step n+1 before it fetches step n's tokens, so the host's
  bookkeeping, emit, retire and uploads run while the device computes
  and the device has the next step queued when one ends. The host's
  books advance at the dispatch; the tokens feed back on the device.
- **Chunked prefill**: admission writes the prompt in bounded
  ``prefill_chunk``-token chunks INTERLEAVED with decode steps — a 4k
  prompt can no longer freeze co-batched decodes for its whole
  prefill; the per-decode-step stall is bounded by one chunk and
  recorded (``serve.prefill`` regions feed the goodput ledger's
  ``prefill`` leg; ``Info()['prefill_stall_ms']`` and the
  ``serve.prefill_stall_ms`` gauge carry the host-side maximum).
- **Prefix reuse**: prompt blocks are content-addressed by the
  fnv32a hash chain (blocks.block_hashes — the SAME hash family the
  gateway's affinity routing keys on), so an affinity-routed request
  skips prefill for every already-resident full block. Hits/misses/
  evictions surface in ``Info()`` and as ``serve.*`` gauges the
  health sampler picks up.
- **Sampling on the continuous path**: per-slot RNG keys fold into
  the engine step (``generate.sample_token_rows``) — single-row
  sampled requests (temperature/top-k/top-p) ride the engine with
  exact solo-path RNG parity instead of convoying the lock-serialized
  solo path. Multi-row sampled requests and repetition-penalty
  requests keep the solo fallback (batch-shaped RNG / seen-set state).

Admission control: the waiting room is bounded (``max_queue``) and
every request reserves its worst-case block count up front — an
arrival the pool or queue can't hold sheds with a typed
:class:`~ptype_tpu.errors.ShedError` (+ backlog-proportional
``retry_after_s``) instead of wedging the engine; the ``serve.admit``
chaos seam forces sheds/delays and pairs with success-path beacons.

Observability (ISSUE 10): every latency stamp in this engine rides a
seam on its :class:`~ptype_tpu.health.serving.ServingLedger` (lint
PT010 bars raw timers in ``serve_engine/``) — per-request lifecycle
records with TTFT/TPOT/e2e histograms, per-iteration batch
composition, ``kv.*`` pressure series, and a synthesized
``serve.admit`` / ``serve.prefill.chunk[i]`` / ``serve.decode`` span
tree under the caller's traceparent so one stitched Perfetto trace
answers "where did this request's latency go" across processes.
"""

from __future__ import annotations

import itertools
import threading

from ptype_tpu import lockcheck
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ptype_tpu import chaos, jitwatch, logs, trace
from ptype_tpu import metrics as metrics_mod
from ptype_tpu.errors import ShedError
from ptype_tpu.health.serving import ServingLedger
from ptype_tpu.models import generate as gen
from ptype_tpu.models import transformer as tfm
from ptype_tpu.serve import LIFECYCLE_CODES, GeneratorActor, _pow2
from ptype_tpu.serve_engine.blocks import BlockPool, block_hashes
from ptype_tpu.serve_engine.migrate import (WIRE_MODES, KVMigrator,
                                            migrate_refusal)

log = logs.get_logger("serve_engine")

#: Replica classes for disaggregated serving (ISSUE 16): a "prefill"
#: replica fills KV blocks and exports them; a "decode" replica
#: imports migrated block sets and owns the decode lifetime;
#: "unified" does both (the pre-disaggregation behavior, and the
#: fallback class the router uses when a class pool is empty). The
#: class is ADVISORY — every engine serves every endpoint — routing
#: and the reconciler's per-class scaling are where it binds.
SERVE_CLASSES = ("unified", "prefill", "decode")
#: Numeric codes for the ``serve.class`` gauge (obs serve renders
#: the names back; same pattern as ``serve.lifecycle``).
SERVE_CLASS_CODES = {"unified": 0, "prefill": 1, "decode": 2}
#: How often an idle engine looks up from its wait for work to see
#: whether a capture or the recorder has started or stopped listening.
IDLE_LOOK_S = 0.25


def _host_prompt(prompt) -> np.ndarray:
    """Tokens from the wire -> (B, S) int32 on the host, where the
    engine's rows read them. Not through the device: with a decode step
    always queued there, a round trip on the caller's thread would wait
    a whole step."""
    prompt = np.array(prompt, np.int32)
    return prompt[None] if prompt.ndim == 1 else prompt


@dataclass
class SpecConfig:
    """Speculative decoding on the paged engine (ISSUE 12).

    A small same-family draft transformer proposes ``k`` tokens per
    live slot (its own paged KV tables in a second :class:`BlockPool`;
    :func:`~ptype_tpu.models.generate.truncated_draft_params` builds
    the zero-extra-memory layer-truncated variant), the target model
    scores all ``k + 1`` positions in ONE batched forward through the
    ragged per-slot gather path, and acceptance sampling commits the
    accepted prefix plus one corrected token — greedy output
    bit-identical to the non-speculative engine, sampled output
    distributed exactly as the target (the residual-acceptance
    contract in ``generate.spec_accept_rows``).

    ``adaptive``: back speculation off when the measured accept rate
    makes it a loss — the accept-rate EWMA under ``accept_floor``
    sheds one proposal depth per window; at depth 1 and under
    ``accept_floor / 2`` speculation disables outright and re-probes
    with one k=1 window every ``probe_every`` plain decode iterations
    (a draft gone stale against new traffic re-earns its depth instead
    of taxing every token forever). Above ``accept_floor + 0.15`` the
    depth climbs back toward ``k``.
    """

    #: Draft model params pytree (same family: embed/blocks/head).
    draft_params: dict
    #: Draft model config; vocab must match the target's.
    draft_cfg: tfm.TransformerConfig
    #: Proposal depth per window (the max tokens drafted per slot).
    k: int = 4
    #: Back off / re-probe on the measured accept rate.
    adaptive: bool = True
    #: Accept-rate EWMA floor under which depth backs off.
    accept_floor: float = 0.35
    #: Plain iterations between re-probes once speculation disabled.
    probe_every: int = 32
    #: Accept-rate EWMA smoothing.
    ewma_alpha: float = 0.2


class _PagedRow:
    """One prompt ROW moving through the engine: queued → admitting
    (chunked prefill) → active slot → done."""

    __slots__ = ("prompt", "max_new", "stop_token", "temperature",
                 "top_k", "top_p", "key", "emitted", "done", "err",
                 "table", "hashes", "reused", "prefill_pos",
                 "reserve_left", "rec", "cancelled", "draft_table",
                 "draft_reserve_left", "export_id", "migrated",
                 "wtable", "wfirst", "wreserve_left")

    def __init__(self, prompt, max_new, stop_token, temperature,
                 top_k, top_p, key):
        self.prompt = prompt          # 1-D int32 np array
        self.max_new = max_new
        self.stop_token = stop_token
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.key = key                # (2,) uint32 np array
        self.emitted: list[int] = []
        self.done = threading.Event()
        self.err = None
        self.table: list[int] = []    # block ids, position order
        self.hashes: list[int] = []
        self.reused = 0
        self.prefill_pos = -1         # -1: reuse walk not yet run
        self.reserve_left = 0
        #: Lifecycle record (health/serving.RequestRecord) — every
        #: stamp the engine needs comes through its ledger seams
        #: (lint PT010: no raw timers in serve_engine/).
        self.rec = None
        self.cancelled = False
        #: Draft-model block table + reservation (speculative
        #: decoding only; mirrors table/reserve_left on the target
        #: pool — the draft's KV state rides its own BlockPool).
        self.draft_table: list[int] = []
        self.draft_reserve_left = 0
        #: Disaggregated serving (ISSUE 16): a non-None export_id
        #: marks a prefill-class row — at prompt completion its block
        #: refs park under the id for ExportBlocks instead of taking
        #: a slot; ``migrated`` marks a decode-class row whose prompt
        #: KV arrived over the wire (admission skips reservation and
        #: prefill — both already happened).
        self.export_id: int | None = None
        self.migrated = False
        #: The window layers' block table (two kinds of cache only),
        #: indexed as ``table`` is, by position // block_tokens:
        #: entries before ``wfirst`` were given back (0) once wholly
        #: behind the row's window; ``wreserve_left`` the window
        #: pool's units the row still holds.
        self.wtable: list[int] = []
        self.wfirst = 0
        self.wreserve_left = 0


class PagedGeneratorActor(GeneratorActor):
    """Continuous batching over the paged KV block pool.

    Knobs (docs/OPERATIONS.md "Serving at scale"): ``n_slots`` live
    sequences; ``block_tokens`` KV block granularity (sublane-aligned,
    also the prefix-sharing granularity); ``n_blocks`` pool size
    (default ``n_slots × reach/block_tokens + 1`` — the contiguous
    engine's worst case; shrink it to oversubscribe on real token
    counts; for a model with window layers beside full ones it is the
    FULL layers' pool, and the window layers' is sized by the engine
    from ``n_slots``, the window and ``prefill_chunk``);
    ``prefill_chunk`` admission token budget per engine
    iteration (the decode-stall bound; ``None`` = whole-prompt, the
    legacy behavior); ``max_queue`` waiting-room bound before typed
    sheds; ``admit_timeout_s`` bound on how long a head-of-line
    request may wait for a pool reservation before it sheds typed
    (pool exhaustion becomes a routing signal instead of a gateway
    deadline burn; 0 = wait forever); ``spec`` a :class:`SpecConfig`
    arming speculative decoding — draft-propose, one batched
    target-verify, exact-distribution acceptance (greedy output stays
    bit-identical to the non-speculative engine; per-slot accept
    lengths make iterations ragged, which the retirement path already
    tolerates); ``device`` the device the replica lives on — params
    and both block pools are committed there (see
    :class:`~ptype_tpu.serve.GeneratorActor`).
    """

    def __init__(self, cfg: tfm.TransformerConfig, params=None,
                 rng: jax.Array | None = None, n_slots: int = 8,
                 max_len: int | None = None, block_tokens: int = 16,
                 n_blocks: int | None = None,
                 prefill_chunk: int | None = 64,
                 max_queue: int = 64, admit_timeout_s: float = 10.0,
                 spec: SpecConfig | None = None,
                 metrics_registry: metrics_mod.MetricsRegistry | None
                 = None, serve_class: str = "unified", device=None):
        super().__init__(cfg, params, rng, device=device)
        #: Registry the engine's gauges/histograms land in (default:
        #: the process-global one; drills and simulated multi-replica
        #: fleets pass a per-node registry so each replica's series
        #: stay distinct in the cluster snapshot).
        self._reg = (metrics_registry if metrics_registry is not None
                     else metrics_mod.metrics)
        #: The serving observability ledger (ISSUE 10): request
        #: lifecycle records, TTFT/TPOT/e2e histograms, engine-
        #: iteration composition, KV-pressure series — every latency
        #: stamp in this engine rides its seams.
        self.ledger = ServingLedger(registry=self._reg)
        self.n_slots = int(n_slots)
        bt = int(block_tokens)
        reach = min(int(max_len) if max_len else cfg.max_seq,
                    cfg.max_seq)
        self.reach = -(-reach // bt) * bt  # block-aligned
        self.block_tokens = bt
        self.nb = self.reach // bt
        n_blocks = (int(n_blocks) if n_blocks
                    else self.n_slots * self.nb + 1)
        self.prefill_chunk = (int(prefill_chunk) if prefill_chunk
                              else self.reach)
        #: Two kinds of cache (``tfm.cache_layers``): ``pool`` is then
        #: the full layers' and ``n_blocks`` its size; ``_wpool`` the
        #: window layers', sized from what a row can hold there:
        #: ``_wrow`` blocks while it prefills (window + chunk), ``_wdec``
        #: while it decodes, for every slot, plus room for each slot's
        #: last sealed window (the prefix rule needs those resident).
        kinds = tfm.cache_layers(cfg)
        self._wpool: BlockPool | None = None
        self._window = cfg.window
        if kinds is None:
            self.pool = BlockPool(cfg, n_blocks, bt, device=device)
        else:
            W = self._window
            self._wrow = min(self.nb,
                             (W + self.prefill_chunk - 1) // bt + 2)
            self._wdec = min(self.nb, (W - 1) // bt + 2)
            self.pool = BlockPool(cfg, n_blocks, bt, device=device,
                                  n_layers=len(kinds["full"]))
            self._wpool = BlockPool(
                cfg, self.n_slots * (self._wrow + self._wdec) + 1, bt,
                device=device, n_layers=len(kinds["window"]))
        self._window_freed = 0
        self.max_queue = int(max_queue)
        self.admit_timeout_s = float(admit_timeout_s)
        if serve_class not in SERVE_CLASSES:
            raise ValueError(f"serve_class must be one of "
                             f"{SERVE_CLASSES}, got {serve_class!r}")
        #: Disaggregated-serving class (ISSUE 16) — advisory: routing
        #: and per-class scaling key on it; every endpoint still
        #: answers (the gateway's fallback path relies on that).
        self.serve_class = serve_class
        #: KV wire state: pack/unpack programs + the prefill-side EF
        #: residual store (docs/OPERATIONS.md "Disaggregated
        #: serving"). One per engine — residuals are keyed by chain
        #: hash, so they follow block CONTENT, not requests.
        self._migrator = (KVMigrator(self.pool.block_shapes(), cfg.dtype)
                          if self._wpool is None else None)
        #: export_id -> finished prefill row whose block refs are
        #: parked for migration (released by ReleaseExport).
        self._exports: dict[int, _PagedRow] = {}
        #: ticket -> decode-side migration state (reserved blocks,
        #: resident refs, the ledger record with the migration leg).
        self._tickets: dict[int, dict] = {}
        self._mig_ids = itertools.count(1)
        self._migrations = 0
        self._migrate_bytes = 0
        self._migrate_dedup_hits = 0
        if spec is not None and not cfg.plain:
            raise ValueError(
                "speculative decoding drafts with a truncated GQA "
                "stack of one group; this configuration has latent "
                "attention or several layer groups (its own next-token "
                "module would be the drafter, and is not run)")

        # Speculative decoding (ISSUE 12): the draft model's own paged
        # KV tables ride a second BlockPool (same block geometry, its
        # own reservation discipline — admission reserves BOTH pools'
        # worst case so a mid-window boundary crossing can never find
        # either empty).
        self._spec = spec
        self._dpool: BlockPool | None = None
        if spec is not None:
            if spec.draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"spec draft vocab {spec.draft_cfg.vocab_size} != "
                    f"target vocab {cfg.vocab_size}")
            if int(spec.k) < 1:
                raise ValueError(f"spec.k must be >= 1, got {spec.k}")
            self._dpool = BlockPool(spec.draft_cfg, n_blocks, bt,
                                    device=device)
        #: Current proposal depth (adaptive-k backs this off; 0 =
        #: speculation disabled pending a re-probe).
        self._k_cur = int(spec.k) if spec is not None else 0
        self._spec_ewma = 0.0
        self._spec_windows = 0
        self._spec_probe_left = 0
        self._window_progs: dict = {}
        self._draft_chunk_progs: dict = {}
        #: Device mirror of the slow-moving spec slot state; None =
        #: re-upload (admission, retire, boundary allocation).
        self._sdev: dict | None = None

        ns = self.n_slots
        self._tables = np.zeros((ns, self.nb), np.int32)
        self._wtables = np.zeros((ns, self.nb), np.int32)
        self._wfirst = np.zeros(ns, np.int32)
        self._nalloc = np.zeros(ns, np.int32)
        self._tok = np.zeros(ns, np.int32)
        self._pos = np.zeros(ns, np.int32)
        self._active = np.zeros(ns, bool)
        self._keys = np.zeros((ns, 2), np.uint32)
        self._temps = np.zeros(ns, np.float32)
        self._topk = np.zeros(ns, np.int32)
        self._topp = np.ones(ns, np.float32)
        self._eidx = np.zeros(ns, np.int32)
        # Draft-side slot mirrors + the per-slot speculative RNG
        # counter (advances by k+2 per window: k+1 draft draws plus
        # the acceptance pair ride domain-separated folds of it).
        self._dtables = np.zeros((ns, self.nb), np.int32)
        self._dnalloc = np.zeros(ns, np.int32)
        self._sctr = np.zeros(ns, np.int32)
        #: First position whose draft KV is NOT yet written — plain
        #: decode steps (chaos reject, adaptive k=0, remaining-1
        #: windows) advance the target without touching the draft
        #: pool, and the next window catches the draft up from here
        #: (a cold draft cache would silently bias every later accept
        #: rate, including the re-probe that decides recovery).
        self._dpos = np.zeros(ns, np.int32)
        self._slot_state: dict[int, _PagedRow] = {}
        self._queue: list[_PagedRow] = []
        self._admitting: _PagedRow | None = None
        self._cond = lockcheck.condition("serve_engine.queue")
        self._closed = False
        self._steps = 0
        self._max_live = 0
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefill_chunks = 0
        self._prefill_tokens = 0
        self._max_stall_ms = 0.0

        def engine_step(sampled, params, banks, tok, pos, tables,
                        active, keys, eidx, temps, topk, topp,
                        live_list):
            B = tok.shape[0]
            bt_ = self.block_tokens
            # Write routing in-graph: inactive lanes scatter to the
            # trash block. Keeping this (and the pos/eidx increments)
            # on device lets the engine loop skip re-uploading its
            # slot state on steps where nothing was admitted/retired —
            # the steady-state decode step transfers nothing in.
            wr_b = jax.tree.map(
                lambda t: jnp.where(active,
                                    t[jnp.arange(B), pos // bt_], 0),
                tables)
            wr_o = pos % bt_
            logits, banks, load = gen.decode_step_banks(
                params, tok, pos, self.cfg, banks, tables, wr_b,
                wr_o, live=active, live_list=live_list)
            with jax.named_scope("sample"):
                if sampled:
                    nxt = gen.sample_token_rows(logits, keys, eidx,
                                                temps, topk, topp)
                else:
                    # All-greedy step: skip the per-row sort/gumbel
                    # machinery entirely (the serving hot path; two
                    # cached programs, picked per step by live-slot
                    # inspection).
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                nxt = jnp.where(active, nxt, 0)
            # A dropless router's layers count their load on the
            # device (transformer._moe_dropless): the step then returns,
            # for the host's one fetch, the tokens with the counts of
            # the live lanes behind them, and last the tiles the
            # layers' loops visited and the held experts they hit.
            fetch = (() if load is None
                     else (jnp.concatenate([nxt, load]),))
            return (banks, nxt, jnp.where(active, pos + 1, pos),
                    jnp.where(active, eidx + 1, eidx)) + fetch

        # Donate the banks: the engine must not copy the pool per step.
        self._engine_step = jax.jit(engine_step, donate_argnums=(2,),
                                    static_argnums=(0,))
        #: Device mirrors of the slot state; None = host copy is
        #: authoritative and must be re-uploaded (set dirty by
        #: admission, retire, and block-boundary allocation).
        self._dev: dict | None = None
        #: The decode step in flight: dispatched, its tokens not yet
        #: fetched — ``(tokens, what the fetch reads, [(slot, row)])``.
        #: The host books (``_pos``, ``_eidx``, tables) already count
        #: it; ``_tok`` does not, so a re-upload takes a continuing
        #: row's token from the step's output and a host token only
        #: where ``_fresh`` marks a row activated since that dispatch.
        self._flight: tuple | None = None
        self._fresh = np.zeros(ns, bool)
        #: A non-final prefill chunk dispatched this pass: the step's
        #: tokens are emitted once it is done too, so that the gap it
        #: stalls is the gap of the pass that carried it.
        self._chunk_out = None
        self._merge_tok = jax.jit(
            lambda fresh, host, dev: jnp.where(fresh, host, dev))
        # Compiled now, not at the first admission beside a step in
        # flight: committed where the step's outputs are, under the
        # engine thread's default device (a part of the program's key).
        with jax.default_device(self.device):
            z = jax.device_put(np.zeros(ns, np.int32), self.device)
            self._merge_tok(
                jax.device_put(np.zeros(ns, bool), self.device), z, z)
        #: An indexer selects each query's keys (latent attention
        #: behind one): the step then reads by lane, not by block.
        self._selects = cfg.latent is not None and cfg.latent.indexer
        self.ledger.decode_attn = (
            "lanes" if self._selects
            else "list" if cfg.latent is None else "latent_kernel")
        #: The engine hands the step the list of what its live rows
        #: hold, rebuilt with ``_dev``: the blocks
        #: (gen.live_block_list; a latent cache's with each row's run
        #: on tiles of its own, read by ops.latent_block_attention),
        #: or where an indexer selects the lanes
        #: (gen.live_lane_list). The step's attention then costs what
        #: is in flight, not n_slots x reach. ``_kv``: the list's
        #: counts, as the dispatch span carries them (``kv_blocks``,
        #: ``kv_tiles``; ``live_lanes``, ``lane_tiles``).
        self._kv: dict = {}

        def sample_first(logits, key, temp, topk, topp):
            return gen.sample_token_rows(
                logits, key[None], jnp.zeros((1,), jnp.int32),
                temp[None], topk[None], topp[None])[0]

        self._sample_first = jax.jit(sample_first)
        self._chunk_progs: dict[int, object] = {}
        self._thread = threading.Thread(
            target=self._engine, name="paged-engine", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------ public

    def Generate(self, prompt, max_new_tokens: int = 16,
                 temperature: float = 0.0, seed: int = 0,
                 top_k: int = 0, top_p: float = 1.0,
                 stop_token: int = -1, pad_token: int = 0,
                 repetition_penalty: float = 1.0):
        # One traceparent per call: the actor handler span (when the
        # request arrived over a traced RPC) — the synthesized
        # admit/prefill/decode span tree parents under it, which is
        # what stitches gateway.request → ... → serve.decode. Read
        # before the ingress span becomes the current one.
        tp = trace.traceparent()
        # The request's clock starts here, not at the enqueue stamp:
        # what runs before it (a sampled row's key is an eager
        # program, behind whatever the engine has queued) is the
        # request's wait too.
        with self.ledger.ingress(np.shape(prompt)) as ing:
            return self._generate(
                ing, tp, prompt, max_new_tokens, temperature, seed,
                top_k, top_p, stop_token, pad_token, repetition_penalty)

    def _generate(self, ing, tp, prompt, max_new_tokens, temperature,
                  seed, top_k, top_p, stop_token, pad_token,
                  repetition_penalty):
        prompt = _host_prompt(prompt)
        if (float(repetition_penalty) != 1.0
                or (float(temperature) != 0.0 and prompt.shape[0] > 1)):
            # Repetition penalty needs per-request seen-set state, and
            # a MULTI-row sampled request draws from the solo path's
            # batch-shaped RNG stream — both keep the solo fallback.
            # Single-row sampled requests ride the engine with exact
            # solo RNG parity (sample_token_rows).
            ing.close()
            return super().Generate(prompt, max_new_tokens, temperature,
                                    seed, top_k, top_p, stop_token,
                                    pad_token, repetition_penalty)
        if not 0.0 < float(top_p) <= 1.0:
            raise ValueError(
                f"generate: top_p must be in (0, 1], got {top_p}")
        max_new = int(max_new_tokens)
        if max_new <= 0:
            return jnp.zeros((prompt.shape[0], 0), jnp.int32)
        if prompt.shape[1] + max_new > self.reach:
            raise ValueError(
                f"prompt {prompt.shape[1]} + max_new {max_new} exceeds "
                f"engine reach {self.reach}")
        bt = self.block_tokens
        blocks_per_row = -(-(prompt.shape[1] + max_new) // bt)
        if blocks_per_row > self.pool.capacity:
            raise ValueError(
                f"request needs {blocks_per_row} blocks; pool holds "
                f"{self.pool.capacity}")
        self._enter_request()
        try:
            # The drain seam (ISSUE 13): a draining replica refuses
            # NEW work typed — the frontdoor re-routes to a sibling —
            # while the engine runs already-admitted rows to
            # completion. Checked INSIDE _enter_request (see its
            # docstring): a request must be counted in in_flight
            # before it passes the gate, or drained() could flip true
            # with this request still executing.
            if self._draining:
                self.ledger.shed_untracked()
                raise ShedError("replica draining (scale-down in "
                                "progress); route elsewhere",
                                retry_after_s=0.05)
            # The admission seam: chaos can force a shed/delay here;
            # real sheds (queue full) ride the same typed contract.
            f = chaos.hit("serve.admit", f"rows={prompt.shape[0]}")
            if f is not None:
                if f.action == "delay":
                    f.sleep()
                elif f.action == "shed":
                    self.ledger.shed_untracked()
                    raise ShedError("chaos: serve.admit shed",
                                    retry_after_s=self._retry_after())
            key = (np.asarray(jax.random.PRNGKey(int(seed)))
                   if float(temperature) != 0.0
                   else np.zeros(2, np.uint32))
            rows = [_PagedRow(np.asarray(prompt[i]), max_new,
                              int(stop_token), float(temperature),
                              int(top_k), float(top_p), key)
                    for i in range(prompt.shape[0])]
            for r in rows:
                r.rec = self.ledger.enqueued(len(r.prompt), max_new,
                                             tp=tp, t_call=ing.t_call)
            ing.close()
            with self._lock:
                self._calls += 1
            with self._cond:
                if self._closed:
                    raise RuntimeError("generator actor is closed")
                if (self.max_queue
                        and len(self._queue) + len(rows) > self.max_queue):
                    for r in rows:
                        self.ledger.retired(r.rec, "shed")
                    raise ShedError(
                        f"serving backlog full "
                        f"({len(self._queue)} queued, cap "
                        f"{self.max_queue})",
                        retry_after_s=self._retry_after())
                self._queue.extend(rows)
                # Exported from the CALLER thread on purpose: the
                # serve-stall rule gates on a non-empty queue, and a
                # wedged engine thread (its primary target) would
                # never export the depth that pages it.
                self._reg.gauge("serve.queue_depth").set(
                    len(self._queue))
                self._cond.notify()
            chaos.note_ok("serve.admit")
            out = np.full((len(rows), max_new), int(pad_token),
                          np.int32)
            for i, r in enumerate(rows):
                r.done.wait()
                if r.err is not None:
                    # One row failed (e.g. admit-timeout shed): the
                    # caller gets the error for the WHOLE request, so
                    # withdraw the sibling rows — otherwise they keep
                    # decoding output nobody reads, holding the very
                    # blocks an exhausted pool's shed exists to free.
                    self._cancel_rows(rows)
                    raise r.err
                out[i, :len(r.emitted)] = r.emitted
            return jnp.asarray(out)
        finally:
            self._exit_request()

    def _cancel_rows(self, rows) -> None:
        """Withdraw a request's not-yet-finished rows: queued ones
        leave the queue now; the admitting/active ones are flagged and
        the engine retires them at its next boundary."""
        with self._cond:
            live = set()
            for r in rows:
                if not r.done.is_set():
                    r.cancelled = True
                    live.add(id(r))
            if live:
                kept = []
                for q in self._queue:
                    if id(q) in live:
                        q.err = RuntimeError("request cancelled")
                        self.ledger.retired(q.rec, "cancelled")
                        q.done.set()
                    else:
                        kept.append(q)
                self._queue = kept

    def _retry_after(self) -> float:
        with self._cond:
            backlog = len(self._queue) + len(self._slot_state) + 1
        per = self.ledger.svc_ewma_s() or 0.1
        return round(max(0.05, backlog * per), 3)

    # -------------------------------------------- migration (ISSUE 16)

    def _one_cache(self, what: str) -> None:
        """Migration ships one pool's blocks by one table: say so, not
        a key error, for a model with two kinds of cache."""
        if self._wpool is not None:
            raise ValueError(migrate_refusal(what))

    def Prefill(self, prompt, max_new_tokens: int = 16,
                temperature: float = 0.0, seed: int = 0,
                top_k: int = 0, top_p: float = 1.0,
                stop_token: int = -1) -> dict:
        """Disaggregated prefill: run the prompt through chunked
        prefill (prefix reuse and all), emit the FIRST token, and park
        the prompt's KV blocks under an export id instead of taking a
        decode slot. The gateway pairs this with MigratePlan/
        ImportBlocks/MigrateDecode on a decode-class replica;
        ``max_new_tokens`` is advisory here (the decode side reserves
        for it) — this replica only ever computes token one."""
        self._one_cache("disaggregated prefill")
        prompt = _host_prompt(prompt)
        if prompt.shape[0] != 1:
            raise ValueError("Prefill is single-row (the gateway "
                             "migrates one request at a time)")
        L = int(prompt.shape[1])
        if L + 1 > self.reach:
            raise ValueError(f"prompt {L} exceeds engine reach "
                             f"{self.reach}")
        self._enter_request()
        try:
            if self._draining:
                self.ledger.shed_untracked()
                raise ShedError("replica draining (scale-down in "
                                "progress); route elsewhere",
                                retry_after_s=0.05)
            f = chaos.hit("serve.admit", "prefill")
            if f is not None:
                if f.action == "delay":
                    f.sleep()
                elif f.action == "shed":
                    self.ledger.shed_untracked()
                    raise ShedError("chaos: serve.admit shed",
                                    retry_after_s=self._retry_after())
            key = (np.asarray(jax.random.PRNGKey(int(seed)))
                   if float(temperature) != 0.0
                   else np.zeros(2, np.uint32))
            row = _PagedRow(np.asarray(prompt[0]), 1, int(stop_token),
                            float(temperature), int(top_k),
                            float(top_p), key)
            row.export_id = next(self._mig_ids)
            row.rec = self.ledger.enqueued(L, 1,
                                           tp=trace.traceparent())
            with self._lock:
                self._calls += 1
            with self._cond:
                if self._closed:
                    raise RuntimeError("generator actor is closed")
                if (self.max_queue
                        and len(self._queue) + 1 > self.max_queue):
                    self.ledger.retired(row.rec, "shed")
                    raise ShedError(
                        f"serving backlog full ({len(self._queue)} "
                        f"queued, cap {self.max_queue})",
                        retry_after_s=self._retry_after())
                self._queue.append(row)
                self._reg.gauge("serve.queue_depth").set(
                    len(self._queue))
                self._cond.notify()
            chaos.note_ok("serve.admit")
            row.done.wait()
            if row.err is not None:
                raise row.err
            return {"export_id": int(row.export_id),
                    "first_token": int(row.emitted[0]),
                    "n_tokens": L,
                    "block_tokens": self.block_tokens,
                    "reused": int(row.reused),
                    "hashes": [int(h) for h in row.hashes]}
        finally:
            self._exit_request()

    def ExportBlocks(self, export_id: int, need_idx=None,
                     kv_wire: str = "q8") -> dict:
        """Pack an export's blocks for the wire: the full blocks in
        ``need_idx`` (None = all of them) plus the unsealed partial
        tail — only what the decode side doesn't already hold rides
        the transfer (the manifest dedup MigratePlan computed)."""
        if kv_wire not in WIRE_MODES:
            raise ValueError(f"kv_wire must be one of {WIRE_MODES}, "
                             f"got {kv_wire!r}")
        with self._cond:
            row = self._exports.get(int(export_id))
        if row is None:
            raise RuntimeError(f"unknown export {export_id}")
        toks = row.prompt
        L = len(toks)
        bt = self.block_tokens
        nfull = L // bt
        want = sorted(set(int(i) for i in need_idx)
                      if need_idx is not None else range(nfull))
        if any(i < 0 or i >= nfull for i in want):
            raise ValueError(f"need_idx out of range for {nfull} "
                             f"full blocks: {want}")
        if L % bt:
            want.append(nfull)  # the partial tail always ships
        blocks: list[dict] = []
        nbytes = 0
        # Under the dispatch lock: pack reads the banks the engine
        # thread's prefill/decode programs DONATE — lock-ordered
        # dispatch keeps every read on a live buffer. The hot region
        # holds the pack path to explicit-transfers-only (the wire
        # hop is the one sanctioned sync).
        with self._lock:
            with jitwatch.hot_region("serve.migrate"):
                for i in want:
                    h = row.hashes[i] if i < nfull else None
                    payload, nb = self._migrator.pack_block(
                        self.pool.banks, row.table[i], h, kv_wire)
                    entry = {"idx": int(i),
                             "hash": int(h) if h is not None else None}
                    entry.update(payload)
                    blocks.append(entry)
                    nbytes += nb
        return {"mode": kv_wire, "block_tokens": bt, "n_tokens": L,
                "nbytes": int(nbytes), "blocks": blocks}

    def ReleaseExport(self, export_id: int) -> bool:
        """Drop an export's parked block refs (after migration, or on
        abort). Sealed full blocks park in the LRU — the next request
        sharing the prefix still reuses them here."""
        with self._cond:
            row = self._exports.pop(int(export_id), None)
        if row is None:
            return False
        for bid in row.table:
            self.pool.deref(bid)
        row.table = []
        self._export_gauges()
        return True

    def MigratePlan(self, prompt, max_new_tokens: int = 16,
                    temperature: float = 0.0, seed: int = 0,
                    top_k: int = 0, top_p: float = 1.0,
                    stop_token: int = -1) -> dict:
        """Decode-side admission for a migrating request: reserve the
        worst-case block count BEFORE any bytes move (a transfer that
        could land nowhere is wasted wire), then walk the chain-hash
        manifest and take refs on every block already resident — the
        dedup leg: those are never re-sent. Returns the ticket plus
        ``need`` (full-block indices to ship); a pool that can't
        cover the worst case sheds typed, same contract as
        admission."""
        self._one_cache("a migration")
        prompt = _host_prompt(prompt)
        if prompt.shape[0] != 1:
            raise ValueError("MigratePlan is single-row")
        toks = np.asarray(prompt[0])
        L = int(toks.shape[0])
        max_new = int(max_new_tokens)
        if max_new <= 0:
            raise ValueError("max_new_tokens must be >= 1")
        if L + max_new > self.reach:
            raise ValueError(
                f"prompt {L} + max_new {max_new} exceeds engine "
                f"reach {self.reach}")
        bt = self.block_tokens
        need_total = -(-(L + max_new) // bt)
        if need_total > self.pool.capacity:
            raise ValueError(
                f"request needs {need_total} blocks; pool holds "
                f"{self.pool.capacity}")
        self._enter_request()
        try:
            if self._draining:
                self.ledger.shed_untracked()
                raise ShedError("replica draining (scale-down in "
                                "progress); route elsewhere",
                                retry_after_s=0.05)
            reserved = self.pool.try_reserve(need_total)
            if reserved and self._dpool is not None \
                    and not self._dpool.try_reserve(need_total):
                self.pool.unreserve(need_total)
                reserved = False
            if not reserved:
                self.ledger.shed_untracked()
                raise ShedError(
                    f"kv pool cannot cover migration: need "
                    f"{need_total} blocks, free "
                    f"{self.pool.free_blocks()}",
                    retry_after_s=self._retry_after())
            hashes = block_hashes(toks, bt)
            nfull = L // bt
            table: dict[int, int] = {}
            for i in range(nfull):
                bid = self.pool.lookup(hashes[i],
                                       toks[i * bt:(i + 1) * bt])
                if bid is not None:
                    self.pool.ref(bid)  # consumes one reserved unit
                    table[i] = bid
            resident = len(table)
            self._prefix_hits += resident
            self._prefix_misses += nfull - resident
            self._migrate_dedup_hits += resident
            self._reg.counter("serve.migrate_dedup_hits").add(resident)
            key = (np.asarray(jax.random.PRNGKey(int(seed)))
                   if float(temperature) != 0.0
                   else np.zeros(2, np.uint32))
            rec = self.ledger.enqueued(L, max_new,
                                       tp=trace.traceparent())
            rec.reused_blocks = resident
            self.ledger.migrate_begin(rec)
            need = [i for i in range(nfull) if i not in table]
            tail = L % bt
            ticket = next(self._mig_ids)
            with self._cond:
                if self._closed:
                    raise RuntimeError("generator actor is closed")
                self._tickets[ticket] = {
                    "toks": toks, "hashes": hashes, "table": table,
                    "need": set(need), "tail": tail,
                    "max_new": max_new, "stop_token": int(stop_token),
                    "temperature": float(temperature),
                    "top_k": int(top_k), "top_p": float(top_p),
                    "key": key, "rec": rec, "resident": resident,
                    "reserve_left": need_total - resident,
                    "draft_reserve_left": (need_total
                                           if self._dpool is not None
                                           else 0),
                    "imported": not need and not tail,
                }
            self._export_gauges()
            return {"ticket": int(ticket), "need": need,
                    "resident": resident, "tail": int(tail),
                    "block_tokens": bt}
        finally:
            self._exit_request()

    def ImportBlocks(self, ticket: int, wire: dict) -> dict:
        """Land a migration wire into the pool: allocate from the
        ticket's reservation, scatter each block through the unpack
        program (bank-donating, inside the dispatch lock — imports
        INTERLEAVE with in-flight decode iterations instead of
        stalling them), then seal the full blocks so the whole fleet
        cache warms. A wire missing planned blocks raises — the
        gateway's fallback leg (local prefill on the decode replica)
        owns recovery."""
        with self._cond:
            t = self._tickets.get(int(ticket))
        if t is None:
            raise RuntimeError(f"unknown migration ticket {ticket}")
        mode = wire.get("mode")
        if mode not in WIRE_MODES:
            raise RuntimeError(f"bad kv_wire mode on wire: {mode!r}")
        bt = self.block_tokens
        if int(wire.get("block_tokens", -1)) != bt:
            raise RuntimeError(
                f"wire block_tokens {wire.get('block_tokens')} != "
                f"engine {bt}")
        toks = t["toks"]
        L = len(toks)
        nfull = L // bt
        entries = {}
        for b in wire.get("blocks", ()):
            i = int(b["idx"])
            if i not in t["table"]:  # resident blocks never re-land
                entries[i] = b
        expected = set(t["need"]) | ({nfull} if t["tail"] else set())
        missing = expected - set(entries)
        if missing:
            raise RuntimeError(
                f"migration wire truncated: missing blocks "
                f"{sorted(missing)} of {sorted(expected)}")
        for i in sorted(entries):
            bid = self.pool.alloc()  # consumes one reserved unit
            t["reserve_left"] -= 1
            t["table"][i] = bid
        with self._lock:
            with jitwatch.hot_region("serve.migrate"):
                for i in sorted(entries):
                    self.pool.banks = self._migrator.unpack_block(
                        self.pool.banks, entries[i], t["table"][i],
                        mode)
        for i in sorted(entries):
            if i < nfull:
                self.pool.seal(t["table"][i], t["hashes"][i],
                               toks[i * bt:(i + 1) * bt])
        nbytes = int(wire.get("nbytes", 0))
        t["imported"] = True
        self._migrations += 1
        self._migrate_bytes += nbytes
        self._reg.counter("serve.migrations").add(1)
        self._reg.counter("serve.migrate_bytes").add(nbytes)
        self.ledger.migrate_done(t["rec"], len(entries), nbytes)
        self._export_gauges()
        return {"imported": len(entries), "nbytes": nbytes}

    def MigrateDecode(self, ticket: int, first_token: int):
        """Own the decode lifetime of a migrated request: build the
        row from the ticket's imported table, ride the normal
        admission/decode path (slot activation runs the LOCAL draft
        prefill when speculation is armed), and return the full
        emitted token list — ``first_token`` (computed by the prefill
        replica) included."""
        self._enter_request()
        try:
            if self._draining:
                self.ledger.shed_untracked()
                raise ShedError("replica draining (scale-down in "
                                "progress); route elsewhere",
                                retry_after_s=0.05)
            with self._cond:
                t = self._tickets.get(int(ticket))
                if t is not None and not t["imported"]:
                    t = None  # leave it for AbortMigration
                else:
                    self._tickets.pop(int(ticket), None)
            if t is None:
                raise RuntimeError(
                    f"migration ticket {ticket} unknown or not "
                    f"imported")
            row = _PagedRow(t["toks"], t["max_new"], t["stop_token"],
                            t["temperature"], t["top_k"], t["top_p"],
                            t["key"])
            row.migrated = True
            row.hashes = t["hashes"]
            row.reused = t["resident"]
            row.table = [t["table"][i] for i in range(len(t["table"]))]
            row.prefill_pos = len(t["toks"])
            row.reserve_left = t["reserve_left"]
            row.draft_reserve_left = t["draft_reserve_left"]
            row.emitted = [int(first_token)]
            row.rec = t["rec"]
            with self._cond:
                if self._closed:
                    raise RuntimeError("generator actor is closed")
                # No max_queue gate: this request was admitted (and
                # its blocks committed) at MigratePlan time.
                self._queue.append(row)
                self._reg.gauge("serve.queue_depth").set(
                    len(self._queue))
                self._cond.notify()
            row.done.wait()
            if row.err is not None:
                raise row.err
            return [int(x) for x in row.emitted]
        finally:
            self._exit_request()

    def AbortMigration(self, ticket: int) -> bool:
        """Unwind a ticket whose transfer failed (chaos, transport, a
        dead prefill replica): drop refs, return the reservation,
        retire the ledger record — the request itself is NOT lost,
        the gateway re-runs it as a local prefill on this replica."""
        with self._cond:
            t = self._tickets.pop(int(ticket), None)
        if t is None:
            return False
        for bid in t["table"].values():
            self.pool.deref(bid)
        if t["reserve_left"] > 0:
            self.pool.unreserve(t["reserve_left"])
        if self._dpool is not None and t["draft_reserve_left"] > 0:
            self._dpool.unreserve(t["draft_reserve_left"])
        self.ledger.retired(t["rec"], "cancelled")
        self._export_gauges()
        return True

    # ------------------------------------------------------------ engine

    def _engine(self) -> None:
        """Wrapper: ANY escape — clean close or an engine error — must
        fail every pending row, or callers hang in done.wait()."""
        err: Exception | None = None
        try:
            # The slot state this thread uploads each iteration lands
            # on the replica's own device, next to its params and
            # banks, not on jax.devices()[0].
            with jax.default_device(self.device):
                self._engine_loop()
        except Exception as e:  # noqa: BLE001 — delivered to callers
            err = e
            log.warning("paged engine died", kv={"err": repr(e)})
        with self._cond:
            self._closed = True
            stragglers, self._queue = self._queue, []
            if self._admitting is not None:
                stragglers.append(self._admitting)
                self._admitting = None
        for slot in list(self._slot_state):
            stragglers.append(self._slot_state.pop(slot))
        if self._flight is not None:
            stragglers += [r for _, r in self._flight[2]]
            self._flight = None
        for r in stragglers:
            if not r.done.is_set():
                r.err = err or RuntimeError("generator actor closed")
                self.ledger.retired(r.rec, "error")
                r.done.set()

    def _no_work_locked(self) -> bool:
        """(under _cond) No queue, nothing admitting, no live row, no
        step in flight."""
        return (not self._queue and self._admitting is None
                and not self._active.any() and self._flight is None
                and not self._closed)

    def _engine_loop(self) -> None:
        pending_stall = 0.0
        while True:
            with self._cond:
                while self._no_work_locked():
                    # Idle for want of load: the device's idle time
                    # inside this span is the traffic's, outside it the
                    # host's. The wait looks up each IDLE_LOOK_S and
                    # opens the span anew when who listens has changed:
                    # a capture started on an idle replica shows it
                    # idle, not a gap no span covers.
                    heard = trace.capturing() or trace.enabled()
                    with metrics_mod.annotate("serve.idle"):
                        while (self._no_work_locked() and heard == (
                                trace.capturing() or trace.enabled())):
                            self._cond.wait(IDLE_LOOK_S)
                    pending_stall = 0.0  # idle time is not stall
                if self._closed:
                    return
            # A pass: cancel sweep -> admission round -> block
            # crossings and any upload for step n+1 -> dispatch n+1 ->
            # fetch n -> emit n. One step stays in flight, so the
            # host's work between steps runs while the device computes,
            # and a final chunk's first-token sync waits for step n and
            # the chunk, never for n+1.
            # One iteration record a pass (the batch-composition seam):
            # whose chunks it carried, the step's wall, the rows it
            # emitted for and the co-batched stall, the gap they saw,
            # the whole pass (a speculative window sets its ragged
            # emitted total on the meter before the scope closes).
            with self.ledger.iteration() as it:
                # Cancelled rows (their caller already got a sibling's
                # error) retire before admission: their blocks are
                # exactly the headroom the queue head is waiting on.
                with metrics_mod.annotate("serve.admit"):
                    for slot in list(self._slot_state):
                        if (self._active[slot]
                                and self._slot_state[slot].cancelled):
                            self._retire(slot, "cancelled")
                # Admission round, bounded by the TOKEN budget: several
                # short prompts (or one chunk of a long one) may
                # prefill, but never more than prefill_chunk prompt
                # tokens — that budget IS the stall bound a co-batched
                # decode step sees. Charge it as stall only when a
                # decode was LIVE to wait on it: the chunk that
                # activates the first row of an idle engine stalls
                # nobody (that row's own first decode is not a
                # co-batched waiter). The rows of a step in flight
                # wait on it: their tokens are emitted once it is done.
                if self._active.any() or self._flight is not None:
                    pending_stall += self._admission_round()
                else:
                    self._admission_round()
                    pending_stall = 0.0
                if not self._active.any() and self._flight is None:
                    # Prefill-only pass (no decode co-batched): still
                    # an engine iteration — metered, so `serve.steps`
                    # advances (a burst of max_new=1 requests
                    # completing entirely inside prefill must not read
                    # as a stalled engine with a non-empty queue).
                    continue
                stall_ms, pending_stall = pending_stall * 1e3, 0.0
                self._record_stall(stall_ms)
                # The rows this pass emits for: the step in flight's.
                it.step(sum(not r.done.is_set()
                            for _, r in self._flight[2])
                        if self._flight is not None else 0, stall_ms)
                with metrics_mod.annotate("serve.step"):
                    self._step(it)

    def _banks(self):
        """The cache as the paged programs take it: the pool's banks,
        or with two kinds of cache a dict of each kind's."""
        if self._wpool is None:
            return self.pool.banks
        return {"full": self.pool.banks, "window": self._wpool.banks}

    def _put_banks(self, banks) -> None:
        if self._wpool is None:
            self.pool.banks = banks
        else:
            self.pool.banks = banks["full"]
            self._wpool.banks = banks["window"]

    def _admission_round(self) -> float:
        """Prefill up to ``prefill_chunk`` prompt tokens; returns the
        wall seconds spent (the stall charged to the next step)."""
        budget = self.prefill_chunk
        spent = 0.0
        while budget > 0:
            with metrics_mod.annotate("serve.admit"), self._cond:
                self._maybe_start_admission_locked()
                row = self._admitting
                if row is not None and row.cancelled:
                    # Withdrawn mid-prefill: drop its blocks +
                    # reservation.
                    self._admitting = None
            if row is not None and row.cancelled:
                self._finish_row(row, "cancelled")
                continue
            if row is None:
                break
            with metrics_mod.annotate("serve.prefill"):
                n, dur_s = self._prefill_one_chunk(row, budget)
            budget -= n
            spent += dur_s
        return spent

    def _maybe_start_admission_locked(self) -> None:
        """(under _cond) Move the queue head into admission when a
        slot is free and the pool can cover its worst case. FIFO:
        head-of-line blocking is the fairness contract."""
        if self._admitting is not None or not self._queue:
            return
        if self._active.all():
            return  # no slot to land in
        row = self._queue[0]
        if row.migrated:
            # A migrated row's worst case was reserved at MigratePlan
            # and its prompt KV imported already — admission is just
            # taking the slot.
            self._queue.pop(0)
            self.ledger.admitted(row.rec)
            self._admitting = row
            return
        need = -(-(len(row.prompt) + row.max_new) // self.block_tokens)
        reserved = self.pool.try_reserve(need)
        if reserved and self._dpool is not None \
                and not self._dpool.try_reserve(need):
            # Both pools or neither: a row admitted against the target
            # pool only would dead-end at its first draft write.
            self.pool.unreserve(need)
            reserved = False
        # The window layers' pool likewise: the most the row holds
        # there at once, which what it gives back as it advances
        # covers for its whole length.
        wneed = 0 if self._wpool is None else min(need, self._wrow)
        if reserved and wneed and not self._wpool.try_reserve(wneed):
            self.pool.unreserve(need)
            reserved = False
        if not reserved:
            # Blocks come back at retire; re-checked each loop. But a
            # bounded wait only: past admit_timeout_s AT THE QUEUE
            # HEAD (not counting time spent behind other requests —
            # backlog depth must not convert momentary pressure into
            # sheds) the pool is EXHAUSTED for this request and it
            # sheds typed — the frontdoor re-routes on that, a burned
            # gateway deadline reads as replica failure.
            head_wait = self.ledger.head_refused(row.rec)
            if (self.admit_timeout_s > 0
                    and head_wait > self.admit_timeout_s):
                self._queue.pop(0)
                row.err = ShedError(
                    f"kv pool exhausted: need {need} blocks, "
                    f"free {self.pool.free_blocks()} after "
                    f"{self.admit_timeout_s:g}s at queue head",
                    retry_after_s=self._retry_after())
                self.ledger.retired(row.rec, "shed")
                row.done.set()
            return
        row.reserve_left = need
        row.wreserve_left = wneed
        if self._dpool is not None:
            row.draft_reserve_left = need
        self._queue.pop(0)
        self.ledger.admitted(row.rec)
        self._admitting = row

    def _chunk_prog(self, C: int):
        prog = self._chunk_progs.get(C)
        if prog is None:
            def prefill_chunk(params, banks, tokens, start, length,
                              table):
                logits, banks, _load = gen.prefill_chunk_banks(
                    params, tokens, start, length, self.cfg, banks,
                    table)
                return logits, banks

            prog = jax.jit(prefill_chunk, donate_argnums=(1,))
            self._chunk_progs[C] = prog
        return prog

    def _prefill_one_chunk(self, row, budget: int | None = None
                           ) -> tuple[int, float]:
        """Prefill one bounded chunk of ``row`` (the admitting row,
        handed over by ``_admission_round`` — reading it back off
        ``self._admitting`` here would be a bare cross-thread read);
        returns (prompt tokens written — the budget consumed, chunk
        seconds — the stall charge)."""
        if row.migrated:
            return self._activate_migrated(row)
        with metrics_mod.annotate("serve.prefill/host"):
            start, n, padded, table_arr = self._prefill_host(row, budget)
        toks = row.prompt
        L = len(toks)
        bt = self.block_tokens
        # The meter stays open through the FINAL chunk's first-token
        # sampling: under async dispatch the program call returns
        # before the device runs, and the np.asarray/sample host sync
        # below is where that chunk's wall is actually paid — closing
        # the meter early would under-report the stall charge (and the
        # chunk span) by the final chunk's compute.
        cm = self.ledger.chunk(row.rec, n)
        cm.ctx = start + n
        with cm:
            # The dispatch lock orders this bank-donating call against
            # ExportBlocks' pack reads on RPC threads (ISSUE 16): a
            # pack that dispatched first still reads the pre-donation
            # buffers; one that dispatches after sees the NEW bank
            # refs — never a half-donated alias.
            with self._lock:
                logits, banks = self._chunk_prog(padded.shape[1])(
                    self.params, self._banks(),
                    jnp.asarray(padded), jnp.int32(start), jnp.int32(n),
                    jax.tree.map(jnp.asarray, table_arr))
                self._put_banks(banks)
            self._chunk_out = logits
            row.prefill_pos += n
            done = row.prefill_pos >= L
            if done:
                # Prompt fully resident: seal the freshly-computed
                # full blocks (reused ones are already in the index)
                # and emit the first token.
                for i in range(row.reused, len(row.hashes)):
                    self.pool.seal(row.table[i], row.hashes[i],
                                   toks[i * bt:(i + 1) * bt])
                    if self._wpool is not None and i >= row.wfirst:
                        self._wpool.seal(row.wtable[i], row.hashes[i],
                                         toks[i * bt:(i + 1) * bt])
                with metrics_mod.annotate("serve.prefill/fetch"):
                    # The host waits here for every chunk of the prompt.
                    if row.temperature == 0.0:
                        first = int(np.asarray(logits)[0].argmax())
                    else:
                        first = int(self._sample_first(
                            logits, jnp.asarray(row.key),
                            jnp.float32(row.temperature),
                            jnp.int32(row.top_k),
                            jnp.float32(row.top_p)))
                if (self._dpool is not None and row.max_new > 1
                        and not (row.stop_token >= 0
                                 and first == row.stop_token)):
                    # The row will take a slot: give the draft model
                    # its prompt KV (inside this chunk's meter, so the
                    # activation cost is a charged stall, not free).
                    self._draft_prefill(row, toks, L)
        self._prefill_chunks += 1
        self._prefill_tokens += n
        if not done:
            return n, cm.dur_s
        # The TTFT stamp: the first token exists on the host here.
        self.ledger.first_token(row.rec)
        row.emitted.append(first)
        with self._cond:
            self._admitting = None
        self._export_gauges()
        if row.export_id is not None:
            self._stash_export(row)
            return n, cm.dur_s
        if (row.max_new == 1
                or (row.stop_token >= 0 and first == row.stop_token)):
            self._finish_row(row,
                             "stop" if (row.stop_token >= 0
                                        and first == row.stop_token)
                             else "complete")
            return n, cm.dur_s
        self._take_slot(row, first, L)
        return n, cm.dur_s

    def _prefill_host(self, row, budget: int | None):
        """What a chunk needs before its program runs: the reuse walk
        (first chunk only), the chunk's blocks, its tokens padded to
        their bucket and its table. → (start, tokens, padded, table)."""
        toks = row.prompt
        L = len(toks)
        bt = self.block_tokens
        if row.prefill_pos < 0:
            # Reuse walk first: ref every leading resident full block.
            # Never through the LAST prompt token — its logits must be
            # computed to emit the first token, so at least one token
            # always prefills.
            row.hashes = block_hashes(toks, bt)
            cap = min(len(row.hashes), (L - 1) // bt)
            found = []
            for i in range(cap):
                bid = self.pool.lookup(row.hashes[i],
                                       toks[i * bt:(i + 1) * bt])
                if bid is None:
                    break
                found.append(bid)
            wfound = []
            if self._wpool is not None:
                r, row.wfirst, wfound = self._window_hit(row, found)
                del found[r:]
            for bid in found:
                self.pool.ref(bid)
                row.reserve_left -= 1
                row.table.append(bid)
                row.reused += 1
            for bid in wfound:
                self._wpool.ref(bid)
                row.wreserve_left -= 1
            row.wtable = [0] * row.wfirst + wfound
            self._prefix_hits += row.reused
            self._prefix_misses += len(row.hashes) - row.reused
            row.prefill_pos = row.reused * bt
            row.rec.reused_blocks = row.reused
        start = row.prefill_pos
        n = min(self.prefill_chunk, L - start)
        if budget is not None:
            n = max(1, min(n, budget))  # always progress: a 0-token
            #                             chunk would loop forever
        while len(row.table) * bt < start + n:
            row.table.append(self.pool.alloc())
            row.reserve_left -= 1
        padded = np.zeros((1, max(16, _pow2(n))), np.int32)
        padded[0, :n] = toks[start:start + n]
        table_arr = np.zeros(self.nb, np.int32)
        table_arr[:len(row.table)] = row.table
        if self._wpool is not None:
            # The window layers: what lies wholly behind the chunk's
            # first query's window goes back first, then the chunk's
            # own blocks come out of the units that freed.
            self._release_window(row, start)
            while len(row.wtable) * bt < start + n:
                row.wtable.append(self._wpool.alloc())
                row.wreserve_left -= 1
            wtable_arr = np.zeros(self.nb, np.int32)
            wtable_arr[:len(row.wtable)] = row.wtable
            table_arr = {"full": table_arr, "window": wtable_arr}
        return start, n, padded, table_arr

    def _window_hit(self, row: _PagedRow, found: list
                    ) -> tuple[int, int, list]:
        """The prefix rule with two kinds of cache. ``found``: the
        full-layer blocks of the prompt's longest chained prefix that
        are resident. A prefix of ``r`` blocks can be skipped only if
        the window layers still hold the blocks its last ``window``
        tokens lie in (the first query after it sees those). → the
        longest such ``r``, the first block of that window, and the
        window pool's blocks from there to ``r`` (a shorter hit where
        later ones were evicted; none: ``(0, 0, [])``)."""
        bt, toks = self.block_tokens, row.prompt
        held: dict[int, int | None] = {}  # looked up as the walk asks

        def wbid(i):
            if i not in held:
                held[i] = self._wpool.lookup(row.hashes[i],
                                             toks[i * bt:(i + 1) * bt])
            return held[i]

        for r in range(len(found), 0, -1):
            lo = max(r * bt - self._window + 1, 0) // bt
            got = [wbid(i) for i in range(lo, r)]
            if all(b is not None for b in got):
                return r, lo, got
        return 0, 0, []

    def _release_window(self, row: _PagedRow, pos: int) -> None:
        """Give back the row's window-layer blocks that lie wholly
        behind the window of a query at ``pos`` (sealed first where
        they are whole prompt blocks this row computed, so that they
        park in the LRU for the prefix rule; the row keeps their
        reserved units for the blocks ahead)."""
        bt = self.block_tokens
        first = min(max(pos - self._window + 1, 0) // bt, len(row.wtable))
        for i in range(row.wfirst, first):
            if row.reused <= i < len(row.hashes):
                self._wpool.seal(row.wtable[i], row.hashes[i],
                                 row.prompt[i * bt:(i + 1) * bt])
            self._wpool.deref(row.wtable[i], keep_unit=True)
            row.wreserve_left += 1
            row.wtable[i] = 0
        self._window_freed += max(first - row.wfirst, 0)
        row.wfirst = max(row.wfirst, first)

    def _take_slot(self, row: _PagedRow, first: int, L: int) -> None:
        """Land a prompt-complete row in a free slot (the caller
        guaranteed one exists — admission gates on it)."""
        slot = int(np.flatnonzero(~self._active)[0])
        self._slot_state[slot] = row
        self._tables[slot] = 0
        self._tables[slot, :len(row.table)] = row.table
        self._nalloc[slot] = len(row.table)
        if self._wpool is not None:
            # Decoding, a row holds its window and no chunk behind it.
            self._release_window(row, L)
            self._wtables[slot] = 0
            self._wtables[slot, :len(row.wtable)] = row.wtable
            self._wfirst[slot] = row.wfirst
        self._tok[slot] = first
        self._fresh[slot] = True
        self._pos[slot] = L
        self._active[slot] = True
        self._keys[slot] = row.key
        self._temps[slot] = row.temperature
        self._topk[slot] = row.top_k
        self._topp[slot] = row.top_p
        self._eidx[slot] = 1
        if self._dpool is not None:
            self._dtables[slot] = 0
            self._dtables[slot, :len(row.draft_table)] = \
                row.draft_table
            self._dnalloc[slot] = len(row.draft_table)
            self._sctr[slot] = 0
            self._dpos[slot] = L  # draft prefill wrote 0..L-1
        self._dev = None  # slot state changed: re-upload next step
        self._sdev = None

    def _activate_migrated(self, row: _PagedRow) -> tuple[int, float]:
        """Land an imported migration in a slot: no prefill — the
        prompt KV arrived over the wire — but when speculation is
        armed the DRAFT model prefills locally from the prompt tokens
        (draft KV is draft-params specific and never rides the wire),
        so migration cannot introduce draft/target disagreement and
        the accept rate is untouched by the transfer. The TTFT stamp
        here is the decode replica's own attribution: plan →
        activation, the migration leg included."""
        toks = row.prompt
        L = len(toks)
        first = row.emitted[0]
        cm = self.ledger.chunk(row.rec, 0)
        with cm:
            if (self._dpool is not None and row.max_new > 1
                    and not (row.stop_token >= 0
                             and first == row.stop_token)):
                self._draft_prefill(row, toks, L)
        self.ledger.first_token(row.rec)
        with self._cond:
            self._admitting = None
        self._export_gauges()
        if (row.max_new == 1
                or (row.stop_token >= 0 and first == row.stop_token)):
            self._finish_row(row,
                             "stop" if (row.stop_token >= 0
                                        and first == row.stop_token)
                             else "complete")
            return 0, cm.dur_s
        self._take_slot(row, first, L)
        return 0, cm.dur_s

    def _stash_export(self, row: _PagedRow) -> None:
        """Disaggregated prefill complete: park the prompt's block
        refs under the export id (ExportBlocks packs from them;
        ReleaseExport drops them) and return every unused reservation
        unit now — an export row never decodes here, so holding its
        decode worst-case would starve admission for nothing."""
        if row.reserve_left > 0:
            self.pool.unreserve(row.reserve_left)
            row.reserve_left = 0
        if self._dpool is not None and row.draft_reserve_left > 0:
            self._dpool.unreserve(row.draft_reserve_left)
            row.draft_reserve_left = 0
        with self._cond:
            self._exports[row.export_id] = row
        self.ledger.retired(row.rec, "complete")
        row.done.set()

    def _step(self, meter=None) -> None:
        """One engine iteration over the live slots: a speculative
        window when speculation is armed and earns its depth (the step
        in flight drained first: a window commits ragged advances
        from host tokens), else the plain one-token batched decode
        step, dispatched ahead of the previous one's fetch."""
        if self._spec is not None:
            k_eff = self._spec_k_eff()
            if k_eff >= 1:
                # The speculation chaos seam: "reject" poisons the
                # window (this iteration falls back to the plain step
                # — correct tokens, just slower), "delay" stalls the
                # draft forward; the next committed window beacons the
                # paired recovery.
                f = chaos.hit("serve.spec", f"k={k_eff}")
                if f is not None and f.action == "delay":
                    f.sleep()
                    f = None
                if f is None:
                    prev, self._flight = self._flight, None
                    drained = self._emit(prev) if prev is not None else 0
                    if self._active.any():
                        self._spec_step(k_eff, meter)
                        if meter is not None:
                            meter.ahead = 0
                            meter.decode_tokens += drained
                    return
        if meter is not None and self._active.any():
            meter.ahead = int(self._flight is not None)
        self._plain_step()

    def _plain_step(self) -> None:
        """Dispatch the next decode step over the live slots, then
        fetch and emit the one dispatched the pass before: the host's
        work for step n+1 runs while the device computes step n, and
        the device has step n+1 queued when step n ends. A pass with
        no live slot only fetches and emits."""
        # The iteration's phases are regions of their own inside
        # serve.step, so a device profile says what the host was doing
        # in each of the device's gaps (PERF.md §3).
        prev, self._flight = self._flight, None
        if self._active.any():
            self._dispatch(prev)
        if prev is not None:
            self._emit(prev)

    def _dispatch(self, flight) -> None:
        """Dispatch a decode step over the live slots, ``flight`` the
        previous step if it is still on the device: the step's block
        crossings, its upload where the slot state changed, the call."""
        annotate = metrics_mod.annotate
        with annotate("serve.step/blocks"):
            # Boundary crossings first: a slot whose next write lands
            # past its allocated blocks materializes one from its
            # reservation (guaranteed — admission reserved the worst
            # case).
            for slot in np.flatnonzero(self._active):
                if (self._pos[slot]
                        == self._nalloc[slot] * self.block_tokens):
                    row = self._slot_state[slot]
                    bid = self.pool.alloc()
                    row.reserve_left -= 1
                    row.table.append(bid)
                    self._tables[slot, self._nalloc[slot]] = bid
                    if self._wpool is not None:
                        # The window layers cross the same boundary:
                        # the block behind the window goes back, the
                        # one ahead comes out of the unit that freed.
                        self._release_window(row, int(self._pos[slot]))
                        self._wtables[slot, :row.wfirst] = 0
                        self._wfirst[slot] = row.wfirst
                        wbid = self._wpool.alloc()
                        row.wreserve_left -= 1
                        row.wtable.append(wbid)
                        self._wtables[slot, self._nalloc[slot]] = wbid
                    self._nalloc[slot] += 1
                    self._dev = None  # tables changed: re-upload
                    self._sdev = None
            sampled = bool((self._temps[self._active] > 0.0).any())
        if self._dev is None:
            # device_put, not jnp.asarray: on a placed replica the
            # step's outputs are COMMITTED to its device, and tok/pos/
            # eidx feed straight back in — a fresh upload must carry
            # the same commitment or the second step of every request
            # sees a new signature and compiles again (chip run, PR 21).
            with annotate("serve.step/upload"):
                tables = self._tables
                if not self._selects:
                    live_list = gen.live_block_list(
                        self._tables, self._nalloc, self._active,
                        self.block_tokens,
                        own_tiles=self.cfg.latent is not None)
                    self._kv = {
                        "kv_blocks": int(
                            self._nalloc[self._active].sum()),
                        "kv_tiles": int(live_list[1])}
                    if self._wpool is not None:
                        wlist = gen.live_block_list(
                            self._wtables, self._nalloc, self._active,
                            self.block_tokens, first=self._wfirst,
                            row_blocks=self._wdec)
                        self._kv.update(
                            win_blocks=int((self._nalloc - self._wfirst)[
                                self._active].sum()),
                            win_tiles=int(wlist[1]))
                        live_list = {"full": live_list, "window": wlist}
                        tables = {"full": self._tables,
                                  "window": self._wtables}
                else:
                    live_list = gen.live_lane_list(self._active)
                    self._kv = {
                        "live_lanes": int(self._active.sum()),
                        "lane_tiles": int(live_list[1])}
                up = {"pos": self._pos, "tables": tables,
                      "active": self._active, "keys": self._keys,
                      "eidx": self._eidx, "temps": self._temps,
                      "topk": self._topk, "topp": self._topp}
                # With a step in flight the host's tokens are one step
                # stale: a continuing row's token is that step's
                # output, already on the device; only the rows
                # activated since its dispatch send theirs.
                fresh = flight is not None and self._fresh.any()
                if flight is None or fresh:
                    up["tok"] = self._tok
                if fresh:
                    up["fresh"] = self._fresh
                # Copies: the books change in place right after the
                # dispatch, while the step (and the transfer; on a
                # CPU none, the device array IS the host's) may not
                # have run yet.
                up = jax.tree.map(np.array, up)
                up["live_list"] = live_list
                d = jax.device_put(up, self.device)
                if fresh:
                    d["tok"] = self._merge_tok(d.pop("fresh"), d["tok"],
                                               flight[0])
                elif flight is not None:
                    d["tok"] = flight[0]
                self._dev = d
        d = self._dev
        self._steps += 1
        n_live = int(self._active.sum())
        self._max_live = max(self._max_live, n_live)
        kv = self._kv
        if not self._selects:
            full_list = (d["live_list"] if self._wpool is None
                         else d["live_list"]["full"])
            self.ledger.kv_list(
                kv["kv_blocks"], kv["kv_tiles"],
                int(self._pos[self._active].sum()) + n_live,
                full_list[0].shape[2] * self.block_tokens)
            if self._wpool is not None:
                self.ledger.cache(kv["kv_blocks"], kv["win_blocks"],
                                  self._window_freed, kv["kv_blocks"])
                self._window_freed = 0
        else:
            self.ledger.lane_list(kv["live_lanes"], kv["lane_tiles"],
                                  d["live_list"][0].shape[1])
        with annotate("serve.step/dispatch", **kv), self._lock:
            # Armed (PTYPE_JITWATCH=1), the hot region makes any
            # unsanctioned implicit transfer into the decode step
            # raise at the call — the steady-state step re-uploads
            # NOTHING, and jitwatch counts its compiles.
            with jitwatch.hot_region("serve.decode"):
                (banks, nxt, d["pos"], d["eidx"],
                 *fetch) = self._engine_step(
                    sampled, self.params, self._banks(),
                    d["tok"], d["pos"], d["tables"], d["active"],
                    d["keys"], d["eidx"], d["temps"], d["topk"],
                    d["topp"], d["live_list"])
                self._put_banks(banks)
        d["tok"] = nxt
        # The host books advance at dispatch: the positions, emission
        # indices and block crossings of the next step are known
        # before its tokens are. A row whose last token this step
        # computes leaves the batch now; it finishes when the token
        # is emitted.
        live = [(int(s), self._slot_state[int(s)])
                for s in np.flatnonzero(self._active)]
        self._pos[self._active] += 1
        self._eidx[self._active] += 1
        self._fresh[:] = False
        self._flight = (nxt, fetch[0] if fetch else nxt, live)
        for slot, row in live:
            if self._eidx[slot] >= row.max_new:
                self._release(slot)

    def _emit(self, flight) -> int:
        """Fetch a dispatched step's tokens and hand them to their
        rows; → the rows that emitted. A row retired since the
        dispatch (cancelled, or stopped one step before) discards its
        lane."""
        nxt, out, rows = flight
        annotate = metrics_mod.annotate
        with annotate("serve.step/fetch"):
            # The host waits for the device here: for the step, and
            # for a chunk this pass queued behind it, so that a chunk's
            # time is in the gap of the pass that carried it (and two
            # chunks never share one gap).
            host = np.array(out)  # host mirror for retire bookkeeping
            if self._chunk_out is not None:
                self._chunk_out.block_until_ready()
                self._chunk_out = None
        if out is not nxt:
            *counts, tiles, hit = host[self.n_slots:]
            self.ledger.moe_load(
                counts, tiles, hit, tfm.expert_tile(self.n_slots),
                self.cfg.n_layers - self.cfg.n_dense_layers)
        with annotate("serve.step/emit"):
            live = [(slot, row) for slot, row in rows
                    if not row.done.is_set()]
            if live:
                # One shared stamp for every row that just emitted —
                # the per-token decode-delta trail behind the TPOT
                # histogram.
                self.ledger.tokens_emitted([row.rec for _, row in live])
            for slot, row in live:
                t = int(host[slot])
                row.emitted.append(t)
                held = self._slot_state.get(slot) is row
                if held:
                    self._tok[slot] = t
                stop = row.stop_token >= 0 and t == row.stop_token
                if stop or len(row.emitted) >= row.max_new:
                    reason = "stop" if stop else "complete"
                    if held:
                        self._retire(slot, reason)
                    else:
                        self._finish_row(row, reason)
            if self._steps % 32 == 0:
                self._export_gauges()  # sampler cadence is ~50 ms+;
                #                        the retire/admission exports
                #                        keep the block gauges fresh
                #                        between these.
        return len(live)

    # ------------------------------------------------------ speculation

    def _draft_prefill(self, row: _PagedRow, toks, L: int) -> None:
        """Whole-prompt draft prefill into the row's draft tables at
        activation (no prefix reuse — draft KV is draft-params
        specific, and the draft model is the cheap one). Runs inside
        the final chunk's meter; the trailing block wait pins the
        draft compute's wall there instead of deferring it into the
        first speculation window under async dispatch."""
        bt = self.block_tokens
        while len(row.draft_table) * bt < L:
            row.draft_table.append(self._dpool.alloc())
            row.draft_reserve_left -= 1
        table_arr = np.zeros(self.nb, np.int32)
        table_arr[:len(row.draft_table)] = row.draft_table
        C = max(16, _pow2(L))
        padded = np.zeros((1, C), np.int32)
        padded[0, :L] = toks
        _, self._dpool.banks = self._draft_chunk_prog(C)(
            self._spec.draft_params, self._dpool.banks,
            jnp.asarray(padded), jnp.int32(0), jnp.int32(L),
            jnp.asarray(table_arr))
        self._dpool.k.block_until_ready()

    def _draft_chunk_prog(self, C: int):
        prog = self._draft_chunk_progs.get(C)
        if prog is None:
            dcfg = self._spec.draft_cfg

            def draft_prefill_chunk(params, banks, tokens, start,
                                    length, table):
                return gen.prefill_chunk_banks(
                    params, tokens, start, length, dcfg, banks,
                    table)[:2]

            prog = jax.jit(draft_prefill_chunk, donate_argnums=(1,))
            self._draft_chunk_progs[C] = prog
        return prog

    def _draft_catch_up(self, slot: int, row: _PagedRow) -> None:
        """Backfill the draft pool's KV for positions the row
        committed through PLAIN decode steps (chaos-rejected windows,
        adaptive k=0 stretches, remaining-1 tails): one chunked draft
        pass over the known committed tokens in
        ``[_dpos, pos)`` — without it, every later window's draft
        forward attends through garbage at those positions, silently
        depressing the accept rate (including the k=1 re-probe that
        decides whether a backed-off draft re-earns its depth)."""
        start = int(self._dpos[slot])
        end = int(self._pos[slot])
        if start >= end:
            return
        seq = np.concatenate(
            [np.asarray(row.prompt, np.int32),
             np.asarray(row.emitted, np.int32)])
        n = end - start
        C = max(16, _pow2(n))
        padded = np.zeros((1, C), np.int32)
        padded[0, :n] = seq[start:end]
        table_arr = np.zeros(self.nb, np.int32)
        table_arr[:len(row.draft_table)] = row.draft_table
        _, self._dpool.banks = self._draft_chunk_prog(C)(
            self._spec.draft_params, self._dpool.banks,
            jnp.asarray(padded), jnp.int32(start), jnp.int32(n),
            jnp.asarray(table_arr))
        self._dpos[slot] = end

    def _spec_k_eff(self) -> int:
        """Proposal depth for this iteration: the adaptive depth,
        capped so no window can overshoot the deepest live row's
        remaining budget (k ≤ remaining − 1 keeps every write inside
        the reservation the row admitted with — the worst-case cover
        the extended pool audit asserts). 0 = plain decode (depth
        backed off to nothing, or every live row is one token from
        done); while disabled, a k=1 probe window re-runs every
        ``probe_every`` plain iterations."""
        if self._k_cur == 0:
            self._spec_probe_left -= 1
            if self._spec_probe_left > 0:
                return 0
            self._k_cur = 1
            # Fresh evidence decides: park the EWMA at the floor so
            # the probe window's own accept rate dominates via alpha.
            self._spec_ewma = self._spec.accept_floor
        live = np.flatnonzero(self._active)
        if not len(live):
            return 0
        # Emission indices count a step in flight, its tokens not.
        max_r = max(self._slot_state[int(s)].max_new - int(self._eidx[s])
                    for s in live)
        return max(0, min(self._k_cur, max_r - 1))

    def _spec_adapt(self) -> None:
        """Adaptive k (docs/PERF.md "Speculative decoding"): shed one
        proposal depth per window while the accept-rate EWMA sits
        under the floor; at depth 1 and under half the floor, disable
        outright (plain decode + periodic k=1 re-probe — a stale
        draft must not tax every token forever); climb back one depth
        at a time once the rate clears the floor with margin."""
        sp = self._spec
        ew = self._spec_ewma
        if ew < sp.accept_floor:
            if self._k_cur > 1:
                self._k_cur -= 1
            elif self._k_cur == 1 and ew < sp.accept_floor / 2:
                self._k_cur = 0
                self._spec_probe_left = int(sp.probe_every)
        elif ew > sp.accept_floor + 0.15 and self._k_cur < sp.k:
            self._k_cur += 1

    def _window_prog(self, W: int, sampled: bool):
        """ONE fused program per (window width, sampled): draft scan →
        batched target verify → acceptance, with the write routing
        computed in-graph from the device-resident tables — a window
        costs one dispatch and one host sync, whatever k is. That
        amortization (weights read once per window on memory-bound
        hardware, dispatch+sync paid once per window on a host mesh)
        is the whole speedup; three separate dispatches plus
        host-built routing arrays measurably gave it back."""
        key = (W, sampled)
        prog = self._window_progs.get(key)
        if prog is None:
            dcfg = self._spec.draft_cfg
            bt = self.block_tokens
            nb = self.nb

            def spec_window(tparams, dparams, tok, pos, kb, vb, dkb,
                            dvb, tables, dtables, nalloc, dnalloc,
                            active, keys, sctr, temps, topk, topp):
                ap = pos[:, None] + jnp.arange(W)[None, :]  # (B, W)
                blk = jnp.minimum(ap // bt, nb - 1)
                wr_o = ap % bt
                # Inactive lanes and positions past a row's allocated
                # span (an overshooting window on a nearly-done row)
                # scatter to the trash block.
                ok_t = active[:, None] & (ap // bt < nalloc[:, None])
                wr_b = jnp.where(
                    ok_t, jnp.take_along_axis(tables, blk, axis=1), 0)
                ok_d = active[:, None] & (ap // bt < dnalloc[:, None])
                dwr_b = jnp.where(
                    ok_d, jnp.take_along_axis(dtables, blk, axis=1),
                    0)
                prop, dlg, dkb, dvb = gen.draft_propose_paged(
                    dparams, tok, pos, dcfg, dkb, dvb, dtables,
                    dwr_b, wr_o, keys, sctr, temps, topk, topp,
                    n_steps=W, sampled=sampled)
                toks_w = jnp.concatenate(
                    [tok[:, None], prop[:, :W - 1]], axis=1)
                tlg, kb, vb = gen.verify_step_paged(
                    tparams, toks_w, pos, self.cfg, kb, vb, tables,
                    wr_b, wr_o)
                out, n_acc = gen.spec_accept_rows(
                    prop[:, :W - 1], dlg[:, :W - 1], tlg, keys, sctr,
                    temps, topk, topp, sampled=sampled)
                return out, n_acc, kb, vb, dkb, dvb

            prog = jax.jit(spec_window, donate_argnums=(4, 5, 6, 7))
            self._window_progs[key] = prog
        return prog

    def _spec_step(self, k_eff: int, meter=None) -> None:
        """One speculation window over the live slots: the draft
        proposes ``k_eff`` tokens per slot (one scanned program), the
        target verifies all ``k_eff + 1`` positions in ONE batched
        forward through the per-slot gather path, and acceptance
        sampling commits each row's accepted prefix plus one
        corrected/bonus token — ONE dispatch and ONE host sync per
        window instead of one per token. Rejected positions roll back
        as a position rewind (their writes sit in the row's own
        reserved blocks, masked by the position limit until
        overwritten) — block tables are never
        truncated-and-reallocated."""
        W = k_eff + 1
        bt = self.block_tokens
        live = [int(s) for s in np.flatnonzero(self._active)]
        # Worst-case block cover for the window, BOTH pools: every
        # allocation consumes a unit the row reserved at admission
        # (the span cap keeps pos + W inside ceil(span / bt) blocks,
        # so reservation exhaustion is structurally impossible — the
        # extended check_invariants audit pins that).
        for slot in live:
            row = self._slot_state[slot]
            span = len(row.prompt) + row.max_new
            need_tokens = min(int(self._pos[slot]) + W, span)
            while self._nalloc[slot] * bt < need_tokens:
                bid = self.pool.alloc()
                row.reserve_left -= 1
                self._tables[slot, self._nalloc[slot]] = bid
                self._nalloc[slot] += 1
                row.table.append(bid)
                self._sdev = None  # tables changed: re-mirror
            while self._dnalloc[slot] * bt < need_tokens:
                bid = self._dpool.alloc()
                row.draft_reserve_left -= 1
                self._dtables[slot, self._dnalloc[slot]] = bid
                self._dnalloc[slot] += 1
                row.draft_table.append(bid)
                self._sdev = None
            # Positions committed through plain steps left draft-KV
            # holes: backfill before this window's draft attends
            # through them.
            self._draft_catch_up(slot, row)
        if self._sdev is None:
            # Device mirror of the SLOW-moving slot state (tables,
            # routing bounds, sampling params): refreshed only on
            # admission/retire/boundary allocation — the steady-state
            # window uploads just tok/pos/sctr.
            self._sdev = {
                "tables": jnp.asarray(self._tables),
                "dtables": jnp.asarray(self._dtables),
                "nalloc": jnp.asarray(self._nalloc),
                "dnalloc": jnp.asarray(self._dnalloc),
                "active": jnp.asarray(self._active),
                "keys": jnp.asarray(self._keys),
                "temps": jnp.asarray(self._temps),
                "topk": jnp.asarray(self._topk),
                "topp": jnp.asarray(self._topp),
            }
        sd = self._sdev
        sampled = bool((self._temps[self._active] > 0.0).any())
        self._steps += 1
        self._max_live = max(self._max_live, len(live))
        tok_dev = jnp.asarray(self._tok)
        pos_dev = jnp.asarray(self._pos)
        sctr_dev = jnp.asarray(self._sctr)
        with metrics_mod.annotate("serve.step/dispatch"), self._lock:
            with jitwatch.hot_region("serve.spec_window"):
                (out_toks, n_acc, self.pool.k, self.pool.v,
                 self._dpool.k, self._dpool.v) = \
                    self._window_prog(W, sampled)(
                        self.params, self._spec.draft_params,
                        tok_dev, pos_dev,
                        self.pool.k, self.pool.v, self._dpool.k,
                        self._dpool.v, sd["tables"], sd["dtables"],
                        sd["nalloc"], sd["dnalloc"], sd["active"],
                        sd["keys"], sctr_dev, sd["temps"],
                        sd["topk"], sd["topp"])
        with metrics_mod.annotate("serve.step/fetch"):
            out_host = np.asarray(out_toks)  # the window's ONE host sync
            acc_host = np.asarray(n_acc)
        emit_recs, emit_counts = [], []
        retires: list[tuple[int, str]] = []
        total_acc = total_emit = 0
        for slot in live:
            row = self._slot_state[slot]
            remaining = row.max_new - len(row.emitted)
            a = int(acc_host[slot])
            toks = [int(t) for t in out_host[slot, :min(a + 1,
                                                        remaining)]]
            reason = None
            if row.stop_token >= 0 and row.stop_token in toks:
                # Stop mid-window: commit through the stop token only
                # (the tail past it was never part of the sequence).
                toks = toks[:toks.index(row.stop_token) + 1]
                reason = "stop"
            row.emitted.extend(toks)
            n = len(toks)
            self._pos[slot] += n
            self._eidx[slot] += n
            self._tok[slot] = toks[-1]
            # Draft KV is correct through the accepted prefix; the
            # new position's token (this window's corrected/bonus, or
            # a rejected slot's overwrite) is written by the NEXT
            # window's first draft step.
            self._dpos[slot] = self._pos[slot]
            self._sctr[slot] += W + 1
            total_acc += a
            total_emit += n
            emit_recs.append(row.rec)
            emit_counts.append(n)
            if reason is None and len(row.emitted) >= row.max_new:
                reason = "complete"
            if reason is not None:
                retires.append((slot, reason))
        self.ledger.tokens_emitted(emit_recs, emit_counts)
        rate = total_acc / max(1, k_eff * len(live))
        al = self._spec.ewma_alpha
        self._spec_ewma = (rate if self._spec_windows == 0
                           else al * rate + (1 - al) * self._spec_ewma)
        self._spec_windows += 1
        self.ledger.spec_window(k_eff * len(live), total_acc,
                                total_emit, self._spec_ewma)
        if meter is not None:
            meter.active = len(live)
            meter.decode_tokens = total_emit
        chaos.note_ok("serve.spec")
        for slot, reason in retires:
            self._retire(slot, reason)
        if self._spec.adaptive:
            self._spec_adapt()
        # Ragged per-slot advances: the host copy is authoritative.
        self._dev = None
        if self._steps % 32 == 0:
            self._export_gauges()

    def check_spec_reservations(self) -> list[str]:
        """Audit both pools' reservation discipline against the
        worst-case speculative advance of every live row (the ISSUE 12
        :meth:`BlockPool.check_invariants` extension). Call from the
        engine thread (tests wrap ``_spec_step``) — row state is
        mid-mutation on any other thread."""
        if self._spec is None:
            return []
        rows_t, rows_d = [], []
        for slot in np.flatnonzero(self._active):
            row = self._slot_state.get(int(slot))
            if row is None:
                continue
            remaining = row.max_new - len(row.emitted)
            adv = min(self._k_cur, max(0, remaining - 1)) + 1
            rows_t.append((int(self._pos[slot]),
                           int(self._nalloc[slot]),
                           row.reserve_left, adv))
            rows_d.append((int(self._pos[slot]),
                           int(self._dnalloc[slot]),
                           row.draft_reserve_left, adv))
        bad = self.pool.check_invariants(spec_rows=rows_t)
        bad += [f"draft: {b}"
                for b in self._dpool.check_invariants(
                    spec_rows=rows_d)]
        return bad

    def check_invariants(self) -> list[str]:
        """Audit every pool this engine holds (tests; the engine quiet
        or on its own thread): the target pool, a drafter's, and with
        two kinds of cache the window layers' — each pool's own
        consistency, and that no row holds more window blocks than it
        was admitted for."""
        bad = self.pool.check_invariants()
        if self._dpool is not None:
            bad += [f"draft: {b}" for b in self._dpool.check_invariants()]
        if self._wpool is not None:
            bad += [f"window: {b}"
                    for b in self._wpool.check_invariants()]
            rows = list(self._slot_state.values())
            if self._admitting is not None:  # ptlint: disable=PT013 -- audit for tests, engine quiet
                rows.append(self._admitting)
            for row in rows:
                held = len(row.wtable) - row.wfirst
                if held + row.wreserve_left > self._wrow or held < 0:
                    bad.append(
                        f"window: a row holds {held} blocks and "
                        f"{row.wreserve_left} units; admitted for "
                        f"{self._wrow}")
        return bad

    def _retire(self, slot: int, reason: str = "complete") -> None:
        self._finish_row(self._release(slot), reason)

    def _release(self, slot: int) -> _PagedRow:
        """Take a decoding row out of its slot and give back its
        blocks; → the row, which finishes once its last token is on
        the host (at once, from ``_retire``)."""
        self._active[slot] = False
        self._temps[slot] = 0.0
        self._dev = None  # slot state changed: re-upload next step
        self._sdev = None
        row = self._slot_state.pop(slot)
        # A step in flight may still write the row's blocks (the one
        # that computes its last token, or a lane-step past a stop or a
        # cancel the host sees only now). They go back to the pool's
        # books here all the same: whatever writes them next is a
        # program dispatched later to the same device, which runs
        # after that step. The lane's own write lands at the row's
        # next position, past the prompt's sealed blocks.
        self._free_row(row)
        self._export_gauges()
        return row

    def _finish_row(self, row: _PagedRow,
                    reason: str = "complete") -> None:
        self._free_row(row)
        self.ledger.retired(row.rec, reason)
        row.done.set()

    def _free_row(self, row: _PagedRow) -> None:
        """Give back every block and reserved unit ``row`` holds, in
        each pool (a second call finds none)."""
        for bid in row.table:
            self.pool.deref(bid)
        row.table = []
        if row.reserve_left > 0:
            self.pool.unreserve(row.reserve_left)
        row.reserve_left = 0
        if self._wpool is not None:
            for bid in row.wtable[row.wfirst:]:
                self._wpool.deref(bid)
            row.wtable, row.wfirst = [], 0
            if row.wreserve_left > 0:
                self._wpool.unreserve(row.wreserve_left)
            row.wreserve_left = 0
        if self._dpool is not None:
            for bid in row.draft_table:
                self._dpool.deref(bid)
            row.draft_table = []
            if row.draft_reserve_left > 0:
                self._dpool.unreserve(row.draft_reserve_left)
            row.draft_reserve_left = 0

    # -------------------------------------------------------- telemetry

    def _record_stall(self, stall_ms: float) -> None:
        if stall_ms > self._max_stall_ms:
            self._max_stall_ms = stall_ms

    def begin_drain(self) -> None:
        """Engine drain seam (ISSUE 13): flip the admission gate —
        Generate sheds typed from here on — and let the engine loop
        run the queue + live slots dry. Lifecycle lands in Info() and
        the ``serve.lifecycle`` gauge so the gateway pool (which sorts
        draining replicas last) and ``obs serve`` both see it."""
        super().begin_drain()
        self._export_gauges()

    def drained(self) -> bool:
        """True once draining AND nothing is admitted, queued, live
        in a slot, or still blocked in a caller thread — the exact
        point where deregister-and-exit loses zero requests."""
        if not self._draining:
            return False
        with self._load_lock:
            if self._in_flight:
                return False
        with self._cond:
            if self._queue or self._admitting is not None:
                return False
            if self._exports or self._tickets:
                # An in-flight migration still references this
                # replica's blocks (export refs on the prefill side,
                # a planned-but-undecoded ticket on the decode side)
                # — exiting now would strand it mid-transfer.
                return False
        return not self._active.any() and self._flight is None

    def _export_gauges(self) -> None:
        reg = self._reg
        reg.gauge("serve.lifecycle").set(
            LIFECYCLE_CODES.get(self.lifecycle, 2))
        reg.gauge("serve.class").set(
            SERVE_CLASS_CODES.get(self.serve_class, 0))
        # Open migration legs on this replica (tickets planned but not
        # yet decoded) — the migration-stall health rule pages when
        # this sits non-zero while serve.migrations stops advancing.
        reg.gauge("serve.migrate_inflight").set(
            len(self._tickets) + len(self._exports))
        st = self.pool.stats()
        reg.gauge("serve.kv_free_blocks").set(st["kv_free_blocks"])
        reg.gauge("serve.kv_util_pct").set(st["kv_util_pct"])
        reg.gauge("serve.prefix_hit_rate").set(self.prefix_hit_rate())
        reg.gauge("serve.prefill_stall_ms").set(
            round(self._max_stall_ms, 3))
        # len() read without _cond on purpose: a point-in-time gauge,
        # and the exporters run on the engine thread mid-admission.
        reg.gauge("serve.queue_depth").set(
            len(self._queue))  # ptlint: disable=PT013 -- point-in-time gauge; list len is GIL-atomic and the engine thread must not contend admission for a sample
        # The kv.* pressure sample the serving alert rules key on.
        self.ledger.kv_sample(st, self.prefix_hit_rate())

    def prefix_hit_rate(self) -> float:
        total = self._prefix_hits + self._prefix_misses
        return round(self._prefix_hits / total, 4) if total else 0.0

    def Info(self) -> dict:
        info = super().Info()
        info["n_slots"] = self.n_slots
        info["engine_steps"] = self._steps
        info["max_live_slots"] = self._max_live
        # Disaggregated-serving surface (ISSUE 16): the class the
        # gateway's two-stage router and the per-class reconcilers
        # key on, plus the migration counters `obs serve` renders.
        info["serve_class"] = self.serve_class
        info["migrations"] = self._migrations
        info["migrate_bytes"] = self._migrate_bytes
        info["migrate_dedup_hits"] = self._migrate_dedup_hits
        with self._cond:
            info["migrate_inflight"] = (len(self._tickets)
                                        + len(self._exports))
        with self._cond:
            info["queue_depth"] = len(self._queue)
        info["live_slots"] = int(self._active.sum())
        info.update(self.pool.stats())
        if self._wpool is not None:
            # The window layers' pool, under its own names: the keys
            # above are the full layers', which grow with a row.
            info.update({k.replace("kv_", "kv_window_", 1): v
                         for k, v in self._wpool.stats().items()})
        info["block_tokens"] = self.block_tokens
        info["prefill_chunk"] = self.prefill_chunk
        info["admit_timeout_s"] = self.admit_timeout_s
        info["prefix_hits"] = self._prefix_hits
        info["prefix_misses"] = self._prefix_misses
        info["prefix_hit_rate"] = self.prefix_hit_rate()
        info["prefill_chunks"] = self._prefill_chunks
        info["prefill_tokens"] = self._prefill_tokens
        info["prefill_stall_ms"] = round(self._max_stall_ms, 3)
        # Serving-ledger surface (ISSUE 10): TTFT/TPOT/e2e tails the
        # gateway's probes and `obs serve` read, plus the recent
        # per-request TTFT samples the pool drains into the fleet SLO
        # tracker (sequence-tagged so probes never double-count).
        info.update(self.ledger.summary())
        info["ttft_recent"] = self.ledger.ttft_recent()
        if self._spec is not None:
            # Speculation surface (ISSUE 12): the accept rate the
            # gateway probes carry fleet-wide (same plumbing as
            # kv_free_blocks / prefix_hit_rate) plus the adaptive-k
            # state an operator diagnoses a collapse with. Totals
            # come from the ledger (the one accumulation home).
            prop, acc, toks = self.ledger.spec_totals()
            info["spec_k"] = int(self._spec.k)
            info["spec_k_cur"] = self._k_cur
            info["spec_windows"] = self._spec_windows
            info["spec_proposed"] = prop
            info["spec_accepted"] = acc
            info["spec_tokens"] = toks
            if prop:
                # Only once speculation actually RAN: the gateway
                # snapshot / runbook contract distinguishes "never
                # speculated" (absent, renders "-") from "collapsed
                # to 0" — a fresh idle replica must not fake a 0.0.
                info["spec_accept_rate"] = round(acc / prop, 4)
            info["spec_accept_ewma"] = round(self._spec_ewma, 4)
            info["kv_draft_free_blocks"] = self._dpool.free_blocks()
        return info

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=10)
