"""Model serving over the actor RPC plane.

The reference's serving story was "register a handler object, join,
serve" (example/calculator/server.go:15-41). This module packages the
generation path the same way: a :class:`GeneratorActor` whose
``Generate`` endpoint runs the compiled KV-cache decode loop, dropping
into an ActorServer next to any other handler. Prompts/outputs ride the
tensor codec as device buffers; callers use the balanced client
(``cluster.new_client("llm").call("Generator.Generate", toks, 16)``).
"""

from __future__ import annotations

import threading

from ptype_tpu import lockcheck

import jax
import jax.numpy as jnp

from ptype_tpu import logs
from ptype_tpu import metrics as metrics_mod
from ptype_tpu.errors import ShedError
from ptype_tpu.models import generate as gen
from ptype_tpu.models import transformer as tfm

log = logs.get_logger("serve")

#: Replica lifecycle states (ISSUE 13): the reconciler's state machine,
#: reported through ``Info()`` so the gateway pool's snapshots and
#: ``obs serve``/``obs scale`` render the same view the reconciler
#: acts on. Numeric codes back the ``serve.lifecycle`` gauge (metric
#: series carry floats; the views map them back).
LIFECYCLES = ("spawning", "warm", "active", "draining", "drained")
LIFECYCLE_CODES = {name: i for i, name in enumerate(LIFECYCLES)}


def _norm_prompt(prompt) -> jnp.ndarray:
    """Tokens from the wire → (B, S) int32 (a bare (S,) gets a batch
    dim) — one normalization for every endpoint."""
    prompt = jnp.asarray(prompt, jnp.int32)
    return prompt[None] if prompt.ndim == 1 else prompt


class GeneratorActor:
    """Generation endpoint over a params pytree.

    Serializes requests (one decode loop at a time per actor — the
    single-chip serving model; scale out by registering more actors
    under the same service and letting the balancer spread callers).

    ``device``: the device this replica lives on. Params are committed
    there, and every jitted program follows them, so several replicas
    in one process (one per chip) do not stack on ``jax.devices()[0]``.
    None leaves placement to JAX's default device.
    """

    def __init__(self, cfg: tfm.TransformerConfig, params=None,
                 rng: jax.Array | None = None, device=None):
        self.cfg = cfg
        self.device = device
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if device is not None:
            # Committed inputs pin the init program (and its outputs)
            # to the device; a given params tree is moved there.
            rng = jax.device_put(rng, device)
            if params is not None:
                params = jax.device_put(params, device)
        self.params = (params if params is not None
                       else jax.jit(lambda r: tfm.init_params(r, cfg))(rng))
        self._lock = lockcheck.lock("serve.actor.decode")
        self._calls = 0
        #: Load telemetry for the gateway's replica pool: requests that
        #: have entered Generate/Logits and not yet returned. Kept
        #: under its own lock — _lock is HELD for a whole decode loop,
        #: and Info() must answer while one is in flight.
        self._load_lock = lockcheck.lock("serve.actor.load")
        self._in_flight = 0
        #: Replica lifecycle (ISSUE 13): "active" for a bare actor;
        #: the reconciler's ReplicaHost moves it through spawning →
        #: warm → active, and :meth:`begin_drain` to "draining".
        self.lifecycle = "active"
        self._draining = False
        self._forward = jax.jit(
            lambda p, t: tfm.forward(p, t, self.cfg))

    def _enter_request(self) -> None:
        with self._load_lock:
            self._in_flight += 1

    def _exit_request(self) -> None:
        with self._load_lock:
            self._in_flight -= 1

    # ------------------------------------------------------------- drain

    def _check_draining(self) -> None:
        """The drain gate: a draining replica refuses NEW work with a
        typed shed (the gateway's frontdoor re-routes it to a sibling
        — no eviction, no lost request) while already-admitted work
        runs to completion. MUST be called AFTER ``_enter_request``
        (inside its try/finally): a request checked before it is
        counted could pass the gate, get preempted, and be invisible
        to ``drained()`` — the replica would deregister and exit with
        the request still executing, exactly the lost request the
        drain contract forbids."""
        with self._load_lock:
            draining = self._draining
        if draining:
            raise ShedError("replica draining (scale-down in "
                            "progress); route elsewhere",
                            retry_after_s=0.05)

    def begin_drain(self) -> None:
        """Stop admitting; in-flight requests finish normally. The
        reconciler (or operator) polls :meth:`drained` and
        deregisters/exits the replica once it reports True."""
        with self._load_lock:
            self._draining = True
            in_flight = self._in_flight
        self.lifecycle = "draining"
        log.info("replica draining", kv={"in_flight": in_flight})

    def drained(self) -> bool:
        """True once a drain was requested AND no request is in
        flight — the point where deregister-and-exit loses nothing."""
        with self._load_lock:
            return self._draining and self._in_flight == 0

    def Generate(self, prompt, max_new_tokens: int = 16,
                 temperature: float = 0.0, seed: int = 0,
                 top_k: int = 0, top_p: float = 1.0,
                 stop_token: int = -1, pad_token: int = 0,
                 repetition_penalty: float = 1.0):
        """prompt: (B, S) int32 tokens → (B, max_new_tokens) int32."""
        prompt = _norm_prompt(prompt)
        self._enter_request()
        try:
            self._check_draining()
            with self._load_lock:
                self._calls += 1
            with self._lock:
                out = gen.generate(
                    self.params, self.cfg, prompt, int(max_new_tokens),
                    float(temperature), jax.random.PRNGKey(int(seed)),
                    top_k=int(top_k), top_p=float(top_p),
                    stop_token=int(stop_token), pad_token=int(pad_token),
                    repetition_penalty=float(repetition_penalty),
                )
            return out
        finally:
            self._exit_request()

    def Logits(self, tokens):
        """Full-sequence logits (B, S, V) — the eval endpoint."""
        tokens = _norm_prompt(tokens)
        self._enter_request()
        try:
            self._check_draining()
            with self._lock:
                return self._forward(self.params, tokens)
        finally:
            self._exit_request()

    def Info(self) -> dict:
        with self._load_lock:
            in_flight = self._in_flight
            calls = self._calls
        return {
            "n_params": tfm.count_params(self.params),
            "d_model": self.cfg.d_model,
            "n_layers": self.cfg.n_layers,
            "vocab_size": self.cfg.vocab_size,
            "max_seq": self.cfg.max_seq,
            "calls": calls,
            # Lifecycle (ISSUE 13): the reconciler's state machine,
            # surfaced so the gateway pool's snapshots (and `obs
            # serve`) render the same fleet view the reconciler acts
            # on — routing sorts draining replicas last.
            "lifecycle": self.lifecycle,
            # Load telemetry (the gateway's least-loaded signal): the
            # serialized actor's backlog is everyone parked on _lock.
            "in_flight": in_flight,
            "queue_depth": max(0, in_flight - 1),
            # Device HBM watermarks (RSS fallback) — refreshed into the
            # mem.* gauges as a side effect, so the health plane's
            # sampler/alerts see the same numbers the probe reads.
            "memory": metrics_mod.record_memory_gauges(
                device=self.device),
        }


def _pow2(n: int) -> int:
    """Smallest power of two >= n (compile-cache bucketing)."""
    return 1 << max(n - 1, 0).bit_length()


class _Pending:
    __slots__ = ("prompt", "max_new", "done", "out", "err")

    def __init__(self, prompt, max_new):
        self.prompt = prompt          # (b_i, S) int32
        self.max_new = max_new
        self.done = threading.Event()
        self.out = None
        self.err = None


class BatchingGeneratorActor(GeneratorActor):
    """GeneratorActor with dynamic request batching.

    Concurrent GREEDY requests that share ``max_new_tokens`` coalesce
    into one decode loop — MIXED prompt lengths included: the batcher
    thread takes the first queued request, drains more for up to
    ``window_ms``, left-pads ragged groups (``generate``'s
    ``prompt_lens`` path — exact greedy parity with solo), and buckets
    both rows and padded length to powers of two so the compile cache
    stays bounded (one program per (B_bucket, S_bucket, max_new)).
    Greedy rows are independent (no cross-row ops in the model), so
    batched results match solo results. Sampled requests (``temperature > 0``) keep
    their exact per-request RNG semantics by running through the solo
    path — batching them would change which fold_in stream each row
    sees.

    This is dynamic batching (triton-style), not continuous batching:
    requests join at loop boundaries, not mid-decode — the right
    cost/benefit at the framework's actor granularity; scale out by
    registering more actors and letting the balancer spread callers.
    """

    def __init__(self, cfg: tfm.TransformerConfig, params=None,
                 rng: jax.Array | None = None, window_ms: float = 5.0,
                 max_batch: int = 32):
        super().__init__(cfg, params, rng)
        self.window_s = window_ms / 1000.0
        self.max_batch = max_batch
        self._queue: list[_Pending] = []
        self._cond = lockcheck.condition("serve.batcher")
        self._closed = False
        self._batches = 0
        self._batched_requests = 0
        self._thread = threading.Thread(
            target=self._worker, name="generate-batcher", daemon=True)
        self._thread.start()

    def Generate(self, prompt, max_new_tokens: int = 16,
                 temperature: float = 0.0, seed: int = 0,
                 top_k: int = 0, top_p: float = 1.0,
                 stop_token: int = -1, pad_token: int = 0,
                 repetition_penalty: float = 1.0):
        if (float(temperature) != 0.0
                or float(repetition_penalty) != 1.0
                or int(stop_token) >= 0):
            # Sampling params / stop masking are per-request semantics:
            # solo path (greedy same-shape requests still batch).
            return super().Generate(prompt, max_new_tokens, temperature,
                                    seed, top_k, top_p, stop_token,
                                    pad_token, repetition_penalty)
        req = _Pending(_norm_prompt(prompt), int(max_new_tokens))
        self._enter_request()
        try:
            self._check_draining()
            with self._cond:
                if self._closed:
                    raise RuntimeError("generator actor is closed")
                self._queue.append(req)
                self._cond.notify()
            req.done.wait()
            if req.err is not None:
                raise req.err
            return req.out
        finally:
            self._exit_request()

    # ------------------------------------------------------------ worker

    def _worker(self) -> None:
        import time

        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if self._closed and not self._queue:
                    return
                # Coalesce: first request opens a window; late arrivals
                # within it join this round.
                deadline = time.monotonic() + self.window_s
                rows = sum(p.prompt.shape[0] for p in self._queue)
                while rows < self.max_batch and not self._closed:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    got = self._cond.wait(timeout=remaining)
                    rows = sum(p.prompt.shape[0] for p in self._queue)
                    if not got:
                        break
                # Take only up to max_batch rows — the window loop
                # stops WAITING at the cap, but a burst (or a fat
                # request queued behind others) could have overshot it;
                # decoding past the cap would pad to a bigger bucket
                # and blow the configured device footprint. A single
                # request larger than max_batch runs alone, uncapped —
                # it can't be split without changing its result shape.
                batch, rows = [], 0
                while self._queue:
                    nxt_rows = self._queue[0].prompt.shape[0]
                    if batch and rows + nxt_rows > self.max_batch:
                        break
                    batch.append(self._queue.pop(0))
                    rows += nxt_rows
            self._run_round(batch)

    def _run_round(self, batch: list[_Pending]) -> None:
        """Group by max_new only: MIXED prompt lengths coalesce via the
        ragged left-padded path (exact greedy parity with solo). Rows
        AND padded lengths bucket to powers of two so the compile cache
        stays bounded; lengths themselves are traced, not compiled."""
        import numpy as np

        groups: dict[int, list[_Pending]] = {}
        for p in batch:
            groups.setdefault(p.max_new, []).append(p)
        for max_new, reqs in groups.items():
            try:
                rows = [np.asarray(p.prompt[i])
                        for p in reqs for i in range(p.prompt.shape[0])]
                n = len(rows)
                # Row-pad to the next power of two: one compiled
                # program per bucket instead of per request count.
                # Never capped below n — a clamp would hand XLA the raw
                # request count again (one compile per distinct n, the
                # unbounded cache this padding exists to avoid).
                bucket = _pow2(n)
                rows += [rows[0]] * (bucket - n)
                # One path for uniform AND mixed lengths: always the
                # ragged lens route, so the compile cache is bounded
                # by (B_bucket, S_bucket, max_new) — a uniform fast
                # path would compile one program per distinct length.
                prompts, lens = gen.pad_prompts(rows)
                # Bucket the PADDED length too (further left-pad; lens
                # stay exact, so results are unchanged) — capped so
                # bucketing can never push a group past max_seq that
                # its members individually fit in.
                S = prompts.shape[1]
                S_b = max(S, min(_pow2(S), self.cfg.max_seq - max_new))
                if S_b > S:
                    prompts = jnp.pad(prompts, ((0, 0), (S_b - S, 0)))
                with self._load_lock:
                    self._calls += len(reqs)
                    self._batches += 1
                    self._batched_requests += len(reqs)
                with self._lock:
                    out = gen.generate(self.params, self.cfg, prompts,
                                       max_new, 0.0,
                                       jax.random.PRNGKey(0),
                                       prompt_lens=lens)
                row = 0
                for p in reqs:
                    b = p.prompt.shape[0]
                    p.out = out[row:row + b]
                    row += b
                    p.done.set()
            except Exception as e:  # noqa: BLE001 — deliver to callers
                for p in reqs:
                    if not p.done.is_set():
                        p.err = e
                        p.done.set()

    def Info(self) -> dict:
        info = super().Info()
        with self._load_lock:
            info["batches"] = self._batches
            info["batched_requests"] = self._batched_requests
        with self._cond:
            # Requests queued for a batching round, not lock-waiters.
            info["queue_depth"] = len(self._queue)
        return info

    def close(self) -> None:
        # Lowercase on purpose: register() exposes only Uppercase
        # methods, so this lifecycle call is NOT remotely reachable.
        with self._cond:
            self._closed = True
            # Claim not-yet-taken requests under the lock: whatever the
            # worker already took it will finish serving (a mid-decode
            # round can outlive any join timeout — don't fail requests
            # a live worker is about to complete).
            stragglers, self._queue = self._queue, []
            self._cond.notify_all()
        for p in stragglers:
            if not p.done.is_set():
                p.err = RuntimeError("generator actor closed")
                p.done.set()
        self._thread.join(timeout=5)


def __getattr__(name: str):
    """Lazy re-exports (PEP 562): the continuous engine now lives in
    :mod:`ptype_tpu.serve_engine` — the paged KV-cache rebase (block
    pool + prefix reuse + chunked prefill; ISSUE 9). Importing it here
    eagerly would cycle (serve_engine subclasses GeneratorActor), and
    serve.py itself must never allocate a full-reach contiguous bank
    again (lint PT009) — ``ContinuousGeneratorActor`` IS the paged
    engine now, same ctor surface (``n_slots``/``max_len``) plus the
    pool knobs (``block_tokens``/``n_blocks``/``prefill_chunk``/
    ``max_queue``/``attn``)."""
    if name in ("ContinuousGeneratorActor", "PagedGeneratorActor"):
        from ptype_tpu.serve_engine.engine import PagedGeneratorActor

        return PagedGeneratorActor
    raise AttributeError(f"module {__name__!r} has no attribute "
                         f"{name!r}")
