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
