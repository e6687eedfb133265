"""The jit'd train step — GSPMD fast path.

One compiled program per (config, mesh): loss → grads → optax update,
jit'd with NamedSharding on every input/output and donated state buffers.
XLA inserts the collectives the shardings imply (grad allreduce over
data axes, per-layer allgathers for fsdp, psums for model/TP) and
overlaps them with compute — the compiler-scheduled equivalent of the
reference's hand-rolled scatter-gather (coordinator.go:67-99).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ptype_tpu.models import transformer as tfm
from ptype_tpu.parallel.topology import DATA_AXIS


@dataclass
class TrainState:
    """Minimal train state pytree (params + optax state + step)."""

    params: Any
    opt_state: Any
    step: jax.Array

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


def _decay_mask(params) -> Any:
    """True for leaves that should receive weight decay: matmul weights
    only — norm scales and biases (ndim ≤ 1) are exempt, the standard
    AdamW recipe. Note block leaves carry a leading layer dim, so norm
    scales there are ndim == 2; they are exempted by name."""

    def mask_leaf(path, leaf):
        name = ""
        for p in path:
            if hasattr(p, "key"):
                name = str(p.key)
        if "norm" in name:
            return False
        return jnp.ndim(leaf) > 1

    return jax.tree_util.tree_map_with_path(mask_leaf, params)


@dataclass(frozen=True)
class OptHParams:
    """The default recipe's hyperparameters as ONE hashable record —
    the single source every materialization of the recipe reads:
    :func:`default_optimizer` (whole-tree optax chain),
    :func:`default_optimizer_pieces` (per-bucket optax, overlap mode),
    and the flat shard-local AdamW in :mod:`ptype_tpu.parallel.zero`
    (ZeRO-1). Three copies of ``b1=0.9`` would silently drift; one
    frozen dataclass cannot."""

    lr: float = 3e-4
    weight_decay: float = 0.1
    warmup: int = 100
    decay_steps: int = 100_000
    clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8

    def schedule(self):
        return optax.warmup_cosine_decay_schedule(
            0.0, self.lr, self.warmup, decay_steps=self.decay_steps,
            end_value=self.lr * 0.1)


def default_optimizer_hparams(**overrides) -> OptHParams:
    """The default :class:`OptHParams` (overridable per field)."""
    return OptHParams(**overrides)


def default_optimizer_pieces(lr: float = 3e-4, weight_decay: float = 0.1,
                             warmup: int = 100, decay_steps: int = 100_000,
                             clip: float = 1.0):
    """The default recipe split at its one cross-leaf coupling: the
    global-norm clip. Returns ``(clip, make_inner)`` where
    ``make_inner(mask)`` builds the AdamW-with-schedule transform for
    any (sub)tree — per-leaf independent, so the overlap trainer can
    run it per gradient BUCKET as each bucket's collective lands,
    coordinating only the clip scale across buckets
    (train/store_dp.py). :func:`default_optimizer` is assembled from
    the same pieces, so the two paths cannot drift."""
    hp = OptHParams(lr=lr, weight_decay=weight_decay, warmup=warmup,
                    decay_steps=decay_steps, clip=clip)
    sched = hp.schedule()

    def make_inner(mask):
        return optax.adamw(sched, b1=hp.b1, b2=hp.b2,
                           weight_decay=hp.weight_decay, mask=mask)

    return hp.clip, make_inner


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup: int = 100, decay_steps: int = 100_000,
                      clip: float = 1.0):
    """AdamW + cosine schedule + global-norm clip — the standard recipe.
    Weight decay applies to matmul weights only (mask exempts norm
    scales), matching common practice."""
    clip, make_inner = default_optimizer_pieces(
        lr, weight_decay, warmup, decay_steps, clip)
    return optax.chain(
        optax.clip_by_global_norm(clip),
        make_inner(_decay_mask),
    )


def make_apply_fn(optimizer):
    """Jitted ``(params, grads, opt_state) -> (params, opt_state)`` —
    the one optimizer-step helper every eager trainer shares (store_dp,
    param_server, actor_pipeline)."""

    @jax.named_scope("optimizer")
    def optimizer_apply(params, grads, opt_state):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    return jax.jit(optimizer_apply)


def _path_key(path) -> tuple[str, ...]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(p))
    return tuple(out)


def _shard_update_spec(spec: P, shape: tuple, axis: str,
                       size: int) -> P:
    """Add ``axis`` onto the first unsharded, divisible dim of an
    optimizer-moment spec — cross-replica weight-update sharding
    (ZeRO-1; "Automatic Cross-Replica Sharding of Weight Update in
    Data-Parallel Training", PAPERS.md). Annotation is the whole
    implementation: GSPMD lowers the moment update to reduce-scatter +
    sharded update + all-gather on its own."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (e, d) in enumerate(zip(entries, shape)):
        if e is None and d >= size and d % size == 0:
            entries[i] = axis
            return P(*entries)
    return spec


def opt_state_shardings(opt_shape, params_shape, param_sh_tree, repl,
                        shard_update_axis: str | None = None):
    """Sharding for every optimizer-state leaf.

    Optax moment trees (adam mu/nu, …) mirror the params tree inside a
    larger state structure, so each opt leaf is matched to a param by
    PATH SUFFIX (('mu','blocks','wq') ends with ('blocks','wq')) with a
    shape check — never by shape alone, where two unrelated leaves that
    happen to share a shape would silently swap shardings. Unmatched
    leaves (step counts, schedule scalars) replicate.

    ``shard_update_axis``: additionally shard each matched moment over
    that (data-parallel) axis — 1/N optimizer memory per device while
    the PARAMS stay replicated (the plain-DP memory win; the fsdp axis
    already shards moments by construction).
    """
    param_map: dict[tuple[str, ...], tuple[tuple, Any]] = {}
    flat_p = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    flat_sh = jax.tree_util.tree_leaves(
        param_sh_tree, is_leaf=lambda x: isinstance(x, NamedSharding))
    for (path, leaf), sh in zip(flat_p, flat_sh):
        param_map[_path_key(path)] = (tuple(leaf.shape), sh)

    def match(path, leaf):
        key = _path_key(path)
        for i in range(len(key)):
            hit = param_map.get(key[i:])
            if hit is not None and hit[0] == tuple(leaf.shape):
                sh = hit[1]
                if shard_update_axis:
                    mesh = sh.mesh
                    size = int(mesh.shape[shard_update_axis])
                    spec = _shard_update_spec(
                        sh.spec, hit[0], shard_update_axis, size)
                    if spec != sh.spec:
                        return NamedSharding(mesh, spec)
                return sh
        return repl

    return jax.tree_util.tree_map_with_path(match, opt_shape)


def _state_shardings(mesh: Mesh, cfg: tfm.TransformerConfig,
                     optimizer, shard_update: bool = False) -> TrainState:
    """Sharding pytree for TrainState: optax mirrors param specs."""
    axis_sizes = {n: int(mesh.shape[n]) for n in mesh.axis_names}
    pspecs = tfm.param_specs(cfg, axis_sizes)
    to_ns = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    param_sh = jax.tree.map(to_ns, pspecs,
                            is_leaf=lambda x: isinstance(x, P))

    params_shape = jax.eval_shape(lambda: tfm.init_params(
        jax.random.PRNGKey(0), cfg))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    upd_axis = (DATA_AXIS
                if (shard_update and DATA_AXIS in axis_sizes
                    and axis_sizes[DATA_AXIS] > 1) else None)
    if shard_update and upd_axis is None:
        from ptype_tpu import logs

        logs.get_logger("train").warning(
            "shard_update requested but the mesh has no data axis of "
            "size > 1 — optimizer moments stay unsharded",
            kv={"axes": axis_sizes})
    opt_sh = opt_state_shardings(opt_shape, params_shape, param_sh,
                                 to_ns(P()),
                                 shard_update_axis=upd_axis)
    return TrainState(param_sh, opt_sh, to_ns(P()))


def init_state(rng: jax.Array, cfg: tfm.TransformerConfig, mesh: Mesh,
               optimizer=None,
               shard_update: bool = False) -> tuple[TrainState, TrainState]:
    """Initialize a sharded TrainState ON DEVICE: init is jit'd with
    out_shardings so even 8B params never materialize unsharded.
    Returns (state, state_shardings)."""
    optimizer = optimizer or default_optimizer()
    shardings = _state_shardings(mesh, cfg, optimizer, shard_update)
    state = jax.jit(
        lambda r: _init_impl(r, cfg, optimizer),
        out_shardings=shardings,
    )(rng)
    return state, shardings


def _init_impl(rng, cfg, optimizer):
    params = tfm.init_params(rng, cfg)
    return TrainState(params, optimizer.init(params),
                      jnp.zeros((), jnp.int32))


def make_train_step(cfg: tfm.TransformerConfig, mesh: Mesh,
                    optimizer=None, attn_fn: Callable | None = None,
                    seq_axis: bool = False,
                    batch_keys: tuple[str, ...] = ("tokens", "targets"),
                    grad_accum: int = 1,
                    shard_update: bool = False):
    """Compile the train step: (state, batch) → (state, metrics).

    State buffers are donated (in-place update, no HBM copy). Batch comes
    in sharded over the data-like axes; grads reduce over them via the
    sharding-implied allreduce. ``batch_keys`` fixes the batch signature
    (add "loss_mask" for masked training — every key shards the same way).
    ``grad_accum > 1`` splits the batch into that many microbatches and
    averages their grads in a ``lax.scan`` before one optimizer step —
    big effective batches on bounded activation memory.
    """
    optimizer = optimizer or default_optimizer()
    axis_sizes = {n: int(mesh.shape[n]) for n in mesh.axis_names}
    state_sh = _state_shardings(mesh, cfg, optimizer, shard_update)
    batch_sh = NamedSharding(mesh, tfm.batch_spec(axis_sizes, seq_axis))
    batch_shardings = {k: batch_sh for k in batch_keys}
    repl = NamedSharding(mesh, P())

    def grads_of(params, batch):
        if grad_accum == 1:
            return jax.value_and_grad(tfm.loss_fn)(
                params, batch, cfg, attn_fn)
        split = jax.tree.map(
            lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                *x.shape[1:]),
            batch,
        )

        # Global normalizer computed over the WHOLE batch up front (the
        # mask is data, no model eval needed): each microbatch then
        # contributes nll_sum/denom, so loss and grads match grad_accum=1
        # exactly even when valid-token counts differ per microbatch.
        mask = batch.get("loss_mask")
        denom = (jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
                 if mask is not None
                 else jnp.float32(batch["targets"].size))

        def micro_loss(params, mb):
            nll_sum, _, aux = tfm.loss_terms(params, mb, cfg, attn_fn)
            loss = nll_sum / denom
            if cfg.n_experts:
                loss = loss + cfg.moe_aux_coef * aux / grad_accum
            return loss

        def micro(carry, mb):
            loss_sum, grads_sum = carry
            loss, grads = jax.value_and_grad(micro_loss)(params, mb)
            return (loss_sum + loss,
                    jax.tree.map(jnp.add, grads_sum, grads)), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(
            micro, (jnp.float32(0.0), zeros), split)
        return loss, grads

    def step(state: TrainState, batch: dict):
        loss, grads = grads_of(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        new = TrainState(params, opt_state, state.step + 1)
        return new, {"loss": loss, "grad_norm": gnorm, "step": new.step}

    return jax.jit(
        step,
        in_shardings=(state_sh, batch_shardings),
        out_shardings=(state_sh, {"loss": repl, "grad_norm": repl,
                                  "step": repl}),
        donate_argnums=(0,),
    )


#: Batch keys the loss reads; extra stream keys (ids, metadata) are
#: dropped before sharding/tracing. One constant for the train path's
#: filter and the eval path's — two copies would silently drift.
BATCH_KEYS = ("tokens", "targets", "loss_mask")


def make_eval_step(cfg: tfm.TransformerConfig, mesh: Mesh,
                   attn_fn: Callable | None = None,
                   seq_axis: bool = False,
                   batch_keys: tuple[str, ...] = ("tokens", "targets")):
    """Compile the evaluation step: (params, batch) → (nll_sum, denom)
    as replicated device scalars.

    Same shardings and loss lowering as the train step (the fused
    head+loss, so (B,S,V) never materializes) with no optimizer and no
    state mutation. Returning the unnormalized pieces lets callers
    accumulate lazily (no per-batch host sync) and token-weight across
    ragged masks exactly.
    """
    axis_sizes = {n: int(mesh.shape[n]) for n in mesh.axis_names}
    batch_sh = NamedSharding(mesh, tfm.batch_spec(axis_sizes, seq_axis))
    batch_shardings = {k: batch_sh for k in batch_keys}
    repl = NamedSharding(mesh, P())

    def step(params, batch):
        nll_sum, denom, _aux = tfm.loss_terms(params, batch, cfg,
                                              attn_fn)
        return nll_sum, denom

    return jax.jit(step, in_shardings=(None, batch_shardings),
                   out_shardings=(repl, repl))


def evaluate(params, cfg: tfm.TransformerConfig, mesh: Mesh,
             batches, steps: int, attn_fn: Callable | None = None,
             seq_axis: bool = False, _step_cache: dict | None = None)\
        -> dict:
    """Mean loss + perplexity over ``steps`` batches from ``batches``.

    Token-weighted across batches (sums NLL and token counts, divides
    once) so ragged masks can't skew the mean; the per-batch scalars
    stay on device until the end, so dispatch overlaps compute.
    ``_step_cache`` (any dict the caller keeps alive, e.g. the
    Trainer's) reuses compiled eval steps across calls instead of
    retracing per evaluation.
    """
    cache = _step_cache if _step_cache is not None else {}
    nlls, denoms = [], []
    for _ in range(steps):
        batch = next(batches)
        batch = {k: v for k, v in batch.items() if k in BATCH_KEYS}
        keys = tuple(sorted(batch))
        if keys not in cache:
            cache[keys] = make_eval_step(cfg, mesh, attn_fn, seq_axis,
                                         keys)
        nll_sum, denom = cache[keys](params, batch)
        nlls.append(nll_sum)
        denoms.append(denom)
    nll_total = float(sum(nlls))
    tok_total = float(sum(denoms))
    loss = nll_total / max(tok_total, 1.0)
    import math as _math

    return {"loss": loss, "perplexity": _math.exp(min(loss, 700.0)),
            "tokens": int(tok_total)}


class Trainer:
    """Convenience loop: init + compiled step + throughput stats.

    The user-facing shape mirrors the reference's optimus coordinator
    (make work → fan out → gather → repeat, coordinator.go:46-99), but
    the fan-out/gather is one compiled SPMD program per step.
    """

    def __init__(self, cfg: tfm.TransformerConfig, mesh: Mesh,
                 optimizer=None, rng: jax.Array | None = None,
                 attn_fn=None, seq_axis: bool = False,
                 sync_every: int = 16,
                 shard_update: bool = False):
        from ptype_tpu.metrics import StepStats, device_peak_tflops

        self.cfg = cfg
        self.mesh = mesh
        self.optimizer = optimizer or default_optimizer()
        # Resolve attn_impl here (not in forward) so mesh-needing
        # implementations (ring/ulysses) work and tests can introspect.
        self._attn_fn = attn_fn or tfm.resolve_attn_fn(cfg, mesh)
        if cfg.attn_impl in ("ring", "ulysses") and attn_fn is None:
            seq_axis = True
        self._seq_axis = seq_axis
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        #: Cross-replica weight-update sharding (ZeRO-1): optimizer
        #: moments shard over the data axis while params stay
        #: replicated — 1/N optimizer HBM on plain-DP meshes.
        self._shard_update = shard_update
        self.state, self.state_shardings = init_state(
            rng, cfg, mesh, self.optimizer, shard_update=shard_update
        )
        # Compiled steps keyed by the batch's key set (tokens/targets
        # always; loss_mask when the data provides one).
        self._steps: dict[tuple[str, ...], Callable] = {}
        self._eval_steps: dict[tuple[str, ...], Callable] = {}
        self.n_params = tfm.count_params(self.state.params)
        self._stats: StepStats | None = None
        self._peak = device_peak_tflops(mesh.devices.flat[0])
        #: Drain the device queue every N steps (0 = never): keeps the
        #: throughput stats honest without paying a per-step sync —
        #: host input prep overlaps device compute in between.
        self.sync_every = sync_every
        self._host_step = 0

    _BATCH_KEYS = BATCH_KEYS

    def _step_for(self, batch: dict) -> Callable:
        keys = tuple(k for k in self._BATCH_KEYS if k in batch)
        if "tokens" not in keys or "targets" not in keys:
            raise ValueError("batch must contain 'tokens' and 'targets'")
        fn = self._steps.get(keys)
        if fn is None:
            fn = make_train_step(self.cfg, self.mesh, self.optimizer,
                                 self._attn_fn, self._seq_axis,
                                 batch_keys=keys,
                                 shard_update=self._shard_update)
            self._steps[keys] = fn
        return fn

    @property
    def train_step(self) -> Callable:
        """The compiled (tokens, targets) step — compile on first access."""
        return self._step_for({"tokens": None, "targets": None})

    def shard_batch(self, batch: dict) -> dict:
        axis_sizes = {n: int(self.mesh.shape[n])
                      for n in self.mesh.axis_names}
        sh = NamedSharding(
            self.mesh, tfm.batch_spec(axis_sizes, self._seq_axis)
        )
        return {k: jax.device_put(v, sh) for k, v in batch.items()
                if k in self._BATCH_KEYS}

    def step(self, batch: dict) -> dict:
        """Dispatch one step WITHOUT waiting for it: loss/grad_norm come
        back as device scalars (reading them blocks; not reading is
        free), so the next batch's host prep overlaps device compute.
        Throughput stats advance ONLY at drain boundaries (every
        ``sync_every`` steps, or :meth:`sync`): between drains the
        previous drained rates are reported, so ``mfu``/``tokens_per_sec``
        never credit dispatched-but-unexecuted work."""
        from ptype_tpu.metrics import (StepStats, annotate, metrics,
                                       step_annotation)

        batch = self.shard_batch(batch)
        train_step = self._step_for(batch)
        first = self._stats is None
        if first:
            self._stats = StepStats(
                flops_per_token=tfm.flops_per_token(
                    self.cfg, batch["tokens"].shape[1]),
                n_chips=self.mesh.devices.size,
                peak_tflops=self._peak,
            )
            self._host_step = int(self.state.step)
            self._pending_tokens = 0
            self._pending_steps = 0
        # train.step is the health-plane seam too (goodput ledger /
        # trace span). NOTE: this trainer dispatches asynchronously —
        # the region measures dispatch between drains and the whole
        # queue at a drain boundary; the store-DP trainer is the
        # per-step-accurate goodput source.
        with annotate("train.step"), step_annotation(self._host_step):
            self.state, out = train_step(self.state, batch)
        self._host_step += 1
        metrics.counter("train.steps").add(1)
        if first:
            # The first step compiles. Drain it and start the clock
            # behind it: the rates (and "mfu") are of steady steps.
            jax.block_until_ready(out["loss"])
            self._stats.start()
        else:
            self._pending_tokens += batch["tokens"].size
            self._pending_steps += 1
        if self.sync_every and self._host_step % self.sync_every == 0:
            jax.block_until_ready(out["loss"])
            # loss is materialized at the drain anyway — stamp the
            # health gauge without adding a sync.
            metrics.gauge("train.loss").set(float(out["loss"]))
            self._fold_pending()
        return {
            "loss": out["loss"],
            "grad_norm": out["grad_norm"],
            "step": self._host_step,
            "tokens_per_sec": self._stats.tokens_per_sec,
            "tokens_per_sec_per_chip": self._stats.tokens_per_sec_per_chip,
            "mfu": self._stats.mfu,
        }

    def _fold_pending(self) -> None:
        if self._stats is not None and self._pending_steps:
            self._stats.step(self._pending_tokens, self._pending_steps)
            self._pending_tokens = 0
            self._pending_steps = 0

    def sync(self) -> None:
        """Drain the device queue (call before reading final stats)."""
        jax.block_until_ready(self.state.params)
        self._fold_pending()

    def evaluate(self, batches, steps: int) -> dict:
        """Held-out mean loss + perplexity with this trainer's mesh,
        attention lowering, and sharding — no state mutation. Compiled
        eval steps are cached on the trainer across calls."""
        self.sync()  # evaluate the CURRENT params, not a queued update
        return evaluate(self.state.params, self.cfg, self.mesh, batches,
                        steps, attn_fn=self._attn_fn,
                        seq_axis=self._seq_axis,
                        _step_cache=self._eval_steps)

    def throughput(self) -> dict:
        """Drained throughput rates. Call after :meth:`sync` (or at any
        drain boundary) for numbers that reflect completed compute."""
        if self._stats is None:
            return {"tokens_per_sec": 0.0,
                    "tokens_per_sec_per_chip": 0.0, "mfu": 0.0}
        return {
            "tokens_per_sec": self._stats.tokens_per_sec,
            "tokens_per_sec_per_chip":
                self._stats.tokens_per_sec_per_chip,
            "mfu": self._stats.mfu,
        }
