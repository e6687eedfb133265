"""Store-backed data-parallel training — the north-star lowering, literal.

BASELINE.json: "`cluster/store.go`'s replicated KV becomes an
XLA-collective parameter store whose push/pull lowers to allreduce/
allgather over ICI". This trainer exercises that contract exactly:

- each data-parallel worker computes grads on its shard,
- ``TensorStore.push_tree("grads", stacked)`` reduces them (psum/pmean
  over the mesh's data axis — the Put that raft used to replicate,
  store.go:56-62),
- the optimizer applies the reduced grads and ``put``s params back, and
  workers ``pull`` them (the linearizable Get, store.go:38-53).

It is deliberately eager between the compiled pieces so the Store
semantics stay observable (epochs bump per push, manifests publish to the
KV tier). The fully-fused GSPMD path in trainer.py is the throughput
choice; this mode exists for Store-semantics parity + the async
param-server family built on it (train/param_server.py).

Gradient-exchange modes (``overlap``):

- ``False`` (default): the legacy fully-async barrier step — push_tree
  dispatches every bucket, the whole-tree optimizer apply consumes the
  results, and nothing on the host blocks until the loss readback.
- ``"drain"``: the synchronous-DDP accounting baseline — same step,
  but the host waits out the collectives (``store.push_wait`` region)
  before the apply, so the goodput ledger's collective leg carries the
  reduce wall time. This is the honest "before" for the overlap
  comparison.
- ``True``: T3-style fine-grained overlap (PAPERS.md arXiv
  2401.16677): buckets dispatch lazily through
  ``TensorStore.push_tree_iter``, each bucket's wait interleaves with
  the next bucket's dispatch + commit + the per-bucket optimizer
  bookkeeping, and the optimizer applies per BUCKET (the default AdamW
  recipe decomposed via ``trainer.default_optimizer_pieces``; the
  global-norm clip — the recipe's one cross-bucket coupling — is
  coordinated through per-bucket partial norms as a device value, so
  the host never syncs for it). A custom ``optimizer`` falls back to
  the whole-tree apply with streamed waits (an arbitrary optax chain
  can't be split per bucket safely).

``zero`` selects a rung of the cross-replica sharding LADDER
(parallel/zero.py, PAPERS.md arXiv 2004.13336); every rung shards the
optimizer state 1/N and runs the identical shard-local AdamW:

- ``zero=1``: grads ride the bucketed ALLREDUCE stream
  (``push_tree_iter``) and stay replicated; the fused apply slices
  each replica's shard of params and grads, then allgathers the
  updated params back.
- ``zero=2`` (also the back-compat ``zero=True``): gradients
  reduce-SCATTER bucket-by-bucket
  (``TensorStore.push_tree_scatter_iter`` — half the wire bytes, same
  int8+EF wire, residuals owned per shard), each replica's grad shard
  feeds the update directly, and the updated params allgather back —
  fused into the per-bucket apply program — before committing to the
  Store. The allgathers dispatch asynchronously, so they overlap the
  next step's data staging the same way the push_tree_iter stream
  overlaps the reduce.
- ``zero=3``: params are RESIDENT sharded too (``ZeroState.pflat`` —
  ``ScatteredTree``-style flats are the only layout); each bucket
  allgathers just-in-time for the forward (one fused launch per
  bucket, the gathered buffers donated to the grads program so they
  die after the forward), the update is purely elementwise on the
  flats, and the new param flats commit straight back to the Store.

All rungs survive churn in-place: :meth:`StoreDPTrainer.reshard`
re-pads and re-places the whole resident state onto a survivor mesh
(``ZeroState.reshard`` — atomic, moments bit-preserved) without a
checkpoint round trip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ptype_tpu import jitwatch
from ptype_tpu.models import transformer as tfm
from ptype_tpu.parallel.mesh import axis_n
from ptype_tpu.parallel.tensorstore import TensorStore, _path_part
from ptype_tpu.parallel.topology import DATA_AXIS
from ptype_tpu.parallel.zero import ShardPlan, ZeroState
from ptype_tpu.train.trainer import (_decay_mask, default_optimizer,
                                     default_optimizer_hparams,
                                     default_optimizer_pieces,
                                     make_apply_fn)

_OVERLAP_MODES = (False, "drain", True)

#: Per-bucket partial square-norm over FULL reduced leaves (the zero=1
#: allreduce stream) — same global-norm coordination as the sharded
#: flats' _sqnorm, summed across buckets by clip_scale.
_leaves_sqnorm = jax.jit(
    lambda vs: sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                   for v in vs))


def _resident_nbytes(arr) -> int:
    """Bytes THIS replica holds of ``arr`` (one addressable shard for
    sharded arrays, the whole buffer for replicated ones)."""
    shards = getattr(arr, "addressable_shards", None)
    return shards[0].data.nbytes if shards else arr.nbytes


class StoreDPTrainer:
    """Data-parallel trainer whose gradient exchange IS the Store."""

    def __init__(self, cfg: tfm.TransformerConfig, store: TensorStore,
                 optimizer=None, rng: jax.Array | None = None,
                 overlap=False, zero: bool = False,
                 zero_hparams=None):
        if overlap not in _OVERLAP_MODES:
            raise ValueError(
                f"StoreDPTrainer: overlap must be one of "
                f"{_OVERLAP_MODES}, got {overlap!r}")
        # Normalize the ladder knob: bool True predates the ladder and
        # IS the reduce-scatter rung (kept as the back-compat
        # spelling); integers name the rung explicitly. The identity
        # check matters — ``True == 1`` but the bool spelling must map
        # to stage 2, not 1.
        if zero is True:
            zero_stage = 2
        elif zero in (False, 0, None):
            zero_stage = 0
        elif zero in (1, 2, 3):
            zero_stage = int(zero)
        else:
            raise ValueError(
                f"StoreDPTrainer: zero must be False, True (= stage "
                f"2), or a ZeRO ladder stage 1/2/3, got {zero!r}")
        if zero and optimizer is not None:
            raise ValueError(
                "StoreDPTrainer: zero=True shards the DEFAULT AdamW "
                "recipe (parallel/zero.py); an arbitrary optimizer "
                "cannot be decomposed into shard-local flat applies — "
                "tune it via zero_hparams (trainer.OptHParams) or "
                "pass zero=False")
        if zero_hparams is not None and not zero:
            raise ValueError(
                "StoreDPTrainer: zero_hparams only applies with "
                "zero=True")
        if zero and overlap is not False:
            raise ValueError(
                "StoreDPTrainer: zero=True has its own streamed "
                "reduce-scatter pipeline; combine it with "
                "overlap=False")
        self.cfg = cfg
        self.store = store
        self.mesh: Mesh = store.mesh
        self.axis = store.axis
        self.n_workers = axis_n(self.mesh, self.axis)
        self.overlap = overlap
        self.zero = zero_stage > 0
        self.zero_stage = zero_stage
        self._custom_opt = optimizer is not None
        self.optimizer = optimizer or default_optimizer()
        rng = rng if rng is not None else jax.random.PRNGKey(0)

        # Replicated over the mesh from the start — the placement every
        # later step's params carry (the apply program's outputs), so
        # the grads program compiles once, not once for the seed
        # placement and again for the steady one.
        params = jax.jit(lambda r: tfm.init_params(r, cfg),
                         out_shardings=NamedSharding(self.mesh, P()))(rng)
        # overlap=True with the default recipe trains through
        # _bucket_states — and zero=True through the 1/N-resident
        # ZeroState — NOT this whole-tree state: leave it None so a
        # consumer (checkpoint, mode switch) fails loudly instead of
        # silently reading never-updated init moments. PT007 enforces
        # the converse: nothing in train/ may build full-tree state
        # outside these init helpers.
        self.opt_state = (None if zero
                          or (overlap is True and not self._custom_opt)
                          else self.optimizer.init(params))
        seed_seq = self.store.put_tree("params", params)
        self._treedef = jax.tree_util.tree_structure(params)
        # Keys in TREEDEF leaf order (tree_flatten_with_path order), NOT
        # the Store's string-sorted order — string sort permutes numeric
        # path components ('10' < '2'), which would silently cross-wire
        # leaves on unflatten.
        self._keys = [
            "params/" + "/".join(_path_part(p) for p in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
        ]
        self._key_index = {k: i for i, k in enumerate(self._keys)}
        # The committed device views, kept locally: the trainer itself
        # wrote them, so re-pulling the whole tree from the store every
        # step is a pure round trip. tree_seq guards external mutation.
        self._param_leaves = list(jax.tree_util.tree_leaves(params))
        self._params_seq = seed_seq
        self.step_count = 0

        # Per-bucket apply machinery (overlap=True, default recipe) —
        # built lazily on the first step, when the bucket plan is known.
        self._buckets: list[list[int]] | None = None
        self._bucket_states: list | None = None
        self._apply_fns: list | None = None
        self._sqnorm_fns: list | None = None
        self._scale_fn = None

        # ZeRO-1 sharded update state (zero=True): the shard plan is
        # known AT INIT (it is a pure function of the param shapes and
        # the wire's bucket_bytes), so the moments materialize sharded
        # from step 0 — no replica ever holds the full optimizer state.
        self._zero: ZeroState | None = None
        self._zero_order: list[int] | None = None
        if self.zero:
            # Slot order is the gradient stream's: store-sorted keys
            # ("grads/..." sorts like "params/..." — same suffixes).
            order = sorted(range(len(self._keys)),
                           key=lambda i: self._keys[i])
            self._zero_order = order
            mask_leaves = jax.tree_util.tree_leaves(_decay_mask(params))
            plan = ShardPlan.for_leaves(
                [self._param_leaves[i] for i in order],
                self.n_workers, self.store.wire.bucket_bytes)
            self._zero = ZeroState.create(
                plan, self.mesh, self.axis,
                zero_hparams or default_optimizer_hparams(),
                [mask_leaves[i] for i in order])
            if self.zero_stage == 3:
                # Params leave the replicated world entirely: resident
                # as P(axis) bucket flats. The seed put_tree's
                # replicated leaf entries are dropped from the store
                # and replaced with per-bucket flat commits (epoch
                # semantics like the grad scatter path) — no replica
                # holds the full tree after this point.
                self._zero.scatter_params(
                    [self._param_leaves[i] for i in order])
                for k in self._keys:
                    self.store.delete(k)
                for bi, flat in enumerate(self._zero.pflat):
                    self.store.commit_sharded(
                        f"params/bucket{bi:05d}", flat)
                self._param_leaves = None
                self._params_seq = self.store.tree_seq("params")
        #: Per-replica resident gradient bytes of the last step's
        #: exchange (full leaves under zero=1, one shard per replica
        #: under zero=2/3) — the bench ladder's grad column.
        self.last_grad_bytes: int | None = None

        # Under zero=3 the gathered param leaves are TRANSIENT: they
        # live only for the forward (locals of _step) and die when it
        # returns — the resident footprint stays the sharded flats,
        # and the apply program's donation (parallel/zero.py
        # _shard_apply3_fn, pinned by progaudit) keeps the update
        # in-place on those flats.
        #: The jitted per-worker ``(params, stacked batch) -> (losses,
        #: grads)`` program — public for inspection, like
        #: ``Trainer.train_step``.
        self.grads_step = self._make_grads_fn()
        self._apply_fn = make_apply_fn(self.optimizer)
        #: (params avals, stacked-batch avals) stashed on the first
        #: step — what compiled_cost() lowers the cost programs
        #: against without holding batch data.
        self._cost_avals: tuple | None = None

    def _make_grads_fn(self):
        """Per-worker grad fn: one compiled program in which every
        device along the store axis computes ITS worker's loss and
        grads on its own shard of the stacked batch, results stacked
        back over the axis. A ``shard_map``, not a ``vmap`` left to
        the SPMD partitioner: the partitioner cannot split the flash
        kernel's custom call, and on a TPU backend JAX refuses to lower
        one in a multi-device jit (see
        ``ops/flash_attention.make_flash_attn_fn``)."""
        cfg = self.cfg
        stack = lambda x: x[None]  # noqa: E731

        def local_grads(params, batch):
            # The local block keeps the stacked worker dim at size 1.
            loss, grads = jax.value_and_grad(tfm.loss_fn)(
                params, jax.tree.map(lambda x: x[0], batch), cfg)
            return stack(loss), jax.tree.map(stack, grads)

        per_worker = P(self.axis)
        return jax.jit(jax.shard_map(
            local_grads, mesh=self.mesh, in_specs=(P(), per_worker),
            out_specs=(per_worker, per_worker), check_vma=False))

    def params(self) -> dict:
        """The current parameter tree. Served from the locally-kept
        committed views — the store is only re-pulled when its write
        stamp says some OTHER writer touched the namespace since this
        trainer's own last put (external mutation / epoch mismatch).

        Under ``zero=3`` there IS no replicated residency: the tree is
        materialized just-in-time from the resident shards via the ONE
        sanctioned full-tree gather (``ZeroState.gather_params``)."""
        if self.zero_stage == 3:
            gathered = self._zero.gather_params()
            leaves = [None] * len(self._keys)
            for slot, i in enumerate(self._zero_order):
                leaves[i] = gathered[slot]
            return jax.tree_util.tree_unflatten(self._treedef, leaves)
        seq = self.store.tree_seq("params")
        if seq == self._params_seq and self._param_leaves is not None:
            return jax.tree_util.tree_unflatten(
                self._treedef, self._param_leaves)
        flat = self.store.get_tree("params")
        self._param_leaves = [flat[k] for k in self._keys]
        self._params_seq = seq
        return jax.tree_util.tree_unflatten(
            self._treedef, self._param_leaves)

    def step(self, batch: dict) -> dict:
        """One DP step. ``batch`` leaves are (B, S); B splits evenly into
        n_workers stacked shards (the scatter, coordinator.go:67-73).

        The whole step runs inside a ``train.step`` region (the
        metrics.annotate seam): one profiler annotation AND — when the
        trace plane is armed — one span whose children are the Store
        push (``store.push_tree/...``) and any coord manifest traffic,
        so a soak failure shows which step a fault landed in. The same
        seam feeds the health plane's goodput ledger (per-step
        data/compute/collective breakdown) when one is installed."""
        from ptype_tpu.metrics import annotate, metrics

        with annotate("train.step"):
            out = self._step(batch)
        # The scalar families the health alert rules watch: loss
        # (NaN/spike) as a gauge, step progress (stall detection) as a
        # counter — sampled into series by the health Sampler.
        metrics.gauge("train.loss").set(out["loss"])
        metrics.counter("train.steps").add(1)
        return out

    def _stage(self, batch: dict):
        from ptype_tpu.metrics import annotate

        B = batch["tokens"].shape[0]
        if B % self.n_workers:
            raise ValueError(
                f"batch size {B} not divisible by {self.n_workers} workers"
            )
        # The data leg of the goodput breakdown: host→device batch
        # staging, attributed separately from compute/collective.
        with annotate("train.data"), \
                jitwatch.sanctioned_transfer("train.data"):
            # The sanctioned host→device seam: the batch upload IS the
            # data leg's contract — typed and counted, so an armed
            # hot region elsewhere can disallow every other transfer.
            sh = NamedSharding(self.mesh, P(self.axis, None, None))
            return {
                k: jax.device_put(
                    jnp.reshape(v,
                                (self.n_workers, B // self.n_workers, -1)),
                    sh,
                )
                for k, v in batch.items()
            }

    def _step(self, batch: dict) -> dict:
        from ptype_tpu.metrics import annotate

        stacked = self._stage(batch)
        params = self.params()
        if self._cost_avals is None:
            aval = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
            self._cost_avals = (
                jax.tree_util.tree_map(aval, params),
                jax.tree_util.tree_map(aval, stacked))
        with jitwatch.hot_region("train.step"):
            # Armed, the guard disallows implicit transfers across the
            # whole dispatch chain (grads → reduce → apply): the batch
            # already staged through the sanctioned seam, so anything
            # else crossing the host boundary here is a leak.
            losses, grads = self.grads_step(params, stacked)

            if self.zero_stage == 1:
                self._reduce_apply_zero1(grads)
            elif self.zero_stage == 3:
                self._reduce_apply_zero3(grads)
            elif self.zero:
                self._reduce_apply_zero(grads)
            elif self.overlap is True:
                self._reduce_apply_overlapped(params, grads)
            elif self.overlap == "drain":
                # Synchronous-DDP accounting: every bucket dispatched,
                # then waited out through BucketPush.wait (the one
                # collective-attribution contract), so the goodput
                # ledger's collective leg is the reduce wall time — the
                # honest baseline the overlap mode shrinks.
                handles = self.store.push_tree_stream("grads", grads,
                                                      op="mean")
                for h in handles:
                    h.wait()
                reduced = self._tree_from_handles(handles)
                with annotate("train.opt"):
                    new_params, self.opt_state = self._apply_fn(
                        params, reduced, self.opt_state)
                self._param_leaves = list(
                    jax.tree_util.tree_leaves(new_params))
                self._params_seq = self.store.put_tree("params",
                                                       new_params)
            else:
                # The gather: Store push == pmean allreduce over the
                # data axis, bucketed — the whole grad tree reduces in
                # ceil(bytes/bucket) fused launches per dtype group,
                # all in flight before the optimizer consumes the
                # first leaf. push_tree returns the committed views
                # directly.
                reduced_flat = self.store.push_tree("grads", grads,
                                                    op="mean")
                reduced = jax.tree_util.tree_unflatten(
                    self._treedef,
                    [reduced_flat[k.replace("params/", "grads/", 1)]
                     for k in self._keys])
                with annotate("train.opt"):
                    new_params, self.opt_state = self._apply_fn(
                        params, reduced, self.opt_state
                    )
                self._param_leaves = list(
                    jax.tree_util.tree_leaves(new_params))
                # Stamp from the seqs OUR put assigned (not a re-read
                # of the global max, which would absorb a concurrent
                # external write into the cache stamp and hide it).
                self._params_seq = self.store.put_tree("params",
                                                       new_params)

        self.step_count += 1
        return {
            "loss": float(jnp.mean(losses)),
            "step": self.step_count,
            "grad_epoch": self.store.epoch(self._grad_key0()),
        }

    # ------------------------------------------- ZeRO sharded updates

    def _reduce_apply_zero(self, grads) -> None:
        """The sharded weight update: stream the per-bucket gradient
        reduce-SCATTER (bucket i's wait interleaves bucket i+1's
        dispatch, like the overlap mode's allreduce stream), coordinate
        the global-norm clip through per-bucket partial sqnorms, then
        run the fused shard-local-AdamW + param-allgather program per
        bucket. Everything dispatches async — the final put_tree's
        arrays are still in flight while the next step stages data."""
        from ptype_tpu.metrics import annotate

        handles = []
        sqs = []
        prev = None
        for h in self.store.push_tree_scatter_iter("grads", grads,
                                                   op="mean"):
            handles.append(h)
            sqs.append(self._zero.partial_sqnorm(h.flat))
            if prev is not None:
                prev.wait()
            prev = h
        if prev is not None:
            prev.wait()
        # The shard-local optimizer leg — its own component in the
        # goodput breakdown (health/goodput.py), so ZeRO's update-FLOP
        # savings are visible in `obs top` and the bench tail.
        with annotate("train.opt/zero"):
            scale = self._zero.clip_scale(sqs)
            for bi, h in enumerate(handles):
                idxs = [self._zero_order[s.index]
                        for s in h.bucket.slots]
                newp = self._zero.apply_bucket(
                    bi, [self._param_leaves[i] for i in idxs],
                    h.flat, scale)
                for i, leaf in zip(idxs, newp):
                    self._param_leaves[i] = leaf
            self._zero.finish_step()
        self.last_grad_bytes = sum(_resident_nbytes(h.flat)
                                   for h in handles)
        new_params = jax.tree_util.tree_unflatten(
            self._treedef, self._param_leaves)
        self._params_seq = self.store.put_tree("params", new_params)

    def _reduce_apply_zero1(self, grads) -> None:
        """ZeRO-1 rung: grads ride the bucketed ALLREDUCE stream
        (``push_tree_iter`` — full reduced leaves, replicated) and the
        fused apply slices each replica's shard of params AND grads
        before the shard-local AdamW + param allgather. Optimizer
        memory is 1/N like the other rungs; grad memory stays full —
        the ladder's measured middle step."""
        from ptype_tpu.metrics import annotate

        handles = []
        sqs = []
        prev = None
        for h in self.store.push_tree_iter("grads", grads, op="mean"):
            handles.append(h)
            sqs.append(_leaves_sqnorm([v for _, v in h.items()]))
            if prev is not None:
                prev.wait()
            prev = h
        if prev is not None:
            prev.wait()
        if len(handles) != len(self._zero.plan.buckets):
            raise ValueError(
                f"zero=1: grad stream produced {len(handles)} "
                f"buckets, the shard plan has "
                f"{len(self._zero.plan.buckets)} — plans diverged")
        with annotate("train.opt/zero"):
            scale = self._zero.clip_scale(sqs)
            grad_bytes = 0
            for bi, h in enumerate(handles):
                idxs = [self._grad_index(k) for k in h.keys]
                gleaves = [v for _, v in h.items()]
                grad_bytes += sum(v.nbytes for v in gleaves)
                newp = self._zero.apply_bucket_full(
                    bi, [self._param_leaves[i] for i in idxs],
                    gleaves, scale)
                for i, leaf in zip(idxs, newp):
                    self._param_leaves[i] = leaf
            self._zero.finish_step()
        self.last_grad_bytes = grad_bytes
        new_params = jax.tree_util.tree_unflatten(
            self._treedef, self._param_leaves)
        self._params_seq = self.store.put_tree("params", new_params)

    def _reduce_apply_zero3(self, grads) -> None:
        """ZeRO-3 rung: grads reduce-scatter exactly like ZeRO-2, but
        params are resident sharded too — the apply is purely
        elementwise on the flats (NO collective; progaudit pins it at
        zero launches) and each bucket's new param flat commits
        straight back to the store with an epoch bump. The full tree
        is never materialized on the update path."""
        from ptype_tpu.metrics import annotate

        handles = []
        sqs = []
        prev = None
        for h in self.store.push_tree_scatter_iter("grads", grads,
                                                   op="mean"):
            handles.append(h)
            sqs.append(self._zero.partial_sqnorm(h.flat))
            if prev is not None:
                prev.wait()
            prev = h
        if prev is not None:
            prev.wait()
        with annotate("train.opt/zero"):
            scale = self._zero.clip_scale(sqs)
            grad_bytes = 0
            for bi, h in enumerate(handles):
                grad_bytes += _resident_nbytes(h.flat)
                newflat = self._zero.apply_bucket3(bi, h.flat, scale)
                self.store.commit_sharded(
                    f"params/bucket{bi:05d}", newflat)
            self._zero.finish_step()
        self.last_grad_bytes = grad_bytes
        self._params_seq = self.store.tree_seq("params")

    # ---------------------------------------------- live resharding

    def reshard(self, mesh: Mesh, axis: str | None = None) -> dict:
        """LIVE reshard onto a survivor mesh — no checkpoint round
        trip. Re-pads and re-places the resident ZeRO state
        (``ZeroState.reshard`` — atomic, moments bit-preserved),
        re-homes the store, re-places the params, and training
        continues on the next ``step()`` call (the jitted programs
        retrace for the new mesh on first use).

        The move runs as a ``train.reshard`` span with an inflight
        gauge and a completion counter — the ``reshard-stall`` health
        rule's series. On a raise (the per-bucket ``train.reshard``
        chaos seam's drop, a placement failure) EVERYTHING is left
        intact — old plan, old mesh, old arrays — and the inflight
        gauge stays up (that IS the stall signal); the caller
        (``ElasticZeroTrainer.recover``) just retries."""
        import time as _t

        from ptype_tpu.metrics import annotate, metrics

        if not self.zero:
            raise ValueError(
                "StoreDPTrainer.reshard: live resharding needs the "
                "sharded ZeRO state — construct with zero=True/1/2/3 "
                "(replicated modes restart from a checkpoint instead)")
        axis = axis or self.axis
        old_n = self.n_workers
        new_n = axis_n(mesh, axis)
        t0 = _t.perf_counter()
        metrics.gauge("train.reshard_inflight").set(1.0)
        with annotate("train.reshard"):
            self._zero.reshard(mesh, axis)
            self.store.reshard(mesh, axis)
            self.mesh = mesh
            self.axis = axis
            self.n_workers = new_n
            self.grads_step = self._make_grads_fn()
            if self.zero_stage == 3:
                for bi, flat in enumerate(self._zero.pflat):
                    self.store.commit_sharded(
                        f"params/bucket{bi:05d}", flat)
                self._params_seq = self.store.tree_seq("params")
            else:
                new_params = jax.tree_util.tree_unflatten(
                    self._treedef,
                    [jax.device_put(np.asarray(x),
                                    NamedSharding(mesh, P()))
                     for x in self._param_leaves])
                self._param_leaves = list(
                    jax.tree_util.tree_leaves(new_params))
                self._params_seq = self.store.put_tree("params",
                                                       new_params)
            self._cost_avals = None
        metrics.gauge("train.reshard_inflight").set(0.0)
        metrics.counter("train.reshards").add(1)
        return {"old_n": old_n, "new_n": new_n,
                "reshard_ms": round((_t.perf_counter() - t0) * 1e3, 2)}

    # --------------------------------------- compiled-cost accounting

    def compiled_cost(self) -> dict:
        """FLOPs/bytes per step as XLA compiled them (ISSUE 8) — the
        ``mfu_compiled`` numerator, fed to a goodput ledger via
        ``ledger.set_compiled_flops(trainer.compiled_cost()["flops"])``.

        Sums the gradient program (lowered with the layer scan fully
        unrolled so ``cost_analysis`` counts every layer — see
        :func:`ptype_tpu.health.profiling.compiled_cost`) and the
        optimizer-apply program(s) of whichever exchange mode this
        trainer runs: the whole-tree apply, the per-bucket overlap
        applies, or the ZeRO-1 shard-local applies. Requires one
        completed step (the batch avals and bucket plans come from
        it)."""
        import dataclasses

        from ptype_tpu.health import profiling

        if self._cost_avals is None:
            raise ValueError(
                "StoreDPTrainer.compiled_cost: run at least one step "
                "first (the cost programs lower against the real "
                "batch shapes)")
        params_avals, stacked_avals = self._cost_avals
        cost_cfg = dataclasses.replace(
            self.cfg, scan_unroll=max(1, self.cfg.n_layers))

        def local_grads(p, b):
            return jax.value_and_grad(tfm.loss_fn)(p, b, cost_cfg)

        programs = {"grads": profiling.compiled_cost(
            jax.jit(jax.vmap(local_grads, in_axes=(None, 0))),
            params_avals, stacked_avals)}
        if self.zero:
            programs["optimizer"] = self._zero.compiled_cost()
        elif self._apply_fns is not None:
            flops = nbytes = 0.0
            scale = jax.ShapeDtypeStruct((), jnp.float32)
            for bi, idxs in enumerate(self._buckets):
                leaves = jax.tree_util.tree_leaves(params_avals)
                subp = {str(i): leaves[i] for i in idxs}
                c = profiling.compiled_cost(
                    self._apply_fns[bi], subp, subp,
                    profiling.tree_avals(self._bucket_states[bi]),
                    scale)
                flops += c["flops"]
                nbytes += c["bytes_accessed"]
            programs["optimizer"] = {"flops": flops,
                                     "bytes_accessed": nbytes}
        elif self.opt_state is not None:
            programs["optimizer"] = profiling.compiled_cost(
                self._apply_fn, params_avals, params_avals,
                profiling.tree_avals(self.opt_state))
        w, b, s = stacked_avals["tokens"].shape
        tokens = w * b * s
        flops = sum(p["flops"] for p in programs.values())
        return {
            "flops": flops,
            "bytes_accessed": sum(p["bytes_accessed"]
                                  for p in programs.values()),
            "tokens_per_step": tokens,
            "flops_per_token": flops / tokens,
            "programs": programs,
        }

    def zero_state(self) -> ZeroState:
        """The 1/N-resident sharded optimizer state (zero=True only) —
        what checkpoint.ZeroCheckpoint saves and restores."""
        if self._zero is None:
            raise ValueError(
                "StoreDPTrainer: no ZeRO state — construct with "
                "zero=True")
        return self._zero

    # ---------------------------------------------- fine-grained overlap

    def _reduce_apply_overlapped(self, params, grads) -> None:
        """Consume the lazy bucket stream: bucket i's wait interleaves
        with bucket i+1's dispatch/commit, then the optimizer applies
        per bucket. The global-norm clip scale is a device value built
        from per-bucket partial norms — no host sync on the clip."""
        handles = []
        sub_grads = []
        sqs = []
        prev = None
        for h in self.store.push_tree_iter("grads", grads, op="mean"):
            handles.append(h)
            if self._sqnorm_fns is not None:
                bi = len(handles) - 1
                g = self._sub_grads(bi, h)
                sub_grads.append(g)
                sqs.append(self._sqnorm_fns[bi](g))
            if prev is not None:
                # Wait out the PREVIOUS bucket while this one (and its
                # partial-norm compute) is in flight — the measured
                # collective wait shrinks by exactly the overlapped
                # host+device work.
                prev.wait()
            prev = h
        if self._buckets is None:
            # First step: the bucket plan is now known — build the
            # per-bucket sub-optimizers, then redo the cheap bookkeeping.
            self._init_bucket_apply(handles)
            if self._sqnorm_fns is not None:
                sub_grads = [self._sub_grads(bi, h)
                             for bi, h in enumerate(handles)]
                sqs = [fn(g) for fn, g in
                       zip(self._sqnorm_fns, sub_grads)]
        if prev is not None:
            prev.wait()
        from ptype_tpu.metrics import annotate

        if self._custom_opt:
            # Arbitrary optimizer: whole-tree apply (streamed waits
            # above still gave the ledger its collective attribution).
            reduced = self._tree_from_handles(handles)
            with annotate("train.opt"):
                new_params, self.opt_state = self._apply_fn(
                    params, reduced, self.opt_state)
            self._param_leaves = list(
                jax.tree_util.tree_leaves(new_params))
        else:
            with annotate("train.opt"):
                scale = self._scale_fn(jnp.stack(sqs))
                for bi in range(len(handles)):
                    subp = {str(i): self._param_leaves[i]
                            for i in self._buckets[bi]}
                    newp, self._bucket_states[bi] = self._apply_fns[bi](
                        subp, sub_grads[bi], self._bucket_states[bi],
                        scale)
                    for i in self._buckets[bi]:
                        self._param_leaves[i] = newp[str(i)]
        new_params = jax.tree_util.tree_unflatten(
            self._treedef, self._param_leaves)
        self._params_seq = self.store.put_tree("params", new_params)

    def _grad_index(self, grad_key: str) -> int:
        return self._key_index[grad_key.replace("grads/", "params/", 1)]

    def _sub_grads(self, bi: int, h) -> dict:
        return {str(self._grad_index(k)): v for k, v in h.items()}

    def _tree_from_handles(self, handles):
        leaves = [None] * len(self._keys)
        for h in handles:
            for k, v in h.items():
                leaves[self._grad_index(k)] = v
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def _init_bucket_apply(self, handles) -> None:
        """Build the per-bucket optimizer machinery from the first
        step's bucket plan: each bucket gets the default AdamW recipe
        over its own param sub-tree (same schedule/decay-mask
        semantics as ``default_optimizer`` — assembled from the same
        pieces), plus a jitted partial-sqnorm fn; one jitted scale fn
        coordinates the global-norm clip across buckets."""
        self._buckets = [[self._grad_index(k) for k in h.keys]
                         for h in handles]
        if self._custom_opt:
            return
        import optax

        clip, make_inner = default_optimizer_pieces()
        mask_leaves = jax.tree_util.tree_leaves(
            _decay_mask(jax.tree_util.tree_unflatten(
                self._treedef, self._param_leaves)))
        self._bucket_states, self._apply_fns, self._sqnorm_fns = [], [], []
        for idxs in self._buckets:
            subp = {str(i): self._param_leaves[i] for i in idxs}
            inner = make_inner({str(i): mask_leaves[i] for i in idxs})
            self._bucket_states.append(inner.init(subp))

            @jax.named_scope("optimizer")
            def store_apply(p, g, s, scale, _inner=inner):
                g = jax.tree_util.tree_map(
                    lambda t: (t.astype(jnp.float32) * scale).astype(
                        t.dtype), g)
                updates, s = _inner.update(g, s, p)
                return optax.apply_updates(p, updates), s

            self._apply_fns.append(jax.jit(store_apply))
            self._sqnorm_fns.append(jax.jit(
                lambda g: sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                              for x in jax.tree_util.tree_leaves(g))))

        clip_f = float(clip)

        def scale_of(sq_stack):
            gnorm = jnp.sqrt(jnp.sum(sq_stack))
            return jnp.where(gnorm < clip_f, 1.0, clip_f / gnorm)

        self._scale_fn = jax.jit(scale_of)

    def _grad_key0(self) -> str:
        if self.zero_stage >= 2:
            # The scatter path commits per BUCKET, not per leaf (the
            # zero=1 allreduce stream commits per leaf like overlap).
            return "grads/bucket00000"
        return self._keys[0].replace("params/", "grads/", 1)


# ----------------------------------------------------------- benching


def measure_overlap(mesh: Mesh, preset: str = "tiny", steps: int = 6,
                    batch: int = 16, bucket_bytes: int = 64 * 1024,
                    compress: str | None = "int8") -> dict:
    """Collective share of store-DP step time, synchronous baseline vs
    fine-grained overlap — the bench.py ``collective_overlap_pct``
    probe and the ISSUE 6 acceptance metric. Runs the same training
    loop twice (``overlap="drain"`` then ``overlap=True``) with a
    private goodput ledger each, and reports how much of the measured
    collective leg the overlap hides."""
    from ptype_tpu.health.goodput import GoodputLedger
    from ptype_tpu.metrics import MetricsRegistry
    from ptype_tpu.parallel.collectives import WireConfig
    from ptype_tpu.train.data import synthetic_batches

    cfg = tfm.preset(preset)
    seq = min(cfg.max_seq, 128)

    def run(overlap):
        wire = WireConfig(compress=compress, bucket_bytes=bucket_bytes,
                          int8_min_bytes=0)
        store = TensorStore(mesh, wire=wire)
        trainer = StoreDPTrainer(cfg, store, overlap=overlap)
        stream = synthetic_batches(cfg.vocab_size, batch, seq)
        trainer.step(next(stream))  # compile + warm outside the ledger
        ledger = GoodputLedger(registry=MetricsRegistry()).install()
        try:
            for _ in range(steps):
                out = trainer.step(next(stream))
        finally:
            ledger.uninstall()
        assert jnp.isfinite(out["loss"])
        return ledger.summary()

    base = run("drain")
    over = run(True)
    share_base = base["collective_share_pct"]
    share_over = over["collective_share_pct"]
    return {
        "collective_share_drain_pct": round(share_base, 2),
        "collective_share_overlap_pct": round(share_over, 2),
        "collective_overlap_pct": round(
            100.0 * (1.0 - share_over / share_base), 2)
        if share_base else 0.0,
        "drain_step_ms": base["step_breakdown"]["step_ms"],
        "overlap_step_ms": over["step_breakdown"]["step_ms"],
        "steps": steps,
        "bucket_bytes": bucket_bytes,
        "compress": compress,
    }


def measure_zero(mesh: Mesh, preset: str = "tiny", steps: int = 6,
                 batch: int = 16, compress: str | None = None) -> dict:
    """Per-replica optimizer-state bytes and step time, ZeRO-1 sharded
    update vs the replicated store-DP baseline — the bench.py
    ``zero_opt_mem_mb`` / ``zero_step_ms`` probe and the ISSUE 7
    acceptance numbers. Runs the same loop twice with the same seed and
    reports measured resident bytes (``addressable_shards``, not a
    formula) plus the loss gap."""
    from ptype_tpu.parallel.collectives import WireConfig
    from ptype_tpu.train.data import synthetic_batches
    import time as _t

    cfg = tfm.preset(preset)
    seq = min(cfg.max_seq, 128)

    def opt_bytes(tree) -> int:
        total = 0
        for x in jax.tree_util.tree_leaves(tree):
            shards = getattr(x, "addressable_shards", None)
            total += (shards[0].data.nbytes if shards
                      else getattr(x, "nbytes", 0))
        return total

    def run(zero: bool):
        wire = WireConfig(compress=compress, int8_min_bytes=0)
        trainer = StoreDPTrainer(cfg, TensorStore(mesh, wire=wire),
                                 rng=jax.random.PRNGKey(0), zero=zero)
        stream = synthetic_batches(cfg.vocab_size, batch, seq, seed=5)
        trainer.step(next(stream))  # compile + warm
        t0 = _t.perf_counter()
        for _ in range(steps):
            out = trainer.step(next(stream))
        dt = (_t.perf_counter() - t0) / steps
        if zero:
            nbytes = trainer.zero_state().moment_bytes_per_replica()
        else:
            nbytes = opt_bytes(trainer.opt_state)
        return dt, nbytes, out["loss"]

    repl_dt, repl_bytes, repl_loss = run(False)
    zero_dt, zero_bytes, zero_loss = run(True)
    return {
        "zero_opt_mem_mb": round(zero_bytes / 2**20, 3),
        "repl_opt_mem_mb": round(repl_bytes / 2**20, 3),
        "opt_mem_ratio": round(repl_bytes / zero_bytes, 2)
        if zero_bytes else None,
        "zero_step_ms": round(zero_dt * 1e3, 2),
        "repl_step_ms": round(repl_dt * 1e3, 2),
        "final_loss_zero": round(float(zero_loss), 5),
        "final_loss_repl": round(float(repl_loss), 5),
        "n_replicas": axis_n(mesh, DATA_AXIS),
        "steps": steps,
        "compress": compress,
    }


def measure_zero_ladder(mesh: Mesh, preset: str = "tiny",
                        steps: int = 4, batch: int = 16) -> dict:
    """The full ladder measured (ISSUE 17): replicated baseline vs
    ZeRO-1/2/3, same seed and stream — per-replica resident bytes for
    optimizer moments, the grad reduction, and params, plus step time
    and final loss (which must match across rungs; the ladder changes
    residency, never math). Feeds ``zero2_grad_mem_mb`` /
    ``zero3_param_mem_mb`` in the bench tail and the ``make
    zero-bench`` ladder table."""
    import time as _t

    from ptype_tpu.train.data import synthetic_batches

    cfg = tfm.preset(preset)
    seq = min(cfg.max_seq, 128)
    n = axis_n(mesh, DATA_AXIS)
    rows = {}
    for stage in (0, 1, 2, 3):
        trainer = StoreDPTrainer(cfg, TensorStore(mesh),
                                 rng=jax.random.PRNGKey(0),
                                 zero=stage if stage else False)
        stream = synthetic_batches(cfg.vocab_size, batch, seq, seed=5)
        trainer.step(next(stream))  # compile + warm
        t0 = _t.perf_counter()
        for _ in range(steps):
            out = trainer.step(next(stream))
        dt = (_t.perf_counter() - t0) / steps
        if stage:
            opt_b = trainer.zero_state().moment_bytes_per_replica()
            param_b = trainer.zero_state().param_bytes_per_replica()
        else:
            opt_b = sum(
                _resident_nbytes(x) for x in
                jax.tree_util.tree_leaves(trainer.opt_state))
            param_b = 0
        if not param_b:  # replicated leaves resident (stages 0-2)
            param_b = sum(x.nbytes for x in
                          jax.tree_util.tree_leaves(trainer.params()))
        rows[f"zero{stage}" if stage else "repl"] = {
            "step_ms": round(dt * 1e3, 2),
            "opt_mem_mb": round(opt_b / 2**20, 3),
            "grad_mem_mb": round((trainer.last_grad_bytes or 0)
                                 / 2**20, 3),
            "param_mem_mb": round(param_b / 2**20, 3),
            "final_loss": round(float(out["loss"]), 5),
        }
    return {
        "ladder": rows,
        "zero2_grad_mem_mb": rows["zero2"]["grad_mem_mb"],
        "zero3_param_mem_mb": rows["zero3"]["param_mem_mb"],
        "repl_grad_mem_mb": rows["zero1"]["grad_mem_mb"],
        "repl_param_mem_mb": rows["repl"]["param_mem_mb"],
        "n_replicas": n,
        "steps": steps,
    }


def measure_reshard(preset: str = "tiny", steps: int = 3,
                    batch: int = 16, zero: int = 2) -> dict:
    """Live reshard vs the checkpoint-restore round trip it replaces
    (ISSUE 17): train on the full 8-device host mesh, shrink to 4
    survivors both ways, and report each recovery in STEP units
    (``reshard_resume_steps`` — wall time to be training again on the
    survivor set, divided by the steady step time). The live path is
    ``StoreDPTrainer.reshard`` (in memory, atomic); the baseline is
    ZeroCheckpoint + StoreCheckpoint save → fresh trainer → restore."""
    import tempfile
    import time as _t

    from ptype_tpu.checkpoint import StoreCheckpoint, ZeroCheckpoint
    from ptype_tpu.parallel.mesh import build_mesh
    from ptype_tpu.train.data import synthetic_batches

    cfg = tfm.preset(preset)
    seq = min(cfg.max_seq, 128)
    mesh8 = build_mesh({DATA_AXIS: 8})
    mesh4 = build_mesh({DATA_AXIS: 4}, devices=jax.devices()[:4])

    def trained():
        tr = StoreDPTrainer(cfg, TensorStore(mesh8),
                            rng=jax.random.PRNGKey(0), zero=zero)
        stream = synthetic_batches(cfg.vocab_size, batch, seq, seed=5)
        tr.step(next(stream))
        t0 = _t.perf_counter()
        for _ in range(steps):
            tr.step(next(stream))
        return tr, (_t.perf_counter() - t0) / steps, stream

    # Live path: reshard + the first survivor step (pays the retrace).
    tr, step_s, stream = trained()
    t0 = _t.perf_counter()
    info = tr.reshard(mesh4)
    tr.step(next(stream))
    live_s = _t.perf_counter() - t0

    # Checkpoint path on an identical twin: save, fresh trainer on
    # the survivor mesh, restore, first step.
    twin, _, stream2 = trained()
    with tempfile.TemporaryDirectory() as td:
        t0 = _t.perf_counter()
        ZeroCheckpoint(td + "/zero").save(steps, twin.zero_state())
        StoreCheckpoint(twin.store, td + "/store",
                        keys_prefix="params/").save(steps)
        fresh = StoreDPTrainer(cfg, TensorStore(mesh4),
                               rng=jax.random.PRNGKey(0), zero=zero)
        StoreCheckpoint(fresh.store, td + "/store",
                        keys_prefix="params/").resume()
        ZeroCheckpoint(td + "/zero").restore_into(fresh.zero_state())
        if zero == 3:
            for bi, flat in enumerate(fresh.zero_state().pflat):
                fresh.store.commit_sharded(
                    f"params/bucket{bi:05d}", flat)
        fresh.step(next(stream2))
        ckpt_s = _t.perf_counter() - t0

    return {
        "zero_stage": zero,
        "step_ms": round(step_s * 1e3, 2),
        "reshard_ms": info["reshard_ms"],
        "live_resume_ms": round(live_s * 1e3, 2),
        "ckpt_resume_ms": round(ckpt_s * 1e3, 2),
        "reshard_resume_steps": round(live_s / step_s, 2),
        "ckpt_resume_steps": round(ckpt_s / step_s, 2),
        "resume_speedup": round(ckpt_s / live_s, 2),
    }
