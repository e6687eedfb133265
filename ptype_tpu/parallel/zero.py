"""ZeRO-style sharded optimizer update — reduce-scatter → shard-local
apply → allgather over the TensorStore bucket space.

Store-DP replicated the full optimizer state on every replica, which
caps trainable model size well below what the mesh's memory allows.
Following "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (PAPERS.md, arXiv 2004.13336), this module
implements the full sharding LADDER over the flat bucket space:

- **ZeRO-1** (``_shard_apply_full_fn``): optimizer state sharded,
  grads arrive as full allreduced leaves, each replica slices its
  shard of params AND grads inside the fused apply;
- **ZeRO-2** (``_shard_apply_fn``): grads ride the bucketed
  reduce-scatter and arrive shard-resident — the original path below;
- **ZeRO-3** (``_shard_apply3_fn`` + ``_bucket_gather_fn``): params
  are resident as flat ``P(axis)`` shards too (``ZeroState.pflat``),
  allgathered just-in-time per bucket for the forward; the update is
  purely elementwise with donated buffers.

Live elasticity rides the same math: :meth:`ZeroState.reshard` applies
the ``ZeroCheckpoint.restore_into`` re-pad in memory (strip old tail
pad → re-pad for the survivor count → re-place), atomically, with the
``train.reshard`` chaos seam exercising mid-move faults.

The original ZeRO-2 data path:

- gradients ride a bucketed **reduce-scatter**
  (``collectives.bucketed_reduce_scatter_stream`` /
  ``TensorStore.push_tree_scatter_iter``) — half the allreduce's wire
  bytes, the same block-scaled int8 + error-feedback wire as the
  allreduce paths — leaving each replica ONE contiguous flat shard per
  bucket;
- the optimizer applies **shard-locally**: each replica materializes
  only ``1/N`` of the Adam moments (flat f32 vectors sharded over the
  data axis) and computes only its shard's update — ~N× less optimizer
  memory AND ~N× fewer update FLOPs per replica;
- the updated parameter shards **allgather** back to the replicated
  params, fused into the same per-bucket program as the update (one
  launch per bucket: slice-my-shard → AdamW → all_gather → unpack).

The flat bucket space is the unit of sharding: :class:`ShardPlan`
partitions it (``plan_buckets`` over the sorted leaf keys — the same
planner and therefore the same buckets as the gradient stream), and the
plan's JSON manifest makes sharded checkpoints **reshardable**: bucket
boundaries depend only on leaf order/dtype/``bucket_bytes``, never on
the replica count — only the tail pad does — so a state saved from 8
replicas re-pads onto 4 (checkpoint.ZeroCheckpoint).

The shard-local AdamW mirrors the default recipe
(``trainer.default_optimizer``: clip-by-global-norm → AdamW with
warmup-cosine schedule and a decay mask) element-for-element, with the
hyperparameters read from the one shared
:class:`~ptype_tpu.train.trainer.OptHParams` record. The global-norm
clip — the recipe's one cross-shard coupling — is coordinated through
per-bucket partial square-norms as a device value, exactly like the
overlap trainer's per-bucket apply.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ptype_tpu import chaos
from ptype_tpu.errors import CheckpointError, ClusterError
from ptype_tpu.parallel.mesh import axis_n
from ptype_tpu.parallel.collectives import (Bucket, DEFAULT_BUCKET_BYTES,
                                            _slot_offsets, _unpack,
                                            plan_buckets)

#: zero_plan.json schema version.
PLAN_VERSION = 1


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Partition of the flat bucket space across ``n`` replicas.

    ``buckets`` come from the SAME planner as the gradient
    reduce-scatter stream (``collectives.plan_buckets`` over leaves in
    store-sorted key order), so slot ``index`` here is a position in
    that sorted order and each bucket's flat ``(elems,)`` payload
    divides into ``n`` contiguous ``elems/n`` shards — replica ``r``
    owns shard ``r`` of every bucket.
    """

    n: int
    bucket_bytes: int
    buckets: tuple  # tuple[Bucket, ...]

    @staticmethod
    def for_leaves(leaves, n: int,
                   bucket_bytes: int = DEFAULT_BUCKET_BYTES
                   ) -> "ShardPlan":
        """Plan over UNSTACKED leaves (params as the trainer holds
        them): each leaf is given the synthetic ``(n, *shape)`` stacked
        form the planner expects, which adds nothing but the leading
        contribution axis — the resulting slots are identical to the
        gradient stream's."""
        fake = [jax.ShapeDtypeStruct((n,) + tuple(np.shape(x)),
                                     jnp.dtype(x.dtype))
                for x in leaves]
        return ShardPlan(n, int(bucket_bytes),
                         tuple(plan_buckets(fake, n, bucket_bytes)))

    def with_n(self, n: int) -> "ShardPlan":
        """The SAME flat space re-padded for ``n`` replicas — the live
        reshard's plan math. Slots (and therefore payloads) are
        untouched; only the tail pads change, exactly as
        ``check_plan_compatible`` permits."""
        n = int(n)
        if n <= 0:
            raise ValueError(f"with_n: need n >= 1, got {n}")
        buckets = tuple(
            dataclasses.replace(b, pad=(-(b.elems - b.pad)) % n)
            for b in self.buckets)
        return ShardPlan(n, self.bucket_bytes, buckets)

    @property
    def n_slots(self) -> int:
        return sum(len(b.slots) for b in self.buckets)

    def shard_elems(self, bucket: Bucket) -> int:
        return bucket.elems // self.n

    def moment_bytes_per_replica(self, itemsize: int = 4) -> int:
        """Adam mu+nu bytes each replica materializes under this plan."""
        return sum(2 * self.shard_elems(b) * itemsize
                   for b in self.buckets)

    def manifest(self) -> dict:
        """JSON-able description — rides the checkpoint commit so a
        restore can validate compatibility and re-pad for a different
        replica count."""
        return {
            "version": PLAN_VERSION,
            "n": self.n,
            "bucket_bytes": self.bucket_bytes,
            "buckets": [
                {"dtype": b.dtype, "pad": b.pad,
                 "slots": [{"index": s.index, "offset": s.offset,
                            "size": s.size, "shape": list(s.shape)}
                           for s in b.slots]}
                for b in self.buckets],
        }


def check_plan_compatible(saved: dict, current: dict) -> None:
    """A saved plan manifest is restorable into the current one iff the
    bucket SLOTS match exactly (same leaves, same offsets, same
    dtypes): slots are replica-count-independent, so only ``n`` and the
    tail pads may differ — that is the reshard case. Anything else
    (different model, different ``bucket_bytes``) is a different flat
    space and must fail loudly, never zero-fill."""
    if saved.get("version") != PLAN_VERSION:
        raise CheckpointError(
            f"zero restore: plan version {saved.get('version')!r} != "
            f"{PLAN_VERSION}")

    def slots_of(m):
        return [(b["dtype"], b["slots"]) for b in m["buckets"]]

    if slots_of(saved) != slots_of(current):
        raise CheckpointError(
            "zero restore: saved shard plan does not match this "
            "trainer's (different parameter space or bucket_bytes) — "
            f"saved {len(saved['buckets'])} buckets / "
            f"{sum(len(b['slots']) for b in saved['buckets'])} slots, "
            f"current {len(current['buckets'])} buckets / "
            f"{sum(len(b['slots']) for b in current['buckets'])} slots")


# ------------------------------------------------- fused shard programs


def _pack_replicated(leaves, pad: int):
    """Flatten + concatenate UNSTACKED leaves and zero-pad — the
    replicated-params analog of ``collectives._pack_flat``."""
    parts = [x.reshape(-1) for x in leaves]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


@functools.lru_cache(maxsize=512)
def _shard_apply_fn(mesh: Mesh, axis: str, shapes: tuple, dtype: str,
                    pad: int, hp):
    """ONE fused program per bucket: pack params → slice my shard →
    AdamW on the shard only → all_gather updated shards → unpack.

    Args (in order): ``*param_leaves`` (replicated), ``grad_flat``
    (``(elems,)`` sharded ``P(axis)`` — the reduce-scatter output),
    ``mu``/``nu``/``mask`` (flat, sharded ``P(axis)`` — the 1/N
    resident state), ``count`` (int32 scalar), ``scale`` (the
    coordinated global-norm clip scale). Returns
    ``(*new_param_leaves replicated, new_mu, new_nu)``.

    The math mirrors ``optax.chain(clip_by_global_norm, adamw(sched))``
    element-for-element (clip applied as the precomputed ``scale``;
    decay as an elementwise masked add — identical values to optax's
    per-leaf mask for leaf-constant masks), with every hyperparameter
    read from the shared :class:`OptHParams`.
    """
    sched = hp.schedule()
    n = axis_n(mesh, axis)
    in_specs = tuple(P(*(None,) * len(s)) for s in shapes) + (
        P(axis), P(axis), P(axis), P(axis), P(), P())
    out_specs = tuple(P(*(None,) * len(s)) for s in shapes) + (
        P(axis), P(axis))
    offs = _slot_offsets(shapes)

    def f(*args):
        leaves = args[:len(shapes)]
        g, mu, nu, mask, count, scale = args[len(shapes):]
        flat = _pack_replicated(leaves, pad)
        shard = flat.shape[0] // n
        idx = lax.axis_index(axis)
        p_sh = lax.dynamic_slice(flat, (idx * shard,), (shard,))
        p32 = p_sh.astype(jnp.float32)
        g32 = g.astype(jnp.float32) * scale
        mu2 = (1.0 - hp.b1) * g32 + hp.b1 * mu.astype(jnp.float32)
        nu2 = (1.0 - hp.b2) * (g32 * g32) \
            + hp.b2 * nu.astype(jnp.float32)
        cnt1 = (count + 1).astype(jnp.float32)
        mu_hat = mu2 / (1.0 - hp.b1 ** cnt1)
        nu_hat = nu2 / (1.0 - hp.b2 ** cnt1)
        upd = mu_hat / (jnp.sqrt(nu_hat) + hp.eps)
        upd = upd + hp.weight_decay * mask * p32
        new_sh = (p32 - sched(count) * upd).astype(flat.dtype)
        gathered = lax.all_gather(new_sh, axis).reshape(-1)
        out = _unpack(gathered, offs)
        return out + (mu2.astype(mu.dtype), nu2.astype(nu.dtype))

    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


@functools.lru_cache(maxsize=512)
def _shard_apply_full_fn(mesh: Mesh, axis: str, shapes: tuple,
                         dtype: str, pad: int, hp):
    """ZeRO-1 rung: the grads arrive as FULL reduced leaves (bucketed
    allreduce — ``push_tree_iter``), so the fused program packs BOTH
    params and grads, slices its shard of each, and runs the identical
    shard-local AdamW + all_gather as :func:`_shard_apply_fn`. Same
    optimizer memory as ZeRO-2, but the grads stay replicated — the
    ladder's measurable middle step.

    Args: ``*param_leaves``, ``*grad_leaves`` (both replicated, slot
    order), ``mu``/``nu``/``mask`` (flat ``P(axis)``), ``count``,
    ``scale``. Returns ``(*new_param_leaves, new_mu, new_nu)``.
    """
    sched = hp.schedule()
    n = axis_n(mesh, axis)
    rep = tuple(P(*(None,) * len(s)) for s in shapes)
    in_specs = rep + rep + (P(axis), P(axis), P(axis), P(), P())
    out_specs = rep + (P(axis), P(axis))
    offs = _slot_offsets(shapes)
    L = len(shapes)

    def f(*args):
        leaves = args[:L]
        grads = args[L:2 * L]
        mu, nu, mask, count, scale = args[2 * L:]
        flat = _pack_replicated(leaves, pad)
        gflat = _pack_replicated(grads, pad)
        shard = flat.shape[0] // n
        idx = lax.axis_index(axis)
        p_sh = lax.dynamic_slice(flat, (idx * shard,), (shard,))
        g_sh = lax.dynamic_slice(gflat, (idx * shard,), (shard,))
        p32 = p_sh.astype(jnp.float32)
        g32 = g_sh.astype(jnp.float32) * scale
        mu2 = (1.0 - hp.b1) * g32 + hp.b1 * mu.astype(jnp.float32)
        nu2 = (1.0 - hp.b2) * (g32 * g32) \
            + hp.b2 * nu.astype(jnp.float32)
        cnt1 = (count + 1).astype(jnp.float32)
        mu_hat = mu2 / (1.0 - hp.b1 ** cnt1)
        nu_hat = nu2 / (1.0 - hp.b2 ** cnt1)
        upd = mu_hat / (jnp.sqrt(nu_hat) + hp.eps)
        upd = upd + hp.weight_decay * mask * p32
        new_sh = (p32 - sched(count) * upd).astype(flat.dtype)
        gathered = lax.all_gather(new_sh, axis).reshape(-1)
        out = _unpack(gathered, offs)
        return out + (mu2.astype(mu.dtype), nu2.astype(nu.dtype))

    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


@functools.lru_cache(maxsize=32)
def _shard_apply3_fn(hp):
    """ZeRO-3 rung: params are RESIDENT as flat ``P(axis)`` shards, the
    reduce-scatter hands each replica exactly its grad shard, so the
    update is purely elementwise — NO collective at all (the forward's
    just-in-time :func:`_bucket_gather_fn` is where the one all_gather
    per bucket lives; progaudit pins this program at zero collectives).

    Donation consumes the old param shard and both moments: the update
    is in-place in the XLA sense, so ZeRO-3's resident footprint never
    doubles mid-step.

    Args: ``p_flat`` (bucket dtype, ``P(axis)``), ``grad_flat``,
    ``mu``/``nu``/``mask`` (f32 flats, ``P(axis)``), ``count``,
    ``scale``. Returns ``(new_p_flat, new_mu, new_nu)``.
    """
    sched = hp.schedule()

    def f(p_flat, g, mu, nu, mask, count, scale):
        p32 = p_flat.astype(jnp.float32)
        g32 = g.astype(jnp.float32) * scale
        mu2 = (1.0 - hp.b1) * g32 + hp.b1 * mu
        nu2 = (1.0 - hp.b2) * (g32 * g32) + hp.b2 * nu
        cnt1 = (count + 1).astype(jnp.float32)
        mu_hat = mu2 / (1.0 - hp.b1 ** cnt1)
        nu_hat = nu2 / (1.0 - hp.b2 ** cnt1)
        upd = mu_hat / (jnp.sqrt(nu_hat) + hp.eps)
        upd = upd + hp.weight_decay * mask * p32
        new_p = (p32 - sched(count) * upd).astype(p_flat.dtype)
        return new_p, mu2, nu2

    return jax.jit(f, donate_argnums=(0, 2, 3))


@functools.lru_cache(maxsize=512)
def _bucket_gather_fn(mesh: Mesh, axis: str, shapes: tuple, dtype: str,
                      pad: int):
    """ZeRO-3's just-in-time param materialization: ONE fused program
    per bucket — all_gather the resident flat shard, unpack to the
    bucket's leaves (replicated). This is the single home for full-tree
    param allgather (lint PT022 bars it from ``train/``); progaudit
    pins it at exactly one ``all_gather`` launch per bucket."""
    offs = _slot_offsets(shapes)
    out_specs = tuple(P(*(None,) * len(s)) for s in shapes)

    def f(flat):
        gathered = lax.all_gather(flat, axis).reshape(-1)
        return _unpack(gathered, offs)

    return jax.jit(shard_map(f, mesh=mesh, in_specs=(P(axis),),
                             out_specs=out_specs, check_vma=False))


#: Partial square-norm of one flat (possibly sharded) buffer — jit
#: handles the sharded input, the cross-shard psum is implied.
_sqnorm = jax.jit(
    lambda flat: jnp.sum(jnp.square(flat.astype(jnp.float32))))


@functools.lru_cache(maxsize=32)
def _scale_fn(clip: float):
    """Global-norm clip scale from stacked per-bucket partial sqnorms —
    the same device-value coordination as the overlap trainer's."""

    def scale_of(sq_stack):
        gnorm = jnp.sqrt(jnp.sum(sq_stack))
        return jnp.where(gnorm < clip, 1.0, clip / gnorm)

    return jax.jit(scale_of)


@functools.lru_cache(maxsize=512)
def _zeros_sharded_fn(mesh: Mesh, axis: str, elems: int, dtype: str):
    """Materialize a flat zeros vector DIRECTLY sharded over ``axis`` —
    shard-local init: no replica ever holds the full moment vector."""
    return jax.jit(
        lambda: jnp.zeros((elems,), jnp.dtype(dtype)),
        out_shardings=NamedSharding(mesh, P(axis)))


class ZeroState:
    """The sharded optimizer state: per-bucket flat Adam moments
    (``mu``/``nu``, f32, sharded ``P(axis)`` — 1/N resident per
    replica), the packed decay-mask vectors, and the shared step
    ``count`` that positions the schedule.
    """

    def __init__(self, plan: ShardPlan, mesh: Mesh, axis: str,
                 hparams, mask_flats: list, mu: list, nu: list,
                 count: int = 0, pflat: list = None):
        self.plan = plan
        self.mesh = mesh
        self.axis = axis
        self.hparams = hparams
        self._masks = mask_flats
        self.mu = mu
        self.nu = nu
        self.count = int(count)
        #: ZeRO-3 only: per-bucket resident param flats (bucket dtype,
        #: sharded ``P(axis)``) — installed by :meth:`scatter_params`,
        #: ``None`` under ZeRO-1/2 where params stay replicated.
        self.pflat = pflat

    @staticmethod
    def create(plan: ShardPlan, mesh: Mesh, axis: str, hparams,
               mask_leaves: list) -> "ZeroState":
        """Init moments sharded from step 0 and pack the per-leaf decay
        mask (True = weight decay applies) into per-bucket flat f32
        vectors. ``mask_leaves`` aligns with the plan's slot order."""
        sh = NamedSharding(mesh, P(axis))
        masks, mu, nu = [], [], []
        for b in plan.buckets:
            vec = np.zeros((b.elems,), np.float32)
            for s in b.slots:
                if bool(mask_leaves[s.index]):
                    vec[s.offset:s.offset + s.size] = 1.0
            masks.append(jax.device_put(vec, sh))
            # Moments are f32 whatever the param dtype — the module's
            # documented contract (and what moment_bytes_per_replica's
            # itemsize=4 accounts): bf16 moments would drop the
            # (1-b2)-scaled nu increments below the mantissa.
            for acc in (mu, nu):
                acc.append(_zeros_sharded_fn(
                    mesh, axis, b.elems, "float32")())
        return ZeroState(plan, mesh, axis, hparams, masks, mu, nu)

    # ----------------------------------------------- ZeRO-3 residency

    def scatter_params(self, param_leaves: list) -> None:
        """Install the params as the RESIDENT sharded layout (ZeRO-3):
        pack each bucket's leaves (``param_leaves`` in plan slot order)
        into the flat space, zero the tail pad, place ``P(axis)``.
        After this the trainer holds no replicated param tree — every
        full materialization goes through :meth:`gather_bucket`."""
        sh = NamedSharding(self.mesh, P(self.axis))
        pflat = []
        for b in self.plan.buckets:
            vec = np.zeros((b.elems,), jnp.dtype(b.dtype))
            for s in b.slots:
                vec[s.offset:s.offset + s.size] = np.asarray(
                    param_leaves[s.index]).reshape(-1)
            pflat.append(jax.device_put(vec, sh))
        self.pflat = pflat

    def gather_bucket(self, bi: int) -> list:
        """Just-in-time full params for bucket ``bi``: one fused
        all_gather + unpack launch; returns replicated leaves in slot
        order. The gathered buffers are TRANSIENT — callers feed them
        to a donating consumer (the grads program) so they die after
        the forward."""
        b = self.plan.buckets[bi]
        fn = _bucket_gather_fn(
            self.mesh, self.axis, tuple(s.shape for s in b.slots),
            b.dtype, b.pad)
        return list(fn(self.pflat[bi]))

    def gather_params(self) -> list:
        """Full param leaves (plan slot order) — the ONE sanctioned
        full-tree materialization under ZeRO-3 (checkpoint export,
        eval, ``params()``)."""
        if self.pflat is None:
            raise ValueError("gather_params: no resident param shards "
                             "(ZeRO-3 only; call scatter_params first)")
        out = [None] * self.plan.n_slots
        for bi, b in enumerate(self.plan.buckets):
            for s, leaf in zip(b.slots, self.gather_bucket(bi)):
                out[s.index] = leaf
        return out

    # --------------------------------------------------------- step ops

    def partial_sqnorm(self, grad_flat):
        return _sqnorm(grad_flat)

    def clip_scale(self, sqnorms: list):
        return _scale_fn(float(self.hparams.clip))(jnp.stack(sqnorms))

    def apply_bucket(self, bi: int, param_leaves: list, grad_flat,
                     scale) -> list:
        """Shard-local AdamW + allgather for bucket ``bi``; updates
        ``mu``/``nu`` in place and returns the new param leaves (slot
        order, replicated). Call :meth:`finish_step` once per step."""
        b = self.plan.buckets[bi]
        fn = _shard_apply_fn(
            self.mesh, self.axis, tuple(s.shape for s in b.slots),
            b.dtype, b.pad, self.hparams)
        outs = fn(*param_leaves, grad_flat, self.mu[bi], self.nu[bi],
                  self._masks[bi], jnp.int32(self.count), scale)
        L = len(b.slots)
        self.mu[bi], self.nu[bi] = outs[L], outs[L + 1]
        return list(outs[:L])

    def apply_bucket_full(self, bi: int, param_leaves: list,
                          grad_leaves: list, scale) -> list:
        """ZeRO-1 apply for bucket ``bi``: full (allreduced) grad
        leaves in, slice-my-shard-of-both inside the fused program;
        otherwise identical contract to :meth:`apply_bucket`."""
        b = self.plan.buckets[bi]
        fn = _shard_apply_full_fn(
            self.mesh, self.axis, tuple(s.shape for s in b.slots),
            b.dtype, b.pad, self.hparams)
        outs = fn(*param_leaves, *grad_leaves, self.mu[bi],
                  self.nu[bi], self._masks[bi], jnp.int32(self.count),
                  scale)
        L = len(b.slots)
        self.mu[bi], self.nu[bi] = outs[L], outs[L + 1]
        return list(outs[:L])

    def apply_bucket3(self, bi: int, grad_flat, scale):
        """ZeRO-3 apply for bucket ``bi``: purely elementwise on the
        resident flats — updates ``pflat``/``mu``/``nu`` in place
        (donated) and returns the new param flat (``P(axis)``) so the
        trainer can commit it to the store. No collective launches."""
        if self.pflat is None:
            raise ValueError("apply_bucket3: no resident param shards "
                             "(ZeRO-3 only; call scatter_params first)")
        fn = _shard_apply3_fn(self.hparams)
        new_p, mu2, nu2 = fn(self.pflat[bi], grad_flat, self.mu[bi],
                             self.nu[bi], self._masks[bi],
                             jnp.int32(self.count), scale)
        self.pflat[bi], self.mu[bi], self.nu[bi] = new_p, mu2, nu2
        return new_p

    def finish_step(self) -> None:
        self.count += 1

    def compiled_cost(self) -> dict:
        """XLA ``cost_analysis`` totals for the per-bucket fused
        shard-apply programs (ISSUE 8 compiled-cost accounting).
        ``cost_analysis`` reports the per-partition SPMD module, so
        the totals are multiplied by the mesh size — the CLUSTER's
        update FLOPs, comparable with the gradient program's
        full-batch count."""
        from ptype_tpu.health.profiling import compiled_cost

        n = axis_n(self.mesh, self.axis)
        flops = nbytes = 0.0
        for b in self.plan.buckets:
            shapes = tuple(s.shape for s in b.slots)
            fn = _shard_apply_fn(self.mesh, self.axis, shapes,
                                 b.dtype, b.pad, self.hparams)
            dt = jnp.dtype(b.dtype)
            leaves = [jax.ShapeDtypeStruct(s, dt) for s in shapes]
            vec = jax.ShapeDtypeStruct((b.elems,), jnp.float32)
            c = compiled_cost(
                fn, *leaves, jax.ShapeDtypeStruct((b.elems,), dt),
                vec, vec, vec, jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.float32))
            flops += c["flops"] * n
            nbytes += c["bytes_accessed"] * n
        return {"flops": flops, "bytes_accessed": nbytes,
                "n_buckets": len(self.plan.buckets)}

    # ------------------------------------------------------- accounting

    def moment_bytes_per_replica(self) -> int:
        """Measured, not planned: the actual per-replica bytes of the
        resident moment shards."""
        total = 0
        for arr in list(self.mu) + list(self.nu):
            shards = getattr(arr, "addressable_shards", None)
            total += (shards[0].data.nbytes if shards
                      else arr.nbytes)
        return total

    def param_bytes_per_replica(self) -> int:
        """Measured per-replica bytes of the resident ZeRO-3 param
        shards (0 when params are replicated — ZeRO-1/2)."""
        if self.pflat is None:
            return 0
        total = 0
        for arr in self.pflat:
            shards = getattr(arr, "addressable_shards", None)
            total += (shards[0].data.nbytes if shards
                      else arr.nbytes)
        return total

    # ------------------------------------------------- live resharding

    def reshard(self, mesh: Mesh, axis: str = None) -> None:
        """Re-place the WHOLE resident state (moments, masks, and the
        ZeRO-3 param flats if present) onto ``mesh`` — the
        ``ZeroCheckpoint.restore_into`` reshard math applied in memory:
        gather each flat to host, strip the old tail pad, zero-pad for
        the survivor count, place ``P(axis)`` on the new mesh. Values
        in ``[:total]`` are byte-copied, so moments are bit-preserved.

        ATOMIC: everything is staged into locals and swapped in only
        after the last bucket lands. A fault mid-loop (the
        ``train.reshard`` chaos seam, a placement error) leaves the old
        plan/mesh/arrays fully intact, so the caller can retry against
        the same state.
        """
        axis = axis or self.axis
        new_n = axis_n(mesh, axis)
        new_plan = self.plan.with_n(new_n)
        sh = NamedSharding(mesh, P(axis))
        groups = [("mu", self.mu), ("nu", self.nu),
                  ("mask", self._masks)]
        if self.pflat is not None:
            groups.append(("p", self.pflat))
        staged = {name: [] for name, _ in groups}
        for i, (old_b, new_b) in enumerate(zip(self.plan.buckets,
                                               new_plan.buckets)):
            f = chaos.hit("train.reshard", f"bucket{i:05d}")
            if f is not None:
                if f.action == "drop":
                    raise ClusterError(
                        f"chaos: reshard dropped at bucket {i} "
                        f"(plan unchanged; retry)")
                f.sleep()  # delay / wedge: stall this bucket's move
            total = old_b.elems - old_b.pad
            for name, acc in groups:
                full = np.asarray(acc[i])
                out = np.zeros((new_b.elems,), full.dtype)
                out[:total] = full[:total]
                staged[name].append(jax.device_put(out, sh))
            # Per-bucket recovery beacon, mirroring the per-bucket
            # hit: a delayed/wedged bucket pairs on its own landing.
            chaos.note_ok("train.reshard", f"bucket{i:05d}")
        # -- atomic swap: nothing above mutated self.
        self.plan = new_plan
        self.mesh = mesh
        self.axis = axis
        self.mu = staged["mu"]
        self.nu = staged["nu"]
        self._masks = staged["mask"]
        if self.pflat is not None:
            self.pflat = staged["p"]
        chaos.note_ok("train.reshard", f"n={new_n}")

    # ------------------------------------------------------- checkpoint

    def state_tree(self) -> dict:
        """The checkpointable pytree: per-bucket sharded moments (the
        Checkpointer writes one crc32'd shard file per replica shard)
        plus the schedule count. Masks are derived state — rebuilt from
        the params at init, never persisted."""
        tree = {
            "buckets": {f"{i:05d}": {"mu": self.mu[i], "nu": self.nu[i]}
                        for i in range(len(self.plan.buckets))},
            "count": jnp.int32(self.count),
        }
        if self.pflat is not None:
            tree["pbuckets"] = {f"{i:05d}": {"p": self.pflat[i]}
                                for i in range(len(self.plan.buckets))}
        return tree

    def load_state_tree(self, tree: dict, saved_plan: dict) -> None:
        """Install restored moments, RE-SHARDING when the saved replica
        count differs: slots are n-independent, so resharding is
        strip-the-old-tail-pad → re-pad for this plan → place
        ``P(axis)`` on this mesh. ``tree`` holds full host arrays (the
        Checkpointer merged the per-replica shards already)."""
        check_plan_compatible(saved_plan, self.plan.manifest())
        saved_buckets = saved_plan["buckets"]
        sh = NamedSharding(self.mesh, P(self.axis))
        for i, b in enumerate(self.plan.buckets):
            total = b.elems - b.pad
            old_pad = int(saved_buckets[i]["pad"])
            for name, acc in (("mu", self.mu), ("nu", self.nu)):
                full = np.asarray(tree["buckets"][f"{i:05d}"][name])
                if full.shape != (total + old_pad,):
                    raise CheckpointError(
                        f"zero restore: bucket {i} {name} has "
                        f"{full.shape} elements, manifest says "
                        f"{total + old_pad}")
                out = np.zeros((b.elems,), np.float32)
                out[:total] = full[:total]
                acc[i] = jax.device_put(out, sh)
            if self.pflat is not None and "pbuckets" in tree:
                full = np.asarray(tree["pbuckets"][f"{i:05d}"]["p"])
                if full.shape != (total + old_pad,):
                    raise CheckpointError(
                        f"zero restore: bucket {i} params have "
                        f"{full.shape} elements, manifest says "
                        f"{total + old_pad}")
                out = np.zeros((b.elems,), jnp.dtype(b.dtype))
                out[:total] = full[:total]
                self.pflat[i] = jax.device_put(out, sh)
        # reshape(-1)[0]: the Checkpointer round-trips 0-d scalars as
        # shape (1,) — accept either form.
        self.count = int(np.asarray(tree["count"]).reshape(-1)[0])
