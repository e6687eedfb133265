"""TensorStore — the Store's tensor tier: push/pull as ICI collectives.

The reference Store was a namespaced KV over raft (cluster/store.go:38-74):
``Put`` replicated a value to every member, ``Get`` read it linearizably.
The north star (BASELINE.json) lowers exactly that contract onto the mesh:

- ``push(key, contributions)``  → allreduce (``psum``/``pmean``) — every
  device ends up with the same reduced tensor, like a raft-replicated Put.
- ``push_scatter(key, ...)``    → reduce-scatter — each device keeps one
  shard (half the ICI bytes; the FSDP/ZeRO-style reduction).
- ``pull(key)``                 → the stored array, or an allgathered
  replicated view (``gather=True``), like a linearizable Get.

Values live device-resident under a per-key **binding** (a PartitionSpec),
so a pull never round-trips through the host. Ordering, which the
reference got free from raft linearizability, is provided by an explicit
**epoch**: every push bumps the key's epoch, and the optional metadata
KVStore carries ``{shape, dtype, spec, epoch}`` manifests so any member
(or a checkpointer) can discover the parameter space — the control-plane/
data-plane split mandated by SURVEY.md §7 stage 6.

Compression hooks (EQuARX pattern, PAPERS.md):

- ``compress="bf16"`` casts contributions to bfloat16 for the wire and
  restores dtype after the reduce — halves ICI bytes at <1 ulp-bf16 cost.
- ``compress="int8"`` runs push through the two-phase int8-quantized
  allreduce (``collectives.quantized_all_reduce``: all_to_all
  reduce-scatter leg + all_gather leg, both carrying int8 payloads with
  f32 absmax scales) — ≈4× fewer ICI bytes; lossy, meant for gradients.
  Leaves too small to chunk over the axis (scalars, short vectors) ride
  the exact allreduce instead.
"""

from __future__ import annotations

import json
import threading
import time as _time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ptype_tpu import chaos, logs
from ptype_tpu.errors import ClusterError, CoordinationError, NoKeyError
from ptype_tpu.parallel import collectives
from ptype_tpu.parallel.mesh import axis_n
from ptype_tpu.parallel.topology import Topology
from ptype_tpu.store import KVStore

log = logs.get_logger("tensorstore")

TENSOR_PREFIX = "tensors"


def _store_fault(site: str, key: str) -> None:
    """Apply an armed store fault: ``delay`` (a straggler bucket —
    the collective completes late) sleeps; ``timeout`` raises before
    any state changes, so the caller's retry re-runs a clean push."""
    f = chaos.hit(site, key)
    if f is None:
        return
    if f.action == "delay":
        f.sleep()
    elif f.action == "timeout":
        raise ClusterError(f"chaos: {site} timed out for {key!r}")


def spec_to_json(spec: P) -> str:
    return json.dumps([list(p) if isinstance(p, tuple) else p for p in spec])


def spec_from_json(raw: str) -> P:
    return P(*[tuple(p) if isinstance(p, list) else p for p in json.loads(raw)])


@dataclass
class Binding:
    """Per-key placement + reduction policy."""

    spec: P = P()
    reduce_op: str = "mean"


@dataclass
class _Entry:
    value: jax.Array
    epoch: int = 0
    binding: Binding = field(default_factory=Binding)
    #: Store-wide monotonic write stamp — lets a caller that itself
    #: wrote the key detect EXTERNAL mutations without re-pulling
    #: (epoch can't: put() resets it, so two writers look identical).
    seq: int = 0


@dataclass
class BucketPush:
    """One dispatched bucket of a streamed :meth:`TensorStore.
    push_tree_stream`: the committed per-key views (async jax arrays),
    plus a :meth:`wait` that blocks on them inside a
    ``store.push_wait`` region — so the time a consumer actually
    spends waiting on this bucket's collective lands in the goodput
    ledger's collective leg, not in untracked compute."""

    prefix: str
    keys: list
    values: list

    def items(self):
        return zip(self.keys, self.values)

    def wait(self) -> "BucketPush":
        from ptype_tpu.metrics import annotate

        with annotate(f"store.push_wait/{self.prefix}"):
            for v in self.values:
                v.block_until_ready()
        return self


@dataclass
class ShardPush:
    """One dispatched bucket of :meth:`TensorStore.push_tree_scatter_
    iter`: the committed flat reduction, sharded ``P(axis)`` — each
    replica holds its contiguous ``elems/n`` shard (the ZeRO resident
    form). ``keys`` are the leaf keys packed into the bucket, in slot
    order; :meth:`wait` blocks inside a ``store.push_wait`` region so
    consumer wait time lands in the goodput ledger's collective leg."""

    prefix: str
    index: int
    key: str
    bucket: object          # collectives.Bucket
    keys: list
    flat: jax.Array

    def wait(self) -> "ShardPush":
        from ptype_tpu.metrics import annotate

        with annotate(f"store.push_wait/{self.prefix}"):
            self.flat.block_until_ready()
        return self


class TensorStore:
    """Device-resident tensor KV over a mesh (the Store push/pull lowering)."""

    def __init__(self, mesh: Mesh, axis: str = "data",
                 kv: KVStore | None = None, namespace: str = "params",
                 compress: str | None = None,
                 wire: collectives.WireConfig | None = None,
                 topology: Topology | None = None):
        if (wire is not None and compress is not None
                and compress != wire.compress):
            raise ValueError(
                f"TensorStore: conflicting compress={compress!r} and "
                f"wire.compress={wire.compress!r} — pass one")
        self.wire = (wire if wire is not None
                     else collectives.WireConfig(compress=compress))
        #: Hierarchical topology: every tree push rides the per-leg
        #: decomposition (collectives._hier_bucket_*) over the
        #: composite ("inner", "outer") axis. The default axis follows
        #: the topology so call sites (ZeRO trainers, store-DP) stay
        #: unchanged.
        self.topology = topology
        if topology is not None and axis == "data":
            axis = topology.flat_axis
        self.mesh = mesh
        self.axis = axis
        self.namespace = namespace
        self.compress = self.wire.compress
        self._kv = kv
        self._entries: dict[str, _Entry] = {}
        self._bindings: dict[str, Binding] = {}
        self._lock = threading.RLock()
        self._manifest_failed: set[str] = set()
        #: Per-key error-feedback residuals (stacked layout) for the
        #: int8 wire — each pushing process carries its own local
        #: quantization error into its next contribution.
        self._residuals: dict[str, jax.Array] = {}
        #: Per-push-site OUTER-leg residuals for the hierarchical
        #: int8 wire: site → {bucket index → flat f32 sharded
        #: ``P(flat_axis)``}. Outer-leg quantization error lives at
        #: bucket granularity (the cross-domain chunk boundaries cut
        #: across leaf slots, so a per-leaf keying cannot represent
        #: it); the collectives stream mutates the popped dict in
        #: place and this store carries it across steps under the
        #: same pop/store-back ownership as the per-leaf residuals.
        self._outer_residuals: dict[str, dict[int, jax.Array]] = {}
        self._seq = 0
        #: prefix → highest write stamp under it (every "/"-ancestor
        #: of each written key) — tree_seq in O(1) instead of an
        #: all-entries scan under the lock on every cache check.
        self._prefix_seq: dict[str, int] = {}

    # ---------------------------------------------------------- bindings

    def bind(self, key: str, spec: P = P(), reduce_op: str = "mean") -> None:
        """Declare a key's sharding + reduction before first use.

        Unbound keys default to replicated placement and mean reduction —
        the closest analog of the reference's replicate-everywhere Put.
        """
        with self._lock:
            self._bindings[key] = Binding(spec, reduce_op)
            if key in self._entries:
                self._entries[key].binding = self._bindings[key]

    def binding(self, key: str) -> Binding:
        with self._lock:
            return self._bindings.get(key, Binding())

    # ------------------------------------------------------------- basic

    def put(self, key: str, value, spec: P | None = None,
            epoch: int = 0) -> jax.Array:
        """Place a value under the key's binding; no collective, epoch
        reset to ``epoch`` (default 0 — a checkpoint resume passes the
        saved epoch so versions never go backwards). The
        initial-parameters path (ref Put store.go:56-62). Passing
        ``spec`` records it as the key's binding, same as bind()."""
        if spec is None:
            b = self.binding(key)
        else:
            b = Binding(spec, self.binding(key).reduce_op)
        arr = jax.device_put(jnp.asarray(value), NamedSharding(self.mesh, b.spec))
        with self._lock:
            if spec is not None:
                self._bindings[key] = b
            self._entries[key] = _Entry(arr, epoch, b,
                                        self._stamp_locked(key))
        self._publish(key)
        return arr

    def get(self, key: str) -> jax.Array:
        """The stored array in its bound sharding (ref Get store.go:38-53)."""
        with self._lock:
            entry = self._entries.get(key)
        if entry is None:
            raise NoKeyError(key)
        return entry.value

    def pull(self, key: str, gather: bool = False) -> jax.Array:
        """Get; with ``gather=True``, return a fully-replicated view
        (allgather lowering of a linearizable read)."""
        from ptype_tpu.metrics import annotate

        with annotate(f"store.pull/{key}"):
            _store_fault("store.pull", key)
            value = self.get(key)
            if gather:
                value = jax.device_put(value,
                                       NamedSharding(self.mesh, P()))
            chaos.note_ok("store.pull", key)
            return value

    def delete(self, key: str) -> None:
        with self._lock:
            if key not in self._entries:
                raise NoKeyError(key)
            del self._entries[key]
            self._stamp_locked(key)  # a deletion is a mutation: cached
            #                   readers must notice and re-pull
        if self._kv is not None:
            try:
                self._kv.delete(self._manifest_key(key))
            except NoKeyError:
                pass

    def keys(self) -> list[str]:
        with self._lock:
            return sorted(self._entries)

    def epoch(self, key: str) -> int:
        with self._lock:
            entry = self._entries.get(key)
        if entry is None:
            raise NoKeyError(key)
        return entry.epoch

    def tree_seq(self, prefix: str) -> int:
        """Highest store-wide write stamp under ``prefix/`` (0 when
        never written; deletions bump it too — they are mutations). A
        caller that recorded this after its own put_tree can cheaply
        detect whether ANY other writer has since touched the
        namespace — the re-pull guard train/store_dp.py uses instead
        of a full get_tree every step."""
        with self._lock:
            return self._prefix_seq.get(prefix, 0)

    def _stamp_locked(self, key: str) -> int:
        """Bump the store write stamp and index it under every
        "/"-ancestor of ``key``; callers hold the lock."""
        self._seq += 1
        parts = key.split("/")
        for i in range(1, len(parts)):
            self._prefix_seq["/".join(parts[:i])] = self._seq
        return self._seq

    # ------------------------------------------------------------- push

    def push(self, key: str, stacked, op: str | None = None) -> jax.Array:
        """Reduce per-worker contributions into the key — the allreduce
        lowering of Store.Put (north star). ``stacked``'s leading dim is
        the contribution axis (== mesh axis size); the reduced tensor is
        stored under the key's binding and returned.

        Rides the same single-bucket fused program as the tree pushes,
        so the wire policy is uniform across every push path: the int8
        wire is block-scaled, the bucket pad removes the per-leaf
        ``rest[0] % n`` eligibility lottery (the size floor
        ``int8_min_bytes`` still routes small leaves exact), and an
        armed error-feedback residual is carried per key here too —
        EF must not silently vanish because a caller used the per-key
        API instead of push_tree."""
        from ptype_tpu.metrics import annotate

        b = self.binding(key)
        op = op or b.reduce_op
        stacked = jnp.asarray(stacked)
        with annotate(f"store.push/{key}"):
            # Fault seam INSIDE the region: a chaos straggler delay
            # must be attributed to the collective leg of the goodput
            # breakdown, exactly like a real slow allreduce.
            _store_fault("store.push", key)
            items = [(key, stacked)]
            res = self._group_residuals(items)
            ores = self._pop_outer(key)
            try:
                outs = collectives.bucketed_all_reduce(
                    [stacked], self.mesh, self.axis, op, residuals=res,
                    outer_residuals=ores, **self._wire_kwargs(None))
            except BaseException:
                self._restore_residuals(items, res)
                self._restore_outer(key, ores)
                raise
            if res is not None:
                outs, new_res = outs
                self._store_residuals(items, new_res)
            self._store_outer(key, ores)
            reduced = outs[0]
        return self._commit_reduced(key, reduced)

    def push_scatter(self, key: str, stacked, op: str | None = None) -> jax.Array:
        """Reduce-scatter variant: each device keeps one shard of the
        reduced tensor (binding forced to shard dim 0 over the push axis).
        Pull with ``gather=True`` to reassemble — together they form the
        bandwidth-optimal allreduce decomposition."""
        _store_fault("store.push", key)
        b = Binding(P(self.axis), op or self.binding(key).reduce_op)
        stacked = jnp.asarray(stacked)
        n = axis_n(self.mesh, self.axis)
        if (self.compress == "int8"
                and collectives.quantized_all_reduce_eligible(
                    stacked.shape, n, b.reduce_op)):
            reduced = collectives.quantized_reduce_scatter(
                stacked, self.mesh, self.axis, b.reduce_op,
                q_block=self.wire.q_block)
        else:
            # int8-ineligible leaves ride the exact allreduce — the
            # caller opted into int8 loss, not bf16 loss.
            wire = (stacked.astype(jnp.bfloat16)
                    if self.compress == "bf16" else stacked)
            reduced = collectives.reduce_scatter(
                wire, self.mesh, self.axis, b.reduce_op)
        if self.compress:
            reduced = reduced.astype(stacked.dtype)
        return self._commit(key, reduced, b)

    def _commit(self, key: str, value: jax.Array, b: Binding) -> jax.Array:
        with self._lock:
            prev = self._entries.get(key)
            epoch = (prev.epoch + 1) if prev else 1
            self._entries[key] = _Entry(value, epoch, b,
                                        self._stamp_locked(key))
        self._publish(key)
        chaos.note_ok("store.push", key)
        return value

    def commit_sharded(self, key: str, flat: jax.Array) -> jax.Array:
        """Commit an ALREADY-PLACED ``P(axis)`` flat under ``key`` with
        push epoch semantics (epoch bumps, manifest publishes) — the
        ZeRO-3 trainer's per-step resident-param commit. No collective,
        no re-placement: the caller's fused apply produced the flat in
        its final sharding already."""
        return self._commit(key, flat, Binding(P(self.axis)))

    def reshard(self, mesh: Mesh, axis: str | None = None) -> None:
        """Re-home the store on a new (survivor) mesh — the live
        elastic reshard's store leg. Replicated entries are re-placed
        onto the new mesh with their epochs preserved; axis-SHARDED
        entries (scatter-path grad flats, ZeRO-3 param flats) are
        dropped, because their payloads are padded for the OLD replica
        count — their owner re-commits them in the new layout (the
        trainer re-pads via ``ZeroState.reshard``). Error-feedback
        residuals reset for the same reason: they are laid out per the
        old contribution count."""
        axis = axis or self.axis
        with self._lock:
            entries = list(self._entries.items())
        for key, entry in entries:
            if entry.binding.spec == P():
                arr = jax.device_put(np.asarray(entry.value),
                                     NamedSharding(mesh, P()))
                with self._lock:
                    cur = self._entries.get(key)
                    if cur is entry:
                        self._entries[key] = _Entry(
                            arr, entry.epoch, entry.binding,
                            self._stamp_locked(key))
            else:
                with self._lock:
                    self._entries.pop(key, None)
                    self._stamp_locked(key)
        with self._lock:
            self._residuals.clear()
            self._outer_residuals.clear()
        # mesh/axis are rebind-on-reshard like __init__'s bare writes:
        # the trainer quiesces pushes across a reshard (the step that
        # raised never ran), so no concurrent reader sees the old mesh.
        self.mesh = mesh
        self.axis = axis

    # -------------------------------------------------------------- tree

    def put_tree(self, prefix: str, tree) -> int:
        """Place every leaf under its path-derived key (no collective).

        All host→device transfers dispatch through ONE batched
        device_put instead of a per-leaf loop, then each key commits
        with the same epoch-0/binding/manifest semantics as
        :meth:`put`. Returns the highest write stamp THIS call
        assigned — the stamp a caller records to detect external
        writers via :meth:`tree_seq` (re-reading the global max after
        the fact would absorb a concurrent writer's stamp and hide
        their write)."""
        pairs = _flatten(prefix, tree)
        bindings = [self.binding(key) for key, _ in pairs]
        arrs = jax.device_put(
            [jnp.asarray(leaf) for _, leaf in pairs],
            [NamedSharding(self.mesh, b.spec) for b in bindings])
        with self._lock:
            for (key, _), b, arr in zip(pairs, bindings, arrs):
                self._entries[key] = _Entry(arr, 0, b, self._stamp_locked(key))
            assigned = self._seq
        for key, _ in pairs:
            self._publish(key)
        return assigned

    def push_tree(self, prefix: str, stacked_tree, op: str | None = None,
                  *, bucketed: bool = True,
                  bucket_bytes: int | None = None) -> dict[str, jax.Array]:
        """Push every leaf of a pytree of stacked contributions.

        Bucketed (the default): leaves are grouped by reduce op, packed
        into large same-dtype flat buckets, and reduced with ONE fused
        collective per bucket (``collectives.bucketed_all_reduce``) —
        the whole optimus-125M tree costs ceil(bytes/bucket) launches
        per dtype group instead of one per leaf, and every bucket is in
        flight before the first result commits. The store's compression
        policy applies per bucket (int8 finally meets its
        size-eligibility threshold there). Per-key semantics are
        unchanged: each key commits its unpacked view — epoch bump,
        binding spec, manifest publish — exactly like a per-leaf
        :meth:`push`.

        ``bucketed=False`` is the legacy per-leaf path, kept as the
        parity baseline and escape hatch. Returns ``{key: reduced}``.
        """
        from ptype_tpu.metrics import annotate, metrics

        pairs = _flatten(prefix, stacked_tree)
        if not bucketed:
            return {key: self.push(key, leaf, op) for key, leaf in pairs}

        t0 = _time.perf_counter()
        groups = self._push_groups(pairs, op)
        reduced: dict[str, jax.Array] = {}
        with annotate(f"store.push_tree/{prefix}"):
            # Fault seam INSIDE the region (see push): a straggler
            # delay lands in the collective leg of the goodput ledger
            # and on the push_tree span, not in untracked step time.
            _store_fault("store.push", prefix)
            for group_op, items in groups.items():
                res = self._group_residuals(items)
                site = f"{prefix}|{group_op}"
                ores = self._pop_outer(site)
                try:
                    outs = collectives.bucketed_all_reduce(
                        [leaf for _, leaf in items], self.mesh,
                        self.axis, group_op, residuals=res,
                        outer_residuals=ores,
                        **self._wire_kwargs(bucket_bytes))
                except BaseException:
                    self._restore_residuals(items, res)
                    self._restore_outer(site, ores)
                    raise
                if res is not None:
                    outs, new_res = outs
                    self._store_residuals(items, new_res)
                self._store_outer(site, ores)
                for (key, _), out in zip(items, outs):
                    reduced[key] = out
        # Commit the unpacked views: reshard keys with non-replicated
        # bindings in one batched device_put, then bump epoch + publish
        # manifest per key (the per-key Store contract).
        sharded = [k for k in reduced if self.binding(k).spec != P()]
        if sharded:
            arrs = jax.device_put(
                [reduced[k] for k in sharded],
                [NamedSharding(self.mesh, self.binding(k).spec)
                 for k in sharded])
            reduced.update(zip(sharded, arrs))
        out = {key: self._commit(key, reduced[key], self.binding(key))
               for key, _ in pairs}
        metrics.timing("store.push_tree").observe(
            _time.perf_counter() - t0)
        metrics.counter("store.push_tree.leaves").add(len(pairs))
        chaos.note_ok("store.push", prefix)
        return out

    def _push_groups(self, pairs, op: str | None):
        """Group (key, leaf) pairs by resolved reduce op (dtype
        grouping happens inside the bucket planner); op=None honors
        each key's binding — shared by the barrier and streamed push
        paths so key/op resolution cannot drift between them."""
        groups: dict[str, list[tuple[str, jax.Array]]] = {}
        for key, leaf in pairs:
            resolved = op or self.binding(key).reduce_op
            groups.setdefault(resolved, []).append(
                (key, jnp.asarray(leaf)))
        return groups

    def _wire_kwargs(self, bucket_bytes: int | None) -> dict:
        kw = {
            "bucket_bytes": bucket_bytes or self.wire.bucket_bytes,
            "compress": self.compress,
            "int8_min_bytes": self.wire.int8_min_bytes,
            "q_block": self.wire.q_block,
        }
        if self.topology is not None:
            kw["topology"] = self.topology
        return kw

    def _commit_reduced(self, key: str, out: jax.Array) -> jax.Array:
        """Reshard to the key's binding (if any) and commit — the
        per-key tail both push paths share."""
        kb = self.binding(key)
        if kb.spec != P():
            out = jax.device_put(out, NamedSharding(self.mesh, kb.spec))
        return self._commit(key, out, kb)

    def _group_residuals(self, items) -> list | None:
        """Per-leaf EF residuals for one push group (None when the
        wire doesn't carry feedback). Missing/stale-shape entries stay
        None — the collectives layer seeds zeros.

        Residuals are POPPED, not read: taking ownership under the
        lock means a concurrent pusher of the same key folds zeros
        instead of double-applying the same accumulated error (each
        concurrent push then writes back its own fresh residual)."""
        if not self._feedback_armed():
            return None
        with self._lock:
            return [self._residuals.pop(key, None) for key, _ in items]

    def _feedback_armed(self) -> bool:
        """Per-leaf EF is armed when the flat wire is int8+EF, OR when
        a topology's INNER leg resolves to int8 while the flat policy
        is exact (a LegWire override) — the inner leg owns the
        per-leaf residual in the hierarchical decomposition."""
        if self.wire.feedback_armed:
            return True
        t = self.topology
        if t is None or not self.wire.error_feedback:
            return False
        cw, _ = t.resolve_leg("inner", self.compress, self.wire.q_block)
        return cw == "int8"

    def _store_residuals(self, items, new_res: list) -> None:
        with self._lock:
            for (key, _), r in zip(items, new_res):
                if r is not None:
                    self._residuals[key] = r

    def _restore_residuals(self, items, popped: list | None) -> None:
        """Put popped-but-unconsumed residuals back (a push that
        failed between pop and store-back must not drop the
        accumulated error). setdefault: never clobber a fresher
        residual a concurrent pusher wrote meanwhile."""
        if popped is None:
            return
        with self._lock:
            for (key, _), r in zip(items, popped):
                if r is not None:
                    self._residuals.setdefault(key, r)

    def _outer_armed(self) -> bool:
        """Whether the topology's OUTER (cross-domain) leg carries an
        int8 wire with error feedback — the only case the per-bucket
        outer residual dict is worth threading through a push."""
        t = self.topology
        if t is None or not self.wire.error_feedback:
            return False
        cw, _ = t.resolve_leg("outer", self.compress, self.wire.q_block)
        return cw == "int8"

    def _pop_outer(self, site: str) -> dict | None:
        """Take ownership of a push site's outer-leg residual dict
        (popped under the lock, same two-phase discipline as
        :meth:`_group_residuals`): the collectives stream mutates it
        in place per bucket; store it back when the push completes."""
        if not self._outer_armed():
            return None
        with self._lock:
            return self._outer_residuals.pop(site, {})

    def _store_outer(self, site: str, ores: dict | None) -> None:
        """Write back a consumed outer residual dict; our entries are
        freshest for every bucket this push actually ran, so they
        clobber (mirror of :meth:`_store_residuals`)."""
        if ores:
            with self._lock:
                self._outer_residuals.setdefault(site, {}).update(ores)

    def _restore_outer(self, site: str, ores: dict | None) -> None:
        """Failure path: put popped-but-possibly-unconsumed entries
        back without clobbering a concurrent pusher's fresher ones
        (mirror of :meth:`_restore_residuals`)."""
        if ores:
            with self._lock:
                cur = self._outer_residuals.setdefault(site, {})
                for bi, r in ores.items():
                    cur.setdefault(bi, r)

    def push_tree_iter(self, prefix: str, stacked_tree,
                       op: str | None = None, *,
                       bucket_bytes: int | None = None):
        """The fine-grained-overlap variant of :meth:`push_tree`
        (T3 pattern, PAPERS.md): a generator that dispatches ONE
        bucket's fused collective per iteration, commits its keys, and
        yields the :class:`BucketPush` — so a consumer can interleave
        its own dispatches (per-bucket optimizer apply) and waits with
        the remaining buckets' dispatches, putting reduce i+1 on the
        wire while bucket i is being consumed. Same per-key
        epoch/manifest/residual semantics as push_tree."""
        from ptype_tpu.metrics import annotate, metrics

        pairs = _flatten(prefix, stacked_tree)
        t0 = _time.perf_counter()
        groups = self._push_groups(pairs, op)
        # Each bucket's dispatch+commit runs in its OWN annotate region
        # (not one region held open across yields): the consumer's
        # work between buckets — optimizer applies, waits — must land
        # in its own legs of the goodput breakdown, not inflate the
        # collective leg here.
        first = True
        for group_op, items in groups.items():
            res = self._group_residuals(items)
            site = f"{prefix}|{group_op}"
            ores = self._pop_outer(site)
            done = False
            # The pop in _group_residuals took ownership of every
            # carried residual in the group — track the ones no int8
            # bucket has consumed yet, and RESTORE them when the
            # stream ends (or is abandoned mid-way): a bucket whose
            # wire resolved exact, or one the consumer never drained,
            # must not silently lose its accumulated error.
            pending = ({i: r for i, r in enumerate(res)
                        if r is not None} if res is not None else {})
            try:
                it = collectives.bucketed_all_reduce_stream(
                    [leaf for _, leaf in items], self.mesh,
                    self.axis, group_op, residuals=res,
                    outer_residuals=ores,
                    **self._wire_kwargs(bucket_bytes))
                while True:
                    with annotate(f"store.push_tree/{prefix}"):
                        if first:
                            # Fault seam INSIDE the region (see push):
                            # a straggler delay lands in the
                            # collective leg.
                            _store_fault("store.push", prefix)
                            first = False
                        try:
                            b, outs, new_res = next(it)
                        except StopIteration:
                            break
                        keys, vals = [], []
                        for i, (s, out) in enumerate(zip(b.slots, outs)):
                            key = items[s.index][0]
                            vals.append(self._commit_reduced(key, out))
                            keys.append(key)
                            if new_res is not None:
                                pending.pop(s.index, None)
                                if new_res[i] is not None:
                                    with self._lock:
                                        self._residuals[key] = new_res[i]
                        handle = BucketPush(prefix, keys, vals)
                    yield handle
                done = True
            finally:
                # Outer-leg residuals: the stream updated the popped
                # dict in place for every bucket it ran; clobber-store
                # on a full drain, setdefault-restore on abandonment.
                (self._store_outer if done
                 else self._restore_outer)(site, ores)
                if pending:
                    with self._lock:
                        for i, r in pending.items():
                            # setdefault: never clobber a fresher
                            # residual a concurrent pusher wrote.
                            self._residuals.setdefault(items[i][0], r)
        metrics.timing("store.push_tree").observe(
            _time.perf_counter() - t0)
        metrics.counter("store.push_tree.leaves").add(len(pairs))
        chaos.note_ok("store.push", prefix)

    def push_tree_scatter_iter(self, prefix: str, stacked_tree,
                               op: str | None = None, *,
                               bucket_bytes: int | None = None):
        """The ZeRO gradient leg (parallel/zero.py): reduce-SCATTER
        every bucket of a stacked pytree instead of allreducing it —
        half the wire bytes, each device left holding one contiguous
        flat shard per bucket, committed under
        ``<prefix>/bucketNNNNN`` with a ``P(axis)`` binding (the Store
        contract at bucket granularity: epoch bump + manifest publish
        per scatter, pullable with ``gather=True``). A generator like
        :meth:`push_tree_iter`: one fused collective dispatched per
        iteration, yielding :class:`ShardPush` handles so the consumer
        (the shard-local optimizer apply) interleaves with the
        remaining buckets' dispatches.

        Error-feedback residuals ride the int8 wire exactly like the
        allreduce paths, keyed per LEAF (ownership is uniform across
        push_tree/push_tree_iter/scatter — a trainer switching modes
        carries its accumulated error along); with no all_gather leg,
        the residual is the phase-1 error of this replica's whole
        contribution.
        """
        from ptype_tpu.metrics import annotate, metrics

        pairs = _flatten(prefix, stacked_tree)
        t0 = _time.perf_counter()
        groups = self._push_groups(pairs, op)
        first = True
        bucket_no = 0
        for group_op, items in groups.items():
            res = self._group_residuals(items)
            site = f"{prefix}|{group_op}"
            ores = self._pop_outer(site)
            done = False
            pending = ({i: r for i, r in enumerate(res)
                        if r is not None} if res is not None else {})
            try:
                it = collectives.bucketed_reduce_scatter_stream(
                    [leaf for _, leaf in items], self.mesh,
                    self.axis, group_op, residuals=res,
                    outer_residuals=ores,
                    **self._wire_kwargs(bucket_bytes))
                while True:
                    with annotate(f"store.push_tree/{prefix}"):
                        if first:
                            # Fault seam INSIDE the region (see push):
                            # a straggler delay lands in the
                            # collective leg.
                            _store_fault("store.push", prefix)
                            first = False
                        try:
                            b, flat, new_res = next(it)
                        except StopIteration:
                            break
                        key = f"{prefix}/bucket{bucket_no:05d}"
                        leaf_keys = [items[s.index][0]
                                     for s in b.slots]
                        flat = self._commit(
                            key, flat, Binding(P(self.axis), group_op))
                        if new_res is not None:
                            for i, s in enumerate(b.slots):
                                pending.pop(s.index, None)
                                if new_res[i] is not None:
                                    with self._lock:
                                        self._residuals[
                                            items[s.index][0]
                                        ] = new_res[i]
                        handle = ShardPush(prefix, bucket_no, key, b,
                                           leaf_keys, flat)
                        bucket_no += 1
                    yield handle
                done = True
            finally:
                # Outer-leg residuals: clobber-store on a full drain,
                # setdefault-restore on abandonment (see
                # push_tree_iter).
                (self._store_outer if done
                 else self._restore_outer)(site, ores)
                if pending:
                    with self._lock:
                        for i, r in pending.items():
                            # setdefault: never clobber a fresher
                            # residual a concurrent pusher wrote.
                            self._residuals.setdefault(items[i][0], r)
        metrics.timing("store.push_tree").observe(
            _time.perf_counter() - t0)
        metrics.counter("store.push_tree.leaves").add(len(pairs))
        chaos.note_ok("store.push", prefix)

    def push_tree_stream(self, prefix: str, stacked_tree,
                         op: str | None = None, *,
                         bucket_bytes: int | None = None
                         ) -> list[BucketPush]:
        """:meth:`push_tree_iter` drained eagerly: every bucket
        dispatched and committed, handles returned in bucket order —
        for consumers that want all collectives in flight before the
        first wait."""
        return list(self.push_tree_iter(prefix, stacked_tree, op,
                                        bucket_bytes=bucket_bytes))

    def get_tree(self, prefix: str,
                 gather: bool = False) -> dict[str, jax.Array]:
        """All keys under ``prefix/`` as a flat dict. ``gather=True``
        returns fully-replicated views (the allgather lowering of a
        linearizable read), resharded through one batched device_put.

        Runs as a ``store.pull_tree/<prefix>`` region through the
        metrics.annotate seam — profiler timeline + distributed-trace
        span from the one hook (same contract as push_tree)."""
        from ptype_tpu.metrics import annotate

        with annotate(f"store.pull_tree/{prefix}"):
            return self._get_tree(prefix, gather)

    def _get_tree(self, prefix: str,
                  gather: bool = False) -> dict[str, jax.Array]:
        _store_fault("store.pull", prefix)
        sep = prefix + "/"
        with self._lock:
            hits = {k: e.value for k, e in self._entries.items()
                    if k.startswith(sep)}
        if not hits:
            raise NoKeyError(prefix)
        hits = dict(sorted(hits.items()))
        if gather:
            keys = list(hits)
            arrs = jax.device_put(
                [hits[k] for k in keys],
                [NamedSharding(self.mesh, P())] * len(keys))
            hits = dict(zip(keys, arrs))
        chaos.note_ok("store.pull", prefix)
        return hits

    # ---------------------------------------------------------- manifest

    def _manifest_key(self, key: str) -> str:
        return f"{TENSOR_PREFIX}/{self.namespace}/{key}"

    def _publish(self, key: str) -> None:
        """Best-effort manifest publish + catch-up of earlier misses.

        Manifests are DISCOVERY metadata; the tensors themselves are
        device-resident and the collectives never touch the
        coordinator. A control-plane outage (e.g. the seed dying
        before its standby promotes) must lag the manifest, not kill
        the training step. Keys whose publish failed are remembered
        and republished on the next successful KV contact — a key
        put exactly once (params) self-heals too, not just re-pushed
        gradient keys.
        """
        if self._kv is None:
            return
        if not self._try_publish(key):
            return
        with self._lock:
            missed = [k for k in self._manifest_failed
                      if k != key and k in self._entries]
        recovered = [k for k in missed if self._try_publish(k)]
        if recovered:
            log.info("manifest publishing recovered",
                     kv={"republished": len(recovered)})

    def _try_publish(self, key: str) -> bool:
        with self._lock:
            entry = self._entries[key]
        try:
            self._kv.put(
                self._manifest_key(key),
                json.dumps({
                    "shape": list(entry.value.shape),
                    "dtype": str(entry.value.dtype),
                    "spec": spec_to_json(entry.binding.spec),
                    "epoch": entry.epoch,
                }, separators=(",", ":")),
            )
        except CoordinationError as e:
            with self._lock:
                self._manifest_failed.add(key)
            log.warning("manifest publish failed; will retry on next "
                        "successful publish",
                        kv={"key": key, "err": str(e)})
            return False
        with self._lock:
            self._manifest_failed.discard(key)
        return True

    def manifest(self) -> dict[str, dict]:
        """Key → {shape, dtype, spec, epoch} for the whole namespace —
        what a checkpointer or late joiner reads to discover the space."""
        out = {}
        with self._lock:
            for key, entry in self._entries.items():
                out[key] = {
                    "shape": list(entry.value.shape),
                    "dtype": str(entry.value.dtype),
                    "spec": spec_to_json(entry.binding.spec),
                    "epoch": entry.epoch,
                }
        return out


def _flatten(prefix: str, tree) -> list[tuple[str, jax.Array]]:
    """Pytree → sorted (key, leaf) pairs with path-derived key names."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in leaves:
        parts = [prefix] + [_path_part(p) for p in path]
        out.append(("/".join(parts), leaf))
    return sorted(out)


def _path_part(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


# ---------------------------------------------------------------- benching


def measure_push_tree(mesh: Mesh, axis="data",
                      preset: str = "tiny", iters: int = 3,
                      compress: str | None = None,
                      bucket_bytes: int | None = None,
                      wire: collectives.WireConfig | None = None,
                      topology: Topology | None = None) -> dict:
    """Wall-clock a full param-tree gradient push, bucketed vs
    per-leaf — the BENCH ``store_push_tree_ms`` metric.

    Builds the ``preset`` transformer's parameter tree, fakes stacked
    per-worker grads (each device holding one contribution), and times
    ``push_tree`` both ways after a warm/compile pass.
    """
    from ptype_tpu.models import transformer as tfm

    cfg = tfm.preset(preset)
    params = jax.jit(lambda r: tfm.init_params(r, cfg))(
        jax.random.PRNGKey(0))
    n = axis_n(mesh, axis)
    stacked = jax.tree_util.tree_map(
        lambda p: jax.device_put(
            jnp.broadcast_to(p[None], (n, *p.shape)),
            NamedSharding(mesh, P(axis, *(None,) * p.ndim))),
        params)
    store = TensorStore(mesh, axis, compress=compress, wire=wire,
                        topology=topology)
    leaves = jax.tree_util.tree_leaves(params)
    nbytes = sum(v.size * v.dtype.itemsize for v in leaves)

    def drain(out: dict) -> None:
        jax.block_until_ready(list(out.values()))

    def timed(bucketed: bool) -> float:
        drain(store.push_tree("g", stacked, op="mean",
                              bucketed=bucketed,
                              bucket_bytes=bucket_bytes))  # compile+warm
        t0 = _time.perf_counter()
        for _ in range(iters):
            out = store.push_tree("g", stacked, op="mean",
                                  bucketed=bucketed,
                                  bucket_bytes=bucket_bytes)
        drain(out)
        return (_time.perf_counter() - t0) / iters

    per_leaf = timed(False)
    bucketed = timed(True)
    plan = collectives.plan_buckets(
        jax.tree_util.tree_leaves(stacked), n,
        bucket_bytes or collectives.DEFAULT_BUCKET_BYTES)
    return {
        "bucketed_ms": round(bucketed * 1e3, 2),
        "per_leaf_ms": round(per_leaf * 1e3, 2),
        "speedup": round(per_leaf / bucketed, 2) if bucketed else None,
        "n_leaves": len(leaves),
        "n_buckets": len(plan),
        "payload_mb": round(nbytes / 2**20, 2),
        # Ring allreduce moves 2*(n-1)/n of the buffer per device.
        "gbps": round(2 * (n - 1) / n * nbytes / bucketed / 1e9, 2),
    }
