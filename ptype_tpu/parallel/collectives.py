"""Compiled XLA collectives over mesh axes — the ICI data plane.

The reference's data plane was gob-encoded ``net/rpc`` over TCP
(cluster/rpc.go:277); here the equivalent primitive set is XLA collectives
compiled over ICI (SURVEY.md §2 "Distributed communication backend").
These wrappers give the *eager* entry points the TensorStore and benches
use; inside a jit'ed train step you use ``jax.lax`` collectives (under
``shard_map``) or let GSPMD insert them from sharding annotations.

Conventions: the "stacked" layout carries one leading contribution axis of
size ``mesh.shape[axis]``, sharded over ``axis`` — the eager analog of
per-worker values in a multi-controller program.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ptype_tpu.parallel.mesh import axis_n
from ptype_tpu.parallel.topology import (INNER_AXIS, OUTER_AXIS,
                                         Topology)

_REDUCERS = ("sum", "mean", "max", "min")


def _rest(ndim: int) -> tuple[None, ...]:
    return (None,) * (ndim - 1)


@functools.lru_cache(maxsize=256)
def _all_reduce_fn(mesh: Mesh, axis: str, ndim: int, op: str):
    in_spec = P(axis, *_rest(ndim))
    out_spec = P(*_rest(ndim))

    def f(local):
        x = jnp.squeeze(local, axis=0)
        if op == "sum":
            return lax.psum(x, axis)
        if op == "mean":
            return lax.pmean(x, axis)
        if op == "max":
            return lax.pmax(x, axis)
        return lax.pmin(x, axis)

    return jax.jit(
        shard_map(f, mesh=mesh, in_specs=in_spec, out_specs=out_spec)
    )


def all_reduce(stacked: jax.Array, mesh: Mesh, axis: str = "data",
               op: str = "sum") -> jax.Array:
    """Reduce per-worker contributions; result replicated over ``axis``.

    ``stacked``: shape ``(mesh.shape[axis], *rest)``, sharded on dim 0.
    Returns shape ``rest`` with every device holding the reduction — the
    Store push lowering (ref Put store.go:56-62 → psum).
    """
    if op not in _REDUCERS:
        raise ValueError(f"all_reduce: op must be one of {_REDUCERS}")
    n = axis_n(mesh, axis)
    if stacked.shape[0] != n:
        raise ValueError(
            f"all_reduce: leading dim {stacked.shape[0]} != axis size {n}"
        )
    stacked = jax.device_put(stacked, NamedSharding(mesh, P(axis, *_rest(stacked.ndim))))
    return _all_reduce_fn(mesh, axis, stacked.ndim, op)(stacked)


@functools.lru_cache(maxsize=256)
def _all_gather_fn(mesh: Mesh, axis: str, ndim: int):
    spec = P(axis, *_rest(ndim))

    def f(local):
        return lax.all_gather(jnp.squeeze(local, axis=0), axis)

    # all_gather's output is replicated by construction, but the varying-
    # manual-axes check cannot infer that — disable it for this wrapper.
    return jax.jit(
        shard_map(f, mesh=mesh, in_specs=spec,
                  out_specs=P(*_rest(ndim + 1)), check_vma=False)
    )


def all_gather(stacked: jax.Array, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Gather per-worker contributions to every device, replicated.

    ``(n, *rest)`` sharded on dim 0 → ``(n, *rest)`` replicated — the Store
    pull lowering (ref Get store.go:38-53 → allgather).
    """
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P(axis, *_rest(stacked.ndim)))
    )
    return _all_gather_fn(mesh, axis, stacked.ndim)(stacked)


@functools.lru_cache(maxsize=256)
def _reduce_scatter_fn(mesh: Mesh, axis: str, ndim: int, op: str):
    in_spec = P(axis, *_rest(ndim))
    # Output keeps rank ndim-1; dim 0 of the payload is scattered.
    out_spec = P(axis, *_rest(ndim - 1))

    def f(local):
        x = jnp.squeeze(local, axis=0)
        n = axis_size(axis)
        red = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
        if op == "mean":
            red = red / n
        return red

    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_spec, out_specs=out_spec))


def reduce_scatter(stacked: jax.Array, mesh: Mesh, axis: str = "data",
                   op: str = "sum") -> jax.Array:
    """Reduce contributions, leaving each device one shard of the result.

    ``(n, *payload)`` with ``payload[0] % n == 0`` → ``payload`` sharded on
    dim 0 over ``axis``. Half the ICI bytes of an all_reduce when the
    consumer is itself sharded (ZeRO/FSDP-style grad reduction).
    """
    if op not in ("sum", "mean"):
        raise ValueError(
            f"reduce_scatter: op must be 'sum' or 'mean', got {op!r}"
        )
    n = axis_n(mesh, axis)
    if stacked.ndim < 2 or stacked.shape[1] % n != 0:
        raise ValueError(
            f"reduce_scatter: payload dim 0 ({stacked.shape[1:]}) must "
            f"divide by axis size {n}"
        )
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P(axis, *_rest(stacked.ndim)))
    )
    return _reduce_scatter_fn(mesh, axis, stacked.ndim, op)(stacked)


@functools.lru_cache(maxsize=256)
def _ring_shift_fn(mesh: Mesh, axis: str, ndim: int, shift: int):
    spec = P(axis, *_rest(ndim))

    def f(local):
        n = axis_size(axis)
        perm = [(i, (i + shift) % n) for i in range(n)]
        return lax.ppermute(local, axis, perm)

    return jax.jit(shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec))


def ring_shift(stacked: jax.Array, mesh: Mesh, axis: str = "data",
               shift: int = 1) -> jax.Array:
    """Rotate shards around the ``axis`` ring by ``shift`` (ppermute) —
    the building block of ring attention (SURVEY.md §5 long-context)."""
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P(axis, *_rest(stacked.ndim)))
    )
    return _ring_shift_fn(mesh, axis, stacked.ndim, shift)(stacked)


@functools.lru_cache(maxsize=256)
def _all_to_all_fn(mesh: Mesh, axis: str, ndim: int):
    spec = P(axis, *_rest(ndim))

    def f(local):
        # local: (1, n*chunk, *rest) → exchange chunks around the axis.
        x = jnp.squeeze(local, axis=0)
        out = lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)
        return out[None]

    return jax.jit(shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec))


def all_to_all(stacked: jax.Array, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Transpose shard ownership: device i's chunk j goes to device j —
    the EP/Ulysses exchange. ``(n, n*chunk, *rest)`` sharded on dim 0."""
    n = axis_n(mesh, axis)
    if stacked.ndim < 2 or stacked.shape[1] % n != 0:
        raise ValueError(
            f"all_to_all: payload dim 0 must divide by axis size {n}"
        )
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P(axis, *_rest(stacked.ndim)))
    )
    return _all_to_all_fn(mesh, axis, stacked.ndim)(stacked)


#: Default elements per quantization scale block (EQuARX pattern,
#: PAPERS.md arXiv 2506.17615): small enough that one outlier poisons
#: ~0.2% of a bucket instead of a whole all_to_all chunk, large enough
#: that the f32 scale overhead stays <1% of the int8 wire bytes.
DEFAULT_QUANT_BLOCK = 512


def _q_int8_blockwise(chunks: jax.Array, block: int | None):
    """Int8-quantize ``chunks: (m, c)`` with one absmax scale per
    ``block`` contiguous elements (``block=None`` → one scale per
    whole chunk — PR 1's coarse granularity, kept for the wire bench
    comparison). Each chunk zero-pads to a block multiple internally;
    zero blocks quantize exactly. Deterministic round-to-nearest —
    collective results must be reproducible across reruns for the
    numerics test tier. Returns ``(q (m, nb, block) int8,
    scales (m, nb) f32)``."""
    m, c = chunks.shape
    block = c if block is None else min(int(block), c)
    pad = (-c) % block
    if pad:
        chunks = jnp.pad(chunks, ((0, 0), (0, pad)))
    b = chunks.reshape(m, -1, block)
    amax = jnp.max(jnp.abs(b), axis=2)
    scale = jnp.where(amax == 0.0, 1.0, amax / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(b.astype(jnp.float32) / scale[:, :, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dq_int8_blockwise(q: jax.Array, scale: jax.Array, c: int):
    """Inverse of :func:`_q_int8_blockwise`: ``(m, nb, block)`` int8 +
    ``(m, nb)`` scales → ``(m, c)`` f32 (internal block pad dropped)."""
    out = (q.astype(jnp.float32) * scale[:, :, None])
    return out.reshape(q.shape[0], -1)[:, :c]


def _int8_phase1(x, axis: str, op: str, block: int | None):
    """The int8 reduce-scatter leg, shared by the quantized allreduce
    and the standalone quantized reduce_scatter (one implementation so
    numerics fixes can't drift between them): slice my flat
    contribution into n chunks, quantize each with per-``block``
    absmax scales, all_to_all so device j collects everyone's chunk j,
    dequantize and reduce. Returns this device's reduced f32 chunk
    ``(elems/n,)`` plus the local quantization error ``(n, elems/n)``
    (what error feedback carries to the next step)."""
    n = axis_size(axis)
    c = x.shape[0] // n
    chunks = x.astype(jnp.float32).reshape(n, c)
    q, scale = _q_int8_blockwise(chunks, block)
    err = chunks - _dq_int8_blockwise(q, scale, c)
    q = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
    scale = lax.all_to_all(scale, axis, split_axis=0, concat_axis=0,
                           tiled=True)
    red = jnp.sum(_dq_int8_blockwise(q, scale, c), axis=0)
    if op == "mean":
        red = red / n
    return red, err


def _int8_all_reduce_body(x, axis: str, op: str,
                          block: int | None = DEFAULT_QUANT_BLOCK,
                          res=None):
    """Both wire legs of the block-scaled int8 allreduce on one
    device's flat contribution ``x`` (``len(x) % n == 0``): phase 1
    (:func:`_int8_phase1` in sum space), then the all_gather leg —
    re-quantize my reduced chunk with per-block scales, gather,
    dequantize — so every device reassembles the full f32 reduction
    (mean divided at the very end, so both wire legs and the error
    terms live in one space).

    ``res`` arms **error feedback** (EQuARX/EF-SGD): the residual is
    added to the contribution before quantizing, and the returned
    residual carries BOTH legs' quantization error — phase 1's error
    across my whole contribution, plus phase 2's error on the chunk I
    own, folded in at my chunk's offset (I re-own the same chunk next
    step, so adding it to my next contribution cancels it in the
    reduction). Returns ``(out shaped like x, new_res | None)``."""
    n = axis_size(axis)
    c = x.shape[0] // n
    xf = x.astype(jnp.float32)
    if res is not None:
        xf = xf + res.astype(jnp.float32)
    red, err1 = _int8_phase1(xf, axis, "sum", block)
    q2, s2 = _q_int8_blockwise(red[None], block)
    err2 = red - _dq_int8_blockwise(q2, s2, c)[0]
    qg = lax.all_gather(q2[0], axis)                # (n, nb, block)
    sg = lax.all_gather(s2[0], axis)                # (n, nb)
    out = _dq_int8_blockwise(qg, sg, c).reshape(x.shape)
    if op == "mean":
        out = out / n
    if res is None:
        return out, None
    new_res = err1.reshape(x.shape)
    idx = lax.axis_index(axis)
    mine = lax.dynamic_slice(new_res, (idx * c,), (c,)) + err2
    new_res = lax.dynamic_update_slice(new_res, mine, (idx * c,))
    return out, new_res.astype(res.dtype)


@functools.lru_cache(maxsize=256)
def _quantized_all_reduce_fn(mesh: Mesh, axis: str, ndim: int, op: str,
                             block: int | None):
    in_spec = P(axis, *_rest(ndim))
    out_spec = P(*_rest(ndim))

    def f(local):
        x = jnp.squeeze(local, axis=0)
        out, _ = _int8_all_reduce_body(x.reshape(-1), axis, op, block)
        return out.reshape(x.shape).astype(x.dtype)

    return jax.jit(
        shard_map(f, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
                  check_vma=False)
    )


@functools.lru_cache(maxsize=256)
def _quantized_reduce_scatter_fn(mesh: Mesh, axis: str, ndim: int,
                                 op: str, block: int | None):
    in_spec = P(axis, *_rest(ndim))
    out_spec = P(axis, *_rest(ndim - 1))

    def f(local):
        x = jnp.squeeze(local, axis=0)
        red, _ = _int8_phase1(x.reshape(-1), axis, op, block)
        n = axis_size(axis)
        return red.reshape((x.shape[0] // n,) + x.shape[1:]).astype(
            x.dtype)

    return jax.jit(shard_map(f, mesh=mesh, in_specs=in_spec,
                             out_specs=out_spec))


def quantized_reduce_scatter(stacked: jax.Array, mesh: Mesh,
                             axis: str = "data",
                             op: str = "sum", *,
                             q_block: int | None = DEFAULT_QUANT_BLOCK
                             ) -> jax.Array:
    """Phase 1 of :func:`quantized_all_reduce` alone: int8-quantized
    all_to_all + local dequant-reduce — each device keeps ONE f32
    shard of the reduced tensor (the bandwidth-optimal int8 grad
    reduction for consumers that are themselves sharded, ZeRO/FSDP
    style). Same shape contract and error bound as the allreduce's
    first phase (one round-to-nearest quantization)."""
    n = axis_n(mesh, axis)
    if not quantized_all_reduce_eligible(stacked.shape, n, op):
        raise ValueError(
            f"quantized_reduce_scatter: need op in sum/mean (got "
            f"{op!r}), leading dim == axis size {n} (got "
            f"{stacked.shape[0]}), and payload dim 0 to divide by {n} "
            f"(got {stacked.shape[1:]})")
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P(axis, *_rest(stacked.ndim))))
    return _quantized_reduce_scatter_fn(mesh, axis, stacked.ndim, op,
                                        q_block)(stacked)


def quantized_all_reduce_eligible(shape: tuple, n: int,
                                  op: str) -> bool:
    """Whether a stacked ``(n, *rest)`` payload can take the int8 path
    — the single source of its constraints (callers like TensorStore
    route ineligible leaves to the exact allreduce)."""
    return (op in ("sum", "mean") and len(shape) >= 2
            and shape[0] == n and shape[1] % n == 0)


def quantized_all_reduce(stacked: jax.Array, mesh: Mesh,
                         axis: str = "data",
                         op: str = "sum", *,
                         q_block: int | None = DEFAULT_QUANT_BLOCK
                         ) -> jax.Array:
    """Block-scaled int8 allreduce — the EQuARX pattern (PAPERS.md):
    both wire phases of the bandwidth-optimal allreduce decomposition
    (all_to_all reduce-scatter, then all_gather) carry int8 payloads
    with one f32 absmax scale per ``q_block`` elements, ≈4× fewer ICI
    bytes than f32 at a bounded relative error (two round-to-nearest
    quantizations of ≤ block-absmax/254 each — an outlier poisons one
    block, not the whole chunk). ``q_block=None`` falls back to one
    scale per all_to_all chunk (the PR 1 wire, kept for comparison).
    Lossy: for gradients, not parameters.

    ``stacked``: ``(axis_size, *rest)`` with ``rest[0] % axis_size
    == 0``; returns ``rest``, replicated.
    """
    n = axis_n(mesh, axis)
    if not quantized_all_reduce_eligible(stacked.shape, n, op):
        raise ValueError(
            f"quantized_all_reduce: need op in sum/mean (got {op!r}), "
            f"leading dim == axis size {n} (got {stacked.shape[0]}), "
            f"and payload dim 0 to divide by {n} "
            f"(got {stacked.shape[1:]})")
    stacked = jax.device_put(
        stacked, NamedSharding(mesh, P(axis, *_rest(stacked.ndim))))
    return _quantized_all_reduce_fn(mesh, axis, stacked.ndim, op,
                                    q_block)(stacked)


def broadcast(value: jax.Array, mesh: Mesh) -> jax.Array:
    """Replicate a host/single-device value across the whole mesh."""
    return jax.device_put(value, NamedSharding(mesh, P()))


# ------------------------------------------------- bucketed tree collectives
#
# A pytree pushed leaf-by-leaf costs one XLA launch per leaf — ~100
# eager collectives for optimus-125M, which is why BENCH_r05's
# store_allreduce_gbps (one big fused buffer) is unreachable from the
# per-leaf push_tree path. The bucketing layer packs same-dtype leaves
# into large flat buckets (EQuARX: quantized collectives only pay off
# on large fused buffers; T3: overlap the reduction instead of
# serializing per-leaf round trips) and runs ONE fused collective per
# bucket inside a single jit'd shard_map program. Buckets dispatch
# asynchronously — the host races ahead and issues every bucket before
# the first finishes, so reduction overlaps host work and later compute.

#: Default per-device payload target per bucket. Big enough that launch
#: overhead and per-collective latency amortize; small enough that the
#: first bucket's reduction overlaps the packing of the rest.
DEFAULT_BUCKET_BYTES = 32 * 1024 * 1024

#: Buckets below this per-device payload ride the EXACT allreduce even
#: under compress="int8": at small sizes the quantize/dequantize math
#: and the second collective leg cost more than the wire bytes saved.
INT8_MIN_BUCKET_BYTES = 64 * 1024


@dataclasses.dataclass(frozen=True)
class WireConfig:
    """One place for the gradient-wire policy, plumbed from the
    trainers through :class:`~ptype_tpu.parallel.tensorstore.
    TensorStore` down to the bucketed collectives.

    ``compress``: None (exact) | "bf16" | "int8" (block-scaled).
    ``q_block``: elements per int8 scale block (None = one scale per
    all_to_all chunk — the PR 1 wire, kept for benches).
    ``error_feedback``: carry a per-leaf residual of the quantization
    error into the next push (int8 wire only) so error does not
    accumulate across steps.
    """

    compress: str | None = None
    q_block: int | None = DEFAULT_QUANT_BLOCK
    error_feedback: bool = True
    bucket_bytes: int = DEFAULT_BUCKET_BYTES
    int8_min_bytes: int = INT8_MIN_BUCKET_BYTES

    def __post_init__(self):
        if self.compress not in (None, "bf16", "int8"):
            raise ValueError(
                f"WireConfig: unknown compression {self.compress!r}")
        # Floor of 8: below that the 4-byte f32 scale per block costs
        # more than the 3 bytes/element int8 saves (at q_block=1 the
        # "compressed" wire is 5 bytes/elem vs fp32's 4 — lossy AND
        # bigger). A config typo must fail here, not ship that.
        if self.q_block is not None and self.q_block < 8:
            raise ValueError(
                f"WireConfig: q_block must be None or >= 8 (the f32 "
                f"scale overhead is 4/q_block bytes per element), got "
                f"{self.q_block!r}")

    @property
    def feedback_armed(self) -> bool:
        return self.compress == "int8" and self.error_feedback


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """One leaf's location inside a bucket's flat per-device payload."""

    index: int            # position in the caller's flat leaf list
    offset: int           # element offset into the bucket payload
    size: int             # payload elements (per device)
    shape: tuple          # per-device payload shape (``rest``)


@dataclasses.dataclass(frozen=True)
class Bucket:
    """A dtype-homogeneous pack of leaves reduced as one flat buffer."""

    dtype: str            # numpy dtype name — the grouping key
    slots: tuple          # tuple[LeafSlot, ...], ascending offsets
    pad: int              # zero elements appended so elems % n == 0

    @property
    def elems(self) -> int:
        last = self.slots[-1]
        return last.offset + last.size + self.pad

    @property
    def payload_bytes(self) -> int:
        return (self.elems - self.pad) * jnp.dtype(self.dtype).itemsize


def plan_buckets(leaves, n: int,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> list[Bucket]:
    """Greedy same-dtype packing of stacked ``(n, *rest)`` leaves.

    Leaves keep their original order within a dtype group; a group's
    open bucket closes when the next leaf would push its per-device
    payload past ``bucket_bytes`` (so a single oversize leaf gets its
    own bucket, and a leaf that would straddle the target starts the
    next bucket instead of splitting). Every bucket's payload is
    zero-padded to a multiple of ``n`` so the scatter and int8 paths
    are always shape-eligible — the per-leaf eligibility lottery
    (``rest[0] % n``) disappears at the bucket level.
    """
    out: list[Bucket] = []
    open_slots: dict[str, list[LeafSlot]] = {}
    open_bytes: dict[str, int] = {}

    def close(dt: str) -> None:
        slots = open_slots.pop(dt, [])
        if slots:
            total = slots[-1].offset + slots[-1].size
            out.append(Bucket(dt, tuple(slots), (-total) % n))
        open_bytes.pop(dt, None)

    for i, leaf in enumerate(leaves):
        shape = tuple(leaf.shape)
        if not shape or shape[0] != n:
            raise ValueError(
                f"plan_buckets: leaf {i} shape {shape} must lead with "
                f"the contribution axis (size {n})")
        dt = jnp.dtype(leaf.dtype).name
        size = 1
        for d in shape[1:]:
            size *= int(d)
        nbytes = size * jnp.dtype(dt).itemsize
        if dt in open_slots and open_bytes[dt] + nbytes > bucket_bytes:
            close(dt)
        slots = open_slots.setdefault(dt, [])
        off = (slots[-1].offset + slots[-1].size) if slots else 0
        slots.append(LeafSlot(i, off, size, shape[1:]))
        open_bytes[dt] = open_bytes.get(dt, 0) + nbytes
    for dt in list(open_slots):
        close(dt)
    return out


def _bucket_wire(bucket: Bucket, op: str, compress: str | None,
                 int8_min_bytes: int) -> str | None:
    """Resolve a bucket's wire format. Non-float buckets always ride
    exact (step counters must not round-trip through bf16/int8 — the
    caller opted into float loss only); int8 additionally needs a
    sum/mean op and enough payload to amortize the quantize legs."""
    if compress is None:
        return None
    if not jnp.issubdtype(jnp.dtype(bucket.dtype), jnp.floating):
        return None
    if compress == "bf16":
        return "bf16"
    # max(..., 1): a zero-element bucket must never quantize — the
    # blockwise kernel's chunk math divides by the block size.
    if op in ("sum", "mean") and \
            bucket.payload_bytes >= max(int8_min_bytes, 1):
        return "int8"
    return None


def _unpack(red, slots):
    """Slice a reduced flat buffer back into leaf views (static offsets
    — XLA fuses these with the collective's output)."""
    return tuple(red[s.offset:s.offset + s.size].reshape(s.shape)
                 for s in slots)


def _slot_offsets(shapes: tuple) -> list:
    """Contiguous :class:`LeafSlot` layout for per-device payload
    ``shapes`` — the ONE offset computation every fused bucket program
    (allreduce, reduce-scatter, the zero shard-apply) unpacks with, so
    the flat layout cannot drift between them."""
    offs = []
    off = 0
    for s in shapes:
        size = 1
        for d in s:
            size *= int(d)
        offs.append(LeafSlot(0, off, size, s))
        off += size
    return offs


def _pack_flat(locals_, pad: int):
    """Squeeze the stacked dim off each per-device leaf, flatten,
    concatenate, and zero-pad to the bucket's padded length — the ONE
    packing both fused bucket programs (allreduce and reduce-scatter)
    share, so the wire layouts cannot drift."""
    parts = [jnp.squeeze(x, axis=0).reshape(-1) for x in locals_]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


@functools.lru_cache(maxsize=512)
def _bucket_all_reduce_fn(mesh: Mesh, axis: str, op: str, shapes: tuple,
                          dtype: str, pad: int, wire: str | None,
                          restore: bool,
                          q_block: int | None = DEFAULT_QUANT_BLOCK,
                          ef: bool = False):
    """One fused program: pack → (quantize?) → allreduce → unpack.

    ``shapes``: per-device payload shapes of the bucket's leaves, in
    slot order. The whole thing is a single jit'd shard_map, so the
    bucket costs ONE collective launch (two wire legs under int8)
    regardless of leaf count.

    ``ef`` (int8 wire only): the program takes a second set of stacked
    per-leaf residual operands, adds them into the contribution before
    quantizing, and returns updated residuals (stacked layout) after
    the reduced leaves — error feedback fused into the same launch.
    """
    in_specs = tuple(P(axis, *(None,) * len(s)) for s in shapes)
    out_specs = tuple(P(*(None,) * len(s)) for s in shapes)
    if ef:
        in_specs = in_specs + in_specs
        out_specs = out_specs + tuple(
            P(axis, *(None,) * len(s)) for s in shapes)
    offs = _slot_offsets(shapes)

    def store_push(*locals_):
        flat = _pack_flat(locals_[:len(shapes)], pad)
        if wire == "int8":
            res = _pack_flat(locals_[len(shapes):], pad) if ef else None
            red, new_res = _int8_all_reduce_body(flat, axis, op,
                                                 q_block, res)
        else:
            new_res = None
            w = flat.astype(jnp.bfloat16) if wire == "bf16" else flat
            if op == "sum":
                red = lax.psum(w, axis)
            elif op == "mean":
                red = lax.pmean(w, axis)
            elif op == "max":
                red = lax.pmax(w, axis)
            else:
                red = lax.pmin(w, axis)
        # Restore the leaf dtype only when a wire compression was
        # REQUESTED (per-leaf push semantics): the exact path returns
        # whatever the lax op produces (pmean promotes ints to float).
        if restore:
            red = red.astype(jnp.dtype(dtype))
        out = _unpack(red, offs)
        if not ef:
            return out
        # ef is armed only for int8 buckets (the stream layer's
        # contract) — the body always produced a residual. Zeroing a
        # missing one here would silently WIPE carried error, so fail
        # loudly at trace time instead.
        assert new_res is not None, "ef requires the int8 wire"
        return out + tuple(r[None] for r in _unpack(new_res, offs))

    return jax.jit(shard_map(store_push, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


@functools.lru_cache(maxsize=512)
def _bucket_reduce_scatter_fn(mesh: Mesh, axis: str, op: str,
                              shapes: tuple, dtype: str, pad: int,
                              wire: str | None, restore: bool,
                              q_block: int | None = DEFAULT_QUANT_BLOCK,
                              ef: bool = False):
    """Pack → (quantize?) → reduce-scatter; each device keeps one flat
    ``elems/n`` shard of the bucket (half the allreduce's ICI bytes).

    ``ef`` (int8 wire only): the program takes stacked per-leaf
    error-feedback residual operands, folds them into the contribution
    before quantizing, and returns the new residuals (the phase-1
    quantization error — the scatter has no all_gather leg, so each
    replica owns the error of its WHOLE contribution and cancels it in
    the next step's reduction) after the scattered shard."""
    in_specs = tuple(P(axis, *(None,) * len(s)) for s in shapes)
    out_specs = P(axis)
    if ef:
        in_specs = in_specs + in_specs
        out_specs = (P(axis),) + tuple(
            P(axis, *(None,) * len(s)) for s in shapes)
    offs = _slot_offsets(shapes)

    def store_push_scatter(*locals_):
        flat = _pack_flat(locals_[:len(shapes)], pad)
        if wire == "int8":
            if ef:
                flat = flat.astype(jnp.float32) + _pack_flat(
                    locals_[len(shapes):], pad).astype(jnp.float32)
            shard, err = _int8_phase1(flat, axis, op, q_block)
        else:
            err = None
            w = flat.astype(jnp.bfloat16) if wire == "bf16" else flat
            shard = lax.psum_scatter(w, axis, scatter_dimension=0,
                                     tiled=True)
            if op == "mean":
                shard = shard / axis_size(axis)
        if restore:
            shard = shard.astype(jnp.dtype(dtype))
        if not ef:
            return shard
        # ef is armed only on int8 buckets (the stream layer's
        # contract): a missing residual here would mean carried error
        # silently wiped — fail loudly at trace time.
        assert err is not None, "ef requires the int8 wire"
        new_res = err.reshape(flat.shape).astype(jnp.dtype(dtype))
        return (shard,) + tuple(r[None] for r in _unpack(new_res, offs))

    return jax.jit(shard_map(store_push_scatter, mesh=mesh,
                             in_specs=in_specs, out_specs=out_specs,
                             check_vma=False))


# --------------------------------------- hierarchical (2-D) programs
#
# Real fleets are hierarchical: fast ICI inside a pod (the topology's
# ``inner`` axis), slow DCN between pods (``outer``). The flat ring
# over a 2-D layout crosses domains on ~every hop, so it prices the
# WHOLE payload at the slow leg. The hierarchical decomposition
# (PAPERS.md arXiv 1909.09756) reduce-scatters inside the fast domain,
# exchanges only 1/n_inner of the bytes across the slow leg, and
# allgathers back out — with the int8+EF wire resolved PER LEG
# (EQuARX: quantize the slow hop harder). Error feedback follows the
# flat paths' ownership discipline, per leg:
#
# - the INNER residual is the producer's own phase-1 quantization
#   error across its whole contribution (plus, on the allreduce, its
#   share of the gather-leg error at its own chunk offset — divided by
#   n_outer since every domain's copy of that chunk folds the same
#   deterministic error);
# - the OUTER residual is the error of quantizing the inner-RS chunk
#   this device carries into the cross-domain exchange — it re-owns
#   the same chunk next step, so adding it back pre-quantize cancels
#   it in the next reduction. It is a per-bucket FLAT vector (chunk
#   boundaries cut across leaf slots), keyed per bucket by callers.


def _hier_allreduce_body(flat, ni: int, no: int, op: str,
                         wire_in, wire_out, qb_in, qb_out,
                         res_in, res_out):
    """Three legs on one device's flat contribution ``flat`` (length
    ``E``, ``E % (ni*no) == 0``): inner reduce-scatter → outer
    exchange of the ``E/ni`` chunk → inner allgather. Size-1 legs are
    skipped (identity), so every (outer, inner) factorization lowers
    through the same body. Returns ``(out (E,), new_res_in (E,) |
    None, new_res_out (E/ni,) | None)``; all error terms live in sum
    space (mean divides at the very end, like the flat bodies)."""
    E = flat.shape[0]
    c1 = E // ni
    quant = wire_in == "int8" or wire_out == "int8"
    xf = flat.astype(jnp.float32) if quant else flat
    if res_in is not None:
        xf = xf + res_in.astype(jnp.float32)
    # -- leg 1: reduce-scatter inside the fast inner domain.
    new_res_in = None
    if ni == 1:
        red = xf
    elif wire_in == "int8":
        red, err1 = _int8_phase1(xf, INNER_AXIS, "sum", qb_in)
        if res_in is not None:
            new_res_in = err1.reshape(xf.shape)
    else:
        w = xf.astype(jnp.bfloat16) if wire_in == "bf16" else xf
        red = lax.psum_scatter(w, INNER_AXIS, scatter_dimension=0,
                               tiled=True)
        if wire_in == "bf16":
            red = red.astype(xf.dtype)
    # -- leg 2: exchange only this 1/ni chunk across the slow leg.
    new_res_out = None
    if no > 1:
        if wire_out == "int8":
            red, new_res_out = _int8_all_reduce_body(
                red, OUTER_AXIS, "sum", qb_out, res_out)
        else:
            w = red.astype(jnp.bfloat16) if wire_out == "bf16" else red
            red = lax.psum(w, OUTER_AXIS)
            if wire_out == "bf16":
                red = red.astype(xf.dtype)
    # -- leg 3: allgather the reduced chunk back out, fast leg again.
    if ni == 1:
        out = red
    elif wire_in == "int8":
        q2, s2 = _q_int8_blockwise(red[None], qb_in)
        err3 = red - _dq_int8_blockwise(q2, s2, c1)[0]
        qg = lax.all_gather(q2[0], INNER_AXIS)
        sg = lax.all_gather(s2[0], INNER_AXIS)
        out = _dq_int8_blockwise(qg, sg, c1).reshape(xf.shape)
        if new_res_in is not None:
            # Every domain holds an identical copy of this chunk and
            # folds the same deterministic gather error — divide by
            # n_outer so the next step's sum corrects it exactly once.
            idx = lax.axis_index(INNER_AXIS)
            mine = lax.dynamic_slice(new_res_in, (idx * c1,), (c1,)) \
                + err3 / no
            new_res_in = lax.dynamic_update_slice(new_res_in, mine,
                                                  (idx * c1,))
    else:
        w = red.astype(jnp.bfloat16) if wire_in == "bf16" else red
        out = lax.all_gather(w, INNER_AXIS, tiled=True)
        if wire_in == "bf16":
            out = out.astype(xf.dtype)
    if op == "mean":
        out = out / (ni * no)
    return out, new_res_in, new_res_out


def _hier_reduce_scatter_body(flat, ni: int, no: int, op: str,
                              wire_in, wire_out, qb_in, qb_out,
                              res_in, res_out):
    """The scatter half of :func:`_hier_allreduce_body` (no gather
    leg): inner reduce-scatter, then outer reduce-scatter of the
    ``E/ni`` chunk. Chunk ordering matches the flat composite-axis
    reduce-scatter exactly, so ZeRO's flat shards ride unchanged.
    Returns ``(shard (E/(ni*no),), new_res_in, new_res_out)``."""
    quant = wire_in == "int8" or wire_out == "int8"
    xf = flat.astype(jnp.float32) if quant else flat
    if res_in is not None:
        xf = xf + res_in.astype(jnp.float32)
    new_res_in = None
    if ni == 1:
        red = xf
    elif wire_in == "int8":
        red, err1 = _int8_phase1(xf, INNER_AXIS, "sum", qb_in)
        if res_in is not None:
            new_res_in = err1.reshape(xf.shape)
    else:
        w = xf.astype(jnp.bfloat16) if wire_in == "bf16" else xf
        red = lax.psum_scatter(w, INNER_AXIS, scatter_dimension=0,
                               tiled=True)
        if wire_in == "bf16":
            red = red.astype(xf.dtype)
    new_res_out = None
    if no > 1:
        if wire_out == "int8":
            rf = red.astype(jnp.float32)
            if res_out is not None:
                rf = rf + res_out.astype(jnp.float32)
            shard, err_o = _int8_phase1(rf, OUTER_AXIS, "sum", qb_out)
            if res_out is not None:
                new_res_out = err_o.reshape(rf.shape)
        else:
            w = red.astype(jnp.bfloat16) if wire_out == "bf16" else red
            shard = lax.psum_scatter(w, OUTER_AXIS,
                                     scatter_dimension=0, tiled=True)
            if wire_out == "bf16":
                shard = shard.astype(xf.dtype)
    else:
        shard = red
    if op == "mean":
        shard = shard / (ni * no)
    return shard, new_res_in, new_res_out


@functools.lru_cache(maxsize=512)
def _hier_bucket_all_reduce_fn(mesh: Mesh, op: str, shapes: tuple,
                               dtype: str, pad: int,
                               wire_in, wire_out, restore: bool,
                               qb_in, qb_out,
                               ef_in: bool = False,
                               ef_out: bool = False):
    """Hierarchical counterpart of :func:`_bucket_all_reduce_fn`: ONE
    fused program per bucket over the 2-D mesh — inner reduce-scatter,
    outer exchange, inner allgather, with per-leg wire formats and
    per-leg error-feedback operands. Operand order: ``*leaves``
    (stacked over the composite axis), then stacked inner residuals
    when ``ef_in``, then the flat outer residual (global ``(n * E/ni,)``
    f32, sharded over the composite axis) when ``ef_out``. Outputs
    mirror: reduced leaves, new inner residuals, new outer residual."""
    ax = (INNER_AXIS, OUTER_AXIS)
    ni = int(mesh.shape[INNER_AXIS])
    no = int(mesh.shape[OUTER_AXIS])
    stacked = tuple(P(ax, *(None,) * len(s)) for s in shapes)
    in_specs = stacked
    out_specs = tuple(P(*(None,) * len(s)) for s in shapes)
    if ef_in:
        in_specs = in_specs + stacked
        out_specs = out_specs + stacked
    if ef_out:
        in_specs = in_specs + (P(ax),)
        out_specs = out_specs + (P(ax),)
    offs = _slot_offsets(shapes)
    k = len(shapes)

    def store_push_hier(*locals_):
        flat = _pack_flat(locals_[:k], pad)
        pos = k
        res_in = None
        if ef_in:
            res_in = _pack_flat(locals_[pos:pos + k], pad)
            pos += k
        res_out = locals_[pos] if ef_out else None
        out, nri, nro = _hier_allreduce_body(
            flat, ni, no, op, wire_in, wire_out, qb_in, qb_out,
            res_in, res_out)
        if restore:
            out = out.astype(jnp.dtype(dtype))
        outs = _unpack(out, offs)
        if ef_in:
            # ef_in is armed only with the int8 inner leg (the stream
            # layer's contract) — a missing residual would silently
            # wipe carried error; fail loudly at trace time.
            assert nri is not None, "ef_in requires the int8 inner leg"
            outs = outs + tuple(
                r[None] for r in _unpack(
                    nri.astype(jnp.dtype(dtype)), offs))
        if ef_out:
            assert nro is not None, "ef_out requires the int8 outer leg"
            outs = outs + (nro.astype(jnp.float32),)
        return outs

    return jax.jit(shard_map(store_push_hier, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


@functools.lru_cache(maxsize=512)
def _hier_bucket_reduce_scatter_fn(mesh: Mesh, op: str, shapes: tuple,
                                   dtype: str, pad: int,
                                   wire_in, wire_out, restore: bool,
                                   qb_in, qb_out,
                                   ef_in: bool = False,
                                   ef_out: bool = False):
    """Hierarchical counterpart of :func:`_bucket_reduce_scatter_fn`:
    inner reduce-scatter then outer reduce-scatter of the chunk —
    each device ends with the SAME flat ``elems/n`` shard the flat
    composite-axis scatter would give it (ZeRO consumes it
    unchanged). Same operand/result order as the hier allreduce,
    with the scattered flat shard first."""
    ax = (INNER_AXIS, OUTER_AXIS)
    ni = int(mesh.shape[INNER_AXIS])
    no = int(mesh.shape[OUTER_AXIS])
    stacked = tuple(P(ax, *(None,) * len(s)) for s in shapes)
    in_specs = stacked
    out_specs: tuple = (P(ax),)
    if ef_in:
        in_specs = in_specs + stacked
        out_specs = out_specs + stacked
    if ef_out:
        in_specs = in_specs + (P(ax),)
        out_specs = out_specs + (P(ax),)
    offs = _slot_offsets(shapes)
    k = len(shapes)

    def store_push_scatter_hier(*locals_):
        flat = _pack_flat(locals_[:k], pad)
        pos = k
        res_in = None
        if ef_in:
            res_in = _pack_flat(locals_[pos:pos + k], pad)
            pos += k
        res_out = locals_[pos] if ef_out else None
        shard, nri, nro = _hier_reduce_scatter_body(
            flat, ni, no, op, wire_in, wire_out, qb_in, qb_out,
            res_in, res_out)
        if restore:
            shard = shard.astype(jnp.dtype(dtype))
        outs = (shard,)
        if ef_in:
            assert nri is not None, "ef_in requires the int8 inner leg"
            outs = outs + tuple(
                r[None] for r in _unpack(
                    nri.astype(jnp.dtype(dtype)), offs))
        if ef_out:
            assert nro is not None, "ef_out requires the int8 outer leg"
            outs = outs + (nro.astype(jnp.float32),)
        return outs if len(outs) > 1 else outs[0]

    return jax.jit(shard_map(store_push_scatter_hier, mesh=mesh,
                             in_specs=in_specs,
                             out_specs=(out_specs if ef_in or ef_out
                                        else out_specs[0]),
                             check_vma=False))


def _wire_scale(wire, q_block, itemsize: int) -> float:
    """Bytes-on-the-wire multiplier of a leg's format vs the bucket's
    native dtype (f32 scale overhead included for int8)."""
    if wire == "bf16":
        return 2.0 / itemsize
    if wire == "int8":
        qb = q_block if q_block else DEFAULT_QUANT_BLOCK
        return (1.0 + 4.0 / qb) / itemsize
    return 1.0


def _resolve_leg_wires(topo: Topology, bucket: Bucket, op: str,
                       compress, int8_min_bytes, q_block):
    """Per-leg wire resolution for one bucket: the topology's leg
    policy overrides the caller's flat setting, then the bucket-level
    eligibility gate (:func:`_bucket_wire`) applies per leg, and
    size-1 legs are forced exact (their collectives are skipped)."""
    c_in, qb_in = topo.resolve_leg(INNER_AXIS, compress, q_block)
    c_out, qb_out = topo.resolve_leg(OUTER_AXIS, compress, q_block)
    wire_in = _bucket_wire(bucket, op, c_in, int8_min_bytes)
    wire_out = _bucket_wire(bucket, op, c_out, int8_min_bytes)
    if int(topo.n_inner) == 1:
        wire_in = None
    if int(topo.n_outer) == 1:
        wire_out = None
    restore = c_in is not None or c_out is not None
    return wire_in, wire_out, qb_in, qb_out, restore


def _count_leg_bytes(topo: Topology, bucket: Bucket, kind: str,
                     wire_in, wire_out, qb_in, qb_out) -> None:
    """Analytic per-leg wire-byte accounting for one hierarchical
    bucket launch — the metrics family ``obs topo`` and the bench
    read. Bytes are per device, scaled by each leg's wire format."""
    from ptype_tpu.metrics import metrics

    itemsize = jnp.dtype(bucket.dtype).itemsize
    legs = topo.leg_bytes(bucket.elems * itemsize, kind)
    inner = legs["inner"] * _wire_scale(wire_in, qb_in, itemsize)
    outer = legs["outer"] * _wire_scale(wire_out, qb_out, itemsize)
    metrics.counter("collectives.leg_bytes.inner").add(int(inner))
    metrics.counter("collectives.leg_bytes.outer").add(int(outer))
    metrics.counter("collectives.leg_bytes.flat_outer").add(
        int(legs["flat_outer"]))
    metrics.counter("collectives.hier_launches").add(1)


def _seed_outer_residual(outer_residuals, bi: int, want: tuple,
                         mesh: Mesh):
    """Pop bucket ``bi``'s flat outer-leg residual from the caller's
    dict (zeros when absent or shape-stale — a replan changed the
    bucket) and place it sharded over the composite axis."""
    ax = (INNER_AXIS, OUTER_AXIS)
    r = outer_residuals.get(bi)
    if r is None or tuple(r.shape) != want:
        r = jnp.zeros(want, jnp.float32)
    return jax.device_put(r, NamedSharding(mesh, P(ax)))


def bucketed_reduce_scatter_stream(leaves, mesh: Mesh,
                                   axis: str = "data", op: str = "sum",
                                   *,
                                   bucket_bytes: int =
                                   DEFAULT_BUCKET_BYTES,
                                   compress: str | None = None,
                                   int8_min_bytes: int =
                                   INT8_MIN_BUCKET_BYTES,
                                   q_block: int | None =
                                   DEFAULT_QUANT_BLOCK,
                                   residuals: list | None = None,
                                   topology: Topology | None = None,
                                   outer_residuals: dict | None = None):
    """Reduce-scatter counterpart of :func:`bucketed_all_reduce_stream`
    — the gradient leg of the ZeRO-style sharded weight update
    (parallel/zero.py): one fused reduce-scatter per bucket, yielding
    ``(bucket, flat_shard, new_residuals_by_slot | None)`` right after
    the dispatch. ``flat_shard`` is the bucket's reduced flat
    ``(elems,)`` buffer sharded ``P(axis)`` — each device holds its
    contiguous ``elems/n`` shard, half the allreduce's wire bytes and
    exactly the resident form the shard-local optimizer consumes.

    ``residuals``: per-leaf stacked error-feedback residuals aligned
    with ``leaves`` (None entries seed zeros); they engage only on
    buckets whose wire resolves to int8, like the allreduce stream.

    ``topology``: a hierarchical :class:`Topology` routes every bucket
    through the 2-leg decomposition (``axis`` must be the composite
    ``("inner", "outer")`` tuple on the topology's mesh); the shard
    layout is IDENTICAL to the flat path's, so consumers don't change.
    ``outer_residuals``: mutable per-bucket dict of outer-leg EF flats
    — read for the seed, updated in place after each dispatch (leaf
    slots can't carry them: chunk boundaries cut across slots).
    """
    if op not in ("sum", "mean"):
        raise ValueError(
            f"bucketed_reduce_scatter: op must be 'sum' or 'mean', "
            f"got {op!r}")
    if compress not in (None, "bf16", "int8"):
        raise ValueError(
            f"bucketed_reduce_scatter: unknown compression {compress!r}")
    leaves = [jnp.asarray(x) for x in leaves]
    n = axis_n(mesh, axis)
    buckets = plan_buckets(leaves, n, bucket_bytes)
    placed = _place_stacked(leaves, mesh, axis)
    for bi, b in enumerate(buckets):
        if topology is not None:
            wire_in, wire_out, qb_in, qb_out, restore = \
                _resolve_leg_wires(topology, b, op, compress,
                                   int8_min_bytes, q_block)
            ef_in = wire_in == "int8" and residuals is not None
            ef_out = (wire_out == "int8"
                      and outer_residuals is not None)
            fn = _hier_bucket_reduce_scatter_fn(
                mesh, op, tuple(s.shape for s in b.slots), b.dtype,
                b.pad, wire_in, wire_out, restore, qb_in, qb_out,
                ef_in, ef_out)
            args = [placed[s.index] for s in b.slots]
            if ef_in:
                args += _place_stacked(
                    [residuals[s.index]
                     if residuals[s.index] is not None
                     and tuple(residuals[s.index].shape)
                     == tuple(leaves[s.index].shape)
                     else jnp.zeros_like(leaves[s.index])
                     for s in b.slots], mesh, axis)
            if ef_out:
                args.append(_seed_outer_residual(
                    outer_residuals, bi,
                    (b.elems * int(topology.n_outer),), mesh))
            outs = fn(*args)
            _count_launch()
            _count_leg_bytes(topology, b, "reduce_scatter",
                             wire_in, wire_out, qb_in, qb_out)
            if ef_out:
                outer_residuals[bi] = outs[-1]
                outs = outs[:-1]
            if ef_in:
                yield b, outs[0], list(outs[1:])
            elif ef_out:
                yield b, outs[0], None
            else:
                yield b, outs, None
            continue
        wire = _bucket_wire(b, op, compress, int8_min_bytes)
        ef = wire == "int8" and residuals is not None
        fn = _bucket_reduce_scatter_fn(
            mesh, axis, op, tuple(s.shape for s in b.slots), b.dtype,
            b.pad, wire, compress is not None, q_block, ef)
        args = [placed[s.index] for s in b.slots]
        if ef:
            args += _place_stacked(
                [residuals[s.index]
                 if residuals[s.index] is not None
                 and tuple(residuals[s.index].shape)
                 == tuple(leaves[s.index].shape)
                 else jnp.zeros_like(leaves[s.index])
                 for s in b.slots], mesh, axis)
        outs = fn(*args)
        _count_launch()
        if ef:
            yield b, outs[0], list(outs[1:])
        else:
            yield b, outs, None


def _count_launch(n: int = 1) -> None:
    from ptype_tpu.metrics import metrics

    metrics.counter("collectives.bucket_launches").add(n)


def _place_stacked(leaves, mesh: Mesh, axis: str):
    """One batched device_put onto the stacked layout (transfers for
    every leaf dispatch together; a no-op for already-placed grads)."""
    shardings = [NamedSharding(mesh, P(axis, *_rest(x.ndim)))
                 for x in leaves]
    return jax.device_put(leaves, shardings)


def bucketed_all_reduce_stream(leaves, mesh: Mesh, axis: str = "data",
                               op: str = "sum", *,
                               bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                               compress: str | None = None,
                               int8_min_bytes: int = INT8_MIN_BUCKET_BYTES,
                               q_block: int | None = DEFAULT_QUANT_BLOCK,
                               residuals: list | None = None,
                               topology: Topology | None = None,
                               outer_residuals: dict | None = None):
    """Generator core of :func:`bucketed_all_reduce`: dispatches one
    fused collective per bucket and yields
    ``(bucket, reduced_by_slot, new_residuals_by_slot | None)`` right
    after that bucket's dispatch — the T3-style consumption surface
    (PAPERS.md arXiv 2401.16677): a caller can commit / apply the
    optimizer on bucket i while buckets i+1.. are still reducing.
    All results are async jax arrays; nothing here blocks.

    ``residuals``: per-leaf stacked error-feedback residuals aligned
    with ``leaves`` (entries may be None → zeros). Residuals engage
    only on buckets whose wire resolves to int8; other buckets yield
    ``None`` and the caller keeps its residuals untouched.

    ``topology``: a hierarchical :class:`Topology` routes sum/mean
    buckets through the 3-leg decomposition (inner reduce-scatter,
    outer exchange of ``1/n_inner`` of the bytes, inner allgather) —
    ``axis`` must be the composite ``("inner", "outer")`` tuple on the
    topology's mesh; max/min buckets fall back to the flat program
    over the same composite axis (same numerics, no decomposition).
    ``outer_residuals``: mutable per-bucket dict of outer-leg EF
    flats, read for the seed and updated in place per dispatch.
    """
    if op not in _REDUCERS:
        raise ValueError(f"bucketed_all_reduce: op must be one of "
                         f"{_REDUCERS}")
    if compress not in (None, "bf16", "int8"):
        raise ValueError(
            f"bucketed_all_reduce: unknown compression {compress!r}")
    leaves = [jnp.asarray(x) for x in leaves]
    n = axis_n(mesh, axis)
    buckets = plan_buckets(leaves, n, bucket_bytes)
    placed = _place_stacked(leaves, mesh, axis)
    for bi, b in enumerate(buckets):
        if topology is not None and op in ("sum", "mean"):
            wire_in, wire_out, qb_in, qb_out, restore = \
                _resolve_leg_wires(topology, b, op, compress,
                                   int8_min_bytes, q_block)
            ef_in = wire_in == "int8" and residuals is not None
            ef_out = (wire_out == "int8"
                      and outer_residuals is not None)
            fn = _hier_bucket_all_reduce_fn(
                mesh, op, tuple(s.shape for s in b.slots), b.dtype,
                b.pad, wire_in, wire_out, restore, qb_in, qb_out,
                ef_in, ef_out)
            args = [placed[s.index] for s in b.slots]
            if ef_in:
                args += _place_stacked(
                    [residuals[s.index]
                     if residuals[s.index] is not None
                     and tuple(residuals[s.index].shape)
                     == tuple(leaves[s.index].shape)
                     else jnp.zeros_like(leaves[s.index])
                     for s in b.slots], mesh, axis)
            if ef_out:
                args.append(_seed_outer_residual(
                    outer_residuals, bi,
                    (b.elems * int(topology.n_outer),), mesh))
            outs = fn(*args)
            _count_launch()
            _count_leg_bytes(topology, b, "allreduce",
                             wire_in, wire_out, qb_in, qb_out)
            if ef_out:
                outer_residuals[bi] = outs[-1]
                outs = outs[:-1]
            L = len(b.slots)
            yield b, list(outs[:L]), (list(outs[L:]) if ef_in
                                      else None)
            continue
        wire = _bucket_wire(b, op, compress, int8_min_bytes)
        ef = wire == "int8" and residuals is not None
        fn = _bucket_all_reduce_fn(
            mesh, axis, op, tuple(s.shape for s in b.slots), b.dtype,
            b.pad, wire, compress is not None, q_block, ef)
        args = [placed[s.index] for s in b.slots]
        if ef:
            args += _place_stacked(
                [residuals[s.index]
                 if residuals[s.index] is not None
                 and tuple(residuals[s.index].shape)
                 == tuple(leaves[s.index].shape)
                 else jnp.zeros_like(leaves[s.index])
                 for s in b.slots], mesh, axis)
        outs = fn(*args)
        _count_launch()
        L = len(b.slots)
        yield b, list(outs[:L]), (list(outs[L:]) if ef else None)


def bucketed_all_reduce(leaves, mesh: Mesh, axis: str = "data",
                        op: str = "sum", *,
                        bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                        compress: str | None = None,
                        int8_min_bytes: int = INT8_MIN_BUCKET_BYTES,
                        q_block: int | None = DEFAULT_QUANT_BLOCK,
                        residuals: list | None = None,
                        topology: Topology | None = None,
                        outer_residuals: dict | None = None):
    """Allreduce a flat list of stacked leaves through dtype buckets.

    Numerically identical to per-leaf :func:`all_reduce` on the exact
    path (same psum, different operand fusion); under ``compress`` the
    wire format resolves per bucket (:func:`_bucket_wire`) and int8
    payloads carry one scale per ``q_block`` elements. Buckets
    dispatch without any intervening sync, so every bucket's
    collective is in flight before the first result is consumed.

    Returns reduced leaves (shape ``rest``) in input order; when
    ``residuals`` is given, returns ``(reduced, new_residuals)`` where
    ``new_residuals[i]`` is the updated error-feedback residual for
    leaves that rode an int8 bucket and the input residual otherwise.
    """
    out: list = [None] * len(leaves)
    new_res = list(residuals) if residuals is not None else None
    for b, reduced, res in bucketed_all_reduce_stream(
            leaves, mesh, axis, op, bucket_bytes=bucket_bytes,
            compress=compress, int8_min_bytes=int8_min_bytes,
            q_block=q_block, residuals=residuals, topology=topology,
            outer_residuals=outer_residuals):
        for i, (s, r) in enumerate(zip(b.slots, reduced)):
            out[s.index] = r
            if res is not None:
                new_res[s.index] = res[i]
    return out if residuals is None else (out, new_res)


def tree_all_reduce(stacked_tree, mesh: Mesh, axis: str = "data",
                    op: str = "sum", *,
                    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                    compress: str | None = None,
                    int8_min_bytes: int = INT8_MIN_BUCKET_BYTES,
                    q_block: int | None = DEFAULT_QUANT_BLOCK):
    """Bucketed allreduce over a whole pytree of stacked contributions
    — the fused lowering of "push every leaf" (one collective per
    bucket, not per leaf). Returns the tree of reduced leaves."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked_tree)
    reduced = bucketed_all_reduce(
        leaves, mesh, axis, op, bucket_bytes=bucket_bytes,
        compress=compress, int8_min_bytes=int8_min_bytes,
        q_block=q_block)
    return jax.tree_util.tree_unflatten(treedef, reduced)


@dataclasses.dataclass
class ScatteredTree:
    """Result of :func:`tree_reduce_scatter`: per-bucket flat shards.

    Each bucket's reduction lives as a flat ``(elems,)`` array sharded
    over ``axis`` — each device owns ``elems/n`` contiguous elements
    (the ZeRO/FSDP resident form). :meth:`gather` reassembles the full
    tree via one allgather-reshard per bucket.

    This flat-bucket layout is the repo's ONE resident sharded form
    (ISSUE 17): grads here, Adam moments and ZeRO-3 param shards in
    ``zero.ZeroState`` all live as ``(elems,)`` flats over the same
    ``ShardPlan`` slot space. Because slot offsets are replica-count
    independent (only tail pads depend on n), live resharding across
    a survivor set is strip-pad / re-pad / re-place — no layout
    translation (``ZeroState.reshard``).
    """

    treedef: object
    buckets: list          # [(Bucket, flat jax.Array sharded P(axis))]
    mesh: Mesh
    axis: str
    n_leaves: int

    def gather(self):
        """Allgather every bucket and unpack back to the pytree —
        together with the scatter this is the bandwidth-optimal
        allreduce decomposition."""
        flats = jax.device_put(
            [a for _, a in self.buckets],
            [NamedSharding(self.mesh, P())] * len(self.buckets))
        leaves: list = [None] * self.n_leaves
        for (b, _), flat in zip(self.buckets, flats):
            for s, r in zip(b.slots, _unpack(flat, b.slots)):
                leaves[s.index] = r
        return jax.tree_util.tree_unflatten(self.treedef, leaves)


def tree_reduce_scatter(stacked_tree, mesh: Mesh, axis: str = "data",
                        op: str = "sum", *,
                        bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                        compress: str | None = None,
                        int8_min_bytes: int = INT8_MIN_BUCKET_BYTES,
                        q_block: int | None = DEFAULT_QUANT_BLOCK
                        ) -> ScatteredTree:
    """Bucketed reduce-scatter over a pytree: half the allreduce's ICI
    bytes, each device left holding one flat shard per bucket. Pad to
    a multiple of the axis size makes every bucket eligible — no
    per-leaf ``rest[0] % n`` lottery."""
    if op not in ("sum", "mean"):
        raise ValueError(
            f"tree_reduce_scatter: op must be 'sum' or 'mean', got "
            f"{op!r}")
    if compress not in (None, "bf16", "int8"):
        raise ValueError(
            f"tree_reduce_scatter: unknown compression {compress!r}")
    leaves, treedef = jax.tree_util.tree_flatten(stacked_tree)
    leaves = [jnp.asarray(x) for x in leaves]
    n = axis_n(mesh, axis)
    buckets = plan_buckets(leaves, n, bucket_bytes)
    placed = _place_stacked(leaves, mesh, axis)
    shards = []
    for b in buckets:
        fn = _bucket_reduce_scatter_fn(
            mesh, axis, op, tuple(s.shape for s in b.slots), b.dtype,
            b.pad, _bucket_wire(b, op, compress, int8_min_bytes),
            compress is not None, q_block)
        shards.append((b, fn(*[placed[s.index] for s in b.slots])))
        _count_launch()
    return ScatteredTree(treedef, shards, mesh, axis, len(leaves))


# ------------------------------------------------ host-side wire codec
#
# The same block-scaled int8 + error-feedback wire, applied per leaf on
# the HOST side — for gradients that ride a TCP RPC instead of an ICI
# collective (the async param-server push, train/param_server.py).
# Format is codec-marshallable (dicts + arrays), ~4× fewer wire bytes.

_Q8_KEY = "__ptype_q8__"


def quantize_leaf(x, q_block: int | None = DEFAULT_QUANT_BLOCK,
                  residual=None, *, want_residual: bool = True):
    """Block-scaled int8 encoding of one array (+ optional EF residual
    added in before quantizing). Returns ``(wire_dict, new_residual)``;
    non-float arrays pass through unquantized (``new_residual=None``).
    ``want_residual=False`` skips the dequantize+subtract entirely —
    a feedback-disarmed caller must not pay for a residual it
    discards."""
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.floating) or x.size == 0:
        return {_Q8_KEY: 0, "raw": x}, None
    flat = x.astype(jnp.float32).reshape(1, -1)
    if residual is not None and residual.size == x.size:
        flat = flat + residual.reshape(1, -1).astype(jnp.float32)
    q, scale = _q_int8_blockwise(flat, q_block)
    new_res = None
    if want_residual:
        new_res = (flat - _dq_int8_blockwise(q, scale, flat.shape[1])
                   ).reshape(x.shape).astype(x.dtype)
    return {_Q8_KEY: 1, "q": q[0], "s": scale[0],
            "shape": list(x.shape), "dtype": str(x.dtype)}, new_res


def dequantize_leaf(wire: dict):
    """Inverse of :func:`quantize_leaf`."""
    if not wire.get(_Q8_KEY):
        return wire["raw"]
    n = 1
    for d in wire["shape"]:
        n *= int(d)
    out = _dq_int8_blockwise(jnp.asarray(wire["q"])[None],
                             jnp.asarray(wire["s"])[None], n)
    return out.reshape(wire["shape"]).astype(jnp.dtype(wire["dtype"]))


def quantize_tree(tree, q_block: int | None = DEFAULT_QUANT_BLOCK,
                  residuals: list | None = None, *,
                  want_residuals: bool = True):
    """Encode a pytree for the RPC wire: ``({"__ptype_q8_tree__":
    [leaf wires in tree_flatten order]}, new_residuals)``. The
    receiver reassembles with its own treedef
    (:func:`dequantize_tree`) — both ends of a param-server push
    already share the parameter structure."""
    leaves = jax.tree_util.tree_leaves(tree)
    wires, new_res = [], []
    for i, leaf in enumerate(leaves):
        r = residuals[i] if residuals is not None else None
        w, nr = quantize_leaf(leaf, q_block, r,
                              want_residual=want_residuals)
        wires.append(w)
        new_res.append(nr)
    return {"__ptype_q8_tree__": wires}, new_res


def is_quantized_tree(obj) -> bool:
    return isinstance(obj, dict) and "__ptype_q8_tree__" in obj


def dequantize_tree(obj, treedef):
    """Decode :func:`quantize_tree` output back into ``treedef``."""
    leaves = [dequantize_leaf(w) for w in obj["__ptype_q8_tree__"]]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def measure_allreduce_gbps(mesh: Mesh, axis: str = "data",
                           mbytes: int = 64, iters: int = 10) -> float:
    """Measured algorithmic allreduce bandwidth (GB/s) over ``axis`` — the
    BASELINE.md "Store push/pull collective bandwidth" metric."""
    import time

    n = axis_n(mesh, axis)
    elems = mbytes * 1024 * 1024 // 4
    # Pre-place the input in the collective's layout so the timed loop
    # measures only the compiled allreduce, not a per-iteration reshard.
    x = jax.device_put(
        jnp.ones((n, elems), jnp.float32),
        NamedSharding(mesh, P(axis, None)),
    )
    fn = _all_reduce_fn(mesh, axis, 2, "sum")
    fn(x).block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    # Ring allreduce moves 2*(n-1)/n of the buffer per device.
    bytes_moved = 2 * (n - 1) / n * elems * 4
    return bytes_moved / dt / 1e9


def measure_wire_gbps(mesh: Mesh, axis: str = "data", mbytes: int = 32,
                      iters: int = 5,
                      blocks: tuple = (256, 512, 1024)) -> dict:
    """Algorithmic bandwidth of one bucketed allreduce under each wire
    format — fp32 (exact) vs PR 1's per-chunk-scale int8 vs the
    block-scaled int8 wire at several block sizes. The bench.py
    ``store_wire_gbps`` probe and the PERF.md block-size sweep.

    GB/s is app-level (f32 payload bytes reduced per second, ring
    convention 2(n-1)/n), so a wire that spends less time on the same
    payload scores higher whatever bytes it moved. ``wire_bytes_pct``
    is the analytic wire footprint of each int8 format vs fp32."""
    import time

    n = axis_n(mesh, axis)
    elems = mbytes * 1024 * 1024 // 4
    leaf = jax.device_put(
        jnp.ones((n, elems), jnp.float32) * 0.5,
        NamedSharding(mesh, P(axis, None)))
    app_bytes = 2 * (n - 1) / n * elems * 4

    def timed(compress, q_block):
        def run():
            return bucketed_all_reduce(
                [leaf], mesh, axis, "sum", compress=compress,
                int8_min_bytes=0, q_block=q_block)[0]

        run().block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = run()
        out.block_until_ready()
        return round(app_bytes / ((time.perf_counter() - t0) / iters)
                     / 1e9, 3)

    def wire_pct(q_block):
        if q_block is None:
            q_block = elems // n
        return round(100.0 * (elems + elems / q_block * 4)
                     / (elems * 4), 2)

    return {
        "payload_mb": mbytes,
        "fp32_gbps": timed(None, None),
        "int8_chunk_gbps": timed("int8", None),
        "int8_chunk_wire_pct": wire_pct(None),
        "int8_block_gbps": {str(b): timed("int8", b) for b in blocks},
        "int8_block_wire_pct": {str(b): wire_pct(b) for b in blocks},
    }


def measure_hier_allreduce(topology: Topology | None = None,
                           mbytes: int = 16, iters: int = 5) -> dict:
    """Hierarchical vs flat bucketed allreduce over the SAME composite
    mesh — the ``make hier-bench`` probe (ISSUE 18).

    The flat baseline is the one-launch bucketed program over the
    composite ``("inner", "outer")`` axis; the hierarchical program is
    the 3-leg decomposition (inner reduce-scatter, outer exchange of
    ``1/n_inner`` of the bytes, inner allgather), both at the exact
    wire. On the virtual host mesh every hop is host memory, so the
    measured step times price launch overhead only; the wire
    acceptance is the slow-leg byte counter (``hier_slow_leg_bytes``
    <= ``flat_outer_bytes / n_inner``) and the topology's per-leg
    bandwidth model prices the same two programs on the emulated
    ICI/DCN asymmetry (``model_*`` fields)."""
    import time

    from ptype_tpu.metrics import metrics

    if topology is None:
        n = len(jax.devices())
        no = 2 if n % 2 == 0 and n >= 4 else 1
        topology = Topology.emulated_host(no, max(n // no, 1))
    topo = topology
    n = topo.n
    elems = mbytes * 1024 * 1024 // 4
    payload = elems * 4
    mesh, ax = topo.mesh(), topo.flat_axis
    leaf = jax.device_put(jnp.ones((n, elems), jnp.float32) * 0.5,
                          NamedSharding(mesh, P(ax, None)))

    def timed(run):
        run()[0].block_until_ready()  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = run()
        out[0].block_until_ready()
        return round((time.perf_counter() - t0) / iters * 1e3, 3)

    flat_ms = timed(
        lambda: bucketed_all_reduce([leaf], mesh, ax, "sum"))

    def snap():
        c = metrics.snapshot()["counters"]
        keys = ("leg_bytes.inner", "leg_bytes.outer",
                "leg_bytes.flat_outer", "hier_launches")
        return {k: c.get(f"collectives.{k}", 0) for k in keys}

    base = snap()
    hier_ms = timed(
        lambda: bucketed_all_reduce([leaf], mesh, ax, "sum",
                                    topology=topo))
    d = {k: v - base[k] for k, v in snap().items()}
    launches = max(int(d["hier_launches"]), 1)
    slow = d["leg_bytes.outer"] / launches
    flat_outer = d["leg_bytes.flat_outer"] / launches
    model_flat = topo.flat_allreduce_ms(payload)
    model_hier = topo.hier_allreduce_ms(payload)
    return {
        "geometry": topo.describe(),
        "payload_mb": mbytes,
        "flat_step_ms": flat_ms,
        "hier_step_ms": hier_ms,
        "hier_slow_leg_bytes": int(slow),
        "hier_inner_leg_bytes": int(d["leg_bytes.inner"] / launches),
        "flat_outer_bytes": int(flat_outer),
        "slow_leg_pct": (round(100.0 * slow / flat_outer, 2)
                         if flat_outer else None),
        "model_flat_ms": round(model_flat, 3),
        "model_hier_ms": round(model_hier, 3),
        "model_speedup": (round(model_flat / model_hier, 2)
                          if model_hier else None),
    }
