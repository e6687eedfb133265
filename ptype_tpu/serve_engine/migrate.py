"""KV-block migration: the quantized wire between serving classes.

Disaggregated serving (ISSUE 16) splits the fleet into prefill-class
and decode-class replicas: a prefill replica fills a prompt's KV
blocks, then migrates the block set to the decode replica that owns
the request for its whole decode lifetime. This module is the wire
between them — and the ONE module in ``serve_engine/`` where KV wire
serialization may live (lint PT021 bars ``quantize_leaf`` /
``dequantize_leaf`` on block banks anywhere else, the same
single-home discipline PT008/PT011 apply to collectives and RNG).

Wire format, by analogy with the training plane: the int8+EF codec
that quantizes gradient collectives (``parallel/collectives.py``,
PR 6 — the EQuARX move, arXiv 2506.17615) quantizes the KV transfer
leg too. Per migrated block:

- ``kv_wire="q8"`` (default): block-scaled int8 with per-block
  error-feedback residuals. The residual stays on the PREFILL side,
  keyed by the block's chain hash — a shared prefix block re-exported
  to a second decode replica carries the previous transfer's
  quantization error folded in, so repeated transfers of the same
  content do not accumulate bias (exactly the EF contract the
  quantized allreduce keeps across steps).
- ``kv_wire="exact"``: raw-dtype passthrough — the bit-exactness
  escape hatch parity tests pin greedy token equality with (int8 is
  lossy; "migrated decode == solo decode" is only a theorem in exact
  mode).

Only blocks the target does not already hold ride the wire: the
transfer manifest is :func:`~ptype_tpu.serve_engine.blocks.
block_hashes`'s chain-hash family (hash i commits to the whole prefix
through block i), so the decode side's content-verified residency
check is exact, and dedup hits are counted, never re-sent.

The pack/unpack programs carry the dispatch-discipline contracts the
rest of the data plane lives by: pack DONATES the residual buffer
(consumed into the pre-quantization sum, replaced by the new error),
unpack DONATES the target bank (scatter-in-place) — both registered
with ``progaudit`` as ``serve.kv_pack`` / ``serve.kv_unpack``
(donation consumed, no callbacks, no f64), and the engine runs them
inside a ``jitwatch.hot_region("serve.migrate")``.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from ptype_tpu.parallel.collectives import (_Q8_KEY, DEFAULT_QUANT_BLOCK,
                                            dequantize_leaf, quantize_leaf)

#: The two wire encodings ``kv_wire`` accepts.
WIRE_MODES = ("q8", "exact")


def _wire_leaf(arr: np.ndarray) -> dict:
    """Codec-safe exact-mode leaf: the socket codec buffers standard
    dtypes only, so a non-native bank dtype (bf16) ships as its raw
    bits + the dtype name; bit-exactness is a view, not a cast."""
    try:
        memoryview(arr)
        return {"raw": arr}
    except (ValueError, TypeError):
        return {"raw": arr.view(np.uint8), "dtype": arr.dtype.name}


def _unwire_leaf(leaf: dict) -> np.ndarray:
    raw = np.ascontiguousarray(leaf["raw"])
    if "dtype" in leaf:
        raw = raw.view(np.dtype(leaf["dtype"]))
    return raw


def make_pack_prog(q_block: int | None = DEFAULT_QUANT_BLOCK):
    """One jitted program quantizing one block of ONE bank for the
    wire: ``(blk, res) -> (q, s, new_res)``. The residual is DONATED —
    consumed into the pre-quantization sum and replaced by the new
    per-block error (the ``serve.kv_pack`` progaudit contract). A
    block's banks go through it one named array at a time, whatever
    the model's cache holds (``transformer.cache_spec``)."""

    def pack(blk, res):
        w, new_res = quantize_leaf(blk, q_block, res)
        return w["q"], w["s"], new_res

    return jax.jit(pack, donate_argnums=(1,))


def make_unpack_prog(block_shape, bank_dtype):
    """One jitted program scattering a quantized block into its target
    bank at ``bid``: ``(bank, q, s, bid) -> bank``. The bank is
    DONATED — the import is a scatter-in-place, never a bank copy (the
    ``serve.kv_unpack`` progaudit contract)."""
    shape = [int(d) for d in block_shape]
    dstr = np.dtype(bank_dtype).name

    def unpack(bank, q, s, bid):
        blk = dequantize_leaf(
            {_Q8_KEY: 1, "q": q, "s": s, "shape": shape, "dtype": dstr})
        return bank.at[:, bid].set(blk.astype(bank.dtype))

    return jax.jit(unpack, donate_argnums=(0,))


def make_unpack_exact_prog():
    """Exact-mode import scatter (no dequantize): ``(bank, blk, bid) ->
    bank``, the bank donated."""

    def unpack(bank, blk, bid):
        return bank.at[:, bid].set(blk.astype(bank.dtype))

    return jax.jit(unpack, donate_argnums=(0,))


def migrate_refusal(what: str) -> str:
    """Why a model with two kinds of cache is not migrated: the wire
    format ships, for each block index, one block of each bank the
    ONE pool holds (``BlockPool.block_shapes``), by one table."""
    return (f"{what} ships one pool's blocks by one table; this "
            f"configuration has window layers beside full ones (two "
            f"pools, and a window table that has given back what lies "
            f"behind the window): pack both kinds' blocks, the window "
            f"layers' last window alone, before it is migrated")


class KVMigrator:
    """Per-engine wire state: the jitted pack/unpack programs plus the
    prefill-side error-feedback residual store.

    ``block_shapes``: name -> ``(L, block_tokens, ...)``, one block of
    each of the pool's banks (``BlockPool.block_shapes()``): the same
    description of what a token holds that the pool allocated from. A
    payload has one entry a name.

    Residuals are keyed by the block's CHAIN hash (content-stable —
    the same key the pool's dedup index and the gateway's prefix
    directory use), bounded by an LRU of ``max_residuals`` blocks; the
    unsealed partial tail block of a prompt has no hash and carries no
    residual (it is exported at most once per request). Thread
    contract: calls come from the engine's RPC handler threads under
    the engine's dispatch lock — the same lock that orders
    bank-donating programs."""

    def __init__(self, block_shapes: dict, bank_dtype, *,
                 q_block: int | None = DEFAULT_QUANT_BLOCK,
                 max_residuals: int = 64):
        self.block_shapes = {n: tuple(int(d) for d in shape)
                             for n, shape in block_shapes.items()}
        self.bank_dtype = np.dtype(bank_dtype)
        self.q_block = q_block
        self.max_residuals = int(max_residuals)
        self._pack = make_pack_prog(q_block)
        self._unpack = {n: make_unpack_prog(shape, bank_dtype)
                        for n, shape in self.block_shapes.items()}
        self._unpack_exact = make_unpack_exact_prog()
        #: hash -> {name: residual}, LRU oldest-first.
        self._res: collections.OrderedDict[int, dict] = \
            collections.OrderedDict()

    # ------------------------------------------------------------- pack

    def pack_block(self, banks: dict, bid: int, h: int | None,
                   mode: str) -> tuple[dict, int]:
        """Encode block ``bid`` of ``banks`` for the wire. Returns
        ``(payload, nbytes)`` — the payload is codec-marshalable
        (numpy leaves only)."""
        if mode not in WIRE_MODES:
            raise ValueError(f"kv_wire must be one of {WIRE_MODES}, "
                             f"got {mode!r}")
        if mode == "exact":
            # device_get, not np.asarray: the engine packs inside an
            # armed hot_region, where only EXPLICIT transfers are
            # legal — the wire hop IS the contract here. One get for
            # all of the block's arrays.
            host = jax.device_get({name: banks[name][:, bid]
                                   for name in self.block_shapes})
            blks = {n: np.ascontiguousarray(a) for n, a in host.items()}
            return ({n: _wire_leaf(a) for n, a in blks.items()},
                    sum(a.nbytes for a in blks.values()))
        res = self._res.pop(h, None) if h is not None else None
        wire, new_res = {}, {}
        for name, shape in self.block_shapes.items():
            r = (res[name] if res is not None
                 else jnp.zeros(shape, self.bank_dtype))
            q, s, new_res[name] = self._pack(banks[name][:, bid], r)
            wire[name] = {"q": q, "s": s}
        if h is not None:
            self._res[h] = new_res
            while len(self._res) > self.max_residuals:
                self._res.popitem(last=False)
        payload = jax.device_get(wire)
        return payload, sum(leaf["q"].nbytes + leaf["s"].nbytes
                            for leaf in payload.values())

    # ----------------------------------------------------------- unpack

    def unpack_block(self, banks: dict, payload: dict, bid: int,
                     mode: str) -> dict:
        """Scatter one wire payload into ``banks`` at ``bid``; returns
        the new banks (the old ones are donated)."""
        out = {}
        for name in self.block_shapes:
            leaf = payload[name]
            if mode == "exact":
                out[name] = self._unpack_exact(
                    banks[name], jnp.asarray(_unwire_leaf(leaf)),
                    jnp.int32(bid))
            else:
                out[name] = self._unpack[name](
                    banks[name], jnp.asarray(leaf["q"]),
                    jnp.asarray(leaf["s"]), jnp.int32(bid))
        return out

    # -------------------------------------------------------- residuals

    def residual_count(self) -> int:
        return len(self._res)
