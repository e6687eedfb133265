"""Block pool: device-resident paged KV storage + content addressing.

Banks of fixed-size blocks back every live sequence on a serving
actor: one ``(L, n_blocks, block_tokens, ...)`` array for each named
per-token array the model says a layer's cache holds
(``transformer.cache_spec``: ``k`` and ``v`` of ``(Kh, Dh)`` for GQA,
the latent ``ckv`` and, behind an indexer, its key ``ki`` for latent
attention).
The pool allocates from that description, the migrator packs by it and
the engine's programs carry the banks as one dict. A model whose
layers do not all keep the same tokens (``transformer.cache_layers``:
full-attention layers keep every token, window layers the last
``window``) has one pool a kind, each with the layers of its kind, its
own tables and its own reservations: the engine admits a row only when
both can hold it, and gives a window layer's blocks back as the row
advances (:meth:`BlockPool.deref` with ``keep_unit``). Sequences hold
*block tables* (ordered block ids); position ``p`` of a sequence lives
in table entry ``p // block_tokens`` at offset ``p % block_tokens``.
Three lifetimes per block:

- **active** (refcount > 0): owned by one or more live sequences —
  prompt blocks shared through prefix reuse carry refcount > 1;
- **cached** (refcount 0, content-hashed): released but kept resident
  in an LRU so a later request with the same prefix re-refs it without
  recomputing prefill — eviction (oldest first) only happens when an
  allocation needs the slot;
- **free**: never written, or evicted.

Admission is deadlock-free by *reservation*: a request reserves its
worst-case block count (``ceil((prompt + max_new) / block_tokens)``)
up front, and every later acquisition — a prefix-reuse ref or a fresh
allocation, including the decode-time boundary crossings — consumes
one reserved unit, so a decode step can never find the pool empty.
``free_blocks()`` (free + cached − reserved) is the admission headroom
the gateway's probes read as ``kv_free_blocks``.

Content addressing uses a hash *chain* over block token contents built
on :func:`ptype_tpu.rpc.fnv32a` — the SAME hash the gateway's
prefix-affinity routing keys on (gateway/pool.py pins
``fnv32a(affinity_key)``), so a request routed to its affinity replica
lands where its prefix blocks are actually resident.
:func:`prefix_affinity_key` derives the routing key from a prompt
(first block's chain hash); 32-bit chains can collide, so the pool
stores each sealed block's token contents and :meth:`BlockPool.lookup`
verifies them — reuse is exact, never probabilistic.

Block 0 is a reserved *trash* block: padded/inactive lanes of the
batched engine step scatter their garbage writes there, so a masked
write can never corrupt a real (possibly shared) block.
"""

from __future__ import annotations

import collections

from ptype_tpu import lockcheck

import jax.numpy as jnp

from ptype_tpu.models import transformer as tfm
from ptype_tpu.rpc import fnv32a

#: Sublane width of the f32 Mosaic tile: block_tokens must divide by
#: it so a (block_tokens, head_dim) block tile is layout-aligned on
#: TPU (the gathers tolerate anything; the lane-aligned bank layout
#: does not).
SUBLANES = 8


def block_hashes(tokens, block_tokens: int) -> list[int]:
    """Chain hashes for every FULL block of ``tokens``: ``h_i`` covers
    tokens ``[0, (i+1)·block_tokens)`` — block i's hash commits to the
    whole prefix through it, so equal hashes mean equal *prefixes*,
    not just equal blocks (the property reuse needs)."""
    out: list[int] = []
    h: int | None = None
    for i in range(len(tokens) // block_tokens):
        blk = tokens[i * block_tokens:(i + 1) * block_tokens]
        body = ",".join(str(int(t)) for t in blk)
        prefix = "" if h is None else f"{h:08x}|"
        h = fnv32a(prefix + body)
        out.append(h)
    return out


def prefix_affinity_key(tokens, block_tokens: int) -> str | None:
    """Gateway affinity key for a prompt: the FIRST full block's chain
    hash, hex-tagged. Keying on the first block (not the longest
    prefix) routes every request sharing ≥ one block to the same
    replica — the block-granular sharing the pool can actually serve.
    None when the prompt has no full block (nothing reusable)."""
    hs = block_hashes(tokens[:block_tokens], block_tokens)
    return f"kv:{hs[0]:08x}" if hs else None


class BlockPool:
    """Ref-counted, content-addressed pool of KV blocks on device,
    for the layers of ONE kind of cache: all of a model's where every
    layer keeps the same tokens, else ``n_layers`` of them (the full
    layers, or the window layers; the engine holds a pool a kind).

    Thread contract: mutating calls come from the one engine thread;
    :meth:`stats` / :meth:`free_blocks` are read from Info/probe
    threads — all state sits under one lock.
    """

    def __init__(self, cfg: tfm.TransformerConfig, n_blocks: int,
                 block_tokens: int, device=None,
                 n_layers: int | None = None):
        if block_tokens % SUBLANES:
            raise ValueError(
                f"block_tokens {block_tokens} must divide by "
                f"{SUBLANES} (sublane-aligned KV tiles)")
        if n_blocks < 2:
            raise ValueError("n_blocks must be >= 2 (block 0 is the "
                             "reserved trash block)")
        self.block_tokens = int(block_tokens)
        self.n_blocks = int(n_blocks)
        #: name -> per-token shape: what a block holds, from the model.
        self.spec = tfm.cache_spec(cfg)
        #: The banks, one a named array, committed to ``device`` when
        #: one is given. The engine owns these references — jitted
        #: steps/prefills donate the dict and replace it. ``n_layers``:
        #: the layers of this pool's kind, where the model has two.
        self.banks = {
            name: jnp.zeros((n_layers or cfg.n_layers, n_blocks,
                             block_tokens)
                            + tuple(shape), cfg.dtype, device=device)
            for name, shape in self.spec.items()}
        self._lock = lockcheck.lock("serve_engine.pool")
        # Block 0 never allocated: the trash target for masked writes.
        self._free: list[int] = list(range(1, n_blocks))
        #: LRU of refcount-0 hashed blocks (oldest first).
        self._cached: collections.OrderedDict[int, None] = \
            collections.OrderedDict()
        self._ref: dict[int, int] = {}
        self._hash_of: dict[int, int] = {}
        self._by_hash: dict[int, int] = {}
        self._content: dict[int, tuple] = {}
        self._reserved = 0
        self.evictions = 0
        self.sealed = 0

    @property
    def k(self):
        """A GQA model's K bank (``banks["k"]``)."""
        return self.banks["k"]

    @k.setter
    def k(self, bank) -> None:
        self.banks["k"] = bank

    @property
    def v(self):
        return self.banks["v"]

    @v.setter
    def v(self, bank) -> None:
        self.banks["v"] = bank

    def block_shapes(self) -> dict[str, tuple[int, ...]]:
        """name -> ``(L, block_tokens, ...)``: one block of each bank,
        all layers — the unit the migrator packs."""
        return {n: (b.shape[0],) + b.shape[2:]
                for n, b in self.banks.items()}

    # --------------------------------------------------------- capacity

    @property
    def capacity(self) -> int:
        """Allocatable blocks (total minus the trash block)."""
        return self.n_blocks - 1

    def _available(self) -> int:
        return len(self._free) + len(self._cached)

    def free_blocks(self) -> int:
        """Admission headroom: blocks a NEW reservation could still
        claim (free + cached − already reserved)."""
        with self._lock:
            return max(0, self._available() - self._reserved)

    def used_blocks(self) -> int:
        """Blocks held by live sequences (refcount > 0)."""
        with self._lock:
            return len(self._ref)

    def try_reserve(self, n: int) -> bool:
        """Claim ``n`` future acquisitions; False when the pool can't
        cover them (the caller queues or sheds — never dead-ends a
        decode mid-flight)."""
        with self._lock:
            if self._available() - self._reserved < n:
                return False
            self._reserved += n
            return True

    def unreserve(self, n: int) -> None:
        """Return unused reservation units (early stop / retire)."""
        with self._lock:
            self._reserved = max(0, self._reserved - n)

    # ------------------------------------------------------- lifecycle

    def alloc(self) -> int:
        """Materialize one reserved unit into a fresh block id: free
        list first, else evict the LRU cached block (its hash leaves
        the index — the content is about to be overwritten)."""
        with self._lock:
            if self._free:
                bid = self._free.pop()
            elif self._cached:
                bid, _ = self._cached.popitem(last=False)  # LRU
                h = self._hash_of.pop(bid, None)
                if h is not None:
                    self._by_hash.pop(h, None)
                self._content.pop(bid, None)
                self.evictions += 1
            else:
                raise RuntimeError(
                    "block pool exhausted despite reservation — "
                    "reserve/acquire accounting is broken")
            self._ref[bid] = 1
            self._reserved = max(0, self._reserved - 1)
            return bid

    def ref(self, bid: int) -> None:
        """Take a reference on a looked-up block (prefix reuse),
        consuming one reserved unit: a cached block leaves the LRU
        (it is live again); an already-active block just gains a
        holder (and the unit effectively returns to the pool)."""
        with self._lock:
            if self._ref.get(bid, 0) == 0:
                self._cached.pop(bid, None)
                self._ref[bid] = 1
            else:
                self._ref[bid] += 1
            self._reserved = max(0, self._reserved - 1)

    def deref(self, bid: int, keep_unit: bool = False) -> None:
        """Drop one reference. At zero, a hashed block parks in the
        LRU (reusable until evicted); an unhashed one (decode tail)
        frees outright. ``keep_unit``: the holder takes back the
        reserved unit the block consumed — a window layer's row gives
        up the blocks behind its window and will allocate as many
        again ahead of it, so what it reserved at admission (the most
        it ever holds at once) covers its whole life."""
        with self._lock:
            if keep_unit:
                self._reserved += 1
            n = self._ref.get(bid, 0) - 1
            if n > 0:
                self._ref[bid] = n
                return
            self._ref.pop(bid, None)
            if bid in self._hash_of:
                self._cached[bid] = None
                self._cached.move_to_end(bid)
            else:
                self._free.append(bid)

    # ------------------------------------------------- content address

    def seal(self, bid: int, h: int, content) -> None:
        """Publish a fully-written prompt block into the hash index.
        First writer wins: a concurrent recompute of the same prefix
        keeps its private copy unhashed (it frees on deref)."""
        with self._lock:
            if h in self._by_hash:
                return
            self._hash_of[bid] = h
            self._by_hash[h] = bid
            self._content[bid] = tuple(int(t) for t in content)
            self.sealed += 1

    def lookup(self, h: int, content) -> int | None:
        """Resident block for chain hash ``h`` — contents verified, so
        a 32-bit collision is a miss, never silent corruption."""
        with self._lock:
            bid = self._by_hash.get(h)
            if bid is None:
                return None
            want = tuple(int(t) for t in content)
            return bid if self._content.get(bid) == want else None

    # ------------------------------------------------------ inspection

    def stats(self) -> dict:
        with self._lock:
            used = len(self._ref)
            cached = len(self._cached)
            free = len(self._free)
            return {
                "kv_total_blocks": self.capacity,
                "kv_used_blocks": used,
                "kv_cached_blocks": cached,
                "kv_free_blocks": max(0, free + cached - self._reserved),
                "kv_reserved_blocks": self._reserved,
                "kv_evictions": self.evictions,
                "kv_sealed_blocks": self.sealed,
                "kv_util_pct": round(100.0 * used / self.capacity, 2)
                if self.capacity else 0.0,
            }

    def check_invariants(self, spec_rows=()) -> list[str]:
        """Consistency audit for tests: every block in exactly one
        lifetime, index bijective, reservation covered.

        ``spec_rows`` (speculative decoding, ISSUE 12): per-live-row
        ``(pos, nalloc, reserve_left, advance)`` tuples — asserts each
        row's remaining reservation covers its worst-case
        ``advance``-token speculative window (positions
        ``[pos, pos + advance)``, including decode-boundary block
        crossings mid-speculation), so a verify step can never find
        the pool empty. The engine builds these via
        ``PagedGeneratorActor.check_spec_reservations()``."""
        bad: list[str] = []
        bt = self.block_tokens
        for i, (pos, nalloc, reserve_left, advance) in \
                enumerate(spec_rows):
            need = -(-(int(pos) + int(advance)) // bt) - int(nalloc)
            if need > int(reserve_left):
                bad.append(
                    f"row {i}: reservation does not cover a "
                    f"{advance}-token advance from pos {pos} "
                    f"(needs {need} new blocks past its {nalloc} "
                    f"allocated, holds {reserve_left} reserved)")
        with self._lock:
            free, cached, active = (set(self._free), set(self._cached),
                                    set(self._ref))
            if free & cached or free & active or cached & active:
                bad.append("block in two lifetime sets")
            if len(free) + len(cached) + len(active) != self.capacity:
                bad.append(
                    f"lost blocks: {len(free)}+{len(cached)}+"
                    f"{len(active)} != {self.capacity}")
            if any(n <= 0 for n in self._ref.values()):
                bad.append("non-positive refcount")
            for h, bid in self._by_hash.items():
                if self._hash_of.get(bid) != h:
                    bad.append(f"hash index not bijective at {bid}")
            if not set(self._hash_of) >= cached:
                bad.append("cached block without a hash")
            if self._reserved > len(free) + len(cached):
                bad.append("reservation exceeds available blocks")
        return bad
