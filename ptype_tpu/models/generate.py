"""Autoregressive generation — static-shape KV-cache decode.

The reference served request/reply actors (calculator.go); the model
framework's equivalent of "serve a request" is generate-from-prompt.
TPU-first decisions:

- **Static shapes everywhere**: the KV cache is allocated at
  ``max_seq`` up front; the decode loop is a ``lax.scan`` over step
  index with ``dynamic_update_slice`` writes — one compiled program
  regardless of prompt/output length, no retracing.
- **Prefill + decode split**: prefill runs the full-sequence forward
  (MXU-efficient batched matmuls) while collecting per-layer K/V;
  decode steps attend against the cache with a position mask.
- Sampling: greedy or temperature with top-k / top-p (nucleus,
  temperature-first semantics), HF-style repetition penalty, and
  stop-token early stopping (output-masked outside the compiled
  program); RNG is explicit (fold_in per step).
- Ragged serving: ``generate(prompt_lens=...)`` decodes a LEFT-padded
  mixed-length batch in one compiled program — lengths are traced,
  pad keys masked, RoPE offsets per row; greedy rows match their solo
  decode exactly.

Works for any dense ``TransformerConfig`` (MoE generation uses
zero-drop expert capacity — dropping is a training regularizer). GQA
caches only ``kv_heads`` heads.
"""

from __future__ import annotations

from dataclasses import dataclass

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ptype_tpu.models import transformer as tfm


@dataclass(frozen=True)
class KVCache:
    """Stacked per-layer KV: (L, B, Smax, Kh, Dh)."""

    k: jax.Array
    v: jax.Array

    def tree_flatten(self):
        return (self.k, self.v), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    KVCache, KVCache.tree_flatten, KVCache.tree_unflatten
)


def init_cache(cfg: tfm.TransformerConfig, batch: int,
               max_seq: int | None = None) -> KVCache:
    _gqa_one_group(cfg, "the contiguous K,V cache")
    S = max_seq or cfg.max_seq
    shape = (cfg.n_layers, batch, S, cfg.kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


@jax.named_scope("attn")
def _cached_attention(q, k_cache, v_cache, pos_limit, cfg,
                      valid_from=None):
    """q: (B, 1, H, Dh); caches: (B, Smax, Kh, Dh); attend to
    positions < pos_limit. GQA-native: query heads are grouped onto
    their kv head inside the einsum — no ``jnp.repeat``
    materializing H-head caches every decode step (the G=1 MHA case
    is the same einsum).

    ``valid_from`` (B,), optional: per-row first valid cache slot —
    left-padded ragged prompts leave pad rows in slots
    [0, valid_from); they stay masked for the row's whole decode.

    ``pos_limit`` may be a scalar (uniform batch) or (B,) — per-row
    limits are the continuous-batching case, where every slot is at
    its own depth."""
    B, _, H, Dh = q.shape
    Kh = k_cache.shape[2]
    G = H // Kh
    qg = q.reshape(B, 1, Kh, G, Dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                        k_cache).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(Dh))
    cols = jnp.arange(k_cache.shape[1])  # (Smax,)
    pos_limit = jnp.asarray(pos_limit)
    if pos_limit.ndim == 1:
        mask = cols[None, :] < pos_limit[:, None]  # (B, Smax)
    else:
        mask = (cols < pos_limit)[None, :]
    if valid_from is not None:
        mask = mask & (cols[None, :] >= valid_from[:, None])
    scores = jnp.where(mask[:, None, None, None, :], scores,
                       jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_cache)
    return o.reshape(B, 1, H, Dh)


def _head_logits(params, x_last, cfg):
    # One LM-head lowering for train and decode: bf16 operands with f32
    # MXU accumulation (transformer.head_logits), so precision policy
    # can never drift between the two paths.
    return tfm.head_logits(x_last, tfm._head_weight(params, cfg), cfg)


def prefill(params: dict, tokens: jax.Array, cfg: tfm.TransformerConfig,
            cache: KVCache,
            prompt_lens: jax.Array | None = None,
            last_index: jax.Array | None = None
            ) -> tuple[jax.Array, KVCache]:
    """Full-sequence forward, filling cache[:, :, :S]. Returns
    (last-position logits (B, V), cache). Block math is the shared
    transformer pieces (qkv_proj/attn_residual/mlp_residual), so
    training and generation can never diverge.

    ``prompt_lens`` (B,), optional: tokens are LEFT-padded — row i's
    real prompt occupies columns [S - L_i, S). RoPE positions shift
    per row so every prompt starts at position 0, pad keys are masked
    out of attention, and the last column is every row's final real
    token (which is why left-padding is the serving layout).

    ``last_index`` (B,), optional: return logits at these columns
    instead of the last — the RIGHT-padded layout continuous batching
    prefills slots with (each slot's prompt occupies [0, L_i), so its
    final real token sits at column L_i - 1, and decode writes grow
    from L_i, overwriting the never-attended pad garbage)."""
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)
    if prompt_lens is None:
        sin, cos = tfm.rope_tables(cfg, S)
        kv_mask = None
    else:
        pad = S - prompt_lens  # (B,)
        positions = jnp.maximum(
            jnp.arange(S)[None, :] - pad[:, None], 0)
        sin, cos = tfm.rope_tables(cfg, positions=positions)
        kv_mask = jnp.arange(S)[None, :] >= pad[:, None]  # (B, S)

    # MoE: generation prefill always uses ZERO-DROP expert capacity
    # (per-expert bound = T, since each token routes to top_k DISTINCT
    # experts — the same reasoning behind decode_step's capacity=B).
    # Factor-capacity dropping is a TRAINING regularizer; at inference
    # it would (a) silently degrade prompts whose routing concentrates
    # and (b) break batched-equals-solo parity — batch composition
    # would change which tokens drop (left-pad columns, coming first,
    # would even outrank real tokens in token-priority order).
    cap = B * S if cfg.n_experts else None

    # Uniform causal prefill is ordinary full-sequence attention: use
    # the flash kernel when the resolved impl says so (auto → flash on
    # TPU; explicit "flash" also forces the interpret-mode kernel on
    # CPU for tests) — dense prefill pays B·H·S² f32 scores exactly
    # where long-prompt serving hurts. Ragged (kv_mask) prompts keep
    # the masked dense path: the kernel has no kv-mask, which is a
    # different algorithm, not a shape the kernel could be handed. An
    # UNALIGNED length does take the kernel: the block's sequence dim
    # must be lane-aligned (128) and divide S, so q/k/v are
    # right-padded to the next tile here — causal masking keeps every
    # real row from seeing a pad key, so the slice back is exact.
    impl = cfg.attn_impl
    if impl == "auto":
        impl = tfm.default_attn_impl()
    if kv_mask is None and cfg.causal and impl == "flash":
        from ptype_tpu.ops.flash_attention import flash_attention

        tile = 128 if S <= 1024 else 1024
        s_pad = -(-S // tile) * tile - S

        def attn(q, k, v):
            if s_pad:
                pad = ((0, 0), (0, s_pad), (0, 0), (0, 0))
                q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
            return flash_attention(q, k, v, causal=True)[:, :S]
    else:
        def attn(q, k, v):
            return tfm._attention(q, k, v, cfg, kv_mask=kv_mask)

    def body(x, inputs):
        layer, kc, vc = inputs
        q, k, v = tfm.qkv_proj(x, layer, cfg, sin, cos)
        with jax.named_scope("attn"):
            o = attn(q, k, v)
        x = tfm.attn_residual(x, o, layer, cfg)
        x, _aux, _load = tfm.mlp_residual(x, layer, cfg,
                                          moe_capacity=cap)
        with jax.named_scope("kv_write"):
            kc = lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
            vc = lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
        return x, (kc, vc)

    x, (kcs, vcs) = lax.scan(body, x,
                             (params["blocks"], cache.k, cache.v))
    with jax.named_scope("head"):
        x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        x_last = (x[:, -1] if last_index is None
                  else x[jnp.arange(B), last_index])
        logits = _head_logits(params, x_last, cfg)
    return logits, KVCache(kcs, vcs)


def decode_step(params: dict, token: jax.Array, pos: jax.Array,
                cfg: tfm.TransformerConfig, cache: KVCache,
                rope_pos: jax.Array | None = None,
                valid_from: jax.Array | None = None
                ) -> tuple[jax.Array, KVCache]:
    """One decode step. token: (B,) int32 at CACHE slot ``pos``
    (scalar). Returns (logits (B, V), updated cache). MoE capacity is
    pinned to the step's token count (B) so no routed token can drop
    at decode.

    Ragged (left-padded) prompts: ``rope_pos`` (B,) gives each row's
    TOKEN position (cache slot minus its pad) and ``valid_from`` (B,)
    its first real cache slot — slot and position coincide only in the
    uniform-length case."""
    B = token.shape[0]
    with jax.named_scope("embed"):
        x = params["embed"][token][:, None, :].astype(cfg.dtype)  # (B, 1, D)
    if rope_pos is None:
        sin, cos = tfm.rope_tables(cfg, positions=jnp.asarray(pos)[None])
    else:
        sin, cos = tfm.rope_tables(cfg, positions=rope_pos[:, None])

    def body(x, inputs):
        layer, kc, vc = inputs  # kc/vc: (B, Smax, Kh, Dh)
        q, k, v = tfm.qkv_proj(x, layer, cfg, sin, cos)
        with jax.named_scope("kv_write"):
            kc = lax.dynamic_update_slice(kc, k, (0, pos, 0, 0))
            vc = lax.dynamic_update_slice(vc, v, (0, pos, 0, 0))
        o = _cached_attention(q, kc, vc, pos + 1, cfg,
                              valid_from=valid_from)
        x = tfm.attn_residual(x, o, layer, cfg)
        x, _aux, _load = tfm.mlp_residual(x, layer, cfg,
                                          moe_capacity=B)
        return x, (kc, vc)

    x, (kcs, vcs) = lax.scan(body, x,
                             (params["blocks"], cache.k, cache.v))
    with jax.named_scope("head"):
        x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _head_logits(params, x[:, 0], cfg)
    return logits, KVCache(kcs, vcs)


def _paged_attention_gather(q, kc, vc, tables, pos_limit, cfg):
    """Attention through a block table — the XLA gather path of the
    paged serving engine. q: (B, Q, H, Dh); kc/vc: (n_blocks,
    block_tokens, Kh, Dh) bank layers; tables: (B, nb) int32 block ids
    in POSITION order, so the gathered layout is exactly the
    contiguous cache (garbage in never-written / trash-block columns
    is masked, and masked-out columns contribute exact zeros to the
    softmax sums — greedy rows match the contiguous path token for
    token, logits to float32 rounding). The prefill chunk and the
    speculative programs attend through it; the decode step only when
    it is given no block list (:func:`_live_block_attention`).

    ``pos_limit``: (B,) per-row limits (decode, Q=1) or (B, Q)
    per-query limits (chunked prefill: query c attends positions
    ``<= start + c``). Same grouped-GQA einsums as
    :func:`_cached_attention`."""
    B, Q, H, Dh = q.shape
    nb = tables.shape[1]
    bt = kc.shape[1]
    Kh = kc.shape[2]
    with jax.named_scope("kv_gather"):
        ks = kc[tables].reshape(B, nb * bt, Kh, Dh)
        vs = vc[tables].reshape(B, nb * bt, Kh, Dh)
    with jax.named_scope("attn"):
        G = H // Kh
        qg = q.reshape(B, Q, Kh, G, Dh)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                            ks).astype(jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(Dh))
        cols = jnp.arange(nb * bt)
        pos_limit = jnp.asarray(pos_limit)
        if pos_limit.ndim == 1:
            mask = cols[None, None, :] < pos_limit[:, None, None]
        else:  # (B, Q) per-query
            mask = cols[None, None, :] < pos_limit[:, :, None]
        scores = jnp.where(mask[:, None, None, :, :], scores,
                           jnp.float32(-1e30))
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        o = jnp.einsum("bkgqs,bskd->bqkgd", probs, vs)
        return o.reshape(B, Q, H, Dh)


#: Blocks in one tile of the live-block list (4,096 tokens at 16 a
#: block: 8 MB of K, 8 MB of V and 17 MB of float32 scores at mistral's
#: widths and 32 lanes). Chosen on the chip (PERF.md §6, PR 29); a
#: latent list's kernel reads a tile in sub-tiles of its own choosing.
LIVE_TILE_BLOCKS = 256
#: Lanes in one tile of the live-lane list: one float32 sublane tile,
#: so a tile's ``(8, reach)`` indexer scores sort in whole tiles.
#: Chosen on the chip against 16, which is slower at every number of
#: live lanes (PERF.md §6, PR 32).
LIVE_TILE_LANES = 8


def live_block_list(tables, nalloc, active, block_tokens: int,
                    tile: int | None = None, first=None,
                    row_blocks: int | None = None,
                    own_tiles: bool = False):
    """The K,V blocks the live rows hold, as the decode step's
    attention reads them (:func:`_live_block_attention`). Host side,
    numpy: ``tables`` (n_slots, nb) block ids in position order,
    ``nalloc`` (n_slots,) blocks allocated a row, ``active`` (n_slots,)
    bool. Returns ``(blocks, n_tiles)``: ``blocks`` int32 ``(3,
    max_tiles, tile)`` holds, row after row, each allocated block's id,
    its owning lane and the position of its first token, padded with
    the trash block (id 0) under an owner no lane has (``n_slots``);
    ``n_tiles`` int32 is the number of tiles in use, the step's trip
    count. A block two rows share (prefix reuse) is listed once a row.
    ``max_tiles * tile`` covers every lane at its whole reach, so the
    shape never changes while the engine lives.

    A window layer's table (``serve_engine``: the blocks wholly behind
    a row's window are given back) is listed from ``first`` (n_slots,),
    each row's first block still held; ``row_blocks`` is then the most
    a row holds, so the list covers ``n_slots * row_blocks`` blocks and
    not every lane's reach.

    ``own_tiles`` (a list from block 0, without ``first``): each row's
    run starts on a tile (its last tile padded), so that a tile's
    blocks belong to ONE lane, in position order, and the step scores
    that lane's queries alone against it: the list of a cache whose
    one K,V head many query heads share (a latent cache), where a
    lane's queries are a matmul's rows by themselves
    (``ops.latent_block_attention`` reads it: a tile's owner and first
    position from its first entry, its ids a tile at a time)."""
    ns, nb = tables.shape
    row = min(nb, int(row_blocks or nb))
    tile = min(int(tile or LIVE_TILE_BLOCKS), ns * row)
    if own_tiles:
        tile = min(tile, row)
    max_tiles = ns * -(-row // tile) if own_tiles else -(-ns * row // tile)
    held = (np.arange(nb)[None, :] < np.asarray(nalloc)[:, None]) \
        & np.asarray(active, bool)[:, None]
    if first is not None:
        held &= np.arange(nb)[None, :] >= np.asarray(first)[:, None]
    lane, col = np.nonzero(held)  # row-major: row after row
    n = lane.size
    at = np.arange(n)
    if own_tiles:
        # Where each lane's run starts: on the tile after the one the
        # lanes before it end in.
        runs = -(-held.sum(axis=1) // tile) * tile
        ends = np.cumsum(runs)
        at = (ends - runs)[lane] + col
        n = int(ends[-1])
    blocks = np.zeros((3, max_tiles * tile), np.int32)
    blocks[1] = ns
    blocks[0, at] = tables[lane, col]
    blocks[1, at] = lane
    blocks[2, at] = col * block_tokens
    return (blocks.reshape(3, max_tiles, tile),
            np.int32(-(-n // tile)))


def live_lane_list(active, tile: int | None = None):
    """The lanes that hold a request, as a latent decode step's
    attention reads them (``sparse_mla.attend_paged``). Host side,
    numpy: ``active`` (n_slots,) bool. Returns ``(lanes, n_tiles)``:
    ``lanes`` int32 ``(max_tiles, tile)`` holds the live slots' ids in
    slot order, padded with the id no lane has (``n_slots``);
    ``n_tiles`` int32 is the number of tiles in use, the step's trip
    count. ``max_tiles * tile`` covers every slot, so the shape never
    changes while the engine lives."""
    active = np.asarray(active, bool)
    ns = active.size
    tile = min(int(tile or LIVE_TILE_LANES), ns)
    ids = np.flatnonzero(active)
    lanes = np.full(-(-ns // tile) * tile, ns, np.int32)
    lanes[:ids.size] = ids
    return lanes.reshape(-1, tile), np.int32(-(-ids.size // tile))


def _live_block_attention(q, kf, vf, base, blocks, limits,
                          window: int = 0, scope: str = "attn"):
    """GQA decode attention over the blocks live rows hold: work
    follows Σ live context, not lanes x reach. q: (B, 1, H, Dh);
    ``kf``/``vf``: the flat banks ``(L * n_blocks, block_tokens, Kh,
    Dh)``, ``base`` the layer's first row in them; ``blocks`` as
    :func:`live_block_list` gives it; ``limits`` (B,): lane ``b``
    attends positions ``< limits[b]`` of its own blocks and, in a
    window layer, ``>= limits[b] - window`` (the list then holds the
    window pool's blocks). ``scope`` names the loop in a device trace
    (``attn_window`` / ``attn_full`` in a stack with attention kinds).
    (A latent cache with no indexer reads its list, each tile one
    lane's, through a kernel: ``ops.latent_block_attention``.)

    One loop over the list's tiles in use (a ``while`` whose trip count
    is data, so ONE compiled program whatever the load): a tile's
    blocks are gathered once, EVERY lane's queries are scored against
    them (``M = B * G`` rows a KV head: a real matmul, where a product a
    block would be ``G x block_tokens``), what is not the lane's own or
    lies past its limit is masked, and the tile folds into a running
    max / sum / accumulator per (lane, head): the same float32 softmax
    as :func:`_paged_attention_gather`, accumulated tile by tile, so
    equal to it to float32 rounding. The banks are closed over and only
    read: nothing of them is loop state. A lane that owns no listed
    block (inactive) gets zeros."""
    lst, n_tiles = blocks
    B, _, H, Dh = q.shape
    bt, Kh = kf.shape[1], kf.shape[2]
    G = H // Kh
    S = lst.shape[2] * bt
    f32 = jnp.float32
    with jax.named_scope(scope):
        qg = q.reshape(B, Kh, G, Dh)
        lanes = jnp.arange(B, dtype=jnp.int32)
        limits = jnp.asarray(limits, jnp.int32)
        offs = jnp.arange(bt, dtype=jnp.int32)

        def fold(t, carry):
            ids, owner, first = lst[0, t], lst[1, t], lst[2, t]
            with jax.named_scope("kv_gather"):
                ks = kf[base + ids].reshape(S, Kh, Dh)
                vs = vf[base + ids].reshape(S, Kh, Dh)
            m, l, acc = carry
            s = jnp.einsum("bkgd,skd->bkgs", qg, ks).astype(f32)
            s = s / jnp.sqrt(f32(Dh))
            owner = jnp.repeat(owner, bt)
            at = (first[:, None] + offs[None, :]).reshape(S)
            mask = ((owner[None, :] == lanes[:, None])
                    & (at[None, :] < limits[:, None]))
            if window:
                mask &= at[None, :] >= limits[:, None] - window
            mask = mask[:, None, None, :]
            s = jnp.where(mask, s, f32(-1e30))
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # A lane with nothing in the tiles so far has m_new at the
            # mask value, where exp(s - m_new) would read 1.
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), f32(0))
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgs,skd->bkgd", p.astype(q.dtype), vs,
                            preferred_element_type=f32)
            return m_new, l, acc * alpha[..., None] + pv

        m, l, acc = lax.fori_loop(
            0, n_tiles, fold,
            (jnp.full((B, Kh, G), -1e30, f32),
             jnp.zeros((B, Kh, G), f32),
             jnp.zeros((B, Kh, G, Dh), f32)))
        o = acc / jnp.where(l > 0, l, f32(1))[..., None]
        return o.astype(q.dtype).reshape(B, 1, H, Dh)


#: Blocks in one tile of a full-attention layer's table walk
#: (:func:`_table_attention`): 1,024 keys at 16 a block, so a
#: 512-query chunk's float32 scores are 134 MB at 64 heads whatever
#: the context.
TABLE_TILE_BLOCKS = 64


def _table_attention(q, kf, vf, tables, limits, window: int, scope: str,
                     scale: float | None = None, v_dim: int | None = None,
                     keep=None):
    """Attention through block tables in a stack with attention kinds
    or over a latent cache (``vf`` None, ``v_dim`` and ``scale`` as
    :func:`_live_block_attention` takes them), for queries that are
    many to a table (a prefill chunk) or rows with no block list: the
    tables are walked a tile of blocks at a time and folded into a
    float32 running softmax, the trip count from the data, so ONE
    compiled program serves every context up to the reach and its
    temporaries do not grow with it. q: (B, Q, H,
    Dh); ``kf``/``vf`` the flat banks, ``tables`` (B, nb) their rows
    in position order (the layer's base added); ``limits`` (B,) or
    (B, Q): a query attends positions ``< limit`` and, with ``window``,
    ``>= limit - window``.

    A full layer (``window`` 0) walks from block 0 in tiles of
    :data:`TABLE_TILE_BLOCKS` up to the largest limit. A window layer
    reads ONE tile, the ``window + Q`` positions its queries can see
    between them, starting at the first block any of them sees:
    what lies behind it was given back (the table names the trash
    block there) and is masked.

    Behind an indexer (a full layer's walk, ``sparse_mla.attend_paged``)
    ``keep(first, at)`` gives, for the tile whose first position is
    ``first`` and whose positions are ``at`` (B, S), the (B, Q, S) keys
    each query's selection holds, and the scores stay float32; without
    it they are rounded to ``q``'s type as its product gives them
    (A.X-K1's chunk keeps that rounding: PERF.md §6, PR 36)."""
    B, Q, H, Dh = q.shape
    nb = tables.shape[1]
    bt, Kh = kf.shape[1], kf.shape[2]
    G = H // Kh
    Dv = Dh if vf is not None else v_dim
    f32 = jnp.float32
    limits = jnp.asarray(limits, jnp.int32)
    hi = limits[:, None] if limits.ndim == 1 else limits  # (B, 1|Q)
    top = jnp.max(hi, axis=1)                             # (B,)
    if window:
        tile = min(nb, (window + Q - 1) // bt + 2)
        # The lowest position a REAL query sees (pads have limit 0).
        low = jnp.min(jnp.where(hi > 0, hi, top[:, None]), axis=1)
        col0 = jnp.maximum(low - window, 0) // bt          # (B,)
        n_tiles = jnp.int32(1)
    else:
        tile = min(nb, TABLE_TILE_BLOCKS)
        col0 = jnp.zeros((B,), jnp.int32)
        n_tiles = -(-jnp.max(top) // (tile * bt))
    S = tile * bt
    with jax.named_scope(scope):
        qg = q.reshape(B, Q, Kh, G, Dh)
        offs = jnp.arange(S, dtype=jnp.int32)

        def fold(t, carry):
            m, l, acc = carry
            cols = col0[:, None] + t * tile + jnp.arange(tile)[None, :]
            with jax.named_scope("kv_gather"):
                ids = jnp.take_along_axis(
                    tables, jnp.minimum(cols, nb - 1), axis=1)
                ks = kf[ids].reshape(B, S, Kh, Dh)
                vs = (ks[..., :Dv] if vf is None
                      else vf[ids].reshape(B, S, Kh, Dh))
            s = jnp.einsum("bqkgd,bskd->bkgqs", qg, ks,
                           preferred_element_type=None if keep is None
                           else f32).astype(f32)
            s = s / jnp.sqrt(f32(Dh)) if scale is None else s * f32(scale)
            # Columns past the table sit past every limit.
            at = (col0[:, None] + t * tile) * bt + offs[None, :]  # (B, S)
            mask = at[:, None, :] < hi[:, :, None]             # (B, Q, S)
            if window:
                mask &= at[:, None, :] >= hi[:, :, None] - window
            if keep is not None:
                mask &= keep(t * S, at)
            mask = mask[:, None, None, :, :]
            s = jnp.where(mask, s, f32(-1e30))
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(mask, jnp.exp(s - m_new[..., None]), f32(0))
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(q.dtype), vs,
                            preferred_element_type=f32)
            return m_new, l, acc * alpha[..., None] + pv

        m, l, acc = lax.fori_loop(
            0, n_tiles, fold,
            (jnp.full((B, Kh, G, Q), -1e30, f32),
             jnp.zeros((B, Kh, G, Q), f32),
             jnp.zeros((B, Kh, G, Q, Dv), f32)))
        o = acc / jnp.where(l > 0, l, f32(1))[..., None]
        o = jnp.transpose(o, (0, 3, 1, 2, 4))  # (B, Q, Kh, G, Dv)
        return o.astype(q.dtype).reshape(B, Q, H, Dv)


def _paged_layers(params, tokens, positions, cfg, banks, tables, wr_b,
                  wr_o, limits, moe_capacity, live=None,
                  chunk: bool = False, live_list=None):
    """Embedding and the ONE layer loop of the paged programs (decode
    step, prefill chunk, speculative verify and draft). ``banks`` is
    the cache as the model describes it (``tfm.cache_spec``: a dict of
    ``(L, n_blocks, block_tokens, ...)`` arrays — ``k``, ``v`` for GQA,
    ``ckv`` and, behind an indexer, ``ki`` for latent attention). The
    banks ride the scan's CARRY, whole, and a layer reaches its own rows through the indices
    of its scatter and gather: in the free flat view ``(L * n_blocks,
    block_tokens, ...)`` layer ``l``'s block ``b`` is row ``l *
    n_blocks + b``. (Scanned, every layer sliced its bank out of the
    stack and wrote all of it back, and the program copied the banks
    again: a scanned output cannot alias a donated input.) A stack of
    several groups (``tfm.layer_groups``) is one scan a group, the
    same carry walking through them. In a stack that states attention
    kinds (``tfm.cache_layers``: window and full layers, a run of one
    kind a group) there are two caches, and ``banks``, ``tables``,
    ``wr_b`` and ``live_list`` are each a dict ``{"full": ...,
    "window": ...}`` of what is described here for one: a layer reads
    and writes its own kind's, at its rank among that kind's layers.
    A decode step attends over each kind's block list
    (:func:`_live_block_attention`, a window layer's with the lower
    limit in its mask); a prefill chunk through the tables
    (:func:`_table_attention`).

    ``tokens``/``positions``/``wr_b``/``wr_o`` (B, Q): each position's
    cache row goes to ``(wr_b, wr_o)`` (inactive lanes and pads name
    the trash block 0); ``tables`` (B, nb), ``limits`` ((B,) or (B, Q))
    as :func:`_paged_attention_gather` takes them. ``chunk``: the
    queries are a prefill chunk's, many to a table (behind an indexer
    each block of them walks the table with its selection as a mask,
    ``sparse_mla.attend_paged``). ``live_list``: what
    the live rows hold, in the form the cache kind reads, given to a
    decode step. GQA: the block list (:func:`live_block_list`); the
    step attends over it (:func:`_live_block_attention`) and not
    through ``tables``, which then only route the writes. Latent
    behind an indexer: the lane list (:func:`live_lane_list`); index,
    selection, gather and attention run over its lanes alone. Latent
    with no indexer: the block list again, each lane's run on tiles of
    its own (``own_tiles``), read in the absorbed form by one kernel a
    layer (``ops.latent_block_attention``: a row's blocks copied into
    VMEM once, out of the bank as it lies); its prefill chunk walks
    the table (:func:`_table_attention`). Returns ``(x (B,
    Q, D) before the final norm, banks, load)``; ``load`` is a
    dropless router's counts
    summed over its layers (``tfm._moe_dropless``; of the tokens
    ``live`` (B, Q) marks, if given) and behind them two more int32,
    the tiles its layers' loops visited and the held experts they hit
    (``tfm.expert_tiles``, summed likewise); None without one."""
    def flat_view(bs):
        return {n: b.reshape((-1,) + b.shape[2:]) for n, b in bs.items()}

    def as_banks(fs, like):
        return {n: f.reshape(like[n].shape) for n, f in fs.items()}

    kinds = tfm.cache_layers(cfg)
    if kinds is None:
        n_blocks = next(iter(banks.values())).shape[1]
        flat = flat_view(banks)
    else:
        # Two kinds of cache: each per-kind argument is a dict of the
        # kind's own ("full", "window"), and a layer's base is its
        # rank among the layers of its kind times that kind's blocks.
        n_blocks = {k: banks[k]["k"].shape[1] for k in kinds}
        flat = {k: flat_view(banks[k]) for k in kinds}
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(cfg.dtype)

    if cfg.latent is None:
        sin, cos = tfm.rope_tables(cfg, positions=positions)

        def attention(x, bf, layer, base, window=None):
            # ``window`` None: one kind of cache; else this group's
            # kind picks its own of each per-kind argument.
            kind = None if window is None else (
                "window" if window else "full")
            own, wb = (bf, wr_b) if kind is None else (bf[kind],
                                                       wr_b[kind])
            q, k, v = tfm.qkv_proj(
                x, layer, cfg, sin, cos,
                rotate=kind != "full" or not cfg.nope_full)
            kf, vf = own["k"], own["v"]
            with jax.named_scope("kv_write"):
                kf = kf.at[base + wb, wr_o].set(k)
                vf = vf.at[base + wb, wr_o].set(v)
            own = {"k": kf, "v": vf}
            if kind is None:
                if live_list is not None:
                    o = _live_block_attention(q, kf, vf, base, live_list,
                                              limits)
                else:
                    o = _paged_attention_gather(q, kf, vf, base + tables,
                                                limits, cfg)
                return o, own
            if live_list is not None:
                o = _live_block_attention(
                    q, kf, vf, base, live_list[kind], limits,
                    window=window, scope="attn_" + kind)
            else:
                o = _table_attention(q, kf, vf, base + tables[kind],
                                     limits, window, "attn_" + kind)
            return o, {**bf, kind: own}
    else:
        from ptype_tpu.models import sparse_mla
        from ptype_tpu.ops.latent_block_attention import (
            latent_block_attention)

        la = cfg.latent

        def attention(x, bf, layer, base, window=None):
            q_nope, q_rope, ckv, qi, ki, wi = sparse_mla.project(
                x, layer, cfg, positions)
            with jax.named_scope("kv_write"):
                own = {"ckv": bf["ckv"].at[base + wr_b, wr_o].set(ckv)}
                if la.indexer:
                    own["ki"] = bf["ki"].at[base + wr_b, wr_o].set(ki)
            if la.indexer:
                o = sparse_mla.attend_paged(
                    q_nope, q_rope, qi, wi, own["ckv"], own["ki"],
                    base + tables, limits, layer, cfg,
                    whole_context=chunk, lanes=live_list)
                return o, own
            # Nothing selects: every held position is read, and the
            # bank is one K,V head whose row is key and value at once.
            with jax.named_scope("attn"):
                qa = sparse_mla.absorb_query(q_nope, q_rope, layer, cfg)
            how = dict(scale=sparse_mla.score_scale(cfg), v_dim=la.kv_rank)
            if live_list is not None:
                # A decode step: one kernel reads each live row's
                # blocks once, out of the bank where it lies.
                with jax.named_scope("attn"):
                    ol = latent_block_attention(
                        qa[:, 0], own["ckv"], base, live_list, limits,
                        **how)[:, None]
            else:
                ol = _table_attention(qa, own["ckv"][:, :, None], None,
                                      base + tables, limits, 0, "attn",
                                      **how)
            with jax.named_scope("attn"):
                return sparse_mla.expand_values(ol, layer, cfg), own

    def layers_of(window, experts):
        def body(carry, inputs):
            x, bf = carry
            # base: the layer's first row of the view; at, where the
            # group's expert stacks are closed over: its index in them.
            layer, base, *at = inputs
            o, bf = attention(x, bf, layer, base, window)
            x = tfm.attn_residual(x, o, layer, cfg)
            x, _aux, load = tfm.mlp_residual(
                x, {**layer, **experts}, cfg, moe_capacity=moe_capacity,
                live=live, at=at[0] if at else None)
            if load is not None:
                load = jnp.concatenate(
                    [load, tfm.expert_tiles(load, x.shape[0] * x.shape[1])])
            return (x, bf), load
        return body

    load = None
    for stacked, first, n in tfm.block_groups(params, cfg):
        if kinds is None:
            window = None
            bases = jnp.arange(first, first + n,
                               dtype=jnp.int32) * n_blocks
        else:
            window = cfg.attn_windows[first]
            kind = "window" if window else "full"
            rank = kinds[kind].index(first)
            bases = jnp.arange(rank, rank + n,
                               dtype=jnp.int32) * n_blocks[kind]
        # A dropless router's expert stacks stay out of the scan: its
        # tile loop reads an expert's matrices in place out of the
        # whole group's stack (tfm._moe_dropless).
        experts, xs = {}, (stacked, bases)
        if cfg.moe_router == "sigmoid_bias" and "router" in stacked:
            experts = {m: stacked[m] for m in ("w_gate", "w_up", "w_down")}
            xs = ({m: w for m, w in stacked.items() if m not in experts},
                  bases, jnp.arange(n, dtype=jnp.int32))
        (x, flat), loads = lax.scan(
            layers_of(window, experts), (x, flat), xs)
        if loads is not None:
            total = jnp.sum(loads, axis=0)
            load = total if load is None else load + total
    if kinds is None:
        return x, as_banks(flat, banks), load
    return x, {k: as_banks(flat[k], banks[k]) for k in kinds}, load


def decode_step_banks(params: dict, token: jax.Array, pos: jax.Array,
                      cfg: tfm.TransformerConfig, banks: dict,
                      tables: jax.Array, wr_blocks: jax.Array,
                      wr_off: jax.Array, live=None, live_list=None):
    """One decode step through per-sequence BLOCK TABLES — the paged
    engine step (serve_engine.PagedGeneratorActor). ``banks``: the
    cache as ``tfm.cache_spec(cfg)`` describes it, ``(L, n_blocks,
    block_tokens, ...)`` arrays shared by every sequence; ``tables``
    (B, nb) maps each row's positions onto bank blocks. Each row
    writes its new cache row at ``(wr_blocks[b], wr_off[b])`` — the
    engine routes INACTIVE rows to the trash block so a masked lane can
    never scatter into a real (possibly shared) block — and attends
    over exactly its own positions, so greedy rows match the solo
    :func:`generate` decode token for token (the engine's parity bar)
    and its logits to the float32 rounding of a softmax summed in
    another order.

    ``live_list``: what the live rows hold, in the form the cache kind
    reads. GQA (:func:`live_block_list`'s pair): their K,V blocks; the
    step reads those and no others, tile by tile
    (:func:`_live_block_attention`), so its cost follows the tokens in
    flight; a latent cache with no indexer is read from the same
    list, each row's run of it on tiles of its own, by one kernel a
    layer (``ops.latent_block_attention``), its one row a token key
    and value at once. Latent behind an indexer
    (:func:`live_lane_list`'s pair): their lanes; the
    indexer, the selection, the latent gather and the attention run
    over those, a tile of lanes at a time
    (``sparse_mla.attend_paged``), so their cost follows the rows in
    flight, and an inactive lane's attention reads zeros. Without it
    every row gathers or indexes its whole table, reach and all, live
    or not. In a stack with attention kinds ``banks``, ``tables``,
    ``wr_blocks`` and ``live_list`` are dicts of the two caches' own
    (:func:`_paged_layers`). Returns
    ``(logits (B, V), banks, load)``, ``load`` as :func:`_paged_layers`
    gives it (of the rows ``live`` (B,) marks, if given)."""
    x, banks, load = _paged_layers(
        params, token[:, None], pos[:, None], cfg, banks, tables,
        jax.tree.map(lambda w: w[:, None], wr_blocks), wr_off[:, None],
        pos + 1, token.shape[0],
        None if live is None else live[:, None], live_list=live_list)
    with jax.named_scope("head"):
        x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _head_logits(params, x[:, 0], cfg)
    return logits, banks, load


def prefill_chunk_banks(params: dict, tokens: jax.Array,
                        start: jax.Array, length: jax.Array,
                        cfg: tfm.TransformerConfig, banks: dict,
                        table: jax.Array):
    """One CHUNK of paged prefill for a single sequence — the bounded
    unit chunked admission interleaves with decode steps. ``tokens``
    (1, C): prompt positions ``[start, start + length)`` right-padded
    to the chunk bucket C; ``table`` (nb,) the sequence's block table.
    Cache rows for real tokens scatter into their blocks (pad columns
    go to the trash block); attention runs per-query-causal against the
    table, i.e. query ``c`` sees every previously-written position plus
    the chunk through itself — mathematically the same full causal
    prefill, split at chunk boundaries (with an indexer, each query
    selects among exactly those). In a stack with attention kinds
    ``banks`` and ``table`` are dicts of the two caches' own, and a
    window layer's query sees the last ``cfg.window`` positions alone.
    Returns ``(logits (1, V) at the
    chunk's LAST REAL token, banks, load)`` — only the final chunk's
    logits feed the first sampled token."""
    B, C = tokens.shape
    bt = jax.tree.leaves(banks)[0].shape[2]
    nb = jax.tree.leaves(table)[0].shape[0]
    pos_vec = start + jnp.arange(C)  # (C,) positions of chunk columns
    valid = jnp.arange(C) < length
    wr_b = jax.tree.map(
        lambda t: jnp.where(valid, t[jnp.clip(pos_vec // bt, 0, nb - 1)],
                            0), table)
    wr_o = pos_vec % bt
    # Per-query limits: pad queries attend nothing (their garbage
    # outputs are never read — x_last indexes the last REAL token).
    limits = jnp.where(valid, pos_vec + 1, 0)
    # MoE: zero-drop capacity over the padded chunk (same reasoning as
    # prefill's B*S bound — dropping is a training regularizer).
    cap = C if cfg.n_experts else None

    x, banks, load = _paged_layers(
        params, tokens, pos_vec[None], cfg, banks,
        jax.tree.map(lambda t: t[None], table),
        jax.tree.map(lambda w: w[None], wr_b), wr_o[None], limits[None],
        cap, live=valid[None], chunk=True)
    with jax.named_scope("head"):
        x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        x_last = x[jnp.arange(B), jnp.asarray(length)[None] - 1]
        logits = _head_logits(params, x_last, cfg)
    return logits, banks, load


# ------------------------------------------------- speculative decoding

#: RNG domain separators for the speculative path: the draft's
#: proposal draws and the acceptance test's uniforms/residual draws
#: fold these into the row key FIRST, so the three streams (engine
#: sampling, draft sampling, acceptance) can never collide at a shared
#: fold index. Arbitrary constants; changing them changes sampled
#: outputs (never greedy ones).
_DRAFT_FOLD = 0x5bec
_ACCEPT_FOLD = 0xacce


def _gqa_one_group(cfg: tfm.TransformerConfig, what: str) -> None:
    """The speculative paths and the contiguous cache hold K and V per
    head for one stacked group of layers: say so, not a shape error."""
    if not cfg.plain:
        raise ValueError(
            f"{what} needs a GQA stack of one group with one kind of "
            f"cache; this configuration has latent attention, several "
            f"layer groups, a dropless router or window layers beside "
            f"full ones (two caches), which only "
            f"the plain paged decode step and prefill chunk run "
            f"(its own next-token module would be the drafter)")


def truncated_draft_params(params: dict, cfg: tfm.TransformerConfig,
                           n_layers: int = 1
                           ) -> tuple[dict, tfm.TransformerConfig]:
    """The shared-prefix-truncated draft: reuse the target's embedding
    / final norm / LM head and its FIRST ``n_layers`` transformer
    blocks as a cheap same-family draft model. Zero extra parameter
    memory (the returned tree aliases the target's arrays — blocks are
    stacked on the scan axis, so truncation is one leading slice).
    Returns ``(draft_params, draft_cfg)`` for
    ``SpecConfig(draft_params=..., draft_cfg=...)``."""
    _gqa_one_group(cfg, "a truncated draft")
    if not 1 <= n_layers <= cfg.n_layers:
        raise ValueError(
            f"truncated draft needs 1 <= n_layers <= {cfg.n_layers}, "
            f"got {n_layers}")
    from dataclasses import replace

    blocks = jax.tree_util.tree_map(lambda a: a[:n_layers],
                                    params["blocks"])
    return dict(params, blocks=blocks), replace(cfg, n_layers=n_layers)


def verify_step_paged(params: dict, tokens: jax.Array,
                      pos0: jax.Array, cfg: tfm.TransformerConfig,
                      kb: jax.Array, vb: jax.Array, tables: jax.Array,
                      wr_b: jax.Array, wr_o: jax.Array
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Target-model verification of one speculation window in ONE
    batched forward — the speculative-decoding counterpart of
    :func:`decode_step_banks`. ``tokens`` (B, W): each row's last
    committed token followed by its draft proposals, at positions
    ``pos0 + [0..W)``; every position's K/V scatters through the block
    tables (``wr_b``/``wr_o`` (B, W) — the engine routes inactive
    lanes and positions past a row's reserved span to the trash
    block), and query ``j`` attends causally through position
    ``pos0 + j`` via the same ragged per-slot gather path decode uses.
    Returns ``(logits (B, W, V) f32, kb, vb)``: ``logits[:, j]`` is
    the target distribution for the token AT position ``pos0 + j + 1``
    given the prefix through ``tokens[:, j]`` — exactly the logits W
    sequential :func:`decode_step_banks` calls would produce, which is
    what makes greedy speculative acceptance bit-identical to the
    non-speculative engine. Rejected positions need no KV cleanup:
    their writes land inside the row's already-reserved blocks and the
    position-limit mask hides them until a later token overwrites them
    (rollback is a position rewind, never a reallocation)."""
    _gqa_one_group(cfg, "speculative verification")
    B, W = tokens.shape
    pos = pos0[:, None] + jnp.arange(W)[None, :]   # (B, W)
    # MoE: zero-drop capacity over the whole window (same reasoning
    # as decode_step's B bound — dropping is a training regularizer).
    cap = B * W if cfg.n_experts else None

    # Per-query causal limits: query j attends through pos0 + j.
    x, banks, _ = _paged_layers(params, tokens, pos, cfg,
                                {"k": kb, "v": vb}, tables, wr_b, wr_o,
                                pos + 1, cap)
    with jax.named_scope("head"):
        x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _head_logits(params, x, cfg)
    return logits, banks["k"], banks["v"]


def draft_propose_paged(params: dict, tok: jax.Array,
                        pos0: jax.Array, cfg: tfm.TransformerConfig,
                        kb: jax.Array, vb: jax.Array,
                        tables: jax.Array, wr_b: jax.Array,
                        wr_o: jax.Array, keys: jax.Array,
                        steps0: jax.Array, temps: jax.Array,
                        top_ks: jax.Array, top_ps: jax.Array,
                        n_steps: int, sampled: bool = True
                        ) -> tuple[jax.Array, jax.Array, jax.Array,
                                   jax.Array]:
    """``n_steps`` draft decode steps through the draft model's own
    block tables inside ONE program (a ``lax.scan`` — one dispatch per
    window, not per proposal). Step ``j`` feeds the previous token at
    position ``pos0 + j``, writes its K/V (``wr_b``/``wr_o``
    (B, n_steps), trash-routed like the verify step), and draws the
    next token from the draft distribution: greedy rows take the
    argmax; sampled rows draw from the same filtered/temperature-
    scaled logits the acceptance test will score, with a
    draft-domain-separated key folded at ``steps0 + j`` per row
    (:func:`sample_token_rows` — the one RNG home). The engine runs
    ``n_steps = k + 1``: the last step's K/V write covers the
    all-accepted case (the bonus token's context) and its proposal is
    discarded. Returns ``(proposed (B, n_steps) int32, draft_logits
    (B, n_steps, V) f32 raw, kb, vb)`` — ``proposed[:, j]`` is the
    draft's token for position ``pos0 + j + 1`` and
    ``draft_logits[:, j]`` the logits it was drawn from (acceptance
    recomputes the filtered distribution from these, so q is scored
    exactly as sampled)."""
    _gqa_one_group(cfg, "a speculative draft")
    B = tok.shape[0]
    dkeys = jax.vmap(
        lambda kk: jax.random.fold_in(kk, _DRAFT_FOLD))(keys)

    def step(carry, inputs):
        tok, kb, vb = carry
        j, wb, wo = inputs
        pos = pos0 + j  # (B,)
        x, banks, _ = _paged_layers(
            params, tok[:, None], pos[:, None], cfg, {"k": kb, "v": vb},
            tables, wb[:, None], wo[:, None], pos + 1, B)
        kb, vb = banks["k"], banks["v"]
        with jax.named_scope("head"):
            x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
            lg = _head_logits(params, x[:, 0], cfg)  # (B, V) f32
        with jax.named_scope("sample"):
            if sampled:
                nxt = sample_token_rows(lg, dkeys, steps0 + j, temps,
                                        top_ks, top_ps)
            else:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return (nxt, kb, vb), (nxt, lg)

    (_, kb, vb), (toks, lgs) = lax.scan(
        step, (tok, kb, vb),
        (jnp.arange(n_steps), jnp.swapaxes(wr_b, 0, 1),
         jnp.swapaxes(wr_o, 0, 1)))
    return (jnp.swapaxes(toks, 0, 1), jnp.swapaxes(lgs, 0, 1), kb, vb)


def spec_accept_rows(draft_toks: jax.Array, draft_logits: jax.Array,
                     target_logits: jax.Array, keys: jax.Array,
                     steps0: jax.Array, temps: jax.Array,
                     top_ks: jax.Array, top_ps: jax.Array,
                     sampled: bool = True
                     ) -> tuple[jax.Array, jax.Array]:
    """Per-row acceptance sampling over one speculation window — the
    exact-distribution contract (:func:`sample_token_rows`'s
    draw-for-draw machinery extended to a residual-distribution
    acceptance). ``draft_toks`` (B, k), ``draft_logits`` (B, k, V)
    raw f32, ``target_logits`` (B, k+1, V) raw f32.

    Greedy rows (``temps == 0``): accept the longest draft prefix
    matching the target argmax chain, then emit the target argmax at
    the first mismatch — bit-identical to sequential greedy decode,
    whatever the draft proposed. Sampled rows: token ``j`` accepts
    with probability ``min(1, p_j(d_j) / q_j(d_j))`` where ``p`` / ``q``
    are the filtered, temperature-scaled target / draft distributions
    (the SAME filtering the draws came from); the first rejection
    draws the corrected token from the normalized residual
    ``max(p_j − q_j, 0)``, and a fully-accepted window draws the bonus
    token from ``p_k`` — the classic speculative-sampling identity, so
    the emitted stream is distributed EXACTLY as sequential
    ``jax.random.categorical`` sampling from the target
    (contract-tested statistically; the residual draw rides an
    acceptance-domain-separated key at ``steps0``/``steps0 + 1``).

    Returns ``(out_toks (B, k+1), n_acc (B,))``: row ``b`` emits
    ``out_toks[b, :n_acc[b] + 1]`` — its accepted draft prefix plus
    one corrected/bonus token."""
    k = draft_toks.shape[1]

    if not sampled:
        # All-greedy window: the argmax chain only — no softmax, no
        # RNG, no filter machinery on the serving hot path.
        def one_greedy(d_toks, t_lg):
            gt = jnp.argmax(t_lg, axis=-1).astype(jnp.int32)
            match = (d_toks == gt[:k]).astype(jnp.int32)
            n_acc = jnp.sum(jnp.cumprod(match))
            out = jnp.concatenate(
                [d_toks, jnp.zeros((1,), jnp.int32)])
            return out.at[n_acc].set(gt[n_acc]), n_acc

        return jax.vmap(one_greedy)(draft_toks, target_logits)

    akeys = jax.vmap(
        lambda kk: jax.random.fold_in(kk, _ACCEPT_FOLD))(keys)

    def one(d_toks, d_lg, t_lg, key, step0, t, tk, tp):
        gt = jnp.argmax(t_lg, axis=-1).astype(jnp.int32)  # (k+1,)
        match_g = d_toks == gt[:k]

        def dist(lg):  # raw (V,) logits → filtered sampling probs
            x = lg.astype(jnp.float32) / jnp.where(t > 0, t, 1.0)
            return jax.nn.softmax(_filter_logits_traced(x, tk, tp))

        p = jax.vmap(dist)(t_lg)  # (k+1, V)
        q = jax.vmap(dist)(d_lg)  # (k, V)
        idx = jnp.arange(k)
        ratio = p[idx, d_toks] / jnp.maximum(q[idx, d_toks], 1e-30)
        u = jax.random.uniform(jax.random.fold_in(key, step0), (k,))
        ok = jnp.where(t > 0.0, u < jnp.minimum(ratio, 1.0), match_g)
        n_acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
        # Residual at the rejection point; q padded with a zero row so
        # a fully-accepted window (n_acc == k) draws the bonus token
        # from the bare target distribution p_k.
        q_pad = jnp.concatenate([q, jnp.zeros((1, q.shape[-1]),
                                              q.dtype)])
        res = jnp.maximum(p[n_acc] - q_pad[n_acc], 0.0)
        rs = jnp.sum(res)
        # A numerically-empty residual (p == q to float precision but
        # the ratio test still rejected) falls back to p itself.
        res = jnp.where(rs > 0, res / jnp.maximum(rs, 1e-30),
                        p[n_acc])
        c_s = jax.random.categorical(
            jax.random.fold_in(key, step0 + 1),
            jnp.log(jnp.maximum(res, 1e-38))).astype(jnp.int32)
        c = jnp.where(t > 0.0, c_s, gt[n_acc])
        out = jnp.concatenate([d_toks, jnp.zeros((1,), jnp.int32)])
        return out.at[n_acc].set(c), n_acc

    return jax.vmap(one)(draft_toks, draft_logits, target_logits,
                         akeys, steps0, temps, top_ks, top_ps)


@functools.lru_cache(maxsize=64)
def _compiled_generate(cfg: tfm.TransformerConfig, B: int, S: int,
                       max_new_tokens: int, temperature: float,
                       top_k: int, top_p: float, rep_penalty: float):
    """One jitted prefill+decode program per (cfg, shapes, sampling
    params) — repeated calls (the serving hot path) reuse the
    compilation. ``run(params, prompt, lens, rng)``: ``lens`` is None
    for uniform-length prompts (a static, empty pytree under jit) or
    a traced (B,) lengths array for LEFT-padded ragged batches — ONE
    implementation for both, so sampling fixes can't drift between
    them."""
    penalize = rep_penalty != 1.0

    def run(params, prompt, lens, rng):
        # Size the cache to THIS request's reach (128-lane aligned),
        # not cfg.max_seq: decode reads the whole static cache every
        # step, so a 128+128-token call against a 1024-slot cache was
        # paying 4× the attention HBM traffic for masked-out zeros.
        reach = min(cfg.max_seq, -(-(S + max_new_tokens) // 128) * 128)
        cache = init_cache(cfg, B, max_seq=reach)
        logits, cache = prefill(params, prompt, cfg, cache,
                                prompt_lens=lens)
        # (B,) first valid cache slot per row (0 when uniform).
        pad = None if lens is None else S - lens
        # Token-presence mask for repetition penalty: prompt tokens
        # count as seen (HF semantics), emitted tokens join per step.
        seen = None
        if penalize:
            if lens is None:
                idx = prompt
            else:
                # Pad columns must not count as "seen": redirect them
                # to an out-of-bounds index dropped by the scatter.
                valid = jnp.arange(S)[None, :] >= pad[:, None]
                idx = jnp.where(valid, prompt, cfg.vocab_size)
            seen = (jnp.zeros((B, cfg.vocab_size), jnp.bool_)
                    .at[jnp.arange(B)[:, None], idx]
                    .set(True, mode="drop"))

        def sample(logits, key, seen):
            if penalize:
                # HF repetition penalty: seen tokens' positive logits
                # divide by the penalty, negative multiply — both push
                # probability down for penalty > 1.
                pen = jnp.where(logits > 0, logits / rep_penalty,
                                logits * rep_penalty)
                logits = jnp.where(seen, pen, logits)
            if temperature == 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # Temperature FIRST: the nucleus must be measured on the
            # distribution actually sampled (HF/llama.cpp semantics) —
            # top-k is scale-invariant but top-p is not.
            logits = logits / jnp.float32(temperature)
            logits = _filter_logits(logits, top_k, top_p)
            return jax.random.categorical(key, logits,
                                          axis=-1).astype(jnp.int32)

        def mark(seen, token):
            if not penalize:
                return None
            return seen.at[jnp.arange(B), token].set(True)

        first = sample(logits, jax.random.fold_in(rng, 0), seen)
        seen = mark(seen, first)

        def step(carry, i):
            token, cache, seen = carry
            # Cache slot S+i is uniform; each ragged row's TOKEN
            # position is its own length + i (the left-pad offset).
            logits, cache = decode_step(
                params, token, S + i, cfg, cache,
                rope_pos=None if lens is None else lens + i,
                valid_from=pad)
            nxt = sample(logits, jax.random.fold_in(rng, i + 1), seen)
            return (nxt, cache, mark(seen, nxt)), token

        (_, _, _), toks = lax.scan(
            step, (first, cache, seen), jnp.arange(max_new_tokens))
        return toks.T  # (B, max_new_tokens): ys are the emitted tokens

    return jax.jit(run)


def pad_prompts(prompts, pad_token: int = 0):
    """LEFT-pad a list of 1-D token arrays to one (B, S) batch.
    Returns (padded int32 (B, S), lens int32 (B,)) for
    ``generate(..., prompt_lens=lens)``."""
    lens = np.asarray([len(p) for p in prompts], np.int32)
    S = int(lens.max())
    out = np.full((len(prompts), S), pad_token, np.int32)
    for i, p in enumerate(prompts):
        out[i, S - len(p):] = np.asarray(p, np.int32)
    return jnp.asarray(out), jnp.asarray(lens)


def _filter_logits(logits: jax.Array, top_k: int,
                   top_p: float) -> jax.Array:
    """Nucleus/top-k filtering: mask logits outside the top-k set and
    outside the smallest prefix whose probability mass reaches top_p.
    ``top_k <= 0`` / ``top_p >= 1`` disable the respective filter.
    logits: (B, V) f32."""
    if top_k > 0:
        k = min(top_k, logits.shape[-1])  # top_k > V means "keep all"
        # lax.top_k (selection) beats a full-vocab sort in the decode
        # hot loop; the smallest of the k kept values is the threshold.
        kth = lax.top_k(logits, k)[0][:, -1][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]  # descending
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep every token whose PRECEDING mass is < top_p (the first
        # token always survives; the one that crosses the threshold is
        # included, matching the standard nucleus definition).
        keep_sorted = (cum - probs) < top_p
        # Threshold back in logit space: the smallest kept logit.
        cutoff = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf),
            axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _filter_logits_traced(logits: jax.Array, top_k: jax.Array,
                          top_p: jax.Array) -> jax.Array:
    """:func:`_filter_logits` with TRACED per-slot ``top_k``/``top_p``
    — the continuous engine samples every live slot in ONE compiled
    program, so the filters can't be compile-time constants. Same
    masking values (k-th-largest threshold via sort instead of
    ``lax.top_k``; identical nucleus cutoff math), with the
    enable/disable branches as ``jnp.where`` gates so a disabled
    filter is bit-for-bit a no-op, exactly like the skipped Python
    branch in the solo path. logits: (V,) f32."""
    V = logits.shape[-1]
    desc = jnp.sort(logits)[::-1]
    kth = desc[jnp.clip(top_k, 1, V) - 1]  # k-th largest == top_k's
    logits = jnp.where((top_k > 0) & (logits < kth), -jnp.inf, logits)
    desc2 = jnp.sort(logits)[::-1]
    probs = jax.nn.softmax(desc2)
    cum = jnp.cumsum(probs)
    keep = (cum - probs) < top_p
    cutoff = jnp.min(jnp.where(keep, desc2, jnp.inf))
    return jnp.where((top_p < 1.0) & (logits < cutoff), -jnp.inf,
                     logits)


def sample_token_rows(logits: jax.Array, keys: jax.Array,
                      steps: jax.Array, temps: jax.Array,
                      top_ks: jax.Array, top_ps: jax.Array
                      ) -> jax.Array:
    """Per-ROW sampling for the continuous engine step: row i draws
    with ITS OWN key folded at ITS OWN emitted-token index, so a
    co-batched sampled request sees exactly the RNG stream its solo
    (B=1) call would — ``jax.random.categorical(key, (1, V)) ==
    argmax(logits + gumbel(key, (1, V)))`` (asserted in tests), over
    the identically filtered/temperature-scaled logits. Rows with
    ``temperature == 0`` take the plain argmax (the greedy path).

    logits: (B, V) f32; keys: (B, 2) uint32 per-request PRNG keys;
    steps: (B,) emitted-token index (0 = first token, matching the
    solo path's ``fold_in(rng, 0)`` prefill draw)."""
    V = logits.shape[-1]

    def one(lg, key, step, t, k, p):
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        x = lg.astype(jnp.float32) / jnp.where(t > 0, t, 1.0)
        x = _filter_logits_traced(x, k, p)
        # (1, V) gumbel then [0]: the exact draw categorical makes on
        # a (1, V) logits batch — the solo path's shape.
        g = jax.random.gumbel(jax.random.fold_in(key, step), (1, V))[0]
        samp = jnp.argmax(x + g, axis=-1).astype(jnp.int32)
        return jnp.where(t > 0.0, samp, greedy)

    return jax.vmap(one)(logits, keys, steps, temps, top_ks, top_ps)


def generate(params: dict, cfg: tfm.TransformerConfig,
             prompt: jax.Array, max_new_tokens: int,
             temperature: float = 0.0,
             rng: jax.Array | None = None,
             top_k: int = 0, top_p: float = 1.0,
             stop_token: int = -1, pad_token: int = 0,
             repetition_penalty: float = 1.0,
             prompt_lens: jax.Array | None = None) -> jax.Array:
    """Generate ``max_new_tokens`` continuations of ``prompt`` (B, S).

    One compiled program (cached per cfg/shape/sampling params):
    prefill then a ``lax.scan`` decode loop. ``temperature == 0`` →
    greedy; else softmax sampling, optionally filtered to the top-k
    logits and/or the top-p (nucleus) probability mass.
    ``stop_token >= 0``: output positions after a row's first stop
    token are filled with ``pad_token`` (static-shape early stopping —
    the loop length never varies, only the output mask).
    ``repetition_penalty > 1`` discounts logits of every token already
    seen (prompt + emitted, HF semantics) — applies to greedy too.
    ``prompt_lens`` (B,): the prompt batch is LEFT-padded ragged
    (``pad_prompts``); lengths are traced, so one compiled program
    serves any mix of lengths at this padded shape. Pad keys are
    masked and RoPE offsets are per-row, so a GREEDY row decodes
    exactly as it would solo; sampled rows draw from the batch-shaped
    RNG stream, which differs from a solo call (same caveat as
    uniform batching — the serving batcher coalesces greedy only).
    """
    B, S = prompt.shape
    total = S + max_new_tokens
    if total > cfg.max_seq:
        raise ValueError(
            f"generate: prompt {S} + new {max_new_tokens} exceeds "
            f"max_seq {cfg.max_seq}"
        )
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"generate: top_p must be in (0, 1], got {top_p}")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if temperature == 0.0:
        # Greedy ignores the filters — normalize them out of the
        # compile-cache key so differing sampling params can't force
        # redundant recompiles of an identical program.
        top_k, top_p = 0, 1.0
    if repetition_penalty <= 0.0:
        raise ValueError(
            f"generate: repetition_penalty must be > 0, "
            f"got {repetition_penalty}")
    lens = None
    if prompt_lens is not None:
        lens = jnp.asarray(prompt_lens, jnp.int32)
        if lens.shape != (B,):
            raise ValueError(
                f"generate: prompt_lens shape {lens.shape} != ({B},)")
        ln = np.asarray(lens)
        if (ln <= 0).any() or (ln > S).any():
            raise ValueError(
                f"generate: prompt_lens must be in [1, {S}], got "
                f"range [{ln.min()}, {ln.max()}]")
    run = _compiled_generate(cfg, B, S, int(max_new_tokens),
                             float(temperature), int(top_k),
                             float(top_p), float(repetition_penalty))
    out = run(params, prompt, lens, rng)
    if stop_token >= 0:
        # Post-processing OUTSIDE the jitted program: everything after
        # a row's first stop token becomes pad. Keeping stop/pad out of
        # the compile key means two tokenizers' EOS ids share one
        # compiled decode program; the O(B·max_new) mask is trivial.
        hit = out == stop_token
        after_stop = (jnp.cumsum(hit.astype(jnp.int32), axis=1)
                      - hit.astype(jnp.int32)) > 0
        out = jnp.where(after_stop, jnp.int32(pad_token), out)
    return out
