"""Latent K,V attention, with or without a learned sparse-attention
indexer.

The attention of the DeepSeek-V2/V3 line (MLA), alone or with
DeepSeek-V3.2's indexer in front of it, as ``TransformerConfig.latent``
sizes it:

- **Latent cache.** A token holds, a layer, the RMS-normed latent
  ``cKV`` (``kv_rank``) with one rotary key ``kR`` (``rope_dim``)
  shared by all heads — bank ``ckv``, its row padded to whole lane
  tiles (``LatentAttention.cache_dim``) — instead of K and V per head,
  and with an indexer its key ``kI`` (``index_dim``) — bank ``ki``.
- **Indexer** (``LatentAttention.indexer``). ``I[t, s] = Σ_j w[t, j] ·
  ReLU(qI[t, j] · kI[s])`` over ``index_heads``; a query attends the
  ``index_topk`` keys ``s ≤ t`` with the largest ``I`` (all of them
  while fewer are held). **Without one** a query attends every key
  ``s ≤ t``: nothing is selected, and in the absorbed form the cache
  row is one K,V head of ``cache_dim`` lanes shared by all query heads
  whose values are its leading ``kv_rank`` lanes, so the paged
  programs read it through the block-list and table walks GQA has
  (``generate._live_block_attention``, ``generate._table_attention``;
  :func:`absorb_query` and :func:`expand_values` around them).
- **Two forms of one attention.** *Expanded* (:func:`attend_expanded`,
  the contiguous forward): per-head keys and values are made from the
  latent and every unselected key is masked. *Absorbed*
  (:func:`attend_paged`, the paged decode step and prefill chunk):
  ``W_UK`` is folded into the query and ``W_UV`` applied after the
  sum, so the rows of ``ckv`` are read as they lie: a decode step
  gathers its selected rows through the block table, a prefill chunk
  walks the table with the selection as a mask.
  tests/benchmark/test_bench_glm.py holds the two to each other and to
  the plain reference.

The decode step (one query a lane) runs the absorbed form over the
lanes that hold a request, a tile of them at a time, in one loop
whose trip count is data (``generate.live_lane_list``): indexing,
selection, gather and attention cost what is live, not ``n_slots``.

Rotary positions rotate adjacent pairs (``rope_interleave``), on all of
``kR`` / ``q^rope`` and on the leading ``index_rope_dim`` dims of the
indexer's q and k; with ``cfg.rope_yarn`` the tables are YaRN's and the
scores carry its factor (:func:`score_scale`). Scopes: ``qkv`` (norms
and projections), ``index``
(the indexer's projections and scores), ``select`` (top-k),
``kv_gather``, ``attn``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ptype_tpu.models import transformer as tfm

#: Queries scored, selected and attended at a time by the paged path:
#: a block of a prefill chunk walks the table alone, its float32 scores
#: 34 MB a tile at GLM-5's 64 heads. All 512 queries of a chunk walking
#: together (134 MB a tile, and every query's indexer scores held
#: through the walk) ran a chunk at 16.4k of context in 134.2 ms
#: against 113.7 (chip run, PR 38).
QUERY_BLOCK = 128
#: The indexer's LayerNorm epsilon (DeepSeek-V3.2's inference code).
INDEX_NORM_EPS = 1e-6
_NEG = -1e30


def init_attention(key, cfg: tfm.TransformerConfig, n: int) -> dict:
    """The attention half of ``n`` stacked layers."""
    la, D, H, pd = cfg.latent, cfg.d_model, cfg.n_heads, cfg.param_dtype
    resid = 0.02 / (2.0 * cfg.n_layers) ** 0.5
    ks = jax.random.split(key, 10)

    def norm(k, shape, scale=0.02):
        return tfm.scaled_normal(k, shape, scale, pd)

    attn = {
        "attn_norm": jnp.ones((n, D), pd),
        "w_dq": norm(ks[0], (n, D, la.q_rank)),
        "q_norm": jnp.ones((n, la.q_rank), pd),
        "w_uq": norm(ks[1], (n, la.q_rank, H, la.qk_dim)),
        "w_dkv": norm(ks[2], (n, D, la.row_dim)),
        "kv_norm": jnp.ones((n, la.kv_rank), pd),
        "w_uk": norm(ks[3], (n, la.kv_rank, H, la.nope_dim)),
        "w_uv": norm(ks[4], (n, la.kv_rank, H, la.v_dim)),
        "wo": norm(ks[5], (n, H, la.v_dim, D), resid),
    }
    if la.indexer:
        attn.update(
            w_iq=norm(ks[6], (n, la.q_rank, la.index_heads, la.index_dim)),
            w_ik=norm(ks[7], (n, D, la.index_dim)),
            ik_norm=jnp.ones((n, la.index_dim), pd),
            ik_norm_b=norm(ks[8], (n, la.index_dim)),
            w_iw=norm(ks[9], (n, D, la.index_heads)))
    return attn


def rope_interleaved(x, sin, cos):
    """Rotate adjacent pairs ``(x[2i], x[2i+1])`` of the last dim.
    ``sin``/``cos``: (B or 1, Q, d/2); ``x``: (B, Q, d), or
    (B, Q, heads, d)."""
    if x.ndim == sin.ndim + 1:
        sin, cos = sin[..., None, :], cos[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rope_lead(x, sin, cos, n: int):
    """Rotate the leading ``n`` dims of the last axis; the rest pass."""
    return jnp.concatenate(
        [rope_interleaved(x[..., :n], sin, cos), x[..., n:]], axis=-1)


def _layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def project(x, layer, cfg: tfm.TransformerConfig, positions):
    """Everything attention needs of ``x`` (B, Q, D) at ``positions``
    ((Q,) shared or (B, Q)): ``q_nope`` (B, Q, H, nope), ``q_rope``
    (B, Q, H, rope), the cache row ``ckv`` (B, Q, cache_dim) =
    [normed latent ; rotated shared key ; zeros to whole lane tiles],
    and the indexer's ``qi``
    (B, Q, J, di), ``ki`` (B, Q, di) and head weights ``wi``
    (B, Q, J) float32, each None without an indexer."""
    la, dt, eps = cfg.latent, cfg.dtype, cfg.norm_eps
    positions = jnp.asarray(positions)
    if positions.ndim == 1:
        positions = positions[None]
    with jax.named_scope("qkv"):
        h = tfm.rms_norm(x, layer["attn_norm"], eps)
        cq = tfm.rms_norm(
            jnp.einsum("bsd,dr->bsr", h, layer["w_dq"].astype(dt)),
            layer["q_norm"], eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, layer["w_uq"].astype(dt))
        sin, cos = tfm.rope_tables(cfg, positions=positions,
                                   dim=la.rope_dim)
        q_nope = q[..., :la.nope_dim]
        q_rope = rope_interleaved(q[..., la.nope_dim:], sin, cos)
        kv = jnp.einsum("bsd,dc->bsc", h, layer["w_dkv"].astype(dt))
        pad = la.cache_dim - la.row_dim  # the bank's row is whole tiles
        ckv = jnp.concatenate(
            [tfm.rms_norm(kv[..., :la.kv_rank], layer["kv_norm"], eps),
             rope_interleaved(kv[..., la.kv_rank:], sin, cos),
             jnp.zeros(kv.shape[:-1] + (pad,), kv.dtype)], axis=-1)
    if not la.indexer:
        return q_nope, q_rope, ckv, None, None, None
    with jax.named_scope("index"):
        isin, icos = tfm.rope_tables(cfg, positions=positions,
                                     dim=la.index_rope_dim)
        qi = _rope_lead(
            jnp.einsum("bsr,rjk->bsjk", cq, layer["w_iq"].astype(dt)),
            isin, icos, la.index_rope_dim)
        ki = _rope_lead(
            _layer_norm(
                jnp.einsum("bsd,dk->bsk", h, layer["w_ik"].astype(dt)),
                layer["ik_norm"], layer["ik_norm_b"], INDEX_NORM_EPS),
            isin, icos, la.index_rope_dim)
        wi = jnp.einsum("bsd,dj->bsj", h, layer["w_iw"].astype(dt),
                        preferred_element_type=jnp.float32)
        wi = wi * (la.index_heads ** -0.5 * la.index_dim ** -0.5)
    return q_nope, q_rope, ckv, qi, ki, wi


def score_scale(cfg: tfm.TransformerConfig) -> float:
    """What a score is multiplied by: ``qk_dim ** -0.5`` and YaRN's
    factor (``tfm.yarn_score_factor``)."""
    return cfg.latent.qk_dim ** -0.5 * tfm.yarn_score_factor(cfg)


def absorb_query(q_nope, q_rope, layer, cfg: tfm.TransformerConfig):
    """Absorb W_UK into the query: (q^nope W_UK^T) · cKV = q^nope ·
    (W_UK cKV); the rotary part rides beside it, so one product against
    the cache row [cKV ; kR ; 0] gives the score. → (B, Q, H,
    cache_dim)."""
    la, dt = cfg.latent, cfg.dtype
    B, Q, H, _ = q_nope.shape
    return jnp.concatenate(
        [jnp.einsum("bqhn,chn->bqhc", q_nope, layer["w_uk"].astype(dt)),
         q_rope, jnp.zeros((B, Q, H, la.cache_dim - la.row_dim), dt)],
        axis=-1)


def expand_values(ol, layer, cfg: tfm.TransformerConfig):
    """W_UV after the sum: the attention's output over the latent
    (B, Q, H, kv_rank) → per-head values (B, Q, H, v)."""
    return jnp.einsum("bqhc,chv->bqhv", ol,
                      layer["w_uv"].astype(cfg.dtype))


def _scaled(scores, cfg: tfm.TransformerConfig):
    scores = scores / jnp.sqrt(jnp.float32(cfg.latent.qk_dim))
    f = tfm.yarn_score_factor(cfg)
    return scores if f == 1.0 else scores * jnp.float32(f)


def index_scores(qi, wi, ki):
    """``I`` (B, Q, T) float32 of queries ``qi`` (B, Q, J, di), ``wi``
    (B, Q, J) on keys ``ki`` (B, T, di)."""
    dots = jnp.einsum("bqjd,btd->bqjt", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(wi[..., None] * jax.nn.relu(dots), axis=2)


def attend_expanded(x, layer, cfg: tfm.TransformerConfig):
    """The expanded form over one contiguous sequence from position 0:
    x (B, S, D) → o (B, S, H, v). Per-head keys and values are made
    from every token's latent; a query's unselected keys are masked
    (without an indexer: the keys after it)."""
    la, dt = cfg.latent, cfg.dtype
    B, S, _ = x.shape
    q_nope, q_rope, ckv, qi, ki, wi = project(x, layer, cfg,
                                              jnp.arange(S))
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    if la.indexer:
        with jax.named_scope("index"):
            I = jnp.where(causal[None], index_scores(qi, wi, ki), _NEG)
        with jax.named_scope("select"):
            k = min(la.index_topk, S)
            _, idx = lax.top_k(I, k)  # (B, S, k)
            chosen = jnp.zeros((B, S, S), jnp.bool_).at[
                jnp.arange(B)[:, None, None],
                jnp.arange(S)[None, :, None], idx].set(True)
            mask = chosen & causal[None]
    else:
        mask = causal[None]
    with jax.named_scope("attn"):
        c_kv = ckv[..., :la.kv_rank]
        k_r = ckv[..., la.kv_rank:la.row_dim]
        k_nope = jnp.einsum("bsc,chn->bshn", c_kv,
                            layer["w_uk"].astype(dt))
        v = jnp.einsum("bsc,chv->bshv", c_kv, layer["w_uv"].astype(dt))
        scores = (jnp.einsum("bqhn,bshn->bhqs", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bsr->bhqs", q_rope, k_r,
                               preferred_element_type=jnp.float32))
        scores = _scaled(scores, cfg)
        scores = jnp.where(mask[:, None], scores, _NEG)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        return jnp.einsum("bhqs,bshv->bqhv", probs, v)


def kept_by_top_k(I, thr, last, span: int):
    """What ``lax.top_k`` kept of the scores ``I`` (B, Q, T) float32, as
    ``generate._table_attention``'s ``keep`` over tiles of ``span``
    positions: ``thr`` (B, Q) is each query's k-th score and ``last``
    (B, Q) the position of that k-th entry. ``top_k`` puts the lower
    position first among equal scores, so it kept exactly the positions
    that score over ``thr`` and those that score ``thr`` at or before
    ``last``: the same set, ties included, with no scatter."""
    pad = -I.shape[-1] % span
    if pad:
        I = jnp.pad(I, ((0, 0), (0, 0), (0, pad)), constant_values=_NEG)
    thr, last = thr[..., None], last[..., None]

    def keep(first, at):
        tile = lax.dynamic_slice_in_dim(I, first, span, axis=2)
        return (tile > thr) | ((tile == thr) & (at[:, None, :] <= last))

    return keep


def attend_paged(q_nope, q_rope, qi, wi, ckv_bank, ki_bank, tables,
                 limits, layer, cfg: tfm.TransformerConfig,
                 whole_context: bool = False, lanes=None):
    """The absorbed form through a block table, behind the indexer
    (without one the paged programs walk the block list or the table:
    ``generate._paged_layers``). Queries (B, Q, ...) as
    :func:`project` gives them; ``ckv_bank`` (rows, bt, cache_dim)
    and ``ki_bank`` (rows, bt, di) the banks in the flat view;
    ``tables`` (B, nb) this layer's rows of them in position order;
    ``limits`` (B,) or (B, Q): a query attends positions ``< limit``.
    The indexer scores every position of the table and the top
    ``index_topk`` are selected, :data:`QUERY_BLOCK` queries at a time.
    A decode step's rows (one query a row, a table each) gather the
    selected rows of ``ckv`` alone and read them. ``whole_context`` (a
    prefill chunk says so: many queries a table): each block of queries
    walks the table ONCE, a tile of blocks at a time
    (``generate._table_attention``), every query's float32 scores
    against the tile, with the selection as the mask
    (:func:`kept_by_top_k`), into a float32 running softmax; the trip
    count follows the chunk's context, and no query's selected rows
    are copied out. ``lanes`` (``generate.live_lane_list``'s
    pair; a decode step's, one query a lane): the lanes that hold a
    request. With it the same arithmetic runs on those lanes alone, a
    tile of them a trip of one loop whose trip count is data (ONE
    compiled program whatever the load), and every other lane reads
    zeros; without it, on all ``B``. → o (B, Q, H, v)."""
    la, dt = cfg.latent, cfg.dtype
    B, Q, H, _ = q_nope.shape
    nb, bt = tables.shape[1], ckv_bank.shape[1]
    T = nb * bt
    k = min(la.index_topk, T)
    limits = jnp.asarray(limits)
    if limits.ndim == 1:
        limits = jnp.broadcast_to(limits[:, None], (B, Q))

    def keys(tables):
        with jax.named_scope("index"):
            return ki_bank[tables].reshape(-1, T, la.index_dim)

    def split(a):
        return jnp.moveaxis(a.reshape(
            B, Q // QUERY_BLOCK, QUERY_BLOCK, *a.shape[2:]), 1, 0)

    def merge(a):
        return jnp.moveaxis(a, 0, 1).reshape(B, Q, *a.shape[3:])

    def blocks(f, *xs):
        """``f`` over the queries a block of QUERY_BLOCK at a time."""
        if Q <= QUERY_BLOCK:
            return f(*xs)
        if Q % QUERY_BLOCK:
            raise ValueError(f"{Q} queries do not divide into blocks of "
                             f"{QUERY_BLOCK}")
        return merge(lax.map(lambda xs: f(*xs), tuple(map(split, xs))))

    ki = keys(tables) if lanes is None else None
    with jax.named_scope("attn"):
        qa = absorb_query(q_nope, q_rope, layer, cfg)

    if whole_context:
        from ptype_tpu.models import generate as gen

        def pick(qi, wi, limits):
            n, Qb = qi.shape[:2]
            with jax.named_scope("index"):
                I = index_scores(qi, wi, ki)
                # -0.0 as 0.0: top_k orders the two, a mask's == does not.
                I = jnp.where(jnp.arange(T)[None, None] < limits[..., None],
                              jnp.where(I == 0, 0.0, I), _NEG)
            with jax.named_scope("select"):
                vals, idx = lax.top_k(I.reshape(n * Qb, T), k)
            return I, vals[:, -1].reshape(n, Qb), idx[:, -1].reshape(n, Qb)

        span = min(nb, gen.TABLE_TILE_BLOCKS) * bt

        def walk(qa, qi, wi, limits):
            return gen._table_attention(
                qa, ckv_bank[:, :, None], None, tables, limits, 0, "attn",
                scale=score_scale(cfg), v_dim=la.kv_rank,
                keep=kept_by_top_k(*pick(qi, wi, limits), span))

        with jax.named_scope("attn"):
            return expand_values(blocks(walk, qa, qi, wi, limits), layer,
                                 cfg)

    def block(qa, qi, wi, limits, tables=tables, ki=ki):
        n, Qb = qa.shape[:2]
        with jax.named_scope("index"):
            I = index_scores(qi, wi, ki)
            I = jnp.where(jnp.arange(T)[None, None] < limits[..., None],
                          I, _NEG)
        with jax.named_scope("select"):
            # Rows flat: a (B, 1, T) operand sorts in (1, 128) tiles,
            # five times slower than (B, T) (chip run, PR 28).
            _, idx = lax.top_k(I.reshape(n * Qb, T), k)
            idx = idx.reshape(n, Qb, k)  # positions
            ok = idx < limits[..., None]
        with jax.named_scope("kv_gather"):
            blk = jnp.take_along_axis(tables[:, None, :], idx // bt,
                                      axis=-1)
            sel = ckv_bank[blk, idx % bt]  # (B, Qb, k, cache_dim)
        with jax.named_scope("attn"):
            scores = jnp.einsum("bqhc,bqkc->bqhk", qa, sel,
                                preferred_element_type=jnp.float32)
            scores = _scaled(scores, cfg)
            scores = jnp.where(ok[:, :, None], scores, _NEG)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            ol = jnp.einsum("bqhk,bqkc->bqhc", probs,
                            sel[..., :la.kv_rank])
            return expand_values(ol, layer, cfg)

    if lanes is not None:
        # The banks are closed over and only read: a bank in the loop's
        # state is copied (2.27 GB at GLM-5's pool: PERF.md §6, PR 29).
        lst, n_tiles = jnp.asarray(lanes[0]), lanes[1]

        def tile(t, o):
            ids = lst[t]  # (tile,) lanes; the pad id B is no lane
            at = jnp.minimum(ids, B - 1)
            held = jnp.where((ids < B)[:, None], limits[at], 0)
            own = tables[at]
            return o.at[ids].set(
                block(qa[at], qi[at], wi[at], held, own, keys(own)),
                mode="drop")

        with jax.named_scope("attn"):
            return lax.fori_loop(0, n_tiles, tile,
                                 jnp.zeros((B, Q, H, la.v_dim), dt))
    return blocks(block, qa, qi, wi, limits)
