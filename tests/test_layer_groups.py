"""What ISSUE 28 added to the model layer, at test size: a layer stack
of several groups (one ``lax.scan`` a run of identical layers, the
paged loop and the contiguous forward walking them with one carry), the
cache described by the model (``cache_spec``) and allocated from that by
the pool, and the dropless router over a share of the experts."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu.models import generate as gen
from ptype_tpu.models import sparse_mla
from ptype_tpu.models import transformer as tfm
from ptype_tpu.serve_engine import BlockPool

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
#: One dense layer, then two layers of capacity-routed experts, GQA.
GROUPED = tfm.preset("tiny-moe", n_layers=3, n_dense_layers=1, d_ff=64,
                     capacity_factor=8.0, tie_embeddings=False, **F32)
DROPLESS = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64, max_seq=64,
    n_experts=8, expert_top_k=2, d_ff_expert=16, n_shared_experts=1,
    moe_router="sigmoid_bias", routed_scale=2.5, **F32)
#: The tiny preset with two K,V heads, and the same behind latent
#: attention with the indexer.
DENSE = tfm.preset("tiny", n_kv_heads=2)
LATENT = dataclasses.replace(DENSE, latent=tfm.LatentAttention(
    q_rank=16, kv_rank=8, nope_dim=6, rope_dim=2, v_dim=8, index_heads=2,
    index_dim=4, index_topk=8, index_rope_dim=2))
BT, N_BLOCKS = 16, 10


def test_stack_of_two_groups_holds_one_stacked_dict_a_group():
    assert tfm.layer_groups(GROUPED) == (("dense", 1), ("experts", 2))
    assert tfm.layer_groups(tfm.preset("tiny")) == (("dense", 2),)
    assert tfm.layer_groups(tfm.preset("tiny-moe")) == (("experts", 2),)
    params = tfm.init_params(jax.random.PRNGKey(0), GROUPED)
    dense, experts = params["blocks"]
    assert dense["w_gate"].shape == (1, 64, 64) and "router" not in dense
    assert experts["w_gate"].shape == (2, 4, 64, 64)
    assert [(first, n) for _, first, n in tfm.block_groups(
        params, GROUPED)] == [(0, 1), (1, 2)]
    # A stack of one group keeps its one stacked dict, as checkpoints
    # hold it.
    one = tfm.init_params(jax.random.PRNGKey(0), tfm.preset("tiny"))
    assert isinstance(one["blocks"], dict)
    assert [(f, n) for _, f, n in tfm.block_groups(
        one, tfm.preset("tiny"))] == [(0, 2)]
    with pytest.raises(ValueError, match="2 layer group"):
        tfm.block_groups(params, tfm.preset("tiny"))


def test_paged_loop_walks_the_groups_as_the_contiguous_forward_does():
    """Prefill in two chunks, then decode, through ``_paged_layers``
    over a two-group stack: each logit vector equals the contiguous
    forward's, and layer ``l`` of the banks is the layer's own (group
    two's first layer is bank layer 1, not 0)."""
    params = tfm.init_params(jax.random.PRNGKey(1), GROUPED)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (40,), 1,
                                         GROUPED.vocab_size), np.int32)
    want = np.asarray(tfm.forward(params, jnp.asarray(toks)[None],
                                  GROUPED))[0]
    shape = (3, N_BLOCKS, BT, GROUPED.kv_heads, GROUPED.head_dim)
    banks = {"k": jax.random.normal(jax.random.PRNGKey(3), shape,
                                    jnp.float32),
             "v": jax.random.normal(jax.random.PRNGKey(4), shape,
                                    jnp.float32)}
    table = jnp.asarray([2, 4, 6, 0, 0, 0, 0, 0], jnp.int32)
    for start, n in ((0, 16), (16, 16)):
        lg, banks, _ = gen.prefill_chunk_banks(
            params, jnp.asarray(toks[start:start + n])[None],
            jnp.int32(start), jnp.int32(n), GROUPED, banks, table)
    np.testing.assert_allclose(np.asarray(lg)[0], want[31], atol=2e-5)
    for pos in range(32, 40):
        lg, banks, _ = gen.decode_step_banks(
            params, jnp.asarray(toks[pos:pos + 1]), jnp.asarray([pos]),
            GROUPED, banks, table[None], table[None][:, pos // BT],
            jnp.asarray([pos % BT]))
        np.testing.assert_allclose(np.asarray(lg)[0], want[pos],
                                   atol=2e-5)


def test_pool_allocates_what_the_model_says_a_token_holds():
    assert tfm.cache_spec(DENSE) == {"k": (2, 16), "v": (2, 16)}
    pool = BlockPool(DENSE, 6, 16)
    assert pool.banks["k"].shape == (2, 6, 16, 2, 16)
    assert pool.k is pool.banks["k"] and pool.v is pool.banks["v"]
    pool.k = pool.k + 1
    assert float(pool.banks["k"][0, 0, 0, 0, 0]) == 1.0
    assert pool.block_shapes() == {"k": (2, 16, 2, 16), "v": (2, 16, 2, 16)}
    assert tfm.cache_spec(LATENT) == {"ckv": (10,), "ki": (4,)}
    pool = BlockPool(LATENT, 6, 16)
    assert {n: b.shape for n, b in pool.banks.items()} == {
        "ckv": (2, 6, 16, 10), "ki": (2, 6, 16, 4)}


def _speculate_on_latent():
    from ptype_tpu.serve_engine import PagedGeneratorActor, SpecConfig

    params = tfm.init_params(jax.random.PRNGKey(0), LATENT)
    PagedGeneratorActor(LATENT, params=params, n_slots=2,
                        spec=SpecConfig(draft_params=params,
                                        draft_cfg=LATENT, k=2))


@pytest.mark.parametrize("call, sentence", [
    (lambda: gen.truncated_draft_params({}, LATENT), "latent"),
    (lambda: gen.init_cache(LATENT, 1), "latent"),
    (lambda: tfm.param_specs(LATENT, {"model": 2}), "latent"),
    (lambda: tfm.flops_per_token(LATENT, 64), "latent"),
    (_speculate_on_latent, "next-token module"),
], ids=["truncated_draft_params", "init_cache", "param_specs",
        "flops_per_token", "spec_config"])
def test_what_a_latent_configuration_cannot_run_is_refused(call, sentence):
    """Latent attention runs the plain paged decode step and prefill
    chunk and nothing else: the contiguous cache, the truncated draft,
    speculation, the training shardings and the dense FLOP count each
    say so in a sentence, not with a shape error."""
    with pytest.raises(ValueError, match=sentence):
        call()


def _layer(cfg, key=0):
    params = tfm.init_params(jax.random.PRNGKey(key), cfg)
    return jax.tree.map(lambda a: a[0], params["blocks"])


def _by_hand(h, layer, cfg):
    """Every expert on every token, weighted by the gate or 0."""
    s = jax.nn.sigmoid(h @ layer["router"])
    _, idx = jax.lax.top_k(s + layer["router_bias"], cfg.expert_top_k)
    w = jnp.take_along_axis(s, idx, -1)
    g = cfg.routed_scale * w / w.sum(-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(cfg.n_experts):
        ge = jnp.sum(jnp.where(idx == e, g, 0.0), -1)
        out = (jax.nn.silu(h @ layer["w_gate"][e])
               * (h @ layer["w_up"][e])) @ layer["w_down"][e]
        y = y + ge[:, None] * out
    shared = (jax.nn.silu(h @ layer["ws_gate"])
              * (h @ layer["ws_up"])) @ layer["ws_down"]
    return y + shared, idx


@pytest.mark.parametrize("crowd", [False, True], ids=["spread", "crowded"])
def test_dropless_router_drops_no_token_at_any_load(crowd):
    """Sigmoid scores, selection by score + bias, gates normalised and
    scaled, the shared expert: against every expert computed on every
    token. ``crowded``: the bias sends every token to experts 0 and 1
    (a capacity of 1.25 x the even share would drop three quarters)."""
    layer = _layer(DROPLESS)
    if crowd:
        layer["router_bias"] = layer["router_bias"].at[:2].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32), jnp.float32)
    y, load = tfm._moe_dropless(h, layer, DROPLESS)
    want, idx = _by_hand(h.reshape(48, 32), layer, DROPLESS)
    np.testing.assert_allclose(np.asarray(y).reshape(48, 32),
                               np.asarray(want), atol=2e-5)
    assert load.shape == (9,) and int(load[-1]) == 0
    np.testing.assert_array_equal(
        np.asarray(load[:8]), np.bincount(np.asarray(idx).ravel(),
                                          minlength=8))
    if crowd:
        assert int(load[0]) == int(load[1]) == 48


def _padded(h, layer, cfg, live=None):
    """The product the tile loop replaced, kept here as the formula it
    is held to: every held expert's assignments scattered into its rows
    of an (held, T, D) buffer, one batched product a matrix over all of
    it, a row gathered back an assignment. → (y, load), ``load`` of the
    tokens ``live`` marks."""
    B, S, D = h.shape
    k, (first, count), T = cfg.expert_top_k, cfg.held, B * S
    x = h.reshape(T, D)
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x, layer["router"],
                                  precision="highest"))
    _, idx = jax.lax.top_k(s + layer["router_bias"], k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    g = cfg.routed_scale * w / jnp.sum(w, axis=-1, keepdims=True)
    local = idx - first
    here = ((local >= 0) & (local < count)).reshape(-1)
    e = jnp.where(here, local.reshape(-1), count)
    onehot = jax.nn.one_hot(e, count + 1, dtype=jnp.int32)
    counted = onehot if live is None else onehot * jnp.repeat(
        live.reshape(T).astype(jnp.int32), k)[:, None]
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    inv = jnp.zeros((count, T), jnp.int32).at[
        e, jnp.where(here, pos, T)].set(jnp.arange(T * k) // k + 1,
                                        mode="drop")
    X = jnp.where((inv > 0)[..., None], x[jnp.maximum(inv - 1, 0)], 0)
    a = jnp.einsum("ecd,edf->ecf", X, layer["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", X, layer["w_up"])
    Y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(a) * u, layer["w_down"])
    ys = Y[jnp.minimum(e, count - 1), jnp.clip(pos, 0, T - 1)]
    ys = ys * jnp.where(here, g.reshape(-1), 0.0)[:, None]
    y = jnp.sum(ys.reshape(T, k, D), axis=1).reshape(B, S, D)
    return y + tfm._swiglu(h, layer["ws_gate"], layer["ws_up"],
                           layer["ws_down"], jnp.float32), jnp.sum(
                               counted, axis=0)


def _share(layer, cfg, first, count):
    """The layer and configuration of a member that holds ``count``
    experts from ``first``."""
    held = {m: layer[m][first:first + count]
            for m in ("w_gate", "w_up", "w_down")}
    return {**layer, **held}, dataclasses.replace(
        cfg, experts_held=(first, count))


#: shape (B, S); held (first, count); the experts the bias sends every
#: token to; the lanes that are dead (None: no ``live`` given).
TILE_CASES = {
    "no-held-expert-hit": ((2, 12), (6, 2), (0, 1), None),
    "one-expert-takes-every-row": ((2, 12), (0, 8), (3,), None),
    "every-expert-a-partial-tile": ((2, 12), (0, 8), (), None),
    "assignments-not-a-multiple-of-the-tile": ((1, 13), (2, 5), (), None),
    "dead-lanes": ((10, 1), (0, 8), (), (0, 3, 4, 8)),
    "dead-lanes-that-would-crowd": ((10, 1), (0, 8), (), "alike"),
    "chunk-tile": ((1, 160), (0, 8), (), None),
    "chunk-tile-one-expert-takes-every-row": ((1, 131), (1, 6), (4,), None),
    "chunk-tile-pads": ((1, 128), (0, 8), (), tuple(range(50, 128))),
}


@pytest.mark.parametrize("case", list(TILE_CASES))
def test_tile_loop_gives_what_the_padded_product_gives(case):
    """The grouped product over tiles of routed rows against the padded
    ``(held, T, D)`` formula, float32: the outputs of live tokens to
    rounding, the load exactly, and the tiles the loop visits
    ``Σ_e ceil(n_e / tile)`` over the live held assignments, at both
    tile constants (T under and from ``EXPERT_TILE_CHUNK``)."""
    (B, S), (first, count), crowd, dead = TILE_CASES[case]
    layer, cfg = _share(_layer(DROPLESS, key=3), DROPLESS, first, count)
    if crowd:
        layer["router_bias"] = layer["router_bias"].at[
            jnp.asarray(crowd)].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(11), (B, S, 32), jnp.float32)
    live = None
    if dead == "alike":
        # Inactive lanes hold one token at one position: the same row.
        dead = (1, 2, 4, 5, 6, 7, 9)
        h = h.at[jnp.asarray(dead)].set(h[1])
    if dead is not None:
        mask = np.ones(B * S, bool)
        mask[list(dead)] = False
        live = jnp.asarray(mask.reshape(B, S))
    T, tile = B * S, tfm.expert_tile(B * S)
    assert tile == (tfm.EXPERT_TILE_CHUNK if T >= tfm.EXPERT_TILE_CHUNK
                    else tfm.EXPERT_TILE_STEP)
    y, load = jax.jit(lambda h: tfm._moe_dropless(h, layer, cfg, live))(h)
    want, want_load = _padded(h, layer, cfg, live)
    keep = np.ones(T, bool) if live is None else np.asarray(live).ravel()
    np.testing.assert_allclose(np.asarray(y).reshape(T, -1)[keep],
                               np.asarray(want).reshape(T, -1)[keep],
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(want_load))
    assert load.shape == (count + 1,)
    assert int(load.sum()) == int(keep.sum()) * cfg.expert_top_k
    n = np.asarray(load)[:-1]
    tiles, hit = tfm.expert_tiles(load, T)
    assert int(tiles) == int(np.sum(-(-n // tile)))
    assert int(hit) == int(np.sum(n > 0))
    # What each case is there for.
    if case == "no-held-expert-hit":
        assert int(tiles) == 0 and int(load[-1]) == T * 2
    if "one-expert-takes-every-row" in case:
        assert int(n[crowd[0] - first]) == T and int(tiles) > -(-T // tile)
    if case == "every-expert-a-partial-tile":
        assert (n > 0).all() and (n % tile > 0).all()
    if case == "assignments-not-a-multiple-of-the-tile":
        assert int(n.sum()) % tile
    if case == "dead-lanes-that-would-crowd":
        # Counted, the seven rows alike would fill a tile of their own.
        assert int(_padded(h, layer, cfg)[1][:-1].max()) >= 7 > n.max()


def test_tile_loop_reads_a_groups_stack_at_the_layers_index():
    """The paged programs close over a group's ``(n, held, D, F)``
    stacks and hand the layer's index: the same output as the layer's
    own ``(held, D, F)`` slices."""
    params = tfm.init_params(jax.random.PRNGKey(2), DROPLESS)
    stacked = params["blocks"]
    h = jax.random.normal(jax.random.PRNGKey(12), (3, 5, 32), jnp.float32)
    for l in range(DROPLESS.n_layers):
        layer = jax.tree.map(lambda a: a[l], stacked)
        whole = {**layer, **{m: stacked[m]
                             for m in ("w_gate", "w_up", "w_down")}}
        y, load = tfm._moe_dropless(h, whole, DROPLESS, at=jnp.int32(l))
        want, want_load = tfm._moe_dropless(h, layer, DROPLESS)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(load),
                                      np.asarray(want_load))


def test_step_reports_the_tiles_its_layers_visited_and_the_ledger_keeps_them():
    """Through a tiny engine: a decode step returns, behind its load
    counts, the tiles its expert layers' loops visited and the held
    experts they hit, of the live lanes alone; the ledger's summary and
    the ``serve.moe_load`` record say so."""
    from ptype_tpu import trace
    from ptype_tpu.metrics import MetricsRegistry
    from ptype_tpu.serve_engine import PagedGeneratorActor

    cfg = dataclasses.replace(DROPLESS, experts_held=(2, 4))
    params = tfm.init_params(jax.random.PRNGKey(6), cfg)
    assert params["blocks"]["w_gate"].shape[:2] == (2, 4)
    eng = PagedGeneratorActor(cfg, params=params, n_slots=4, max_len=64,
                              block_tokens=16, prefill_chunk=16,
                              n_blocks=12,
                              metrics_registry=MetricsRegistry())
    rec = trace.enable("moe-tiles")
    try:
        prompt = np.arange(3, 23, dtype=np.int32)
        eng.Generate(jnp.asarray(prompt)[None], 9)
    finally:
        trace.disable()
        eng.close()
    s = eng.ledger.summary()
    recs = [sp.attrs for sp in rec.spans() if sp.name == "serve.moe_load"]
    iters = s["moe_load"]["iterations"]
    assert iters == len(recs) == 8
    held = np.sum([[int(c) for c in r["held"].split(":")] for r in recs],
                  axis=0)
    assert held.tolist() == s["moe_load"]["held"]
    # One live lane of four, two choices a layer, two expert layers.
    assert held.sum() + s["moe_load"]["elsewhere"] == iters * 1 * 2 * 2
    # A lone row's choices are distinct experts: a tile a held choice.
    assert sum(r["tiles"] for r in recs) == held.sum() > 0
    assert s["expert_tiles"] == pytest.approx(held.sum() / iters, abs=1e-3)
    assert s["experts_hit"] == pytest.approx(held.sum() / iters / 2,
                                             abs=1e-3)
    assert s["expert_tile_fill"] == pytest.approx(
        1 / tfm.EXPERT_TILE_STEP, abs=1e-4)


def test_selection_is_by_score_plus_bias_and_the_gate_by_score_alone():
    layer = _layer(DROPLESS, key=7)
    layer["router_bias"] = jnp.zeros(8).at[5].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(8), (1, 6, 32), jnp.float32)
    y, load = tfm._moe_dropless(h, layer, DROPLESS)
    assert int(load[5]) == 6  # every token chose expert 5 ...
    want, _ = _by_hand(h[0], layer, DROPLESS)
    # ... and weighs it by its sigmoid score, not by score + 10.
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               atol=2e-5)


def test_rope_rotates_adjacent_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 3, 8))
    sin, cos = tfm.rope_tables(tfm.preset("tiny"),
                               positions=jnp.arange(5)[None], dim=8)
    got = np.asarray(sparse_mla.rope_interleaved(x, sin, cos))
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    rot = z * np.exp(1j * np.arctan2(np.asarray(sin),
                                     np.asarray(cos)))[:, :, None]
    np.testing.assert_allclose(got[..., 0::2], rot.real, atol=1e-6)
    np.testing.assert_allclose(got[..., 1::2], rot.imag, atol=1e-6)
    np.testing.assert_allclose(np.asarray(x[:, 0]), got[:, 0], atol=1e-7)


# ---------------------------------------- the live-lane list (ISSUE 32)

LATENT32 = dataclasses.replace(LATENT, **F32)
LANES, LANE_NB = 10, 4  # ten slots, a reach of four blocks


@pytest.mark.parametrize("active,tile,lanes,n_tiles", [
    ([0, 1, 0, 0, 1, 1, 0], 2, [[1, 4], [5, 7], [7, 7], [7, 7]], 2),
    ([1, 1, 1, 1, 0, 0, 0], 4, [[0, 1, 2, 3], [7, 7, 7, 7]], 1),
    ([0, 0, 0], 2, [[3, 3], [3, 3]], 0),
    ([1, 0, 1], 8, [[0, 2, 3]], 1),  # a tile is at most every slot
    ([1] * 9, None, [list(range(8)), [8] + [9] * 7], 2),
], ids=["scattered", "exactly-a-tile", "empty", "tile-over-slots",
        "module-tile"])
def test_live_lane_list_names_the_live_slots_in_order(active, tile,
                                                     lanes, n_tiles):
    """The live slots' ids in slot order, padded with the id no lane
    has (``n_slots``); the trip count is the tiles in use; the shape
    depends on ``n_slots`` and the tile alone."""
    lst, n = gen.live_lane_list(np.asarray(active, bool), tile)
    assert lst.dtype == np.int32 and n.dtype == np.int32
    assert lst.tolist() == lanes and int(n) == n_tiles
    assert gen.live_lane_list(np.zeros(len(active), bool),
                              tile)[0].shape == lst.shape
    if tile is None:
        assert lst.shape[1] == gen.LIVE_TILE_LANES


LIVE_SETS = {"one-lane": [6], "scattered-three": [1, 4, 9],
             "exactly-a-tile": [0, 1, 2, 3, 5, 6, 8, 9],
             "a-tile-and-one": [0, 1, 2, 3, 4, 5, 6, 7, 9],
             "all": list(range(LANES))}


@functools.lru_cache(maxsize=None)
def _latent_step():
    """A decode step over ten lanes at ragged contexts past the
    indexer's top-k, banks pre-filled with noise."""
    params = tfm.init_params(jax.random.PRNGKey(0), LATENT32)
    n_blocks = 1 + LANES * LANE_NB
    banks = {n: jax.random.normal(
        jax.random.PRNGKey(i), (LATENT32.n_layers, n_blocks, BT) + w,
        jnp.float32) for i, (n, w) in
        enumerate(tfm.cache_spec(LATENT32).items())}
    tables = jnp.asarray(1 + np.random.default_rng(0).permutation(
        LANES * LANE_NB).reshape(LANES, LANE_NB), jnp.int32)
    pos0 = jnp.asarray([3, 40, 17, 60, 30, 9, 25, 50, 12, 33], jnp.int32)

    @jax.jit
    def step(banks, tok, pos, active, live_list):
        wr_b = jnp.where(active, tables[jnp.arange(LANES), pos // BT], 0)
        lg, banks, _ = gen.decode_step_banks(
            params, tok, pos, LATENT32, banks, tables, wr_b, pos % BT,
            live=active, live_list=live_list)
        nxt = jnp.where(active, jnp.argmax(lg, -1).astype(jnp.int32), 0)
        return lg, banks, nxt, jnp.where(active, pos + 1, pos)

    return params, banks, tables, pos0, step


@pytest.mark.parametrize("live", list(LIVE_SETS))
def test_lane_list_step_equals_the_all_lanes_step_on_live_lanes(live):
    """Three greedy decode steps of a latent stack through
    ``decode_step_banks``, handed the live lanes' list (tiles of
    ``LIVE_TILE_LANES``), against the same steps over all lanes: a live
    lane's logits to float32 rounding and its tokens exactly; the banks
    change in the rows the live lanes wrote and the trash block alone;
    and the attention of a lane that is not listed reads zeros."""
    params, banks0, tables, pos, step = _latent_step()
    active = np.zeros(LANES, bool)
    active[LIVE_SETS[live]] = True
    lst, n_tiles = gen.live_lane_list(active)
    assert int(n_tiles) == -(-len(LIVE_SETS[live]) // gen.LIVE_TILE_LANES)
    act = jnp.asarray(active)
    tok = jnp.arange(5, 5 + LANES, dtype=jnp.int32)
    a = b = banks0
    tok_a = tok_b = tok
    pos_a = pos_b = pos
    written = set()
    for _ in range(3):
        written |= {(int(tables[s, pos_a[s] // BT]), int(pos_a[s] % BT))
                    for s in LIVE_SETS[live]}
        lg_a, a, tok_a, pos_a = step(a, tok_a, pos_a, act, None)
        lg_b, b, tok_b, pos_b = step(b, tok_b, pos_b, act, (lst, n_tiles))
        np.testing.assert_allclose(np.asarray(lg_b)[active],
                                   np.asarray(lg_a)[active], atol=2e-5)
        np.testing.assert_array_equal(np.asarray(tok_b), np.asarray(tok_a))
    assert len(written) == 3 * len(LIVE_SETS[live])
    for name, bank in b.items():
        got, was = np.asarray(bank), np.asarray(banks0[name]).copy()
        for blk, off in written:
            assert (got[:, blk, off] != was[:, blk, off]).any()
            was[:, blk, off] = got[:, blk, off]
        np.testing.assert_array_equal(got[:, 1:], was[:, 1:], err_msg=name)

    layer = jax.tree.map(lambda w: w[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(9),
                          (LANES, 1, LATENT32.d_model), jnp.float32)
    q_nope, q_rope, _, qi, _, wi = sparse_mla.project(
        x, layer, LATENT32, pos[:, None])
    args = (q_nope, q_rope, qi, wi, banks0["ckv"][0], banks0["ki"][0],
            tables, pos + 1, layer, LATENT32)
    want = np.asarray(sparse_mla.attend_paged(*args))
    got = np.asarray(sparse_mla.attend_paged(*args, lanes=(lst, n_tiles)))
    np.testing.assert_allclose(got[active], want[active], atol=2e-6)
    assert (got[~active] == 0).all() and np.abs(want).min() > 0


def test_lane_list_step_is_one_program_whatever_is_live():
    """The list's shape depends on ``n_slots`` alone, so the trip count
    is data: every live set above ran one compiled step."""
    _, banks, _, pos, step = _latent_step()
    tok = jnp.zeros(LANES, jnp.int32)
    before = step._cache_size()
    for ids in LIVE_SETS.values():
        active = np.zeros(LANES, bool)
        active[ids] = True
        step(banks, tok, pos, jnp.asarray(active),
             gen.live_lane_list(active))
    assert step._cache_size() - before <= 1


def test_latent_step_keeps_its_temporaries_under_one_bank():
    """``progaudit``'s bound on the paged decode step, for the latent
    step over the lane list: banks donated, and every temporary of the
    program together under a quarter of the smaller bank (the banks are
    read inside the tile loop, where a bank taken into the loop's state
    would be copied: PERF.md §6, PR 29)."""
    from ptype_tpu import progaudit

    cfg, n_blocks, B = LATENT32, 1024, 16
    nb = cfg.max_seq // BT
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    banks = {n: jax.ShapeDtypeStruct((cfg.n_layers, n_blocks, BT) + w,
                                     jnp.float32)
             for n, w in tfm.cache_spec(cfg).items()}
    i32 = jnp.int32
    row = jax.ShapeDtypeStruct((B,), i32)
    lst, _ = gen.live_lane_list(np.zeros(B, bool))

    def decode_step(params, banks, tok, pos, tables, wr_b, wr_o,
                    live_list):
        return gen.decode_step_banks(params, tok, pos, cfg, banks, tables,
                                     wr_b, wr_o, live_list=live_list)[:2]

    smaller = min(int(np.prod(b.shape)) * 4 for b in banks.values())
    rep = progaudit.audit(
        decode_step,
        (params, banks, row, row, jax.ShapeDtypeStruct((B, nb), i32), row,
         row, (jax.ShapeDtypeStruct(lst.shape, i32),
               jax.ShapeDtypeStruct((), i32))),
        name="serve.latent_decode_step", donate_argnums=(1,),
        expect_collectives=0, max_temp_bytes=smaller // 4)
    rep.raise_if_failed()
    assert 0 < rep.temp_bytes < smaller // 4
