"""What ISSUE 33 added to the model layer and the engine, at test size
and with the program's own ``init_params`` (no benchmark family): a
stack that states attention kinds (window layers among full ones) as
runs of identical layers, a stated head width, the per-head q/k norm,
rotation on the window layers alone, two kinds of cache (``cache_layers``)
each with a pool of its own, and the block list of a window table."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu.models import generate as gen
from ptype_tpu.models import transformer as tfm
from ptype_tpu.serve_engine import BlockPool, PagedGeneratorActor

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
#: Two periods LLLG of window 8 over a dense stack: heads of 24, not
#: 64 / 4; q/k norm; no rotation on the full layers.
MIXED = tfm.preset("tiny", n_layers=8, n_kv_heads=2, d_head=24,
                   attn_windows=(8, 8, 8, 0) * 2, qk_norm=True,
                   nope_full=True, tie_embeddings=False, **F32)
#: The same kinds over a dense layer and dropless experts.
MIXED_MOE = dataclasses.replace(
    MIXED, n_dense_layers=1, n_experts=8, expert_top_k=2, d_ff_expert=16,
    n_shared_experts=1, moe_router="sigmoid_bias", routed_scale=2.5)
BT = 8


def naive_forward(params, toks, cfg):
    """Every layer by hand over one sequence: full softmax under the
    layer's own mask. → logits (T, V)."""
    T = len(toks)
    x = params["embed"][jnp.asarray(toks)][None].astype(jnp.float32)
    pos = jnp.arange(T)
    sin, cos = tfm.rope_tables(cfg, T)
    l = 0
    for stacked, first, n in tfm.block_groups(params, cfg):
        for i in range(n):
            layer = jax.tree.map(lambda a: a[i], stacked)
            w = cfg.attn_windows[first + i]
            q, k, v = tfm.qkv_proj(x, layer, cfg, sin, cos,
                                   rotate=bool(w) or not cfg.nope_full)
            G = cfg.n_heads // cfg.kv_heads
            qg = q.reshape(1, T, cfg.kv_heads, G, cfg.head_dim)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / np.sqrt(
                cfg.head_dim)
            see = pos[None, :] <= pos[:, None]
            if w:
                see &= pos[:, None] - pos[None, :] < w
            p = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
            o = jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(
                1, T, cfg.n_heads, cfg.head_dim)
            x = tfm.attn_residual(x, o, layer, cfg)
            x, _, _ = tfm.mlp_residual(x, layer, cfg)
            l += 1
    x = tfm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return np.asarray(tfm.head_logits(x, params["lm_head"], cfg))[0]


@pytest.mark.parametrize("cfg,groups", [
    (MIXED, (("dense+L", 3), ("dense+G", 1)) * 2),
    (MIXED_MOE, (("dense+L", 1), ("experts+L", 2), ("experts+G", 1),
                 ("experts+L", 3), ("experts+G", 1))),
    (dataclasses.replace(MIXED, attn_windows=(0,) * 8), (("dense+G", 8),)),
    (dataclasses.replace(MIXED, attn_windows=(4, 0) * 4),
     (("dense+L", 1), ("dense+G", 1)) * 4)],
    ids=["LLLG", "dense-then-experts", "all-full", "period-of-two"])
def test_layer_groups_are_runs_of_one_mlp_and_one_attention_kind(
        cfg, groups):
    assert tfm.layer_groups(cfg) == groups
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    got = tfm.block_groups(params, cfg)
    assert [n for _, _, n in got] == [n for _, n in groups]
    assert [f for _, f, _ in got] == list(np.cumsum(
        [0] + [n for _, n in groups])[:-1])
    for stacked, _, n in got:
        assert stacked["wq"].shape == (n, 64, 4, 24)
        assert stacked["q_norm"].shape == stacked["k_norm"].shape == (n, 24)
    assert not cfg.plain and cfg.head_dim == 24


def test_a_stated_head_width_leaves_the_presets_where_they_were():
    assert tfm.preset("tiny").head_dim == 16
    assert tfm.preset("optimus-125m").head_dim == 128
    assert tfm.preset("tiny", d_head=24).head_dim == 24
    assert tfm.preset("tiny", d_head=24).plain
    assert tfm.cache_layers(tfm.preset("tiny")) is None
    assert tfm.cache_layers(MIXED) == {"full": (3, 7),
                                       "window": (0, 1, 2, 4, 5, 6)}
    assert MIXED.window == 8 and tfm.preset("tiny").window == 0
    assert tfm.cache_spec(MIXED) == {"k": (2, 24), "v": (2, 24)}


@pytest.mark.parametrize("windows,sentence", [
    ((8, 0), "states 2 layer"), ((8, 4, 0, 0, 8, 8, 8, 0), "share one"),
    ((8, -1, 0, 0, 8, 8, 8, 0), "window >= 1")])
def test_attention_kinds_that_cannot_be_held_are_refused(windows, sentence):
    with pytest.raises(ValueError, match=sentence):
        dataclasses.replace(MIXED, attn_windows=windows)


def test_latent_attention_states_no_attention_kinds():
    with pytest.raises(ValueError, match="latent cache"):
        tfm.preset("tiny", n_layers=2, attn_windows=(4, 0),
                   latent=tfm.LatentAttention(
                       q_rank=16, kv_rank=8, nope_dim=6, rope_dim=2,
                       v_dim=8, index_heads=2, index_dim=4, index_topk=8))


@pytest.mark.parametrize("call,sentence", [
    (lambda: tfm.flops_per_token(MIXED, 64), "attention kinds"),
    (lambda: tfm.param_specs(MIXED, {"model": 2}), "attention kinds"),
    (lambda: tfm.forward({}, jnp.zeros((1, 4), jnp.int32), MIXED),
     "flash kernels take a window mask"),
    (lambda: gen.init_cache(MIXED, 1), "window layers beside"),
    (lambda: gen.truncated_draft_params({}, MIXED), "two caches"),
    (lambda: tfm.flops_per_token(tfm.preset("tiny", qk_norm=True), 64),
     "q/k norm")],
    ids=["flops_per_token", "param_specs", "forward", "init_cache",
         "truncated_draft", "qk_norm_alone"])
def test_what_a_stack_with_attention_kinds_cannot_run_is_refused(
        call, sentence):
    with pytest.raises(ValueError, match=sentence):
        call()


def test_qkv_proj_norms_each_head_and_rotates_when_told():
    params = tfm.init_params(jax.random.PRNGKey(1), MIXED)
    layer = jax.tree.map(lambda a: a[0], params["blocks"][0])
    layer = {**layer, "q_norm": layer["q_norm"] * 1.5}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 64), jnp.float32)
    sin, cos = tfm.rope_tables(MIXED, 5)
    q0, k0, v0 = tfm.qkv_proj(x, layer, MIXED, sin, cos, rotate=False)
    np.testing.assert_allclose(
        np.sqrt(np.mean(np.asarray(q0) ** 2, -1)), 1.5, atol=1e-3)
    np.testing.assert_allclose(
        np.sqrt(np.mean(np.asarray(k0) ** 2, -1)), 1.0, atol=1e-3)
    q1, k1, v1 = tfm.qkv_proj(x, layer, MIXED, sin, cos)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(
        tfm.apply_rope(q0, sin, cos)), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    assert not np.allclose(np.asarray(k1)[0, 1:], np.asarray(k0)[0, 1:])


@pytest.mark.parametrize("first,row_blocks,want_ids,shape", [
    (None, None, [3, 4, 5, 9, 10], (3, 1, 16)),
    ([1, 0], None, [4, 5, 9, 10], (3, 1, 16)),
    ([2, 1], 2, [5, 10], (3, 1, 4)),
    ([3, 2], 2, [], (3, 1, 4))],
    ids=["every-block", "from-the-first-held", "bounded-rows", "none-held"])
def test_block_list_of_a_window_table(first, row_blocks, want_ids, shape):
    tables = np.array([[3, 4, 5, 0, 0, 0, 0, 0],
                       [9, 10, 0, 0, 0, 0, 0, 0]], np.int32)
    nalloc, active = np.array([3, 2]), np.array([True, True])
    lst, n = gen.live_block_list(
        tables, nalloc, active, BT, tile=16,
        first=None if first is None else np.array(first),
        row_blocks=row_blocks)
    assert lst.shape == shape
    k = len(want_ids)
    assert list(lst[0].ravel()[:k]) == want_ids
    assert (lst[1].ravel()[k:] == 2).all() and (lst[0].ravel()[k:] == 0).all()
    assert int(n) == (1 if k else 0)
    # The position of a listed block's first token is its column's.
    cols = {3: 0, 4: 1, 5: 2, 9: 0, 10: 1}
    assert list(lst[2].ravel()[:k]) == [cols[i] * BT for i in want_ids]


def test_pools_of_two_kinds_hold_the_layers_of_their_kind():
    kinds = tfm.cache_layers(MIXED)
    full = BlockPool(MIXED, 6, BT, n_layers=len(kinds["full"]))
    window = BlockPool(MIXED, 4, BT, n_layers=len(kinds["window"]))
    assert full.banks["k"].shape == (2, 6, BT, 2, 24)
    assert window.banks["v"].shape == (6, 4, BT, 2, 24)
    # A block given back with its unit kept: the row may allocate again.
    assert window.try_reserve(3) and not window.try_reserve(1)
    a = window.alloc()
    window.deref(a, keep_unit=True)
    assert window.stats()["kv_reserved_blocks"] == 3
    assert window.check_invariants() == []
    b, c, d = window.alloc(), window.alloc(), window.alloc()
    assert len({b, c, d}) == 3 and window.check_invariants() == []


@pytest.mark.parametrize("cfg,slots", [(MIXED, 2), (MIXED_MOE, 2),
                                       (MIXED_MOE, 7)],
                         ids=["dense", "experts", "experts-dead-lanes"])
def test_engine_serves_what_the_layers_by_hand_give(cfg, slots):
    """Through ``PagedGeneratorActor``: two pools, chunks of 16 over a
    window of 8, rows several windows long, a shared prefix. Every
    served token is the by-hand forward's first; both pools' books
    balance. ``experts-dead-lanes``: six of seven lanes never hold a
    request; they route all alike, onto one pair of experts, and
    their assignments are neither computed nor counted."""
    params = tfm.init_params(jax.random.PRNGKey(5), cfg)
    eng = PagedGeneratorActor(cfg, params=params, n_slots=slots,
                              max_len=128, block_tokens=BT,
                              prefill_chunk=16, n_blocks=48)
    rng = np.random.default_rng(4)
    shared = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)
    try:
        for n_own, new in ((40, 14), (9, 10), (61, 8)):
            prompt = np.concatenate(
                [shared, rng.integers(1, cfg.vocab_size, n_own)]
            ).astype(np.int32)
            out = np.asarray(eng.Generate(jnp.asarray(prompt)[None], new))[0]
            want = naive_forward(params, np.concatenate([prompt, out]), cfg)
            rows = want[len(prompt) - 1:len(prompt) - 1 + new]
            gap = rows.max(-1) - rows[np.arange(new), out]
            assert gap.max() < 2e-5
            assert eng.check_invariants() == []
        assert eng.ledger.records()[-1]["reused_blocks"] == 3
        s = eng.ledger.summary()
        assert s["window_freed"] > 0 and s["window_blocks"] <= 2
        assert "kv_window_free_blocks" in eng.Info()
        if cfg.n_experts:
            # One live lane: 29 decode steps x 2 choices x 7 layers,
            # each choice a tile of its own holding the one row.
            load = s["moe_load"]
            assert load["iterations"] == 29 and load["elsewhere"] == 0
            assert sum(load["held"]) == 29 * 2 * 7
            assert s["expert_tiles"] == s["experts_hit"] * 7 == 14
            assert s["expert_tile_fill"] == 1 / tfm.EXPERT_TILE_STEP
    finally:
        eng.close()
