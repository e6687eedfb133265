"""What ISSUE 28 added to the model layer, at test size: a layer stack
of several groups (one ``lax.scan`` a run of identical layers, the
paged loop and the contiguous forward walking them with one carry), the
cache described by the model (``cache_spec``) and allocated from that by
the pool, and the dropless router over a share of the experts."""

import dataclasses

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
