"""The axk1 family (ISSUE 35) at test size on the CPU: the program's
paged path (absorbed latent attention over a row's whole context, read
through the block list and the table walk GQA has, YaRN positions,
dropless experts chosen inside groups over a share) against the
family's plain reference on seeded weights; YaRN's tables and the
selection by hand; the share test; the planted departures; a whole tiny
run of the cell and its faults; the family's counts by hand; the shipped
configuration, cell and metric files; the new reader on a hand-made
trace."""

import dataclasses
import functools
import importlib
import inspect
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import axk1_tiny  # noqa: E402
import perfbench_tiny  # noqa: E402
from benchmark import family, manifest, run, traffic  # noqa: E402
from benchmark.families.axk1 import reference, weights, work  # noqa: E402
from benchmark.readers import latent_attn_roofline  # noqa: E402
from ptype_tpu.models import generate as gen  # noqa: E402
from ptype_tpu.models import sparse_mla  # noqa: E402
from ptype_tpu.models import transformer as tfm  # noqa: E402
from test_bench_seam import ctx_of, made, op  # noqa: E402

CELL = axk1_tiny.CELL
SMALL = axk1_tiny.SMALL
FAM = family.of(SMALL)
SEED = 11
BT, N_BLOCKS, REACH = 8, 40, 128
NB = REACH // BT
#: Program and reference both compute in float32 here; they differ in
#: the order of their sums (absorbed against expanded attention, a
#: softmax folded tile by tile against one over the whole row, one
#: grouped product against a loop over experts), so logits of magnitude
#: ~2 agree to a few float32 roundings. A bfloat16 matmul anywhere
#: reads 1e-2, the smallest planted departure 0.2.
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    tcfg = dataclasses.replace(FAM.program_config(SMALL, REACH, "float32"),
                               dtype=jnp.float32)
    return tcfg, FAM.tree(SMALL, SEED, "float32")


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(3), (96,), 1,
                                         SMALL["vocab_size"]), np.int32)


def ref_of(row, idx, mode="f32", seed=SEED):
    return np.asarray(FAM.served_logits(
        SMALL, seed, "float32", jnp.asarray(row)[None],
        jnp.asarray(idx)[None], modes=(mode,))[mode])[0]


@pytest.fixture(scope="module")
def ref_logits(tokens):
    """The reference's logits at every position of the 96-token row."""
    return ref_of(tokens, np.arange(96))


class Paged:
    """The two paged programs over a bank of noise (a row that read a
    key it never wrote cannot pass), four lanes to a step."""

    LANES = 4

    def __init__(self, model, noise=5):
        self.cfg, self.params = model
        (name, shape), = tfm.cache_spec(self.cfg).items()
        self.banks = {name: jax.random.normal(
            jax.random.PRNGKey(noise),
            (self.cfg.n_layers, N_BLOCKS, BT) + shape, jnp.float32)}

    def prefill(self, toks, start, table, bucket):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(toks)] = toks
        tab = np.zeros(NB, np.int32)
        tab[:len(table)] = table
        logits, self.banks, self.load = _chunk_prog(self.cfg)(
            self.params, self.banks, jnp.asarray(padded),
            jnp.int32(start), jnp.int32(len(toks)), jnp.asarray(tab))
        return np.asarray(logits)[0]

    def decode(self, rows, listed=True):
        """One step of ``rows`` = {lane: (token, position, table)}; the
        other lanes are inactive. → {lane: logits}."""
        n = self.LANES
        tok, pos = np.zeros(n, np.int32), np.zeros(n, np.int32)
        tabs = np.zeros((n, NB), np.int32)
        nalloc, active = np.zeros(n, np.int32), np.zeros(n, bool)
        wb = np.zeros(n, np.int32)
        for lane, (t, p, table) in rows.items():
            tok[lane], pos[lane], active[lane] = t, p, True
            tabs[lane, :len(table)] = table
            nalloc[lane] = p // BT + 1
            wb[lane] = table[p // BT]
        lst = gen.live_block_list(tabs, nalloc, active, BT,
                                  own_tiles=True) if listed else None
        logits, self.banks, self.load = _step_prog(self.cfg)(
            self.params, self.banks, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(tabs), jnp.asarray(wb), jnp.asarray(pos % BT),
            jnp.asarray(active), lst)
        return {lane: np.asarray(logits)[lane] for lane in rows}


@functools.lru_cache(maxsize=None)
def _chunk_prog(cfg):
    return jax.jit(lambda p, b, t, s, n, tab: gen.prefill_chunk_banks(
        p, t, s, n, cfg, b, tab))


@functools.lru_cache(maxsize=None)
def _step_prog(cfg):
    return jax.jit(lambda p, b, tok, pos, tabs, wb, wo, live, lst:
                   gen.decode_step_banks(p, tok, pos, cfg, b, tabs, wb, wo,
                                         live=live, live_list=lst))


TABLE = list(range(3, 3 + NB))


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of two blocks (16 keys) in the chunk's table walk and the
    step's block list, so that test-size rows cross tile boundaries."""
    monkeypatch.setattr(gen, "TABLE_TILE_BLOCKS", 2)
    monkeypatch.setattr(gen, "LIVE_TILE_BLOCKS", 2)
    _chunk_prog.cache_clear()
    _step_prog.cache_clear()
    yield
    _chunk_prog.cache_clear()
    _step_prog.cache_clear()


# ------------------------------------------- the program and the reference


def test_contiguous_forward_agrees_with_the_reference(model, tokens,
                                                      ref_logits):
    """(a) The full-sequence forward (expanded attention, every key
    behind the query) against the family's plain reference."""
    cfg, params = model
    got = np.asarray(tfm.forward(params, jnp.asarray(tokens)[None], cfg))[0]
    np.testing.assert_allclose(got, ref_logits, atol=TOL, rtol=0)
    assert float(np.abs(ref_logits).max()) > 1.0


@pytest.mark.parametrize("prompt,new,chunk", [
    (8, 6, 64), (40, 10, 64), (45, 12, 16), (64, 20, 32)],
    ids=["one-block", "past-the-original-reach", "chunks-of-two-blocks",
         "on-a-chunk-boundary"])
def test_prefill_then_decode_agrees_with_the_reference(
        model, tokens, ref_logits, small_tiles, prompt, new, chunk):
    """(b) Prefill a prompt in chunks, then decode through the paged
    cache, the served tokens forced to the row's own: every logit
    vector equals the reference's full forward at that position. The
    prompts cross block (8), chunk and tile (16 keys) boundaries and
    YaRN's original reach (16)."""
    pg = Paged(model)
    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        got = [pg.prefill(tokens[start:start + n], start, TABLE, chunk)]
    for pos in range(prompt, prompt + new):
        got.append(pg.decode({2: (int(tokens[pos]), pos, TABLE)})[2])
    want = ref_logits[prompt - 1:prompt + new]
    np.testing.assert_allclose(np.stack(got), want, atol=TOL, rtol=0)


def test_two_rows_sharing_a_prefix_decode_in_one_step(model, tokens,
                                                      small_tiles):
    """(b) A second prompt that shares its first 32 tokens (four sealed
    blocks) with one already resident starts at position 32 on a table
    whose first four blocks are the first prompt's; the two then decode
    side by side, the shared blocks listed once a row, each lane's run
    of the list on tiles of its own: every logit the reference's."""
    other = np.concatenate([tokens[:32], tokens[60:80]])
    pg = Paged(model)
    pg.prefill(tokens[:48], 0, TABLE, 64)
    shared = TABLE[:4] + [30, 31, 32, 33]
    hit = pg.prefill(other[32:44], 32, shared, 16)
    want_b = ref_of(other, np.arange(52))
    want_a = ref_of(tokens, np.arange(96))
    np.testing.assert_allclose(hit, want_b[43], atol=TOL, rtol=0)
    for i in range(6):
        got = pg.decode({0: (int(tokens[48 + i]), 48 + i, TABLE),
                         3: (int(other[44 + i]), 44 + i, shared)})
        np.testing.assert_allclose(got[0], want_a[48 + i], atol=TOL, rtol=0)
        np.testing.assert_allclose(got[3], want_b[44 + i], atol=TOL, rtol=0)


def test_a_step_with_no_list_walks_the_tables_to_the_same_logits(
        model, tokens, ref_logits):
    pg = Paged(model)
    pg.prefill(tokens[:40], 0, TABLE, 64)
    got = pg.decode({1: (int(tokens[40]), 40, TABLE)}, listed=False)[1]
    np.testing.assert_allclose(got, ref_logits[40], atol=TOL, rtol=0)


def test_absorbed_agrees_with_expanded(model, tokens):
    """(c) One layer's attention both ways: the contiguous forward's
    expanded form (per-head keys and values made from every latent) and
    the absorbed form the paged programs use (W_UK folded into the
    query, the cache row read as it lies as key and value, W_UV after
    the sum)."""
    cfg, params = model
    layer = jax.tree.map(lambda a: a[0], params["blocks"][1])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 40, 64), jnp.float32)
    expanded = sparse_mla.attend_expanded(x, layer, cfg)
    q_nope, q_rope, ckv, *_ = sparse_mla.project(x, layer, cfg,
                                                 jnp.arange(40))
    qa = sparse_mla.absorb_query(q_nope, q_rope, layer, cfg)
    s = jnp.einsum("bqhc,bsc->bhqs", qa, ckv) * sparse_mla.score_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((40, 40), bool)), s, -1e30)
    ol = jnp.einsum("bhqs,bsc->bqhc", jax.nn.softmax(s, -1),
                    ckv[..., :cfg.latent.kv_rank])
    np.testing.assert_allclose(
        np.asarray(sparse_mla.expand_values(ol, layer, cfg)),
        np.asarray(expanded), atol=2e-6, rtol=0)
    assert ckv.shape[-1] == cfg.latent.cache_dim == 24
    assert not np.asarray(ckv[..., cfg.latent.row_dim:]).any()


def test_the_list_gives_each_lane_tiles_of_its_own():
    """The host's list for a latent cache: a lane's run starts on a
    tile, pads inside it are owned by no lane, and the trip count is
    the tiles in use."""
    tabs = np.arange(4 * 6).reshape(4, 6) + 1
    lst, n = gen.live_block_list(tabs, np.array([3, 0, 5, 2]),
                                 np.array([1, 0, 1, 1], bool), 8, tile=2,
                                 own_tiles=True)
    assert lst.shape == (3, 12, 2) and n == 2 + 3 + 1
    assert lst[1, :6].tolist() == [[0, 0], [0, 4], [2, 2], [2, 2], [2, 4],
                                   [3, 3]]
    assert lst[0, :6].tolist() == [[1, 2], [3, 0], [13, 14], [15, 16],
                                   [17, 0], [19, 20]]
    assert lst[2, :6].tolist() == [[0, 8], [16, 0], [0, 8], [16, 24],
                                   [32, 0], [0, 8]]
    # GQA's list is untouched by the option's absence: rows end to end.
    flat, m = gen.live_block_list(tabs, np.array([3, 0, 5, 2]),
                                  np.array([1, 0, 1, 1], bool), 8, tile=2)
    assert m == 5 and flat[1].reshape(-1)[:10].tolist() == [
        0, 0, 0, 2, 2, 2, 2, 2, 3, 3]


def test_the_steps_trip_count_follows_the_live_rows_blocks(model, tokens,
                                                           small_tiles):
    """One compiled step whatever the load: the list's tiles in use are
    data, and rows at 40 and 17 tokens take 3 + 2 tiles of 16 keys."""
    pg = Paged(model)
    pg.prefill(tokens[:40], 0, TABLE, 64)
    pg.prefill(tokens[:17], 0, [20, 21, 22], 64)
    before = _step_prog(pg.cfg)._cache_size()
    pg.decode({0: (1, 40, TABLE)})
    pg.decode({0: (1, 41, TABLE), 1: (2, 17, [20, 21, 22])})
    assert _step_prog(pg.cfg)._cache_size() - before == 1
    tabs = np.zeros((4, NB), np.int32)
    _, n = gen.live_block_list(tabs, np.array([6, 3, 0, 0]),
                               np.array([1, 1, 0, 0], bool), BT,
                               own_tiles=True)
    assert n == 3 + 2


# --------------------------------------------------------- YaRN by hand


def _yarn_cfg(**over):
    y = dict(factor=32.0, original_max=4096, beta_fast=32.0, beta_slow=1.0,
             mscale=1.0, mscale_all_dim=1.0)
    y.update(over)
    return tfm.TransformerConfig(
        latent=tfm.LatentAttention(8, 8, 8, 64, 8), rope_theta=10000.0,
        rope_yarn=tfm.YarnScaling(**y))


def test_yarn_tables_are_the_references_past_the_original_reach():
    """(d) At the published numbers (64 rotary dims, factor 32 over
    4,096): the program's tables against the reference's formula, and
    both against DeepSeek-V3's written out by hand, at positions up to
    131,071."""
    pos = np.array([0, 1, 4095, 4096, 20000, 131071])
    sin, cos = tfm.rope_tables(_yarn_cfg(), positions=jnp.asarray(pos),
                               dim=64)
    published = {"rope_theta": 10000, "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}}
    freq, m = reference.yarn_frequencies(64, 10000.0,
                                         published["rope_scaling"])
    assert m == 1.0
    # By hand: correction dims of 32 turns and of 1 turn in 4,096.
    dim_of = lambda r: 64 * math.log(4096 / (r * 2 * math.pi)) / (  # noqa: E731
        2 * math.log(10000))
    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    assert (low, high) == (10, 23)
    i = np.arange(32)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    extra = 10000.0 ** (-i / 32)
    by_hand = extra / 32 * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(np.asarray(freq), by_hand, rtol=2e-6)
    assert by_hand[9] == extra[9] and by_hand[24] == extra[24] / 32
    ang = pos[:, None] * by_hand[None]
    # float32 angles of up to 131,071 radians carry 8e-3 of rounding.
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-2)
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-2)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 64), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(sparse_mla.rope_interleaved(x[None], sin[None],
                                               cos[None]))[0],
        np.asarray(reference.rot_pairs(x, jnp.asarray(pos), published)),
        atol=2e-2)  # the angles' float32 rounding again, on |x| ~ 3
    assert reference.score_scale(
        {**axk1_tiny.SMALL, "qk_nope_head_dim": 128,
         "qk_rope_head_dim": 64, "rope_scaling":
         published["rope_scaling"]}) == pytest.approx(
            192 ** -0.5 * 1.8133, rel=1e-4)
    assert tfm.yarn_score_factor(_yarn_cfg()) == pytest.approx(
        (0.1 * math.log(32) + 1) ** 2)
    assert tfm.yarn_score_factor(_yarn_cfg(mscale_all_dim=0.0)) == 1.0
    s2, _ = tfm.rope_tables(_yarn_cfg(mscale_all_dim=0.0),
                            positions=jnp.asarray(pos), dim=64)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(sin) * (
        0.1 * math.log(32) + 1), rtol=1e-6)


def test_yarn_of_factor_one_is_todays_tables_bit_for_bit():
    """(d) ``factor`` 1 scales nothing, exactly."""
    plain = dataclasses.replace(_yarn_cfg(), rope_yarn=None)
    pos = jnp.arange(0, 20000, 7)
    for dim in (64, 8):
        want = tfm.rope_tables(plain, positions=pos, dim=dim)
        got = tfm.rope_tables(_yarn_cfg(factor=1.0), positions=pos, dim=dim)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))
    with pytest.raises(ValueError, match="latent"):
        tfm.TransformerConfig(rope_yarn=tfm.YarnScaling(2.0, 16))


# ---------------------------------------------------- selection by hand


def _route(h, router, cfg):
    """What ``_moe_dropless`` chose: → (T, E) gates, 0 where not."""
    E = cfg.n_experts
    # Expert e's SwiGLU is made to return a constant one-hot of e, so
    # the layer's output reads the gates off: silu(1)·1 through w_down.
    ones = jnp.zeros((E, 64, 4)).at[:, 0, :].set(1.0)
    layer = {"router": router, "router_bias": jnp.zeros((E,), jnp.float32),
             "w_gate": ones, "w_up": ones,
             "w_down": jnp.eye(E, 64)[:, None, :].repeat(4, 1)
             / (4 * float(jax.nn.silu(1.0)))}
    x = h.at[..., 0].set(1.0)
    y, load = tfm._moe_dropless(x, layer, dataclasses.replace(
        cfg, experts_held=None, n_shared_experts=0))
    return np.asarray(y[0, :, :E]), np.asarray(load), x


@pytest.mark.parametrize("groups", [(4, 2), (1, 1)],
                         ids=["inside-groups", "no-limit"])
def test_selection_inside_groups_is_the_numpy_loops(model, groups):
    """(e) 16 sigmoid scores in 4 groups of 4; a group's standing is the
    sum of its two largest; the 2 best groups stay; the 4 largest among
    their 8 are chosen; gates 2.5 · s / Σ chosen. With one group it is
    the selection the router had (the 4 largest of all)."""
    cfg = dataclasses.replace(model[0], expert_groups=groups)
    router = jax.random.normal(jax.random.PRNGKey(4), (64, 16)) * 0.5
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 50, 64), jnp.float32)
    gates, load, x = _route(h, router, cfg)
    s = 1 / (1 + np.exp(-np.asarray(x[0], np.float64)
                        @ np.asarray(router, np.float64)))
    kept_all = []
    for t in range(50):
        if groups[0] > 1:
            standing = [np.sort(s[t, g * 4:g * 4 + 4])[-2:].sum()
                        for g in range(4)]
            kept = np.argsort(standing)[-groups[1]:]
            allowed = [e for e in range(16) if e // 4 in kept]
        else:
            kept, allowed = range(4), list(range(16))
        chosen = sorted(allowed, key=lambda e: s[t, e])[-4:]
        want = np.zeros(16)
        want[chosen] = 2.5 * s[t, chosen] / s[t, chosen].sum()
        np.testing.assert_allclose(gates[t], want, atol=1e-5)
        kept_all.append(set(kept))
    assert load[:-1].sum() == 50 * 4 and load[-1] == 0
    if groups[0] > 1:
        # The limit binds: some token's 4 largest of all 16 are not all
        # inside its 2 kept groups.
        assert any({e // 4 for e in np.argsort(s[t])[-4:]} - kept_all[t]
                   for t in range(50))
    with pytest.raises(ValueError, match="equal groups"):
        dataclasses.replace(cfg, expert_groups=(3, 1))


def test_the_shares_of_the_experts_add_up_to_the_whole_layer(model):
    """(f) The share test. Eight members hold two of the sixteen experts
    each (here as in the deployment: a share is part of one group). The
    routed parts the eight compute, and the shared expert counted once,
    add up to what the uncut reference gives for the whole layer
    (float32: the order of the sum is all that differs), and each
    member's share is the reference's for its experts."""
    whole = {**SMALL, "n_routed_experts": 16, "experts_held_first": 0}
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     weights.uncut_layer(SMALL, SEED, 1, "float32"))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64), jnp.float32)
    want = reference.experts(h[0], w, whole, "f32")
    cfg = model[0]
    routed = {k: v for k, v in w.items() if not k.startswith("ws_")}
    total, seen, idle = jnp.zeros_like(h), 0, 0
    for first in range(0, 16, 2):
        mine = {**SMALL, "experts_held_first": first}
        held = jax.tree.map(lambda a: a.astype(jnp.float32),
                            weights.one_layer(mine, SEED, 1, "float32"))
        for k in ("w_gate", "w_up", "w_down"):
            # A member's experts are the uncut layer's own.
            assert np.array_equal(np.asarray(held[k]),
                                  np.asarray(w[k][first:first + 2]))
        share = {**routed, **{k: held[k] for k in ("w_gate", "w_up",
                                                   "w_down")}}
        y, load = tfm._moe_dropless(
            h, share, dataclasses.replace(cfg, experts_held=(first, 2)))
        total, seen = total + y, seen + int(load[:2].sum())
        idle += int(load[:2].sum() == 0)
        assert int(load.sum()) == 24 * 4
        np.testing.assert_allclose(
            np.asarray(y[0]), np.asarray(reference.routed(
                h[0], share, whole, "f32", (first, 2))), atol=TOL, rtol=0)
    assert seen == 24 * 4  # every choice fell on exactly one share
    total = total + tfm._swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"],
                                jnp.float32)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=TOL, rtol=0)


# --------------------------------------------------------- the controls


def _gap(ref, other):
    first = other.argmax(-1)
    return float(np.max(ref.max(-1) - np.take_along_axis(
        ref, first[..., None], -1)[..., 0]))


CONTROL_SEEDS = (0, 1, 2, 3)
CONTROLS = ("fp8",) + reference.DEPARTURES


@pytest.fixture(scope="module")
def control_readings():
    """Over four seeds x 96 positions, each mode against the float32
    reference: (the served gap of its first token, the widest distance
    between the two logit vectors)."""
    idx = np.arange(96)
    out = {m: [] for m in ("bf16",) + CONTROLS}
    for seed in CONTROL_SEEDS:
        toks = np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed), (96,), 1, 128))
        ref = ref_of(toks, idx, seed=seed)
        for m in out:
            got = ref_of(toks, idx, m, seed=seed)
            out[m].append((_gap(ref, got), float(np.abs(got - ref).max())))
    return out


@pytest.mark.parametrize("mode", CONTROLS)
def test_a_planted_departure_does_not_pass_as_rounding(control_readings,
                                                       mode):
    """(g) YaRN left out, its factor on the scores left out, the group
    limit left out, float8 operands: on every seed each moves the
    logits by a thousand times the tolerance the program is held to
    above (0.048 at the least); and the cell's own measure, the served
    gap, which reads 0 wherever no first token changed, passes the
    tiny cell's limit over the four seeds where bfloat16 (what the
    configuration states) stays under it on each."""
    limit = axk1_tiny.LIMITS["served_logit_gap_max"]
    assert max(g for g, _ in control_readings["bf16"]) < limit
    assert min(d for _, d in control_readings[mode]) > 1000 * TOL
    assert max(g for g, _ in control_readings[mode]) > limit


def test_the_program_in_bfloat16_reads_as_the_witness(tokens):
    """The program itself, bfloat16 compute over float32 weights through
    the contiguous forward: under the tiny cell's limit, like the
    bfloat16 witness."""
    cfg = FAM.program_config(SMALL, REACH, "float32")
    got = np.asarray(tfm.forward(FAM.tree(SMALL, SEED, "float32"),
                                 jnp.asarray(tokens)[None], cfg))[0]
    assert _gap(ref_of(tokens, np.arange(96)), got) < axk1_tiny.LIMITS[
        "served_logit_gap_max"]


# ------------------------------------------------------- through the engine


def _engine(model, **over):
    from ptype_tpu.metrics import MetricsRegistry
    from ptype_tpu.serve_engine import PagedGeneratorActor

    cfg, params = model
    kw = dict(params=params, n_slots=2, block_tokens=16, prefill_chunk=32,
              n_blocks=24, metrics_registry=MetricsRegistry())
    kw.update(over)
    return PagedGeneratorActor(cfg, **kw)


def _served_gap(prompt, out):
    row = np.concatenate([prompt, out]).astype(np.int32)
    idx = len(prompt) - 1 + np.arange(len(out))
    ref = ref_of(row, idx)
    return float(np.max(ref.max(-1) - ref[np.arange(len(out)), out]))


def test_engine_serves_the_references_tokens_from_one_bank(model, tokens):
    """The normal path: ``PagedGeneratorActor`` over a pool allocated
    from the model's own description of what a token holds — one bank,
    no indexer's keys. Every served token is the reference's first; the
    step's list reports its blocks and tiles; a second ask of the same
    document reuses its sealed blocks."""
    eng = _engine(model)
    try:
        assert set(eng.pool.banks) == {"ckv"}
        assert eng.pool.banks["ckv"].shape == (3, 24, 16, 24)
        assert eng.pool.block_shapes() == {"ckv": (3, 16, 24)}
        prompt = tokens[:40]
        out = np.asarray(eng.Generate(jnp.asarray(prompt)[None], 12))[0]
        assert _served_gap(prompt, out) < TOL
        s = eng.ledger.summary()
        assert s["kv_tiles"] == 1.0 and 3.0 <= s["kv_blocks"] <= 4.0
        assert 0 < s["kv_tile_fill"] <= 1 and "lane_tiles" not in s
        load = s["moe_load"]
        assert load["iterations"] == 11 and len(load["held"]) == 2
        assert sum(load["held"]) + load["elsewhere"] == 11 * 1 * 4 * 2
        again = np.concatenate([tokens[:32], tokens[50:58]])
        out2 = np.asarray(eng.Generate(jnp.asarray(again)[None], 6))[0]
        assert eng.ledger.records()[-1]["reused_blocks"] == 2
        assert _served_gap(again, out2) < TOL
    finally:
        eng.close()


def test_migrated_sequence_decodes_as_at_home(model, tokens):
    """Prefill on one replica, the one bank packed by the pool's own
    description and landed on another (the exact wire), decode there:
    the tokens a lone engine serves."""
    prompt, new = jnp.asarray(tokens[:40])[None], 8
    solo = _engine(model)
    pre, dec = _engine(model, serve_class="prefill"), _engine(
        model, serve_class="decode")
    try:
        want = np.asarray(solo.Generate(prompt, new))[0]
        rep = pre.Prefill(prompt, new)
        plan = dec.MigratePlan(prompt, new)
        wire = pre.ExportBlocks(rep["export_id"], plan["need"], "exact")
        assert set(wire["blocks"][0]) >= {"ckv"}
        assert "ki" not in wire["blocks"][0]
        dec.ImportBlocks(plan["ticket"], wire)
        assert pre.ReleaseExport(rep["export_id"])
        got = dec.MigrateDecode(plan["ticket"], rep["first_token"])
        assert list(got) == [int(t) for t in want]
    finally:
        for e in (solo, pre, dec):
            e.close()


def test_what_the_program_cannot_run_is_refused_with_a_sentence(model):
    from ptype_tpu.serve_engine import SpecConfig

    cfg, params = model
    for call in (lambda: gen.truncated_draft_params(params, cfg),
                 lambda: gen.init_cache(cfg, 1),
                 lambda: tfm.param_specs(cfg, {"model": 2}),
                 lambda: tfm.flops_per_token(cfg, 64)):
        with pytest.raises(ValueError, match="latent"):
            call()
    with pytest.raises(ValueError, match="next-token module"):
        _engine(model, spec=SpecConfig(draft_params=params, draft_cfg=cfg,
                                       k=2))
    for fn in (lambda: work.train_flops_per_token(SMALL, 64),
               lambda: FAM.train_steps(SMALL, {}, None, [], "f32", 1),
               lambda: work.flash_train_floor_s(SMALL, 1, 1, {})):
        with pytest.raises(SystemExit, match="served, not trained"):
            fn()


def test_a_program_without_the_layers_fails_at_once(monkeypatch):
    """What the parent commit does with this cell: the family's
    ``program_config`` exits with a sentence before anything is built."""
    monkeypatch.delattr(tfm, "YarnScaling")
    with pytest.raises(SystemExit, match="YaRN.*cannot serve"):
        FAM.program_config(SMALL, REACH, "float32")


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("topk_method", "noaux_tc"),
    ("rope_scaling", {**SMALL["rope_scaling"], "type": "linear"})])
def test_what_the_file_states_and_the_program_cannot_run_is_refused(
        key, value):
    with pytest.raises(SystemExit, match="yarn" if key == "rope_scaling"
                       else key):
        FAM.program_config({**SMALL, key: value}, REACH, "float32")


# ------------------------------------------------------------ whole runs


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return perfbench_tiny.make_root(str(tmp_path_factory.mktemp("axk1")))


def test_a_whole_run_is_correct_and_reuses_its_documents(tiny_root):
    res = run.execute(CELL, 2 ** 31 + 17, 0.5, False, jax.devices()[:1],
                      root=tiny_root)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert res["counters"]["prefix_hit_pct"] > 40
    assert {"ttft_mean_ms", "itl_p95_ms", "setup_s"} <= set(res["metrics"])


def test_a_token_altered_comes_out_not_correct(tiny_root):
    """(h) The serving fault ``perfbench_tiny``'s cells plant, over a
    whole tiny run."""
    res = run.execute(CELL, 2 ** 31 + 99, 0.5, False, jax.devices()[:1],
                      root=tiny_root, fault="token_altered")
    c = res["checks"]["served_logit_gap_max"]
    assert res["correct"] is False and not c["value"] <= c["limit"]


def test_a_request_that_never_answers_is_not_correct(tiny_root, monkeypatch):
    """(h) A failed request fails the run: its limit is 0."""
    from benchmark import serve_cell

    ask = serve_cell.Server.ask
    calls = {"n": 0}

    def flaky(self, prompt, max_new):
        calls["n"] += 1
        if calls["n"] == 5:       # past the warm-up asks
            raise RuntimeError("planted: the replica dropped the call")
        return ask(self, prompt, max_new)

    monkeypatch.setattr(serve_cell.Server, "ask", flaky)
    res = run.execute(CELL, 5, 1.0, False, jax.devices()[:1],
                      root=tiny_root)
    assert res["failed"] == 1 and res["correct"] is False
    assert res["checks"]["requests_failed"] == {"value": 1.0, "limit": 0.0}


@pytest.mark.parametrize("mode", reference.DEPARTURES)
def test_a_departure_in_the_programs_place_is_not_correct(tiny_root, mode):
    """As ``benchmark/readings.py`` reads a control: the run's own
    sample through the reference in ``mode``, judged by the cell's
    limit. (A seed whose sample of three short answers shows all three:
    the served gap reads 0 wherever no first token changed, and the
    test above reads each departure over 96 positions a seed.)"""
    res = run.execute(CELL, 2 ** 31 + 5, 0.5, False, jax.devices()[:1],
                      root=tiny_root, readings=(mode, "bf16"))
    limit = res["checks"]["served_logit_gap_max"]["limit"]
    assert res["readings"][mode]["served_logit_gap_max"] > limit
    assert res["readings"]["bf16"]["served_logit_gap_max"] <= limit


# -------------------------------------------------------- counts by hand


@pytest.fixture(scope="module")
def axk1():
    with open(os.path.join(ROOT, "benchmark", "configs", "a.x-k1.json")) as f:
        return json.load(f)


def test_parameters_by_hand(axk1):
    D, H = 7168, 64
    attn = (D * 1536 + 1536 * H * 192 + D * 576 + 512 * H * 256
            + H * 128 * D)
    assert attn == work.attention_params(axk1) == 101_122_048
    expert = 3 * D * 2048
    norms = 2 * D + 1536 + 512
    dense = attn + norms + 3 * D * 18432
    moe = attn + norms + D * 192 + 192 + 13 * expert
    assert work.layer_params(axk1, "dense") == dense
    assert work.layer_params(axk1, "experts") == moe
    total = dense + 7 * moe + 2 * D * 20480 + D
    assert work.total_params(axk1) == total
    assert 5_516_300_000 <= total < 5_516_400_000
    assert f"{total:,}" in axk1["deployment"]


def test_decode_bytes_and_flops_by_hand(axk1):
    """Every cached row of every live row is read: nothing is selected."""
    D = 7168
    attn, expert = 101_122_048, 3 * D * 2048
    rows = [17408] * 10 + [300]
    hit = 12 * (1 - (1 - 8 / 192) ** 11)
    w = (8 * attn + 3 * D * 18432
         + 7 * (D * 192 + (1 + hit) * expert) + D * 20480)
    want = 2 * (w + 8 * 576 * sum(rows))
    assert work.decode_needed_bytes(axk1, rows) == pytest.approx(want)
    assert work.decode_needed_bytes(axk1, rows, 16384) == pytest.approx(
        want - 2 * 8 * 576 * 16384)
    assert work.cache_bytes_per_token(axk1) == 9216
    per_tok = (8 * attn + 3 * D * 18432
               + 7 * (D * 192 + (1 + 8 * 12 / 192) * expert) + D * 20480)
    assert work.forward_flops(axk1, 11, rows) == pytest.approx(
        2.0 * per_tok * 11 + 8 * 2.0 * 64 * (1024 + 64) * sum(rows))
    b, f = work.latent_attention_work(axk1, rows)
    assert b == 8 * 1152 * sum(rows)
    assert f == 8 * 2 * 64 * (576 + 512) * sum(rows)


def test_configuration_keeps_every_published_width(axk1):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    assert axk1["family"] == "axk1"
    assert axk1["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert axk1["published"] == {"num_hidden_layers": 61,
                                 "n_routed_experts": 192,
                                 "vocab_size": 163840}
    assert {"topk_method", "rope_interleave", "yarn", "weights",
            "experts_held_first"} <= set(axk1["assumed"])
    assert (axk1["param_dtype"], axk1["compute_dtype"]) == ("bfloat16",
                                                            "bfloat16")
    m = manifest.load()
    entry = [c for c in m["configs"] if c["name"] == "a.x-k1"]
    assert entry[0]["reduced"] == axk1["reduced"]
    assert entry[0]["source"] == axk1["source"]
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = [json.loads(ln) for ln in f if '"A.X-K1"' in ln][0]
        differs = {k for k, v in row["config"].items() if axk1.get(k) != v}
        assert differs == set(axk1["reduced"])
        assert axk1["source"] == row["source_url"]
    cfg = FAM.program_config(axk1, 19456, "bfloat16")
    assert tfm.layer_groups(cfg) == (("dense", 1), ("experts", 7))
    assert tfm.cache_spec(cfg) == {"ckv": (640,)}
    assert (cfg.held, cfg.expert_groups, cfg.n_experts) == ((36, 12),
                                                            (8, 4), 192)
    # Experts 36..47 are the second half of group 1 (24..47).
    assert {e // 24 for e in range(36, 48)} == {1}
    assert sparse_mla.score_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.8133, rel=1e-4)
    assert not cfg.latent.indexer and cfg.rope_yarn.factor == 32.0


def test_cell_and_traffic_are_the_ones_issue_35_names():
    m = manifest.load()
    assert manifest.check(m) == []
    cell = manifest.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "a.x-k1", "hotdocs", 1)
    with open(manifest.traffic_file("hotdocs", m["paths"])) as f:
        mix = json.load(f)
    assert mix["kind"] == "open" and mix["arrivals"] == "poisson"
    assert mix["shared_prefixes"] == {"count": 4, "tokens": 16384}
    assert mix["suffix"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.7, "min": 16, "max": 2048,
                             "quantum": 16}
    assert mix["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.7, "min": 8, "max": 1024,
                             "quantum": 8}
    assert mix["engine"] == {"n_slots": 64, "max_len": 19456,
                             "block_tokens": 16, "n_blocks": 16384,
                             "prefill_chunk": 512, "max_queue": 4096,
                             "admit_timeout_s": 0}
    assert (mix["check_sample"], mix["check_bucket"]) == (4, 1024)
    # The knee by ISSUE 35's rule (the highest swept rate at which the
    # last third's mean first-token wait does not grow over the middle
    # third's) is 1.75, and the cell offers 0.8 x it. The issue's
    # fallback of 0.67 x (for steadiness) was tried and is the LESS
    # steady rate: the driver refused the cell there for the spread of
    # `itl_p95_ms` (PERF.md §4, §6). The issue reckoned a knee of 3-8
    # req/s and so at least 60 asks a window; at this knee 56 are due.
    assert (mix["knee_rps"], mix["rate_rps"]) == (1.75, 1.4)
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"], abs=0.005)
    assert len(traffic.requests(mix, 1, 40.0, 20480)) == 56
    with open(manifest.traffic_file("mixed", m["paths"])) as f:
        mixed = json.load(f)
    for key in ("gateway", "deadline_s", "drain_s"):
        assert mix[key] == mixed[key]
    for name in ("ttft_mean_ms", "itl_p95_ms", "setup_s"):
        assert name in [x["name"] for x in manifest.metrics_for(
            m, CELL, "end_to_end")]


HOT_METRICS = [x for x in manifest.metrics_for(manifest.load(), CELL,
                                               "per_layer")]


def test_the_cell_reports_every_layer_it_runs():
    names = {x["name"] for x in HOT_METRICS}
    assert all(n.endswith(".hotdocs") for n in names)
    assert names == {n + ".hotdocs" for n in (
        "decode_attn_pct", "decode_kv_gather_pct", "decode_kv_write_pct",
        "decode_experts_pct", "decode_matmul_pct", "decode_unscoped_pct",
        "prefill_attn_pct", "prefill_experts_pct", "prefill_unscoped_pct",
        "prefill_time_pct", "prefix_hit_pct", "decode_hbm_roofline",
        "step_mfu", "engine_iter_ms_p50", "engine_host_ms_per_iter",
        "engine_fetch_wait_pct", "device_idle_pct", "ttft_queue_ms_mean",
        "ttft_prefill_ms_mean", "ttft_stall_ms_mean", "ttft_p90_ms",
        "expert_load_max_over_mean", "gateway_ms_p50", "issue_lag_p95_ms",
        "compiles_in_window", "decode_latent_attn_roofline")}


@pytest.mark.parametrize("x", HOT_METRICS, ids=lambda x: x["name"])
def test_every_hotdocs_metric_binds_a_reader(x):
    assert x["workloads"] == [CELL]
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           x["name"] + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    inspect.signature(reader.read).bind({}, **spec["params"])
    if "cell" in spec["params"]:
        assert spec["params"]["cell"] == CELL


# ------------------------------------------------------------- the reader

STEP = "jit(engine_step)/while/body/closed_call/"


def test_latent_roofline_reader_on_a_hand_made_trace(axk1):
    """Two decode steps of 20 ms whose attention takes 8: two layers'
    loops of 4 ms from the first operation to the last, of which the
    operations themselves take 2.5 (a product 1, a gather 1, a product
    0.5) and the rest is time between them, a compiler's copy under no
    name among it; the two `serve.step/dispatch` spans say 1,088 and
    2,176 blocks were listed. Floor: the bytes of those blocks' latent
    rows at 819 GB/s against the FLOPs at 197 TFLOP/s, the larger; the
    time is the loops' own, gaps included."""
    ops, modules, host = [], [], []
    for t0, blocks in ((10, 1088), (40, 2176)):
        ops.append(op("while.2", t0, 20, 7))
        for t in (t0, t0 + 10):
            ops += [op("fusion.1", t, 1, 7, STEP + "attn/while/body/dot:"),
                    op("fusion.2", t + 1.5, 1, 7,
                       STEP + "attn/while/body/kv_gather/gather:"),
                    op("copy.4", t + 2.75, 0.25, 7),
                    op("fusion.1", t + 3.5, 0.5, 7,
                       STEP + "attn/while/body/dot:"),
                    op("copy.5", t + 4, 1, 7),
                    op("fusion.3", t + 5, 5, 7, STEP + "mlp/dot_general:")]
        modules.append(("jit_engine_step(7)", t0, 20))
        host.append(("serve.step/dispatch", t0 - 1, 0.5,
                     {"kv_blocks": blocks, "kv_tiles": 5}))
    xs, tr = made(ops, modules, host=host)
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "decode_latent_attn_roofline.hotdocs.json")) as f:
        params = json.load(f)["params"]
    ctx = {**ctx_of(xs, tr), "cfg": axk1, "mix": {"engine": {
        "block_tokens": 16}}, "peaks": {"bf16_flops": 197e12,
                                        "hbm_bytes_per_s": 819e9}}
    tokens = (1088 + 2176) * 16
    floor = max(tokens * 8 * 1152 / 819e9,
                tokens * 8 * 2 * 64 * 1088 / 197e12)
    assert latent_attn_roofline.read(ctx, **params) == pytest.approx(
        100.0 * floor / 16e-3, rel=1e-6)
    lo, hi = 0, 100 * 10 ** 6
    assert latent_attn_roofline.scoped_extent_ns(
        xs, lo, hi, params["program"], params["known"],
        params["scopes"]) == {"extent_ns": 16 * 10 ** 6, "runs": 2,
                              "ops_ns": 10 * 10 ** 6, "stretches": 4}
    # A trace that ends before the window does (the profiler's store is
    # full): the steps counted are those the device's line holds.
    xs1, tr1 = made(ops[:len(ops) // 2], modules, host=host)
    first = 1088 * 16
    assert latent_attn_roofline.read({**ctx, **ctx_of(xs1, tr1)},
                                     **params) == pytest.approx(
        100.0 * max(first * 8 * 1152 / 819e9,
                    first * 8 * 2 * 64 * 1088 / 197e12) / 8e-3, rel=1e-6)
    # A window that closes inside a loop counts the loop as far as it got.
    assert latent_attn_roofline.scoped_extent_ns(
        xs, lo, 52 * 10 ** 6, params["program"], params["known"],
        params["scopes"]) == {"extent_ns": 14 * 10 ** 6, "runs": 2,
                              "ops_ns": 9 * 10 ** 6, "stretches": 4}
    assert latent_attn_roofline.scoped_extent_ns(
        xs, lo, hi, "^jit_other\\(", params["known"],
        params["scopes"]) is None
    # Nothing where nothing is: no trace, no such spans, another family.
    assert latent_attn_roofline.read({"trace": None, "cfg": axk1},
                                     **params) is None
    xs2, tr2 = made(ops, modules)
    assert latent_attn_roofline.read({**ctx, **ctx_of(xs2, tr2)},
                                     **params) is None
