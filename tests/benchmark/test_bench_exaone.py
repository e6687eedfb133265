"""The exaone_moe family (ISSUE 33) at test size on the CPU: the
program's paged path — window and full layers through two pools, the
per-head q/k norm, rotation on the window layers alone, dropless
experts over a share — against the family's plain reference on seeded
weights; the prefix rule over two kinds of cache; the window blocks
given back; the share test; the controls; the family's counts by hand;
the shipped configuration, cell and metric files; the new reader on
hand-made records."""

import dataclasses
import importlib
import inspect
import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import exaone_tiny  # noqa: E402
import perfbench_tiny  # noqa: E402
from benchmark import family, manifest, run  # noqa: E402
from benchmark.families.exaone_moe import reference, weights, work  # noqa: E402
from benchmark.readers import cache_share, named_scope_time_pct  # noqa: E402
from ptype_tpu.models import generate as gen  # noqa: E402
from ptype_tpu.models import transformer as tfm  # noqa: E402
from test_bench_seam import ctx_of, made, op  # noqa: E402

SMALL = exaone_tiny.SMALL
CELL = exaone_tiny.CELL
FAM = family.of(SMALL)
SEED = 11
BT, REACH, WINDOW = 8, 128, 8
NB = REACH // BT
#: Program and reference both compute in float32 here; they differ in
#: the order of their sums (a running softmax over tiles of the table
#: against one softmax a block of queries, one grouped product against
#: a loop over experts), so logits of magnitude ~1 agree to a few
#: float32 roundings. A bfloat16 matmul anywhere would read 1e-2.
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    tcfg = dataclasses.replace(FAM.program_config(SMALL, REACH, "float32"),
                               dtype=jnp.float32)
    return tcfg, FAM.tree(SMALL, SEED, "float32")


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(3), (120,), 1,
                                         SMALL["vocab_size"]), np.int32)


def ref_logits(row, idx, mode="f32", seed=SEED):
    return np.asarray(FAM.served_logits(
        SMALL, seed, "float32", jnp.asarray(row, jnp.int32)[None],
        jnp.asarray(idx, jnp.int32)[None], modes=(mode,))[mode])[0]


# --------------------------------------------- the programs, by hand tables


class Paged:
    """One sequence through the two programs with hand-made tables: the
    full layers' blocks 1.., the window layers' a table of their own
    whose entries behind the window are the trash block, as the engine
    leaves them."""

    def __init__(self, model, n_blocks=40):
        self.cfg, self.params = model
        kinds = tfm.cache_layers(self.cfg)
        spec = tfm.cache_spec(self.cfg)
        self.banks = {k: {n: jnp.zeros((len(kinds[k]), n_blocks, BT) + sh,
                                       jnp.float32)
                          for n, sh in spec.items()} for k in kinds}
        self.table = {"full": np.arange(1, NB + 1, dtype=np.int32),
                      "window": np.arange(20, 20 + NB, dtype=np.int32)}

    def tables_at(self, pos):
        """The window table as of a query at ``pos``: what lies wholly
        behind its window names the trash block."""
        first = max(pos - WINDOW + 1, 0) // BT
        w = self.table["window"].copy()
        w[:first] = 0
        return {"full": self.table["full"], "window": w}, first

    def prefill(self, toks, start, bucket):
        n = len(toks)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = toks
        table, _ = self.tables_at(start)
        logits, self.banks, _ = _chunk_prog(self.cfg)(
            self.params, self.banks, jnp.asarray(padded), jnp.int32(start),
            jnp.int32(n), jax.tree.map(jnp.asarray, table))
        return np.asarray(logits)[0]

    def decode(self, tok, pos, with_list=True):
        table, first = self.tables_at(pos)
        tables = {k: np.stack([t, np.zeros(NB, np.int32)])
                  for k, t in table.items()}
        active = np.array([True, False])
        nalloc = np.array([pos // BT + 1, 0])
        ll = None
        if with_list:
            ll = {"full": gen.live_block_list(tables["full"], nalloc,
                                              active, BT),
                  "window": gen.live_block_list(
                      tables["window"], nalloc, active, BT,
                      first=np.array([first, 0]), row_blocks=2)}
        logits, self.banks, _ = _step_prog(self.cfg)(
            self.params, self.banks, jnp.asarray([tok, 0], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32),
            jax.tree.map(jnp.asarray, tables), jnp.asarray(active),
            None if ll is None else jax.tree.map(jnp.asarray, ll))
        return np.asarray(logits)[0]


_PROGS: dict = {}


def _chunk_prog(cfg):
    if ("chunk", cfg) not in _PROGS:
        _PROGS[("chunk", cfg)] = jax.jit(
            lambda p, b, t, s, n, tb: gen.prefill_chunk_banks(
                p, t, s, n, cfg, b, tb))
    return _PROGS[("chunk", cfg)]


def _step_prog(cfg):
    if ("step", cfg) not in _PROGS:
        def step(p, banks, tok, pos, tables, active, ll):
            wr_b = jax.tree.map(
                lambda t: jnp.where(active, t[jnp.arange(2), pos // BT], 0),
                tables)
            return gen.decode_step_banks(p, tok, pos, cfg, banks, tables,
                                         wr_b, pos % BT, live=active,
                                         live_list=ll)
        _PROGS[("step", cfg)] = jax.jit(step)
    return _PROGS[("step", cfg)]


@pytest.mark.parametrize("prompt,new,chunk", [
    (5, 6, 16), (45, 16, 16), (61, 30, 16), (70, 24, 32), (33, 12, 8)],
    ids=["inside-one-window", "chunk-boundary-inside-a-window",
         "several-windows-and-chunks", "chunks-of-four-windows",
         "a-chunk-a-window"])
def test_prefill_then_decode_agrees_with_the_reference(
        model, tokens, prompt, new, chunk):
    """Prefill in chunks, then decoding through both pools, against the
    reference's full forward: every compared logit to float32 rounding.
    The chunks do not line up with the window (8) or, in the second
    case, with the prompt's end."""
    seq = Paged(model)
    pos = 0
    while pos < prompt:
        n = min(chunk, prompt - pos)
        last = seq.prefill(tokens[pos:pos + n], pos, chunk)
        pos += n
    got = [last]
    for p in range(prompt, prompt + new - 1):
        got.append(seq.decode(int(tokens[p]), p))
    want = ref_logits(tokens[:prompt + new],
                      np.arange(prompt - 1, prompt + new - 1))
    np.testing.assert_allclose(np.stack(got), want, atol=TOL, rtol=0)


def test_a_step_with_no_list_walks_the_tables_to_the_same_logits(
        model, tokens):
    """``decode_step_banks`` without a block list attends through the
    tables (the full layers' in tiles, the window layers' one tile):
    the same logits as over the two lists."""
    a, b = Paged(model), Paged(model)
    for seq in (a, b):
        for pos in range(0, 48, 16):
            seq.prefill(tokens[pos:pos + 16], pos, 16)
    for p in range(48, 54):
        np.testing.assert_allclose(
            a.decode(int(tokens[p]), p, with_list=True),
            b.decode(int(tokens[p]), p, with_list=False),
            atol=TOL, rtol=0)


@pytest.mark.parametrize("window,limits", [
    (0, [37]), (8, [37]), (0, [5, 64]), (8, [1, 29])])
def test_table_attention_is_plain_masked_attention(window, limits):
    """The tile walk against a softmax over the gathered context."""
    rng = np.random.default_rng(5)
    B, Q, H, Kh, Dh, nb, bt = len(limits), 4, 4, 2, 8, 8, 8
    kf = jnp.asarray(rng.normal(size=(40, bt, Kh, Dh)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(40, bt, Kh, Dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, Q, H, Dh)), jnp.float32)
    tables = jnp.asarray(rng.permutation(39)[:B * nb].reshape(B, nb) + 1)
    hi = jnp.asarray([[lim - Q + 1 + i for i in range(Q)]
                      for lim in limits], jnp.int32)
    hi = jnp.maximum(hi, 0)
    got = gen._table_attention(q, kf, vf, tables, hi, window, "attn_full")
    ks = kf[tables].reshape(B, nb * bt, Kh, Dh)
    vs = vf[tables].reshape(B, nb * bt, Kh, Dh)
    at = jnp.arange(nb * bt)
    see = at[None, None, :] < hi[:, :, None]
    if window:
        see &= at[None, None, :] >= hi[:, :, None] - window
    s = jnp.einsum("bqkgd,bskd->bkgqs",
                   q.reshape(B, Q, Kh, H // Kh, Dh), ks) / np.sqrt(Dh)
    s = jnp.where(see[:, None, None], s, -1e30)
    p = jnp.where(see[:, None, None], jax.nn.softmax(s, axis=-1), 0.0)
    want = jnp.einsum("bkgqs,bskd->bqkgd", p, vs).reshape(B, Q, H, Dh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)


def test_the_tile_walk_takes_its_trip_count_from_the_data(monkeypatch):
    """One lowering whatever the context: the limits are data."""
    monkeypatch.setattr(gen, "TABLE_TILE_BLOCKS", 2)
    kf = jnp.zeros((40, 8, 2, 8), jnp.float32)
    q = jnp.ones((1, 4, 4, 8), jnp.float32)
    f = jax.jit(lambda lim: gen._table_attention(
        q, kf, kf, jnp.arange(1, 9)[None], lim, 0, "attn_full"))
    f(jnp.full((1, 4), 3, jnp.int32))
    f(jnp.full((1, 4), 60, jnp.int32))
    assert f._cache_size() == 1


# ------------------------------------------------------- through the engine


def _engine(model, **over):
    from ptype_tpu.metrics import MetricsRegistry
    from ptype_tpu.serve_engine import PagedGeneratorActor

    cfg, params = model
    kw = dict(params=params, n_slots=2, max_len=REACH, block_tokens=BT,
              prefill_chunk=16, n_blocks=64,
              metrics_registry=MetricsRegistry())
    kw.update(over)
    return PagedGeneratorActor(cfg, **kw)


def _served_gap(prompt, out, seed=SEED):
    """How far each served token's logit lies below the reference's
    best at its position, worst case."""
    row = np.concatenate([prompt, out]).astype(np.int32)
    ref = ref_logits(row, len(prompt) - 1 + np.arange(len(out)), seed=seed)
    return float(np.max(ref.max(-1) - ref[np.arange(len(out)), out]))


def _ask(eng, prompt, new):
    return np.asarray(eng.Generate(jnp.asarray(prompt)[None], new))[0]


def test_engine_holds_two_pools_and_serves_the_references_tokens(
        model, tokens):
    """The normal path: ``PagedGeneratorActor`` over a pool a kind of
    layer, each allocated from the model's description. Every served
    token is the reference's first (float32 both: a gap is a
    rounding); the router's load arrives with the step's tokens."""
    eng = _engine(model)
    try:
        assert eng.pool.banks["k"].shape == (2, 64, BT, 2, 24)
        # (8 + 16 - 1) // 8 + 2 = 4 blocks a prefilling row, 2 a
        # decoding one, for each of the two slots, and the trash block.
        assert (eng._wrow, eng._wdec) == (4, 2)
        assert eng._wpool.banks["v"].shape == (6, 13, BT, 2, 24)
        prompt = tokens[:70]
        out = _ask(eng, prompt, 20)
        assert _served_gap(prompt, out) < TOL
        s = eng.ledger.summary()
        # 19 decode iterations x 1 live lane x 4 choices x 7 layers.
        assert s["moe_load"]["iterations"] == 19
        assert (sum(s["moe_load"]["held"]) + s["moe_load"]["elsewhere"]
                == 19 * 4 * 7)
        # The full layers hold the row whole, the window layers the
        # window: 9-12 blocks against 2.
        assert s["uniform_blocks"] == s["full_blocks"] >= 9
        assert s["window_blocks"] <= eng._wdec
        assert s["window_freed"] > 0
        assert eng.check_invariants() == []
        info = eng.Info()
        assert info["kv_window_total_blocks"] == 12
        assert info["kv_window_used_blocks"] == 0
    finally:
        eng.close()


def test_a_long_rows_window_table_never_exceeds_its_bound(model, tokens):
    """Audited from the engine's own thread at every decode step and
    chunk: a row holds at most ``_wrow`` window blocks while it
    prefills and ``_wdec`` while it decodes, whatever its length, and
    both pools' books balance."""
    eng = _engine(model)
    seen = {"chunks": 0, "steps": 0, "bad": [], "most": 0}
    step, chunk = eng._plain_step, eng._prefill_one_chunk

    def audited_step():
        step()
        seen["steps"] += 1
        seen["bad"] += eng.check_invariants()
        for row in eng._slot_state.values():
            held = len(row.wtable) - row.wfirst
            seen["most"] = max(seen["most"], held)
            if held > eng._wdec:
                seen["bad"].append(f"decoding row holds {held}")

    def audited_chunk(row, budget=None):
        got = chunk(row, budget)
        seen["chunks"] += 1
        if len(row.wtable) - row.wfirst > eng._wrow:
            seen["bad"].append("prefilling row over its bound")
        return got

    eng._plain_step, eng._prefill_one_chunk = audited_step, audited_chunk
    try:
        rows = [(tokens[:90], 30), (tokens[20:75], 12)]
        outs = [None, None]

        def ask(i):
            outs[i] = _ask(eng, *rows[i])

        ts = [threading.Thread(target=ask, args=(i,)) for i in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert seen["bad"] == [] and seen["chunks"] >= 9
        assert seen["steps"] >= 25 and seen["most"] == eng._wdec
        for (prompt, _), out in zip(rows, outs):
            assert _served_gap(prompt, out) < TOL
        assert eng.check_invariants() == []
        assert eng.pool.used_blocks() == eng._wpool.used_blocks() == 0
        assert eng._wpool.stats()["kv_reserved_blocks"] == 0
    finally:
        eng.close()


def test_retire_and_cancel_leave_both_pools_whole(model, tokens):
    """A row withdrawn while it decodes gives back its blocks and its
    units in both pools, as one that finishes does."""
    eng = _engine(model)
    try:
        done = {}

        def ask():
            done["out"] = _ask(eng, tokens[:60], 60)

        t = threading.Thread(target=ask)
        t.start()
        for _ in range(3000):
            with eng._cond:
                live = list(eng._slot_state.values())
            if live and len(live[0].emitted) > 4:
                break
            threading.Event().wait(0.01)
        eng._cancel_rows(live)
        t.join(timeout=120)
        assert 4 < len(live[0].emitted) < 60
        assert eng.ledger.summary()["retire_reasons"] == {"cancelled": 1}
        _ask(eng, tokens[30:70], 6)
        assert eng.check_invariants() == []
        for pool in (eng.pool, eng._wpool):
            st = pool.stats()
            assert st["kv_used_blocks"] == st["kv_reserved_blocks"] == 0
    finally:
        eng.close()


def _forget(pool, h):
    """Evict one sealed block from a pool's index, as an allocation
    under pressure would."""
    bid = pool._by_hash.pop(h)
    pool._hash_of.pop(bid)
    pool._content.pop(bid)
    pool._cached.pop(bid)
    pool._free.append(bid)


@pytest.mark.parametrize("evict,reused", [
    ((), 4), ((3,), 3), ((2, 3), 2), ((0, 1, 2, 3), 0)],
    ids=["hit", "partial-hit", "shorter-hit", "miss"])
def test_a_prefix_hit_needs_the_last_windows_blocks_too(
        model, tokens, evict, reused):
    """A prompt shares its first 32 tokens (4 blocks) with one served
    before. The full layers still hold all four. The hit is the longest
    prefix whose last window's window-layer blocks are resident too:
    with the window pool's block 3 evicted the first 3 blocks are
    skipped (its window lies in block 2), and so on down to none. What
    is served is what a cold engine serves, to float32 rounding."""
    from ptype_tpu.serve_engine.blocks import block_hashes

    eng = _engine(model, n_slots=3)
    try:
        first = tokens[:50]
        _ask(eng, first, 4)
        hashes = block_hashes(first, BT)
        for i in evict:
            _forget(eng._wpool, hashes[i])
        again = np.concatenate([tokens[:32], tokens[60:87]])
        out = _ask(eng, again, 10)
        rec = eng.ledger.records()[-1]
        assert rec["reused_blocks"] == reused
        assert _served_gap(again, out) < TOL
        assert eng.check_invariants() == []
    finally:
        eng.close()
    cold = _engine(model)
    try:
        np.testing.assert_array_equal(_ask(cold, again, 10), out)
    finally:
        cold.close()


def test_what_a_mixed_cache_cannot_run_is_refused_with_a_sentence(model):
    from ptype_tpu.serve_engine import PagedGeneratorActor, SpecConfig

    cfg, params = model
    with pytest.raises(ValueError, match="drafts with a truncated GQA"):
        PagedGeneratorActor(cfg, params=params, spec=SpecConfig(
            draft_params=params, draft_cfg=cfg))
    eng = _engine(model)
    try:
        for call in (lambda: eng.Prefill(np.arange(1, 9)[None], 4),
                     lambda: eng.MigratePlan(np.arange(1, 9)[None], 4)):
            with pytest.raises(ValueError, match="two\n? *pools|two pools"):
                call()
    finally:
        eng.close()
    with pytest.raises(ValueError, match="window layers beside"):
        gen.init_cache(cfg, 1)
    with pytest.raises(ValueError, match="attention kinds"):
        tfm.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(ValueError, match="attention kinds"):
        tfm.flops_per_token(cfg, 128)
    with pytest.raises(ValueError, match="attention kinds"):
        tfm.param_specs(cfg, {"model": 2})


def test_a_program_without_the_fields_fails_at_once(monkeypatch):
    """What the parent commit does with this cell: the family's
    ``program_config`` exits with a sentence before anything is built."""
    @dataclasses.dataclass(frozen=True)
    class Old:
        vocab_size: int = 1
        d_model: int = 1

    monkeypatch.setattr(tfm, "TransformerConfig", Old)
    with pytest.raises(SystemExit, match="attn_windows.*cannot serve"):
        FAM.program_config(SMALL, REACH, "float32")


@pytest.mark.parametrize("key,value,sentence", [
    ("scoring_func", "softmax", "scoring_func"),
    ("num_nextn_predict_layers", 1, "num_nextn_predict_layers"),
    ("sliding_windows", [8, 8, 8, 0, 8, 8, 8], "do not each state"),
    ("sliding_windows", [8, 8, 8, 8, 8, 8, 8, 0], "layer 3"),
    ("mlp_layer_types", ["sparse"] * 8, "layer 0")])
def test_what_the_file_states_and_the_program_cannot_run_is_refused(
        key, value, sentence):
    with pytest.raises(SystemExit, match=sentence):
        FAM.program_config({**SMALL, key: value}, REACH, "float32")


# ------------------------------------------------ the share and the controls


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Eight chips hold two of the sixteen experts each. The routed
    parts the eight compute, and the shared expert counted once, add
    up to what the uncut reference gives for the whole layer (float32:
    the order of the sum is all that differs)."""
    whole = {**SMALL, "num_experts": 16, "experts_held_first": 0}
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     weights.one_layer(whole, SEED, 1, "float32"))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64), jnp.float32)
    want = reference.experts(h[0], w, whole, "f32", held=(0, 16))
    cfg = dataclasses.replace(FAM.program_config(whole, REACH, "float32"),
                              dtype=jnp.float32)
    routed = {k: v for k, v in w.items() if not k.startswith("ws_")}
    total, seen = jnp.zeros_like(h), 0
    for first in range(0, 16, 2):
        share = {**routed, **{k: routed[k][first:first + 2]
                              for k in ("w_gate", "w_up", "w_down")}}
        y, load = tfm._moe_dropless(
            h, share, dataclasses.replace(cfg, experts_held=(first, 2)))
        total, seen = total + y, seen + int(load[:2].sum())
        assert int(load.sum()) == 24 * 4
        np.testing.assert_allclose(
            np.asarray(y[0]), np.asarray(reference.experts(
                h[0], share, whole, "f32", held=(first, 2), shared=False)),
            atol=TOL, rtol=0)
    assert seen == 24 * 4  # every choice fell on exactly one share
    total = total + tfm._swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"],
                                jnp.float32)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=TOL, rtol=0)


def _gap(ref, other):
    first = other.argmax(-1)
    return float(np.max(ref.max(-1) - np.take_along_axis(
        ref, first[..., None], -1)[..., 0]))


CONTROL_SEEDS = (0, 1, 2, 3)


@pytest.fixture(scope="module")
def control_readings():
    """Over four seeds x 96 positions: the served gap of each mode's
    first token against the float32 reference."""
    idx = np.arange(96)
    out = {m: [] for m in ("bf16", "fp8", "window_as_full",
                           "rope_on_full")}
    for seed in CONTROL_SEEDS:
        toks = np.asarray(jax.random.randint(
            jax.random.PRNGKey(seed), (96,), 1, 128))
        ref = ref_logits(toks, idx, seed=seed)
        for m in out:
            out[m].append(_gap(ref, ref_logits(toks, idx, m, seed=seed)))
    return out


@pytest.mark.parametrize("mode", ["fp8", "window_as_full", "rope_on_full"])
def test_a_control_does_not_pass_as_rounding(control_readings, mode):
    """The reference computed in float8, or with a window layer run as
    a full one, or with the full layers rotated, put in the program's
    place: on every seed its served gap is over the cell's limit at
    this size, and at least three times the largest that bfloat16 (what
    the configuration states) reads."""
    got = control_readings[mode]
    limit = exaone_tiny.LIMITS["served_logit_gap_max"]
    assert min(got) > limit, got
    assert min(got) >= 3 * max(control_readings["bf16"]), (
        got, control_readings["bf16"])
    assert max(control_readings["bf16"]) < limit


# ------------------------------------- a whole tiny run, each fault planted


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return perfbench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_a_whole_run_is_correct_and_reports_the_two_pools(tiny_root):
    res = run.execute(CELL, 2 ** 31 + 7, 1.0, False, jax.devices()[:1],
                      root=tiny_root)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 10
    assert res["counters"]["prefix_hit_pct"] > 20


def test_a_token_altered_comes_out_not_correct(tiny_root):
    res = run.execute(CELL, 2 ** 31 + 99, 0.5, False, jax.devices()[:1],
                      root=tiny_root, fault="token_altered")
    c = res["checks"]["served_logit_gap_max"]
    assert res["correct"] is False and not c["value"] <= c["limit"]


@pytest.mark.parametrize("mode", ["window_as_full", "rope_on_full"])
def test_a_control_in_the_programs_place_is_not_correct(tiny_root, mode):
    """As ``benchmark/readings.py`` reads a control: the run's own
    sample through the reference in ``mode``, judged by the cell's
    limit. (The float8 control needs more positions than a tiny run's
    sample of three short answers has: the test above reads it over
    96 a seed.)"""
    res = run.execute(CELL, 2 ** 31 + 3, 0.5, False, jax.devices()[:1],
                      root=tiny_root, readings=(mode, "bf16"))
    limit = res["checks"]["served_logit_gap_max"]["limit"]
    assert res["readings"][mode]["served_logit_gap_max"] > limit
    assert res["readings"]["bf16"]["served_logit_gap_max"] <= limit


# -------------------------------------------------------- counts by hand


@pytest.fixture(scope="module")
def exaone():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        return json.load(f)


def test_parameters_by_hand(exaone):
    D, H, K, Dh = 6144, 64, 8, 128
    attn = D * H * Dh * 2 + D * K * Dh * 2
    assert attn == work.attention_params(exaone) == 113_246_208
    expert = 3 * D * 2048
    norms = 2 * D + 2 * Dh
    dense = attn + norms + 3 * D * 18432
    moe = attn + norms + D * 128 + 128 + 17 * expert
    assert work.layer_params(exaone, "dense") == dense
    assert work.layer_params(exaone, "experts") == moe
    total = dense + 7 * moe + 2 * D * 19200 + D
    assert work.total_params(exaone) == total == 5_979_349_888
    assert f"{total:,}" in exaone["deployment"]
    assert weights.runs(exaone) == [
        ("dense", 0, 1), ("experts", 1, 2), ("experts", 3, 1),
        ("experts", 4, 3), ("experts", 7, 1)]


@pytest.mark.parametrize("context,window_keys", [(100, 100), (5000, 128)],
                         ids=["inside-the-window", "past-the-window"])
def test_decode_bytes_and_flops_by_hand(exaone, context, window_keys):
    """A window layer's key costs nothing once it is 128 behind."""
    D = 6144
    attn, expert = 113_246_208, 3 * D * 2048
    rows = [context] * 10
    hit = 16 * (1 - (1 - 8 / 128) ** 10)
    w = (8 * attn + 3 * D * 18432
         + 7 * (D * 128 + (1 + hit) * expert) + D * 19200)
    kv = 2 * 8 * 128
    want = 2 * (w + kv * 10 * (2 * context + 6 * window_keys))
    assert work.decode_needed_bytes(exaone, rows) == pytest.approx(want)
    # A shared prefix is read once in the full layers.
    assert work.decode_needed_bytes(exaone, rows, 256) == pytest.approx(
        want - 2 * kv * 2 * 256)
    per_tok = (8 * attn + 3 * D * 18432
               + 7 * (D * 128 + (1 + 8 * 16 / 128) * expert) + D * 19200)
    assert work.forward_flops(exaone, 10, rows) == pytest.approx(
        2.0 * per_tok * 10
        + 4.0 * 64 * 128 * 10 * (2 * context + 6 * window_keys))
    # A chunk at position 4,096: each query its own keys.
    ctx = range(4097, 4097 + 512)
    assert work.forward_flops(exaone, 512, ctx) == pytest.approx(
        2.0 * per_tok * 512
        + 4.0 * 64 * 128 * (2 * sum(ctx) + 6 * 128 * 512))


def test_cache_bytes_are_the_full_layers_alone(exaone):
    assert work.cache_bytes_per_token(exaone) == 8192
    assert work.window_bytes_per_row(exaone) == 6 * 128 * 4096
    for fn in (lambda: work.train_flops_per_token(exaone, 1024),
               lambda: FAM.train_steps(exaone, {}, None, [], "f32", 1),
               lambda: work.flash_train_floor_s(exaone, 1, 1, {})):
        with pytest.raises(SystemExit, match="served, not trained"):
            fn()


def test_configuration_keeps_every_published_width(exaone):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    assert (exaone["hidden_size"], exaone["num_attention_heads"],
            exaone["num_key_value_heads"], exaone["head_dim"],
            exaone["intermediate_size"], exaone["moe_intermediate_size"],
            exaone["num_experts_per_tok"], exaone["sliding_window"],
            exaone["published"]["num_experts"]) == (
                6144, 64, 8, 128, 18432, 2048, 8, 128, 128)
    assert exaone["head_dim"] != (exaone["hidden_size"]
                                  // exaone["num_attention_heads"])
    assert exaone["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 2
    assert exaone["sliding_windows"] == [128, 128, 128, 0] * 2
    assert exaone["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert {"norm_placement", "qk_norm", "rope", "router_bias",
            "experts_held_first"} <= set(exaone["assumed"])
    m = manifest.load()
    entry = [c for c in m["configs"] if c["name"] == "k-exaone-236b-a23b"]
    assert entry[0]["reduced"] == exaone["reduced"]
    assert entry[0]["source"] == exaone["source"]
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = [json.loads(ln) for ln in f
                   if '"K-EXAONE-236B-A23B"' in ln][0]
        differs = {k for k, v in row["config"].items()
                   if exaone.get(k) != v}
        assert differs == set(exaone["reduced"])
        assert exaone["source"] == row["source_url"]
    cfg = FAM.program_config(exaone, 34816, "bfloat16")
    assert tfm.layer_groups(cfg) == (
        ("dense+L", 1), ("experts+L", 2), ("experts+G", 1),
        ("experts+L", 3), ("experts+G", 1))
    assert (cfg.head_dim, cfg.window, cfg.held) == (128, 128, (48, 16))


def test_cell_and_traffic_are_the_ones_issue_33_names():
    m = manifest.load()
    assert manifest.check(m) == []
    cell = manifest.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "k-exaone-236b-a23b", "mixed", 1)
    with open(manifest.traffic_file("mixed", m["paths"])) as f:
        mix = json.load(f)
    assert mix["kind"] == "open" and mix["arrivals"] == "poisson"
    assert mix["shared_prefixes"] == {"count": 4, "tokens": 256}
    assert mix["suffix"] == {"dist": "lognormal", "median": 2048,
                             "sigma": 1.25, "min": 16, "max": 32768,
                             "quantum": 16}
    assert mix["output"] == {"dist": "lognormal", "median": 128,
                             "sigma": 0.7, "min": 8, "max": 512,
                             "quantum": 8}
    eng = mix["engine"]
    assert (eng["n_slots"], eng["max_len"], eng["block_tokens"],
            eng["n_blocks"], eng["prefill_chunk"]) == (32, 34816, 16,
                                                       16384, 512)
    assert mix["check_sample"] == 6
    # 0.8 x the knee the sweep found (1.2), then lowered until both
    # tails spread by less than a fifth of their bounds (PERF.md §6).
    assert (mix["knee_rps"], mix["rate_rps"]) == (1.5, 1.0)
    assert mix["rate_rps"] <= 0.8 * mix["knee_rps"]
    with open(manifest.traffic_file("chat", m["paths"])) as f:
        chat = json.load(f)
    assert mix["gateway"] == chat["gateway"]
    assert mix["deadline_s"] == chat["deadline_s"]
    for name in ("ttft_mean_ms", "itl_p95_ms", "setup_s"):
        assert name in [x["name"] for x in manifest.metrics_for(
            m, CELL, "end_to_end")]


MIXED_METRICS = [x for x in manifest.metrics_for(manifest.load(), CELL,
                                                 "per_layer")]


def test_the_cell_reports_every_layer_it_runs():
    names = {x["name"] for x in MIXED_METRICS}
    assert len(MIXED_METRICS) == 28
    assert all(n.endswith(".mixed") for n in names)
    assert {n + ".mixed" for n in (
        "decode_attn_window_pct", "decode_attn_full_pct",
        "prefill_attn_window_pct", "prefill_attn_full_pct",
        "cache_held_vs_uniform_pct", "expert_load_max_over_mean",
        "decode_hbm_roofline", "step_mfu", "compiles_in_window")} <= names


@pytest.mark.parametrize("x", MIXED_METRICS, ids=lambda x: x["name"])
def test_every_mixed_metric_binds_a_reader(x):
    assert x["workloads"] == [CELL]
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           x["name"] + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    inspect.signature(reader.read).bind({}, **spec["params"])
    if "cell" in spec["params"]:
        assert spec["params"]["cell"] == CELL
    if "known" in spec["params"]:
        assert "attn" not in spec["params"]["known"]
        assert {"attn_window", "attn_full"} <= set(spec["params"]["known"])


# ------------------------------------------------------------- the readers

STEP = "jit(engine_step)/while/body/closed_call/"
CHUNK = "jit(prefill_chunk)/while/body/closed_call/"


def mixed_trace():
    """Two 20-ms decode steps (id 7): the window list's loop 2 (its
    gather 1 of them), the full list's 5 (gather 2), experts 6 +
    router 1 + shared 1 inside ``mlp``, mlp's own norm 1, qkv 2,
    kv_write 1, a compiler's copy 1; a 10-ms prefill chunk (id 9):
    window 1, full 3 + its gather 1, experts 4, unscoped 1."""
    ops, modules = [], []
    for t0 in (10, 40):
        ops += [
            op("while.2", t0, 20, 7),
            op("fusion.1", t0, 1, 7, STEP + "attn_window/while/body/dot:"),
            op("fusion.2", t0 + 1, 1, 7,
               STEP + "attn_window/while/body/kv_gather/gather:"),
            op("fusion.3", t0 + 2, 3, 7, STEP + "attn_full/while/body/dot:"),
            op("fusion.4", t0 + 5, 2, 7,
               STEP + "attn_full/while/body/kv_gather/gather:"),
            op("fusion.5", t0 + 7, 6, 7, STEP + "mlp/experts/dot_general:"),
            op("fusion.6", t0 + 13, 1, 7, STEP + "mlp/router/dot_general:"),
            op("fusion.7", t0 + 14, 1, 7, STEP + "mlp/shared_expert/dot:"),
            op("fusion.8", t0 + 15, 1, 7, STEP + "mlp/mul:"),
            op("fusion.9", t0 + 16, 2, 7, STEP + "qkv/dot_general:"),
            op("fusion.10", t0 + 18, 1, 7, STEP + "kv_write/scatter:"),
            op("copy.11", t0 + 19, 1, 7, ""),
        ]
        modules.append(("jit_engine_step(7)", t0, 20))
    ops += [op("fusion.1", 70, 1, 9, CHUNK + "attn_window/while/body/dot:"),
            op("fusion.2", 71, 3, 9, CHUNK + "attn_full/while/body/dot:"),
            op("fusion.3", 74, 1, 9,
               CHUNK + "attn_full/while/body/kv_gather/gather:"),
            op("fusion.4", 75, 4, 9, CHUNK + "mlp/experts/dot_general:"),
            op("copy.5", 79, 1, 9, "")]
    modules.append(("jit_prefill_chunk(9)", 70, 10))
    return ops, modules


def _metric(name):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".mixed.json")) as f:
        return json.load(f)["params"]


@pytest.mark.parametrize("name,want", [
    ("decode_attn_window_pct", 5.0), ("decode_attn_full_pct", 15.0),
    ("decode_kv_gather_pct", 15.0), ("decode_experts_pct", 40.0),
    ("decode_matmul_pct", 15.0), ("decode_kv_write_pct", 5.0),
    ("decode_unscoped_pct", 5.0), ("prefill_attn_window_pct", 10.0),
    ("prefill_attn_full_pct", 40.0), ("prefill_experts_pct", 40.0),
    ("prefill_unscoped_pct", 10.0)])
def test_named_scope_reader_on_a_mixed_trace(name, want):
    """The shipped metric files over the trace above: a list's loop is
    its attention kind's, the gather inside it ``kv_gather``'s (the
    innermost name the file states takes the operation)."""
    xs, tr = made(*mixed_trace())
    assert named_scope_time_pct.read(ctx_of(xs, tr), **_metric(name)) == \
        pytest.approx(want)


def test_cache_share_reader_on_hand_made_records(exaone):
    """Three iterations' records in the window and one before it: two
    full layers' blocks and six window layers' against eight layers of
    the blocks one kind of cache would hold."""
    rec = lambda f, w, u, fr=0: {"full_blocks": f, "window_blocks": w,  # noqa: E731
                                 "window_freed": fr, "uniform_blocks": u}
    host = [("serve.cache", -5, 0.01, rec(999, 999, 999)),
            ("serve.cache", 10, 0.01, rec(100, 18, 100, 3)),
            ("serve.cache", 30, 0.01, rec(300, 27, 300)),
            ("serve.cache", 50, 0.01, rec(200, 27, 200))]
    xs, tr = made(*mixed_trace(), host=host)
    params = _metric("cache_held_vs_uniform_pct")
    ctx = {**ctx_of(xs, tr), "cfg": exaone}
    assert cache_share.read(ctx, **params) == pytest.approx(
        100.0 * (2 * 600 + 6 * 72) / (8 * 600))
    # Nothing where nothing is: no record, no layer kinds, no trace.
    assert cache_share.read({**ctx_of(*made(*mixed_trace())),
                             "cfg": exaone}, **params) is None
    assert cache_share.read({**ctx_of(xs, tr), "cfg": {}}, **params) is None
    assert cache_share.read({"trace": None, "cfg": exaone},
                            **params) is None
