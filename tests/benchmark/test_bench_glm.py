"""The glm_moe_dsa family (ISSUE 28) at test size on the CPU: the
program's paged path (absorbed latent attention behind the indexer's
selection, dropless experts over a share) against the family's plain
reference on seeded weights; the share test; the selection control; the
family's counts by hand; the shipped configuration, cell and metric
files; the two new readers on hand-made traces."""

import dataclasses
import functools
import inspect
import json
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

import glm_tiny  # noqa: E402
from benchmark import family, manifest  # noqa: E402
from benchmark.families.glm_moe_dsa import reference, weights, work  # noqa: E402
from benchmark.readers import moe_load, named_scope_time_pct  # noqa: E402
from ptype_tpu.models import generate as gen  # noqa: E402
from ptype_tpu.models import transformer as tfm  # noqa: E402
from test_bench_seam import ctx_of, made, op  # noqa: E402

SMALL = glm_tiny.SMALL
FAM = family.of(SMALL)
SEED = 11
BT, N_BLOCKS, REACH = 8, 40, 128
NB = REACH // BT
#: Program and reference both compute in float32 here; they differ in
#: the order of their sums (absorbed against expanded attention, one
#: grouped product against a loop over experts, blocks of queries), so
#: logits of magnitude ~1 agree to a few float32 roundings. A bfloat16
#: matmul anywhere would read 1e-2.
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


@pytest.fixture(scope="module")
def ref_logits(tokens):
    """The reference's logits at every position of the 96-token row."""
    idx = jnp.arange(96)[None]
    return np.asarray(FAM.served_logits(
        SMALL, SEED, "float32", jnp.asarray(tokens)[None], idx)["f32"])[0]


class Paged:
    """The two paged programs over banks of noise (a row that read a
    key it never wrote cannot pass)."""

    def __init__(self, model, noise=5):
        self.cfg, self.params = model
        ks = jax.random.split(jax.random.PRNGKey(noise), 2)
        self.banks = {
            n: jax.random.normal(k, (self.cfg.n_layers, N_BLOCKS, BT)
                                 + shape, jnp.float32)
            for k, (n, shape) in zip(ks, tfm.cache_spec(self.cfg).items())}
        self.load = None

    def prefill(self, toks, start, table, bucket):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(toks)] = toks
        tab = np.zeros(NB, np.int32)
        tab[:len(table)] = table
        logits, self.banks, self.load = _chunk_prog(self.cfg)(
            self.params, self.banks, jnp.asarray(padded),
            jnp.int32(start), jnp.int32(len(toks)), jnp.asarray(tab))
        return np.asarray(logits)[0]

    def decode(self, tok, pos, table):
        tab = np.zeros((1, NB), np.int32)
        tab[0, :len(table)] = table
        logits, self.banks, self.load = _step_prog(self.cfg)(
            self.params, self.banks, jnp.asarray([tok]),
            jnp.asarray([pos]), jnp.asarray(tab),
            jnp.asarray([table[pos // BT]]), jnp.asarray([pos % BT]))
        return np.asarray(logits)[0]


@functools.lru_cache(maxsize=None)
def _chunk_prog(cfg):
    return jax.jit(lambda p, b, t, s, n, tab: gen.prefill_chunk_banks(
        p, t, s, n, cfg, b, tab))


@functools.lru_cache(maxsize=None)
def _step_prog(cfg):
    return jax.jit(lambda p, b, tok, pos, tabs, wb, wo:
                   gen.decode_step_banks(p, tok, pos, cfg, b, tabs, wb, wo))


TABLE = list(range(3, 3 + NB))


# ------------------------------------------- the program and the reference


@pytest.mark.parametrize("prompt,new", [(8, 6), (40, 10)],
                         ids=["under-topk", "over-topk"])
def test_prefill_then_decode_agrees_with_the_reference(
        model, tokens, ref_logits, prompt, new):
    """Prefill a prompt, then decode through the paged cache, the served
    tokens forced to the row's own: every logit vector equals the
    reference's full forward at that position. Contexts 8..14 select
    every key (top-k 16); contexts 40..50 leave most of them out."""
    pg = Paged(model)
    got = [pg.prefill(tokens[:prompt], 0, TABLE, 64)]
    for pos in range(prompt, prompt + new):
        got.append(pg.decode(int(tokens[pos]), pos, TABLE))
    want = ref_logits[prompt - 1:prompt + new]
    np.testing.assert_allclose(np.stack(got), want, atol=TOL, rtol=0)


def test_absorbed_agrees_with_expanded(model, tokens, ref_logits):
    """The contiguous forward (expanded: per-head keys and values made
    from every latent, unselected keys masked) and the paged path
    (absorbed: W_UK folded into the query, the selected cache rows read
    as they lie) are one attention, and both the reference's."""
    cfg, params = model
    expanded = np.asarray(tfm.forward(params, jnp.asarray(tokens)[None],
                                      cfg))[0]
    np.testing.assert_allclose(expanded, ref_logits, atol=TOL, rtol=0)
    pg = Paged(model)
    pg.prefill(tokens[:64], 0, TABLE, 64)
    absorbed = [pg.decode(int(tokens[p]), p, TABLE) for p in range(64, 72)]
    np.testing.assert_allclose(np.stack(absorbed), expanded[64:72],
                               atol=TOL, rtol=0)


def test_chunked_prefill_agrees_with_one_chunk(model, tokens):
    """40 tokens as one chunk, and as 16 + 16 + 8 (a query of a later
    chunk selects among everything written before it plus the chunk):
    the same last logits, the same rows of both banks."""
    one, parts = Paged(model), Paged(model)
    whole = one.prefill(tokens[:40], 0, TABLE, 64)
    for start, n in ((0, 16), (16, 16), (32, 8)):
        last = parts.prefill(tokens[start:start + n], start, TABLE, 16)
    np.testing.assert_allclose(last, whole, atol=TOL, rtol=0)
    for name in one.banks:
        np.testing.assert_allclose(
            np.asarray(parts.banks[name][:, 3:8]),
            np.asarray(one.banks[name][:, 3:8]), atol=TOL, rtol=0)


def test_queries_in_blocks_agree_with_all_at_once(model, tokens,
                                                  monkeypatch):
    """A chunk of more queries than ``QUERY_BLOCK`` is scored, selected
    and gathered a block of queries at a time (512 queries against 20k
    keys would hold 2.5 GB at once): the same logits and banks."""
    from ptype_tpu.models import sparse_mla

    whole = Paged(model)
    want = whole.prefill(tokens[:64], 0, TABLE, 64)
    monkeypatch.setattr(sparse_mla, "QUERY_BLOCK", 16)
    _chunk_prog.cache_clear()
    try:
        blocked = Paged(model)
        got = blocked.prefill(tokens[:64], 0, TABLE, 64)
    finally:
        _chunk_prog.cache_clear()
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    for name in whole.banks:
        np.testing.assert_allclose(np.asarray(blocked.banks[name]),
                                   np.asarray(whole.banks[name]),
                                   atol=TOL, rtol=0)


def test_prefix_hit_agrees_with_a_cold_prompt(model, tokens):
    """A second prompt that shares its first 32 tokens (four sealed
    blocks) with one already resident starts at position 32 on a table
    whose first four blocks are the first prompt's: the same logits as
    prefilling it cold into blocks of its own."""
    other = np.concatenate([tokens[:32], tokens[60:76]])
    warm = Paged(model)
    warm.prefill(tokens[:48], 0, TABLE, 64)
    shared = TABLE[:4] + [30, 31, 32]
    hit = warm.prefill(other[32:], 32, shared, 16)
    cold = Paged(model, noise=6).prefill(other, 0, [20 + i for i in
                                                    range(7)], 64)
    np.testing.assert_allclose(hit, cold, atol=TOL, rtol=0)
    nxt = int(tokens[80])
    np.testing.assert_allclose(
        warm.decode(nxt, 48, shared),
        Paged(model, noise=6).prefill(np.append(other, nxt), 0,
                                      [20 + i for i in range(7)], 64),
        atol=TOL, rtol=0)


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
    """How far each served token's logit lies below the reference's
    best at its position, worst case."""
    row = np.concatenate([prompt, out]).astype(np.int32)
    idx = (len(prompt) - 1 + np.arange(len(out)))[None]
    ref = np.asarray(FAM.served_logits(
        SMALL, SEED, "float32", jnp.asarray(row)[None],
        jnp.asarray(idx))["f32"])[0]
    return float(np.max(ref.max(-1) - ref[np.arange(len(out)), out]))


def test_engine_serves_the_references_tokens_and_counts_its_load(
        model, tokens):
    """The normal path: ``PagedGeneratorActor`` over a pool allocated
    from the model's own description of what a token holds. Every
    served token is the reference's first (float32 both: a gap is a
    rounding); the router's load arrives with the step's tokens."""
    eng = _engine(model)
    try:
        assert set(eng.pool.banks) == {"ckv", "ki"}
        assert eng.pool.banks["ckv"].shape == (3, 24, 16, 20)
        assert eng.pool.banks["ki"].shape == (3, 24, 16, 8)
        assert eng.pool.block_shapes() == {"ckv": (3, 16, 20),
                                           "ki": (3, 16, 8)}
        prompt = tokens[:40]
        out = np.asarray(eng.Generate(jnp.asarray(prompt)[None], 12))[0]
        assert _served_gap(prompt, out) < TOL
        load = eng.ledger.summary()["moe_load"]
        # 11 decode iterations x 1 live lane (of 2) x 2 choices x 2
        # expert layers.
        assert load["iterations"] == 11 and len(load["held"]) == 4
        assert sum(load["held"]) + load["elsewhere"] == 11 * 1 * 2 * 2
        # A second ask of the same document reuses its sealed blocks.
        again = np.concatenate([tokens[:32], tokens[50:58]])
        out2 = np.asarray(eng.Generate(jnp.asarray(again)[None], 6))[0]
        assert eng.ledger.records()[-1]["reused_blocks"] == 2
        assert _served_gap(again, out2) < TOL
    finally:
        eng.close()


def test_moe_load_record_reaches_a_live_capture_whole(model, tokens,
                                                      tmp_path):
    """The record as a profiler capture holds it (an annotation's
    metadata is a comma-separated list, so the counts are not): what
    the reader sums is what the ledger kept."""
    from benchmark import xplane, xstats

    eng = _engine(model)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.Generate(jnp.asarray(tokens[:40])[None], 5)
    finally:
        jax.profiler.stop_trace()
        eng.close()
    xs = xstats.read(xplane.find_xplane(str(tmp_path)),
                     lambda plane, line: plane.startswith("/host:"))
    recs = [e for p in xs["planes"] for ln in p["lines"]
            for e in ln["events"] if e[0] == moe_load.RECORD]
    kept = eng.ledger.summary()["moe_load"]
    assert len(recs) == kept["iterations"] == 4
    lo, hi = min(e[1] for e in recs), max(e[1] for e in recs) + 1
    tr = {"planes": [{"name": "/host:CPU", "lines": [{
        "name": "t", "events": [[xplane.WINDOW_SPAN, lo, hi - lo]]}]}]}
    ctx = {"trace": tr, "xstats": xs, "counters": {}, "notes": {}}
    held = kept["held"]
    assert moe_load.read(ctx, cell=CELL) == pytest.approx(
        max(held) / (sum(held) / len(held)))


def test_migrated_sequence_decodes_as_at_home(model, tokens):
    """Prefill on one replica, both banks packed by the pool's own
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
        assert set(wire["blocks"][0]) >= {"ckv", "ki"}
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
    with pytest.raises(ValueError, match="attn='gather'"):
        _engine(model, attn="kernel")
    for fn in (lambda: FAM.train_flops_per_token(SMALL, 64),
               lambda: FAM.flash_train_floor_s(SMALL, 1, 64, {}),
               lambda: FAM.train_steps(SMALL, {}, None, [], "f32", 1)):
        with pytest.raises(SystemExit, match="served, not trained"):
            fn()


# ------------------------------------------------- the share and the control


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Four chips hold two of the eight experts each. The routed parts
    the four compute, and the shared expert counted once, add up to
    what the uncut reference gives for the whole layer (float32: the
    order of the sum is all that differs)."""
    whole = {**SMALL, "n_routed_experts": 8, "experts_held_first": 0}
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     weights.one_layer(whole, SEED, 1, "float32"))
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64), jnp.float32)
    want = reference.experts(h[0], w, whole, "f32", held=(0, 8))
    cfg = dataclasses.replace(FAM.program_config(whole, REACH, "float32"),
                              dtype=jnp.float32)
    routed = {k: v for k, v in w.items() if not k.startswith("ws_")}
    total, seen = jnp.zeros_like(h), 0
    for first in range(0, 8, 2):
        share = {**routed, **{k: routed[k][first:first + 2]
                              for k in ("w_gate", "w_up", "w_down")}}
        y, load = tfm._moe_dropless(
            h, share, dataclasses.replace(cfg, experts_held=(first, 2)))
        total, seen = total + y, seen + int(load[:2].sum())
        assert int(load.sum()) == 24 * 2
    assert seen == 24 * 2  # every choice fell on exactly one share
    total = total + tfm._swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"],
                                jnp.float32)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=TOL, rtol=0)


def _gap(ref, other):
    first = other.argmax(-1)
    return float(np.max(ref.max(-1) - np.take_along_axis(
        ref, first[..., None], -1)[..., 0]))


def test_leaving_the_indexer_out_does_not_pass_as_rounding():
    """The control: the reference with its selection replaced by "the
    most recent top-k keys", put in the program's place, reads a served
    gap at least three times the largest the program itself (bfloat16
    compute, as configured) reads at this size, over the same seeds."""
    cfg = FAM.program_config(SMALL, REACH, "float32")
    idx = jnp.tile(jnp.arange(96)[None], (2, 1))
    program, control = [], []
    for seed in range(4):
        toks = jax.random.randint(jax.random.PRNGKey(seed), (2, 96), 1, 128)
        ref = np.asarray(FAM.served_logits(SMALL, seed, "float32", toks,
                                           idx)["f32"])
        program.append(_gap(ref, np.asarray(tfm.forward(
            FAM.tree(SMALL, seed, "float32"), toks, cfg))))
        control.append(_gap(ref, np.asarray(reference.served_logits(
            SMALL, seed, "float32", toks, idx, select="recent")["f32"])))
    assert min(control) >= 3 * max(program), (program, control)


# -------------------------------------------------------- counts by hand


@pytest.fixture(scope="module")
def glm5():
    with open(os.path.join(ROOT, "benchmark", "configs", "glm-5.json")) as f:
        return json.load(f)


def test_parameters_by_hand(glm5):
    attn = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576
            + 512 * 64 * (192 + 256) + 64 * 256 * 6144)
    index = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
    assert work.attention_params(glm5) == attn == 165_019_648
    assert work.indexer_params(glm5) == index == 9_371_648
    assert work.expert_params(glm5) == 3 * 6144 * 2048 == 37_748_736
    norms = 2 * 6144 + 2048 + 512 + 2 * 128
    dense = attn + index + norms + 3 * 6144 * 12288
    moe = attn + index + norms + 6144 * 256 + 256 + 17 * 37_748_736
    assert work.layer_params(glm5, "dense") == dense
    assert work.layer_params(glm5, "experts") == moe
    total = dense + 5 * moe + 2 * 6144 * 19360 + 6144
    assert work.total_params(glm5) == total
    assert abs(total / 4.73e9 - 1) < 0.01          # ISSUE 28's count
    assert work.cache_bytes_per_token(glm5) == (576 + 128) * 2 * 6 == 8448
    tree = jax.eval_shape(lambda: FAM.tree(glm5, 1, "bfloat16"))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(tree)) == total


@pytest.mark.parametrize("context,selected", [(1000, 1000), (17000, 2048)],
                         ids=["under-2048", "over-2048"])
def test_decode_bytes_and_flops_by_hand(glm5, context, selected):
    """Two rows at one context: the indexer's keys of every held token,
    the latents of the selected ones alone; the routed experts as the
    expected number of distinct held experts two tokens hit."""
    fixed = 165_019_648 + 9_371_648
    hit = 16 * (1 - (1 - 8 / 256) ** 2)
    w = (6 * fixed + 3 * 6144 * 12288
         + 5 * (6144 * 256 + (1 + hit) * 37_748_736) + 6144 * 19360)
    cache = 6 * (2 * context * 128 + 2 * selected * 576)
    assert work.decode_needed_bytes(glm5, [context] * 2, 0) == \
        pytest.approx(2 * (w + cache), rel=1e-12)
    # A shared cached prefix's indexer keys are read once.
    assert work.decode_needed_bytes(glm5, [context] * 2, 512) == \
        pytest.approx(2 * (w + cache - 6 * 512 * 128), rel=1e-12)
    per_tok = (6 * fixed + 3 * 6144 * 12288
               + 5 * (6144 * 256 + (1 + 8 * 16 / 256) * 37_748_736)
               + 6144 * 19360)
    keys = 6 * 2 * (2 * 32 * 128 * context
                    + 2 * 64 * (512 + 576) * selected)
    assert work.forward_flops(glm5, 2, [context] * 2) == \
        pytest.approx(2 * 2 * per_tok + keys, rel=1e-12)


# --------------------------------------------------- the shipped data files

CELL = glm_tiny.CELL
WIDTHS = {"hidden_size": 6144, "num_attention_heads": 64,
          "q_lora_rank": 2048, "kv_lora_rank": 512,
          "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
          "v_head_dim": 256, "index_n_heads": 32, "index_head_dim": 128,
          "index_topk": 2048, "moe_intermediate_size": 2048,
          "num_experts_per_tok": 8, "routed_scaling_factor": 2.5,
          "intermediate_size": 12288, "n_shared_experts": 1}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_configuration_keeps_every_published_width(glm5):
    assert {k: glm5[k] for k in WIDTHS} == WIDTHS
    assert glm5["published"]["n_routed_experts"] == 256
    assert glm5["family"] == "glm_moe_dsa"
    assert {"reduced", "published", "assumed", "deployment"} <= set(glm5)
    assert sorted(glm5["reduced"]) == sorted(glm5["published"])
    cfg = FAM.program_config(glm5, 20480, "bfloat16")
    assert (cfg.n_experts, cfg.held, cfg.n_dense_layers, cfg.n_layers) == (
        256, (48, 16), 1, 6)
    assert tfm.layer_groups(cfg) == (("dense", 1), ("experts", 5))
    # 576 values a row, stored in five whole 128-lane tiles.
    assert (cfg.latent.row_dim, cfg.latent.cache_dim) == (576, 640)
    assert tfm.cache_spec(cfg) == {"ckv": (640,), "ki": (128,)}
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(ln) for ln in f if '"GLM-5"' in ln)
        assert glm5["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if glm5.get(k) != v}
        assert differs == set(glm5["reduced"])


def test_cell_and_traffic_are_the_ones_issue_28_names():
    m = manifest.load()
    assert manifest.check(m) == []
    cell = manifest.cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5", "docqa", 1)
    with open(manifest.traffic_file("docqa", m["paths"])) as f:
        mix = json.load(f)
    assert mix["kind"] == "open" and mix["arrivals"] == "poisson"
    assert mix["shared_prefixes"] == {"count": 8, "tokens": 16384}
    assert mix["suffix"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.7, "min": 16, "max": 2048,
                             "quantum": 16}
    assert mix["output"] == {"dist": "lognormal", "median": 128,
                             "sigma": 0.7, "min": 8, "max": 512,
                             "quantum": 8}
    eng = mix["engine"]
    assert (eng["n_slots"], eng["max_len"], eng["block_tokens"],
            eng["n_blocks"], eng["prefill_chunk"]) == (64, 20480, 16,
                                                       20480, 512)
    assert (mix["check_sample"], mix["check_bucket"]) == (4, 1024)
    # 0.8 x the rate a cold window sustains (PERF.md §4's sweep).
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])
    for name in ("ttft_mean_ms", "itl_p95_ms", "setup_s"):
        assert name in [x["name"] for x in manifest.metrics_for(
            m, CELL, "end_to_end")]


DOCQA_METRICS = [x for x in manifest.metrics_for(manifest.load(), CELL,
                                                 "per_layer")]


def test_the_cell_reports_every_layer_it_runs():
    """The issue's 21 and, after review, the five of the chat cell's
    whose layers (gateway, engine loop, the step's cache write) run
    here too."""
    names = {x["name"] for x in DOCQA_METRICS}
    assert len(DOCQA_METRICS) == 26
    assert {n + ".docqa" for n in (
        "ttft_stall_ms_mean", "decode_kv_write_pct", "gateway_ms_p50",
        "engine_fetch_wait_pct", "ttft_p90_ms")} <= names
    assert any("mfu" in n for n in names)


@pytest.mark.parametrize("x", DOCQA_METRICS, ids=lambda x: x["name"])
def test_every_docqa_metric_binds_a_reader(x):
    import importlib

    assert x["workloads"] == [CELL]
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           x["name"] + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    inspect.signature(reader.read).bind({}, **spec["params"])
    if "cell" in spec["params"]:
        assert spec["params"]["cell"] == CELL


# ----------------------------------------------------------- the new readers

STEP = "jit(engine_step)/while/body/closed_call/"


def glm_trace():
    """Two 20-ms decode steps (id 7): index 6, select 4, kv_gather 2,
    attn 1, router 1 + experts 2 (both inside ``mlp``), mlp's own norm
    1, qkv 1, kv_write 1, a compiler's copy 1; and a 10-ms prefill
    chunk (id 9): index 4, select 2, attn 3, unscoped 1."""
    ops, modules = [], []
    for t0 in (10, 40):
        ops += [
            op("while.2", t0, 20, 7),
            op("fusion.1", t0, 6, 7, STEP + "index/dot_general:"),
            op("sort.1", t0 + 6, 4, 7, STEP + "select/sort:"),
            op("fusion.2", t0 + 10, 2, 7, STEP + "kv_gather/gather:"),
            op("fusion.3", t0 + 12, 1, 7, STEP + "attn/dot_general:"),
            op("fusion.4", t0 + 13, 1, 7, STEP + "mlp/router/dot_general:"),
            op("fusion.5", t0 + 14, 2, 7, STEP + "mlp/experts/ragged_dot:"),
            op("fusion.6", t0 + 16, 1, 7, STEP + "mlp/mul:"),
            op("fusion.7", t0 + 17, 1, 7, STEP + "qkv/dot_general:"),
            op("fusion.8", t0 + 18, 1, 7, STEP + "kv_write/scatter:"),
            op("copy.9", t0 + 19, 1, 7, ""),
        ]
        modules.append(("jit_engine_step(7)", t0, 20))
    chunk = "jit(prefill_chunk)/while/body/closed_call/"
    ops += [op("fusion.1", 70, 4, 9, chunk + "index/dot_general:"),
            op("sort.1", 74, 2, 9, chunk + "select/sort:"),
            op("fusion.3", 76, 3, 9, chunk + "attn/dot_general:"),
            op("copy.1", 79, 1, 9, "")]
    modules.append(("jit_prefill_chunk(9)", 70, 10))
    return ops, modules


def _metric(name):
    with open(os.path.join(ROOT, "benchmark", "metrics",
                           name + ".docqa.json")) as f:
        return json.load(f)["params"]


@pytest.mark.parametrize("name,want", [
    ("decode_index_pct", 30.0), ("decode_select_pct", 20.0),
    ("decode_kv_gather_pct", 10.0), ("decode_attn_pct", 5.0),
    ("decode_experts_pct", 15.0), ("decode_matmul_pct", 10.0),
    ("decode_kv_write_pct", 5.0), ("decode_unscoped_pct", 5.0),
    ("prefill_index_pct", 60.0), ("prefill_attn_pct", 30.0)])
def test_named_scope_reader_on_a_hand_made_trace(name, want):
    """The shipped metric files over the trace above: the innermost of
    the names the file states takes an operation (the router inside
    ``mlp`` is the experts', ``mlp``'s own norm the matmuls'), the
    container is left out, and each program's time is its own."""
    xs, tr = made(*glm_trace())
    assert named_scope_time_pct.read(ctx_of(xs, tr), **_metric(name)) == \
        pytest.approx(want)


def test_named_scope_reader_finds_nothing_where_nothing_is():
    """A program that carries none of the names, no such program in the
    window, no trace: the line leaves the metric out."""
    params = _metric("decode_index_pct")
    ops, modules = glm_trace()
    bare = [[o[0], o[1], o[2], {**o[3], "tf_op": ""}] for o in ops]
    assert named_scope_time_pct.read(ctx_of(*made(bare, modules)),
                                     **params) is None
    assert named_scope_time_pct.read(
        ctx_of(*made([o for o in ops if o[3]["program_id"] == 9],
                     modules[2:])), **params) is None
    assert named_scope_time_pct.read({"trace": None}, **params) is None
    assert named_scope_time_pct.innermost(
        "jit(f)/transpose(jvp(mlp))/experts/dot:", {"mlp", "experts"}) == \
        "experts"


def test_moe_load_reader_on_hand_made_records():
    """Three iterations' records in the window and one before it: the
    busiest held expert over the mean one."""
    host = [("serve.moe_load", -5, 0.01, {"held": "9:9:9:9",
                                          "elsewhere": 0}),
            ("serve.moe_load", 10, 0.01, {"held": "4:0:2:2",
                                          "elsewhere": 24}),
            ("serve.moe_load", 30, 0.01, {"held": "4:1:1:2",
                                          "elsewhere": 24}),
            ("serve.moe_load", 50, 0.01, {"held": "4:1:1:2",
                                          "elsewhere": 24})]
    xs, tr = made(*glm_trace(), host=host)
    params = _metric("expert_load_max_over_mean")
    assert moe_load.read(ctx_of(xs, tr), **params) == pytest.approx(
        12 / (24 / 4))
    assert moe_load.read(ctx_of(*made(*glm_trace())), **params) is None
    assert moe_load.read({"trace": None}, **params) is None
