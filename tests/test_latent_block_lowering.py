"""The latent block kernel at the served shapes, with no chip: its
tiling contract as data (the rule tests/test_flash_lowering.py applies
to the flash kernels), its lowering for TPU inside the decode step, a
compile by the TPU's compiler for a described v5e where one can be
described here, and a tiny A.X-K1 engine that serves the reference's
tokens through it; and GLM-5's prefill chunk compiled for that chip,
its temporaries audited (here because one test file alone describes
the chip)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import axk1_tiny  # noqa: E402  (tests/conftest.py puts tests/benchmark on the path)
from benchmark import family  # noqa: E402
from chip_smoke import lowered_kernels, scoped_vmem_bytes  # noqa: E402

from ptype_tpu.models import generate as gen  # noqa: E402
from ptype_tpu.models import transformer as tfm  # noqa: E402
from ptype_tpu.ops import latent_block_attention as lba  # noqa: E402
from ptype_tpu.ops.latent_block_attention import (  # noqa: E402
    KERNEL_NAME, VMEM_CAP, check_tpu_lowering, latent_block_attention,
    vmem_bytes)

#: ``a.x-k1.serve-hotdocs``: 64 lanes, 64 heads, a 576-wide row stored
#: in 640 lanes whose leading 512 are the values, blocks of 16, a reach
#: of 19,456 tokens, 8 layers over a pool of 16,384 blocks.
LANES, HEADS, ROW, VALUES, BT, REACH, LAYERS, POOL = (
    64, 64, 640, 512, 16, 19456, 8, 16384)


def cell_list():
    """The list's shape at the cell's size (no lane live)."""
    nb = REACH // BT
    lst, n = gen.live_block_list(
        np.zeros((LANES, nb), np.int32), np.zeros(LANES, np.int32),
        np.zeros(LANES, bool), BT, own_tiles=True)
    return lst.shape, n


def test_cell_shapes_pass_the_tiling_rule():
    (_, max_tiles, tile), _ = cell_list()
    assert (max_tiles, tile) == (320, gen.LIVE_TILE_BLOCKS)
    for sub in (None, 16, 32, 128):
        bad = check_tpu_lowering(LANES, HEADS, ROW, VALUES, BT, tile,
                                 max_tiles, LAYERS * POOL, sub)
        assert not bad, bad
    assert vmem_bytes(LANES, HEADS, ROW, VALUES, BT, tile) < VMEM_CAP // 2


@pytest.mark.parametrize("over,says", [
    ({"block_tokens": 12}, "rows/block"),   # not whole bf16 sublane tiles
    ({"v_dim": 500}, "rows/values"),        # the values end inside a lane tile
    ({"B": 1024}, "vmem"),                  # queries and output resident
])
def test_rule_catches_what_would_not_lower(over, says):
    kw = dict(B=LANES, H=HEADS, D=ROW, v_dim=VALUES, block_tokens=BT,
              tile=256, max_tiles=320, n_rows=LAYERS * POOL)
    bad = check_tpu_lowering(**{**kw, **over})
    assert bad and any(b.startswith(says) for b in bad), bad


def _kernel_call(sds):
    (shape, _) = cell_list()
    i32 = jnp.int32

    def call(q, bank, base, lst, n, limits):
        return latent_block_attention(q, bank, base, (lst, n), limits,
                                      scale=0.1, v_dim=VALUES)

    return call, (sds((LANES, HEADS, ROW), jnp.bfloat16),
                  sds((LAYERS * POOL, BT, ROW), jnp.bfloat16),
                  sds((), i32), sds(shape, i32), sds((), i32),
                  sds((LANES,), i32))


def test_kernel_lowers_for_tpu_at_the_cells_shapes(monkeypatch):
    """Cross-lowered (no chip, no TPU compiler): the call is a Mosaic
    custom call under its name, not the interpreter's loops."""
    monkeypatch.setattr(lba, "_on_cpu", lambda: False)
    call, args = _kernel_call(jax.ShapeDtypeStruct)
    text = jax.jit(call).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert [name for name, _ in lowered_kernels(text)] == [KERNEL_NAME]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_tpu_compiler_takes_the_kernel_at_the_cells_shapes(one_chip,
                                                           monkeypatch):
    """Compiled for a described v5e: what the interpreter cannot show
    (a slice off the tiling, more VMEM than a kernel may have) is
    refused here, and the bank is the custom call's operand as it is:
    2.68 GB of arguments, no temporary of its size."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(lba, "_on_cpu", lambda: False)
    call, args = _kernel_call(
        lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip))
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(call).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()
    mem = compiled.memory_analysis()
    bank_bytes = LAYERS * POOL * BT * ROW * 2
    assert mem.temp_size_in_bytes < bank_bytes // 100
    used, = scoped_vmem_bytes(compiled.as_text()) or [0]
    assert used <= vmem_bytes(LANES, HEADS, ROW, VALUES, BT, 256)


def test_glm5_chunk_holds_no_gathered_rows_and_no_reach_sized_temporary(
        one_chip):
    """``progaudit``'s bound on GLM-5's prefill chunk at the docqa
    cell's widths (one dense layer; 512 queries, 64 heads, a 640-lane
    latent row, top 2,048), compiled for a described v5e at two
    reaches: the chunk walks its table with the indexer's selection as
    a mask, so no temporary holds a block's gathered rows (128 x 2,048
    x 640 bf16 = 335 MB; the gather form held 524-540 MB of
    temporaries here, PR 38) and none grows with the reach."""
    import json

    from jax.experimental.compilation_cache import compilation_cache

    from ptype_tpu import progaudit

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "glm-5.json")) as f:
        glm = {**json.load(f), "num_hidden_layers": 1, "vocab_size": 128}
    fam = family.of(glm)
    la = fam.program_config(glm, 128, "bfloat16").latent
    gathered = 128 * la.index_topk * la.cache_dim * 2
    bt, C, i32 = 16, 512, jnp.int32

    def sds(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    def temporaries(reach):
        cfg = fam.program_config(glm, reach, "bfloat16")
        nb = reach // bt
        params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)))
        banks = {n: sds((1, nb + 1, bt) + w, cfg.dtype)
                 for n, w in tfm.cache_spec(cfg).items()}

        def chunk(params, banks, tokens, start, length, table):
            return gen.prefill_chunk_banks(params, tokens, start, length,
                                           cfg, banks, table)[:2]

        rep = progaudit.audit(
            chunk, (params, banks, sds((1, C), i32), sds((), i32),
                    sds((), i32), sds((nb,), i32)),
            name="serve.latent_prefill_chunk", donate_argnums=(1,),
            expect_collectives=0, max_temp_bytes=gathered // 4)
        rep.raise_if_failed()
        return rep.temp_bytes

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        half, whole = temporaries(10240), temporaries(20480)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()
    assert 0 < whole <= 1.05 * half


# ------------------------------------------------- through a tiny engine

SMALL = axk1_tiny.SMALL
FAM = family.of(SMALL)
SEED, TOL = 11, 2e-5


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(FAM.program_config(SMALL, 128, "float32"),
                              dtype=jnp.float32)
    return cfg, FAM.tree(SMALL, SEED, "float32")


def test_decode_step_holds_the_kernel_and_no_list_loop(model, monkeypatch):
    """What the engine compiles for a latent cache with no indexer:
    the step, lowered for TPU, holds one kernel a layer group and the
    list is read by nothing else."""
    monkeypatch.setattr(lba, "_on_cpu", lambda: False)
    cfg, params = model
    ns, bt, nblk, nb = 2, 16, 24, 8
    (name, width), = tfm.cache_spec(cfg).items()
    banks = {name: jnp.zeros((cfg.n_layers, nblk, bt) + width,
                             jnp.float32)}
    lst, n = gen.live_block_list(np.zeros((ns, nb), np.int32),
                                 np.zeros(ns, np.int32),
                                 np.zeros(ns, bool), bt, own_tiles=True)
    row = jnp.zeros((ns,), jnp.int32)

    def step(params, banks, ll):
        return gen.decode_step_banks(params, row, row, cfg, banks,
                                     jnp.zeros((ns, nb), jnp.int32), row,
                                     row, live_list=ll)[:2]

    text = jax.jit(step).trace(
        params, banks, (jnp.asarray(lst), jnp.asarray(n))).lower(
        lowering_platforms=("tpu",)).as_text()
    seen = lowered_kernels(text)
    # One dense layer, then the expert layers: two scans, a call each.
    assert [k for k, _ in seen] == [KERNEL_NAME] * 2, seen


def test_tiny_engine_serves_the_references_tokens(model):
    """A whole request through ``PagedGeneratorActor``: the prompt in
    chunks through the table walk, every decode step through the
    kernel (interpreted here); each served token is the float32
    reference's first, and the ledger says which attention ran."""
    from ptype_tpu.metrics import MetricsRegistry
    from ptype_tpu.serve_engine import PagedGeneratorActor

    cfg, params = model
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (96,), 1, SMALL["vocab_size"]), np.int32)
    eng = PagedGeneratorActor(cfg, params=params, n_slots=2,
                              block_tokens=16, prefill_chunk=32,
                              n_blocks=24,
                              metrics_registry=MetricsRegistry())
    try:
        prompt = toks[:40]
        out = np.asarray(eng.Generate(jnp.asarray(prompt)[None], 24))[0]
        row = np.concatenate([prompt, out]).astype(np.int32)
        idx = len(prompt) - 1 + np.arange(len(out))
        ref = np.asarray(FAM.served_logits(
            SMALL, SEED, "float32", jnp.asarray(row)[None],
            jnp.asarray(idx)[None], modes=("f32",))["f32"])[0]
        assert out.tolist() == ref.argmax(-1).tolist()
        assert float(np.max(ref.max(-1) - ref[np.arange(len(out)), out])
                     ) < TOL
        s = eng.ledger.summary()
        assert s["decode_attn"] == "latent_kernel"
        assert eng.Info()["decode_attn"] == "latent_kernel"
        assert s["kv_tiles"] == 1.0 and 3.0 <= s["kv_blocks"] <= 4.0
    finally:
        eng.close()
