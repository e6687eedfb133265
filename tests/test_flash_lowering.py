"""Mosaic lowering contract for the Pallas flash-attention kernels,
checked on CPU (tier-1): the (8, 128) block-shape divisibility rule
over every BlockSpec the three pallas_calls declare, for the configs
the bench/train paths actually run — the BENCH_r02 regression (an LSE
output block with a squeezed size-1 dim second-to-last) stays dead.
Plus a minimal interpreter-mode fwd+bwd so the kernel path itself (not
just the spec table) is exercised in the fast tier."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu.ops.flash_attention import (LANES, _fwd,
                                           check_tpu_lowering,
                                           flash_attention,
                                           lowering_block_shapes)


def test_bench_configs_lower_clean():
    # (B, H, S, Dh, K) — optimus-125m (6×128 heads), the GPT-2-shaped
    # 12×64 variant BENCH_r02 failed on, llama-3-8b GQA, tiny.
    for B, H, S, Dh, K in ((16, 6, 1024, 128, None),
                           (8, 12, 1024, 64, None),
                           (1, 32, 8192, 128, 8),
                           (2, 4, 128, 16, None)):
        bad = check_tpu_lowering(B, H, S, Dh, K)
        assert not bad, bad
        # Smaller block plans from the PERF sweep must lower too.
        for bq, bk in ((512, 1024), (512, 512), (256, 512)):
            bad = check_tpu_lowering(B, H, S, Dh, K,
                                     block_q=bq, block_k=bk)
            assert not bad, bad


def test_rule_catches_bad_blocks():
    # A 12-row block: not a multiple of 8, not the array dim — the
    # class of violation the checker exists to flag.
    bad = check_tpu_lowering(8, 12, 1024, 64, block_q=12)
    assert bad and any("not divisible by 8" in b for b in bad)


def test_lse_output_is_lane_replicated():
    """The BENCH_r02 fix as a shape contract: the forward's LSE
    residual is (B, H, S, LANES) — 128-lane replicated, never a
    squeezed (B, H, S) row layout."""
    specs = dict(
        (name, (block, array)) for name, block, array in
        lowering_block_shapes(8, 12, 1024, 64))
    block, array = specs["fwd/lse"]
    assert array[-1] == LANES and block[-1] == LANES
    assert block[-2] % 8 == 0


def test_interpret_mode_forward_emits_lse_and_grads_flow():
    """Exercise the real kernels (interpret mode) in the fast tier:
    forward with the LSE residual, then a backward through the
    custom VJP — the full path a TPU session compiles."""
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    B, S, H, Dh = 1, 64, 2, 16
    q = jax.random.normal(kq, (B, H, S, Dh), jnp.float32)
    k = jax.random.normal(kk, (B, H, S, Dh), jnp.float32)
    v = jax.random.normal(kv, (B, H, S, Dh), jnp.float32)
    o, lse = _fwd(q, k, v, block_q=32, block_k=32, causal=True,
                  interpret=True)
    assert o.shape == (B, H, S, Dh)
    assert lse.shape == (B, H, S, LANES)
    # Lane-replication is real: every lane carries the row's LSE.
    np.testing.assert_array_equal(np.asarray(lse[..., 0]),
                                  np.asarray(lse[..., LANES - 1]))

    def loss(q, k, v):
        out = flash_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
            jnp.swapaxes(v, 1, 2), block_q=32, block_k=32)
        return jnp.sum(out ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def test_multi_device_trainer_runs_the_kernel_on_the_local_shard(
        monkeypatch):
    """A ``pallas_call`` is opaque to the SPMD partitioner: JAX refuses
    to lower a bare Mosaic kernel inside a multi-device jit. The
    Trainer's flash path runs under ``shard_map``: lowered for TPU
    (cross-lowering needs no chip), the step holds the three named
    kernels, each at batch B/n — and heads H/m on a model axis."""
    import importlib
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chip_smoke import lowered_kernels

    from ptype_tpu.models import transformer as tfm
    from ptype_tpu.parallel.mesh import build_mesh
    from ptype_tpu.train.data import synthetic_batches
    from ptype_tpu.train.trainer import Trainer

    fa = importlib.import_module("ptype_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_on_cpu", lambda: False)  # no interpreter
    cfg = tfm.preset("tiny", attn_impl="flash")  # H = K = 4, Dh = 16
    B, S = 8, 32

    def kernels(axes, attn_fn=None):
        tr = Trainer(cfg, build_mesh(axes), attn_fn=attn_fn)
        batch = tr.shard_batch(
            next(synthetic_batches(cfg.vocab_size, B, S)))
        text = tr.train_step.trace(tr.state, batch).lower(
            lowering_platforms=("tpu",)).as_text()
        return lowered_kernels(text)

    seen = kernels({"data": 4})
    assert {n for n, _ in seen} == set(fa.KERNEL_NAMES)
    assert all(d == (B // 4, 4, S, 16) for _, d in seen), seen
    seen = kernels({"data": 2, "model": 2})
    assert seen and all(d == (B // 2, 2, S, 16) for _, d in seen), seen
    # What the shard_map replaced: the bare kernel does not lower.
    with pytest.raises(NotImplementedError, match="shard_map"):
        kernels({"data": 4}, attn_fn=fa.make_flash_attn_fn())
