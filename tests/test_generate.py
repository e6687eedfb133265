"""KV-cache generation: decode == full forward, greedy/sampled, MoE."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ptype_tpu.models import generate as gen
from ptype_tpu.models import transformer as tfm

CFG = tfm.preset("tiny", dtype=jnp.float32)


def _params(cfg=CFG, seed=0):
    return tfm.init_params(jax.random.PRNGKey(seed), cfg)


def test_prefill_logits_match_forward():
    params = _params()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              CFG.vocab_size, jnp.int32)
    cache = gen.init_cache(CFG, 2)
    logits, cache = gen.prefill(params, toks, CFG, cache)
    want = tfm.forward(params, toks, CFG)[:, -1]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_decode_matches_full_forward():
    """Greedy decode token-by-token == argmax of the full forward run
    on the growing sequence (the KV cache is exact)."""
    params = _params()
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                CFG.vocab_size, jnp.int32)
    out = gen.generate(params, CFG, prompt, max_new_tokens=6)

    seq = prompt
    for _ in range(6):
        logits = tfm.forward(params, seq, CFG)[:, -1]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        seq = jnp.concatenate([seq, nxt], axis=1)
    want = seq[:, 8:]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_generate_batch_and_temperature():
    params = _params()
    prompt = jnp.zeros((3, 4), jnp.int32)
    out = gen.generate(params, CFG, prompt, max_new_tokens=5,
                       temperature=1.0, rng=jax.random.PRNGKey(7))
    assert out.shape == (3, 5)
    assert np.all((np.asarray(out) >= 0)
                  & (np.asarray(out) < CFG.vocab_size))
    # Same rng → deterministic; different rng → (overwhelmingly) different.
    again = gen.generate(params, CFG, prompt, max_new_tokens=5,
                         temperature=1.0, rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))


def test_generate_respects_max_seq():
    params = _params()
    prompt = jnp.zeros((1, 120), jnp.int32)
    with pytest.raises(ValueError, match="max_seq"):
        gen.generate(params, CFG, prompt, max_new_tokens=64)


def test_moe_generate_matches_forward():
    """With ample capacity (no drops either path) MoE greedy decode ==
    step-by-step full forward — decode must not silently lose expert
    outputs to a capacity computed from the tiny per-step token count."""
    cfg = tfm.preset("tiny-moe", dtype=jnp.float32, capacity_factor=8.0)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(9), (2, 4), 0,
                                cfg.vocab_size, jnp.int32)
    out = gen.generate(params, cfg, prompt, max_new_tokens=4)
    seq = prompt
    for _ in range(4):
        logits = tfm.forward(params, seq, cfg)[:, -1]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        seq = jnp.concatenate([seq, nxt], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq[:, 4:]))


def test_generate_program_is_cached():
    params = _params()
    prompt = jnp.zeros((1, 4), jnp.int32)
    gen.generate(params, CFG, prompt, max_new_tokens=3)
    before = gen._compiled_generate.cache_info().hits
    gen.generate(params, CFG, prompt, max_new_tokens=3)
    assert gen._compiled_generate.cache_info().hits == before + 1


def test_gqa_generate_matches_forward():
    cfg = tfm.preset("tiny", dtype=jnp.float32, n_kv_heads=2)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0,
                                cfg.vocab_size, jnp.int32)
    out = gen.generate(params, cfg, prompt, max_new_tokens=4)
    seq = prompt
    for _ in range(4):
        logits = tfm.forward(params, seq, cfg)[:, -1]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        seq = jnp.concatenate([seq, nxt], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq[:, 6:]))


def test_filter_logits_top_k_and_top_p():
    from ptype_tpu.models.generate import _filter_logits

    logits = jnp.log(jnp.asarray([[0.5, 0.25, 0.15, 0.10]]))
    # top_k=2: only the two largest survive.
    out = np.asarray(_filter_logits(logits, top_k=2, top_p=1.0))
    assert np.isfinite(out[0, :2]).all() and np.isneginf(out[0, 2:]).all()
    # top_p=0.6: 0.5 alone is < 0.6 of preceding mass for token 2? The
    # nucleus keeps {0.5, 0.25} (0.5 < 0.6 at the second token's
    # preceding mass) and drops the rest.
    out = np.asarray(_filter_logits(logits, top_k=0, top_p=0.6))
    assert np.isfinite(out[0, :2]).all() and np.isneginf(out[0, 2:]).all()
    # top_p tiny: the argmax always survives.
    out = np.asarray(_filter_logits(logits, top_k=0, top_p=1e-9))
    assert np.isfinite(out[0, 0]) and np.isneginf(out[0, 1:]).all()
    # Disabled filters are a no-op.
    out = np.asarray(_filter_logits(logits, top_k=0, top_p=1.0))
    np.testing.assert_array_equal(out, np.asarray(logits))


def test_generate_top_k1_equals_greedy():
    cfg = tfm.preset("tiny", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((2, 8), jnp.int32)
    greedy = gen.generate(params, cfg, prompt, 6)
    k1 = gen.generate(params, cfg, prompt, 6, temperature=0.9,
                      top_k=1, rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))


def test_generate_top_p_validation():
    cfg = tfm.preset("tiny", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="top_p"):
        gen.generate(params, cfg, jnp.zeros((1, 4), jnp.int32), 2,
                     top_p=0.0)


def test_greedy_normalizes_sampling_params_in_cache():
    from ptype_tpu.models.generate import _compiled_generate

    cfg = tfm.preset("tiny", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((1, 4), jnp.int32)
    before = _compiled_generate.cache_info().currsize
    gen.generate(params, cfg, prompt, 2, temperature=0.0, top_k=5)
    gen.generate(params, cfg, prompt, 2, temperature=0.0, top_p=0.5)
    after = _compiled_generate.cache_info().currsize
    assert after - before <= 1, "greedy sampling params fragmented cache"


def test_stop_token_masks_tail():
    """Positions after a row's first stop token become pad; the stop
    token itself is kept; rows without a stop are untouched."""
    cfg = tfm.preset("tiny", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((2, 8), jnp.int32)
    plain = np.asarray(gen.generate(params, cfg, prompt, 8))
    # Use the model's own most-emitted token as the stop token so the
    # masking path actually triggers.
    stop = int(np.bincount(plain.ravel()).argmax())
    out = np.asarray(gen.generate(params, cfg, prompt, 8,
                                  stop_token=stop, pad_token=255))
    for row_plain, row in zip(plain, out):
        hits = np.where(row_plain == stop)[0]
        if hits.size == 0:
            np.testing.assert_array_equal(row, row_plain)
            continue
        first = hits[0]
        np.testing.assert_array_equal(row[:first + 1],
                                      row_plain[:first + 1])
        assert (row[first + 1:] == 255).all()


def test_repetition_penalty_suppresses_repeats():
    """A huge penalty forbids re-emitting any seen token (greedy): all
    emitted tokens are distinct from each other and from the prompt."""
    cfg = tfm.preset("tiny", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    out = np.asarray(gen.generate(params, cfg, prompt, 12,
                                  repetition_penalty=1e9))[0]
    emitted = list(out)
    assert len(set(emitted)) == len(emitted), f"repeat in {emitted}"
    assert not (set(emitted) & {1, 2, 3, 4}), "prompt token re-emitted"
    # penalty=1.0 is the identity (same program as before the feature).
    a = gen.generate(params, cfg, prompt, 6)
    b = gen.generate(params, cfg, prompt, 6, repetition_penalty=1.0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="repetition_penalty"):
        gen.generate(params, cfg, prompt, 2, repetition_penalty=0.0)


def test_ragged_left_padded_rows_match_solo():
    """The ragged path's whole contract: every row of a left-padded
    mixed-length batch decodes EXACTLY as it would solo (pad keys
    masked out of attention, per-row RoPE offsets, uniform cache
    slots)."""
    from ptype_tpu.models.generate import pad_prompts

    cfg = tfm.preset("tiny", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 5, 8)]
    padded, lens = pad_prompts(prompts)
    out = gen.generate(params, cfg, padded, 6, prompt_lens=lens)
    for i, p in enumerate(prompts):
        solo = gen.generate(params, cfg, jnp.asarray(p)[None], 6)
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(solo[0]),
                                      err_msg=f"row {i} (len {len(p)})")


def test_ragged_with_repetition_penalty_ignores_pad():
    """Pad columns must not count as 'seen' for the repetition penalty
    — a pad_token=0 batch would otherwise suppress token 0 for short
    rows only."""
    from ptype_tpu.models.generate import pad_prompts

    cfg = tfm.preset("tiny", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    p = np.asarray([5, 6, 7], np.int32)
    padded, lens = pad_prompts([p, np.asarray([1, 2, 3, 4, 5], np.int32)])
    out = gen.generate(params, cfg, padded, 4, prompt_lens=lens,
                       repetition_penalty=2.0)
    solo = gen.generate(params, cfg, jnp.asarray(p)[None], 4,
                        repetition_penalty=2.0)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(solo[0]))


def test_ragged_moe_rows_match_solo():
    """Ragged + MoE: pad tokens must not displace real tokens from
    expert capacity (zero-drop capacity in ragged prefill)."""
    from ptype_tpu.models.generate import pad_prompts

    cfg = tfm.preset("tiny-moe", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (2, 7)]
    padded, lens = pad_prompts(prompts)
    out = gen.generate(params, cfg, padded, 4, prompt_lens=lens)
    for i, p in enumerate(prompts):
        solo = gen.generate(params, cfg, jnp.asarray(p)[None], 4)
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(solo[0]),
                                      err_msg=f"moe row {i}")


def test_ragged_lens_validation():
    cfg = tfm.preset("tiny", dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    padded = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="prompt_lens"):
        gen.generate(params, cfg, padded, 2,
                     prompt_lens=jnp.asarray([5, 2], jnp.int32))
    with pytest.raises(ValueError, match="prompt_lens"):
        gen.generate(params, cfg, padded, 2,
                     prompt_lens=jnp.asarray([0, 2], jnp.int32))
    with pytest.raises(ValueError, match="shape"):
        gen.generate(params, cfg, padded, 2,
                     prompt_lens=jnp.asarray([2], jnp.int32))


def test_prefill_flash_matches_dense():
    """Uniform causal prefill through the flash kernel (forced
    interpret-mode on CPU via attn_impl="flash") matches the dense
    prefill — logits and the K/V it writes into the cache."""
    from ptype_tpu.models import generate as gen
    from ptype_tpu.models import transformer as tfm

    base = tfm.preset("tiny", dtype=jnp.float32)
    flash = tfm.preset("tiny", dtype=jnp.float32, attn_impl="flash")
    params = tfm.init_params(jax.random.PRNGKey(0), base)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                              base.vocab_size, jnp.int32)
    ld, cd = gen.prefill(params, toks, base,
                         gen.init_cache(base, 2, max_seq=128))
    lf, cf = gen.prefill(params, toks, flash,
                         gen.init_cache(flash, 2, max_seq=128))
    np.testing.assert_allclose(np.asarray(lf), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(cf.k), np.asarray(cd.k),
                               rtol=2e-5, atol=2e-5)
    # Ragged prompts keep the masked dense path (kernel has no
    # kv-mask): same call must still work with lens given. An
    # unaligned S takes the kernel too, right-padded to the lane tile.
    lens = jnp.asarray([100, 128], jnp.int32)
    lr, _ = gen.prefill(params, toks, flash,
                        gen.init_cache(flash, 2, max_seq=128),
                        prompt_lens=lens)
    assert np.isfinite(np.asarray(lr)).all()
    lu, _ = gen.prefill(params, toks[:, :100], flash,
                        gen.init_cache(flash, 2, max_seq=128))
    np.testing.assert_allclose(
        np.asarray(lu),
        np.asarray(gen.prefill(params, toks[:, :100], base,
                               gen.init_cache(base, 2,
                                              max_seq=128))[0]),
        rtol=2e-4, atol=2e-4)
