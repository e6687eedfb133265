"""The dense family: the decoder-only block the program's
``models/transformer.py`` runs (RMSNorm, rotary positions, grouped-query
causal attention, SwiGLU). What ``benchmark/family.py`` asks of a
family, from this package's own modules: ``weights`` (the program's
layout, made from the seed), ``reference`` (the plain block, its loss,
gradients and AdamW) and ``work`` (operations and bytes from shapes).

``program_config`` goes from a configuration file (public
``config.json`` key names) to the program's own ``TransformerConfig``:
for this family, the one place the benchmark names the program's
fields."""

from __future__ import annotations

from benchmark.families.dense.reference import served_logits, train_steps
from benchmark.families.dense.weights import tree
from benchmark.families.dense.work import (cache_bytes_per_token,
                                           decode_needed_bytes,
                                           flash_train_floor_s,
                                           forward_flops,
                                           train_flops_per_token)
from benchmark.weights import DTYPES

__all__ = ["program_config", "tree", "served_logits", "train_steps",
           "decode_needed_bytes", "forward_flops", "train_flops_per_token",
           "cache_bytes_per_token", "flash_train_floor_s"]


def program_config(cfg: dict, max_seq: int, param_dtype: str):
    """The object the program's ``Trainer`` / ``PagedGeneratorActor``
    takes; ``max_seq`` is the engine's reach or the trained length."""
    import jax.numpy as jnp

    from ptype_tpu.models.transformer import TransformerConfig

    H = int(cfg["num_attention_heads"])
    if cfg.get("head_dim") and int(cfg["head_dim"]) * H != int(
            cfg["hidden_size"]):
        raise SystemExit("benchmark: the program derives head_dim as "
                         "hidden_size / heads; this file disagrees")
    if abs(float(cfg["rms_norm_eps"]) - 1e-6) > 1e-12:
        raise SystemExit("benchmark: the program's RMSNorm epsilon is "
                         "fixed at 1e-6; state that in the file")
    return TransformerConfig(
        vocab_size=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layers=int(cfg["num_hidden_layers"]), n_heads=H,
        n_kv_heads=int(cfg.get("num_key_value_heads") or H),
        d_ff=int(cfg["intermediate_size"]), max_seq=int(max_seq),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=jnp.bfloat16, param_dtype=DTYPES[param_dtype])
