"""From a configuration file (public ``config.json`` key names) to the
program's own ``TransformerConfig``. The one place the benchmark names
the program's fields."""

from __future__ import annotations


def transformer_config(cfg: dict, max_seq: int, param_dtype: str):
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
        dtype=jnp.bfloat16,
        param_dtype={"float32": jnp.float32,
                     "bfloat16": jnp.bfloat16}[param_dtype])
